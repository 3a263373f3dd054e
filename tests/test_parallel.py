"""Ring attention + tensor parallelism on the fake 8-device CPU pod."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from vantage6_tpu.parallel import (
    reference_attention,
    ring_attention,
    ring_attention_sharded,
    tp_mlp,
)
from vantage6_tpu.parallel.tensor import shard_params_for_tp


@pytest.fixture(scope="module")
def mesh8():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 fake devices")
    return Mesh(np.array(devs[:8]), ("seq",))


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, mesh8, causal):
        rng = np.random.default_rng(0)
        b, t, h, d = 2, 64, 4, 16  # t sharded 8 ways -> 8 tokens/shard
        q, k, v = (
            jnp.asarray(rng.normal(0, 1, (b, t, h, d)), jnp.float32)
            for _ in range(3)
        )
        out = ring_attention_sharded(mesh8, q, k, v, "seq", causal=causal)
        ref = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_jit_grad_flows(self, mesh8):
        rng = np.random.default_rng(1)
        b, t, h, d = 1, 32, 2, 8
        q, k, v = (
            jnp.asarray(rng.normal(0, 1, (b, t, h, d)), jnp.float32)
            for _ in range(3)
        )
        spec = P(None, "seq", None, None)

        @jax.jit
        def loss(q, k, v):
            out = jax.shard_map(
                lambda q, k, v: ring_attention(q, k, v, "seq", causal=True),
                mesh=mesh8, in_specs=(spec, spec, spec), out_specs=spec,
            )(q, k, v)
            return jnp.sum(out**2)

        g = jax.grad(loss)(q, k, v)
        ref_g = jax.grad(
            lambda q, k, v: jnp.sum(reference_attention(q, k, v, True) ** 2)
        )(q, k, v)
        np.testing.assert_allclose(np.asarray(g), np.asarray(ref_g),
                                   atol=5e-4, rtol=5e-4)

    def test_long_sequence_memory_shape(self, mesh8):
        # each shard only ever materializes [B, T/8, ...] blocks
        b, t, h, d = 1, 1024, 2, 16
        rng = np.random.default_rng(2)
        q = jnp.asarray(rng.normal(0, 1, (b, t, h, d)), jnp.float32)
        out = ring_attention_sharded(mesh8, q, q, q, "seq", causal=True)
        assert out.shape == (b, t, h, d)
        assert np.isfinite(np.asarray(out)).all()


class TestTensorParallel:
    def test_tp_mlp_matches_dense(self, mesh8):
        rng = np.random.default_rng(3)
        d_model, d_hidden, tp = 16, 32, 8
        x = jnp.asarray(rng.normal(0, 1, (4, d_model)), jnp.float32)
        w_up = jnp.asarray(rng.normal(0, 0.1, (d_model, d_hidden)), jnp.float32)
        w_down = jnp.asarray(rng.normal(0, 0.1, (d_hidden, d_model)), jnp.float32)

        ref = jax.nn.gelu(x @ w_up) @ w_down

        def body(x, w_up_l, w_down_l):
            return tp_mlp(x, w_up_l, w_down_l, "seq")

        out = jax.shard_map(
            body,
            mesh=mesh8,
            in_specs=(P(), P(None, "seq"), P("seq", None)),
            out_specs=P(),
        )(x, w_up, w_down)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_shard_params_rules(self):
        params = {
            "mlp": {
                "w_up": jnp.ones((4, 16)),
                "w_down": jnp.ones((16, 4)),
                "bias": jnp.ones((4,)),
            }
        }
        local = shard_params_for_tp(
            params, axis_index=1, axis_size=4,
            rules={"w_up": 1, "w_down": 0},
        )
        assert local["mlp"]["w_up"].shape == (4, 4)
        assert local["mlp"]["w_down"].shape == (4, 4)
        assert local["mlp"]["bias"].shape == (4,)  # untouched

    def test_indivisible_rejected(self):
        with pytest.raises(ValueError, match="not divisible"):
            shard_params_for_tp(
                {"w_up": jnp.ones((4, 10))}, 0, 4, {"w_up": 1}
            )

"""Pallas flash attention vs jnp oracle (interpret mode on CPU)."""
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vantage6_tpu.ops import flash_attention
from vantage6_tpu.ops.flash_attention import reference

# the module: `vantage6_tpu.ops.flash_attention` the attribute is a function
FA = importlib.import_module("vantage6_tpu.ops.flash_attention")


def rand(shape, seed):
    return jnp.asarray(
        np.random.default_rng(seed).normal(0, 1, shape), jnp.float32
    )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t", [64, 96])  # 96 exercises q/k padding
def test_matches_reference(causal, t):
    b, h, d = 2, 3, 16
    q, k, v = rand((b, h, t, d), 0), rand((b, h, t, d), 1), rand((b, h, t, d), 2)
    out = flash_attention(
        q, k, v, causal=causal, block_q=32, block_k=32, interpret=True
    )
    ref = reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_offsets_for_ring_blocks():
    """Causal masking with block offsets — the ring-attention hop case."""
    b, h, t, d = 1, 2, 32, 8
    full_q = rand((b, h, 2 * t, d), 3)
    full_k = rand((b, h, 2 * t, d), 4)
    full_v = rand((b, h, 2 * t, d), 5)
    ref = reference(full_q, full_k, full_v, causal=True)
    # second shard's queries attending to first shard's keys (fully visible)
    # plus its own keys — compose from two offset kernel calls like a ring hop
    q2 = full_q[:, :, t:]
    out_own = flash_attention(
        q2, full_k[:, :, t:], full_v[:, :, t:],
        q_offset=t, k_offset=t, causal=True, block_q=16, block_k=16,
        interpret=True,
    )
    assert out_own.shape == q2.shape
    # single-call equivalence: q2 against the FULL keys with offset t
    out_full = flash_attention(
        q2, full_k, full_v, q_offset=t, k_offset=0, causal=True,
        block_q=16, block_k=16, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out_full), np.asarray(ref[:, :, t:]), atol=2e-5, rtol=2e-5
    )


def test_fully_masked_rows_are_zero():
    """Queries before every key (ring hop where src block is in the future)
    produce zeros, not NaN."""
    b, h, t, d = 1, 1, 16, 8
    q, k, v = rand((b, h, t, d), 6), rand((b, h, t, d), 7), rand((b, h, t, d), 8)
    out = flash_attention(
        q, k, v, q_offset=0, k_offset=1000, causal=True,
        block_q=16, block_k=16, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(out), 0.0)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_reference(causal):
    """custom_vjp backward (flash-style recompute) vs autodiff through the
    jnp oracle."""
    b, h, t, d = 1, 2, 48, 8
    q, k, v = rand((b, h, t, d), 9), rand((b, h, t, d), 10), rand((b, h, t, d), 11)

    def loss_flash(q, k, v):
        out = flash_attention(
            q, k, v, causal=causal, block_q=16, block_k=16, interpret=True
        )
        return jnp.sum(jnp.sin(out))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(reference(q, k, v, causal=causal)))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=3e-5, rtol=3e-5
        )


def test_gradients_with_offsets():
    """Backward respects the ring-hop offset masking."""
    b, h, t, d = 1, 1, 32, 8
    q, k, v = rand((b, h, t, d), 12), rand((b, h, 2 * t, d), 13), rand((b, h, 2 * t, d), 14)

    def loss_flash(q, k, v):
        out = flash_attention(
            q, k, v, q_offset=t, k_offset=0, causal=True,
            block_q=16, block_k=16, interpret=True,
        )
        return jnp.sum(out**2)

    def loss_ref(q, k, v):
        return jnp.sum(
            reference(q, k, v, q_offset=t, k_offset=0, causal=True) ** 2
        )

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=3e-5, rtol=3e-5
        )


class TestRecomputeAttention:
    """The pallas-free flash-memory path: the tiled jnp forward and backward
    (only the key blocks a query block can see) must match the dense oracle
    in values AND gradients."""

    from vantage6_tpu.ops.flash_attention import recompute_attention as _ra

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("t,block_q,block_k", [
        (48, 32, 20),    # neither tile divides t
        (96, 40, 36),
        (100, 32, 48),
        (100, None, None),  # the tile the shapes give: one, t under a tile
    ])
    def test_tiles_that_do_not_divide_the_sequence(
            self, causal, t, block_q, block_k):
        from vantage6_tpu.ops.flash_attention import recompute_attention

        b, h, d = 2, 3, 8
        q, k, v, w = (rand((b, h, t, d), s) for s in (30, 31, 32, 33))

        def value_and_grads(f):
            return jax.value_and_grad(
                lambda *a: jnp.sum(w * f(*a)), argnums=(0, 1, 2))(q, k, v)

        got = value_and_grads(lambda *a: recompute_attention(
            *a, causal=causal, block_q=block_q, block_k=block_k))
        want = value_and_grads(lambda *a: reference(*a, causal=causal))
        for a, b_ in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(a, b_, atol=3e-5, rtol=3e-5)

    @pytest.mark.parametrize("q_offset,k_offset,t_q,t_k", [
        (64, 0, 32, 64),    # a later shard's queries over every key so far
        (32, 32, 32, 32),   # a shard over its own keys: the diagonal hop
        (32, 64, 32, 48),   # keys of a LATER shard: nothing visible
        (40, 16, 24, 56),   # the diagonal crosses the tiles askew
    ])
    def test_ring_hop_shapes_forward_and_gradients(
            self, q_offset, k_offset, t_q, t_k):
        """`q_offset != k_offset` with `t_q != t_k`, the shapes a ring hop
        hands over; where no key is visible the output and every gradient
        are zero."""
        from vantage6_tpu.ops.flash_attention import recompute_attention

        b, h, d = 1, 2, 8
        q, w = rand((b, h, t_q, d), 34), rand((b, h, t_q, d), 35)
        k, v = rand((b, h, t_k, d), 36), rand((b, h, t_k, d), 37)
        kw = dict(q_offset=q_offset, k_offset=k_offset, causal=True)

        def value_and_grads(f):
            return jax.value_and_grad(
                lambda *a: jnp.sum(w * f(*a)), argnums=(0, 1, 2))(q, k, v)

        got = value_and_grads(lambda *a: recompute_attention(
            *a, block_q=16, block_k=24, **kw))
        if k_offset >= q_offset + t_q:  # the dense softmax reads a mean there
            for leaf in jax.tree.leaves(got):
                np.testing.assert_array_equal(leaf, 0.0)
            return
        want = value_and_grads(lambda *a: reference(*a, **kw))
        for a, b_ in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(a, b_, atol=3e-5, rtol=3e-5)

    @pytest.mark.parametrize("lengths,tile", [
        ((1024, 1024), (256, 256)),    # GPT-2 medium: the swept value
        ((8192, 8192), (512, 512)),    # SmallThinker: the parent's
        ((100, 100), (100, 100)),      # t under a tile: one tile
        ((96, 2048), (96, 256)),       # a ring hop's short queries
        ((2048, 2048), (256, 256)),    # read on the chip too
        ((6000, 6000), (256, 256)),    # whole lane widths only
    ])
    def test_the_tile_is_a_function_of_the_lengths(self, lengths, tile):
        """Where a kernel would be interpreted the XLA walk runs, at the
        tiles it always had, whatever the heads are."""
        for head_dim, group in ((64, 1), (128, 7)):
            assert FA.attention_tile(
                *lengths, head_dim, group, jnp.bfloat16, True
            ) == ("walk", *tile)

    @pytest.mark.parametrize("lengths,head_dim,group,blocks", [
        ((4096, 4096), 128, 1, ("kernel", 512, 512)),  # Ouro
        ((8192, 8192), 128, 7, ("kernel", 512, 512)),  # SmallThinker
        ((100, 300), 128, 1, ("kernel", 128, 384)),    # whole lane tiles
        # heads of 64 are no whole lane tile (and lost on four chips)
        ((1024, 1024), 64, 1, ("walk", 256, 256)),     # GPT-2 medium
        # a head's step no longer fits the chip's VMEM
        ((65536, 65536), 128, 1, ("walk", 512, 512)),
    ])
    def test_compiled_the_rule_names_the_kernels_where_a_head_fits(
            self, lengths, head_dim, group, blocks):
        assert FA.attention_tile(
            *lengths, head_dim, group, jnp.bfloat16, False) == blocks

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("t", [64, 96])  # 96 exercises key padding
    def test_forward_matches_reference(self, causal, t):
        from vantage6_tpu.ops.flash_attention import recompute_attention

        b, h, d = 2, 3, 16
        q, k, v = (rand((b, h, t, d), s) for s in (20, 21, 22))
        out = recompute_attention(q, k, v, causal=causal, block_k=32)
        ref = reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_match_reference(self, causal):
        from vantage6_tpu.ops.flash_attention import recompute_attention

        b, h, t, d = 1, 2, 48, 8
        q, k, v = (rand((b, h, t, d), s) for s in (23, 24, 25))

        g_rc = jax.grad(
            lambda *a: jnp.sum(jnp.sin(recompute_attention(
                *a, causal=causal, block_k=16
            ))), argnums=(0, 1, 2),
        )(q, k, v)
        g_ref = jax.grad(
            lambda *a: jnp.sum(jnp.sin(reference(*a, causal=causal))),
            argnums=(0, 1, 2),
        )(q, k, v)
        for grc, gr in zip(g_rc, g_ref):
            np.testing.assert_allclose(
                np.asarray(grc), np.asarray(gr), atol=3e-5, rtol=3e-5
            )

    def test_the_walk_at_the_shipped_tile_is_the_triangle_the_span_reports(
            self):
        """GPT-2 medium's shapes (T 1024, 16 heads of 64, 24 layers): the
        ranges of `_key_block_range`, summed over the query blocks at the
        tile the shapes give, are n (n + 1) / 2 of n x n tiles, and the
        round's span says that count times the layers."""
        from vantage6_tpu.workloads import fed_transformer as FT

        t = 1024
        path, block_q, block_k = FA.attention_tile(
            t, t, 64, 1, jnp.float32, True)
        assert path == "walk" and block_q == block_k and t % block_q == 0
        n = t // block_q
        walked = 0
        for i in range(n):
            lo, hi = FA._key_block_range(
                i, block_q, block_k, n, t, 0, 0, True, None)
            # Python integers in, Python integers out: counting the walk
            # for the span runs no program on the device
            assert (lo, hi) == (0, i + 1) and type(hi) is int
            walked += hi - lo
        assert walked == n * (n + 1) // 2
        assert FA.tiles_visited(t, t, block_q, block_k, True, None) == (
            walked, n * n)
        assert FA.tiles_visited(t, t, block_q, block_k, False, None) == (
            n * n, n * n)
        engine = FT.make_engine(4, 1, FT.TransformerConfig(
            vocab=50257, d_model=1024, n_heads=16, n_layers=24,
            max_len=1024, attention="recompute", flash_interpret=True),
            devices=jax.devices()[:1])
        assert engine.attention_walk(t) == {
            "attention_path": "walk",
            "attention_tile": f"{block_q}x{block_k}",
            "attention_tiles_visited": 24 * walked,
            "attention_tiles": 24 * n * n}

    @pytest.mark.parametrize("depth", [1, 2])
    def test_under_vmap_the_in_place_update_stays_an_update(self, depth):
        """The backward adds each key block's `dK`/`dV` into its slice in
        place. jax's rule for a batched `dynamic_update_slice` is a scatter;
        with one start for the whole batch `_add_at` keeps the update, one
        axis further in per `vmap`, and the gradients are the dense ones."""
        from vantage6_tpu.ops.flash_attention import recompute_attention

        shape = (3, 2)[:depth] + (2, 2, 40, 8)
        q, k, v, w = (rand(shape, s) for s in (40, 41, 42, 43))

        def grads(f):
            one = lambda q, k, v, w: jax.grad(  # noqa: E731
                lambda *a: jnp.sum(w * f(*a)), argnums=(0, 1, 2))(q, k, v)
            for _ in range(depth):
                one = jax.vmap(one)
            return jax.jit(one)

        tiled = grads(lambda *a: recompute_attention(
            *a, causal=True, block_q=16, block_k=8))
        text = tiled.lower(q, k, v, w).as_text()
        assert "stablehlo.scatter" not in text
        assert "stablehlo.dynamic_update_slice" in text
        want = grads(lambda *a: reference(*a, causal=True))(q, k, v, w)
        for a, b_ in zip(tiled(q, k, v, w), want):
            np.testing.assert_allclose(a, b_, atol=3e-5, rtol=3e-5)

    def test_a_start_per_batch_element_takes_jaxs_own_rule(self):
        acc, block = rand((3, 2, 8, 4), 44), rand((3, 2, 2, 4), 45)
        at = jnp.asarray([0, 2, 6])
        got = jax.vmap(FA._add_at(1))(acc, block, at)
        want = np.array(acc)
        for i, a in enumerate([0, 2, 6]):
            want[i, :, a:a + 2] += np.asarray(block[i])
        np.testing.assert_allclose(got, want, rtol=1e-6)
        # an accumulator shared by the batch, a block each: broadcast
        got = jax.vmap(FA._add_at(1), in_axes=(None, 0, None))(
            acc[0], block, 4)
        want = np.repeat(np.asarray(acc[:1]), 3, axis=0)
        want[:, :, 4:6] += np.asarray(block)
        np.testing.assert_allclose(got, want, rtol=1e-6)

    def test_ring_hop_offsets(self):
        from vantage6_tpu.ops.flash_attention import recompute_attention

        b, h, t, d = 1, 2, 32, 8
        fq, fk, fv = (rand((b, h, 2 * t, d), s) for s in (26, 27, 28))
        ref = reference(fq, fk, fv, causal=True)
        out = recompute_attention(
            fq[:, :, t:], fk, fv, q_offset=t, k_offset=0, causal=True,
            block_k=16,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref[:, :, t:]), atol=2e-5, rtol=2e-5
        )

    def test_transformer_trains_with_recompute(self):
        from vantage6_tpu.workloads import fed_transformer as FT

        cfg = FT.TransformerConfig(
            vocab=32, d_model=16, n_heads=2, n_layers=1, max_len=64,
            attention="recompute", flash_interpret=True,
        )
        eng = FT.make_engine(n_stations=2, seq_devices=1, cfg=cfg, lr=3e-3)
        tokens = FT.make_federated_tokens(2, batch=2, seq_len=16, vocab=32)
        p, o, loss = eng.round(
            *eng.init(jax.random.key(6)), eng.shard_tokens(tokens),
            jnp.ones(2),
        )
        assert np.isfinite(float(loss))


class TestAttentionKernels:
    """The walk inside the Pallas kernels (`_kernel_vjp`), interpreted here,
    against the XLA walk at the same blocks (`_tiled_vjp`): the output and
    all three gradients."""

    MASKS = {"causal": (True, None), "window": (True, 12),
             "full": (False, None)}
    WRAPS = {
        "alone": lambda f: f,
        "vmap": jax.vmap,  # the packed stations
        "checkpoint": jax.checkpoint,  # the block's remat
    }

    @staticmethod
    @functools.lru_cache(maxsize=None)
    def _value_and_grads(path, mask, wrap):
        """One program a (path, mask, wrap): the offsets are traced."""
        causal, window = TestAttentionKernels.MASKS[mask]
        config = (causal, 8 ** -0.5, window, 16, 16)
        fn = (FA._kernel_vjp(*config, True) if path == "kernel"
              else FA._tiled_vjp(*config))
        wrapped = TestAttentionKernels.WRAPS[wrap]

        def loss(q, k, v, w, offset):
            return jnp.sum(w * fn(q, k, v, offset, offset))

        if wrap == "checkpoint":
            return jax.jit(jax.value_and_grad(wrapped(loss), (0, 1, 2)))
        in_axes = (0, 0, 0, 0, None) if wrap == "vmap" else ()
        return jax.jit(wrapped(jax.value_and_grad(loss, (0, 1, 2)),
                               *((in_axes,) if in_axes else ())))

    @pytest.mark.parametrize("wrap", WRAPS)
    @pytest.mark.parametrize("t", [32, 40])  # 40 pads to three blocks of 16
    @pytest.mark.parametrize("offset", [0, 48])  # 48: a later shard's
    @pytest.mark.parametrize("group", [1, 4])  # query heads a kv head
    @pytest.mark.parametrize("mask", MASKS)
    def test_the_kernels_are_the_walk(self, mask, group, offset, t, wrap):
        stations = (2,) if wrap == "vmap" else ()
        q, w = (rand(stations + (1, 4, t, 8), s) for s in (50, 51))
        k, v = (rand(stations + (1, 4 // group, t, 8), s) for s in (52, 53))
        got, want = (
            self._value_and_grads(path, mask, wrap)(
                q, k, v, w, jnp.int32(offset))
            for path in ("kernel", "walk"))
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(a, b, atol=2e-6, rtol=2e-6)

    @pytest.mark.parametrize("mask,group,wrap", [
        ("causal", 1, "vmap"), ("window", 4, "alone"),
        ("full", 4, "checkpoint")])
    def test_a_head_of_whole_lane_tiles_is_read_where_it_lies(
            self, mask, group, wrap):
        """Heads of 128, the width the kernels are compiled at: a head is a
        column slab of [B, T, H * D] (`_walk_call`), the transposes there
        and back cancelling against the caller's; same numbers."""
        stations = (2,) if wrap == "vmap" else ()
        q, w = (rand(stations + (1, 4, 40, 128), s) for s in (57, 58))
        k, v = (rand(stations + (1, 4 // group, 40, 128), s)
                for s in (59, 60))
        got, want = (
            self._value_and_grads(path, mask, wrap)(
                q, k, v, w, jnp.int32(48))
            for path in ("kernel", "walk"))
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)

    def test_no_score_tensor_in_the_gradients_program(self):
        """Nothing the size of [Tq, Tk] in the jaxpr of the kernel path's
        gradient, the kernels' own bodies included: the largest value a
        tile's scores."""
        t, block = 64, 16
        q, k, v = (rand((1, 2, t, 8), s) for s in (54, 55, 56))
        fn = FA._kernel_vjp(True, 8 ** -0.5, None, block, block, True)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(fn(*a, jnp.int32(0), jnp.int32(0))),
            argnums=(0, 1, 2)))(q, k, v)

        def shapes(jaxpr):
            for eqn in jaxpr.eqns:
                yield from (tuple(x.aval.shape) for x in eqn.outvars)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from shapes(sub)

        seen = set(shapes(jaxpr.jaxpr))
        assert (block, block) in seen  # a tile's scores are there
        assert not any(shape.count(t) >= 2 for shape in seen)
        assert max(int(np.prod(shape)) for shape in seen) < t * t

    def test_compiled_blocks_are_whole_lane_tiles(self):
        assert FA._kernel_blocks(1024, 1000, 512, 512, False) == (512, 512)
        assert FA._kernel_blocks(100, 300, 512, 512, False) == (128, 384)
        assert FA._kernel_blocks(40, 40, 16, 16, True) == (16, 16)
        with pytest.raises(ValueError, match="lane"):
            FA._kernel_blocks(1024, 1024, 96, 512, False)


class TestInterpreterTwin:
    """`interpreter_twin` is the kernel's bit-exactness oracle: a pure-jnp
    transliteration of the Pallas grid (same op sequence, same block
    sweep), so interpret-mode flash must match it to the BIT — not within
    a tolerance. A tolerance here would hide an accidental reassociation
    in the kernel (the exact class of bug that later diverges on real TPU
    MXU/VPU paths where op order matters most)."""

    @pytest.mark.parametrize("t", [128, 1024])
    @pytest.mark.parametrize("causal", [False, True])
    def test_bit_exact_vs_interpret_kernel(self, t, causal):
        from vantage6_tpu.ops.flash_attention import interpreter_twin

        b, h, d = 1, 2, 16
        q, k, v = (rand((b, h, t, d), s) for s in (30, 31, 32))
        out = flash_attention(q, k, v, causal=causal, interpret=True)
        twin = interpreter_twin(q, k, v, causal=causal)
        assert out.dtype == twin.dtype
        np.testing.assert_array_equal(np.asarray(out), np.asarray(twin))

    def test_bit_exact_with_padding_and_offsets(self):
        """t=100 forces the ragged tail (block padding + kvalid mask);
        offsets exercise the ring-hop position arithmetic."""
        from vantage6_tpu.ops.flash_attention import interpreter_twin

        b, h, t, d = 2, 2, 100, 8
        q, k, v = (rand((b, h, t, d), s) for s in (33, 34, 35))
        out = flash_attention(
            q, k, v, q_offset=4, k_offset=0, causal=True,
            block_q=32, block_k=32, interpret=True,
        )
        twin = interpreter_twin(
            q, k, v, q_offset=4, k_offset=0, causal=True,
            block_q=32, block_k=32,
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(twin))

    def test_bit_exact_bf16(self):
        from vantage6_tpu.ops.flash_attention import interpreter_twin

        b, h, t, d = 1, 2, 128, 16
        q, k, v = (
            rand((b, h, t, d), s).astype(jnp.bfloat16) for s in (36, 37, 38)
        )
        out = flash_attention(q, k, v, causal=True, interpret=True)
        twin = interpreter_twin(q, k, v, causal=True)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(out.astype(jnp.float32)),
            np.asarray(twin.astype(jnp.float32)),
        )

    def test_twin_itself_matches_reference(self):
        """The oracle is anchored: the twin stays allclose to the naive
        softmax reference, so a kernel+twin agreeing on WRONG math can't
        pass silently."""
        from vantage6_tpu.ops.flash_attention import interpreter_twin

        b, h, t, d = 2, 2, 128, 16
        q, k, v = (rand((b, h, t, d), s) for s in (39, 40, 41))
        twin = interpreter_twin(q, k, v, causal=True)
        ref = reference(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(twin), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

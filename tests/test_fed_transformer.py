"""Federated sequence-parallel transformer on the fake 8-device pod:
4 stations x 2 sequence shards; loss decreases; isolation holds."""
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vantage6_tpu.workloads import fed_transformer as FT


@pytest.fixture(scope="module")
def engine():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 fake devices")
    cfg = FT.TransformerConfig(vocab=64, d_model=32, n_heads=2, n_layers=2,
                               max_len=128)
    return FT.make_engine(n_stations=4, seq_devices=2, cfg=cfg, lr=3e-3)


def test_training_reduces_loss(engine):
    cfg = engine.cfg
    tokens = FT.make_federated_tokens(4, batch=4, seq_len=64, vocab=cfg.vocab)
    sharded = engine.shard_tokens(tokens)
    params, opt_state = engine.init(jax.random.key(0))
    mask = jnp.ones(4)
    first = None
    for step in range(30):
        params, opt_state, loss = engine.round(params, opt_state, sharded, mask)
        if first is None:
            first = float(loss)
    assert np.isfinite(float(loss))
    assert float(loss) < first * 0.8, (first, float(loss))


def test_dropout_station_changes_aggregate(engine):
    cfg = engine.cfg
    tokens = FT.make_federated_tokens(4, batch=2, seq_len=32, vocab=cfg.vocab)
    sharded = engine.shard_tokens(tokens)
    params, opt_state = engine.init(jax.random.key(1))
    full_mask = jnp.ones(4)
    drop_mask = jnp.asarray([1.0, 1.0, 1.0, 0.0])
    # a round consumes the state it is handed: the second one gets a copy
    # taken before the first
    kept = jax.tree.map(jnp.copy, (params, opt_state))
    p_full, _, _ = engine.round(params, opt_state, sharded, full_mask)
    p_drop, _, _ = engine.round(*kept, sharded, drop_mask)
    # station 3's data influenced the full aggregate but not the dropped one
    diff = jax.tree.leaves(
        jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), p_full, p_drop)
    )
    assert max(diff) > 0


def test_sequence_shards_see_full_context(engine):
    """Perplexity must depend on cross-shard context: permuting the first
    half of every sequence changes logits in the second half's shard."""
    cfg = engine.cfg
    rng = np.random.default_rng(2)
    tokens = rng.integers(0, cfg.vocab, (4, 2, 32), dtype=np.int32)
    params, _ = engine.init(jax.random.key(2))

    from functools import partial

    from jax.sharding import PartitionSpec as P

    from vantage6_tpu.core.mesh import STATION_AXIS

    def logits_fn(params, toks):
        def body(params, tokens_block):
            out = FT.forward_local(params, tokens_block[0], cfg)
            return out[None]

        return jax.shard_map(
            body,
            mesh=engine.mesh,
            in_specs=(P(), P(STATION_AXIS, None, FT.SEQ_AXIS)),
            out_specs=P(STATION_AXIS, None, FT.SEQ_AXIS),
        )(params, engine.shard_tokens(toks))

    base = np.asarray(logits_fn(params, tokens))
    mutated = tokens.copy()
    mutated[:, :, :8] = rng.integers(0, cfg.vocab, (4, 2, 8))  # first shard half
    changed = np.asarray(logits_fn(params, mutated))
    # positions in the SECOND half (owned by the other sequence shard) react
    assert np.abs(base[:, :, 20:] - changed[:, :, 20:]).max() > 1e-6


class TestFlashAndMixedPrecision:
    """The Pallas flash kernel wired into the model (interpret mode on CPU)
    and the bf16 compute path: same logits as the default f32 ring path."""

    def _mini(self, attention="ring", dtype=jnp.float32):
        return FT.TransformerConfig(
            vocab=32, d_model=16, n_heads=2, n_layers=1, max_len=64,
            dtype=dtype, attention=attention, flash_interpret=True,
        )

    def test_flash_forward_matches_ring(self):
        cfg_ring = self._mini("ring")
        cfg_flash = self._mini("flash")
        eng = FT.make_engine(n_stations=2, seq_devices=1, cfg=cfg_ring)
        tokens = FT.make_federated_tokens(2, batch=2, seq_len=16, vocab=32)
        params, _ = eng.init(jax.random.key(3))

        from jax.sharding import PartitionSpec as P

        from vantage6_tpu.core.mesh import STATION_AXIS

        def logits_fn(cfg, toks):
            def body(params, tokens_block):
                return FT.forward_local(params, tokens_block[0], cfg)[None]

            return jax.shard_map(
                body,
                mesh=eng.mesh,
                in_specs=(P(), P(STATION_AXIS, None, FT.SEQ_AXIS)),
                out_specs=P(STATION_AXIS, None, FT.SEQ_AXIS),
                check_vma=False,
            )(params, eng.shard_tokens(jnp.asarray(toks)))

        ring = np.asarray(logits_fn(cfg_ring, tokens))
        flash = np.asarray(logits_fn(cfg_flash, tokens))
        np.testing.assert_allclose(ring, flash, atol=2e-5, rtol=2e-5)

    def test_flash_requires_full_sequence_per_device(self):
        with pytest.raises(ValueError, match="seq_devices == 1"):
            FT.make_engine(n_stations=2, seq_devices=2, cfg=self._mini("flash"))

    def test_bf16_round_trains(self):
        cfg = self._mini("ring", dtype=jnp.bfloat16)
        eng = FT.make_engine(n_stations=2, seq_devices=1, cfg=cfg, lr=3e-3)
        tokens = FT.make_federated_tokens(2, batch=4, seq_len=32, vocab=32)
        sharded = eng.shard_tokens(tokens)
        params, opt = eng.init(jax.random.key(4))
        mask = jnp.ones(2)
        first = None
        for _ in range(15):
            params, opt, loss = eng.round(params, opt, sharded, mask)
            if first is None:
                first = float(loss)
        # params remain f32 master weights; loss decreases under bf16 compute
        assert all(
            leaf.dtype == jnp.float32 for leaf in jax.tree.leaves(params)
        )
        assert np.isfinite(float(loss)) and float(loss) < first, (
            first, float(loss),
        )

    def test_bf16_flash_round_trains(self):
        cfg = self._mini("flash", dtype=jnp.bfloat16)
        eng = FT.make_engine(n_stations=2, seq_devices=1, cfg=cfg, lr=3e-3)
        tokens = FT.make_federated_tokens(2, batch=2, seq_len=16, vocab=32)
        sharded = eng.shard_tokens(tokens)
        params, opt = eng.init(jax.random.key(5))
        params, opt, loss = eng.round(params, opt, sharded, jnp.ones(2))
        assert np.isfinite(float(loss))


class TestStationPacking:
    """stations_per_slot > 1: more stations than device slots fold into
    each slot via an inner vmap (FederationMesh.fed_map contract) — one
    chip can run an S-station federated round. The packed round must be
    BIT-COMPATIBLE with the unpacked one: packing is an execution layout,
    not a math change."""

    def _cfg(self):
        return FT.TransformerConfig(vocab=64, d_model=32, n_heads=2,
                                    n_layers=2, max_len=32)

    def _one_round(self, n_devices):
        cfg = self._cfg()
        eng = FT.make_engine(
            n_stations=4, seq_devices=1, cfg=cfg, lr=3e-3,
            devices=jax.devices()[:n_devices],
        )
        tokens = eng.shard_tokens(
            FT.make_federated_tokens(4, batch=2, seq_len=32, vocab=64)
        )
        params, opt = eng.init(jax.random.key(7))
        mask = jnp.ones(4)
        p, _, loss = eng.round(params, opt, tokens, mask)
        return jax.device_get(p), float(loss)

    def test_packed_matches_unpacked(self):
        p4, l4 = self._one_round(4)   # one station per slot
        p1, l1 = self._one_round(1)   # all 4 stations packed on one device
        p2, l2 = self._one_round(2)   # 2 per slot
        assert np.isfinite(l4)
        np.testing.assert_allclose(l1, l4, rtol=1e-5)
        np.testing.assert_allclose(l2, l4, rtol=1e-5)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6)

    def test_too_few_devices_for_seq_shards_rejected(self):
        with pytest.raises(ValueError, match="sequence shards"):
            FT.make_engine(n_stations=1, seq_devices=64, cfg=self._cfg())


# a dense block with a station on each of four devices (the state
# replicated over them, as the four-chip cell holds it), the same block
# packed on one device, and a block with a router and an expert layer
_TINY = dict(vocab=97, d_model=32, n_heads=4, n_layers=2, max_len=16,
             attention="recompute", flash_interpret=True)
CONSUMERS = {
    "dense-sharded": (_TINY, 4),
    "dense-packed": (_TINY, 1),
    "experts-packed": (dict(
        _TINY, remat=True, norm="rmsnorm", n_kv_heads=2, positions="rotary",
        rope_layout=(0, 1), window=8, window_layout=(0, 1), ffn="experts",
        n_experts=4, top_k=2, d_expert=16, experts_held=(2, 3),
        tie_head=False), 1),
}


def _alive(tree) -> list[bool]:
    return [not leaf.is_deleted() for leaf in jax.tree.leaves(tree)]


@pytest.mark.parametrize("block", CONSUMERS)
class TestTheRoundConsumesItsState:
    """`FedTransformer.round` donates `params` and `opt_state`: the state
    handed in is gone when it returns and the new one lies in its buffers;
    `tokens` and `mask` are the caller's still."""

    @pytest.fixture
    def built(self, block):
        kwargs, n_devices = CONSUMERS[block]
        engine = FT.make_engine(4, 1, FT.TransformerConfig(**kwargs),
                                devices=jax.devices()[:n_devices])
        tokens = engine.shard_tokens(FT.make_federated_tokens(4, 2, 16, 97))
        return engine, (*engine.init(jax.random.key(0)), tokens, jnp.ones(4))

    def test_the_state_is_deleted_and_the_batch_is_not(self, built):
        engine, (params, opt_state, tokens, mask) = built
        was = jax.tree.map(lambda x: (x.shape, x.dtype, x.sharding),
                           (params, opt_state))
        *state, loss = engine.round(params, opt_state, tokens, mask)
        assert not any(_alive((params, opt_state)))
        assert all(_alive((tokens, mask, state, loss)))
        # what comes back is placed as what went in, so the next round's
        # outputs find the same buffers again (and no second program)
        assert was == jax.tree.map(
            lambda x: (x.shape, x.dtype, x.sharding), tuple(state))
        programs = engine._round._cache_size()
        engine.round(*state, tokens, mask)
        assert not any(_alive(state))
        assert engine._round._cache_size() == programs

    def test_every_donated_leaf_finds_an_output(self, built):
        """jax warns "Some donated buffers were not usable" for a leaf no
        output matches; XLA's table of aliases then names each of them."""
        engine, args = built
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            compiled = engine._round.lower(engine, *args).compile()
            jax.block_until_ready(engine.round(*args))
        n_state = 3 * len(jax.tree.leaves(args[0])) + 1
        aliased = re.findall(r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)",
                             compiled.as_text())
        assert len(aliased) == n_state
        assert sorted(int(arg) for _, arg in aliased) == list(range(n_state))

    def test_rebinding_reads_what_a_caller_who_copies_first_reads(
            self, built):
        engine, (params, opt_state, tokens, mask) = built
        start = jax.tree.map(jnp.copy, (params, opt_state))
        rebound = []
        for _ in range(3):
            params, opt_state, loss = engine.round(
                params, opt_state, tokens, mask)
            rebound.append(float(loss))
        copied, state = [], start
        for _ in range(3):
            kept = state
            *state, loss = engine.round(
                *jax.tree.map(jnp.copy, kept), tokens, mask)
            assert all(_alive(kept))  # the copy went, not the original
            copied.append(float(loss))
        assert rebound == copied and rebound[2] < rebound[0]
        for a, b in zip(jax.tree.leaves((params, opt_state)),
                        jax.tree.leaves(state)):
            np.testing.assert_array_equal(a, b)


class TestRemat:
    def test_remat_gradients_exact(self, devices):
        """jax.checkpoint recomputes, never approximates: per-layer remat
        must match the plain path to f32 rounding (XLA may fuse
        differently across the checkpoint boundary — ~1 ULP, never
        more)."""
        import numpy as np

        from vantage6_tpu.workloads import fed_transformer as FT

        tokens = FT.make_federated_tokens(2, batch=2, seq_len=16, vocab=32)
        outs = {}
        for remat in (False, True):
            cfg = FT.TransformerConfig(
                vocab=32, d_model=16, n_heads=2, n_layers=2, max_len=32,
                remat=remat,
            )
            eng = FT.make_engine(n_stations=2, seq_devices=1, cfg=cfg)
            params, opt = eng.init(jax.random.key(0))
            p1, _, loss = eng.round(
                params, opt, eng.shard_tokens(tokens), jnp.ones(2)
            )
            outs[remat] = (float(loss), p1)
        assert abs(outs[False][0] - outs[True][0]) < 1e-5
        for a, b in zip(
            jax.tree.leaves(outs[False][1]), jax.tree.leaves(outs[True][1])
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            )


# ---------------------------------------------------------------------------
# The round on several slots: the cross-station mean goes round a ring inside
# the mesh (`collectives.OverAxis`, `RingExchange`). It is `fed_mean`
# over the stacked gradients all the same: the plain statement below.
# ---------------------------------------------------------------------------

MESHES = {  # stations, devices: slots x stations packed in each
    "4x1": (4, 4),
    "2x2": (4, 2),
    "4x2": (8, 4),
}
MASKS = {
    "all": lambda s: np.ones(s, np.float32),
    "one_dropped": lambda s: np.r_[0.0, np.ones(s - 1)].astype(np.float32),
    "a_dropped_station_is_nan": lambda s: np.r_[
        0.0, np.ones(s - 1)].astype(np.float32),
    "all_dropped": lambda s: np.zeros(s, np.float32),
}
POISON = 96  # the station whose first token is this has a NaN loss


def _plain_round(engine, params, opt_state, tokens, mask):
    """What a round computes, stated with `fed_mean` on one device: every
    station's loss and gradient, the masked mean of both, one Adam step."""
    import optax
    from jax.sharding import Mesh, PartitionSpec as P

    from vantage6_tpu.core.mesh import STATION_AXIS
    from vantage6_tpu.fed import collectives

    one = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
               (STATION_AXIS, FT.SEQ_AXIS))

    def stations(params, tokens):
        def station(tok):
            return jax.value_and_grad(
                lambda p: FT._loss_and_load(p, tok, engine.cfg, FT.SEQ_AXIS)[0]
            )(params)
        return jax.vmap(station)(tokens)

    losses, grads = jax.shard_map(
        stations, mesh=one,
        in_specs=(P(), P(STATION_AXIS, None, FT.SEQ_AXIS)),
        out_specs=P(STATION_AXIS), check_vma=False)(params, tokens)
    updates, opt_state = engine.optimizer.update(
        collectives.fed_mean(grads, mask=mask), opt_state, params)
    return (optax.apply_updates(params, updates), opt_state,
            collectives.fed_mean(losses, mask=mask))


@pytest.mark.parametrize("masked", MASKS)
@pytest.mark.parametrize("mesh", MESHES)
def test_a_round_on_several_slots_is_fed_mean_of_the_stacked_gradients(
        mesh, masked, monkeypatch):
    n_stations, n_devices = MESHES[mesh]
    if len(jax.devices()) < n_devices:
        pytest.skip(f"needs {n_devices} fake devices")
    from vantage6_tpu.fed import collectives

    # every layer a group of its own, so that groups wait for one another
    monkeypatch.setattr(collectives, "RING_GROUP_BYTES", 1)
    if masked == "a_dropped_station_is_nan":
        whole = FT._loss_and_load

        def poisoned(params, tok, *args):
            loss, left = whole(params, tok, *args)
            return loss * jnp.where(tok[0, 0] == POISON, jnp.nan, 1.0), left

        monkeypatch.setattr(FT, "_loss_and_load", poisoned)
    cfg = FT.TransformerConfig(vocab=97, d_model=32, n_heads=4, n_layers=3,
                               max_len=16, attention="recompute",
                               flash_interpret=True)
    engine = FT.make_engine(n_stations, 1, cfg,
                            devices=jax.devices()[:n_devices])
    tokens = np.minimum(
        FT.make_federated_tokens(n_stations, 2, 16, 97), POISON - 1)
    tokens[0, 0, 0] = POISON
    mask = MASKS[masked](n_stations)
    params, opt_state = engine.init(jax.random.key(0))
    want = _plain_round(
        engine, *jax.device_get((params, opt_state)), tokens, mask)
    said = dict(engine.aggregation(params))
    # no option of the TPU's compiler reaches this CPU compile: it would be
    # refused ("No such compile option")
    got = engine.round(
        params, opt_state, engine.shard_tokens(tokens), jnp.asarray(mask))
    assert all(np.isfinite(x).all() for x in jax.tree.leaves(got))
    # the optimizer's moments are the mean gradient (and its square) scaled,
    # and the loss is a mean: float32 rounding of another order of summing
    for a, b in zip(jax.tree.leaves(got[1:]), jax.tree.leaves(want[1:])):
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-6 * np.abs(b).max())
    # Adam's first step moves a weight by lr g / (|g| + eps): where |g| is
    # near eps, the last bit of g is a good part of a step of lr = 1e-3
    for a, b in zip(jax.tree.leaves(got[0]), jax.tree.leaves(want[0])):
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-4)
    # every slot's copy of every parameter holds the same bits
    for x in jax.tree.leaves(got[:2]):
        first, *others = [np.asarray(s.data) for s in x.addressable_shards]
        assert len(others) == n_devices - 1
        assert all(np.array_equal(first, other, equal_nan=True)
                   for other in others)
    assert said.pop("aggregate_bytes") > 2 * sum(
        x.nbytes for x in jax.tree.leaves(want[0])) * (
            n_devices - 1) // n_devices
    assert said == {"aggregate_overlap": "ring", "aggregate_groups": 3}

"""Gradient-compression stack (docs/compression.md).

Pins the numerics contracts the compression PR ships on:

- stochastic int8 is UNBIASED: over seeded draws the mean round-trip
  error goes to zero (the bf16-contract-style test for this PR);
- top-k + error feedback is EXACT: the mass a round drops reappears in
  the next round's accumulator bit-for-bit;
- the wire payload (SparseVector + scales) reconstructs the decompressed
  delta identically through v2 AND through the legacy-v1 dense fallback;
- the FedAvg engine with an identity-lossless compressor is fp32-identical
  to the uncompressed path, and the lossy configs still converge;
- the host task plane round-trips compressed updates with per-station
  error-feedback state, spans, and telemetry.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from vantage6_tpu.fed import compression as C
from vantage6_tpu.fed.compression import CompressorSpec

RNG = np.random.default_rng(11)


def _vec(n=512):
    return jnp.asarray(RNG.normal(size=n).astype(np.float32))


# ---------------------------------------------------------------- spec math
class TestCompressorSpec:
    def test_validation(self):
        CompressorSpec(topk_ratio=0.5, int8=True).validate()
        with pytest.raises(ValueError, match="topk_ratio"):
            CompressorSpec(topk_ratio=0.0).validate()
        with pytest.raises(ValueError, match="topk_ratio"):
            CompressorSpec(topk_ratio=1.5).validate()
        with pytest.raises(ValueError, match="chunk"):
            CompressorSpec(int8=True, chunk=0).validate()

    def test_identity_flag(self):
        assert CompressorSpec().identity
        assert not CompressorSpec(int8=True).identity
        assert not CompressorSpec(topk_ratio=0.1).identity

    def test_wire_nbytes_math(self):
        n = 100_000
        # dense f32
        assert CompressorSpec().wire_nbytes(n) == 4 * n
        # int8 only: one code per element + dense-layout scales
        s = CompressorSpec(int8=True, chunk=256)
        assert s.wire_nbytes(n) == n + 4 * ((n + 255) // 256)
        # topk+int8: k codes + k int32 indices + dense-layout scales
        s = CompressorSpec(topk_ratio=0.1, int8=True, chunk=256)
        k = s.k_for(n)
        assert s.wire_nbytes(n) == 5 * k + 4 * ((n + 255) // 256)
        assert s.ratio(n) > 4.0  # the acceptance bar at default knobs

    def test_k_for_bounds(self):
        s = CompressorSpec(topk_ratio=0.001)
        assert s.k_for(10) == 1  # never zero survivors
        assert CompressorSpec(topk_ratio=1.0).k_for(7) == 7


# ------------------------------------------------------------ int8 numerics
class TestStochasticInt8:
    def test_int8_roundtrip_is_unbiased(self):
        """The PR's numerics contract (like PR 1's bf16 test): over seeded
        draws the MEAN round-trip error vanishes while any single draw has
        visible quantization noise — stochastic rounding is unbiased."""
        x = _vec(256)
        chunk = 64
        draws = [
            np.asarray(C.dequantize_int8(
                *C.quantize_int8(x, jax.random.key(i), chunk), chunk
            ))
            for i in range(400)
        ]
        single_err = np.abs(draws[0] - np.asarray(x)).mean()
        mean_err = np.abs(np.mean(draws, axis=0) - np.asarray(x)).mean()
        assert single_err > 0  # quantization really is lossy per draw
        # the bias shrinks ~1/sqrt(draws); 10x is a loose, stable bound
        assert mean_err < single_err / 10

    def test_deterministic_per_key(self):
        x = _vec(100)
        q1, s1 = C.quantize_int8(x, jax.random.key(7), 32)
        q2, s2 = C.quantize_int8(x, jax.random.key(7), 32)
        np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))

    def test_zero_chunk_quantizes_to_zero(self):
        x = jnp.zeros(64)
        q, s = C.quantize_int8(x, jax.random.key(0), 16)
        assert np.all(np.asarray(q) == 0) and np.all(np.asarray(s) == 0)
        np.testing.assert_array_equal(
            np.asarray(C.dequantize_int8(q, s, 16)), np.zeros(64)
        )

    def test_per_chunk_scale_isolates_outliers(self):
        """A 1e4 outlier in one chunk must not destroy the resolution of
        the other chunks — the reason scales are per-chunk, not global."""
        x = np.full(128, 0.01, np.float32)
        x[3] = 1e4
        q, s = C.quantize_int8(jnp.asarray(x), jax.random.key(1), 64)
        out = np.asarray(C.dequantize_int8(q, s, 64))
        # chunk 2 (no outlier) keeps small values at int8 resolution
        assert np.abs(out[64:] - 0.01).max() < 0.01 / 64
        # chunk 1 (outlier's chunk) cannot represent 0.01 at scale 1e4/127
        assert np.abs(out[3] - 1e4) < 1e4 / 100

    def test_codes_stay_in_int8_range(self):
        x = _vec(1000) * 1e6
        q, _ = C.quantize_int8(x, jax.random.key(2), 256)
        q = np.asarray(q)
        assert q.dtype == np.int8
        assert q.min() >= -127 and q.max() <= 127


# ----------------------------------------------------- top-k error feedback
class TestTopKErrorFeedback:
    def test_dropped_mass_reappears_exactly(self):
        """THE error-feedback invariant: new_ef == acc - decompressed,
        bit-for-bit — with no quantization, kept coordinates carry zero
        error and every dropped coordinate's mass lands in the
        accumulator EXACTLY (not approximately)."""
        spec = CompressorSpec(topk_ratio=0.25)
        x = _vec(64)
        ef = jnp.zeros(64)
        payload, hat, new_ef = C.compress_with_feedback(
            spec, x, ef, jax.random.key(0)
        )
        idx = np.asarray(payload["indices"])
        hat_np, ef_np, x_np = map(np.asarray, (hat, new_ef, x))
        np.testing.assert_array_equal(ef_np, x_np - hat_np)
        np.testing.assert_array_equal(ef_np[idx], np.zeros(len(idx)))
        dropped = np.setdiff1d(np.arange(64), idx)
        np.testing.assert_array_equal(ef_np[dropped], x_np[dropped])
        np.testing.assert_array_equal(hat_np[dropped], np.zeros(len(dropped)))

    def test_accumulator_reinjected_next_round(self):
        """Rounds 2 and 3 compress delta + accumulated ef — a coordinate
        dropped round after round accumulates its mass EXACTLY, and ships
        the full total once it finally makes the cut."""
        spec = CompressorSpec(topk_ratio=0.1)
        n = 50  # k = 5 survivors
        # round 1: 11 distractors at 3.0 crowd out coordinate 7's 1.0
        delta = np.zeros(n, np.float32)
        delta[20:31] = 3.0
        delta[7] = 1.0
        ef = jnp.zeros(n)
        _, hat1, ef = C.compress_with_feedback(
            spec, jnp.asarray(delta), ef, jax.random.key(1)
        )
        assert np.asarray(hat1)[7] == 0.0  # dropped (top-5 are all 3.0s)
        assert np.asarray(ef)[7] == 1.0    # ...but remembered exactly
        # round 2: another 1.0 lands on 7; acc[7] = 2.0, still below the
        # six 3.0s the accumulator carries — dropped AGAIN, summed exactly
        delta2 = np.zeros(n, np.float32)
        delta2[7] = 1.0
        _, hat2, ef2 = C.compress_with_feedback(
            spec, jnp.asarray(delta2), ef, jax.random.key(2)
        )
        assert np.asarray(hat2)[7] == 0.0
        assert np.asarray(ef2)[7] == 2.0
        # round 3: +2.0 -> acc[7] = 4.0 beats the remaining distractor
        # mass; the ENTIRE accumulated total ships, accumulator drains
        delta3 = np.zeros(n, np.float32)
        delta3[7] = 2.0
        _, hat3, ef3 = C.compress_with_feedback(
            spec, jnp.asarray(delta3), ef2, jax.random.key(3)
        )
        assert np.asarray(hat3)[7] == 4.0
        assert np.asarray(ef3)[7] == 0.0

    def test_ef_exact_with_int8_composed(self):
        spec = CompressorSpec(topk_ratio=0.2, int8=True, chunk=32)
        x = _vec(200)
        _, hat, new_ef = C.compress_with_feedback(
            spec, x, jnp.zeros(200), jax.random.key(3)
        )
        np.testing.assert_array_equal(
            np.asarray(new_ef), np.asarray(x) - np.asarray(hat)
        )

    def test_error_feedback_off_keeps_zero_state(self):
        spec = CompressorSpec(topk_ratio=0.2, error_feedback=False)
        x = _vec(100)
        _, _, new_ef = C.compress_with_feedback(
            spec, x, jnp.zeros(100), jax.random.key(4)
        )
        assert np.all(np.asarray(new_ef) == 0)

    def test_comm_dtype_cast_error_lands_in_ef(self):
        """Composition order is cast-then-quantize: the bf16 cast error is
        part of the wire error and must land in the accumulator."""
        spec = CompressorSpec(topk_ratio=1.0)  # keep everything
        x = _vec(64) * 1.000123  # values with bf16 rounding error
        _, hat, new_ef = C.compress_with_feedback(
            spec, x, jnp.zeros(64), jax.random.key(5),
            cast_dtype=jnp.bfloat16,
        )
        casted = np.asarray(x).astype(jnp.bfloat16).astype(np.float32)
        np.testing.assert_array_equal(np.asarray(hat), casted)
        np.testing.assert_array_equal(
            np.asarray(new_ef), np.asarray(x) - casted
        )
        assert np.abs(np.asarray(new_ef)).max() > 0  # cast really lossy

    def test_decompress_matches_hat_bitwise(self):
        for spec in (
            CompressorSpec(int8=True),
            CompressorSpec(topk_ratio=0.3),
            CompressorSpec(topk_ratio=0.3, int8=True, chunk=16),
        ):
            x = _vec(300)
            payload, hat, _ = C.compress_with_feedback(
                spec, x, jnp.zeros(300), jax.random.key(6)
            )
            out = C.decompress_flat(spec, payload, 300)
            np.testing.assert_array_equal(np.asarray(out), np.asarray(hat))


# ------------------------------------------------------------- wire payload
SPECS = [
    CompressorSpec(int8=True, chunk=32),
    CompressorSpec(topk_ratio=0.2),
    CompressorSpec(topk_ratio=0.2, int8=True, chunk=32),
]


class TestWirePayload:
    @pytest.mark.parametrize("spec", SPECS, ids=lambda s: repr(s)[:40])
    def test_wire_roundtrip_exact(self, spec):
        x = _vec(150)
        payload, hat, _ = C.compress_with_feedback(
            spec, x, jnp.zeros(150), jax.random.key(0)
        )
        wire = C.payload_to_wire(spec, payload, 150)
        spec2, p2, n2 = C.wire_to_payload(wire)
        out = C.decompress_flat(
            spec2, {k: jnp.asarray(v) for k, v in p2.items()}, n2
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(hat))

    @pytest.mark.parametrize("spec", SPECS, ids=lambda s: repr(s)[:40])
    def test_wire_survives_v2_and_v1_serialization(self, spec):
        """Interop contract: the compressed frame decompresses identically
        after a v2 hop (SparseVector intact) AND after a legacy v1 hop
        (SparseVector densified by the fallback)."""
        from vantage6_tpu.common.serialization import deserialize, serialize

        x = _vec(150)
        payload, hat, _ = C.compress_with_feedback(
            spec, x, jnp.zeros(150), jax.random.key(1)
        )
        wire = C.payload_to_wire(spec, payload, 150)
        for fmt in ("v2", "v1"):
            rt = deserialize(serialize(wire, format=fmt))
            spec2, p2, n2 = C.wire_to_payload(rt)
            out = C.decompress_flat(
                spec2, {k: jnp.asarray(v) for k, v in p2.items()}, n2
            )
            np.testing.assert_array_equal(
                np.asarray(out), np.asarray(hat),
                err_msg=f"format {fmt} broke the reconstruction",
            )

    def test_wire_payload_is_smaller(self):
        spec = CompressorSpec(topk_ratio=0.05, int8=True)
        n = 200_000
        x = jnp.asarray(RNG.normal(size=n).astype(np.float32))
        payload, _, _ = C.compress_with_feedback(
            spec, x, jnp.zeros(n), jax.random.key(2)
        )
        from vantage6_tpu.common.serialization import serialize

        wire = C.payload_to_wire(spec, payload, n)
        dense_len = len(serialize({"delta": np.asarray(x)}, format="v2"))
        comp_len = len(serialize(wire, format="v2"))
        assert dense_len / comp_len > 4.0  # the acceptance bar, measured

    def test_non_payload_rejected(self):
        with pytest.raises(ValueError, match="not a v6t compressed"):
            C.wire_to_payload({"method": "avg"})
        assert not C.is_wire_payload({"x": 1})
        assert not C.is_wire_payload([1, 2])

    def _tamper(self, spec=None, n=150, **overrides):
        spec = spec or CompressorSpec(topk_ratio=0.2, int8=True, chunk=32)
        x = _vec(n)
        payload, _, _ = C.compress_with_feedback(
            spec, x, jnp.zeros(n), jax.random.key(0)
        )
        wire = C.payload_to_wire(spec, payload, n)
        wire.update(overrides)
        return wire

    def test_untrusted_n_cannot_amplify_allocation(self):
        """A ~100-byte frame claiming n=10**12 must be rejected before
        anything allocates a dense [n] vector — decompression is fed
        PEER payloads (amplification defense)."""
        wire = self._tamper(n=150)
        wire["n"] = 10**12
        with pytest.raises(ValueError, match="outside"):
            C.wire_to_payload(wire)
        wire["n"] = -1
        with pytest.raises(ValueError, match="outside"):
            C.wire_to_payload(wire)

    def test_sparse_size_must_match_n(self):
        """sparse.size != n would let tampered indices be silently
        dropped by the scatter instead of rejected."""
        wire = self._tamper(n=150)
        wire["n"] = 149  # sparse half still spans 150
        with pytest.raises(ValueError, match="sparse size"):
            C.wire_to_payload(wire)

    def test_missing_fields_raise_valueerror(self):
        for key in ("sparse", "scales"):
            wire = self._tamper(n=150)
            del wire[key]
            with pytest.raises(ValueError, match=f"missing '{key}'"):
                C.wire_to_payload(wire)
        # dense int8 payload: wrong q/scales lengths rejected too
        spec = CompressorSpec(int8=True, chunk=32)
        wire = self._tamper(spec=spec, n=96)
        wire["q"] = wire["q"][:10]
        with pytest.raises(ValueError, match="10 values, expected 96"):
            C.wire_to_payload(wire)
        wire = self._tamper(spec=spec, n=96)
        wire["scales"] = wire["scales"][:1]
        with pytest.raises(ValueError, match="1 scales, expected 3"):
            C.wire_to_payload(wire)


# ----------------------------------------------------------- pytree packing
class TestTreePacking:
    def test_skeleton_roundtrip(self):
        tree = {
            "a": np.arange(6, dtype=np.float32).reshape(2, 3),
            "z": np.ones(4, np.float32),  # dict order != sorted order
            "nested": [{"b": np.zeros((2, 2), np.float32)}],
        }
        flat = C.flatten_host(tree)
        assert flat.shape == (14,)
        out = C.rebuild_from_skeleton(C.tree_skeleton(tree), flat)
        np.testing.assert_array_equal(out["a"], tree["a"])
        np.testing.assert_array_equal(out["z"], tree["z"])
        np.testing.assert_array_equal(
            out["nested"][0]["b"], tree["nested"][0]["b"]
        )

    def test_skeleton_survives_json(self):
        import json

        tree = {"w": np.arange(3, dtype=np.float32)}
        sk = json.loads(json.dumps(C.tree_skeleton(tree)))
        out = C.rebuild_from_skeleton(sk, C.flatten_host(tree))
        np.testing.assert_array_equal(out["w"], tree["w"])

    def test_tuples_come_back_as_tuples(self):
        """Arming compression must not change container types: a tuple
        update that works uncompressed must round-trip as a TUPLE (a
        list would fail jax.tree.map against the caller's params)."""
        import json

        tree = (np.ones(4, np.float32), {"b": np.zeros(2, np.float32)})
        sk = json.loads(json.dumps(C.tree_skeleton(tree)))
        out = C.rebuild_from_skeleton(sk, C.flatten_host(tree))
        assert isinstance(out, tuple) and len(out) == 2
        jax.tree.map(lambda a, b: a + b, tree, out)  # structures agree
        # and through the full DeltaCompressor round-trip
        dc = C.DeltaCompressor(CompressorSpec(topk_ratio=1.0, int8=True))
        rt = dc.decompress(dc.compress(tree))
        assert isinstance(rt, tuple) and isinstance(rt[1], dict)

    def test_namedtuple_rejected_loudly(self):
        import collections

        Point = collections.namedtuple("Point", "x y")
        with pytest.raises(TypeError, match="NamedTuple"):
            C.tree_skeleton(Point(np.ones(2), np.zeros(2)))

    def test_bfloat16_leaf_dtype_survives(self):
        """ml_dtypes leaves (the TPU compute dtype) must round-trip as
        bfloat16 — dtype.str degrades to a raw void ('<V2') that would
        silently reinterpret bytes; the skeleton carries the NAME."""
        import json

        tree = {"w": jnp.ones((4, 4), jnp.bfloat16)}
        sk = json.loads(json.dumps(C.tree_skeleton(tree)))
        assert sk["w"]["dtype"] == "bfloat16"
        out = C.rebuild_from_skeleton(sk, C.flatten_host(tree))
        assert out["w"].dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(out["w"], np.float32), np.ones((4, 4), np.float32)
        )
        # full round-trip through the host-plane compressor
        dc = C.DeltaCompressor(CompressorSpec(topk_ratio=1.0))
        rt = dc.decompress(dc.compress(tree))
        assert rt["w"].dtype == jnp.bfloat16
        with pytest.raises(ValueError, match="cannot reconstruct"):
            C._resolve_dtype("void16")

    def test_instances_draw_independent_noise(self):
        """Two station PROCESSES (one DeltaCompressor each) must not use
        the same stochastic-rounding stream — correlated noise would stop
        averaging out across stations."""
        a = C.DeltaCompressor(CompressorSpec(int8=True))
        b = C.DeltaCompressor(CompressorSpec(int8=True))
        assert a._seed != b._seed  # os.urandom per instance


# ------------------------------------------------------------ FedAvg engine
@pytest.fixture(scope="module")
def tiny_fed(fresh):
    """A tiny 8-station linear-regression federation (fast on CPU).
    ``p0`` is shared: ``run_rounds`` gets ``fresh(p0)``."""
    from vantage6_tpu.core.mesh import FederationMesh
    from vantage6_tpu.fed.fedavg import FedAvg, FedAvgSpec

    mesh = FederationMesh(8)
    dim = 12
    rng = np.random.default_rng(3)
    w_true = rng.normal(size=(dim,)).astype(np.float32)
    xs = rng.normal(size=(8, 40, dim)).astype(np.float32)
    ys = xs @ w_true + 0.01 * rng.normal(size=(8, 40)).astype(np.float32)
    sx = mesh.shard_stacked(jnp.asarray(xs))
    sy = mesh.shard_stacked(jnp.asarray(ys))
    counts = jnp.full((8,), 40.0)

    def loss_fn(params, bx, by, w):
        pred = bx @ params["w"] + params["b"]
        return jnp.sum(w * (pred - by) ** 2) / jnp.maximum(jnp.sum(w), 1.0)

    p0 = {"w": jnp.zeros(dim), "b": jnp.zeros(())}

    def engine(**kw):
        return FedAvg(mesh, FedAvgSpec(
            loss_fn=loss_fn, local_steps=2, batch_size=16, local_lr=0.05,
            **kw,
        ))

    return {"mesh": mesh, "sx": sx, "sy": sy, "counts": counts, "p0": p0,
            "engine": engine, "fresh": fresh}


class TestFedAvgCompressed:
    def _run(self, fed, eng, rounds=4):
        return eng.run_rounds(
            fed["fresh"](fed["p0"]), fed["sx"], fed["sy"], fed["counts"],
            jax.random.key(0), n_rounds=rounds,
        )

    def test_lossless_compressor_is_fp32_identical(self, tiny_fed):
        """topk_ratio=1.0 without int8 drops nothing and rounds nothing:
        the compressed engine must reproduce the dense engine's params
        BIT-FOR-BIT (the flat-pack seam adds no numerics)."""
        dense = tiny_fed["engine"]()
        lossless = tiny_fed["engine"](
            compressor=CompressorSpec(topk_ratio=1.0)
        )
        pd_, _, ld, _ = self._run(tiny_fed, dense)
        pc_, oc, lc, _ = self._run(tiny_fed, lossless)
        for a, b in zip(jax.tree.leaves(pd_), jax.tree.leaves(pc_)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(ld), np.asarray(lc))
        assert np.all(np.asarray(oc["ef"]) == 0)  # nothing ever dropped

    def test_lossy_compressed_run_converges(self, tiny_fed):
        spec = CompressorSpec(topk_ratio=0.25, int8=True, chunk=8)
        eng = tiny_fed["engine"](compressor=spec)
        params, state, losses, _ = self._run(tiny_fed, eng, rounds=8)
        losses = np.asarray(losses)
        assert losses[-1] < losses[0] * 0.5  # actually learning
        ef = np.asarray(state["ef"])
        assert ef.shape == (8, 13)  # per-station accumulators, N=dim+1
        assert np.abs(ef).sum() > 0  # error feedback is live

    def test_compressed_tracks_dense_accuracy(self, tiny_fed):
        """Accuracy-parity shape of the bench acceptance: the lossy run's
        final loss stays within tolerance of the dense run's."""
        dense = tiny_fed["engine"]()
        lossy = tiny_fed["engine"](
            compressor=CompressorSpec(topk_ratio=0.25, int8=True, chunk=8)
        )
        _, _, ld, _ = self._run(tiny_fed, dense, rounds=8)
        _, _, lc, _ = self._run(tiny_fed, lossy, rounds=8)
        assert float(lc[-1]) < float(ld[-1]) * 2.0 + 0.05

    def test_round_and_run_rounds_state_compatible(self, tiny_fed):
        spec = CompressorSpec(topk_ratio=0.5)
        eng = tiny_fed["engine"](compressor=spec)
        state = eng.init(tiny_fed["p0"])
        assert set(state) == {"server", "ef"}
        p1, state1, _, _ = eng.round(
            tiny_fed["p0"], state, tiny_fed["sx"], tiny_fed["sy"],
            tiny_fed["counts"], jax.random.key(1),
        )
        # resuming run_rounds from a round()'s state must work (the carry
        # is the same pytree shape)
        p2, state2, _, _ = eng.run_rounds(
            p1, tiny_fed["sx"], tiny_fed["sy"], tiny_fed["counts"],
            jax.random.key(2), n_rounds=2, opt_state=state1,
        )
        assert np.asarray(state2["ef"]).shape == (8, 13)

    def test_composes_with_scattered_zero1_update(self, tiny_fed):
        import optax

        spec = CompressorSpec(topk_ratio=0.5, int8=True, chunk=8)
        eng = tiny_fed["engine"](
            compressor=spec, shard_server_update=True,
            comm_dtype=jnp.bfloat16,
            server_optimizer=optax.adam(1e-2),
        )
        params, state, losses, _ = self._run(tiny_fed, eng, rounds=4)
        assert np.isfinite(np.asarray(losses)).all()
        assert np.isfinite(np.asarray(state["ef"])).all()

    def test_participation_mask_still_isolates(self, tiny_fed):
        spec = CompressorSpec(topk_ratio=0.5)
        eng = tiny_fed["engine"](compressor=spec)
        mask = jnp.asarray([1, 1, 0, 1, 1, 1, 1, 1], jnp.float32)
        params, _, losses, _ = eng.run_rounds(
            tiny_fed["fresh"](tiny_fed["p0"]), tiny_fed["sx"],
            tiny_fed["sy"], tiny_fed["counts"], jax.random.key(0),
            n_rounds=2, mask=mask,
        )
        for leaf in jax.tree.leaves(params):
            assert np.isfinite(np.asarray(leaf)).all()

    def test_masked_station_ef_waits(self, tiny_fed):
        """A masked-out station ships nothing, so its accumulator must
        carry over UNCHANGED (docs/compression.md: "its accumulator
        simply waits (mass is never lost)") — participating stations'
        rows advance in the same round."""
        spec = CompressorSpec(topk_ratio=0.25)
        eng = tiny_fed["engine"](compressor=spec)
        state = eng.init(tiny_fed["p0"])
        mask = jnp.asarray([1, 1, 1, 0, 1, 1, 1, 1], jnp.float32)
        # round 1 with everyone in: every EF row becomes nonzero
        _, state, _, _ = eng.round(
            tiny_fed["p0"], state, tiny_fed["sx"], tiny_fed["sy"],
            tiny_fed["counts"], jax.random.key(1),
        )
        ef1 = np.asarray(state["ef"])
        assert np.abs(ef1).sum() > 0
        # round 2 with station 3 masked out: its row is bit-identical
        _, state, _, _ = eng.round(
            tiny_fed["p0"], state, tiny_fed["sx"], tiny_fed["sy"],
            tiny_fed["counts"], jax.random.key(2), mask=mask,
        )
        ef2 = np.asarray(state["ef"])
        np.testing.assert_array_equal(ef2[3], ef1[3])
        changed = [i for i in range(8) if not np.array_equal(ef2[i], ef1[i])]
        assert 3 not in changed and len(changed) == 7

    def test_compression_stats_and_telemetry(self, tiny_fed):
        from vantage6_tpu.common.telemetry import REGISTRY

        spec = CompressorSpec(topk_ratio=0.1, int8=True)
        eng = tiny_fed["engine"](compressor=spec)
        stats = eng.compression_stats(tiny_fed["p0"])
        assert stats["n_params"] == 13
        assert stats["raw_bytes_per_round"] == 4 * 13 * 8
        before = REGISTRY.snapshot()["v6t_compress_calls_total"]
        self._run(tiny_fed, eng, rounds=3)
        after = REGISTRY.snapshot()["v6t_compress_calls_total"]
        assert after == before + 8 * 3  # one uplink per station per round
        assert tiny_fed["engine"]().compression_stats(tiny_fed["p0"]) is None


# ------------------------------------------------------------- host plane
class TestHostPlane:
    def _fed(self, spec):
        from vantage6_tpu.algorithm.context import current_environment
        from vantage6_tpu.core.config import (
            DatabaseConfig,
            FederationConfig,
            StationConfig,
        )
        from vantage6_tpu.runtime.federation import Federation

        def partial_delta(scale=1.0):
            env = current_environment()
            delta = {
                "w": np.full(400, scale, np.float32),
                "b": np.arange(8, dtype=np.float32) * scale,
            }
            return env.client.compress_update(delta)

        cfg = FederationConfig(
            name="comp",
            compressor=spec,
            executor_workers=0,
            stations=[
                StationConfig(
                    name=f"s{i}", organization=f"org_{i}",
                    databases=[DatabaseConfig(label="default", type="array")],
                )
                for i in range(3)
            ],
        )
        fed = Federation(
            cfg, algorithms={"img": {"partial_delta": partial_delta}}
        )
        fed.set_datasets("default", [np.zeros(2)] * 3)
        return fed

    def test_config_validates_compressor(self):
        from vantage6_tpu.core.config import (
            ConfigurationError,
            FederationConfig,
            StationConfig,
        )

        cfg = FederationConfig(
            compressor=object(), stations=[StationConfig(name="s")]
        )
        with pytest.raises(ConfigurationError, match="compressor"):
            cfg.validate()
        cfg2 = FederationConfig(
            compressor=CompressorSpec(topk_ratio=2.0),
            stations=[StationConfig(name="s")],
        )
        with pytest.raises(ConfigurationError, match="bad compressor"):
            cfg2.validate()

    def test_config_from_dict_builds_spec(self):
        from vantage6_tpu.core.config import FederationConfig

        cfg = FederationConfig.from_dict({
            "federation": {
                "name": "x",
                "compression": {"topk_ratio": 0.1, "int8": True},
            },
            "stations": [{"name": "a"}],
        })
        assert isinstance(cfg.compressor, CompressorSpec)
        assert cfg.compressor.topk_ratio == 0.1 and cfg.compressor.int8

    def test_config_compression_true_is_a_config_error(self):
        """'compression: true' in YAML must raise the ConfigurationError
        contract, not an AttributeError deep in from_dict."""
        from vantage6_tpu.core.config import (
            ConfigurationError,
            FederationConfig,
        )

        with pytest.raises(ConfigurationError, match="must be a mapping"):
            FederationConfig.from_dict({
                "federation": {"name": "x", "compression": True},
                "stations": [{"name": "a"}],
            })
        # a typo'd key ('topk' — the V6T_COMPRESS spelling) must not
        # silently disable compression via an identity spec
        with pytest.raises(ConfigurationError, match="unknown key"):
            FederationConfig.from_dict({
                "federation": {"name": "x", "compression": {"topk": 0.1}},
                "stations": [{"name": "a"}],
            })

    def test_roundtrip_with_error_feedback_across_tasks(self):
        spec = CompressorSpec(topk_ratio=0.1, int8=True, chunk=64)
        fed = self._fed(spec)
        t1 = fed.create_task("img", {"method": "partial_delta",
                                     "kwargs": {"scale": 2.0}})
        res1 = fed.wait_for_results(t1.id)
        assert all(C.is_wire_payload(r) for r in res1)
        dense1 = [fed.decompress_update(r) for r in res1]
        assert dense1[0]["w"].shape == (400,)
        # per-station accumulators materialized for every station
        store = fed._delta_compressor._ef
        assert {f"{i}:update" for i in range(3)} <= set(store)
        ef_before = store["0:update"].copy()
        assert np.abs(ef_before).sum() > 0
        t2 = fed.create_task("img", {"method": "partial_delta",
                                     "kwargs": {"scale": 2.0}})
        fed.wait_for_results(t2.id)
        ef_after = store["0:update"]
        assert not np.array_equal(ef_before, ef_after)  # state advanced
        fed.close()

    def test_result_wire_bytes_reflect_compression(self):
        spec = CompressorSpec(topk_ratio=0.05, int8=True)
        fed = self._fed(spec)
        t = fed.create_task("img", {"method": "partial_delta"})
        fed.wait_for_results(t.id)
        # the dense delta is 408 f32 = 1632 payload bytes; the recorded
        # result size must reflect the compressed frame instead
        dense_bytes = 408 * 4
        for r in t.runs:
            assert r.result_wire_bytes is not None
            assert r.result_wire_bytes < dense_bytes
        fed.close()

    def test_passthrough_without_compressor(self):
        fed = self._fed(None)
        t = fed.create_task("img", {"method": "partial_delta"})
        res = fed.wait_for_results(t.id)
        assert isinstance(res[0], dict) and "w" in res[0]
        assert not C.is_wire_payload(res[0])
        # decompress_update tolerates uncompressed results (mixed fleets)
        same = fed.decompress_update(res[0])
        assert same is res[0]
        fed.close()

    def test_spans_and_telemetry_on_host_plane(self):
        from vantage6_tpu.common.telemetry import REGISTRY
        from vantage6_tpu.runtime.tracing import TRACER

        spec = CompressorSpec(topk_ratio=0.2, int8=True)
        fed = self._fed(spec)
        before = REGISTRY.snapshot()
        with TRACER.span("test.root", kind="test") as root:
            t = fed.create_task("img", {"method": "partial_delta"})
            res = fed.wait_for_results(t.id)
            fed.decompress_update(res[0])
            trace_id = root.context.trace_id
        spans = TRACER.drain(trace_id)
        names = [s["name"] for s in spans]
        assert names.count("device.compress") == 3  # one per station
        assert "device.decompress" in names
        comp_span = next(s for s in spans if s["name"] == "device.compress")
        assert comp_span["attrs"]["raw_bytes"] > comp_span["attrs"]["wire_bytes"]
        after = REGISTRY.snapshot()
        assert after["v6t_compress_calls_total"] >= (
            before["v6t_compress_calls_total"] + 3
        )
        assert after["v6t_decompress_calls_total"] >= (
            before["v6t_decompress_calls_total"] + 1
        )
        assert after["v6t_compress_ratio"] > 1.0
        fed.close()


# ----------------------------------------------- containerized client parity
class TestDeltaCompressor:
    def test_compress_decompress_with_named_ef(self):
        dc = C.DeltaCompressor(CompressorSpec(topk_ratio=0.2, int8=True))
        tree = {"w": np.arange(100, dtype=np.float32)}
        wire = dc.compress(tree)
        assert C.is_wire_payload(wire)
        out = dc.decompress(wire)
        assert out["w"].shape == (100,)
        assert "update" in dc._ef
        # independent exchanges keep independent accumulators
        dc.compress(tree, name="other")
        assert set(dc._ef) == {"update", "other"}

    def test_identity_spec_is_passthrough(self):
        dc = C.DeltaCompressor(CompressorSpec())
        tree = {"w": np.ones(3, np.float32)}
        assert dc.compress(tree) is tree

    def test_concurrent_same_name_compresses_serialize(self):
        """The EF read-compute-write cycle is serialized per name: N
        concurrent lossless compresses must leave EF exactly zero (any
        double-injection would show up as nonzero residue) and N distinct
        key sequences consumed."""
        import threading

        dc = C.DeltaCompressor(CompressorSpec(topk_ratio=1.0, int8=False))
        tree = {"w": np.arange(64, dtype=np.float32)}
        errors = []

        def worker():
            try:
                for _ in range(10):
                    dc.compress(tree, name="update")
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=worker) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        assert dc._seq == 40
        np.testing.assert_array_equal(
            dc._ef["update"], np.zeros(64, np.float32)
        )

    def test_spec_from_env(self):
        assert C.spec_from_env({}) is None
        assert C.spec_from_env({"V6T_COMPRESS": "off"}) is None
        s = C.spec_from_env(
            {"V6T_COMPRESS": "topk=0.1,int8,chunk=128,no-ef"}
        )
        assert s == CompressorSpec(topk_ratio=0.1, int8=True, chunk=128,
                                   error_feedback=False)
        with pytest.raises(ValueError, match="unknown knob"):
            C.spec_from_env({"V6T_COMPRESS": "topk=0.1,zstd"})
        with pytest.raises(ValueError, match="topk_ratio"):
            C.spec_from_env({"V6T_COMPRESS": "topk=3.0"})

    def test_rest_client_surface_parity(self, monkeypatch):
        """The containerized client carries the SAME two calls: inert
        pass-throughs by default, armed by V6T_COMPRESS."""
        from vantage6_tpu.client.rest import RestAlgorithmClient

        c = RestAlgorithmClient("http://localhost:1", token="t")
        tree = {"w": np.arange(50, dtype=np.float32)}
        assert c.compress_update(tree) is tree  # unarmed: pass-through
        assert c.decompress_update(tree) is tree
        monkeypatch.setenv("V6T_COMPRESS", "topk=0.2,int8")
        c2 = RestAlgorithmClient("http://localhost:1", token="t")
        wire = c2.compress_update(tree)
        assert C.is_wire_payload(wire)
        out = c2.decompress_update(wire)
        assert out["w"].shape == (50,)
        # and the Federation-side decompress reads the same wire payload
        from vantage6_tpu.fed.compression import decompress_wire_tree

        np.testing.assert_array_equal(
            decompress_wire_tree(wire)["w"], out["w"]
        )

    def test_rest_client_tag_literal_in_sync(self):
        """decompress_update tests the wire tag inline (so pass-throughs
        never import fed/jax) — the literal must track WIRE_TAG."""
        import inspect

        from vantage6_tpu.client import rest as rest_mod

        src = inspect.getsource(rest_mod.RestAlgorithmClient.decompress_update)
        assert repr(C.WIRE_TAG) in src or C.WIRE_TAG in src


# ------------------------------------------------------- trace view summary
class TestTraceSummaryCompression:
    def _span(self, name, dur, kind="device", trace="t1", span_id=None,
              parent_id=None):
        return {"trace_id": trace, "span_id": span_id or name,
                "parent_id": parent_id, "name": name,
                "kind": kind, "dur": dur, "attrs": {}}

    def test_summarize_reports_compression_cost(self):
        from vantage6_tpu.runtime.tracing import summarize

        spans = [
            self._span("runner.exec", 1.0, kind="exec"),
            self._span("device.compress", 0.04),
            self._span("device.compress", 0.03),
            self._span("device.decompress", 0.03),
        ]
        s = summarize(spans)
        comp = s["compression"]
        assert comp["compress_total_ms"] == 70.0
        assert comp["decompress_total_ms"] == 30.0
        assert comp["pct_of_exec"] == 10.0
        # and absent when no compression spans exist
        assert summarize([self._span("x", 1.0, kind="exec")])[
            "compression"] is None

    def test_nested_exec_spans_not_double_counted(self):
        """A central's runner.exec encloses its partials' exec spans —
        exec_total must count the WALL-CLOCK once, or the compression
        pct reads half its true value and spuriously passes the bar."""
        from vantage6_tpu.runtime.tracing import summarize

        spans = [
            self._span("runner.exec", 1.0, kind="exec", span_id="root"),
            self._span("runner.exec", 0.45, kind="exec", span_id="p1",
                       parent_id="root"),
            self._span("runner.exec", 0.45, kind="exec", span_id="p2",
                       parent_id="root"),
            self._span("device.compress", 0.1, parent_id="root"),
        ]
        comp = summarize(spans)["compression"]
        # denominator is 1.0 (root only), not 1.9
        assert comp["pct_of_exec"] == 10.0

    def test_trace_view_renders_compression(self, capsys, tmp_path):
        import json

        from tools.trace_view import main as trace_main

        spans = [
            self._span("runner.exec", 1.0, kind="exec"),
            self._span("device.compress", 0.05),
            self._span("device.decompress", 0.01),
        ]
        f = tmp_path / "spans.jsonl"
        f.write_text("\n".join(json.dumps(s) for s in spans) + "\n")
        assert trace_main([str(f)]) == 0
        out = capsys.readouterr().out
        assert "device.compress" in out
        assert "gradient compression" in out
        assert "cost vs exec total" in out

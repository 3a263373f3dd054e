"""fed/ collectives: property tests against numpy on the fake pod."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vantage6_tpu.core.mesh import FederationMesh
from vantage6_tpu.fed import collectives as C

RNG = np.random.default_rng(42)


def test_fed_sum_matches_numpy():
    x = RNG.normal(size=(8, 3, 4)).astype(np.float32)
    out = C.fed_sum(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(out), x.sum(0), rtol=1e-4, atol=1e-5)


def test_fed_sum_with_mask():
    x = RNG.normal(size=(8, 5)).astype(np.float32)
    mask = np.array([1, 1, 0, 1, 0, 1, 1, 1], np.float32)
    out = C.fed_sum(jnp.asarray(x), mask=jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(out), (x * mask[:, None]).sum(0),
                               rtol=1e-4, atol=1e-5)


def test_fed_mean_weighted():
    x = RNG.normal(size=(4, 6)).astype(np.float32)
    w = np.array([10, 20, 30, 40], np.float32)
    out = C.fed_mean(jnp.asarray(x), weights=jnp.asarray(w))
    expect = (x * w[:, None]).sum(0) / w.sum()
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-4, atol=1e-5)


def test_fed_mean_all_masked_is_finite():
    x = RNG.normal(size=(4, 2)).astype(np.float32)
    out = C.fed_mean(jnp.asarray(x), mask=jnp.zeros(4))
    assert np.isfinite(np.asarray(out)).all()


def test_fed_mean_pytree():
    tree = {"w": jnp.asarray(RNG.normal(size=(4, 3)).astype(np.float32)),
            "b": jnp.asarray(RNG.normal(size=(4,)).astype(np.float32))}
    w = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    out = C.fed_mean(tree, weights=w)
    expect_b = (np.asarray(tree["b"]) * np.asarray(w)).sum() / 10.0
    np.testing.assert_allclose(np.asarray(out["b"]), expect_b, rtol=1e-4, atol=1e-5)


def test_fed_concat():
    x = jnp.arange(24, dtype=jnp.float32).reshape(4, 6)
    out = C.fed_concat(x)
    assert out.shape == (24,)


def test_sharded_aggregation_under_jit():
    """End-to-end: stacked data sharded over stations, reduce inside jit —
    GSPMD must insert the cross-device collective."""
    fm = FederationMesh(8)
    x = RNG.normal(size=(8, 16)).astype(np.float32)
    stacked = fm.shard_stacked(x)

    @jax.jit
    def agg(s):
        return C.fed_mean(s)

    out = agg(stacked)
    np.testing.assert_allclose(np.asarray(out), x.mean(0), rtol=1e-4, atol=1e-5)


# ----------------------------------------------------- bf16 numerics contract
def test_bf16_leaf_rounding_contract():
    """Pins the documented numerics contract (_norm_weights docstring):

    - integer ``weights`` are upcast to f32 (no truncation/overflow);
    - ``fed_mean`` on bf16 leaves computes IN bf16 — the result is bf16 and
      carries visible rounding error vs the f32 truth;
    - the scattered path accumulates in f32, so (on the same inputs) it is
      at least as accurate as the bf16-dtype path — the property that makes
      ``comm_dtype=bfloat16`` a wire format and not a precision downgrade
      of the whole aggregation.
    """
    fm = FederationMesh(8)
    rng = np.random.default_rng(0)
    x_f32 = rng.normal(0, 10, size=(8, 64)).astype(np.float32)
    x_bf16 = jnp.asarray(x_f32, jnp.bfloat16)
    w_int = jnp.asarray(rng.integers(1, 100, size=8), jnp.int32)

    # integer weights: exact upcast (f32 holds ints < 2^24 exactly)
    out_int = C.fed_mean(jnp.asarray(x_f32), weights=w_int)
    w_f = np.asarray(w_int, np.float32)
    truth_f32 = (x_f32 * w_f[:, None]).sum(0) / w_f.sum()
    np.testing.assert_allclose(np.asarray(out_int), truth_f32,
                               rtol=1e-5, atol=1e-5)

    # bf16 leaves: bf16 in, bf16 out, bf16 rounding
    truth = (np.asarray(x_bf16, np.float32) * w_f[:, None]).sum(0) / w_f.sum()
    out_bf = C.fed_mean(x_bf16, weights=w_int)
    assert out_bf.dtype == jnp.bfloat16
    err_bf = np.abs(np.asarray(out_bf, np.float32) - truth).max()
    # worst case ~ a few bf16 ulps of the magnitude scale; it must be
    # VISIBLE (this is real rounding, not noise) yet bounded
    assert 0 < err_bf < 0.25, err_bf

    out_scat = C.fed_mean_scattered_tree(fm, x_bf16, weights=w_int)
    assert out_scat.dtype == jnp.bfloat16  # cast back to the leaf dtype
    err_scat = np.abs(np.asarray(out_scat, np.float32) - truth).max()
    # f32 accumulation: error only from the final bf16 cast (1/2 ulp)
    assert err_scat <= err_bf + 1e-6, (err_scat, err_bf)


# ------------------------------------------------------------- secure sum
def test_secure_sum_exact_cancellation():
    x = RNG.uniform(-5, 5, size=(8, 32)).astype(np.float32)
    key = jax.random.key(7)
    out = C.secure_sum(jnp.asarray(x), key)
    # Quantization error only: S stations * 0.5/scale per element worst case.
    np.testing.assert_allclose(np.asarray(out), x.sum(0), atol=8 * 0.5 / 2**16)


def test_secure_sum_masked_values_look_random():
    """An individual station's masked tensor must not reveal its value."""
    x = jnp.ones((4, 128), jnp.float32)
    key = jax.random.key(0)
    q = jax.vmap(
        lambda i, v: C.mask_station_value(key, i, 4, C.quantize(v, 2.0**16))
    )(jnp.arange(4), x)
    masked = np.asarray(q[0], np.int64)
    clear = np.asarray(C.quantize(x[0], 2.0**16), np.int64)
    # masked should be (near) uniform int32, i.e. huge |values| vs the clear 2^16s
    assert np.abs(masked - clear).mean() > 2**24


def test_secure_fed_mean_matches_fedavg():
    tree = {"w": jnp.asarray(RNG.normal(size=(4, 8)).astype(np.float32))}
    weights = jnp.asarray([10.0, 20.0, 30.0, 40.0])
    key = jax.random.key(3)
    out = C.secure_fed_mean(tree, weights, key, scale=2.0**12)
    expect = C.fed_mean(tree, weights=weights)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(expect["w"]),
                               atol=1e-2)


def test_secure_sum_under_jit_on_mesh():
    fm = FederationMesh(8)
    x = RNG.uniform(-1, 1, size=(8, 64)).astype(np.float32)
    key = jax.random.key(11)

    @jax.jit
    def prog(s):
        return C.secure_sum(s, key)

    out = prog(fm.shard_stacked(x))
    np.testing.assert_allclose(np.asarray(out), x.sum(0), atol=1e-2)


# --------------------------------------------------------------------------
# fed_mean over a named axis, round a ring (`OverAxis`)
# --------------------------------------------------------------------------

class _Chip:
    def __init__(self, *coords):
        self.coords = coords


def _grid(nx, ny):
    """Chips numbered row by row, as a TPU's are."""
    return [_Chip(x, y, 0) for y in range(ny) for x in range(nx)]


@pytest.mark.parametrize("chips", [
    _grid(2, 2),
    [_Chip(0, y, z) for z in range(2) for y in range(2)],  # another plane
    [_Chip(x, y, 0) for x, y in [(1, 1), (0, 0), (0, 1), (1, 0)]],
], ids=["2x2", "2x2_in_y_and_z", "2x2_in_another_order"])
def test_station_ring_goes_round_the_square_of_a_2_by_2(chips):
    ring = C.station_ring(chips)
    assert sorted(ring) == list(range(4))
    for a, b in zip(ring, ring[1:] + ring[:1]):
        hop = sum(abs(p - q) for p, q in zip(chips[a].coords, chips[b].coords))
        assert hop == 1, (ring, a, b)


@pytest.mark.parametrize("chips", [
    [object() for _ in range(4)],                      # say nothing (CPU)
    _grid(4, 1),                                       # a line: no cycle
    [_Chip(0, 0, 0), _Chip(1, 0, 0), _Chip(2, 0, 0), _Chip(0, 1, 0)],
    _grid(2, 1),                                       # two chips
    _grid(4, 2), _grid(3, 3),                          # no such mesh was read
], ids=["no_coords", "line", "four_in_an_L", "pair", "4x2", "3x3"])
def test_station_ring_is_by_index_where_it_knows_no_better(chips):
    assert C.station_ring(chips) == tuple(range(len(chips)))


def test_ring_groups_close_at_their_bytes(monkeypatch):
    monkeypatch.setattr(C, "RING_GROUP_BYTES", 100)
    assert C.ring_groups([60, 60, 10, 200, 5]) == [[0, 1], [2, 3], [4]]
    assert C.ring_groups([100] * 3) == [[0], [1], [2]]
    assert C.ring_groups([]) == []


RING_MASKS = {
    "all": [1, 1, 1, 1, 1, 1, 1, 1], "weighted": [3, 1, 0, 2, 1, 5, 1, 1],
    "all_dropped": [0] * 8,
}


@pytest.mark.parametrize("weights", RING_MASKS)
@pytest.mark.parametrize("slots", [2, 4, 8])
def test_fed_mean_over_an_axis_is_fed_mean_and_the_same_bits_on_every_slot(
        slots, weights):
    """Leaves of every kind a bucket is made of: whole lanes (laid row on
    row), odd shapes and a scalar (raveled into one vector), two dtypes; a
    dropped station holds NaN."""
    if len(jax.devices()) < slots:
        pytest.skip(f"needs {slots} fake devices")
    from jax.sharding import Mesh, PartitionSpec as P

    w = np.asarray(RING_MASKS[weights], np.float32)
    rng = np.random.default_rng(7)
    groups = [
        {"a": rng.normal(size=(8, 64, 128)), "b": rng.normal(size=(8, 32, 128)),
         "odd": rng.normal(size=(8, 5, 3)), "loss": rng.normal(size=(8,))},
        [rng.normal(size=(8, 2, 16, 256)), rng.normal(size=(8, 7))],
    ]
    groups = jax.tree.map(lambda x: x.astype(np.float32), groups)
    groups[1][1] = groups[1][1].astype(jnp.bfloat16)
    for x in jax.tree.leaves(groups):
        x[w == 0] = np.nan
    total = w.sum()
    denom = np.float32(total if total > 0 else 1.0)
    mesh = Mesh(np.array(jax.devices()[:slots]), ("station",))
    ring = tuple(np.random.default_rng(1).permutation(slots).tolist())
    out = jax.jit(jax.shard_map(
        lambda g, w: jax.tree.map(
            lambda x: x[None], [C.fed_mean(
                C.OverAxis(group, "station", ring, denom), weights=w)
                for group in g]),
        mesh=mesh, in_specs=(P("station"), P("station")),
        out_specs=P("station"), check_vma=False))(groups, w)
    want = [C.fed_mean(g, weights=w) for g in groups]
    for got, expect in zip(jax.tree.leaves(out), jax.tree.leaves(want)):
        got = np.asarray(got.astype(jnp.float32))
        assert got.shape == (slots, *expect.shape)
        assert all(np.array_equal(got[0], other) for other in got[1:])
        # a bfloat16 leaf is summed in bfloat16, as `fed_mean` sums it: a
        # rounding a station, in another order (`_norm_weights`)
        coarse = expect.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            got[0], np.asarray(expect.astype(jnp.float32)),
            rtol=5e-2 if coarse else 1e-5, atol=4e-3 if coarse else 1e-6)


def test_ring_bytes_sent_counts_both_ways_and_the_padding():
    # [64, 128] f32 is one bucket of whole lanes: 64 rows pad to 2 * 4 * 8
    one = [((64, 128), jnp.float32)]
    assert C.ring_bytes_sent(one, 4) == 2 * 3 * 64 * 128 * 4 // 4
    # a scalar rides in a vector padded to 2 * 4 chunks of a whole tile
    assert C.ring_bytes_sent([((), jnp.float32)], 4) == 2 * 3 * 8 * 1024 * 4 // 4

"""Sharded server update: scattered collectives + FedAvg ZeRO-1 mode.

Covers the acceptance contract of the sharded-update PR:
- fed_mean_scattered + all-gather == fed_mean (fp32) on every station-axis
  size the 8-device fake pod can express (D = 1/2/4/8), including
  masked-out and all-dropped stations;
- FedAvg `shard_server_update=True` (fp32) matches the replicated path on
  params after 5 rounds with identical participation masks — for plain
  FedAvg *and* a stateful server optimizer (FedAdam, whose moments live
  sharded);
- bf16 on-wire deltas stay close to fp32 but are NOT claimed identical;
- `round()` keeps its inputs; `run_rounds` and `run_rounds_async` consume
  the state they are handed and return one that chains.
"""
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from vantage6_tpu.core.mesh import FederationMesh
from vantage6_tpu.fed import collectives as C
from vantage6_tpu.fed.fedavg import AsyncRoundSpec, FedAvg, FedAvgSpec
from vantage6_tpu.runtime.federation import Federation
from vantage6_tpu.runtime.profiling import ObservedFunction
from vantage6_tpu.workloads import fedavg_mnist as W

RNG = np.random.default_rng(7)


def _tree(s=8):
    """A deliberately awkward pytree: odd sizes, a scalar leaf, >1-D leaf —
    exercises flat-pack padding for every divisor D."""
    return {
        "w": jnp.asarray(RNG.normal(size=(s, 3, 5)).astype(np.float32)),
        "b": jnp.asarray(RNG.normal(size=(s, 7)).astype(np.float32)),
        "s": jnp.asarray(RNG.normal(size=(s,)).astype(np.float32)),
    }


def _assert_trees_close(a, b, atol=1e-5):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=atol)


@pytest.mark.parametrize("slots", [1, 2, 4, 8])
def test_scattered_mean_parity_all_mesh_sizes(slots):
    mesh = FederationMesh(8, devices=jax.devices()[:slots])
    assert mesh.station_axis_size == slots
    tree = mesh.shard_stacked(_tree())
    w = jnp.asarray([1, 2, 3, 4, 5, 6, 7, 8], jnp.float32)
    mask = jnp.asarray([1, 1, 0, 1, 1, 1, 0, 1], jnp.float32)
    ref = C.fed_mean(tree, weights=w, mask=mask)
    out = C.fed_mean_scattered_tree(mesh, tree, weights=w, mask=mask)
    _assert_trees_close(ref, out)
    # and under jit (the shape every round program uses)
    out_jit = jax.jit(
        lambda t: C.fed_mean_scattered_tree(mesh, t, weights=w, mask=mask)
    )(tree)
    _assert_trees_close(ref, out_jit)


@pytest.mark.parametrize("slots", [1, 4, 8])
def test_scattered_sum_parity(slots):
    mesh = FederationMesh(8, devices=jax.devices()[:slots])
    tree = mesh.shard_stacked(_tree())
    mask = jnp.asarray([1, 0, 1, 1, 1, 1, 1, 0], jnp.float32)
    ref = C.fed_sum(tree, mask=mask)
    flat = C.all_gather_stations(
        mesh, C.fed_sum_scattered(mesh, tree, mask=mask)
    )
    out = C.unflatten_like(jax.tree.map(lambda x: x[0], tree), flat)
    _assert_trees_close(ref, out)


def test_scattered_all_dropped_is_finite():
    mesh = FederationMesh(8)
    out = C.fed_mean_scattered_tree(
        mesh, mesh.shard_stacked(_tree()), mask=jnp.zeros(8)
    )
    for leaf in jax.tree.leaves(out):
        assert np.isfinite(np.asarray(leaf)).all()


def test_scattered_nan_isolation():
    """A masked-out station's inf/nan must not poison the scattered sum —
    the `where`-exclusion contract fed_mean has."""
    mesh = FederationMesh(8)
    tree = _tree()
    poisoned = dict(tree)
    poisoned["w"] = tree["w"].at[3].set(jnp.nan)
    mask = np.ones(8, np.float32)
    mask[3] = 0.0
    mask = jnp.asarray(mask)
    ref = C.fed_mean_scattered_tree(
        mesh, mesh.shard_stacked(tree), mask=mask
    )
    out = C.fed_mean_scattered_tree(
        mesh, mesh.shard_stacked(poisoned), mask=mask
    )
    _assert_trees_close(ref, out)


def test_flatten_unflatten_roundtrip():
    tree = jax.tree.map(lambda x: x[0], _tree())
    flat = C.flatten_tree(tree)
    assert flat.size == C.flat_size(tree)
    # padding beyond the true size must be ignored
    padded = jnp.pad(flat, (0, 5))
    _assert_trees_close(tree, C.unflatten_like(tree, padded), atol=0)


# ------------------------------------------------------------ engine parity
@pytest.fixture(scope="module")
def mesh():
    return FederationMesh(8)


@pytest.fixture(scope="module")
def fed_data(mesh):
    return W.make_federated_data(8, n_per_station=64, seed=3, mesh=mesh)


@pytest.mark.parametrize(
    "server_opt", [None, optax.adam(1e-2)], ids=["fedavg", "fedadam"]
)
def test_sharded_server_update_parity_5_rounds(
        mesh, fed_data, server_opt, fresh):
    """Acceptance: shard_server_update=True (fp32) matches replicated within
    atol=1e-5 on params after 5 rounds, identical participation masks."""
    sx, sy, counts = fed_data
    key = jax.random.key(0)
    p0 = W.init_params(jax.random.fold_in(key, 1))
    mask = np.ones(8, np.float32)
    mask[2] = 0.0
    mask = jnp.asarray(mask)
    kw = dict(local_steps=2, batch_size=16, server_optimizer=server_opt)
    e_rep = W.make_engine(mesh, **kw)
    e_shard = W.make_engine(mesh, shard_server_update=True, **kw)
    p_rep, _, l_rep, _ = e_rep.run_rounds(
        fresh(p0), sx, sy, counts, key, 5, mask=mask
    )
    p_shard, _, l_shard, _ = e_shard.run_rounds(
        fresh(p0), sx, sy, counts, key, 5, mask=mask
    )
    _assert_trees_close(p_rep, p_shard, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(l_rep), np.asarray(l_shard), atol=1e-5
    )


def test_sharded_opt_state_is_station_sharded(mesh):
    """ZeRO-1: FedAdam moments in sharded mode are flat [N_pad] vectors
    sharded over the station axis — 1/D per slot, not replicated."""
    eng = W.make_engine(
        mesh, shard_server_update=True, server_optimizer=optax.adam(1e-2)
    )
    params = W.init_params(jax.random.key(0))
    n_pad = C.padded_flat_size(
        C.flat_size(params), mesh.station_axis_size
    )
    flats = [
        leaf for leaf in jax.tree.leaves(eng.init(params))
        if getattr(leaf, "shape", None) == (n_pad,)
    ]
    assert len(flats) >= 2  # adam: mu and nu
    for leaf in flats:
        shards = leaf.addressable_shards
        assert len(shards) == mesh.station_axis_size
        assert all(
            s.data.shape == (n_pad // mesh.station_axis_size,)
            for s in shards
        )


def test_bf16_comm_close_to_fp32(mesh, fed_data, fresh):
    sx, sy, counts = fed_data
    key = jax.random.key(5)
    p0 = W.init_params(jax.random.fold_in(key, 1))
    kw = dict(local_steps=2, batch_size=16)
    p_rep, _, _, _ = W.make_engine(mesh, **kw).run_rounds(
        fresh(p0), sx, sy, counts, key, 5
    )
    p_bf, _, _, _ = W.make_engine(
        mesh, shard_server_update=True, comm_dtype=jnp.bfloat16, **kw
    ).run_rounds(fresh(p0), sx, sy, counts, key, 5)
    # bf16 wire keeps ~2-3 decimal digits; the drift bound documents the
    # accuracy caveat rather than pretending exactness
    for a, b in zip(jax.tree.leaves(p_rep), jax.tree.leaves(p_bf)):
        assert float(jnp.max(jnp.abs(a - b))) < 5e-2


# ----------------------------------------------------- device-engine wiring
def test_device_logistic_fit_agg_modes_agree():
    """The device-engine workload exposes the same aggregation modes; on a
    single-process mesh the three must agree (scattered exactly, bf16
    within wire precision)."""
    import pandas as pd

    rng = np.random.default_rng(0)
    x0 = rng.normal(0, 1, 48)
    df = pd.DataFrame({
        "x0": x0,
        "x1": rng.normal(0, 1, 48),
        "label": (x0 > 0).astype(float),
    })
    from vantage6_tpu.workloads.device_engine import device_logistic_fit

    fit = device_logistic_fit.__wrapped__  # undecorated: df passed directly
    kw = dict(feature_columns=["x0", "x1"], label_column="label",
              rounds=2, local_steps=2, batch_rows=64)
    rep = fit(df, **kw)
    scat = fit(df, agg_mode="scattered", **kw)
    bf = fit(df, agg_mode="scattered_bf16", **kw)
    np.testing.assert_allclose(rep["weights"], scat["weights"], atol=1e-5)
    np.testing.assert_allclose(rep["weights"], bf["weights"], atol=5e-2)
    assert scat["agg_mode"] == "scattered"
    with pytest.raises(ValueError, match="agg_mode"):
        fit(df, agg_mode="bogus", **kw)


# ---------------------------------------------------------------- donation
def test_round_never_donates(mesh, fed_data):
    """Regression: callers legitimately reuse params across round() calls
    (ablations from one init) — round() must never consume its inputs."""
    sx, sy, counts = fed_data
    key = jax.random.key(11)
    p0 = W.init_params(key)
    eng = W.make_engine(mesh, local_steps=1, batch_size=8)
    opt = eng.init(p0)
    out1 = eng.round(p0, opt, sx, sy, counts, key)
    out2 = eng.round(p0, opt, sx, sy, counts, key)  # same buffers again
    _assert_trees_close(out1[0], out2[0], atol=0)


def test_run_rounds_default_donates_and_returns_fresh(mesh, fed_data):
    """The fast path may consume params/opt_state/key (backend permitting);
    the RETURNED carry must always be valid for chaining."""
    sx, sy, counts = fed_data
    key = jax.random.key(17)
    p0 = W.init_params(key)
    eng = W.make_engine(mesh, local_steps=1, batch_size=8)
    p1, o1, _, _ = eng.run_rounds(p0, sx, sy, counts, jax.random.key(1), 2)
    p2, _, losses, _ = eng.run_rounds(
        p1, sx, sy, counts, jax.random.key(2), 2, opt_state=o1
    )
    assert np.isfinite(np.asarray(losses)).all()
    for leaf in jax.tree.leaves(p2):
        assert np.isfinite(np.asarray(leaf)).all()


def _alive(tree) -> list[bool]:
    return [not leaf.is_deleted() for leaf in jax.tree.leaves(tree)]


@pytest.mark.parametrize("call", ["run_rounds", "run_rounds_async"])
def test_a_fused_dispatch_consumes_the_state_it_is_handed(
        mesh, fed_data, call):
    """The one donation rule: the `params` and `opt_state` (and the
    `staleness`) handed in are gone when the dispatch returns, the data is
    the caller's still, and what comes back chains into the next dispatch,
    which consumes it in turn. Server Adam, so the state has moments."""
    sx, sy, counts = fed_data
    eng = W.make_engine(
        mesh, local_steps=1, batch_size=8, server_optimizer=optax.adam(1e-2)
    )

    def dispatch(state, i):
        params, opt_state, *stale = state
        if call == "run_rounds":
            out = eng.run_rounds(
                params, sx, sy, counts, jax.random.key(i), 2,
                opt_state=opt_state,
            )
            return out[:2], out[2]
        out = eng.run_rounds_async(
            params, sx, sy, counts, jax.random.key(i), 2,
            jnp.ones((2, 8)).at[0, 3].set(0.0), AsyncRoundSpec(quorum=7),
            staleness=stale[0], opt_state=opt_state,
        )
        return out[:3], out[3]

    # placed as a dispatch returns its state: the engine's entry commits
    # an unplaced state to the mesh, and it is that one it consumes
    p0 = mesh.replicate(W.init_params(jax.random.key(17)))
    state = (p0, eng.init(p0))
    if call == "run_rounds_async":
        state += (mesh.replicate(jnp.zeros(8, jnp.float32)),)
    for i in range(2):
        handed, (state, losses) = state, dispatch(state, i)
        assert not any(_alive(handed))
        assert all(_alive((sx, sy, state, losses)))
        assert np.isfinite(np.asarray(losses)).all()


@pytest.mark.parametrize("has", [
    FedAvg.run_rounds, FedAvg.run_rounds_async, FedAvgSpec,
    Federation.run_fused_rounds, W.make_engine], ids=lambda f: f.__qualname__)
def test_no_option_selects_a_program(has):
    """`donate=`, `unroll=` and `local_unroll` went with the twin
    executables and loop bodies they selected (a dataclass's signature is
    its fields)."""
    assert not {"donate", "unroll", "local_unroll"} & set(
        inspect.signature(has).parameters)


def test_an_engine_registers_three_programs(mesh):
    eng = W.make_engine(mesh)
    programs = {k: v.name for k, v in vars(eng).items()
                if isinstance(v, ObservedFunction)}
    assert programs == {
        "_round": "fedavg.round", "_run": "fedavg.run_rounds",
        "_run_async": "fedavg.run_rounds_async"}

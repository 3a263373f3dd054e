"""Fused K-round device program == K sequential round() calls.

The tentpole contract of the fused fast path: `run_rounds` executes the
whole K-round FedAvg loop as ONE device program (lax.scan over rounds,
zero host round-trips) and must be fp32-IDENTICAL to K sequential
`round()` dispatches over the same split key stream — dense, compressed
(error-feedback carry), scattered ZeRO-1 and masked/async variants alike.
Bitwise, not allclose: the fused body is the very `_one_round` the
per-round path jits, so ANY drift is a real seam leak (mask plumbing, EF
carry, staleness bookkeeping), never fp noise.
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from vantage6_tpu.core.mesh import FederationMesh
from vantage6_tpu.fed.compression import CompressorSpec
from vantage6_tpu.fed.fedavg import AsyncRoundSpec
from vantage6_tpu.workloads import fedavg_mnist as W

S = 4  # stations
K = 4  # fused rounds per dispatch


@pytest.fixture(scope="module")
def mesh():
    return FederationMesh(S)


@pytest.fixture(scope="module")
def fed_data(mesh):
    return W.make_federated_data(S, n_per_station=32, seed=3, mesh=mesh)


@pytest.fixture(scope="module")
def init(fed_data):
    key = jax.random.key(42)
    return W.init_params(jax.random.fold_in(key, 1)), jax.random.fold_in(
        key, 2
    )


def make(mesh, **kw):
    kw.setdefault("local_steps", 2)
    kw.setdefault("batch_size", 8)
    return W.make_engine(mesh, **kw)


def sequential(engine, params, sx, sy, counts, key, n_rounds, mask=None,
               opt_state=None):
    """The pre-fused driver: K separate round() dispatches over the same
    key stream run_rounds splits internally — the identity oracle."""
    if opt_state is None:
        opt_state = engine.init(params)
    keys = jax.random.split(key, n_rounds)
    losses, stats_seq = [], []
    m = None if mask is None else jnp.asarray(mask, jnp.float32)
    for i in range(n_rounds):
        mi = None if m is None else (m if m.ndim == 1 else m[i])
        params, opt_state, loss, stats = engine.round(
            params, opt_state, sx, sy, counts, keys[i], mask=mi
        )
        losses.append(loss)
        stats_seq.append(stats)
    stacked = (
        jax.tree.map(lambda *a: jnp.stack(a), *stats_seq)
        if stats_seq and stats_seq[0] else {}
    )
    return params, opt_state, jnp.stack(losses), stacked


def assert_trees_identical(a, b, what):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), what
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=what
        )


def check_identity(engine, fed_data, init, fresh, mask=None, n_rounds=K):
    sx, sy, counts = fed_data
    params, key = init
    fp, fo, fl, fs = engine.run_rounds(
        fresh(params), sx, sy, counts, key, n_rounds, mask=mask
    )
    sp, so, sl, ss = sequential(
        engine, params, sx, sy, counts, key, n_rounds, mask=mask
    )
    assert_trees_identical(fp, sp, "params drifted fused vs sequential")
    assert_trees_identical(fo, so, "opt_state drifted fused vs sequential")
    np.testing.assert_array_equal(np.asarray(fl), np.asarray(sl))
    assert_trees_identical(fs, ss, "learning stats drifted")
    return fl


# ------------------------------------------------------------- identities
def test_dense_identity(mesh, fed_data, init, fresh):
    check_identity(make(mesh), fed_data, init, fresh)


def test_compressed_ef_identity(mesh, fed_data, init, fresh):
    """Top-k + int8 compression: the per-station error-feedback carry
    must ride the scan exactly as it rides sequential opt_states."""
    eng = make(
        mesh, compressor=CompressorSpec(topk_ratio=0.25, int8=True, chunk=8)
    )
    check_identity(eng, fed_data, init, fresh)


def test_scattered_zero1_identity(mesh, fed_data, init, fresh):
    """ZeRO-1 sharded server update (FedAdam moments scattered over
    stations) composes with the fused scan unchanged."""
    eng = make(
        mesh, shard_server_update=True, server_optimizer=optax.adam(1e-2)
    )
    check_identity(eng, fed_data, init, fresh)


def test_masked_identity_single_roster(mesh, fed_data, init, fresh):
    mask = np.ones(S, np.float32)
    mask[1] = 0.0
    check_identity(make(mesh), fed_data, init, fresh, mask=jnp.asarray(mask))


def test_masked_identity_per_round_roster(mesh, fed_data, init, fresh):
    """A [K, S] mask gives each fused round its own roster via the scan
    xs — and must equal a sequential driver passing row i to round i."""
    masks = np.ones((K, S), np.float32)
    masks[0, 2] = 0.0
    masks[2, 0] = 0.0
    masks[3, 3] = 0.0
    check_identity(make(mesh), fed_data, init, fresh, mask=jnp.asarray(masks))


def test_per_round_mask_shape_is_validated(mesh, fed_data, init):
    sx, sy, counts = fed_data
    params, key = init
    bad = jnp.ones((K + 1, S), jnp.float32)
    with pytest.raises(ValueError, match="rounds"):
        make(mesh).run_rounds(
            params, sx, sy, counts, key, K, mask=bad
        )


def test_async_identity(mesh, fed_data, init, fresh):
    """Fused buffered-async (staleness riding the scan carry) equals K
    sequential async_round() calls with host-side FedBuff bookkeeping."""
    eng = make(mesh)
    sx, sy, counts = fed_data
    params, key = init
    spec = AsyncRoundSpec(quorum=3, staleness_discount=0.5)
    accepts = np.ones((K, S), np.float32)
    accepts[0, 3] = 0.0  # station 3 misses round 0 -> discounted later
    accepts[1, 3] = 0.0
    accepts[2, 0] = 0.0
    accepts = jnp.asarray(accepts)

    fp, fo, fstale, fl, fs = eng.run_rounds_async(
        fresh(params), sx, sy, counts, key, K, accepts, spec
    )

    sp, so = params, eng.init(params)
    stale = jnp.zeros(S, jnp.float32)
    keys = jax.random.split(key, K)
    losses, stats_seq = [], []
    for i in range(K):
        sp, so, loss, stats = eng.async_round(
            sp, so, sx, sy, counts, keys[i], accepts[i], stale, spec
        )
        stale = jnp.where(accepts[i] != 0, 0.0, stale + 1.0)
        losses.append(loss)
        stats_seq.append(stats)

    assert_trees_identical(fp, sp, "async params drifted")
    assert_trees_identical(fo, so, "async opt_state drifted")
    np.testing.assert_array_equal(np.asarray(fstale), np.asarray(stale))
    np.testing.assert_array_equal(
        np.asarray(fl), np.asarray(jnp.stack(losses))
    )
    assert_trees_identical(
        fs, jax.tree.map(lambda *a: jnp.stack(a), *stats_seq),
        "async learning stats drifted",
    )
    # the seeded absences actually aged: station 3 was discounted, so its
    # trajectory differs from an all-accept run
    assert float(fstale[3]) == 0.0  # re-accepted in rounds 2..3


# ------------------------------------------------- observatory contract
def test_k_sweep_is_static_sweep_not_retrace(mesh, fed_data, init, fresh):
    """Compiling the fused program at several K values (warmup K=1,
    production K, tail-flush) is a declared static sweep — it must not
    count as a retrace or feed recompile_storm."""
    eng = make(mesh)
    sx, sy, counts = fed_data
    params, key = init
    for k in (1, 2, 3):
        eng.run_rounds(fresh(params), sx, sy, counts, key, k)
    assert eng._run.retraces == 0
    assert eng._run.static_sweeps >= 2


def test_check_collect_fused_audit_clean():
    import sys

    sys.path.insert(0, ".")
    from tools.check_collect import check_fused_program

    assert check_fused_program() == []

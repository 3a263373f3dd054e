"""FedAvg engine + flagship workload on the fake pod."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vantage6_tpu.core.mesh import FederationMesh
from vantage6_tpu.utils.datasets import synthetic_image_classes
from vantage6_tpu.workloads import fedavg_mnist as W


@pytest.fixture(scope="module")
def mesh():
    return FederationMesh(8)


@pytest.fixture(scope="module")
def small_engine(mesh):
    return W.make_engine(mesh, local_steps=4, batch_size=16, local_lr=0.1)


@pytest.fixture(scope="module")
def fed_data(mesh):
    return W.make_federated_data(8, n_per_station=64, seed=3, mesh=mesh)


def test_loss_decreases_and_learns(mesh, small_engine, fed_data):
    sx, sy, counts = fed_data
    key = jax.random.key(0)
    params = W.init_params(jax.random.fold_in(key, 1))
    params, _, losses, _stats = small_engine.run_rounds(
        params, sx, sy, counts, jax.random.fold_in(key, 2), 10
    )
    losses = np.asarray(losses)
    assert losses[-1] < losses[0] * 0.8, losses
    # generalization: fresh samples from the same generator
    ex, ey = synthetic_image_classes(256, seed=999)
    acc = W.evaluate(params, ex, ey)
    assert acc > 0.5, f"accuracy {acc} not above chance"


def test_run_rounds_deterministic(mesh, small_engine, fed_data, fresh):
    sx, sy, counts = fed_data
    key = jax.random.key(7)
    p0 = W.init_params(jax.random.fold_in(key, 1))
    # p0 is stepped from twice, and run_rounds consumes what it is handed
    r1 = small_engine.run_rounds(fresh(p0), sx, sy, counts, key, 3)[2]
    r2 = small_engine.run_rounds(fresh(p0), sx, sy, counts, key, 3)[2]
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))


def test_participation_mask_drops_station(mesh, small_engine, fed_data):
    """Masked-out stations must not influence the aggregate: compare a run
    where station k is masked vs one where station k's DATA is replaced by
    garbage and also masked — identical results prove exclusion."""
    sx, sy, counts = fed_data
    key = jax.random.key(11)
    params = W.init_params(key)
    mask = np.ones(8, np.float32)
    mask[3] = 0.0
    out1 = small_engine.round(params, small_engine.init(params), sx,
                              sy, counts, key, mask=jax.numpy.asarray(mask))
    garbage = np.asarray(sx).copy()
    garbage[3] = 1e6
    g_sx = mesh.shard_stacked(garbage)
    out2 = small_engine.round(params, small_engine.init(params), g_sx, sy,
                              counts, key, mask=jax.numpy.asarray(mask))
    np.testing.assert_allclose(
        np.asarray(jax.tree.leaves(out1[0])[0]),
        np.asarray(jax.tree.leaves(out2[0])[0]),
        rtol=1e-5,
    )


def test_reference_shaped_central_fedavg():
    """The AlgorithmClient-shaped FedAvg loop (subtask per round) learns."""
    from vantage6_tpu.algorithm import MockAlgorithmClient

    n, per = 4, 48
    x, y = synthetic_image_classes(n * per, seed=5)
    datasets = []
    for i in range(n):
        sl = slice(i * per, (i + 1) * per)
        datasets.append([{"database": {
            "x": x[sl], "y": y[sl],
            "count": np.float32(per), "sid": np.int32(i),
        }}])
    client = MockAlgorithmClient(datasets=datasets, module=W)
    task = client.task.create(
        input_={"method": "central_fedavg",
                "kwargs": {"n_rounds": 3, "local_steps": 2, "batch_size": 16}},
        organizations=[0],
    )
    (res,) = client.result.get(task["id"])
    assert res["losses"][-1] < res["losses"][0]


# ----------------------------------------------- one row gather per local step
# (ISSUE 27) A local step fetches its minibatch with ONE gather over a table
# whose rows carry their label, built once per dispatch; where x's and y's
# elements differ in width the two gathers stay. `gather_path` reads shapes
# and dtypes; a test steers it on the engine it built, the program has no
# option for it.
def _nll(p, x, y, w):
    z = x @ p["w"] + p["b"]
    return jnp.sum(w * (jnp.logaddexp(0.0, z) - y * z)) / jnp.sum(w)


def _logreg(mesh, y_dtype=jnp.float32, counts=None, pad_value=None):
    """(engine, params, x, y, counts): 8 stations x 16 rows x 5 features;
    rows at or beyond a station's count hold ``pad_value``."""
    from vantage6_tpu.fed.fedavg import FedAvg, FedAvgSpec

    engine = FedAvg(mesh, FedAvgSpec(loss_fn=_nll, local_steps=3,
                                     batch_size=8, local_lr=0.1))
    rng = np.random.default_rng(27)
    x = rng.normal(size=(8, 16, 5)).astype(np.float32)
    y = rng.integers(0, 2, (8, 16)).astype(np.float32)
    counts = np.full(8, 16.0, np.float32) if counts is None else counts
    if pad_value is not None:
        padded = np.arange(16)[None, :] >= counts[:, None]
        x[padded] = pad_value
        y[padded] = pad_value
    params = {"w": jnp.full((5,), 0.01), "b": jnp.zeros(())}
    return (engine, params, mesh.shard_stacked(jnp.asarray(x)),
            mesh.shard_stacked(jnp.asarray(y, y_dtype)), jnp.asarray(counts))


def _cnn(mesh, fed_data):
    sx, sy, counts = fed_data
    assert sy.dtype == jnp.int32 and sx.dtype == jnp.float32
    engine = W.make_engine(mesh, local_steps=2, batch_size=16, local_lr=0.1)
    return engine, W.init_params(jax.random.key(4)), sx, sy, counts


def _three_rounds(engine, call, params, x, y, counts):
    """(params, losses, stats) after 3 rounds from ``params``, by one fused
    dispatch or by three `round()` calls over the same keys."""
    key = jax.random.key(9)
    if call == "run_rounds":
        p, _, losses, stats = engine.run_rounds(params, x, y, counts, key, 3)
        return jax.device_get((p, losses, stats))
    p, state, out = params, engine.init(params), []
    for k in jax.random.split(key, 3):
        p, state, loss, stats = engine.round(p, state, x, y, counts, k)
        out.append((loss, stats))
    return jax.device_get((p, [o[0] for o in out], [o[1] for o in out]))


def _same_bits(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb) and la
    for u, v in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


def _separate(engine, monkeypatch):
    monkeypatch.setattr(engine, "gather_path", lambda x, y: "separate")
    return engine


@pytest.mark.parametrize("call", ["run_rounds", "round"])
@pytest.mark.parametrize("model", ["logreg-f32-labels", "cnn-int32-labels"])
def test_packed_gather_is_bit_for_bit_the_two_gathers(
        mesh, fed_data, monkeypatch, model, call):
    def build():
        return (_logreg(mesh) if model.startswith("logreg")
                else _cnn(mesh, fed_data))

    engine, *args = build()
    assert engine.gather_path(args[1], args[2]) == "packed"
    packed = _three_rounds(engine, call, *args)
    engine, *args = build()
    separate = _three_rounds(_separate(engine, monkeypatch), call, *args)
    _same_bits(packed, separate)
    assert np.all(np.isfinite(np.asarray(packed[1])))


@pytest.mark.parametrize("y_dtype", [jnp.int8, jnp.float16, jnp.bool_])
def test_labels_of_another_width_keep_the_two_gathers(mesh, y_dtype):
    """int8 / float16 / bool labels beside float32 features cannot ride in
    a float32 column: the separate path, and the result it gives today (the
    loss promotes a 0/1 label of any type to the same float32)."""
    engine, params, x, y, counts = _logreg(mesh, y_dtype=y_dtype)
    assert engine.gather_path(x, y) == "separate"
    lowered = _lower_run(engine, params, x, y, counts)
    assert lowered.as_text().count('"stablehlo.gather"') == 2
    assert "tensor<8x16x6x" not in lowered.as_text()  # no table of 5 + 1
    narrow = _three_rounds(engine, "run_rounds", params, x, y, counts)
    engine, params, x, y, counts = _logreg(mesh)
    _same_bits(narrow, _three_rounds(engine, "run_rounds", params, x, y,
                                     counts))


def _lower_run(engine, params, x, y, counts, n_rounds=3):
    p, state, counts, mask, key = engine._place(
        params, engine.init(params), counts, jnp.ones(8), jax.random.key(1))
    return engine._run.lower(p, state, x, y, counts, mask, key,
                             n_rounds=n_rounds)


@pytest.mark.parametrize("n_rounds", [1, 3, 5])
def test_one_gather_in_the_local_step_and_the_pack_outside_the_rounds(
        mesh, n_rounds):
    engine, params, x, y, counts = _logreg(mesh)
    text = _lower_run(engine, params, x, y, counts, n_rounds).as_text()
    # one gather in the whole program: the local step's, over rows of 5 + 1
    assert text.count('"stablehlo.gather"') == 1
    assert "tensor<1x16x6xui32>" in text.split('"stablehlo.gather"')[1]
    # the pack: once, in the entry function, before the loop over rounds
    main = text.split("func.func private")[0]
    join = "(tensor<8x16x5xui32>, tensor<8x16x1xui32>) -> tensor<8x16x6xui32>"
    assert text.count(join) == 1
    assert main.index(join) < main.index("stablehlo.while")


# taken on the parent commit (eddfcac) with PYTHONPATH=<its checkout>: sha256
# of `engine._run_donating.lower(...).as_text()`, the program the engine cell
# ran there, for the engine the benchmark's own entry builds at that cell's
# tiny sizes and its K (the lines below with `_run_donating` for `_run`)
PARENT_RUN_DONATING = (
    "7f6baa01a279d334a8d6f15f10127bb199589c893cb997d13e2609a120a9c2ca")


def test_run_rounds_lowers_to_the_parents_donating_program():
    """With the twins gone `run_rounds` dispatches `_run`, and `_run` is
    the parent's `_run_donating` to the letter: same module name, same
    text, same donated arguments, so the same entry of a compile cache."""
    import hashlib
    import json
    from pathlib import Path

    from perfbench import cells

    cell = cells.load_cell("logreg32.engine-1chip")
    tiny = Path(__file__).parent / "benchmark" / "data" / "tiny"
    for held, name in ((cell.config, "config.logreg-tabular-32st"),
                       (cell.traffic, "traffic.engine-1chip")):
        held.update(json.loads((tiny / f"{name}.json").read_text())["sizes"])
    key = jax.random.key(0)
    program = cell.entry_module().build(
        cell.config, cell.traffic,
        lambda: cell.reference_module().make_inputs(
            cell.config, cell.traffic, key),
        jax.devices()[:1])
    engine = program.engine
    p, state, counts, mask, key = engine._place(
        program.params, program.opt_state, program.counts, program.mask, key)
    text = engine._run.lower(
        p, state, program.x, program.y, counts, mask, key,
        n_rounds=cell.traffic["rounds_per_dispatch"]).as_text()
    assert "jit__run_impl" in text and text.count("tf.aliasing_output") == 2
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_RUN_DONATING


@pytest.mark.parametrize("path", ["packed", "separate"])
def test_no_padded_row_is_ever_drawn(mesh, monkeypatch, path):
    """Ragged stations: rows at or beyond a station's count are padding.
    They hold NaN here, so one draw of one of them would poison the round;
    and the result does not depend on what they hold."""
    counts = np.array([16, 5, 1, 9, 16, 2, 12, 7], np.float32)
    results = []
    for pad_value in (np.nan, 1e6):
        engine, *args = _logreg(mesh, counts=counts, pad_value=pad_value)
        if path == "separate":
            _separate(engine, monkeypatch)
        results.append(_three_rounds(engine, "run_rounds", *args))
    for leaf in jax.tree.leaves(results[0]):
        assert np.all(np.isfinite(leaf))
    _same_bits(*results)


def test_the_packed_table_must_fit_beside_the_table(mesh, monkeypatch):
    """Where the backend reports its memory, table and packed copy together
    may take half of a device's; the CPU reports none and always packs."""
    engine, _, x, y, _ = _logreg(mesh)
    assert engine._bytes_limit is None
    per_device = 4 * (x.size + y.size) // mesh.station_axis_size
    monkeypatch.setattr(engine, "_bytes_limit", 4 * per_device)
    assert engine.gather_path(x, y) == "packed"
    monkeypatch.setattr(engine, "_bytes_limit", 4 * per_device - 2)
    assert engine.gather_path(x, y) == "separate"


# ------------------------------------------ the streamed gather (ISSUE 40)
# Where a step's batch is a large share of its table, a TPU program streams
# the packed table through VMEM once a step (`ops.stream_gather`) and keeps
# the rows its sorted indices name. The rule reads shapes and the devices'
# platform; on the CPU it never streams, so every program above lowers to
# the parent's text. A test steers the platform, or the path, on the engine
# it built.
ENGINE_CELL = (jax.ShapeDtypeStruct((32, 262144, 100), jnp.float32),
               jax.ShapeDtypeStruct((32, 262144), jnp.float32))


@pytest.mark.parametrize("platform,batch_size,y_dtype,path", [
    ("tpu", 32768, jnp.float32, "streamed"),   # the engine cell
    ("tpu", 16384, jnp.float32, "streamed"),   # an eighth: over break-even
    ("tpu", 4096, jnp.float32, "packed"),      # a small batch, a large table
    ("tpu", 32768, jnp.int8, "separate"),      # no pack at all
    ("cpu", 32768, jnp.float32, "packed"),     # the kernel is not compiled
])
def test_the_cost_rule_streams_where_the_table_moves_faster_than_indices(
        mesh, monkeypatch, platform, batch_size, y_dtype, path):
    from vantage6_tpu.fed import fedavg as F

    engine = F.FedAvg(mesh, F.FedAvgSpec(loss_fn=_nll, batch_size=batch_size))
    assert engine._platform == "cpu"
    monkeypatch.setattr(engine, "_platform", platform)
    x, y = ENGINE_CELL
    y = jax.ShapeDtypeStruct(y.shape, y_dtype)
    assert engine.gather_path(x, y) == path
    # the break-even: 262,144 rows of 128 words at 819 bytes a ns against
    # 10.5 ns an index, about 15,600 indices
    stream_ns = 262144 * 512 / F.HBM_BYTES_PER_NS
    assert (batch_size * F.PER_INDEX_NS > stream_ns) == (batch_size > 15604)
    attrs = engine._gather_attrs(x, y)
    assert attrs == ({"gather": "streamed", "gather_block_rows": 16384,
                      "gather_blocks": 16} if path == "streamed"
                     else {"gather": path})


@pytest.mark.parametrize("call", ["run_rounds", "round"])
def test_streamed_rows_train_as_the_packed_rows_do(mesh, monkeypatch, call):
    """Steered to the streamed path (its kernel interpreted on the CPU),
    three rounds give the packed path's losses and parameters to the order
    of a float32 sum: the same rows, fetched in table order."""
    engine, *args = _logreg(mesh)
    assert engine.gather_path(args[1], args[2]) == "packed"
    packed = _three_rounds(engine, call, *args)
    engine, *args = _logreg(mesh)
    monkeypatch.setattr(engine, "gather_path", lambda x, y: "streamed")
    streamed = _three_rounds(engine, call, *args)
    la, lb = jax.tree.leaves(packed), jax.tree.leaves(streamed)
    assert len(la) == len(lb) and la
    for u, v in zip(la, lb):
        np.testing.assert_allclose(np.asarray(v), np.asarray(u),
                                   rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("model", ["logreg-f32-labels", "cnn-int32-labels"])
def test_the_streamed_take_is_the_packed_take_of_the_sorted_indices(
        mesh, fed_data, monkeypatch, model):
    """What `loss_fn` receives, bit for bit: a station's streamed rows and
    labels are the packed path's for the same indices sorted (the CNN
    computes in bfloat16, where another order of a sum is another loss)."""
    engine, _, x, y, counts = (_logreg(mesh) if model.startswith("logreg")
                               else _cnn(mesh, fed_data))
    (packed,), take_packed = engine._minibatch_source(x, y)
    monkeypatch.setattr(engine, "gather_path", lambda x, y: "streamed")
    (streamed,), take_streamed = engine._minibatch_source(x, y)
    assert streamed.shape[-1] % 128 == 0 and streamed.shape[:2] == packed.shape[:2]
    rng = np.random.default_rng(40)
    for s in range(x.shape[0]):
        idx = jnp.asarray(rng.integers(0, int(counts[s]), 16), jnp.int32)
        _same_bits(take_streamed(streamed[s], idx),
                   take_packed(packed[s], jnp.sort(idx)))

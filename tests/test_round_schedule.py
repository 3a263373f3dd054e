"""Where the compiler lays the cross-station mean of a round on four chips.

`FedTransformer._round` is compiled here for a described TPU v5e 2 x 2 (no
chip, nothing runs) at GPT-2 medium's widths, through the engine's own
``_round.lower(...).compile()``, and the compiled program's text is read:
every collective over the station axis has to be asynchronous, and the steps
of the groups that cross from inside the backward pass have to lie INSIDE it.
The compiler's scheduler places every operation as late as it may and would
leave all of them to the end of the backward pass (PERF.md section 6, PR 36);
what stops it is `collectives.RingExchange`, and this is what guards that.

The file holds the repository's other compiles for the described chip
too (the attention kernels, the streamed gather and the sparse
attention's indexer scores), so that one worker of a test run loads the
TPU's library.

The topology is described inside a fixture, never while a module is
imported: only one process may hold the TPU's library, and every worker of
the test run imports every test file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from vantage6_tpu.core.mesh import STATION_AXIS
from vantage6_tpu.workloads import fed_transformer as FT

# six layers of 48 MiB are two groups at the shipped `RING_GROUP_BYTES`
N_LAYERS, N_STATIONS, N_GROUPS = 6, 4, 2
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or another process has it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def uncached():
    """A compile for a described chip is written to jax's persistent cache
    and cannot be read back without a chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(devices, n_layers=N_LAYERS):
    """`_round` at GPT-2 medium's widths, 4 stations x [2, 1024] tokens,
    from shapes: the engine, its state's shapes, the entry computation's
    operations in the order the chip runs them."""
    cfg = FT.TransformerConfig(
        vocab=50257, d_model=1024, n_heads=16, n_layers=n_layers,
        max_len=1024, dtype=jnp.bfloat16, attention="recompute")
    engine = FT.make_engine(N_STATIONS, 1, cfg, devices=devices)
    everywhere = NamedSharding(engine.mesh, P())

    def shapes(tree, sharding=everywhere):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    params = jax.eval_shape(lambda: FT.init_params(jax.random.key(0), cfg))
    opt_state = jax.eval_shape(lambda: engine.optimizer.init(params))
    tokens = jax.ShapeDtypeStruct(
        (N_STATIONS, 2, 1024), jnp.int32, sharding=NamedSharding(
            engine.mesh, P(STATION_AXIS, None, FT.SEQ_AXIS)))
    mask = jax.ShapeDtypeStruct((N_STATIONS,), jnp.float32)
    text = engine._round.lower(
        engine, shapes(params), shapes(opt_state), tokens, shapes(mask),
    ).compile().as_text()
    entry = text[text.index("ENTRY"):]
    return engine, params, [
        line.strip() for line in entry.splitlines() if " = " in line]


def _kind(line: str) -> str | None:
    """The collective an operation is, with its ``-start`` / ``-done``."""
    found = re.search(r"[\])}] ((?:%s)(?:-start|-done)?)\(" % "|".join(
        COLLECTIVES), line)
    return found.group(1) if found else None


@pytest.fixture(scope="module")
def four_chips(topo, uncached):
    """As shipped: nothing patched. Three layers make a group, so the six
    are two and the lower one waits for the upper."""
    return _compile(list(topo.devices))


def test_every_collective_over_the_stations_is_asynchronous(four_chips):
    """But for the stations' four losses, which `fed_mean` takes as it
    always did: one all-reduce of a scalar."""
    _, _, ops = four_chips
    kinds = [(kind, line) for kind, line in zip(map(_kind, ops), ops) if kind]
    assert kinds, "four stations on four chips and nothing crosses them?"
    losses = [line for kind, line in kinds if kind == "all-reduce"]
    assert len(losses) <= 1 and all(" = f32[]" in line for line in losses)
    ring = [kind for kind, _ in kinds if kind != "all-reduce"]
    assert set(ring) == {"collective-permute-start", "collective-permute-done"}
    assert (ring.count("collective-permute-start")
            == ring.count("collective-permute-done"))


def test_the_ring_follows_the_chips_links(four_chips, topo):
    """On the 2 x 2 the order by index crosses the diagonal twice; every
    pair of the compiled permutes has to join chips one link apart."""
    _, _, ops = four_chips
    coords = {dev.id: tuple(dev.coords) for dev in topo.devices}
    pairs = set()
    for line in ops:
        if _kind(line) == "collective-permute-start":
            found = re.search(r"source_target_pairs=\{(.*?)\}\}", line)
            pairs |= {tuple(map(int, pair.split(",")))
                      for pair in found.group(1).strip("{}").split("},{")}
    assert len(pairs) == 2 * N_STATIONS  # both ways round
    for a, b in pairs:
        assert sum(abs(x - y) for x, y in zip(coords[a], coords[b])) == 1


def test_the_groups_steps_lie_inside_the_backward_pass(four_chips):
    """At the shipped `RING_GROUP_BYTES` three of these layers are a group
    (the sharded cell's 24 are 8); a group's mean is 3 buckets x 2 ways x
    2 (d - 1) steps. The stream's cotangent waits for the group above to
    have come round, so every group but the lowest has before the backward
    pass ends, and operations of the backward pass lie between the first
    start and the last of those ends."""
    engine, params, ops = four_chips
    assert FT._layer_groups(params["layers"]) == [[0, 1, 2], [3, 4, 5]]
    said = engine.aggregation(params)
    assert said["aggregate_overlap"] == "ring"
    assert said["aggregate_groups"] == N_GROUPS
    steps = 4 * (N_STATIONS - 1)  # two ways, a reduce-scatter and an all-gather
    starts = [i for i, line in enumerate(ops)
              if _kind(line) == "collective-permute-start"]
    dones = [i for i, line in enumerate(ops)
             if _kind(line) == "collective-permute-done"]
    # the layers' three buckets, then the embeddings' one
    assert len(starts) == steps * (3 * N_GROUPS + 1)
    backward = [i for i, line in enumerate(ops)
                if "transpose(jvp" in line and " fusion(" in line
                and "aggregate" not in line]
    held = 3 * steps * (N_GROUPS - 1)
    inside = [i for i in dones if i < backward[-1]]
    assert len(inside) >= held
    # the three layers below the upper group run beside its steps
    first, last = min(starts), inside[held - 1]
    assert sum(first < i < last for i in backward) >= 3
    # what one chip sends: each group twice round less a chunk, padded
    sent = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    sent = 2 * (N_STATIONS - 1) * sent // N_STATIONS
    assert sent <= said["aggregate_bytes"] < 1.001 * sent


def test_on_one_slot_nothing_crosses(topo, uncached):
    engine, params, ops = _compile(list(topo.devices)[:1], n_layers=2)
    assert not any(_kind(line) for line in ops)  # not even the losses' 
    assert engine.aggregation(params) == {
        "aggregate_overlap": "none", "aggregate_groups": 0,
        "aggregate_bytes": 0}


def test_heads_of_64_keep_the_xla_walk_on_the_chip(four_chips):
    """Compiled for the chip, GPT-2 medium's heads are no whole lane tile:
    the rule keeps the XLA walk (on four chips the kernels lost 2.5% of the
    round, PERF.md section 6, PR 38), the span says so and the program holds
    no attention kernel."""
    engine, _, ops = four_chips
    assert engine.attention_walk(1024) == {
        "attention_path": "walk", "attention_tile": "256x256",
        "attention_tiles_visited": 10 * N_LAYERS,
        "attention_tiles": 16 * N_LAYERS}
    assert not any("attention_walk" in line for line in ops)


# the attention layers of the cells whose heads are whole lane tiles:
# stations, batch, query heads, kv heads, t, head size, window
# (`perfbench/configs`, `perfbench/traffic`)
CELLS = {
    "ouro.looped4k": (2, 1, 16, 16, 4096, 128, None),
    "smallthinker.packed8k": (2, 1, 28, 4, 8192, 128, 4096),
}


@pytest.mark.parametrize("cell", CELLS)
def test_the_attention_kernels_compile_at_a_cells_shapes(
        cell, topo, uncached):
    """What the interpreted kernels cannot show: Mosaic takes the blocks the
    rule names, a head's step fits VMEM, and the stations' `vmap` is a grid
    axis (one custom call a direction, not one a station)."""
    from jax.sharding import SingleDeviceSharding

    from vantage6_tpu.ops.flash_attention import recompute_attention

    stations, batch, h_q, h_kv, t, d, window = CELLS[cell]
    one_chip = SingleDeviceSharding(topo.devices[0])
    q, k, v = (jax.ShapeDtypeStruct(
        (stations, batch, h, t, d), jnp.bfloat16, sharding=one_chip)
        for h in (h_q, h_kv, h_kv))

    def loss(q, k, v):
        return jnp.sum(recompute_attention(
            q, k, v, causal=True, window=window, interpret=False
        ).astype(jnp.float32))

    text = jax.jit(jax.vmap(jax.value_and_grad(loss, argnums=(0, 1, 2)))
                   ).lower(q, k, v).compile().as_text()
    for name in ("attention_walk_fwd", "attention_walk_bwd"):
        assert sum("custom-call" in line and name in line
                   for line in text.splitlines()) == 1


# ------------------------------------------------ the streamed gather (PR 40)
def test_the_engine_cell_streams_its_table_on_the_chip(topo, uncached):
    """On a mesh of a described v5e the cost rule streams the engine cell's
    tables (32 stations x 262,144 rows of 100 float32 features and a label,
    32,768 rows a step) and keeps XLA's gather for a small batch; the kernel
    compiles at the cell's widths, the stations' `vmap` a grid axis (one
    custom call a step, not one a station)."""
    from jax.sharding import SingleDeviceSharding

    from vantage6_tpu.core.mesh import FederationMesh
    from vantage6_tpu.fed.fedavg import STREAM_BLOCK_ROWS, FedAvg, FedAvgSpec
    from vantage6_tpu.ops.stream_gather import stream_gather

    mesh = FederationMesh(32, devices=list(topo.devices)[:1])
    x = jax.ShapeDtypeStruct((32, 262144, 100), jnp.float32)
    y = jax.ShapeDtypeStruct((32, 262144), jnp.float32)

    def engine(batch_size):
        return FedAvg(mesh, FedAvgSpec(loss_fn=None, batch_size=batch_size))

    assert engine(32768).gather_path(x, y) == "streamed"
    assert engine(32768)._gather_attrs(x, y) == {
        "gather": "streamed", "gather_block_rows": STREAM_BLOCK_ROWS,
        "gather_blocks": 262144 // STREAM_BLOCK_ROWS}
    assert engine(1024).gather_path(x, y) == "packed"
    one_chip = SingleDeviceSharding(topo.devices[0])
    table = jax.ShapeDtypeStruct((32, 262144, 128), jnp.uint32,
                                 sharding=one_chip)
    idx = jax.ShapeDtypeStruct((32, 32768), jnp.int32, sharding=one_chip)
    text = jax.jit(jax.vmap(lambda t, i: stream_gather(
        t, jnp.sort(i), block_rows=STREAM_BLOCK_ROWS))
    ).lower(table, idx).compile().as_text()
    assert sum("custom-call" in line and "stream_gather" in line
               for line in text.splitlines()) == 1


# ---------------------------------------- the indexer's scores (sparse attention)
@pytest.mark.parametrize("rows", [128, 512])
def test_the_indexer_scores_kernel_compiles_at_the_cells_shapes(
        rows, topo, uncached):
    """The lightning indexer's scores at `keye.sparse16k-1chip`'s widths (16
    heads of 64, 16,384 keys), for a block of `select`'s rows (128) and of
    the loss walk's (512): Mosaic takes the tiles and a step fits its VMEM
    limit, and the stations' `vmap` is a grid axis (one custom call)."""
    from jax.sharding import SingleDeviceSharding

    from vantage6_tpu.ops.sparse_attention import _block_scores

    one_chip = SingleDeviceSharding(topo.devices[0])
    q, k, w = (jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
               for shape in ((2, 1, rows, 16, 64), (2, 1, 16384, 64),
                             (2, 1, rows, 16)))
    text = jax.jit(jax.vmap(lambda q, k, w: _block_scores(
        q, k, w, jnp.int32(rows), False))).lower(q, k, w).compile().as_text()
    assert sum("custom-call" in line and "indexer_scores" in line
               for line in text.splitlines()) == 1

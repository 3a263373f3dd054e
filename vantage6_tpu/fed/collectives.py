"""Federated aggregation primitives over the station axis.

These replace the reference's application-level aggregation loop
(`client.task.create(partial...)` fan-out + `wait_for_results` polling + HTTPS
result hops; SURVEY.md §3.2): each primitive consumes *stacked* per-station
pytrees (leading axis S, sharded over the mesh's station axis) and reduces
them on-device. Under `jit`, GSPMD lowers the reductions to XLA all-reduce /
reduce-scatter over ICI — the collective IS the aggregation.

All primitives take an optional participation ``mask`` ([S] bool/float): the
SPMD answer to the reference's asynchronous reality (offline nodes,
stragglers, partial participation). A dropped station contributes weight 0 —
bit-accurate FedAvg-with-dropout without breaking the single-program model.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from vantage6_tpu.core.mesh import STATION_AXIS, station_shard_map
from vantage6_tpu.runtime.profiling import RunnerCache, observed_jit

if TYPE_CHECKING:  # pragma: no cover
    from vantage6_tpu.core.mesh import FederationMesh

Pytree = Any

# Eager-path runner cache for the shard_map'd reducers, keyed on
# everything the closure bakes in (mesh fingerprint + the pad/dtype the
# body hard-codes). A fresh closure per call would re-trace on EVERY
# eager invocation — here the second same-shaped call reuses one observed
# executable, and the device observatory (runtime.profiling) records each
# compile as a device.compile span. Called inside an outer jit the
# observed function inlines like a plain jitted one, unchanged.
_SCATTER_RUNNERS = RunnerCache("collectives")


def _scatter_runner(key: tuple, label: str, make):
    return _SCATTER_RUNNERS.get_or_create(
        key, lambda: observed_jit(label, make())
    )


def _station_count(stacked: Pytree) -> int:
    leaves = jax.tree.leaves(stacked)
    if not leaves:
        raise ValueError("empty pytree")
    return leaves[0].shape[0]


def _norm_weights(
    n: int, weights: jax.Array | None, mask: jax.Array | None
) -> jax.Array:
    """Normalize ``weights``/``mask`` into one float32 [n] weight vector.

    NUMERICS CONTRACT: weights are always carried as float32 — integer (or
    bf16) ``weights`` are upcast here. The *reduction* dtype is a separate
    question and differs per primitive:

    - ``fed_sum``/``fed_mean`` accumulate and divide **in each leaf's
      dtype** (the f32 weights are cast down to the leaf dtype first). A
      bf16 leaf therefore pays bf16 rounding once per station in the sum
      and once in the division — with S stations the worst-case relative
      error grows like S * 2^-8, which is visible for S >= ~16.
    - ``fed_sum_scattered``/``fed_mean_scattered`` accumulate **in float32**
      regardless of leaf dtype and return float32; ``comm_dtype`` only
      narrows the cross-slot wire format (see their docstrings).

    tests/test_collectives.py::test_bf16_leaf_rounding_contract pins the
    first behavior so the scattered path's contract stays spelled out.
    """
    w = jnp.ones((n,), jnp.float32) if weights is None else jnp.asarray(weights, jnp.float32)
    if mask is not None:
        w = w * jnp.asarray(mask, jnp.float32)
    return w


def _weighted_leaf_sum(x: jax.Array, w: jax.Array) -> jax.Array:
    """sum_i w[i] * x[i] over the leading (station) axis.

    Zero-weight stations are excluded with `where`, not just multiplied by
    0 — a crashed/diverged station whose contribution is inf/nan must not
    poison the aggregate (nan * 0 == nan). This is what makes participation
    masks a real failure-isolation mechanism.
    """
    ww = w.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)
    safe_x = jnp.where(ww != 0, x, jnp.zeros((), x.dtype))
    return jnp.sum(safe_x * ww, axis=0)


def fed_sum(stacked: Pytree, mask: jax.Array | None = None) -> Pytree:
    """Sum each leaf over the station axis. Parity: the `sum` half of
    v6-average's central step."""
    with jax.named_scope("aggregate"):
        if mask is None:
            return jax.tree.map(lambda x: jnp.sum(x, axis=0), stacked)
        m = jnp.asarray(mask)
        return jax.tree.map(lambda x: _weighted_leaf_sum(x, m), stacked)


def fed_mean(
    stacked: Pytree,
    weights: jax.Array | None = None,
    mask: jax.Array | None = None,
) -> Pytree:
    """Weighted mean over stations — the FedAvg aggregator.

    ``weights`` is typically per-station example counts ([S]); ``mask`` drops
    stations (failure injection / partial participation). Division is by the
    *effective* total weight so dropped stations don't bias the mean.

    Accumulation and division happen in each leaf's own dtype (see
    ``_norm_weights`` for the full numerics contract) — use
    ``fed_mean_scattered`` when f32 accumulation over bf16 leaves matters.

    ``stacked`` may also be the stations of ONE slot of a mesh axis, inside
    a `shard_map` (`OverAxis`): the same mean over ALL stations, its
    cross-slot part a ring of asynchronous steps (`_ring_mean`).
    """
    if isinstance(stacked, OverAxis):
        return stacked.mean(weights)
    with jax.named_scope("aggregate"):
        n = _station_count(stacked)
        w = _norm_weights(n, weights, mask)
        total = jnp.sum(w)
        # Guard the all-dropped edge: return zeros rather than NaN.
        denom = jnp.where(total > 0, total, 1.0)
        return jax.tree.map(
            lambda x: _weighted_leaf_sum(x, w) / jnp.asarray(denom, x.dtype), stacked
        )


# --------------------------------------------------------------------------
# fed_mean over a named axis, as a ring: the cross-slot reduce made of
# asynchronous `ppermute` steps that run beside whatever else is ready (the
# backward pass of the layers below, the optimizer on the groups already
# reduced), where `fed_mean`'s `jnp.sum` over a sharded axis becomes an
# all-reduce with nothing beside it (PERF.md section 6, PR 36).
# --------------------------------------------------------------------------

# a group of leaves is closed once it holds this much. A step's fixed cost
# and the waits at its end are paid once a step, whatever it carries, and
# the compiler writes the gradients of a group's layers straight into its
# buckets; but the last group made goes round with nothing left to run
# beside it but the optimizer. Read on four v5e chips at GPT-2 medium's 48
# MiB a layer (PERF.md section 6, PR 36): a round of 91.1 ms at 32 MiB (a
# layer a group), 88.9 at 128 MiB (three), against 91.6 to 92.2 for the
# all-reduces this replaces.
RING_GROUP_BYTES = 128 << 20
_SUBLANES, _LANES = 8, 128  # the tile a float32 array of rank >= 2 is held in


def station_ring(devices: Any) -> tuple[int, ...]:
    """The order in which the slots of a mesh axis are joined into a ring,
    as positions along the axis, read from the slots' ``devices``. Four
    chips that say where they sit (``coords``, as a TPU's do) in a 2 x 2 go
    round its square, so that every step of the ring crosses one link (the
    order by index crosses the diagonal twice). Anything else (CPU devices,
    a line of chips, a larger grid: none has been read) is joined by index."""
    devices = list(devices)
    coords = [getattr(dev, "coords", None) for dev in devices]
    if len(devices) == 4 and None not in coords:
        varying = [k for k in range(len(coords[0]))
                   if len({c[k] for c in coords}) > 1]
        if len(varying) == 2:
            place = {(c[varying[0]], c[varying[1]]): i
                     for i, c in enumerate(coords)}
            xs, ys = (sorted({xy[k] for xy in place}) for k in range(2))
            if len(place) == 4 and len(xs) == len(ys) == 2:
                return tuple(place[x, y] for x, y in (
                    (xs[0], ys[0]), (xs[1], ys[0]),
                    (xs[1], ys[1]), (xs[0], ys[1])))
    return tuple(range(len(devices)))


def ring_groups(nbytes: list[int]) -> list[list[int]]:
    """Consecutive items gathered into groups of `RING_GROUP_BYTES` or more
    (the last group may hold less): which of them are reduced together."""
    groups: list[list[int]] = []
    held = 0
    for i, n in enumerate(nbytes):
        if not groups or held >= RING_GROUP_BYTES:
            groups.append([])
            held = 0
        groups[-1].append(i)
        held += n
    return groups


def _ring_buckets(shapes: list[tuple[tuple[int, ...], Any]], d: int):
    """How a group's leaves lie on the wire: ``[(key, leaf indices, rows)]``.
    Leaves of one dtype and last dimension (whole lanes) are laid row on row
    without leaving their tiles, the rest are raveled into one vector a
    dtype; ``rows`` is the bucket's length padded so that both directions'
    ``d`` chunks are whole tiles."""
    found: dict[Any, list[int]] = {}
    for i, (shape, dtype) in enumerate(shapes):
        tiled = (len(shape) >= 2 and shape[-1] % _LANES == 0
                 and math.prod(shape[:-1]) >= 2 * d * _SUBLANES)
        found.setdefault(
            (jnp.dtype(dtype), shape[-1] if tiled else None), []).append(i)
    out = []
    for key, members in found.items():
        width = key[1] or 1
        rows = sum(math.prod(shapes[i][0]) for i in members) // width
        unit = 2 * d * (_SUBLANES if key[1] else _SUBLANES * _LANES)
        out.append((key, members, rows + (-rows) % unit))
    return out


def ring_bytes_sent(shapes: list[tuple[tuple[int, ...], Any]], d: int) -> int:
    """What one slot sends when a group of leaves of these shapes and dtypes
    goes round a ring of ``d``: ``d - 1`` steps of a reduce-scatter and as
    many of an all-gather, a ``d``-th of the (padded) group a step."""
    return sum(
        2 * (d - 1) * rows * (key[1] or 1) * key[0].itemsize // d
        for key, _, rows in _ring_buckets(shapes, d))


def _ring_ways(axis_name: str, ring: tuple[int, ...]):
    """The two ways round ``ring``: for each its `ppermute` pairs and, for
    THIS slot, the chunk it holds whole at every step (its place in the ring
    counted the way that half travels, plus one, less the steps gone), read
    from one table by `axis_index`."""
    d = len(ring)
    place = np.argsort(ring)
    ways = []
    for step in (1, -1):
        perm = [(ring[i], ring[(i + step) % d]) for i in range(d)]
        r = place if step == 1 else (d - place) % d
        ways.append((perm, (r[:, None] + 1 - np.arange(d + 1)) % d))
    table = jnp.asarray(np.stack([chunks for _, chunks in ways], 1), jnp.int32)
    mine = table[jax.lax.axis_index(axis_name)]
    return [(perm, [mine[half, s] for s in range(d + 1)])
            for half, (perm, _) in enumerate(ways)]


def _ring_mean(x: jax.Array, denom: jax.Array, axis_name: str, ways) -> jax.Array:
    """``x`` [2, d, ...]: this slot's partial sums, chunk by chunk; half 0
    goes round the ring one way and half 1 the other (``ways``:
    `_ring_ways`), so that both links of a chip carry it. A reduce-scatter
    (chunk c starts at the slot in place c and picks up one slot's part a
    step, so it is summed in ONE order, and ends whole, and is divided, in
    place c - 1) and then an all-gather that copies the whole chunks round:
    every slot ends with the same bits. Nothing but the next step reads
    what a step makes: a second reader (a mark of the reduce-scatter's end,
    say) makes the compiler lay the buffer out another way and copy it whole
    at every update, 23 ms of a GPT-2 medium round (PERF.md section 6)."""
    d = x.shape[1]
    zeros = (0,) * (x.ndim - 2)

    def chunk(half, c):
        return jax.lax.dynamic_slice(
            x, (half, c, *zeros), (1, 1, *x.shape[2:]))

    # at step s a slot in place r holds chunk r - s of the sum under way, and
    # chunk r + 1 - s of the whole ones: ``at[s]`` is chunk r + 1 - s
    part = [chunk(half, at[1]) for half, (_, at) in enumerate(ways)]
    for s in range(d - 1):
        part = [jax.lax.ppermute(p, axis_name, perm) + chunk(half, at[s + 2])
                for half, (p, (perm, at)) in enumerate(zip(part, ways))]
    part = [p / jnp.asarray(denom, x.dtype) for p in part]
    out = x  # every chunk of it is written over: no second buffer
    for s in range(d):
        for half, (p, (_, at)) in enumerate(zip(part, ways)):
            out = jax.lax.dynamic_update_slice(out, p, (half, at[s], *zeros))
        if s < d - 1:
            part = [jax.lax.ppermute(p, axis_name, perm)
                    for p, (perm, _) in zip(part, ways)]
    return out


# traced once for every list of shapes: the layers of a stack after the
# first hit jax's caches, and the lowering emits one function and calls it
@partial(jax.jit, static_argnames=("axis_name", "ring"))
def _ring_group(sums: list[jax.Array], denom: jax.Array, *, axis_name: str,
                ring: tuple[int, ...]) -> list[jax.Array]:
    """The slots' ``sums`` (one group's leaves, each slot's own part) added
    over ``axis_name`` and divided by ``denom``, round ``ring``."""
    d = len(ring)
    ways = _ring_ways(axis_name, ring)
    means: list[Any] = [None] * len(sums)
    for (_, width), members, rows in _ring_buckets(
            [(x.shape, x.dtype) for x in sums], d):
        tail = (width,) if width else ()
        flat = [sums[i].reshape(-1, *tail) for i in members]
        flat = flat[0] if len(flat) == 1 else jnp.concatenate(flat)
        flat = jnp.pad(
            flat, [(0, rows - flat.shape[0])] + [(0, 0)] * len(tail))
        mean = _ring_mean(flat.reshape(2, d, rows // (2 * d), *tail), denom,
                          axis_name, ways).reshape(rows, *tail)
        at = 0
        for i in members:
            n = sums[i].size // (width or 1)
            means[i] = mean[at:at + n].reshape(sums[i].shape)
            at += n
    return means


def _nothing(x: jax.Array) -> jax.Array:
    """Zero, whatever ``x`` holds, and not before ``x`` exists: what ties an
    operation to one it does not read (the compiler folds neither the
    product nor the clamp away, and drops an `optimization_barrier` before
    it orders the program)."""
    return jnp.clip(jnp.where(x == x, x, 0.0), -1.0, 1.0) * 0.0


class OverAxis:
    """What `fed_mean` takes INSIDE a `shard_map` over a mesh axis along
    which the stations lie: ``stacked``, the leaves [K, ...] of the K
    stations packed in THIS slot (``weights`` then their own [K], float32,
    mask applied), and what says how the slots are joined: the axis, the
    ring (`station_ring`) and ``denom``, the effective total weight of ALL
    stations, guarded as `fed_mean` guards it. Where the K stations are the
    lanes of a `vmap` named ``packed_axis`` and not an axis of the leaves,
    ``stacked`` holds the one station of this lane ([1, ...]).

    The mean is `fed_mean`'s to the letter: in each leaf's dtype, the slot's
    stations summed first as ``where(w != 0, x, 0) * w`` (a dropped
    station's NaN never leaves its slot), then the slots' sums added and
    divided by ``denom``; the same bits on every slot. What differs is what
    carries it: the leaves go round the ring as `_ring_mean` has it,
    4 (d - 1) `ppermute` steps a bucket, which the compiler makes
    asynchronous. It also places them as late as it may: for steps that lie
    between the operations that make the next leaves, see `RingExchange`."""

    def __init__(self, stacked: Pytree, axis_name: str, ring: tuple[int, ...],
                 denom: jax.Array, packed_axis: str | None = None):
        self.stacked, self.denom = stacked, denom
        self.axis_name, self.ring, self.packed_axis = (
            axis_name, ring, packed_axis)

    def __getitem__(self, k: int) -> Pytree:
        """Station ``k`` of this slot, as it is: nothing crosses anything.
        For the benchmark's planted fault alone (`no_exchange` patches
        `fed_mean` to hand back ``stacked[0]``); no program calls it. It
        goes when a `benchmark` PR plants that fault by name (PERF.md 7.10d,
        ROADMAP.md A5)."""
        return jax.tree.map(lambda x: x[k], self.stacked)

    def mean(self, w: jax.Array) -> Pytree:
        with jax.named_scope("aggregate"):
            leaves, treedef = jax.tree.flatten(self.stacked)
            sums = [_weighted_leaf_sum(x, w) for x in leaves]
            if self.packed_axis is not None:
                sums = [jax.lax.psum(x, self.packed_axis) for x in sums]
            return jax.tree.unflatten(treedef, _ring_group(
                sums, self.denom, axis_name=self.axis_name, ring=self.ring))


class RingExchange:
    """`fed_mean` over a mesh axis from INSIDE a backward pass, so that a
    group's steps round the ring lie between the operations that make the
    next groups' gradients and not behind them all. One instance serves one
    trace of one station's loss (under the `vmap` over the stations packed
    in a slot, named ``packed_axis``, inside the `shard_map` over
    ``axis_name``); ``w`` is that station's weight and ``denom`` the
    effective total weight.

    ``x, leaves = exchange(x, leaves)`` is the identity on the way forward.
    On the way back the cotangent of ``leaves`` (the station's gradient of
    them, whole by then) comes out as its `fed_mean` over all stations
    (`OverAxis`), and the cotangent of ``x`` (the activations on their way
    to the layers below) is held until the group of the call before
    (further up) has come round. The compiler's scheduler places every
    operation as late as it may and would leave all the steps to the end of
    the backward pass, five under way at a time and nothing beside them;
    held so, a group's steps can lie nowhere but beside the backward pass of
    the layers just below it and the optimizer's step on the group above."""

    def __init__(self, w: jax.Array, denom: jax.Array, axis_name: str,
                 ring: tuple[int, ...], packed_axis: str):
        self._args = (w, denom)
        # on the way back: a mark of the group further up, which exists
        # when that group has come round
        self._came_round = jnp.zeros((1,), jnp.float32)

        @jax.custom_vjp
        def exchange(x, leaves, came_round, w, denom):
            return x, leaves, came_round

        def forward(x, leaves, came_round, w, denom):
            return (x, leaves, came_round), (w, denom)

        def backward(weights, cotangents):
            w, denom = weights
            dx, grads, further_up = cotangents
            mean = fed_mean(
                OverAxis(jax.tree.map(lambda g: g[None], grads), axis_name,
                         ring, denom, packed_axis), weights=w[None])
            with jax.named_scope("aggregate"):
                dx = dx + _nothing(further_up[0]).astype(dx.dtype)
                # of the mean as it comes back and of nothing on its way: a
                # second reader of what a step makes costs 23 ms a round
                # (`_ring_mean`)
                came_round = sum(
                    jax.lax.slice(m, (0,) * m.ndim, (1,) * m.ndim)
                    .reshape(1).astype(jnp.float32)
                    for m in jax.tree.leaves(mean))
            return (dx, mean, came_round,
                    jnp.zeros_like(w), jnp.zeros_like(denom))

        exchange.defvjp(forward, backward)
        self._exchange = exchange

    def __call__(self, x: jax.Array, leaves: Pytree) -> tuple[jax.Array, Pytree]:
        x, leaves, self._came_round = self._exchange(
            x, leaves, self._came_round, *self._args)
        return x, leaves


def fed_weighted_stats(
    sums: Pytree, counts: jax.Array, mask: jax.Array | None = None
) -> tuple[Pytree, jax.Array]:
    """(global sums, global count) from per-station (sums, counts) — the exact
    shape of the reference's federated-average contract: partials return
    {sum, count}, central divides. Returns aggregated sums and total count."""
    g_sums = fed_sum(sums, mask=mask)
    g_count = fed_sum(counts, mask=mask)
    return g_sums, g_count


def fed_concat(stacked: Pytree) -> Pytree:
    """Flatten the station axis into the data axis: [S, n, ...] -> [S*n, ...].

    The on-device analogue of the central step "fetch all partial results and
    concatenate" (e.g. global event-time grids for Kaplan-Meier). With ragged
    true sizes, pair with per-station validity masks.
    """
    return jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), stacked)


# --------------------------------------------------------------------------
# Scattered aggregation: reduce-scatter primitives for the sharded server
# update (ZeRO-1 style; Xu et al., arXiv:2004.13336).
# --------------------------------------------------------------------------
#
# fed_mean above materializes the full aggregate REPLICATED on every mesh
# slot — an all-reduce-shaped round whose per-slot memory and wire bytes
# both scale with full model size. The scattered primitives instead:
#
#   1. each slot locally reduces its S/D stations' contributions (f32),
#   2. flattens the partial-sum pytree into ONE padded f32 vector,
#   3. `psum_scatter`s it over the station axis — each slot keeps only a
#      1/D shard of the global sum (wire: (D-1)/D * N elements per slot,
#      same as one all-reduce's reduce half; memory: N/D instead of N),
#   4. the caller applies the server update shard-locally and re-replicates
#      with `all_gather_stations` only once per round.
#
# ``comm_dtype`` (e.g. jnp.bfloat16) narrows step 3's on-wire dtype only:
# the local accumulation (1) and everything after the scatter stay f32.


def flat_size(tree: Pytree) -> int:
    """Total element count of ``tree``'s leaves (static, host-side)."""
    return sum(math.prod(x.shape) for x in jax.tree.leaves(tree))


def padded_flat_size(n: int, d: int) -> int:
    """``n`` rounded up to a multiple of ``d`` (psum_scatter divisibility)."""
    return n + (-n) % d


def flatten_tree(tree: Pytree, dtype: Any = jnp.float32) -> jax.Array:
    """Ravel + concatenate every leaf into one flat [N] vector."""
    parts = [x.astype(dtype).reshape(-1) for x in jax.tree.leaves(tree)]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def unflatten_like(template: Pytree, flat: jax.Array) -> Pytree:
    """Inverse of ``flatten_tree``: split ``flat`` back into ``template``'s
    shapes/dtypes. Extra trailing elements (scatter padding) are ignored."""
    leaves, treedef = jax.tree.flatten(template)
    out, off = [], 0
    for leaf in leaves:
        size = math.prod(leaf.shape)
        out.append(flat[off : off + size].reshape(leaf.shape).astype(leaf.dtype))
        off += size
    return jax.tree.unflatten(treedef, out)


def flatten_stacked(stacked: Pytree) -> jax.Array:
    """Per-station flat-pack: [S, ...] pytree -> ONE [S, N] f32 matrix
    (row i = station i's delta, leaves concatenated in tree order).

    The seam the gradient-compression stack operates at
    (docs/compression.md): compressors consume flat per-station vectors,
    never pytrees — same flat layout as ``flatten_tree`` per row.
    """
    leaves = jax.tree.leaves(stacked)
    if not leaves:
        raise ValueError("empty pytree")
    s = leaves[0].shape[0]
    parts = [x.astype(jnp.float32).reshape(s, -1) for x in leaves]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def unflatten_stacked(template: Pytree, flat: jax.Array) -> Pytree:
    """Inverse of ``flatten_stacked``: [S, N] rows back into a stacked
    pytree shaped/dtyped like ``template`` (a PER-STATION pytree, i.e.
    one station's leaf shapes) with the leading station axis restored."""
    leaves, treedef = jax.tree.flatten(template)
    s = flat.shape[0]
    out, off = [], 0
    for leaf in leaves:
        size = math.prod(leaf.shape)
        out.append(
            flat[:, off:off + size]
            .reshape((s,) + tuple(leaf.shape))
            .astype(leaf.dtype)
        )
        off += size
    return jax.tree.unflatten(treedef, out)


def station_update_stats(
    flat: jax.Array,
    weights: jax.Array | None = None,
    mask: jax.Array | None = None,
    ef: jax.Array | None = None,
) -> dict[str, jax.Array]:
    """Learning-plane statistics of one round's per-station updates — ONE
    fused f32 pass over the flat-packed ``[S, N]`` rows (the same seam the
    gradient-compression stack operates at; docs/observability.md
    "learning plane"):

    - ``station_norm`` [S]: each station's update L2 norm;
    - ``station_cos`` [S]: cosine similarity of each station's delta to
      the pooled (weighted-mean) delta — the per-client update-quality
      signal async aggregation will accept/down-weight on. A label-flipped
      or poisoned station shows up as a NEGATIVE/low cosine; a scaled one
      as an outlier norm at cosine ~1;
    - ``update_norm`` []: L2 norm of the pooled delta, the global
      convergence signal (its decay trajectory is what the
      ``model_divergence``/``non_convergence`` watchdog rules read);
    - ``station_ef_norm`` [S] (only when ``ef`` is passed): per-station
      error-feedback mass — the per-station refinement of the global
      ``v6t_compress_ef_norm`` gauge.

    The pooled delta uses ``fed_mean``'s exact weighting semantics
    (f32, zero-weight stations nan-isolated, all-dropped guard), computed
    here from the SAME formula regardless of the server-update mode — so
    the stats are fp32-identical between the replicated and scattered
    (ZeRO-1) paths by construction (the bench's parity assertion). The
    per-station reductions are row-local (they ship [S] scalars under
    GSPMD); the cosine leg needs the pooled vector once, which in
    scattered mode costs one extra f32 reduction of N elements — cheap
    next to local training, and `FedAvgSpec(learning_stats=False)` turns
    the whole leg off where wire bytes matter.

    Masked-out stations keep their (fictional, SPMD-computed) norm/cos —
    they are excluded from the POOLED delta, and zeroing them here would
    hide exactly the diverging-station evidence the stats exist to
    surface. The effective weight vector rides along as
    ``station_weight`` so host consumers (RoundHistory, the
    ``anomalous_station`` rule) can tell a participating station from a
    masked-out one — an alert must never name a station the operator
    already excluded.
    """
    x = flat.astype(jnp.float32)
    s = x.shape[0]
    w = _norm_weights(s, weights, mask)
    norms = jnp.sqrt(jnp.sum(x * x, axis=1))
    total = jnp.sum(w)
    denom = jnp.where(total > 0, total, 1.0)
    ww = w.reshape(-1, 1)
    # same nan-isolation as _weighted_leaf_sum: a crashed station's
    # inf/nan delta must not poison the pooled update (nan * 0 == nan)
    safe = jnp.where(ww != 0, x, jnp.zeros((), jnp.float32))
    pooled = jnp.sum(safe * ww, axis=0) / denom
    update_norm = jnp.sqrt(jnp.sum(pooled * pooled))
    dots = x @ pooled
    cos = dots / jnp.maximum(norms * update_norm, 1e-12)
    out = {
        "station_norm": norms,
        "station_cos": cos,
        "update_norm": update_norm,
        "station_weight": w,
    }
    if ef is not None:
        e = ef.astype(jnp.float32)
        out["station_ef_norm"] = jnp.sqrt(jnp.sum(e * e, axis=1))
    return out


def per_round_masks(mask: Any, n_rounds: int) -> jax.Array:
    """Participation masks for a fused K-round program as a ``[K, S]``
    f32 matrix — the scan-xs form of the participation seam.

    Accepts a ``[S]`` mask (one roster for every round — broadcast, the
    common case) or an already per-round ``[K, S]`` matrix (buffered-async
    accept masks, per-round fault schedules). Rank is static under
    tracing, so both forms flow through the SAME fused executable without
    retracing; a wrong leading length on the ``[K, S]`` form is a
    host-side error, not a silent truncation.
    """
    m = jnp.asarray(mask, jnp.float32)
    if m.ndim == 1:
        return jnp.broadcast_to(m, (n_rounds,) + m.shape)
    if m.ndim != 2:
        raise ValueError(
            f"mask must be [S] or [n_rounds, S], got rank {m.ndim}"
        )
    if m.shape[0] != n_rounds:
        raise ValueError(
            f"per-round mask has {m.shape[0]} rounds, expected {n_rounds}"
        )
    return m


def _local_weighted_flat_sum(
    local_stacked: Pytree, local_w: jax.Array
) -> jax.Array:
    """One slot's weighted f32 partial sum over its local station block,
    flattened. Keeps fed_mean's nan-isolation: zero-weight stations are
    excluded with `where`, so a crashed station's inf/nan cannot poison
    the aggregate."""

    def leaf_sum(x: jax.Array) -> jax.Array:
        ww = local_w.reshape((-1,) + (1,) * (x.ndim - 1))
        xf = x.astype(jnp.float32)
        safe = jnp.where(ww != 0, xf, jnp.zeros((), jnp.float32))
        return jnp.sum(safe * ww, axis=0)

    return flatten_tree(
        [leaf_sum(x) for x in jax.tree.leaves(local_stacked)]
    )


def fed_sum_scattered(
    mesh: "FederationMesh",
    stacked: Pytree,
    weights: jax.Array | None = None,
    mask: jax.Array | None = None,
    comm_dtype: Any = None,
) -> jax.Array:
    """Weighted sum over stations, reduce-scattered over the station axis.

    Returns ONE flat float32 vector of ``padded_flat_size(N, D)`` elements
    (N = per-station element count of ``stacked`` minus the leading axis),
    sharded over the mesh's station axis — slot i holds elements
    ``[i*N_pad/D, (i+1)*N_pad/D)`` of the global weighted sum. Recover the
    pytree with ``all_gather_stations`` + ``unflatten_like``.

    Participation ``mask`` / ``weights`` semantics are identical to
    ``fed_sum``/``fed_mean`` (zero-weight stations nan-isolated). Local
    accumulation is float32; ``comm_dtype`` narrows only the cross-slot
    psum_scatter exchange (bf16 halves the on-wire bytes; the D partial
    sums then combine in bf16 — document the accuracy caveat to callers).
    """
    n = _station_count(stacked)
    if n != mesh.n_stations:
        raise ValueError(
            f"stacked has {n} stations but mesh federates {mesh.n_stations}"
        )
    d = mesh.station_axis_size
    n_flat = flat_size(jax.tree.map(lambda x: x[0], stacked))
    pad = padded_flat_size(n_flat, d) - n_flat

    def body(local_stacked: Pytree, local_w: jax.Array) -> jax.Array:
        flat = _local_weighted_flat_sum(local_stacked, local_w)
        if pad:
            flat = jnp.pad(flat, (0, pad))
        if comm_dtype is not None:
            flat = flat.astype(comm_dtype)
        shard = jax.lax.psum_scatter(
            flat, STATION_AXIS, scatter_dimension=0, tiled=True
        )
        return shard.astype(jnp.float32)

    runner = _scatter_runner(
        ("fed_sum_scattered", mesh.fingerprint(), str(comm_dtype),
         n_flat, pad),
        "collectives.fed_sum_scattered",
        lambda: station_shard_map(
            mesh, body,
            in_specs=(P(STATION_AXIS), P(STATION_AXIS)),
            out_specs=P(STATION_AXIS),
        ),
    )
    with jax.named_scope("aggregate"):
        return runner(stacked, _norm_weights(n, weights, mask))


def fed_mean_scattered(
    mesh: "FederationMesh",
    stacked: Pytree,
    weights: jax.Array | None = None,
    mask: jax.Array | None = None,
    comm_dtype: Any = None,
) -> jax.Array:
    """``fed_mean``, reduce-scattered: the FedAvg aggregator returning each
    slot's 1/D shard of the flat weighted mean (float32 — see
    ``fed_sum_scattered`` for layout and the ``comm_dtype`` contract).

    The division by effective total weight happens on the f32 shard AFTER
    the scatter, so the all-dropped guard and dropped-station debiasing
    match ``fed_mean`` exactly.
    """
    with jax.named_scope("aggregate"):
        n = _station_count(stacked)
        w = _norm_weights(n, weights, mask)
        total = jnp.sum(w)
        denom = jnp.where(total > 0, total, 1.0)
        s = fed_sum_scattered(mesh, stacked, weights=weights, mask=mask,
                              comm_dtype=comm_dtype)
        return s / denom


def all_gather_stations(mesh: "FederationMesh", flat: jax.Array) -> jax.Array:
    """Re-replicate a station-axis-sharded flat vector (the once-per-round
    all-gather that closes the reduce-scatter -> shard-local update ->
    all-gather cycle)."""

    def body(local: jax.Array) -> jax.Array:
        return jax.lax.all_gather(local, STATION_AXIS, tiled=True)

    runner = _scatter_runner(
        ("all_gather_stations", mesh.fingerprint()),
        "collectives.all_gather",
        lambda: station_shard_map(
            mesh, body, in_specs=(P(STATION_AXIS),), out_specs=P(),
        ),
    )
    return runner(flat)


def fed_mean_scattered_tree(
    mesh: "FederationMesh",
    stacked: Pytree,
    weights: jax.Array | None = None,
    mask: jax.Array | None = None,
    comm_dtype: Any = None,
) -> Pytree:
    """Convenience: scattered mean -> all-gather -> original pytree shape.

    Communication-equivalent to reduce-scatter + all-gather (i.e. one
    all-reduce, but with a bf16-narrowable reduce half); result leaves are
    float32 cast back to each leaf's dtype.
    """
    with jax.named_scope("aggregate"):
        flat = all_gather_stations(
            mesh,
            fed_mean_scattered(mesh, stacked, weights=weights, mask=mask,
                               comm_dtype=comm_dtype),
        )
        template = jax.tree.map(lambda x: x[0], stacked)
        return unflatten_like(template, flat)


# --------------------------------------------------------------------------
# Secure aggregation: additive masking with exact modular-int cancellation.
# --------------------------------------------------------------------------
#
# The reference's crypto story is (a) hybrid RSA+AES end-to-end payload
# encryption in core and (b) Paillier-style secure sums inside algorithm
# repos (SURVEY.md §2.3). Homomorphic bigint is the wrong tool on an MXU; the
# TPU-native fast path is pairwise additive masking (Bonawitz et al. style):
# station i adds sum_{j>i} PRG(k_ij) - sum_{j<i} PRG(k_ji); masks cancel in
# the all-reduce. Values are quantized to int32 and masked modulo 2^32 so
# cancellation is EXACT (float masking would not cancel bit-wise).
#
# HONESTY NOTE (see docs/THREAT_MODEL.md): masks here derive from one `key`,
# so the guarantee is scoped to observers WITHOUT that key (e.g. a log/trace
# reader, or a party shown a single masked tensor). A real deployment where
# the aggregator is untrusted needs per-pair Diffie-Hellman secrets so no
# single party can strip masks; the collective structure is identical — only
# key provisioning changes. Paillier itself stays host-side
# (`vantage6_tpu.common.paillier`) for parity tests.


def _pair_mask(key: jax.Array, i: jax.Array, j: jax.Array, shape) -> jax.Array:
    """Deterministic pairwise mask PRG(k_ij) as int32, same for both parties."""
    k = jax.random.fold_in(jax.random.fold_in(key, i), j)
    return jax.random.randint(k, shape, jnp.iinfo(jnp.int32).min,
                              jnp.iinfo(jnp.int32).max, dtype=jnp.int32)


def mask_station_value(
    key: jax.Array, station: jax.Array, n_stations: int, quantized: jax.Array
) -> jax.Array:
    """Add this station's pairwise masks (mod 2^32) to its quantized value."""

    def body(s, acc):
        m = _pair_mask(key, jnp.minimum(station, s), jnp.maximum(station, s),
                       quantized.shape)
        sign = jnp.where(s == station, 0, jnp.where(s > station, 1, -1))
        return acc + sign.astype(jnp.int32) * m  # int32 wraps (mod 2^32)

    return jax.lax.fori_loop(0, n_stations, body, quantized)


def quantize(x: jax.Array, scale: float) -> jax.Array:
    return jnp.round(x * scale).astype(jnp.int32)


def dequantize(q: jax.Array, scale: float) -> jax.Array:
    return q.astype(jnp.float32) / scale


def secure_sum(
    stacked: jax.Array,
    key: jax.Array,
    scale: float = 2.0**16,
    mask: jax.Array | None = None,
) -> jax.Array:
    """Secure sum over the station axis via pairwise additive masking.

    ``stacked``: [S, ...] float array. Each station's contribution is
    quantized, masked with pairwise PRG masks (unstrippable by an observer who
    does not hold ``key`` — see the honesty note above for the aggregator
    threat model), then summed; masks cancel exactly in int32 modular
    arithmetic. Returns the dequantized float sum. Max representable |sum| is
    2^31/scale; pick ``scale`` to trade range vs precision.

    ``mask`` ([S]) zeroes non-participating stations' VALUES while every
    station still contributes its pairwise PRG masks — cancellation needs all
    mask pairs present (in a real dropout scenario, recovering lost masks
    requires the Bonawitz secret-sharing protocol; in SPMD all stations are
    always able to compute their masks, so exclusion-by-mask is exact).
    """
    s = stacked.shape[0]
    vals = stacked
    if mask is not None:
        m = jnp.asarray(mask, stacked.dtype).reshape(
            (-1,) + (1,) * (stacked.ndim - 1)
        )
        vals = jnp.where(m != 0, stacked, jnp.zeros((), stacked.dtype)) * m
    q = jax.vmap(lambda i, x: mask_station_value(key, i, s, quantize(x, scale)))(
        jnp.arange(s), vals
    )
    return dequantize(jnp.sum(q, axis=0), scale)


def secure_fed_mean(
    stacked: Pytree,
    weights: jax.Array,
    key: jax.Array,
    scale: float = 2.0**16,
) -> Pytree:
    """FedAvg aggregation where both weighted sums and total weight go through
    the secure-sum path — the aggregator never sees an individual station's
    update in the clear."""
    total_w = secure_sum(jnp.asarray(weights, jnp.float32), key, scale)
    denom = jnp.where(total_w > 0, total_w, 1.0)
    leaves, treedef = jax.tree.flatten(stacked)
    out = []
    for idx, x in enumerate(leaves):
        w = jnp.asarray(weights, x.dtype).reshape((-1,) + (1,) * (x.ndim - 1))
        leaf_key = jax.random.fold_in(key, idx + 1)
        out.append(secure_sum(x * w, leaf_key, scale) / denom)
    return jax.tree.unflatten(treedef, out)

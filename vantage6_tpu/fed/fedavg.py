"""FedAvg engine: a federated round as ONE compiled SPMD program.

This is the TPU-native rewrite of the reference's central/partial round
(SURVEY.md §3.2): where vantage6 pays SocketIO fan-out + N container
lifecycles + 2N HTTPS result hops + polling per round, here a round is a
single jitted program — per-station local SGD under `fed_map` (shard_map over
the station axis), aggregation as a weighted mean the GSPMD partitioner
lowers to an all-reduce over ICI. `run_rounds` additionally folds the round
loop into `lax.scan`, so an entire training run is one XLA computation with
zero host round-trips.

Semantics kept from the reference world:
- per-station example counts weight the aggregation (ragged shards are
  padded; sampling respects true counts);
- a participation mask drops stations (offline nodes / stragglers / failure
  injection) bit-accurately — FedAvg-with-dropout, the SPMD answer to the
  reference's asynchrony (SURVEY.md §7 hard part 1);
- a server optimizer generalizes plain averaging (optax.sgd(1.0) == FedAvg;
  adam == FedAdam etc., Reddi et al. 2021).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax

from vantage6_tpu.core.mesh import FederationMesh
from vantage6_tpu.fed.collectives import (
    all_gather_stations,
    fed_mean,
    fed_mean_scattered,
    flat_size,
    flatten_stacked,
    flatten_tree,
    padded_flat_size,
    per_round_masks,
    station_update_stats,
    unflatten_like,
    unflatten_stacked,
)
from vantage6_tpu.common.telemetry import REGISTRY
from vantage6_tpu.fed.compression import (
    CompressorSpec,
    compress_stacked,
    record_round_telemetry,
)
from vantage6_tpu.ops import stream_gather as SG
from vantage6_tpu.runtime.profiling import engine_call, observed_jit

Pytree = Any
# loss_fn(params, batch_x, batch_y, example_weights) -> scalar mean loss
LossFn = Callable[[Pytree, jax.Array, jax.Array, jax.Array], jax.Array]

# What `gather_path` weighs a streamed table against the gather with: an XLA
# gather costs about 10.5 ns an index on the v5e, whatever the row's width
# (PERF.md section 6, PR 27), and a stream moves the table at the v5e's
# published 819 GB/s of HBM, 819 bytes a nanosecond. The kernel's blocks of
# table rows were read on the chip: 16,384 rows the fastest of 4,096, 8,192
# and 16,384 (PERF.md section 6, PR 40).
PER_INDEX_NS = 10.5
HBM_BYTES_PER_NS = 819.0
STREAM_BLOCK_ROWS = 16384


def _viewable(dtype: Any) -> bool:
    """An element type whose bits ``lax.bitcast_convert_type`` carries into
    another of its width and back: integers and floats, not bool."""
    return jnp.issubdtype(dtype, jnp.integer) or jnp.issubdtype(
        dtype, jnp.floating
    )


def _device_bytes_limit(device: Any) -> int | None:
    """The device's memory as its backend reports it, or None (the CPU
    backend reports nothing; a described device has no client to ask)."""
    try:
        stats = device.memory_stats()
    except Exception:
        return None
    return (stats or {}).get("bytes_limit")


@dataclasses.dataclass(frozen=True)
class FedAvgSpec:
    loss_fn: LossFn
    local_steps: int = 1
    batch_size: int = 32
    local_lr: float = 0.1
    server_optimizer: optax.GradientTransformation | None = None  # default sgd(1)
    # Sharded server update (ZeRO-1 over the station axis): the pseudo-
    # gradient is reduce-scattered, server-optimizer moments and the optax
    # update live only on each slot's 1/D flat param shard, and params are
    # all-gathered once per round. Replicated and sharded modes are
    # numerically equivalent in f32 (tests/test_scattered_update.py parity).
    shard_server_update: bool = False
    # On-wire dtype of the delta reduce-scatter (e.g. jnp.bfloat16 halves
    # collective bytes). Master params, moments and post-scatter math stay
    # f32 — see docs/sharded_update.md for the accuracy caveats. Used by
    # the scattered exchange (shard_server_update=True) and, when a
    # compressor is set, as the pre-quantization cast (cast, THEN
    # quantize — docs/compression.md composition order).
    comm_dtype: Any = None
    # Gradient compression of the per-station delta uplink (CompressorSpec,
    # docs/compression.md): stochastic int8 and/or top-k with per-station
    # error-feedback accumulators carried in the optimizer state. The
    # aggregation consumes the DECOMPRESSED deltas, so this composes with
    # both the replicated and the scattered (ZeRO-1) server update.
    compressor: CompressorSpec | None = None
    # Learning-plane statistics (docs/observability.md "learning plane"):
    # per-station update L2 norms, cosine-to-pooled-delta, per-station EF
    # mass and the global update norm, computed INSIDE the jitted round at
    # the flat-pack seam (collectives.station_update_stats) and returned
    # as the 4th element of round()/run_rounds(). fp32-identical between
    # the replicated and scattered update paths. Off = stats come back as
    # an empty dict and the round pays nothing for them.
    learning_stats: bool = True


@dataclasses.dataclass(frozen=True)
class AsyncRoundSpec:
    """FedBuff-style buffered-async round shape (Nguyen et al. 2022).

    The server dispatches ``quorum + over_select`` stations, aggregates
    the FIRST ``quorum`` results to arrive, and kills whatever is still
    running at quorum (or at ``deadline_s``, whichever comes first).
    Non-accepted stations accrue **staleness**: when a stale station's
    update finally lands in a later round, it participates discounted by
    ``staleness_discount ** staleness`` — the standard FedBuff weighting
    that keeps slow-but-honest contributors in the model without letting
    their stale gradients drag it backwards.

    The discount rides the existing participation-mask seam
    (:meth:`FedAvg.async_round` folds it into ``mask``), so the jitted
    round program is byte-identical to the synchronous one: compression
    error-feedback still waits on mask==0 stations, learning stats stay
    participation-aware, and no new traced signature is introduced.
    """

    quorum: int                      # K: accept the first K results
    over_select: int = 1             # m: dispatch K + m stations
    staleness_discount: float = 0.5  # weight multiplier per round of staleness
    deadline_s: float = 30.0         # hard per-round wall-clock cap

    def validate(self) -> None:
        if self.quorum < 1:
            raise ValueError("AsyncRoundSpec.quorum must be >= 1")
        if self.over_select < 0:
            raise ValueError("AsyncRoundSpec.over_select must be >= 0")
        if not (0.0 < self.staleness_discount <= 1.0):
            raise ValueError(
                "AsyncRoundSpec.staleness_discount must be in (0, 1]"
            )
        if self.deadline_s <= 0:
            raise ValueError("AsyncRoundSpec.deadline_s must be > 0")

    @property
    def n_select(self) -> int:
        return self.quorum + self.over_select

    def staleness_weights(self, staleness: Any) -> jax.Array:
        """Per-station multiplicative discount ``discount ** staleness``
        for a ``[S]`` staleness vector (rounds since the station last
        contributed an accepted update)."""
        return jnp.power(
            jnp.asarray(self.staleness_discount, jnp.float32),
            jnp.asarray(staleness, jnp.float32),
        )


class FedAvg:
    """Compiles and runs federated-averaging rounds on a FederationMesh."""

    def __init__(self, mesh: FederationMesh, spec: FedAvgSpec):
        self.mesh = mesh
        self.spec = spec
        if spec.compressor is not None:
            spec.compressor.validate()
        # an identity compressor (no top-k, no int8) is a no-op: skip the
        # flat-pack round-trip entirely rather than paying it for nothing
        self._compressing = (
            spec.compressor is not None and not spec.compressor.identity
        )
        self.server_opt = spec.server_optimizer or optax.sgd(1.0)
        # what gather_path() weighs the packed table against, and whether
        # the program is compiled for a TPU (the streamed kernel's)
        self._bytes_limit = _device_bytes_limit(mesh.mesh.devices.flat[0])
        self._platform = mesh.mesh.devices.flat[0].platform
        # optional learning-plane sink (attach_history): when set, every
        # round()/run_rounds() host-records its stats into it
        self.history: Any = None
        # The three programs dispatch through the device observatory
        # (runtime.profiling): every lowering/compile is a device.compile
        # span + v6t_jit_* telemetry, and a shape-wobbling caller shows up
        # as a named retrace instead of silent slow rounds. round() keeps
        # its inputs: its callers step several times from one init.
        self._round = observed_jit("fedavg.round", self._round_impl)
        # n_rounds is a SWEEP static: callers legitimately compile the
        # fused program at several K values (warmup K=1, production K=32,
        # a tail-flush K=7). The observatory counts those as
        # static_sweeps, not retraces — a K sweep must not trip
        # recompile_storm (docs/device_speed.md "K-selection").
        # The fused programs donate params and opt_state: XLA updates the
        # scan carry in place instead of double-buffering model + moments
        # for the whole run. (The key is not donated: it is split inside
        # and no output has its type, so XLA could never reuse the buffer.)
        self._run = observed_jit(
            "fedavg.run_rounds", self._run_impl,
            static_argnames=("n_rounds",),
            sweep_statics=("n_rounds",),
            donate_argnums=(0, 1),  # params, opt_state
        )
        # fused buffered-async runner: staleness rides the scan carry so K
        # async rounds (accept masks + FedBuff discounting) are one
        # dispatch, composing with compression EF exactly like _run_impl.
        self._run_async = observed_jit(
            "fedavg.run_rounds_async", self._run_async_impl,
            static_argnames=("n_rounds",),
            sweep_statics=("n_rounds",),
            donate_argnums=(0, 1, 8),  # params, opt_state, staleness
        )

    # ------------------------------------------------------------ local step
    def gather_path(self, stacked_x: Any, stacked_y: Any) -> str:
        """How a local step fetches its minibatch from these tables:
        ``"packed"`` (one gather per step over rows that carry their label),
        ``"streamed"`` (the same rows, the table streamed through VMEM once
        a step) or ``"separate"`` (a gather of ``x`` and one of ``y``). Read
        from what the program sees when it is traced, and from nothing
        else: shapes, dtypes, the devices' platform and, where the backend
        reports one, the device's ``bytes_limit``. The packed table is a
        second copy of the data for the length of a dispatch, so it is built
        only where table and copy together take at most half of a device's
        memory (the other half is the step's); and only where ``y``'s
        elements are as wide as ``x``'s, so that labels and features ride
        side by side as unsigned integers of that width, bit for bit. The
        gather costs per index, not per byte (docs/device_speed.md "One
        gather per local step"); where the packed rows are 32-bit words,
        the program is compiled for a TPU and streaming a station's table
        costs less than a batch of indices (`_streams`), they are
        streamed."""
        xd, yd = jnp.dtype(stacked_x.dtype), jnp.dtype(stacked_y.dtype)
        if not (_viewable(xd) and _viewable(yd) and xd.itemsize == yd.itemsize):
            return "separate"
        if self._bytes_limit is not None:
            # a device's share of the table, and as much again for the copy
            table = xd.itemsize * (stacked_x.size + stacked_y.size)
            if 2 * (table // self.mesh.station_axis_size) > self._bytes_limit // 2:
                return "separate"
        if self._streams(stacked_x, stacked_y):
            return "streamed"
        return "packed"

    def _streams(self, stacked_x: Any, stacked_y: Any) -> bool:
        """Whether the packed rows are streamed: compiled for a TPU (the
        kernel is not interpreted), rows of 32-bit words, a batch and its
        indices that fit the kernel's VMEM and SMEM, and a station's table,
        its rows padded to whole lane tiles, that moves at HBM's bandwidth
        in less time than the gather takes for the batch's indices."""
        if self._platform != "tpu" or jnp.dtype(stacked_x.dtype).itemsize != 4:
            return False
        n_pad = stacked_x.shape[1]
        # a packed row: a row of x and its label
        words = math.prod(stacked_x.shape[2:]) + math.prod(stacked_y.shape[2:])
        batch = self.spec.batch_size
        if not SG.fits(batch, words, STREAM_BLOCK_ROWS):
            return False
        stream_ns = n_pad * 4 * SG.padded_width(words) / HBM_BYTES_PER_NS
        return stream_ns < batch * PER_INDEX_NS

    def _gather_attrs(self, stacked_x: Any, stacked_y: Any) -> dict[str, Any]:
        """What the ``engine.call`` span says of the minibatch path: the
        path, and where it is streamed the kernel's block of table rows and
        the blocks a station's table is streamed in (host integers)."""
        path = self.gather_path(stacked_x, stacked_y)
        if path != "streamed":
            return {"gather": path}
        n_pad = stacked_x.shape[1]
        rows = SG.kernel_rows(n_pad, STREAM_BLOCK_ROWS)
        return {"gather": path, "gather_block_rows": rows,
                "gather_blocks": -(-n_pad // rows)}

    def _minibatch_source(
        self, stacked_x: jax.Array, stacked_y: jax.Array
    ) -> tuple[tuple[jax.Array, ...], Callable[..., tuple[jax.Array, jax.Array]]]:
        """``(tables, take)``: the stacked ``[S, n_pad, ...]`` arrays a
        local step gathers from, and ``take(*station_tables, idx) -> (bx,
        by)`` for one station's share of them. Packed: one table ``[S,
        n_pad, width + label_width]`` built here (once per dispatch: the
        callers stand outside the scan over rounds) and one gather, whose
        rows are split and viewed back. The values ``loss_fn`` receives are
        bit for bit those of the two gathers. Streamed: the same table, its
        rows padded to whole lane tiles, and a step's rows fetched in table
        order by `ops.stream_gather`: the same rows, reordered."""
        path = self.gather_path(stacked_x, stacked_y)
        if path == "separate":
            return (stacked_x, stacked_y), lambda x, y, idx: (
                jnp.take(x, idx, axis=0), jnp.take(y, idx, axis=0)
            )
        x_row, y_row = stacked_x.shape[2:], stacked_y.shape[2:]
        x_dtype, y_dtype = stacked_x.dtype, stacked_y.dtype
        # Features and labels ride as unsigned integers of their width:
        # nothing on the way rounds, flushes or canonicalises an integer.
        # (Float columns do not keep an int32 label on the TPU: it joins
        # columns with a float maximum, and labels 0..9, denormals when
        # viewed as float32, came back as 0.)
        carrier = jnp.dtype(f"uint{8 * x_dtype.itemsize}")
        s, n_pad = stacked_x.shape[:2]
        width, label_width = math.prod(x_row), math.prod(y_row)
        # streamed, the kernel's rows are whole lane tiles: the pack writes
        # them so, zeros after the label
        pad = (SG.padded_width(width + label_width) - width - label_width
               if path == "streamed" else 0)
        with jax.named_scope("pack_table"):
            table = jnp.concatenate([
                jax.lax.bitcast_convert_type(a, carrier).reshape(s, n_pad, -1)
                for a in (stacked_x, stacked_y)
            ] + ([jnp.zeros((s, n_pad, pad), carrier)] if pad else []),
                axis=-1)
        interpret = self._platform != "tpu"

        def take(rows: jax.Array, idx: jax.Array):
            if path == "streamed":
                # The batch is a multiset to the loss (unit weights, a mean
                # over rows): fetched in table order, only the order of a
                # float sum changes.
                batch = SG.stream_gather(rows, jnp.sort(idx),
                                         block_rows=STREAM_BLOCK_ROWS,
                                         interpret=interpret)
            else:
                # idx < safe_count <= n_pad by construction: no row can be
                # out of range, so the gather is told not to guard against it
                batch = jnp.take(rows, idx, axis=0, mode="clip")
            if label_width == 1:
                # the one label column read as a reduction over the row,
                # a pass like the loss's own (0.7 ms a step on the v5e):
                # sliced off, a column one element wide is first spread
                # over the lanes and then copied together (2.3 ms)
                lane = jax.lax.broadcasted_iota(jnp.int32, batch.shape, 1)
                by = jnp.max(jnp.where(lane == width, batch, 0), axis=1)
            else:
                by = batch[:, width:width + label_width]
            return (
                jax.lax.bitcast_convert_type(batch[:, :width], x_dtype)
                .reshape(-1, *x_row),
                jax.lax.bitcast_convert_type(by, y_dtype).reshape(-1, *y_row),
            )

        return (table,), take

    def _local_update(
        self,
        tables: tuple[jax.Array, ...],  # this station's [n_pad, ...] share
        count: jax.Array,      # [] true example count
        station_id: jax.Array, # [] index for per-station RNG
        params: Pytree,        # replicated global model
        round_key: jax.Array,  # replicated per-round RNG key
        *,
        take: Callable[..., tuple[jax.Array, jax.Array]],
    ) -> tuple[Pytree, jax.Array]:
        """`local_steps` of minibatch SGD from the global params; returns
        (delta, mean loss). Runs per-station inside fed_map, on the tables
        and the ``take`` of ``_minibatch_source``."""
        spec = self.spec
        key = jax.random.fold_in(round_key, station_id)
        # Sampling bound: padded rows are never drawn because idx < count.
        safe_count = jnp.maximum(count.astype(jnp.int32), 1)

        def sgd_step(p: Pytree, step_key: jax.Array):
            with jax.named_scope("gather"):
                idx = jax.random.randint(
                    step_key, (spec.batch_size,), 0, safe_count
                )
                bx, by = take(*tables, idx)
            w = jnp.ones((spec.batch_size,), jnp.float32)
            with jax.named_scope("loss_grad"):
                loss, grads = jax.value_and_grad(spec.loss_fn)(p, bx, by, w)
            p = jax.tree.map(lambda a, g: a - spec.local_lr * g, p, grads)
            return p, loss

        step_keys = jax.random.split(key, spec.local_steps)
        new_params, losses = jax.lax.scan(sgd_step, params, step_keys)
        delta = jax.tree.map(lambda n, o: n - o, new_params, params)
        return delta, jnp.mean(losses)

    # ----------------------------------------------------------------- round
    def _round_impl(
        self,
        params: Pytree,
        opt_state: Any,
        stacked_x: jax.Array,   # [S, n_pad, ...]
        stacked_y: jax.Array,   # [S, n_pad, ...]
        counts: jax.Array,      # [S]
        mask: jax.Array,        # [S] participation (1.0 = in this round)
        round_key: jax.Array,
    ):
        """``round()``'s program: the minibatch source, then one round."""
        tables, take = self._minibatch_source(stacked_x, stacked_y)
        return self._one_round(
            params, opt_state, tables, take, counts, mask, round_key
        )

    def _one_round(
        self,
        params: Pytree,
        opt_state: Any,
        tables: tuple[jax.Array, ...],  # of _minibatch_source, with its take
        take: Callable[..., tuple[jax.Array, jax.Array]],
        counts: jax.Array,
        mask: jax.Array,
        round_key: jax.Array,
    ):
        station_ids = jnp.arange(self.mesh.n_stations)
        with jax.named_scope("local_train"):
            deltas, losses = self.mesh.fed_map(
                functools.partial(self._local_update, take=take),
                tables,
                counts,
                station_ids,
                replicated_args=(params, round_key),
            )
        weights = counts * mask
        # Gradient compression at the delta-exchange boundary: the
        # aggregation below consumes the DECOMPRESSED per-station deltas —
        # exactly what a real server reconstructs from each station's
        # compressed uplink — and the per-station error-feedback
        # accumulators ride the optimizer-state carry to the next round.
        ef = None
        flat = None
        if self._compressing:
            server_state = opt_state["server"]
            with jax.named_scope("compress"):
                deltas, ef, flat = self._compress_deltas(
                    deltas, opt_state["ef"], round_key, mask
                )
        else:
            server_state = opt_state
        # learning-plane stats at the flat-pack seam, BEFORE the server
        # update: computed on the (reconstructed, post-decompression)
        # deltas the aggregation actually consumes, by one shared formula
        # independent of the update mode — replicated and scattered rounds
        # report fp32-identical stats (bench parity assertion). When
        # compressing, the flat matrix from the compression pass is reused.
        stats: dict[str, Any] = {}
        if self.spec.learning_stats:
            with jax.named_scope("learning_stats"):
                if flat is None:
                    flat = flatten_stacked(deltas)
                stats = station_update_stats(flat, weights=weights, ef=ef)
        if self.spec.shard_server_update:
            with jax.named_scope("server_update"):
                params, server_state = self._sharded_server_update(
                    params, server_state, deltas, weights
                )
        else:
            mean_delta = fed_mean(deltas, weights=weights)
            with jax.named_scope("server_update"):
                # Server update on the pseudo-gradient (negative mean delta).
                pseudo_grad = jax.tree.map(lambda d: -d, mean_delta)
                updates, server_state = self.server_opt.update(
                    pseudo_grad, server_state, params
                )
                params = optax.apply_updates(params, updates)
        round_loss = fed_mean(losses, weights=weights)
        new_state = (
            {"server": server_state, "ef": ef}
            if self._compressing
            else server_state
        )
        return params, new_state, round_loss, stats

    def _compress_deltas(
        self, deltas: Pytree, ef: jax.Array, round_key: jax.Array,
        mask: jax.Array,
    ) -> tuple[Pytree, jax.Array, jax.Array]:
        """Per-station compress -> decompress of the delta uplink (the
        flat-pack seam): error feedback re-injected before compressing,
        ``comm_dtype`` applied as the pre-quantization cast (cast, then
        quantize). Returns the reconstructed deltas + new EF [S, N] + the
        reconstructed flat [S, N] matrix (reused by the learning-stats
        pass so the round never flat-packs twice).
        Pure/traced — runs inside the round program; wire accounting
        happens host-side in round()/run_rounds().

        A masked-out station never ships anything, so its accumulator
        must WAIT, not update: under SPMD it computes a (fictional) delta
        like everyone else, but both that delta and the would-be shipped
        mass are discarded — its EF row carries over unchanged (the
        docs/compression.md "mass is never lost" contract;
        tests/test_compression.py::test_masked_station_ef_waits)."""
        template = jax.tree.map(lambda x: x[0], deltas)
        flat = flatten_stacked(deltas)
        # a key stream disjoint from _local_update's fold_in(key, station):
        # station ids are < n_stations, 2**31 - 1 never is
        keys = jax.random.split(
            jax.random.fold_in(round_key, 2**31 - 1), self.mesh.n_stations
        )
        _, hat, new_ef = compress_stacked(
            self.spec.compressor, flat, ef, keys,
            cast_dtype=self.spec.comm_dtype,
        )
        participating = (mask != 0).reshape(-1, 1)
        new_ef = jnp.where(participating, new_ef, ef)
        return unflatten_stacked(template, hat), new_ef, hat

    def _sharded_server_update(
        self, params: Pytree, opt_state: Any, deltas: Pytree,
        weights: jax.Array,
    ) -> tuple[Pytree, Any]:
        """Reduce-scatter -> shard-local optax update -> all-gather.

        The mean delta is never materialized in full: each slot receives
        only its 1/D shard of the flat pseudo-gradient (psum_scatter),
        applies the server optimizer against its 1/D flat param shard —
        moments in ``opt_state`` are flat [N_pad] vectors sharded the same
        way (ZeRO-1) — and ONE all-gather re-replicates the updated params
        for the next round's broadcast.
        """
        mesh = self.mesh
        grad_shard = jax.tree.map(
            lambda d: -d,
            fed_mean_scattered(
                mesh, deltas, weights=weights,
                comm_dtype=self.spec.comm_dtype,
            ),
        )
        flat_params = flatten_tree(params)
        n_pad = padded_flat_size(flat_params.size, mesh.station_axis_size)
        flat_params = jnp.pad(flat_params, (0, n_pad - flat_params.size))
        # Hold only this slot's shard live: the update below is elementwise,
        # so GSPMD keeps everything downstream 1/D-sharded too.
        flat_params = jax.lax.with_sharding_constraint(
            flat_params, mesh.station_sharding()
        )
        updates, opt_state = self.server_opt.update(
            grad_shard, opt_state, flat_params
        )
        new_flat = all_gather_stations(
            mesh, optax.apply_updates(flat_params, updates)
        )
        return unflatten_like(params, new_flat), opt_state

    # ------------------------------------------------------------ public API
    def init(self, params: Pytree) -> Any:
        """Server-optimizer state for ``params``.

        With ``shard_server_update`` the state is built over the FLAT padded
        f32 param vector (moments are [N_pad] arrays, placed sharded over
        the station axis) — checkpoints of the two modes are therefore NOT
        interchangeable. With a ``compressor``, the returned state is a
        ``{"server": <optimizer state>, "ef": [S, N]}`` dict carrying each
        station's zero-initialized error-feedback accumulator (sharded over
        the station axis) — again not checkpoint-compatible with the
        uncompressed modes.
        """
        if self.spec.shard_server_update:
            flat = flatten_tree(params)
            n_pad = padded_flat_size(flat.size, self.mesh.station_axis_size)
            state = self.server_opt.init(jnp.pad(flat, (0, n_pad - flat.size)))
        else:
            state = self.server_opt.init(params)
        if self._compressing:
            ef = jnp.zeros(
                (self.mesh.n_stations, flat_size(params)), jnp.float32,
                device=self.mesh.station_sharding(),
            )
            state = {"server": state, "ef": ef}
        return self._place_state(state, flat_size(params))

    def _place_state(self, state: Any, n_flat: int) -> Any:
        """Commit an optimizer state to the shardings a round returns it
        in: ZeRO-1 flat moments and error-feedback rows over the station
        axis, every other leaf on all devices. A state that came out of a
        round is returned as is; one from ``optax`` or a checkpoint is
        placed here, once."""
        mesh = self.mesh
        if self._compressing:
            return {
                "server": self._place_server_state(state["server"], n_flat),
                "ef": mesh.shard_stacked(state["ef"]),
            }
        return self._place_server_state(state, n_flat)

    def _place_server_state(self, state: Any, n_flat: int) -> Any:
        mesh = self.mesh
        if not self.spec.shard_server_update:
            return mesh.replicate(state)
        n_pad = padded_flat_size(n_flat, mesh.station_axis_size)
        scattered, whole = mesh.station_sharding(), mesh.replicated_sharding()
        return jax.tree.map(
            lambda x: jax.device_put(
                x, scattered if jnp.shape(x) == (n_pad,) else whole
            ),
            state,
        )

    def _place(
        self, params: Pytree, opt_state: Any, counts: Any, mask: Any,
        key: jax.Array,
    ) -> tuple[Pytree, Any, jax.Array, jax.Array, jax.Array]:
        """Commit what the round program carries or broadcasts to the
        mesh, at the engine's entry. jit (and the observatory with it)
        keys on committed shardings: fresh unplaced params and the same
        params as a round returns them are two signatures, and the second
        would be a second full compile of the fused program on every cold
        start. Placed here, the first signature is the steady-state one."""
        rep = self.mesh.replicate
        return (
            rep(params),
            self._place_state(opt_state, flat_size(params)),
            rep(jnp.asarray(counts)),
            rep(jnp.asarray(mask)),
            rep(key),
        )

    def round(
        self,
        params: Pytree,
        opt_state: Any,
        stacked_x: jax.Array,
        stacked_y: jax.Array,
        counts: jax.Array,
        key: jax.Array,
        mask: jax.Array | None = None,
    ):
        """One federated round. Returns (params, opt_state, mean_loss,
        stats) — ``stats`` is the learning-plane dict from
        ``collectives.station_update_stats`` ({} when
        ``spec.learning_stats`` is off); feed it to a
        ``runtime.learning.RoundHistory`` to arm convergence tracking and
        the anomalous-station watchdog rules."""
        with engine_call(
            "fedavg.round", 1, **self._gather_attrs(stacked_x, stacked_y)
        ):
            if mask is None:
                mask = jnp.ones_like(counts)
            params, opt_state, counts, mask, key = self._place(
                params, opt_state, counts, mask, key
            )
            self._record_wire(params)
            out = self._round(
                params, opt_state, stacked_x, stacked_y, counts, mask, key
            )
            self._record_history(out[2], out[3], rounds_per_dispatch=1)
            return out

    def async_round(
        self,
        params: Pytree,
        opt_state: Any,
        stacked_x: jax.Array,
        stacked_y: jax.Array,
        counts: jax.Array,
        key: jax.Array,
        accept_mask: jax.Array,
        staleness: jax.Array,
        spec: AsyncRoundSpec,
        mask: jax.Array | None = None,
    ):
        """One buffered-async round: only ``accept_mask`` stations (the
        first-K arrivals, from ``Federation.run_buffered`` or a
        simulator) contribute, each discounted by
        ``spec.staleness_discount ** staleness``.

        Implemented entirely at the participation-mask seam — the
        effective mask is ``mask * accept_mask * discount`` and feeds the
        SAME jitted round program as :meth:`round` (``weights = counts *
        mask`` inside ``_one_round``), so nothing retraces and
        compression EF / learning stats compose unchanged. A fractional
        mask weights the aggregation; EF-wait and stats participation key
        on ``mask != 0``, which is exactly "the station shipped an
        update this round"."""
        spec.validate()
        effective = (
            jnp.asarray(accept_mask, jnp.float32)
            * spec.staleness_weights(staleness)
        )
        if mask is not None:
            effective = effective * jnp.asarray(mask, jnp.float32)
        return self.round(
            params, opt_state, stacked_x, stacked_y, counts, key,
            mask=effective,
        )

    def _record_wire(self, params: Pytree, n_rounds: int = 1) -> None:
        """Host-side wire accounting for the compressed delta uplink
        (``v6t_compress_*`` series) — metadata-only, never touches device
        data and never runs inside the traced round."""
        if self._compressing:
            record_round_telemetry(
                self.spec.compressor, flat_size(params),
                self.mesh.n_stations, rounds=n_rounds,
            )

    def compression_stats(self, params: Pytree) -> dict[str, Any] | None:
        """Static per-round wire accounting of the delta uplink: raw vs
        compressed bytes across all stations + the reduction ratio (the
        bench's acceptance numbers). None without an effective compressor.
        Metadata-only — safe to call around a compiled run."""
        if not self._compressing:
            return None
        n = flat_size(params)
        spec = self.spec.compressor
        s = self.mesh.n_stations
        return {
            "n_params": n,
            "raw_bytes_per_round": 4 * n * s,
            "wire_bytes_per_round": spec.wire_nbytes(n) * s,
            "reduction": round(spec.ratio(n), 2),
        }

    def run_rounds(
        self,
        params: Pytree,
        stacked_x: jax.Array,
        stacked_y: jax.Array,
        counts: jax.Array,
        key: jax.Array,
        n_rounds: int,
        mask: jax.Array | None = None,
        opt_state: Any = None,
    ):
        """`n_rounds` federated rounds as ONE compiled program (lax.scan) —
        the FUSED fast path (docs/device_speed.md): per-station training,
        aggregation, compression EF and learning stats all stay on device
        with zero host round-trips between rounds. ``mask`` may be ``[S]``
        (one roster for the whole dispatch) or ``[n_rounds, S]`` (a
        per-round roster riding the scan xs). Returns (params, opt_state,
        losses[n], stats) — ``stats`` holds the per-round learning-plane
        arrays stacked over the scan axis (``station_norm``/
        ``station_cos`` ``[n, S]``, ``update_norm`` ``[n]``; {} when
        ``spec.learning_stats`` is off).

        Pass the ``opt_state`` from a checkpoint to CONTINUE a run (resuming
        FedAdam etc. without resetting server-optimizer moments); omitted, a
        fresh optimizer state is initialized.

        The ``params`` and ``opt_state`` handed in are CONSUMED: the
        program donates them, so XLA updates the scan carry in place
        instead of double-buffering model + moments. Go on with what is
        returned; a caller who wants the old state does
        ``jax.tree.map(jnp.copy, ...)`` first. ``round()`` keeps its inputs
        (tests/test_scattered_update.py pins both contracts).
        """
        with engine_call(
            "fedavg.run_rounds", n_rounds,
            **self._gather_attrs(stacked_x, stacked_y),
        ):
            if mask is None:
                mask = jnp.ones_like(counts)
            if opt_state is None:
                opt_state = self.init(params)
            params, opt_state, counts, mask, key = self._place(
                params, opt_state, counts, mask, key
            )
            self._record_wire(params, n_rounds=n_rounds)
            self._record_fused(n_rounds)
            out = self._run(
                params, opt_state, stacked_x, stacked_y, counts, mask, key,
                n_rounds=n_rounds,
            )
            self._record_history(out[2], out[3], rounds_per_dispatch=n_rounds)
            return out

    def run_rounds_async(
        self,
        params: Pytree,
        stacked_x: jax.Array,
        stacked_y: jax.Array,
        counts: jax.Array,
        key: jax.Array,
        n_rounds: int,
        accept_masks: jax.Array,
        spec: AsyncRoundSpec,
        staleness: jax.Array | None = None,
        mask: jax.Array | None = None,
        opt_state: Any = None,
    ):
        """``n_rounds`` buffered-async rounds as ONE fused program: the
        FedBuff staleness vector rides the scan carry, so K rounds of
        :meth:`async_round` semantics (accept-mask weighting discounted
        by ``spec.staleness_discount ** staleness``) run with zero host
        round-trips. ``accept_masks`` is ``[n_rounds, S]`` (each fused
        round's first-K arrivals, e.g. from a quorum simulator) or ``[S]``
        (same acceptance every round). Returns (params, opt_state,
        staleness[S], losses[n], stats) — the final staleness vector
        continues into the next fused dispatch, exactly like the host
        bookkeeping it replaces. ``params``, ``opt_state`` and
        ``staleness`` are consumed, as in :meth:`run_rounds`."""
        spec.validate()
        with engine_call(
            "fedavg.run_rounds_async", n_rounds,
            **self._gather_attrs(stacked_x, stacked_y),
        ):
            if mask is None:
                mask = jnp.ones_like(counts)
            if staleness is None:
                staleness = jnp.zeros_like(counts, dtype=jnp.float32)
            if opt_state is None:
                opt_state = self.init(params)
            params, opt_state, counts, mask, key = self._place(
                params, opt_state, counts, mask, key
            )
            self._record_wire(params, n_rounds=n_rounds)
            self._record_fused(n_rounds)
            out = self._run_async(
                params, opt_state, stacked_x, stacked_y, counts, mask, key,
                accept_masks,
                self.mesh.replicate(jnp.asarray(staleness, jnp.float32)),
                jnp.float32(spec.staleness_discount), n_rounds=n_rounds,
            )
            self._record_history(out[3], out[4], rounds_per_dispatch=n_rounds)
            return out

    def _record_fused(self, n_rounds: int) -> None:
        """Fused-program telemetry (host-side, metadata only): how many
        logical rounds each dispatch amortizes — the `v6t_fused_*` series
        docs/device_speed.md reads beside rounds_per_sec."""
        REGISTRY.counter("v6t_fused_dispatches_total").inc()
        REGISTRY.counter("v6t_fused_rounds_total").inc(n_rounds)
        REGISTRY.gauge("v6t_fused_rounds_per_dispatch").set(n_rounds)

    # --------------------------------------------------------- learning plane
    def attach_history(self, history: Any) -> Any:
        """Attach a ``runtime.learning.RoundHistory`` (or a registry key —
        resolved through the process ``LEARNING`` registry): every
        round()/run_rounds() call then host-records its stats into it
        (telemetry gauges, flight notes, a ``learning.round`` span on the
        ambient trace — the learning-plane observatory). Recording pulls
        the tiny [S] stat vectors to host, which BLOCKS on the round's
        completion — attach when observing, not when racing dispatches.
        Returns the history. Pass None to detach."""
        if history is not None and not hasattr(history, "record_engine"):
            from vantage6_tpu.runtime.learning import LEARNING

            history = LEARNING.history(history)
        self.history = history
        return history

    def _record_history(
        self, losses: Any, stats: Any, rounds_per_dispatch: int = 1
    ) -> None:
        history = getattr(self, "history", None)
        if history is None or not stats:
            return
        try:
            history.record_engine(
                losses, stats, rounds_per_dispatch=rounds_per_dispatch
            )
        except Exception:  # observability must never fail the round
            import logging

            logging.getLogger("vantage6_tpu/fedavg").debug(
                "round-history recording failed", exc_info=True
            )

    def _run_impl(
        self, params, opt_state, stacked_x, stacked_y, counts, mask, key,
        *, n_rounds: int
    ):
        # the participation mask rides the scan xs (one [S] row per
        # round), not the closure: a [S] mask broadcasts to every round,
        # a [K, S] matrix gives each fused round its own roster — same
        # executable either way (rank is static), zero host round-trips
        masks = per_round_masks(mask, n_rounds)
        # once per dispatch, outside the loop over rounds
        tables, take = self._minibatch_source(stacked_x, stacked_y)

        def body(carry, xs):
            round_key, m = xs
            p, s = carry
            p, s, loss, stats = self._one_round(
                p, s, tables, take, counts, m, round_key
            )
            return (p, s), (loss, stats)

        keys = jax.random.split(key, n_rounds)
        (params, opt_state), (losses, stats) = jax.lax.scan(
            body, (params, opt_state), (keys, masks)
        )
        return params, opt_state, losses, stats

    def _run_async_impl(
        self, params, opt_state, stacked_x, stacked_y, counts, mask, key,
        accept_masks, staleness, discount, *, n_rounds: int
    ):
        """K buffered-async rounds as ONE program: FedBuff staleness
        (rounds since each station's last accepted update) rides the scan
        CARRY, so the per-round effective mask ``accept * discount**stale
        * mask`` — exactly :meth:`async_round`'s seam — is computed
        on-device between fused rounds with no host in the loop."""
        masks = per_round_masks(mask, n_rounds)
        accepts = per_round_masks(accept_masks, n_rounds)
        disc = jnp.asarray(discount, jnp.float32)
        tables, take = self._minibatch_source(stacked_x, stacked_y)

        def body(carry, xs):
            p, s, stale = carry
            round_key, m, accept = xs
            eff = accept * jnp.power(disc, stale) * m
            p, s, loss, stats = self._one_round(
                p, s, tables, take, counts, eff, round_key
            )
            # accepted stations reset; everyone else ages one round —
            # the same bookkeeping Federation.run_buffered does host-side
            stale = jnp.where(accept != 0, 0.0, stale + 1.0)
            return (p, s, stale), (loss, stats)

        keys = jax.random.split(key, n_rounds)
        init = (params, opt_state, jnp.asarray(staleness, jnp.float32))
        (params, opt_state, staleness), (losses, stats) = jax.lax.scan(
            body, init, (keys, masks, accepts)
        )
        # back as it was handed in, on every device: the donated buffer
        # is then the one it returns in, and the next dispatch takes it
        # as it is
        staleness = jax.lax.with_sharding_constraint(
            staleness, self.mesh.replicated_sharding()
        )
        return params, opt_state, staleness, losses, stats

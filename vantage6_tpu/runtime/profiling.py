"""Device performance observatory: compile/retrace telemetry, XLA
memory & cost introspection, and on-demand profiling windows.

The task plane became observable in two layers (PR-5 tracing + telemetry,
PR-8 watchdog + flight recorder); the DEVICE plane stayed a black box — a
silent retrace storm or creeping executable-cache leak showed up only as
"rounds got slower", with nothing naming the cause. This module is the
attribution layer for everything below `jax.jit`:

- **Observed jit** — :func:`observed_jit` wraps a function the way
  ``jax.jit`` does, but owns the signature→executable cache so every
  lowering+compile is an EVENT it can measure: each one is recorded as a
  ``device.compile`` span (parented on the active trace when there is
  one) carrying lowering and compile wall time plus the compiled
  program's ``memory_analysis()`` (temp/argument/output bytes) and
  ``cost_analysis()`` (flops, bytes accessed), and counted in the
  ``v6t_jit_*`` telemetry series. Every launch is a ``device.launch``
  span (``function``, ``n_buffers`` = the array leaves handed over) around
  signature keying and the call into the executable, and nothing else; a
  launch that compiles has its ``device.compile`` span as a child.
- **Device scopes** — :data:`DEVICE_SCOPES` lists the ``jax.named_scope``
  names the round programs open at their layer boundaries, so that a
  device operation's metadata says which layer it belongs to.
- **Retrace registry** — a *retrace* is the same function name compiling
  against an abstract signature it has NEVER seen. The observatory names
  the differing leaf (shape/dtype before → after) in the compile span, a
  flight-recorder note (kind ``retrace``), and the watchdog feed the
  ``recompile_storm`` rule reads — the storm is detected *and attributed*
  in one place. Recompiling a signature the bounded executable cache
  evicted is marked ``evicted_recompile`` on the span instead: real cost,
  but cache churn, not a storm.
- **Engine-cache counters** — the ``mesh.fingerprint()``-keyed runner
  caches (glm/quantile/device_engine) report hits/misses/entries through
  :func:`engine_cache_event`, emitted here as the ``v6t_engine_cache_*``
  series, so executable-cache effectiveness is a number, not a hope.
- **Per-device memory** — a telemetry collector publishes bytes-in-use /
  peak across ALL local devices (``v6t_device_mem_*``), the series the
  ``device_mem_growth`` watchdog rule trends.
- **Profile windows** — :func:`profile_window` runs a bounded
  ``jax.profiler`` session on demand (``POST /api/debug/profile``),
  registers the artifact path in the flight recorder, and records a
  ``device.profile`` span linked to the requesting trace.

Dispatch semantics: an observed function behaves exactly like its
``jax.jit`` twin. Called under an outer trace (leaves are tracers) it
inlines like any jitted function; called with a known signature it
dispatches straight to the cached executable. The signature agrees with
jit's own cache (shape, dtype, weak type, committed sharding), so one
signature is one executable and nothing is ever compiled twice: a
``lower()``/``compile()`` failure (a Mosaic refusal, a compile OOM) and
an executable that rejects its arguments both RAISE, exactly as the jit
twin would — the device's refusal is never hidden behind a second
compile. Only a call the observatory cannot key at all (an unhashable
static) forwards to plain jit, counted in ``v6t_jit_fallbacks_total``.
Disable the whole layer with ``V6T_DEVICE_OBS=0`` (calls forward to
``jax.jit`` untouched).
"""
from __future__ import annotations

import os
import threading
import time
import weakref
from collections import OrderedDict, deque
from typing import Any, Callable, NamedTuple

import jax

from vantage6_tpu.common.env import env_int
from vantage6_tpu.common.telemetry import REGISTRY
from vantage6_tpu.runtime.tracing import TRACER

__all__ = [
    "DEVICE_OBS",
    "DEVICE_SCOPES",
    "ObservedFunction",
    "ProfileBusyError",
    "RunnerCache",
    "device_launch",
    "engine_cache_event",
    "engine_call",
    "observed_jit",
    "profile_window",
]


# The ``jax.named_scope`` names of the round programs, outermost first: every
# device operation's metadata carries ``.../<scope>/...`` (a backward
# operation ``transpose(jvp(<scope>))``). Metadata only: the compiled program
# and the persistent cache's key are the same with and without them.
# fed/fedavg.py opens pack_table (once per dispatch), local_train (with
# gather and loss_grad inside it), compress, learning_stats and
# server_update; fed/collectives.py aggregate;
# workloads/fed_transformer.py local_train (with embed, attention, mlp and
# lm_head_loss inside it; a block with experts opens router, before
# attention, and experts where the dense one opens mlp) and server_update.
# Between them the block opens qkv (the product and the split into heads),
# rotary, attn_out (the output product, the norm after it, the residual add)
# and, around every norm, norms: these four only ever nest inside the scopes
# above or sit beside them, never around one, so what those read stays put.
# With learned sparse attention (ops/sparse_attention.py) the block opens
# indexer (its projections and scores), select (each query's kept keys) and
# indexer_loss beside attention.
DEVICE_SCOPES = (
    "pack_table", "local_train", "gather", "loss_grad", "embed", "loop",
    "attention", "mlp", "router", "experts", "lm_head_loss", "exit_gate",
    "indexer", "select", "indexer_loss",
    "qkv", "rotary", "attn_out", "norms",
    "compress", "learning_stats", "aggregate", "server_update",
)


def engine_call(engine: str, rounds: int, **attrs: Any):
    """The ``engine.call`` span: the whole host side of one call into a
    round engine (``fedavg.run_rounds``, ``fed_transformer.round``, ...),
    entry to return; the program is then enqueued, not done. It roots a
    trace when the caller is in none and joins the caller's otherwise. Its
    self time, less the ``device.launch`` under it, is the engine's own host
    work: placement, telemetry, history. ``attrs`` are what the engine knows
    of the program it is about to launch (``FedAvg``: ``gather``, the
    minibatch path its executable was built with)."""
    return TRACER.span(
        "engine.call", kind="engine",
        attrs={"engine": engine, "rounds": rounds, **attrs},
    )


def device_launch(
    function: str, n_buffers: int, n_donated: int | None = None
):
    """The ``device.launch`` span: the call into a compiled program and
    nothing else. ``n_buffers`` is the array leaves of the dynamic
    arguments, counted as arrays, not as per-chip shards; ``n_donated``,
    where the caller gives it, is those of them handed over as donated
    arguments (outputs may live in their buffers, so the launch need not
    allocate them)."""
    attrs = {"function": function, "n_buffers": n_buffers}
    if n_donated is not None:
        attrs["n_donated"] = n_donated
    return TRACER.span("device.launch", kind="device", attrs=attrs)


class _LeafSig(NamedTuple):
    """What ``jax.jit``'s own cache keys one argument on: shape, dtype,
    weak type and — for a COMMITTED array — its sharding. An uncommitted
    array (fresh ``jnp`` output, numpy, python scalar) carries ``None``:
    jit is free to place it, so where it happens to live is not part of
    the signature."""

    shape: tuple
    dtype: Any
    weak_type: bool
    sharding: Any

    def str_short(self) -> str:
        out = f"{self.dtype.name}[{','.join(map(str, self.shape))}]"
        if self.sharding is not None:
            out += f"@{getattr(self.sharding, 'spec', self.sharding)}"
        return out


def _abstractify(leaf: Any) -> Any:
    """Hashable signature of one leaf, in agreement with jit's cache
    (:class:`_LeafSig`); a type tag for a leaf jax cannot type (an exotic
    leaf must not crash the observatory — jit raises its own error)."""
    try:
        aval = jax.typeof(leaf)
    except TypeError:
        return ("opaque", type(leaf).__name__)
    sharding = (
        leaf.sharding
        if isinstance(leaf, jax.Array) and leaf.committed else None
    )
    return _LeafSig(
        aval.shape, aval.dtype, getattr(aval, "weak_type", False), sharding
    )


def _leaf_str(aval: Any) -> str:
    return aval.str_short() if isinstance(aval, _LeafSig) else str(aval)


def _signature_diff(
    old_paths: list[str], old_avals: tuple, new_paths: list[str],
    new_avals: tuple, old_statics: tuple = (), new_statics: tuple = (),
) -> str:
    """Name what changed between two abstract signatures — the one string
    an operator needs to find the shape-perturbing call site."""
    if len(old_avals) != len(new_avals):
        return (
            f"arity changed: {len(old_avals)} -> {len(new_avals)} leaves"
        )
    for path, a, b in zip(new_paths, old_avals, new_avals):
        if a != b:
            return f"{path or 'arg'}: {_leaf_str(a)} -> {_leaf_str(b)}"
    olds = dict(old_statics)
    for k, v in new_statics:
        if k not in olds:
            return f"static {k} added: {v!r}"
        if olds[k] != v:
            return f"static {k}: {olds[k]!r} -> {v!r}"
    return "signature changed (treedef)"


def _cost_summary(compiled: Any) -> dict[str, float]:
    """flops / bytes-accessed from ``cost_analysis()`` — tolerant of the
    per-version shape (list of dicts on 0.4.x, dict on newer, None on
    backends that don't report)."""
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if not isinstance(cost, dict):
        return {}
    out: dict[str, float] = {}
    for key, name in (("flops", "flops"), ("bytes accessed", "bytes_accessed")):
        v = cost.get(key)
        if isinstance(v, (int, float)):
            out[name] = float(v)
    return out


def _memory_summary(compiled: Any) -> dict[str, int]:
    """temp/argument/output/code bytes from ``memory_analysis()`` (absent
    on backends that don't report it)."""
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return {}
    if mem is None:
        return {}
    out: dict[str, int] = {}
    for attr, name in (
        ("temp_size_in_bytes", "temp_bytes"),
        ("argument_size_in_bytes", "argument_bytes"),
        ("output_size_in_bytes", "output_bytes"),
        ("generated_code_size_in_bytes", "generated_code_bytes"),
    ):
        v = getattr(mem, attr, None)
        if isinstance(v, (int, float)):
            out[name] = int(v)
    return out


class ObservedFunction:
    """One ``jax.jit`` entry point under observation (see module doc).

    Owns a bounded signature→compiled-executable map. A signature MISS is
    a compile event (measured, traced, counted); a miss on a warm
    function is additionally a RETRACE (named and reported) unless the
    signature was seen before and merely evicted. Statics
    follow jit's contract: ``static_argnums`` positionally,
    ``static_argnames`` by keyword — both join the signature key and are
    dropped from the compiled call (XLA bakes them in).
    """

    def __init__(
        self,
        name: str,
        fun: Callable[..., Any],
        *,
        static_argnums: tuple[int, ...] = (),
        static_argnames: tuple[str, ...] = (),
        sweep_statics: tuple[str, ...] = (),
        **jit_kwargs: Any,
    ):
        self.name = name
        self._static_argnums = tuple(static_argnums)
        self._static_argnames = tuple(static_argnames)
        # statics a caller legitimately SWEEPS (e.g. the fused round
        # program's n_rounds): a compile whose signature differs from a
        # previously seen one ONLY in these keys is a planned new
        # executable, not a retrace — it must not feed recompile_storm
        self._sweep_statics = frozenset(sweep_statics)
        jit_kw: dict[str, Any] = dict(jit_kwargs)
        if self._static_argnums:
            jit_kw["static_argnums"] = self._static_argnums
        if self._static_argnames:
            jit_kw["static_argnames"] = self._static_argnames
        self._jit = jax.jit(fun, **jit_kw)
        self._lock = threading.Lock()
        # serializes _compile: two threads racing the same NEW signature
        # must not both pay the XLA compile, and the loser must not
        # record a phantom "retrace" against an identical signature
        self._compile_lock = threading.Lock()
        # guarded-by: _lock — insertion-ordered for FIFO eviction
        self._sigs: "OrderedDict[tuple, Any]" = OrderedDict()
        # guarded-by: _lock — every signature EVER compiled (bounded,
        # keys only). Distinguishes a true retrace (genuinely new
        # signature — the storm the alert hunts) from recompiling one the
        # FIFO evicted: a workload legitimately rotating through more
        # live shapes than max_signatures pays the compile but must not
        # feed recompile_storm, or the observatory would alert on churn
        # it created itself.
        self._seen_sigs: "OrderedDict[tuple, None]" = OrderedDict()
        # guarded-by: _lock — signatures with sweep statics STRIPPED:
        # membership here means "this shape was seen at SOME swept static
        # value", the evidence that a new (avals, other-statics) miss is a
        # static sweep rather than a shape-perturbing caller
        self._seen_swept: "OrderedDict[tuple, None]" = OrderedDict()
        self._last_sig: tuple | None = None
        self._last_paths: list[str] = []
        self._last_avals: tuple = ()
        self._last_statics: tuple = ()
        self.compiles = 0
        self.retraces = 0
        self.static_sweeps = 0
        self.dispatches = 0
        self.fallbacks = 0
        self.evictions = 0
        self.last_compile: dict[str, Any] = {}

    # ------------------------------------------------------------ plumbing
    def lower(self, *args: Any, **kwargs: Any):
        """AOT escape hatch — identical to ``jax.jit(fun).lower``."""
        return self._jit.lower(*args, **kwargs)

    def _split(self, args: tuple, kwargs: dict) -> tuple[tuple, dict, tuple]:
        """(dynamic args, dynamic kwargs, hashable statics key)."""
        statics: list[tuple[str, Any]] = []
        dyn_args = []
        for i, a in enumerate(args):
            if i in self._static_argnums:
                statics.append((f"arg{i}", a))
            else:
                dyn_args.append(a)
        dyn_kwargs = {}
        for k, v in kwargs.items():
            if k in self._static_argnames:
                statics.append((k, v))
            else:
                dyn_kwargs[k] = v
        return tuple(dyn_args), dyn_kwargs, tuple(sorted(
            statics, key=lambda kv: kv[0]
        ))

    def _swept_key(self, key: tuple) -> tuple | None:
        """``key`` with the sweep statics stripped, or None when this
        function declares none (or the key carries none of them)."""
        if not self._sweep_statics:
            return None
        reduced = tuple(
            kv for kv in key[2] if kv[0] not in self._sweep_statics
        )
        if reduced == key[2]:  # no swept static present in this call
            return None
        return (key[0], key[1], reduced)

    # ------------------------------------------------------------ dispatch
    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        obs = DEVICE_OBS
        if not obs.enabled:
            return self._jit(*args, **kwargs)
        dyn_args, dyn_kwargs, statics = self._split(args, kwargs)
        leaves, treedef = jax.tree.flatten((dyn_args, dyn_kwargs))
        if any(isinstance(leaf, jax.core.Tracer) for leaf in leaves):
            # called inside an outer trace: inline like any jitted fn —
            # the OUTER entry point owns this compile's attribution
            return self._jit(*args, **kwargs)
        with device_launch(self.name, len(leaves)):
            avals = tuple(_abstractify(leaf) for leaf in leaves)
            try:
                key = (avals, treedef, statics)
                hash(key)
            except TypeError:
                # unhashable static (a list-valued kwarg, ...): nothing to
                # key on — forward to jit, which raises its own error
                self.fallbacks += 1
                REGISTRY.counter("v6t_jit_fallbacks_total").inc()
                return self._jit(*args, **kwargs)
            self.dispatches += 1
            with self._lock:
                compiled = self._sigs.get(key)
            if compiled is None:
                compiled = self._compile(key, args, kwargs, avals, dyn_args,
                                         dyn_kwargs)
            return compiled(*dyn_args, **dyn_kwargs)

    def _compile(
        self, key: tuple, args: tuple, kwargs: dict, avals: tuple,
        dyn_args: tuple, dyn_kwargs: dict,
    ) -> Any:
        """Measured lower+compile of one new signature: the
        ``device.compile`` span, the retrace naming, the telemetry.
        One compile at a time per function (compiles are rare; a loser
        of the dispatch race reuses the winner's executable)."""
        with self._compile_lock:
            with self._lock:
                cached = self._sigs.get(key)
            if cached is not None:
                return cached
            return self._compile_locked(
                key, args, kwargs, avals, dyn_args, dyn_kwargs
            )

    def _compile_locked(
        self, key: tuple, args: tuple, kwargs: dict, avals: tuple,
        dyn_args: tuple, dyn_kwargs: dict,
    ) -> Any:
        paths: list[str] = []
        try:
            flat, _ = jax.tree_util.tree_flatten_with_path(
                (dyn_args, dyn_kwargs)
            )
            paths = [jax.tree_util.keystr(p) for p, _ in flat]
        except Exception:
            paths = [f"leaf[{i}]" for i in range(len(avals))]
        swept_key = self._swept_key(key)
        with self._lock:
            warm = bool(self._sigs) or self._last_sig is not None
            seen_before = key in self._seen_sigs
            swept_before = (
                swept_key is not None and swept_key in self._seen_swept
            )
            old_paths, old_avals = self._last_paths, self._last_avals
            old_statics = self._last_statics
        retrace = warm and not seen_before
        # a miss that matches a seen signature after stripping the SWEEP
        # statics is a planned executable for a new static value (the
        # fused program compiling for a new n_rounds) — real compile
        # cost, attributed on the span, but NOT a retrace
        static_sweep = retrace and swept_before
        if static_sweep:
            retrace = False
        changed = (
            _signature_diff(old_paths, old_avals, paths, avals,
                            old_statics, key[2])
            if (retrace or static_sweep) else None
        )
        attrs: dict[str, Any] = {
            "function": self.name,
            "n_leaves": len(avals),
            "retrace": retrace,
        }
        if static_sweep:
            attrs["static_sweep"] = True
        if seen_before:
            # recompiling a signature the FIFO evicted — raise
            # max_signatures (V6T_DEVICE_OBS_SIGS) if this is frequent
            attrs["evicted_recompile"] = True
        if changed:
            attrs["changed"] = changed
        with TRACER.span("device.compile", kind="device", attrs=attrs) as sp:
            t0 = time.perf_counter()
            try:
                lowered = self._jit.lower(*args, **kwargs)
                t1 = time.perf_counter()
                compiled = lowered.compile()
                t2 = time.perf_counter()
            except Exception as e:
                # the compiler refused the program: name it on the span
                # and let the caller see it — retrying through plain jit
                # would only compile (and fail, or OOM) a second time
                sp.set_attr(error=repr(e))
                raise
            lower_s, compile_s = t1 - t0, t2 - t1
            mem = _memory_summary(compiled)
            cost = _cost_summary(compiled)
            sp.set_attr(
                lower_ms=round(lower_s * 1e3, 3),
                compile_ms=round(compile_s * 1e3, 3),
                **mem, **cost,
            )
        self.compiles += 1
        REGISTRY.counter("v6t_jit_compiles_total").inc()
        REGISTRY.counter("v6t_jit_lower_seconds_total").inc(lower_s)
        REGISTRY.counter("v6t_jit_compile_seconds_total").inc(compile_s)
        if mem.get("temp_bytes") is not None:
            REGISTRY.gauge("v6t_jit_compile_temp_bytes").set(
                mem["temp_bytes"]
            )
        if cost.get("flops") is not None:
            REGISTRY.gauge("v6t_jit_compile_flops").set(cost["flops"])
        self.last_compile = {
            "ts": time.time(),
            "lower_s": lower_s,
            "compile_s": compile_s,
            "retrace": retrace,
            "changed": changed,
            **mem, **cost,
        }
        if retrace:
            self.retraces += 1
            REGISTRY.counter("v6t_jit_retraces_total").inc()
            DEVICE_OBS.record_retrace(self.name, changed or "?")
        if static_sweep:
            self.static_sweeps += 1
            REGISTRY.counter("v6t_jit_static_sweeps_total").inc()
        with self._lock:
            self._sigs[key] = compiled
            self._seen_sigs[key] = None
            self._seen_sigs.move_to_end(key)
            while len(self._seen_sigs) > 1024:
                self._seen_sigs.popitem(last=False)
            if swept_key is not None:
                self._seen_swept[swept_key] = None
                self._seen_swept.move_to_end(swept_key)
                while len(self._seen_swept) > 1024:
                    self._seen_swept.popitem(last=False)
            self._last_sig = key
            self._last_paths, self._last_avals = paths, avals
            self._last_statics = key[2]
            while len(self._sigs) > DEVICE_OBS.max_signatures:
                self._sigs.popitem(last=False)
                self.evictions += 1
                REGISTRY.counter("v6t_jit_cache_evictions_total").inc()
        return compiled

    # ------------------------------------------------------------- queries
    def n_signatures(self) -> int:
        with self._lock:
            return len(self._sigs)

    def clear(self) -> None:
        with self._lock:
            self._sigs.clear()
            self._seen_sigs.clear()
            self._seen_swept.clear()
            self._last_sig = None
            self._last_paths, self._last_avals = [], ()
            self._last_statics = ()

    def stats(self) -> dict[str, Any]:
        return {
            "function": self.name,
            "signatures": self.n_signatures(),
            "compiles": self.compiles,
            "retraces": self.retraces,
            "static_sweeps": self.static_sweeps,
            "dispatches": self.dispatches,
            "fallbacks": self.fallbacks,
            "evictions": self.evictions,
            "last_compile": dict(self.last_compile),
        }


class DeviceObservatory:
    """Process-wide registry of observed functions + the device-plane
    state the watchdog feed and tools read. Env knobs (read once;
    ``configure()`` overrides live): ``V6T_DEVICE_OBS=0`` disables,
    ``V6T_DEVICE_OBS_SIGS`` caps live signatures per function."""

    def __init__(self):
        self._lock = threading.Lock()
        # weak refs: an observed function lives exactly as long as its
        # owner's reference (a FedAvg instance's self._round, a module-
        # level runner cache). A per-instance wrapper must not be pinned
        # here for process lifetime — that is the "host references
        # pinning device arrays" leak this module's own runbook warns
        # about. A SET, not a name-keyed map: two live instances sharing
        # a name (two FedAvg engines both registering "fedavg.round")
        # must BOTH stay tracked, or clear() misses one's executables and
        # the v6t_jit_signatures gauge undercounts live programs.
        self._functions: "weakref.WeakSet[ObservedFunction]" = weakref.WeakSet()
        # recent retrace events, newest last (watchdog feed + doctor)
        self._retraces: deque[dict[str, Any]] = deque(maxlen=64)
        self._engine_caches: dict[str, dict[str, int]] = {}
        self.enabled = os.environ.get("V6T_DEVICE_OBS", "1") != "0"
        self.max_signatures = max(1, env_int("V6T_DEVICE_OBS_SIGS", 32))

    def configure(
        self, enabled: bool | None = None, max_signatures: int | None = None
    ) -> "DeviceObservatory":
        if enabled is not None:
            self.enabled = bool(enabled)
        if max_signatures is not None:
            self.max_signatures = max(1, int(max_signatures))
        return self

    # ------------------------------------------------------------ registry
    def register(self, fn: ObservedFunction) -> ObservedFunction:
        with self._lock:
            self._functions.add(fn)
        return fn

    def functions(self) -> list[ObservedFunction]:
        with self._lock:
            return list(self._functions)

    def record_retrace(self, function: str, changed: str) -> None:
        rec = {"ts": time.time(), "function": function, "changed": changed}
        with self._lock:
            self._retraces.append(rec)
        try:
            from vantage6_tpu.common.flight import FLIGHT

            FLIGHT.note("retrace", function=function, changed=changed)
        except Exception:  # pragma: no cover - recorder must stay optional
            pass

    def recent_retraces(self, limit: int = 16) -> list[dict[str, Any]]:
        with self._lock:
            return list(self._retraces)[-limit:]

    # -------------------------------------------------------- engine caches
    def engine_cache_event(
        self, cache: str, hit: bool, entries: int | None = None
    ) -> None:
        """One lookup against a ``mesh.fingerprint()``-keyed runner cache
        (glm/quantile/device_engine): counted process-wide AND per-cache,
        so `/metrics` answers "does the executable cache work at all" and
        :meth:`stats` answers "which one doesn't"."""
        if not self.enabled:
            # V6T_DEVICE_OBS=0 promises the WHOLE layer off — the cache
            # counters must not keep emitting behind the operator's back
            return
        with self._lock:
            st = self._engine_caches.setdefault(
                cache, {"hits": 0, "misses": 0, "entries": 0}
            )
            st["hits" if hit else "misses"] += 1
            if entries is not None:
                st["entries"] = int(entries)
            total_entries = sum(
                c["entries"] for c in self._engine_caches.values()
            )
        REGISTRY.counter(
            "v6t_engine_cache_hits_total" if hit
            else "v6t_engine_cache_misses_total"
        ).inc()
        REGISTRY.gauge("v6t_engine_cache_entries").set(total_entries)

    def engine_cache_stats(self) -> dict[str, dict[str, int]]:
        with self._lock:
            return {k: dict(v) for k, v in self._engine_caches.items()}

    # --------------------------------------------------------------- output
    def stats(self) -> dict[str, Any]:
        return {
            "enabled": self.enabled,
            "functions": [f.stats() for f in self.functions()],
            "engine_caches": self.engine_cache_stats(),
            "recent_retraces": self.recent_retraces(),
        }

    def clear(self) -> None:
        """Drop compiled executables + retrace/engine-cache history (test
        and bench-arm isolation; the plain ``jax.jit`` twins keep their
        own caches, so clearing never causes a recompile storm)."""
        for fn in self.functions():
            fn.clear()
        with self._lock:
            self._retraces.clear()
            self._engine_caches.clear()

    def watchdog_feed(self) -> dict[str, Any]:
        """The ``recompile_storm`` rule's evidence: recent retrace events
        as feed items, newest last."""
        return {"retraces": self.recent_retraces()}


DEVICE_OBS = DeviceObservatory()


def observed_jit(
    name: str,
    fun: Callable[..., Any],
    *,
    static_argnums: tuple[int, ...] = (),
    static_argnames: tuple[str, ...] = (),
    sweep_statics: tuple[str, ...] = (),
    **jit_kwargs: Any,
) -> ObservedFunction:
    """``jax.jit`` with the device observatory attached (module doc).
    ``name`` is the low-cardinality label every compile span, retrace
    note and alert uses — name the OPERATION (``fedavg.round``), not the
    call site. ``sweep_statics`` names statics the caller legitimately
    sweeps (the fused program's ``n_rounds``): compiles differing only in
    those are counted as ``static_sweeps``, not retraces."""
    return DEVICE_OBS.register(ObservedFunction(
        name, fun, static_argnums=static_argnums,
        static_argnames=static_argnames, sweep_statics=sweep_statics,
        **jit_kwargs,
    ))


# ------------------------------------------------------------ module-level
def engine_cache_event(
    cache: str, hit: bool, entries: int | None = None
) -> None:
    """Convenience forwarder to :meth:`DeviceObservatory.engine_cache_event`
    (the glm/quantile/device_engine runner caches call this)."""
    DEVICE_OBS.engine_cache_event(cache, hit, entries=entries)


class RunnerCache:
    """FIFO-bounded get-or-create cache for ``mesh.fingerprint()``-keyed
    observed runners — the ONE implementation behind the glm / quantile /
    device_engine / collectives caches. Every lookup is reported through
    :func:`engine_cache_event` under the cache's name; the bound matters
    because keys legitimately carry sweepable values (n_iter, lr, flat
    length), and an unbounded runner cache would BE the executable leak
    the observatory exists to catch. Evicted runners drop out of the
    weak function registry with their executables."""

    def __init__(self, name: str, max_entries: int = 32):
        self.name = name
        self.max_entries = max_entries
        self._lock = threading.Lock()
        # guarded-by: _lock — insertion-ordered for FIFO eviction
        self._runners: "OrderedDict[Any, Any]" = OrderedDict()

    def get_or_create(self, key: Any, factory: Callable[[], Any]) -> Any:
        with self._lock:
            fn = self._runners.get(key)
        hit = fn is not None
        if not hit:
            # factory() runs unlocked (it may trigger tracing/compiles);
            # a rare duplicate build is benign — last writer wins
            fn = factory()
            with self._lock:
                self._runners[key] = fn
                while len(self._runners) > self.max_entries:
                    self._runners.popitem(last=False)
        engine_cache_event(self.name, hit, entries=len(self._runners))
        return fn

    def __len__(self) -> int:
        with self._lock:
            return len(self._runners)

    def clear(self) -> None:
        with self._lock:
            self._runners.clear()


# ----------------------------------------------------------- device memory
def _device_mem_collector() -> dict[str, float]:
    """Per-device memory as telemetry gauges: bytes-in-use summed over all
    local devices, worst-device peak, device count. Empty on backends
    that report no memory stats (CPU) — an absent series, never a fake
    zero the ``device_mem_growth`` trend rule would chew on."""
    from vantage6_tpu.runtime.metrics import device_memory_all

    per = device_memory_all()
    if not per:
        return {}
    out = {
        "v6t_device_count": float(len(per)),
        "v6t_device_mem_bytes_in_use": float(
            sum(d.get("bytes_in_use") or 0 for d in per)
        ),
    }
    peaks = [d.get("peak_bytes") for d in per if d.get("peak_bytes")]
    if peaks:
        out["v6t_device_mem_peak_bytes"] = float(max(peaks))
    return out


REGISTRY.register_collector("device_mem", _device_mem_collector)


# ---------------------------------------------------------- profile windows
class ProfileBusyError(RuntimeError):
    """A profiling window is already open (jax.profiler sessions cannot
    nest); retry after it closes."""


_PROFILE_LOCK = threading.Lock()

PROFILE_MAX_SECONDS = 30.0


def profile_window(
    seconds: float = 1.0, log_dir: str | None = None
) -> dict[str, Any]:
    """Run one bounded ``jax.profiler`` sampling window NOW and return
    ``{"path", "seconds", "trace_id"}``.

    The window is recorded as a ``device.profile`` span — parented on the
    caller's active trace when there is one (the ``POST
    /api/debug/profile`` handler runs inside the joined request span, so
    a client-initiated window lands in the requesting trace) — and the
    artifact path is registered in the flight recorder (note kind
    ``profile_window``), so a later ``doctor`` of the bundle names where
    the Perfetto session lives. One window at a time per process
    (:class:`ProfileBusyError` otherwise); duration is clamped to
    ``(0.05, PROFILE_MAX_SECONDS)`` — an unbounded window from a REST
    handler would hold the worker hostage.
    """
    seconds = min(PROFILE_MAX_SECONDS, max(0.05, float(seconds)))
    if log_dir is None:
        base = os.environ.get("V6T_PROFILE_DIR") or None
        if base is None:
            import tempfile

            base = tempfile.gettempdir()
        log_dir = os.path.join(
            base, f"v6t-profile-{os.getpid()}-{int(time.time() * 1000)}"
        )
    if not _PROFILE_LOCK.acquire(blocking=False):
        raise ProfileBusyError(
            "a profiling window is already open in this process"
        )
    try:
        with TRACER.span(
            "device.profile", kind="device",
            attrs={"log_dir": str(log_dir), "seconds": seconds,
                   "source": "profile_window"},
        ) as sp:
            ctx = getattr(sp, "context", None)
            trace_id = ctx.trace_id if ctx is not None else None
            jax.profiler.start_trace(str(log_dir))
            try:
                time.sleep(seconds)
            finally:
                jax.profiler.stop_trace()
    finally:
        _PROFILE_LOCK.release()
    try:
        from vantage6_tpu.common.flight import FLIGHT

        FLIGHT.note(
            "profile_window", path=str(log_dir), seconds=seconds,
            trace_id=trace_id,
        )
    except Exception:  # pragma: no cover - recorder must stay optional
        pass
    return {"path": str(log_dir), "seconds": seconds, "trace_id": trace_id}


# --------------------------------------------------------------- telemetry
def _observatory_collector() -> dict[str, float]:
    """The v6t_jit_functions / v6t_jit_signatures gauges: computed at
    snapshot time (collectors run on every scrape/dump/watchdog pass), so
    they always reflect the LIVE registry — evictions, clears, and
    garbage-collected functions included."""
    fns = DEVICE_OBS.functions()
    return {
        "v6t_jit_functions": float(len(fns)),
        "v6t_jit_signatures": float(
            sum(f.n_signatures() for f in fns)
        ),
    }


REGISTRY.register_collector("device_obs", _observatory_collector)


# -------------------------------------------------------------- watchdog
try:
    from vantage6_tpu.runtime.watchdog import WATCHDOG as _WATCHDOG

    _WATCHDOG.register_feed("device_plane", DEVICE_OBS.watchdog_feed)
except Exception:  # pragma: no cover - watchdog must stay optional here
    pass

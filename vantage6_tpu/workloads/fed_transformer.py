"""Federated causal-LM training: stations × sequence-parallel transformer.

The long-context flagship: cross-silo federated training of a decoder-only
transformer where each station's sequences are sharded over its sub-mesh
(`device` axis) and attention runs as ring attention over ICI
(vantage6_tpu.parallel) — context length scales with devices-per-station
while the station axis keeps the federation's data-parallel isolation:
per-station gradients psum only over `device`, never across stations;
cross-station aggregation is an explicit FedAvg (fed.collectives.fed_mean).

No reference counterpart (SURVEY.md §5: sequence models absent upstream) —
this is a capability the TPU rebuild adds, built from the same station
primitives as the tabular workloads.
"""
from __future__ import annotations

import collections
import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from vantage6_tpu.core.mesh import STATION_AXIS, _largest_divisor_leq
from vantage6_tpu.fed import collectives
from vantage6_tpu.models import experts
from vantage6_tpu.ops import sparse_attention as sparse
from vantage6_tpu.ops.flash_attention import (
    attention_tile,
    flash_attention,
    recompute_attention,
    tiles_visited,
)
from vantage6_tpu.parallel.ring_attention import ring_attention
from vantage6_tpu.runtime.profiling import device_launch, engine_call
from vantage6_tpu.runtime.tracing import TRACER

SEQ_AXIS = "device"  # sequence parallelism rides the within-station axis
# sparse attention's selection (`sparse.select`'s outputs), by the names the
# layer's checkpoint keeps them under (`remat_saved`)
SELECTION = ("keep", "log_norm", "tiles")


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 2
    max_len: int = 2048
    # Mixed precision: params/optimizer stay float32 (master weights); all
    # matmuls run in `dtype`. bfloat16 is the MXU-rate dtype on TPU; softmax
    # statistics, layernorm and the loss stay f32 either way.
    dtype: Any = jnp.float32
    # "ring": exact ring attention over the sequence axis (any seq_devices).
    # "flash": the Pallas flash kernel (ops.flash_attention) — requires the
    # full sequence on each device (seq_devices == 1, enforced by
    # make_engine); `flash_interpret` runs it in interpret mode on CPU.
    # "recompute": flash-memory attention (ops.recompute_attention: forward
    # and backward over tiles, only the key blocks a query block can see;
    # inside two Pallas kernels on a TPU, the same walk in jnp where
    # `flash_interpret` says a kernel would be interpreted; path and blocks
    # chosen from the shapes) — same seq_devices == 1 constraint. "flash"
    # runs the kernel or raises; it never gives way to "recompute".
    attention: str = "ring"
    flash_interpret: bool = False
    # Rematerialization: drop every layer's activations on the forward pass
    # and recompute them during backward (jax.checkpoint per layer block).
    # Activation memory falls from O(n_layers * B * T * d) to O(B * T * d)
    # — the standard long-context trade (FLOPs ~+33% for the extra
    # forward) — and composes with the attention choices above (recompute
    # attention already avoids the [T, T] residuals WITHIN a layer; remat
    # drops the per-layer residual stream BETWEEN layers). Exact in math;
    # numerically identical to f32 rounding (XLA may fuse differently
    # across the checkpoint boundary — measured ~1 ULP on the loss). With
    # sparse attention the checkpoint keeps the selection (`remat_saved`).
    remat: bool = False
    # ---- the block, as data. The defaults are the block this file always
    # ran (parameter-free LayerNorm, a learned position table at the input,
    # as many kv heads as query heads of d_model / n_heads, full causal
    # attention, a 4x GELU MLP, the head tied to the embedding): with them
    # `init_params` and `forward_local` produce what they produced, bit for
    # bit, and `_round` lowers to the same program.
    # "layernorm": no learned scale or bias; "rmsnorm": x / rms(x) * g with
    # a learned g per norm (and a final one before the head).
    norm: str = "layernorm"
    norm_eps: float = 1e-6
    head_dim: int | None = None  # None: d_model // n_heads
    n_kv_heads: int | None = None  # None: n_heads; fewer: grouped queries
    # "learned": a [max_len, d_model] table added at the input. "rotary":
    # none at the input; per layer, `rope_layout[i]` 1 rotates q and k
    # (rotate-half, `rope_theta`), 0 gives the layer no positions at all.
    positions: str = "learned"
    rope_layout: tuple[int, ...] | None = None  # None: every layer rotates
    rope_theta: float = 10000.0
    # `window_layout[i]` 1: layer i sees keys i - window < j <= i; 0: every
    # earlier key. None with a `window`: every layer is windowed.
    window: int | None = None
    window_layout: tuple[int, ...] | None = None
    # "mlp": 4x GELU. "swiglu": a dense gated one of width `d_ff`,
    # ``W_down (silu(W_gate h) * (W_up h))``. "experts": a router BEFORE
    # attention (on the block's input) over `n_experts`, `top_k` a token, and
    # ReGLU experts of width `d_expert`, of which this chip holds
    # `experts_held` (their ids): models/experts.py. No shared expert, no
    # dense feed-forward.
    ffn: str = "mlp"
    d_ff: int = 0
    n_experts: int = 0
    top_k: int = 0
    d_expert: int = 0
    experts_held: tuple[int, ...] = ()
    tie_head: bool = True  # False: an output head of its own, [d_model, vocab]
    # a norm AFTER each half of the block as well as before it: ``x +
    # N(attention(N(x)))``, ``x + N(ffn(N(x)))`` (with rmsnorm two more
    # learned scales a layer, `norm1_post` and `norm2_post`)
    norm_after: bool = False
    # the stack is walked `loops` times over THE SAME parameters, the final
    # norm after every walk; the next walk starts from the normed state.
    # With more than one walk an exit gate (``sigmoid(h w + b)`` per token
    # and walk, float32) weighs the walks' cross-entropies by the
    # distribution of the walk a token would leave at, less `exit_beta`
    # times that distribution's entropy (`_exit_loss`)
    loops: int = 1
    exit_beta: float = 0.0
    # per-head RMSNorm of q and of k over head_dim, before rotary (learned
    # `q_norm` and `k_norm`, float32)
    qk_norm: bool = False
    # what the experts' router reads: "block", the block's input before
    # attention; "normed", the normed stream after attention that the
    # experts read too (`norm2`'s output)
    router_input: str = "block"
    # the experts' gate: "relu" (ReGLU) or "silu" (SwiGLU)
    expert_act: str = "relu"
    # learned sparse attention (ops/sparse_attention.py): with a
    # `sparse_top_k`, a lightning indexer of `indexer_heads` heads of
    # `indexer_dim` over one key head scores every (query, key) pair from
    # the normed stream taken as a constant, each query attends to the
    # `sparse_top_k` keys it scores highest, and the loss adds, per layer,
    # the indexer's KL to the attention's head-averaged probabilities over
    # those keys, which trains the indexer alone. Per layer `idx_q`,
    # `idx_k`, `idx_w` and the key's LayerNorm scale `idx_norm`, float32
    sparse_top_k: int = 0
    indexer_heads: int = 0
    indexer_dim: int = 0

    def __post_init__(self):
        if self.head_dim is None:
            assert self.d_model % self.n_heads == 0
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.n_kv_heads is None:
            object.__setattr__(self, "n_kv_heads", self.n_heads)
        for name, layout in (("rope_layout", self.rope_layout),
                             ("window_layout", self.window_layout)):
            if layout is not None:
                if len(layout) != self.n_layers:
                    raise ValueError(
                        f"{name} has {len(layout)} entries for "
                        f"{self.n_layers} layers")
                object.__setattr__(self, name, tuple(layout))
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"no such norm: {self.norm!r}")
        if self.positions not in ("learned", "rotary"):
            raise ValueError(f"no such positions: {self.positions!r}")
        if self.ffn not in ("mlp", "swiglu", "experts"):
            raise ValueError(f"no such ffn: {self.ffn!r}")
        if self.ffn == "swiglu" and self.d_ff < 1:
            raise ValueError("ffn='swiglu' needs its width, d_ff")
        if self.loops < 1:
            raise ValueError(f"the stack is walked once or more: {self.loops}")
        if self.ffn == "experts" and (self.loops > 1 or self.norm_after):
            raise ValueError(
                "a block with experts is walked once (its counts are kept "
                "per layer, not per walk) and takes no norm after its halves")
        if self.router_input not in ("block", "normed"):
            raise ValueError(f"no such router input: {self.router_input!r}")
        if self.expert_act not in ("relu", "silu"):
            raise ValueError(f"no such expert gate: {self.expert_act!r}")
        if self.sparse_top_k and not (
                self.indexer_heads and self.indexer_dim
                and self.indexer_dim % 4 == 0 and self.positions == "rotary"
                and self.attention == "recompute" and self.window is None
                and self.loops == 1):
            raise ValueError(
                "sparse attention needs indexer_heads and an indexer_dim of "
                "whole rotary pairs, rotary positions, attention='recompute', "
                "no window and one walk of the stack")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"{self.n_heads} query heads do not divide over "
                f"{self.n_kv_heads} key/value heads")
        if self.positions == "rotary" and self.head_dim % 2:
            raise ValueError("rotary positions need an even head_dim")
        if self.ffn == "experts" and not (
                self.experts_held and self.top_k and self.d_expert
                and max(self.experts_held) < self.n_experts):
            raise ValueError(
                "ffn='experts' needs n_experts, top_k, d_expert and the ids "
                "of the experts_held here")
        needs_tiles = (self.n_kv_heads != self.n_heads
                       or self.window is not None)
        if needs_tiles and self.attention != "recompute":
            # ring and flash take neither a window nor fewer kv heads; they
            # never give way to another path in silence
            raise ValueError(
                f"attention={self.attention!r} takes no window and no "
                "grouped kv heads: use attention='recompute'")

    def layer_window(self, i: int) -> int | None:
        if self.window is None:
            return None
        if self.window_layout is not None and not self.window_layout[i]:
            return None
        return self.window

    def layer_rotates(self, i: int) -> bool:
        if self.positions != "rotary":
            return False
        return self.rope_layout is None or bool(self.rope_layout[i])


def _layer_shapes(cfg: TransformerConfig) -> dict[str, tuple[int, ...]]:
    """The matrices of one layer, in the order their keys are drawn."""
    d, hd = cfg.d_model, cfg.head_dim
    shapes: dict[str, tuple[int, ...]] = {
        "qkv": (d, (cfg.n_heads + 2 * cfg.n_kv_heads) * hd),
        "proj": (cfg.n_heads * hd, d),
    }
    if cfg.ffn == "mlp":
        shapes.update(w_up=(d, 4 * d), w_down=(4 * d, d))
    elif cfg.ffn == "swiglu":
        shapes.update(w_gate=(d, cfg.d_ff), w_up=(d, cfg.d_ff),
                      w_down=(cfg.d_ff, d))
    else:
        e, f = len(cfg.experts_held), cfg.d_expert
        shapes.update(router=(d, cfg.n_experts), w_gate=(e, d, f),
                      w_up=(e, d, f), w_down=(e, f, d))
    if cfg.sparse_top_k:
        shapes.update(idx_q=(d, cfg.indexer_heads * cfg.indexer_dim),
                      idx_k=(d, cfg.indexer_dim), idx_w=(d, cfg.indexer_heads))
    return shapes


def init_params(key: jax.Array, cfg: TransformerConfig) -> dict[str, Any]:
    """Matrices ~ N(0, 0.02); learned norm scales 1. The tree: ``embed``;
    ``pos`` with learned positions; ``head`` where the head is not tied;
    ``final_norm`` with rmsnorm; ``exit_gate`` (``w`` [d_model, 1] and a
    bias ``b`` of 0) where the stack is walked more than once; ``layers[i]``:
    ``qkv`` (the q, k and v projections side by side), ``proj``, then
    ``w_up``/``w_down`` (mlp), ``w_gate``/``w_up``/``w_down`` (swiglu) or
    ``router``/``w_gate``/``w_up``/``w_down`` (experts, the held ones
    stacked), ``idx_q``/``idx_k``/``idx_w`` (sparse attention's indexer),
    and with rmsnorm ``norm1``/``norm2`` (and ``norm1_post``/``norm2_post``
    where a norm follows each half too); ``q_norm``/``k_norm`` [head_dim]
    with the per-head norm; ``idx_norm`` [indexer_dim] with the indexer."""
    shapes = _layer_shapes(cfg)
    n = len(shapes)
    keys = jax.random.split(key, 2 + n * cfg.n_layers)
    s = 0.02
    params: dict[str, Any] = {
        "embed": s * jax.random.normal(keys[0], (cfg.vocab, cfg.d_model)),
    }
    if cfg.positions == "learned":
        params["pos"] = s * jax.random.normal(
            keys[1], (cfg.max_len, cfg.d_model))
    if not cfg.tie_head:
        params["head"] = s * jax.random.normal(
            jax.random.fold_in(keys[1], 1), (cfg.d_model, cfg.vocab))
    if cfg.norm == "rmsnorm":
        params["final_norm"] = jnp.ones((cfg.d_model,))
    if cfg.loops > 1:
        params["exit_gate"] = {
            "w": s * jax.random.normal(
                jax.random.fold_in(keys[1], 2), (cfg.d_model, 1)),
            "b": jnp.zeros((1,))}
    scales = ("norm1", "norm2") + (
        ("norm1_post", "norm2_post") if cfg.norm_after else ())
    params["layers"] = []
    for i in range(cfg.n_layers):
        k = keys[2 + n * i : 2 + n * (i + 1)]
        layer = {name: s * jax.random.normal(k[j], shape)
                 for j, (name, shape) in enumerate(shapes.items())}
        if cfg.norm == "rmsnorm":
            layer.update({name: jnp.ones((cfg.d_model,)) for name in scales})
        if cfg.qk_norm:
            layer.update(q_norm=jnp.ones((cfg.head_dim,)),
                         k_norm=jnp.ones((cfg.head_dim,)))
        if cfg.sparse_top_k:
            layer["idx_norm"] = jnp.ones((cfg.indexer_dim,))
        params["layers"].append(layer)
    return params


def _ln(x: jax.Array) -> jax.Array:
    # normalization statistics in f32 even under bf16 compute
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    return ((xf - mu) * lax.rsqrt(var + 1e-6)).astype(x.dtype)


def _norm(x: jax.Array, scale: jax.Array | None, cfg: TransformerConfig):
    """The configuration's norm: the parameter-free LayerNorm above, or
    ``x / sqrt(mean(x^2) + eps) * g`` with the learned ``g`` (float32).
    Every norm opens the `norms` scope, nested in whatever scope calls it."""
    with jax.named_scope("norms"):
        if cfg.norm == "layernorm":
            return _ln(x)
        xf = x.astype(jnp.float32)
        ms = jnp.mean(xf * xf, -1, keepdims=True)
        return (xf * lax.rsqrt(ms + cfg.norm_eps) * scale).astype(x.dtype)


def _rotate(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate-half RoPE of ``x`` [B, T, H, D] at global ``positions`` [T]:
    pair ``(x[i], x[i + D/2])`` turns by ``positions * theta^(-2i/D)``;
    in the `rotary` scope."""
    with jax.named_scope("rotary"):
        half = x.shape[-1] // 2
        freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        angle = positions.astype(jnp.float32)[:, None] * freq[None, :]
        cos = jnp.cos(angle)[None, :, None, :]
        sin = jnp.sin(angle)[None, :, None, :]
        xf = x.astype(jnp.float32)
        x1, x2 = xf[..., :half], xf[..., half:]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        ).astype(x.dtype)


def _indexer(h: jax.Array, layer: dict[str, Any], positions: jax.Array,
             cfg: TransformerConfig):
    """The lightning indexer's queries [B, T, H_I, D_I], key [B, T, D_I] and
    head weights [B, T, H_I] from the normed stream ``h``, float32 at
    ``HIGHEST``: the key through a LayerNorm with a learned scale, the first
    half of every query and key width rotated (rotate-half, `rope_theta`),
    the weights scaled by ``1/sqrt(H_I)``."""
    b, t, _ = h.shape
    hf = h.astype(jnp.float32)

    def project(name):
        return jnp.matmul(hf, layer[name], precision=lax.Precision.HIGHEST)

    def rotate_half_width(x):  # [B, T, H, D_I]
        half = x.shape[-1] // 2
        return jnp.concatenate(
            [_rotate(x[..., :half], positions, cfg.rope_theta), x[..., half:]],
            axis=-1)

    q = project("idx_q").reshape(b, t, cfg.indexer_heads, cfg.indexer_dim)
    k = project("idx_k")
    mu = jnp.mean(k, -1, keepdims=True)
    var = jnp.var(k, -1, keepdims=True)
    k = (k - mu) * lax.rsqrt(var + cfg.norm_eps) * layer["idx_norm"]
    w = project("idx_w") * (1.0 / np.sqrt(cfg.indexer_heads))
    return rotate_half_width(q), rotate_half_width(k[:, :, None])[:, :, 0], w


def _head(params: dict[str, Any], cfg: TransformerConfig) -> jax.Array:
    """The output head [d_model, vocab] in the compute dtype."""
    return (params["embed"].astype(cfg.dtype).T if cfg.tie_head
            else params["head"].astype(cfg.dtype))


def remat_saved(cfg: TransformerConfig) -> tuple[str, ...]:
    """The values the layer's checkpoint keeps for the backward pass, by
    name; it recomputes the rest. Sparse attention's selection (`SELECTION`:
    a bit a pair, 64 MiB a layer at two stations' 16k tokens): its backward
    rules need ``keep`` and ``log_norm``, and rebuilt they would cost the
    indexer's scores over every visible pair and the top-k search again, for
    the same bits (nothing is differentiated through them). Nothing where
    there is no checkpoint or no selection."""
    return SELECTION if cfg.remat and cfg.sparse_top_k else ()


def _forward(
    params: dict[str, Any],
    tokens_local: jax.Array,
    cfg: TransformerConfig,
    axis_name: str,
    exchange: collectives.RingExchange | None = None,
) -> tuple[list[jax.Array], list[Any], list[Any]]:
    """The stream after the final norm, once for every walk of the stack
    (the head reads these: `forward_local`, `_loss_and_load`), the expert
    layers' load, one entry a layer: the assignments each held expert
    received and the choices that named one (none for a block without
    experts), and with sparse attention, one entry a layer, the indexer's
    loss summed over the positions and the count of the attention walk's
    tiles that held a kept pair (none without). With an ``exchange`` the
    stream and each group of layers (`_layer_groups`) pass through it on
    their way into the group: nothing
    on the way forward, the cross-station mean of the group's gradient on
    the way back (`FedTransformer._round` on several slots)."""
    b, t_local = tokens_local.shape
    offset = lax.axis_index(axis_name) * t_local  # global positions
    n_q, n_kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim

    def cast(w: jax.Array) -> jax.Array:
        return w.astype(cfg.dtype)

    with jax.named_scope("embed"):
        x = cast(params["embed"])[tokens_local]
        if cfg.positions == "learned":
            x = x + cast(
                lax.dynamic_slice_in_dim(params["pos"], offset, t_local, 0)
            )[None]

    def layer_block(x, layer, window, rotates):
        # the router's matrix and the norms' scales stay float32
        kept = {name: layer[name] for name in (
            "router", "norm1", "norm2", "norm1_post", "norm2_post",
            "q_norm", "k_norm", "idx_q", "idx_k", "idx_w", "idx_norm")
            if name in layer}
        layer = jax.tree.map(
            cast, {k: v for k, v in layer.items() if k not in kept})
        routing = None
        if cfg.ffn == "experts" and cfg.router_input == "block":
            with jax.named_scope("router"):
                # before attention, on the block's input
                routing = experts.route(
                    x.reshape(b * t_local, cfg.d_model), kept["router"],
                    cfg.top_k)
        h = _norm(x, kept.get("norm1"), cfg)
        with jax.named_scope("qkv"):
            qkv = h @ layer["qkv"]
            q, k, v = jnp.split(qkv, [n_q, n_q + n_kv], axis=-1)
            q = q.reshape(b, t_local, cfg.n_heads, cfg.head_dim)
            k = k.reshape(b, t_local, cfg.n_kv_heads, cfg.head_dim)
            v = v.reshape(b, t_local, cfg.n_kv_heads, cfg.head_dim)
        if cfg.qk_norm:
            q = _norm(q, kept["q_norm"], cfg)
            k = _norm(k, kept["k_norm"], cfg)
        if rotates:
            positions = offset + jnp.arange(t_local)
            q = _rotate(q, positions, cfg.rope_theta)
            k = _rotate(k, positions, cfg.rope_theta)
        terms = None
        if cfg.sparse_top_k:
            attn, terms = sparse_half(h, q, k, v, kept)
        elif cfg.attention in ("flash", "recompute"):
            # both want head-major [B, H, T, D]; offsets keep the causal
            # mask correct for any sequence shard (here the full sequence —
            # make_engine enforces seq_devices == 1 for these modes)
            impl = (
                flash_attention if cfg.attention == "flash"
                else recompute_attention
            )
            kw = {"interpret": cfg.flash_interpret}
            if window is not None:
                kw["window"] = window
            with jax.named_scope("attention"):
                attn = impl(
                    q.transpose(0, 2, 1, 3),
                    k.transpose(0, 2, 1, 3),
                    v.transpose(0, 2, 1, 3),
                    q_offset=offset,
                    k_offset=offset,
                    causal=True,
                    **kw,
                ).transpose(0, 2, 1, 3)
        else:
            with jax.named_scope("attention"):
                attn = ring_attention(q, k, v, axis_name, causal=True)
        # the residual add too: where XLA fuses it into the product's
        # output, the fusion carries the add's path
        with jax.named_scope("attn_out"):
            attn = attn.reshape(b, t_local, n_q) @ layer["proj"]
            if cfg.norm_after:
                attn = _norm(attn, kept.get("norm1_post"), cfg)
            x = x + attn
        # beside the stream: the router's choice where it reads the block's
        # input (the expert layer follows: `expert_half`), and sparse
        # attention's terms
        if cfg.ffn == "experts":
            return x, routing, terms
        with jax.named_scope("mlp"):
            h = _norm(x, kept.get("norm2"), cfg)
            if cfg.ffn == "mlp":
                y = jax.nn.gelu(h @ layer["w_up"]) @ layer["w_down"]
            else:
                y = (jax.nn.silu(h @ layer["w_gate"])
                     * (h @ layer["w_up"])) @ layer["w_down"]
            if cfg.norm_after:
                y = _norm(y, kept.get("norm2_post"), cfg)
            return x + y, routing, terms

    def sparse_half(h, q, k, v, kept):
        """Attention over each query's kept keys (ops/sparse_attention.py):
        the indexer reads the normed stream as a constant; returns the
        attention [B, T, Hq, D] and, per layer, the indexer's loss summed
        over the positions and the count of tiles that held a kept pair."""
        positions = offset + jnp.arange(t_local)
        with jax.named_scope("indexer"):
            q_idx, k_idx, w = _indexer(lax.stop_gradient(h), kept, positions,
                                       cfg)
        q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
        walk = sparse.blocks(t_local, cfg.head_dim,
                             cfg.n_heads // cfg.n_kv_heads, cfg.dtype)
        keep, log_norm, tiles = (
            checkpoint_name(x, name) for x, name in zip(sparse.select(
                q_idx, k_idx, w, cfg.sparse_top_k, walk.block_q,
                walk.block_k, cfg.flash_interpret), SELECTION))
        with jax.named_scope("attention"):
            attn, big_l = sparse.attend(q, k, v, keep, offset, offset,
                                        walk.block_q, walk.block_k)
        with jax.named_scope("indexer_loss"):
            loss = sparse.indexer_loss(q, k, big_l, keep, log_norm, q_idx,
                                       k_idx, w, walk.block_q, walk.block_k,
                                       cfg.flash_interpret)
        return attn.transpose(0, 2, 1, 3), (loss, tiles)

    def expert_half(x, layer, routing):
        """Outside the layer's checkpoint: the expert layer recomputes its
        own chunks (models/experts.py), and inside another recomputation it
        would run forward three times. With the router on the normed stream
        it chooses here."""
        with jax.named_scope("experts"):
            h = _norm(x, layer.get("norm2"), cfg)
        if routing is None:
            with jax.named_scope("router"):
                routing = experts.route(
                    h.reshape(b * t_local, cfg.d_model), layer["router"],
                    cfg.top_k)
        with jax.named_scope("experts"):
            y, load = experts.expert_layer(
                h.reshape(b * t_local, cfg.d_model), *routing, layer,
                cfg.experts_held, cfg.n_experts,
                interpret=cfg.flash_interpret, activation=cfg.expert_act)
            return x + y.reshape(x.shape), load

    # one traced block per kind of layer: the layers of a kind after the
    # first hit jax's caches at every step (trace, jvp, partial evaluation,
    # transpose, batching), and the lowering emits the block once and calls
    # it, however often the stack is walked. Its name in an operation's
    # path, `jit(layer_block)`, is no scope.
    kind = (2, 3)  # window, rotates
    block = layer_block
    if cfg.remat:
        saved = remat_saved(cfg)
        block = jax.checkpoint(
            layer_block, static_argnums=kind,
            policy=jax.checkpoint_policies.save_only_these_names(*saved)
            if saved else None)
    block = jax.jit(block, static_argnums=kind)

    def stack(x):
        loads, terms = [], []
        layers = params["layers"]
        if exchange is not None:
            layers = list(layers)
            entered = {group[0]: group for group in _layer_groups(layers)}
        for i in range(len(layers)):
            if exchange is not None and i in entered:
                x, layers[i:entered[i][-1] + 1] = exchange(
                    x, [layers[j] for j in entered[i]])
            layer = layers[i]
            x, routing, sparse_terms = block(
                x, layer, cfg.layer_window(i), cfg.layer_rotates(i))
            if cfg.sparse_top_k:
                terms.append(sparse_terms)
            if cfg.ffn == "experts":
                x, load = expert_half(x, layer, routing)
                loads.append(load)
        return x, loads, terms

    if cfg.loops == 1:
        x, loads, terms = stack(x)
        with jax.named_scope("lm_head_loss"):
            return [_norm(x, params.get("final_norm"), cfg)], loads, terms
    # an unrolled walk, not a `lax.scan` over the walks: read on the chip
    # (PERF.md section 6, PR 35) the scan compiles in half the time to a
    # third of the code and its round is 2.6% slower
    states = []
    with jax.named_scope("loop"):
        for _ in range(cfg.loops):  # the same layers, the same positions
            x = _norm(stack(x)[0], params.get("final_norm"), cfg)
            states.append(x)
    return states, [], []


def forward_local(
    params: dict[str, Any],
    tokens_local: jax.Array,  # [B, T_local] — this device's sequence shard
    cfg: TransformerConfig,
    axis_name: str = SEQ_AXIS,
) -> jax.Array:
    """Logits [B, T_local, V] for this shard (after the last walk, where the
    stack is walked more than once); attention spans the FULL sequence via
    the ring."""
    states = _forward(params, tokens_local, cfg, axis_name)[0]
    with jax.named_scope("lm_head_loss"):
        return states[-1] @ _head(params, cfg)


# positions of a sequence whose logits are live at once. Read on the chip at
# 2 stations x [1, 4096] x 49,152 (PERF.md section 6, PR 35): 512 the
# fastest round (810 ms; 813 at 256, 822 at 1,024 and whole, 875 at 128),
# and whole, a walk's logits made the round's temporaries 10.6 GB for 4.6
HEAD_CHUNK = 512
# a sequence's float32 logits above this many bytes are taken `HEAD_CHUNK`
# positions at a time in a walk of the stack once too (`_loss_and_load`):
# whole, [1, 16384] x 18,992 held 1.2 GB and its cotangent as much again
# beside the activations of a 16k-token round
HEAD_LOGITS_BYTES = 2**30


def _token_nll(h: jax.Array, head: jax.Array, targets: jax.Array):
    """Cross-entropy per position [B, T] of ``h @ head`` against
    ``targets``, `HEAD_CHUNK` positions at a time where they divide T.
    Nothing of a chunk is kept for the backward pass, which computes its
    logits [B, chunk, V] again: one chunk's are live at a time."""
    @jax.checkpoint
    def chunk_nll(chunk):
        h, targets = chunk
        logp = jax.nn.log_softmax((h @ head).astype(jnp.float32))
        return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]

    b, t = targets.shape
    if t <= HEAD_CHUNK or t % HEAD_CHUNK:
        return chunk_nll((h, targets))

    def chunks(x):  # [B, T, ...] -> [T / chunk, B, chunk, ...]
        return jnp.moveaxis(x.reshape(b, -1, HEAD_CHUNK, *x.shape[2:]), 1, 0)

    nll = lax.map(chunk_nll, (chunks(h), chunks(targets)))
    return jnp.moveaxis(nll, 0, 1).reshape(b, t)


def _exit_loss(states, params, tokens_local, cfg):
    """The exit-weighted loss of a stack walked R times, summed over this
    shard's predicted positions, and the exit distribution summed over them
    [R]. With ``lambda_r = sigmoid(h_r w + b)`` a token leaves after walk r
    with ``p_r = lambda_r prod_{j<r} (1 - lambda_j)`` (``p_R`` the rest, so
    they sum to 1), and its loss is ``sum_r p_r CE_r - exit_beta H(p)``. The
    head is taken once a walk, each walk's logits for themselves
    (`_token_nll`); gate, distribution and entropy in float32, in logs."""
    head = _head(params, cfg)
    # the last position predicts nothing: computed with the rest, left out
    targets = jnp.roll(tokens_local, -1, axis=1)
    with jax.named_scope("lm_head_loss"):
        nll = jnp.stack([_token_nll(h, head, targets) for h in states])
    with jax.named_scope("exit_gate"):
        gate = params["exit_gate"]
        z = jnp.stack([
            jnp.matmul(h.astype(jnp.float32), gate["w"],
                       precision=lax.Precision.HIGHEST)[..., 0] + gate["b"]
            for h in states[:-1]])                           # [R - 1, B, T]
        # log prod_{j<r} (1 - lambda_j) for r = 1..R, then log p_r
        stayed = jnp.concatenate([
            jnp.zeros_like(z[:1]), jnp.cumsum(jax.nn.log_sigmoid(-z), 0)])
        log_p = stayed + jnp.concatenate([
            jax.nn.log_sigmoid(z), jnp.zeros_like(z[:1])])
        p = jnp.exp(log_p)[:, :, :-1]
        entropy = -jnp.sum(p * log_p[:, :, :-1], axis=0)
        per_token = jnp.sum(p * nll[:, :, :-1], axis=0)
        return (jnp.sum(per_token - cfg.exit_beta * entropy),
                jnp.sum(p, axis=(1, 2)))


def _loss_and_load(params, tokens_local, cfg, axis_name, exchange=None):
    """The loss, and what a round leaves on the device beside it: the expert
    layers' load stacked over the layers, the exit distribution summed over
    the predicted positions [R], and the count of sparse attention's tiles
    that held a kept pair [L]; each ``None`` where the block has no such
    thing. With sparse attention the loss adds every layer's indexer loss,
    a mean over the positions. ``exchange``: `_forward`'s."""
    states, loads, terms = _forward(
        params, tokens_local, cfg, axis_name, exchange)
    load = exits = tiles = None
    b, t_local = tokens_local.shape
    if cfg.loops > 1:
        local_sum, exits = _exit_loss(states, params, tokens_local, cfg)
        local_cnt = jnp.asarray(b * (t_local - 1), jnp.float32)
    elif b * t_local * cfg.vocab * 4 > HEAD_LOGITS_BYTES:
        if loads:
            load = jax.tree.map(lambda *xs: jnp.stack(xs), *loads)
        with jax.named_scope("lm_head_loss"):
            # the last position predicts nothing: computed with the rest
            nll = _token_nll(states[0], _head(params, cfg),
                             jnp.roll(tokens_local, -1, axis=1))[:, :-1]
            local_sum = jnp.sum(nll)
            local_cnt = jnp.asarray(nll.size, jnp.float32)
    else:
        with jax.named_scope("lm_head_loss"):
            logits = states[0] @ _head(params, cfg)
        if loads:  # between the head's product and the loss, in no scope
            load = jax.tree.map(lambda *xs: jnp.stack(xs), *loads)
        with jax.named_scope("lm_head_loss"):
            targets = tokens_local[:, 1:]
            logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
            nll = -jnp.take_along_axis(
                logp, targets[..., None], axis=-1)[..., 0]
            local_sum = jnp.sum(nll)
            local_cnt = jnp.asarray(nll.size, jnp.float32)
    total = lax.psum(local_sum, axis_name)
    count = lax.psum(local_cnt, axis_name)
    if not terms:
        return total / count, (load, exits, tiles)
    indexer_sum, tiles = (jnp.stack(x) for x in zip(*terms))
    positions = lax.psum(jnp.asarray(b * t_local, jnp.float32), axis_name)
    indexer = lax.psum(jnp.sum(indexer_sum), axis_name) / positions
    return total / count + indexer, (load, exits, tiles)


def loss_local(
    params: dict[str, Any],
    tokens_local: jax.Array,
    cfg: TransformerConfig,
    axis_name: str = SEQ_AXIS,
) -> jax.Array:
    """Mean next-token CE over the GLOBAL sequence (psum over shards).

    Within a shard, position t predicts t+1; each shard's final token has
    its target on the next shard, so that position is masked out (T/P - 1
    predictions per shard — negligible at scale, exact bookkeeping here).
    """
    return _loss_and_load(params, tokens_local, cfg, axis_name)[0]


PACKED_AXIS = "packed"  # the stations packed in one slot of the mesh


def _layer_groups(layers: list[Any]) -> list[list[int]]:
    """The layers whose gradients cross the stations together: whole
    consecutive layers, gathered by their bytes (`collectives.ring_groups`)."""
    return collectives.ring_groups(
        [sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(layer))
         for layer in layers])


@dataclasses.dataclass(eq=False)  # identity hash: engine is a jit static arg
class FedTransformer:
    """Training engine over a ('station', 'device') mesh."""

    mesh: Mesh
    cfg: TransformerConfig
    optimizer: Any
    # the expert layers' counts of the last rounds, still on the device
    # (`record_expert_load` reads and empties it; bounded, oldest out)
    _expert_load: collections.deque = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=4096), repr=False)
    # the exit distributions of the last rounds, still on the device
    # (`record_exit_distribution`), likewise
    _exits: collections.deque = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=4096), repr=False)
    # what `round`'s span says of the attention's walk, by sequence length
    _walks: dict = dataclasses.field(default_factory=dict, repr=False)
    # and of the cross-station mean (`aggregation`)
    _aggregation: dict | None = dataclasses.field(default=None, repr=False)
    # the shape of the tokens whose load `_expert_load` holds (`row_walk`)
    _load_shape: tuple | None = dataclasses.field(default=None, repr=False)
    # sparse attention's counts of tiles that held a kept pair, still on
    # the device (`record_sparse_tiles`), and the shape of their tokens
    _sparse_tiles: collections.deque = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=4096), repr=False)
    _tiles_shape: tuple | None = dataclasses.field(default=None, repr=False)

    def init(self, key: jax.Array) -> tuple[Any, Any]:
        # params AND the whole optimizer state are committed to the mesh:
        # optax's step count is born unplaced, and a round returns it
        # placed — left so, the second round() would be a second compile
        rep = NamedSharding(self.mesh, P())
        params = jax.device_put(init_params(key, self.cfg), rep)
        return params, jax.device_put(self.optimizer.init(params), rep)

    def shard_tokens(self, tokens: np.ndarray | jax.Array) -> jax.Array:
        """[S, B, T] -> sharded (station, none, device)."""
        t = tokens.shape[-1]
        if t > self.cfg.max_len:
            # dynamic_slice would silently CLAMP out-of-range offsets and
            # train with duplicated positional rows — fail loudly instead
            raise ValueError(
                f"sequence length {t} exceeds cfg.max_len={self.cfg.max_len}"
            )
        sh = NamedSharding(self.mesh, P(STATION_AXIS, None, SEQ_AXIS))
        return jax.device_put(jnp.asarray(tokens), sh)

    def round(
        self,
        params: Any,
        opt_state: Any,
        tokens: jax.Array,  # [S, B, T] sharded (station, None, device)
        mask: jax.Array,  # [S] participation
    ) -> tuple[Any, Any, jax.Array]:
        """One federated round: per-station grads (sp inside), FedAvg, step.

        The state handed in is CONSUMED: ``params`` and ``opt_state`` are
        donated to the program, which writes the new state into their
        buffers, so every array of both is deleted when this returns. Go on
        with what is returned; a caller who wants the old state copies it
        first (``jax.tree.map(jnp.copy, ...)``). ``tokens`` and ``mask``
        are left alone.

        Recorded as an ``engine.call`` span over the whole host side of the
        call and, under it, a ``device.launch`` span around the call into
        the jitted program and nothing else (``n_buffers`` = the array
        leaves handed over, ``n_donated`` = those of them the program may
        write its outputs into). With ``attention="recompute"`` the
        ``engine.call`` span also carries the walk the program was built
        with (`attention_walk`; with sparse attention ``sparse_topk`` and
        ``indexer_heads`` too), always how the cross-station mean is taken
        (`aggregation`) and what the layer's checkpoint keeps
        (``remat_saved``, `remat_saved`'s names), and where the stack is
        walked more than once, ``loops`` and ``layer_applications``."""
        attrs = {**self.attention_walk(tokens.shape[-1]),
                 **self.aggregation(params),
                 "remat_saved": list(remat_saved(self.cfg))}
        if self.cfg.loops > 1:
            attrs = {**attrs, "loops": self.cfg.loops,
                     "layer_applications": self.cfg.loops * self.cfg.n_layers}
        with engine_call("fed_transformer.round", 1, **attrs):
            n_donated = len(jax.tree.leaves((params, opt_state)))
            n_buffers = n_donated + len(jax.tree.leaves((tokens, mask)))
            with device_launch("fed_transformer.round", n_buffers, n_donated):
                *out, load, exits, tiles = self._round(
                    params, opt_state, tokens, mask)
        if load is not None:  # stays on the device: record_expert_load
            self._expert_load.append(load)
            self._load_shape = tokens.shape
        if exits is not None:  # likewise: record_exit_distribution
            self._exits.append(exits)
        if tiles is not None:  # likewise: record_sparse_tiles
            self._sparse_tiles.append(tiles)
            self._tiles_shape = tokens.shape
        return tuple(out)

    def attention_walk(self, t: int) -> dict[str, Any]:
        """What says how `recompute_attention` walks visible tiles at sequence
        length ``t``: ``attention_path`` (``"kernel"``: inside the Pallas
        kernels; ``"walk"``: in XLA; ``"sparse"``: the XLA walk over each
        query's kept keys, ops/sparse_attention.py), ``attention_tile``
        (``"<block_q>x<block_k>"``, the blocks that path really uses: both
        `attention_tile`'s) and, summed over the layer applications
        of one sequence and head (every layer once a walk of the stack),
        ``attention_tiles_visited`` of ``attention_tiles`` (a windowed layer
        visits fewer). Computed once a length; nothing for the other
        attention paths."""
        if self.cfg.attention != "recompute":
            return {}
        if t not in self._walks:
            cfg = self.cfg
            path, *tile = attention_tile(
                t, t, cfg.head_dim, cfg.n_heads // cfg.n_kv_heads, cfg.dtype,
                cfg.flash_interpret)
            extra = {}
            if cfg.sparse_top_k:
                path, *tile = sparse.blocks(
                    t, cfg.head_dim, cfg.n_heads // cfg.n_kv_heads, cfg.dtype)
                path = "sparse"
                extra = {"sparse_topk": cfg.sparse_top_k,
                         "indexer_heads": cfg.indexer_heads}
            kinds = collections.Counter(
                cfg.layer_window(i) for i in range(cfg.n_layers))
            counts = cfg.loops * sum(
                n * np.array(tiles_visited(t, t, *tile, True, window))
                for window, n in kinds.items())
            self._walks[t] = {
                "attention_path": path,
                "attention_tile": "{}x{}".format(*tile),
                "attention_tiles_visited": int(counts[0]),
                "attention_tiles": int(counts[1]), **extra}
        return self._walks[t]

    @property
    def _rings(self) -> bool:
        """Whether the cross-station mean goes round a ring inside the mesh
        (`_round`): on several slots. Not where the stack is walked more
        than once: a layer's gradient is whole only when its FIRST walk's
        is, a ring behind the whole backward pass has nothing to run beside,
        and no looped stack on several chips has been read. That keeps
        `fed_mean`'s all-reduces, as one slot does."""
        return self.mesh.shape[STATION_AXIS] > 1 and self.cfg.loops == 1

    def aggregation(self, params: Any) -> dict[str, Any]:
        """How the program takes a round's cross-station mean, from the mesh
        and the shapes of ``params`` (arrays or shapes; computed once, the
        configuration fixes them): ``aggregate_overlap`` ``"ring"`` where the
        mean goes round the slots' ring beside the backward pass (`_rings`)
        and ``"none"`` elsewhere (`fed_mean` after it), ``aggregate_groups``
        the groups of layers whose mean is taken INSIDE the backward pass
        (`collectives.RingExchange`; what is outside the layers follows it)
        and ``aggregate_bytes`` what one chip sends round the ring a round;
        both 0 without a ring. Host integers on `round`'s ``engine.call``
        span; no program reads them."""
        if self._aggregation is None:
            slots = self.mesh.shape[STATION_AXIS]
            crossing = []
            if self._rings:
                crossing = [[params["layers"][i] for i in group]
                            for group in _layer_groups(params["layers"])]
                crossing.append({name: x for name, x in params.items()
                                 if name != "layers"})
            self._aggregation = {
                "aggregate_overlap": "ring" if self._rings else "none",
                "aggregate_groups": max(len(crossing) - 1, 0),
                "aggregate_bytes": sum(
                    collectives.ring_bytes_sent(
                        [(x.shape, x.dtype) for x in jax.tree.leaves(group)],
                        slots)
                    for group in crossing)}
        return self._aggregation

    def row_walk(self, shape: tuple[int, ...]) -> dict[str, int]:
        """What the expert layers of a round over tokens of ``shape``
        [S, B, T] walk at most: ``row_block``, the rows of one block of
        `experts.expert_layer`'s walk over a chunk's sorted assignments, and
        ``row_blocks``, the blocks of a round if every choice of every token
        named an expert held here (all stations, all layers). Host integers
        from the shapes."""
        stations, b, t = shape
        seq = self.mesh.shape[SEQ_AXIS]
        block, blocks = experts.row_walk(b * t // seq, self.cfg.top_k)
        return {"row_block": block,
                "row_blocks": stations * seq * self.cfg.n_layers * blocks}

    def record_expert_load(self) -> dict[str, Any] | None:
        """Read the expert layers' counts of the rounds since the last call
        off the device and record them as ONE ``experts.load`` span:
        ``rounds``, per layer ``assignments_per_round`` (per held expert,
        summed over the stations, mean over the rounds), each round's total
        over layers and experts ``assignments_by_round``, and over all layers
        ``max_over_mean`` (the fullest held expert over the mean one) and
        ``dropped`` (choices that named a held expert less rows its product
        ran over: 0, there is no capacity), and of the walk over row blocks
        ``row_block``, ``row_blocks`` (`row_walk`: what a round would walk in
        the worst case) and ``row_blocks_walked`` (what the rounds walked,
        the blocks that carried an assignment, mean over the rounds). A
        round leaves its counts on the device and this call fetches them, so
        call it OUTSIDE what is timed. Returns the attributes, or None where
        there is nothing to record (no expert layer, no round since the last
        call)."""
        pending = list(self._expert_load)
        self._expert_load.clear()
        if not pending:
            return None
        counts = jax.device_get(pending)
        a = np.stack([c["assignments"] for c in counts])  # [R, L, E]
        routed = np.stack([c["routed_here"] for c in counts])
        attrs = {
            "rounds": len(counts),
            "assignments_per_round": (a.sum(0) / len(counts)).tolist(),
            "assignments_by_round": a.sum((1, 2)).tolist(),
            **experts.load_summary(a, routed),
            **self.row_walk(self._load_shape),
            "row_blocks_walked": float(np.sum(
                [c["row_blocks_walked"] for c in counts]) / len(counts)),
        }
        with TRACER.span("experts.load", kind="engine", attrs=attrs):
            pass
        return attrs

    def record_sparse_tiles(self) -> dict[str, Any] | None:
        """Read sparse attention's counts of the rounds since the last call
        off the device and record them as ONE ``sparse.tiles`` span:
        ``rounds``, ``tiles_selected_per_layer`` (the walk's tiles that held
        a kept pair, summed over the sequences of a round, mean over the
        rounds), ``tiles_visible_per_layer`` (the walk's visible tiles over
        the same sequences: `attention_walk`), and ``selected_tile_share``,
        the one over the other over all layers. Like `record_expert_load`:
        call it OUTSIDE what is timed; None where there is nothing to record
        (no sparse attention, no round since the last call)."""
        pending = list(self._sparse_tiles)
        self._sparse_tiles.clear()
        if not pending:
            return None
        counts = np.stack(jax.device_get(pending)).astype(np.int64)  # [R, L]
        stations, b, t = self._tiles_shape
        walk = self.attention_walk(t)
        visible = stations * b * walk["attention_tiles_visited"] // (
            self.cfg.n_layers)
        attrs = {
            "rounds": len(pending),
            "tiles_selected_per_layer": (
                counts.sum(0) / len(pending)).tolist(),
            "tiles_visible_per_layer": visible,
            "selected_tile_share": float(
                counts.sum() / (counts.size * visible)),
        }
        with TRACER.span("sparse.tiles", kind="engine", attrs=attrs):
            pass
        return attrs

    def record_exit_distribution(self) -> dict[str, Any] | None:
        """Read the exit distributions of the rounds since the last call off
        the device and record them as ONE ``exits.distribution`` span:
        ``rounds``, ``by_round`` (per round the mean over stations and
        predicted tokens of the distribution of the walk a token would leave
        at, [R]), ``mean`` over the rounds, and ``expected_exit_step``
        (``sum_r r * mean_r``). Like `record_expert_load`: call it OUTSIDE
        what is timed; None where there is nothing to record (a stack walked
        once, no round since the last call)."""
        pending = list(self._exits)
        self._exits.clear()
        if not pending:
            return None
        sums = np.stack(jax.device_get(pending)).astype(np.float64)
        by_round = sums / sums.sum(axis=1, keepdims=True)
        mean = by_round.mean(axis=0)
        attrs = {
            "rounds": len(pending),
            "mean": mean.tolist(),
            "by_round": by_round.tolist(),
            "expected_exit_step": float(
                mean @ np.arange(1, mean.size + 1)),
        }
        with TRACER.span("exits.distribution", kind="engine", attrs=attrs):
            pass
        return attrs

    # params and opt_state are donated: every state output has an input of
    # its shape, dtype and placement, so the runtime allocates only the loss
    # (and the experts' counts or the exit distribution) before the program
    # may start
    @partial(jax.jit, static_argnums=0, donate_argnums=(1, 2))
    def _round(
        self, params: Any, opt_state: Any, tokens: jax.Array,
        mask: jax.Array,
    ) -> tuple[Any, Any, jax.Array, Any, Any, Any]:
        rings = self._rings
        ring = collectives.station_ring(self.mesh.devices[:, 0])

        def station_body(params, tokens_block, w=None, denom=None):
            # tokens_block: [S/D_s, B, T/P] — the inner vmap walks the
            # stations PACKED into this mesh slot (stations_per_slot > 1
            # when the mesh folds more stations than device slots, same
            # contract as FederationMesh.fed_map)
            def one_station(tok, w_own=None):
                exchange = collectives.RingExchange(
                    w_own, denom, STATION_AXIS, ring, PACKED_AXIS
                ) if rings else None
                (loss, left), grads = jax.value_and_grad(
                    _loss_and_load, has_aux=True
                )(params, tok, self.cfg, SEQ_AXIS, exchange)
                # reduce over sequence shards WITHIN the station only
                grads = lax.psum(grads, SEQ_AXIS)
                loss = lax.pmean(loss, SEQ_AXIS)
                return loss, grads, lax.psum(left, SEQ_AXIS)

            with jax.named_scope("local_train"):
                if not rings:
                    return jax.vmap(one_station)(tokens_block)
                return jax.vmap(one_station, axis_name=PACKED_AXIS)(
                    tokens_block, w)

        # Variance checking OFF, same stance (and reason) as fed_map: the
        # station body is a purely local program whose only cross-device
        # traffic is the explicit psum over the station's own 'device' axis;
        # works around the pallas-interpret + VMA interaction that rejects
        # the flash kernel inside a checked shard_map (jax 0.9 asks for
        # exactly this workaround).
        if not rings:
            losses, grads, left = jax.shard_map(
                station_body,
                mesh=self.mesh,
                in_specs=(P(), P(STATION_AXIS, None, SEQ_AXIS)),
                out_specs=(P(STATION_AXIS), P(STATION_AXIS), P(STATION_AXIS)),
                check_vma=False,
            )(params, tokens)
            # explicit cross-station aggregation: the ONLY place station data mixes
            g_mean = collectives.fed_mean(grads, mask=mask)
        else:
            # the stations lie on several slots: the same mean of the
            # gradients, taken inside the mesh round a ring of asynchronous
            # steps that run beside the backward pass and the optimizer
            # (`collectives.OverAxis`, `RingExchange`)
            def ring_body(params, tokens_block, w, denom):
                losses, grads, left = station_body(
                    params, tokens_block, w, denom)
                # the layers: every lane came back with the one mean
                layers = jax.tree.map(lambda x: x[0], grads.pop("layers"))
                g_mean = collectives.fed_mean(collectives.OverAxis(
                    grads, STATION_AXIS, ring, denom), weights=w)
                g_mean["layers"] = layers
                return losses, g_mean, left

            w = collectives._norm_weights(mask.shape[0], None, mask)
            total = jnp.sum(w)
            losses, g_mean, left = jax.shard_map(
                ring_body,
                mesh=self.mesh,
                in_specs=(P(), P(STATION_AXIS, None, SEQ_AXIS),
                          P(STATION_AXIS), P()),
                out_specs=(P(STATION_AXIS), P(), P(STATION_AXIS)),
                check_vma=False,
            )(params, tokens, w, jnp.where(total > 0, total, 1.0))
        with jax.named_scope("server_update"):
            updates, opt_state = self.optimizer.update(
                g_mean, opt_state, params
            )
            params = optax.apply_updates(params, updates)
        loss = collectives.fed_mean(losses, mask=mask)
        # the expert layers' counts ([L, E_held] and [L]), the exit
        # distribution ([R]) or sparse attention's tiles ([L]), summed over
        # the stations; nothing for the plain block
        load, exits, tiles = jax.tree.map(lambda x: jnp.sum(x, axis=0), left)
        return params, opt_state, loss, load, exits, tiles


def make_engine(
    n_stations: int,
    seq_devices: int,
    cfg: TransformerConfig | None = None,
    lr: float = 1e-3,
    devices: Any = None,
) -> FedTransformer:
    cfg = cfg or TransformerConfig()
    if cfg.attention in ("flash", "recompute") and seq_devices != 1:
        raise ValueError(
            f"attention={cfg.attention!r} needs the full sequence per "
            f"device (seq_devices == 1, got {seq_devices}); use 'ring' for "
            "sequence-parallel runs"
        )
    devs = list(devices if devices is not None else jax.devices())
    if len(devs) < seq_devices:
        raise ValueError(
            f"need at least {seq_devices} devices for {seq_devices} "
            f"sequence shards, have {len(devs)}"
        )
    # station-axis size: the largest divisor of S that fits the hardware —
    # remaining stations FOLD into each slot (stations_per_slot, walked by
    # an inner vmap in round()), the same packing as FederationMesh. One
    # chip can therefore run an S-station federated round; with S*seq
    # devices every station owns real hardware.
    usable_slots = len(devs) // seq_devices
    station_slots = _largest_divisor_leq(n_stations, usable_slots)
    arr = np.array(devs[: station_slots * seq_devices]).reshape(
        station_slots, seq_devices
    )
    mesh = Mesh(arr, (STATION_AXIS, SEQ_AXIS))
    return FedTransformer(mesh=mesh, cfg=cfg, optimizer=optax.adam(lr))


def make_federated_tokens(
    n_stations: int, batch: int, seq_len: int, vocab: int, seed: int = 0
) -> np.ndarray:
    """Synthetic per-station corpora with station-distinct statistics."""
    rng = np.random.default_rng(seed)
    out = np.empty((n_stations, batch, seq_len), np.int32)
    for s in range(n_stations):
        # each station's corpus favors a distinct token range (non-IID)
        center = (s + 1) * vocab // (n_stations + 1)
        vals = rng.normal(center, vocab / 6, (batch, seq_len))
        out[s] = np.clip(np.round(vals), 0, vocab - 1)
    return out

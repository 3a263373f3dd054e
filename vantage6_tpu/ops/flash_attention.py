"""Pallas flash-attention block kernel (TPU).

The hot op of the long-context path (fed_transformer + ring attention).
XLA already fuses the einsum softmax chain reasonably; this kernel keeps the
whole online-softmax loop in VMEM with no [Tq, Tk] materialization in HBM —
the standard flash formulation (Dao et al. 2022) written natively for the
MXU: scores and the weighted-value accumulation are back-to-back matmuls per
(block_q, block_k) tile, accumulated in float32.

Grid ``(batch*head, q block, k block)``: K and V stream through VMEM one
``[block_k, d]`` tile per step of the last ("arbitrary") grid axis, and the
online-softmax state lives in VMEM scratch across those steps — the running
max ``m`` and normalizer ``l`` as lane-replicated ``[block_q, 128]`` f32
tiles, the accumulator as ``[block_q, d]`` f32. VMEM use is therefore fixed
by the block sizes, not by the sequence length.

``q_offset``/``k_offset`` are runtime scalars (prefetched) giving the global
position of this shard's first query/key token, so the SAME kernel serves
monolithic causal attention (offsets 0) and each hop of ring attention
(offsets = shard index × shard length, see parallel.ring_attention).

Compiled (``interpret=False``) the kernel runs on the TPU and nowhere else;
the CPU tests run it with ``interpret=True``.

``recompute_attention`` is the training path of every benchmark cell: a walk
over the visible (query block, key block) tiles, forward and backward, with
residuals (q, k, v, o, L) and grouped kv heads, a causal window and traced
offsets. On a TPU the walk runs inside two further kernels ("kernel path"
below: a whole head a grid step, the tiles a loop inside it); anywhere else
the same walk runs in XLA ("tiled path"). ``attention_tile`` says which.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128  # lane width of a TPU vector register / VMEM tile


def _sublanes(dtype) -> int:
    """Rows of the smallest TPU tile of ``dtype``: (8, 128) for 4-byte
    types, (16, 128) for bf16, (32, 128) for 1-byte types."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _block_sizes(
    t_q: int, t_k: int, block_q: int, block_k: int, dtype, interpret: bool
) -> tuple[int, int]:
    """The tile sizes one call uses — shared by the kernel path and its
    ``interpreter_twin`` so both pad and block identically. A sequence
    shorter than the requested block gets ONE block, rounded up to what a
    tile of ``dtype`` needs (rows: the dtype's sublane count; the key block
    is also the lane axis of the score tile, so a multiple of 128).
    Caller-chosen blocks are taken as given, and refused for a compiled
    kernel when the chip cannot tile them."""
    sub = _sublanes(dtype)
    block_q = min(block_q, _round_up(t_q, sub))
    block_k = min(block_k, _round_up(t_k, LANES))
    if not interpret and (block_q % sub or block_k % LANES):
        raise ValueError(
            f"compiled flash attention needs block_q % {sub} == 0 and "
            f"block_k % {LANES} == 0 for {jnp.dtype(dtype).name}, got "
            f"block_q={block_q}, block_k={block_k}"
        )
    return block_q, block_k


def _softmax_step(q, kblk, vblk, m, l, acc, q_pos0, k_pos0, k_idx0, k_valid,
                  *, causal: bool, scale: float, window: int | None = None):
    """One (q block, k block) online-softmax update — THE op sequence, run
    by the kernel on refs' values and replayed by ``interpreter_twin`` on
    plain arrays. ``m``/``l`` are lane-replicated ``[block_q, LANES]``,
    ``acc`` is ``[block_q, d]``, all f32; ``q_pos0``/``k_pos0`` are the
    global positions of the tiles' first rows, ``k_idx0`` the key tile's
    first index within this shard, ``k_valid`` the shard's true length."""
    block_q, block_k = q.shape[0], kblk.shape[0]
    # q/k stay in their native dtype: on bf16 inputs the MXU runs at bf16
    # rate with float32 accumulation (preferred_element_type below); an
    # upfront astype(f32) would silently demote to the f32 matmul rate
    s = jax.lax.dot_general(
        q, kblk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # [block_q, block_k], f32 accumulation
    cols = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    # padded key slots (index >= true Tk) never contribute
    s = jnp.where(k_idx0 + cols < k_valid, s, NEG_INF)
    if causal:
        rows = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        s = jnp.where(q_pos0 + rows >= k_pos0 + cols, s, NEG_INF)
        if window is not None:  # none of the keys before the window either
            s = jnp.where(k_pos0 + cols > q_pos0 + rows - window, s, NEG_INF)
    blk_max = jnp.max(s, axis=1, keepdims=True)
    # clamp at a finite floor: for a fully-masked block, exp(s - m_new)
    # must be exp(-huge) = 0, NOT exp(NEG_INF - NEG_INF) = 1
    m_new = jnp.maximum(jnp.maximum(m, blk_max), -1e20)
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[:, :1])
    l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc * corr[:, :1] + pv


def _finish(l, acc, dtype):
    denom = jnp.where(l > 0, l, 1.0)
    return (acc / denom[:, :1]).astype(dtype)


def _kernel(
    qoff_ref,
    koff_ref,
    kvalid_ref,
    q_ref,  # [block_q, d]
    k_ref,  # [block_k, d]
    v_ref,  # [block_k, d]
    o_ref,  # [block_q, d]
    m_ref,  # [block_q, LANES] f32 scratch
    l_ref,  # [block_q, LANES] f32 scratch
    acc_ref,  # [block_q, d] f32 scratch
    *,
    causal: bool,
    scale: float,
):
    block_q, block_k = q_ref.shape[0], k_ref.shape[0]
    qi, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    k_idx0 = kb * block_k
    m_ref[...], l_ref[...], acc_ref[...] = _softmax_step(
        q_ref[...], k_ref[...], v_ref[...],
        m_ref[...], l_ref[...], acc_ref[...],
        qoff_ref[0] + qi * block_q, koff_ref[0] + k_idx0, k_idx0,
        kvalid_ref[0], causal=causal, scale=scale,
    )

    @pl.when(kb == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = _finish(l_ref[...], acc_ref[...], o_ref.dtype)


def flash_attention(
    q: jax.Array,  # [B, H, Tq, D]
    k: jax.Array,  # [B, H, Tk, D]
    v: jax.Array,  # [B, H, Tk, D]
    q_offset: jax.Array | int = 0,
    k_offset: jax.Array | int = 0,
    causal: bool = False,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention per (batch, head); Tq/Tk padded to block multiples
    internally. Layout [B, H, T, D] (head-major for clean 2D tiles).

    Differentiable: the forward runs the Pallas kernel; the backward
    recomputes attention (flash-style, nothing but q/k/v/o saved) and
    applies the standard softmax-attention VJP in jnp — see
    ``_attention_bwd``."""
    d = q.shape[-1]
    if k.shape[1] != q.shape[1]:
        raise ValueError(
            f"the flash kernel takes as many key/value heads as query heads "
            f"(got {k.shape[1]} and {q.shape[1]}): use recompute_attention")
    if scale is None:
        scale = 1.0 / (d**0.5)
    fn = _flash_vjp(causal, float(scale), block_q, block_k, interpret)
    qoff = jnp.asarray(q_offset, jnp.int32)
    koff = jnp.asarray(k_offset, jnp.int32)
    return fn(q, k, v, qoff, koff)


def _attach_recompute_vjp(forward, causal, scale):
    """Wrap `forward(q, k, v, qoff, koff) -> o` in a custom_vjp whose
    backward is the blockwise recompute (_attention_bwd): residuals are
    only (q, k, v, o) — never the [Tq, Tk] score/probability tensors."""

    @jax.custom_vjp
    def fa(q, k, v, qoff, koff):
        return forward(q, k, v, qoff, koff)

    def fwd(q, k, v, qoff, koff):
        o = fa(q, k, v, qoff, koff)
        return o, (q, k, v, o, qoff, koff)

    def bwd(res, do):
        q, k, v, o, qoff, koff = res
        dq, dk, dv = _attention_bwd(
            q, k, v, o, do, qoff, koff, causal, scale
        )
        return dq, dk, dv, None, None

    fa.defvjp(fwd, bwd)
    return fa


@functools.lru_cache(maxsize=None)
def _flash_vjp(causal, scale, block_q, block_k, interpret):
    """custom_vjp wrapper per static config (cached so jax sees ONE callable
    per config — fresh wrappers would defeat jit tracing caches)."""
    return _attach_recompute_vjp(
        functools.partial(
            _flash_forward, causal=causal, scale=scale, block_q=block_q,
            block_k=block_k, interpret=interpret,
        ),
        causal,
        scale,
    )


def recompute_attention(
    q: jax.Array,  # [B, H, Tq, D]
    k: jax.Array,
    v: jax.Array,
    q_offset: jax.Array | int = 0,
    k_offset: jax.Array | int = 0,
    causal: bool = False,
    scale: float | None = None,
    block_k: int | None = None,  # None: `attention_tile` of the shapes
    window: int | None = None,
    block_q: int | None = None,
    interpret: bool | None = None,  # None: whether this is no TPU
) -> jax.Array:
    """Flash-MEMORY attention: an online-softmax forward over (query block,
    key block) tiles and the softmax-attention VJP over the same tiles, as
    two Pallas kernels on a TPU (`_kernel_forward`, `_kernel_bwd`) and in
    plain jnp/XLA wherever a kernel would be interpreted (`_tiled_forward`,
    `_tiled_bwd`): `attention_tile` says which, and at which blocks.

    Peak transient memory is O(block_q * block_k) a head in BOTH directions
    and the residuals are (q, k, v, o) and the row statistics L ([B, H, Tq]
    f32) — the [Tq, Tk] probabilities that a naive XLA attention saves for
    backward (the memory wall for long context) never exist.

    For each query block the walk visits only the key blocks that hold a
    visible key (`_key_block_range`): every one without ``causal``, none
    above the causal diagonal with it and, with ``window``, none wholly
    outside ``(i - window, i]``. ``k``/``v`` may carry fewer heads than
    ``q`` (``[B, H_kv, T, D]`` beside ``[B, H_q, T, D]``, ``H_q % H_kv ==
    0``: query head ``h`` reads kv head ``h // (H_q // H_kv)``; the
    repeated heads are never materialised, forward or backward). Where the
    caller names no ``block_q`` / ``block_k`` the tile is `attention_tile`
    of the shapes handed in, whose values were read on the chip."""
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d**0.5)
    if window is not None and not causal:
        raise ValueError("a window is a causal window: pass causal=True")
    if q.shape[1] % k.shape[1]:
        raise ValueError(
            f"{q.shape[1]} query heads do not divide over "
            f"{k.shape[1]} key/value heads")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rule = attention_tile(q.shape[2], k.shape[2], d,
                          q.shape[1] // k.shape[1], q.dtype, interpret)
    config = (causal, float(scale), window,
              block_q or rule.block_q, block_k or rule.block_k)
    fn = (_kernel_vjp(*config, interpret) if rule.path == "kernel"
          else _tiled_vjp(*config))
    return fn(
        q, k, v,
        jnp.asarray(q_offset, jnp.int32),
        jnp.asarray(k_offset, jnp.int32),
    )


# ------------------------------------------------------------- tiled path
TILED_BLOCK = 512  # the largest tile `attention_tile` names for the XLA
TILED_BLOCK_MIN = 256  # walk, and the smallest (a shorter sequence is one)
KERNEL_BLOCK = 512  # the kernels' (query block, key block): PERF.md, PR 38


class Blocks(NamedTuple):
    """What `attention_tile` says of a call: which walk it takes
    (``"kernel"``: the Pallas kernels; ``"walk"``: the same walk in XLA) and
    the (query block, key block) that walk really uses."""
    path: str
    block_q: int
    block_k: int


def attention_tile(t_q: int, t_k: int, head_dim: int, group: int, dtype,
                   interpret: bool) -> Blocks:
    """The path and the (query block, key block) a call uses where its
    caller names none, from what the call sees: the sequence lengths, the
    head size, the query heads a kv head serves (``group``), the dtype and
    whether a kernel would be interpreted (anything but a TPU).

    Compiled, the walk runs inside the kernels where a head is whole lane
    tiles (128 wide: the kernels read it where it lies, a column slab of
    [B, T, H * D]) and a head's step fits the chip's VMEM (`_kernel_vmem`: t
    8,192 does, t 32,768 does not). Their blocks were read on the chip at t
    4,096 and 8,192, alone and seven query heads a kv head: 512 x 512 was
    the fastest pair at each, so ``group`` moves nothing yet. Heads of 64
    stay on the XLA walk: at t 1,024 the kernels won 3.4% of a round of four
    packed stations and LOST 2.5% where each station has a chip and the
    cross-station ring runs beside the backward pass (PERF.md section 6,
    PR 38).

    Everywhere else the XLA walk runs, at a sixteenth of the longer length
    in whole lane widths, held between ``TILED_BLOCK_MIN`` and
    ``TILED_BLOCK``: on the chip 256 x 256 was the fastest pair, or within
    4% of it, at t 1024 and 2048, and 512 x 512 at t 8192 (PERF.md section
    6, PR 34)."""
    if not interpret and head_dim % LANES == 0:
        block_q, block_k = _kernel_blocks(
            t_q, t_k, KERNEL_BLOCK, KERNEL_BLOCK, interpret)
        if _kernel_vmem(_round_up(t_q, block_q), _round_up(t_k, block_k),
                        head_dim, dtype, block_q, block_k) <= KERNEL_VMEM:
            return Blocks("kernel", block_q, block_k)
    sixteenth = max(t_q, t_k) // 16 // LANES * LANES  # whole lane tiles
    block = min(TILED_BLOCK, max(TILED_BLOCK_MIN, sixteenth))
    return Blocks("walk", min(block, t_q), min(block, t_k))


def tiles_visited(t_q: int, t_k: int, block_q: int, block_k: int,
                  causal: bool, window: int | None) -> tuple[int, int]:
    """How many (query block, key block) tiles one head's walk visits at
    offsets 0, and how many the ``[t_q, t_k]`` square holds: the ranges of
    `_key_block_range`, summed over the query blocks (on the host: Python
    integers in, Python integers out, no program on the device)."""
    block_q, block_k = min(block_q, t_q), min(block_k, t_k)
    n_q, n_k = -(-t_q // block_q), -(-t_k // block_k)
    visited = 0
    for i in range(n_q):
        lo, hi = _key_block_range(
            i, block_q, block_k, n_k, t_k, 0, 0, causal, window)
        visited += max(hi - lo, 0)
    return visited, n_q * n_k


def _clip(x, hi):
    """``x`` held to ``[0, hi]``: a traced scalar, or a Python integer."""
    return min(max(x, 0), hi) if isinstance(x, int) else jnp.clip(x, 0, hi)


def _key_block_range(i, block_q, block_k, n_kblocks, t_k, q_offset, k_offset,
                     causal, window):
    """The key blocks ``[lo, hi)`` that hold a key some query of query block
    ``i`` may see: local key index ``j`` is visible to global query position
    ``p`` iff ``k_offset + j <= p`` (causal) and ``k_offset + j > p -
    window``. Traced scalars (or Python integers throughout, for
    `tiles_visited`); an empty range reads ``hi <= lo``."""
    first_q = q_offset + i * block_q
    hi = n_kblocks
    if causal:
        last_key = _clip(first_q + block_q - k_offset, t_k)  # exclusive
        hi = (last_key + block_k - 1) // block_k
    lo = 0
    if window is not None:
        lo = _clip(first_q - window + 1 - k_offset, t_k) // block_k
    return lo, hi


def _tile_scores(q_i, k_j, q_pos, k_idx, k_offset, t_k, causal, window,
                 scale):
    """Masked scores of one (query block, key block) tile, f32:
    ``q_i`` [B, Hkv, G, bq, D], ``k_j`` [B, Hkv, bk, D]."""
    s = jnp.einsum(
        "bhgqd,bhkd->bhgqk", q_i, k_j, preferred_element_type=jnp.float32
    ) * scale
    valid = (k_idx < t_k)[None, :]
    if causal:
        k_pos = (k_offset + k_idx)[None, :]
        valid = valid & (q_pos[:, None] >= k_pos)
        if window is not None:
            valid = valid & (k_pos > q_pos[:, None] - window)
    return jnp.where(valid[None, None, None], s, NEG_INF)


def _tiles(q, k, v, block_q, block_k):
    """Pad to whole blocks and group the query heads by their kv head:
    q -> [n_q, B, Hkv, G, bq, D] (scan input), k, v -> [B, Hkv, Tk_p, D]."""
    b, h_q, t_q, d = q.shape
    h_kv, t_k = k.shape[1], k.shape[2]
    block_q, block_k = min(block_q, t_q), min(block_k, t_k)
    pad_q, pad_k = (-t_q) % block_q, (-t_k) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    n_q = (t_q + pad_q) // block_q
    qb = jnp.moveaxis(
        q.reshape(b, h_kv, h_q // h_kv, n_q, block_q, d), 3, 0)
    return qb, k, v, block_q, block_k


def _tiled_forward(q, k, v, q_offset, k_offset, *, causal, scale, window,
                   block_q, block_k, keep=None):
    """Online-softmax forward over (query block, visible key block) tiles.
    Returns ``o`` [B, Hq, Tq, D] and the row statistics ``L = m + log l``
    [B, Hq, Tq] (f32), which the backward reads instead of a first pass.
    ``keep``, where given, masks every tile further: ``keep(i, j)`` is the
    [B, bq, bk] bool of the pairs of query block ``i`` and key block ``j``
    that are attended (`ops/sparse_attention.py`)."""
    b, h_q, t_q, d = q.shape
    h_kv, t_k = k.shape[1], k.shape[2]
    g = h_q // h_kv
    qb, k, v, block_q, block_k = _tiles(q, k, v, block_q, block_k)
    n_kblocks = k.shape[2] // block_k
    q_off, k_off = jnp.reshape(q_offset, ()), jnp.reshape(k_offset, ())

    def one_query_block(i, q_i):
        q_pos = q_off + i * block_q + jnp.arange(block_q)
        lo, hi = _key_block_range(i, block_q, block_k, n_kblocks, t_k,
                                  q_off, k_off, causal, window)

        def step(j, carry):
            m, l, acc = carry
            k_j = lax.dynamic_slice_in_dim(k, j * block_k, block_k, 2)
            v_j = lax.dynamic_slice_in_dim(v, j * block_k, block_k, 2)
            s = _tile_scores(q_i, k_j, q_pos, j * block_k
                             + jnp.arange(block_k), k_off, t_k, causal,
                             window, scale)
            if keep is not None:
                s = jnp.where(keep(i, j)[:, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.maximum(jnp.max(s, -1), -1e20))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l = l * corr + jnp.sum(p, -1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(v_j.dtype), v_j,
                preferred_element_type=jnp.float32,
            )
            return m_new, l, acc

        m0 = jnp.full((b, h_kv, g, block_q), NEG_INF, jnp.float32)
        m, l, acc = lax.fori_loop(lo, hi, step, (
            m0, jnp.zeros_like(m0),
            jnp.zeros((b, h_kv, g, block_q, d), jnp.float32)))
        safe_l = jnp.where(l > 0, l, 1.0)
        return (acc / safe_l[..., None]).astype(q.dtype), m + jnp.log(safe_l)

    def scan_body(i, q_i):
        return i + 1, one_query_block(i, q_i)

    _, (ob, lb) = lax.scan(scan_body, jnp.int32(0), qb)
    o = jnp.moveaxis(ob, 0, 3).reshape(b, h_q, -1, d)[:, :, :t_q]
    big_l = jnp.moveaxis(lb, 0, 3).reshape(b, h_q, -1)[:, :, :t_q]
    return o, big_l


@functools.lru_cache(maxsize=None)
def _add_at(axis: int):
    """``add(acc, block, at)``: ``acc`` with ``block`` added to its slice
    ``[at, at + block.shape[axis])`` along ``axis``, in place. Under `vmap`
    with one ``at`` for the whole batch it stays that update, one axis
    further in: jax's own rule for `dynamic_update_slice` makes every batched
    one a scatter, which on the chip cost the backward of the packed
    stations more than its products (PERF.md section 6, PR 34)."""

    def add(acc, block, at):
        size = block.shape[axis]
        return lax.dynamic_update_slice_in_dim(
            acc, lax.dynamic_slice_in_dim(acc, at, size, axis) + block,
            at, axis)

    add_unbatched = jax.custom_batching.custom_vmap(add)

    @add_unbatched.def_vmap
    def _(axis_size, in_batched, acc, block, at):
        if in_batched[2]:  # a start of its own per element: jax's rule
            in_axes = [0 if b else None for b in in_batched]
            return jax.vmap(add, in_axes=in_axes)(acc, block, at), True
        acc, block = (
            x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
            for x, b in zip((acc, block), in_batched))
        return _add_at(axis + 1)(acc, block, at), True

    return add_unbatched


def _tiled_bwd(q, k, v, o, big_l, do, q_offset, k_offset, *, causal, scale,
               window, block_q, block_k, keep=None):
    """The softmax-attention VJP over the same tiles: for each query block
    the visible key blocks only, ``P = exp(S - L)`` recomputed per tile,
    ``dK``/``dV`` accumulated in place in f32 (`_add_at`; a kv head sums
    over its group of query heads inside the product), products in the
    inputs' dtype with f32 accumulation; ``keep``: `_tiled_forward`'s."""
    b, h_q, t_q, d = q.shape
    h_kv, t_k = k.shape[1], k.shape[2]
    g = h_q // h_kv
    d_term = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
    qb, k_p, v_p, block_q, block_k = _tiles(q, k, v, block_q, block_k)
    n_q = qb.shape[0]
    pad_q = n_q * block_q - t_q
    n_kblocks = k_p.shape[2] // block_k
    q_off, k_off = jnp.reshape(q_offset, ()), jnp.reshape(k_offset, ())

    def rows(x):  # [B, Hq, Tq, ...] -> [n_q, B, Hkv, G, bq, ...]
        if pad_q:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, pad_q))
                        + ((0, 0),) * (x.ndim - 3))
        x = x.reshape((b, h_kv, g, n_q, block_q) + x.shape[3:])
        return jnp.moveaxis(x, 3, 0)

    def scan_body(carry, blk):
        i, dk, dv = carry
        q_i, do_i, l_i, d_i = blk
        q_pos = q_off + i * block_q + jnp.arange(block_q)
        lo, hi = _key_block_range(i, block_q, block_k, n_kblocks, t_k,
                                  q_off, k_off, causal, window)

        def step(j, carry):
            dq_i, dk, dv = carry
            k_j = lax.dynamic_slice_in_dim(k_p, j * block_k, block_k, 2)
            v_j = lax.dynamic_slice_in_dim(v_p, j * block_k, block_k, 2)
            s = _tile_scores(q_i, k_j, q_pos, j * block_k
                             + jnp.arange(block_k), k_off, t_k, causal,
                             window, scale)
            if keep is not None:
                s = jnp.where(keep(i, j)[:, None, None], s, NEG_INF)
            p = jnp.exp(s - l_i[..., None])
            dv_j = jnp.einsum(
                "bhgqk,bhgqd->bhkd", p.astype(do_i.dtype), do_i,
                preferred_element_type=jnp.float32)
            dp = jnp.einsum(
                "bhgqd,bhkd->bhgqk", do_i, v_j,
                preferred_element_type=jnp.float32)
            ds = (p * (dp - d_i[..., None])).astype(q_i.dtype)
            dq_i = dq_i + jnp.einsum(
                "bhgqk,bhkd->bhgqd", ds, k_j,
                preferred_element_type=jnp.float32) * scale
            dk_j = jnp.einsum(
                "bhgqk,bhgqd->bhkd", ds, q_i,
                preferred_element_type=jnp.float32) * scale
            add_block = _add_at(2)
            return (dq_i, add_block(dk, dk_j, j * block_k),
                    add_block(dv, dv_j, j * block_k))

        dq_i, dk, dv = lax.fori_loop(lo, hi, step, (
            jnp.zeros((b, h_kv, g, block_q, d), jnp.float32), dk, dv))
        return (i + 1, dk, dv), dq_i.astype(q.dtype)

    zeros = jnp.zeros(k_p.shape, jnp.float32)
    (_, dk, dv), dqb = lax.scan(
        scan_body, (jnp.int32(0), zeros, zeros),
        (qb, rows(do), rows(big_l), rows(d_term)))
    dq = jnp.moveaxis(dqb, 0, 3).reshape(b, h_q, -1, d)[:, :, :t_q]
    return (dq, dk[:, :, :t_k].astype(k.dtype),
            dv[:, :, :t_k].astype(v.dtype))


def _walk_vjp(forward, backward):
    """custom_vjp of a walk over visible tiles: ``forward`` gives ``(o, L)``
    and the residuals are (q, k, v, o, L): L is [B, Hq, Tq] f32, so the
    backward needs no first pass over the keys for the softmax statistics."""

    @jax.custom_vjp
    def fa(q, k, v, qoff, koff):
        return forward(q, k, v, qoff, koff)[0]

    def fwd(q, k, v, qoff, koff):
        o, big_l = forward(q, k, v, qoff, koff)
        return o, (q, k, v, o, big_l, qoff, koff)

    def bwd(res, do):
        q, k, v, o, big_l, qoff, koff = res
        return (*backward(q, k, v, o, big_l, do, qoff, koff), None, None)

    fa.defvjp(fwd, bwd)
    return fa


@functools.lru_cache(maxsize=None)
def _tiled_vjp(causal, scale, window, block_q, block_k):
    """The XLA walk, one callable per static config."""
    kw = dict(causal=causal, scale=scale, window=window, block_q=block_q,
              block_k=block_k)
    return _walk_vjp(functools.partial(_tiled_forward, **kw),
                     functools.partial(_tiled_bwd, **kw))


# ------------------------------------------------------------ kernel path
# The same walk inside two Pallas kernels. A grid step owns ONE query head:
# its queries and its kv head's keys and values are blocks in VMEM, and
# (backward) the kv head's float32 ``dK`` / ``dV`` sums are scratch there,
# while the step walks the query blocks and, for each, the visible key
# blocks (`_key_block_range`, the trip count of the inner loop), so a tile's
# scores, probabilities and ``dS`` live in VMEM from the product that makes
# them to the product that reads them.
KERNEL_VMEM = 100 * 2**20  # what a step may hold of a v5e's 128 MiB


def _kernel_vmem(t_q: int, t_k: int, head_dim: int, dtype, block_q: int,
                 block_k: int) -> int:
    """Bytes of VMEM the backward kernel's step holds (the larger of the
    two): a head's q, dO and dQ, the kv head's k, v, dK and dV, each twice
    (Pallas double-buffers a block), the row statistics, the float32 sums
    of dK and dV and a few float32 tiles of scores."""
    item = jnp.dtype(dtype).itemsize
    blocks = (3 * t_q + 4 * t_k) * item * head_dim + 64 * t_q
    return 2 * blocks + 2 * t_k * head_dim * 4 + 8 * block_q * block_k * 4


def _kernel_blocks(t_q, t_k, block_q, block_k, interpret):
    """A sequence shorter than a block is one block; compiled, a block is
    whole lane tiles (it is the lane axis of a score tile, forward or
    backward)."""
    unit = 1 if interpret else LANES
    block_q = min(block_q, _round_up(t_q, unit))
    block_k = min(block_k, _round_up(t_k, unit))
    if block_q % unit or block_k % unit:
        raise ValueError(
            f"the compiled attention kernels need blocks of whole lane "
            f"tiles ({LANES}), got {block_q} x {block_k}")
    return block_q, block_k


def _pad_rows(x, block, axis=2):
    pad = (-x.shape[axis]) % block
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _rows(i, block):
    return pl.ds(pl.multiple_of(i * block, block), block)


_CONTRACT_LAST = (((1,), (1,)), ((), ()))  # a @ b.T
_CONTRACT_FIRST = (((0,), (0,)), ((), ()))  # a.T @ b


def _walk_fwd_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, o_ref, l_ref,
                     *, causal, scale, window, block_q, block_k, t_k):
    """One query head forward: q_ref / o_ref [Tq, D], k_ref / v_ref
    [Tk, D], l_ref [Tq / block_q, block_q] (a query block's statistics are
    a row). `_tiled_forward`'s online softmax, a tile by `_softmax_step`."""
    n_k = k_ref.shape[0] // block_k
    q_off, k_off = qoff_ref[0], koff_ref[0]

    def query_block(i, _):
        q_i = q_ref[_rows(i, block_q), :]
        lo, hi = _key_block_range(i, block_q, block_k, n_k, t_k, q_off,
                                  k_off, causal, window)

        def key_block(j, carry):
            keys = _rows(j, block_k)
            return _softmax_step(
                q_i, k_ref[keys, :], v_ref[keys, :], *carry,
                q_off + i * block_q, k_off + j * block_k, j * block_k, t_k,
                causal=causal, scale=scale, window=window)

        m, l, acc = lax.fori_loop(lo, hi, key_block, (
            jnp.full((block_q, LANES), NEG_INF, jnp.float32),
            jnp.zeros((block_q, LANES), jnp.float32),
            jnp.zeros((block_q, q_ref.shape[1]), jnp.float32)))
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[_rows(i, block_q), :] = (acc / safe_l[:, :1]).astype(o_ref.dtype)
        # the lane-replicated column [block_q, LANES] turned into a row
        l_ref[pl.ds(i, 1), :] = (m + jnp.log(safe_l)).T[:1]
        return 0

    lax.fori_loop(0, q_ref.shape[0] // block_q, query_block, 0)


def _walk_bwd_kernel(qoff_ref, koff_ref, q_ref, do_ref, k_ref, v_ref, l_ref,
                     dsum_ref, dq_ref, dk_ref, dv_ref, dk_sum, dv_sum, *,
                     causal, scale, window, block_q, block_k, t_k, group):
    """One query head backward, `_tiled_bwd`'s five products on TRANSPOSED
    tiles (keys down the sublanes, queries along the lanes), so that the
    statistics are rows ([1, block_q] of l_ref / dsum_ref), ``dV`` and
    ``dK`` are plain products and only ``dS`` is turned once a tile, for
    ``dQ``. dk_sum / dv_sum are the kv head's float32 sums in scratch: the
    steps of its query heads follow one another, the first clears them and
    the last writes them out."""
    n_k = k_ref.shape[0] // block_k
    q_off, k_off = qoff_ref[0], koff_ref[0]
    in_group = pl.program_id(1) % group

    @pl.when(in_group == 0)
    def _():
        dk_sum[...] = jnp.zeros(dk_sum.shape, dk_sum.dtype)
        dv_sum[...] = jnp.zeros(dv_sum.shape, dv_sum.dtype)

    def query_block(i, _):
        q_i, do_i = q_ref[_rows(i, block_q), :], do_ref[_rows(i, block_q), :]
        l_i, d_i = l_ref[pl.ds(i, 1), :], dsum_ref[pl.ds(i, 1), :]
        lo, hi = _key_block_range(i, block_q, block_k, n_k, t_k, q_off,
                                  k_off, causal, window)
        q_pos = q_off + i * block_q + lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 1)

        def key_block(j, dq_i):
            keys = _rows(j, block_k)
            k_j, v_j = k_ref[keys, :], v_ref[keys, :]
            s = lax.dot_general(k_j, q_i, _CONTRACT_LAST,
                                preferred_element_type=jnp.float32) * scale
            k_idx = j * block_k + lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            visible = k_idx < t_k
            if causal:
                visible &= q_pos >= k_off + k_idx
                if window is not None:
                    visible &= k_off + k_idx > q_pos - window
            p = jnp.exp(jnp.where(visible, s, NEG_INF) - l_i)
            dv_sum[keys, :] += jnp.dot(p.astype(do_i.dtype), do_i,
                                       preferred_element_type=jnp.float32)
            dp = lax.dot_general(v_j, do_i, _CONTRACT_LAST,
                                 preferred_element_type=jnp.float32)
            ds = (p * (dp - d_i)).astype(q_i.dtype)
            dk_sum[keys, :] += jnp.dot(
                ds, q_i, preferred_element_type=jnp.float32) * scale
            return dq_i + lax.dot_general(
                ds, k_j, _CONTRACT_FIRST,
                preferred_element_type=jnp.float32) * scale

        dq_i = lax.fori_loop(lo, hi, key_block, jnp.zeros(
            (block_q, q_ref.shape[1]), jnp.float32))
        dq_ref[_rows(i, block_q), :] = dq_i.astype(dq_ref.dtype)
        return 0

    lax.fori_loop(0, q_ref.shape[0] // block_q, query_block, 0)

    @pl.when(in_group == group - 1)
    def _():
        dk_ref[...] = dk_sum[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_sum[...].astype(dv_ref.dtype)


def _walk_call(kernel, name, offsets, q_like, kv_like, stats, out_kinds,
               interpret, block_q, **static):
    """One of the two kernels over the grid (batch, query head): ``q_like``
    arrays ([B, Hq, Tq, D]) are blocked a query head [Tq, D], ``kv_like``
    ([B, Hkv, Tk, D]) a kv head [Tk, D], ``stats`` a head's rows
    [Tq / block_q, block_q]; the outputs by kind
    (``"q"``: like q; ``"stats"``; ``"kv"``: like k, and each with a float32
    sum of its size in scratch). ``offsets`` (q's, k's) are prefetched
    scalars."""
    q = q_like[0]
    b, h_q, t_q, d = q.shape
    h_kv, t_k = kv_like[0].shape[1:3]
    group = h_q // h_kv
    vma = jax.typeof(q).vma  # under shard_map the outputs vary as q does
    # A head is read where it lies in [B, T, H * D], a column slab a head:
    # the caller's [B, T, H, D] -> [B, H, T, D] and this way back cancel in
    # XLA, and no transposed copy of q, k, v, o or of a gradient is made.
    # Compiled, a slab is whole lane tiles: `attention_tile` sees to that.

    def blocked(t, heads, index):
        return (pl.BlockSpec((None, t, d), lambda b, h, *_: (b, 0, index(h))),
                (b, t, heads * d))

    def lay(x):  # [B, H, T, D] as the kernel reads it
        return x.transpose(0, 2, 1, 3).reshape(x.shape[0], x.shape[2], -1)

    def unlay(x):
        return x.reshape(x.shape[0], x.shape[1], -1, d).transpose(0, 2, 1, 3)

    q_spec, q_shape = blocked(t_q, h_q, lambda h: h)
    kv_spec, kv_shape = blocked(t_k, h_kv, lambda h: h // group)
    stats_shape = (b, h_q, t_q // block_q, block_q)
    specs = {
        "q": (q_spec, jax.ShapeDtypeStruct(q_shape, q.dtype, vma=vma)),
        "stats": (pl.BlockSpec((None, None) + stats_shape[2:],
                               lambda b, h, *_: (b, h, 0, 0)),
                  jax.ShapeDtypeStruct(stats_shape, jnp.float32, vma=vma)),
        "kv": (kv_spec, jax.ShapeDtypeStruct(
            kv_shape, kv_like[0].dtype, vma=vma)),
    }
    out = pl.pallas_call(
        functools.partial(kernel, block_q=block_q, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h_q),
            in_specs=[specs["q"][0]] * len(q_like)
            + [specs["kv"][0]] * len(kv_like)
            + [specs["stats"][0]] * len(stats),
            out_specs=[specs[kind][0] for kind in out_kinds],
            scratch_shapes=[pltpu.VMEM((t_k, d), jnp.float32)
                            for kind in out_kinds if kind == "kv"],
        ),
        out_shape=[specs[kind][1] for kind in out_kinds],
        compiler_params=pltpu.CompilerParams(
            # a kv head's sums are added to by its query heads in turn
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=KERNEL_VMEM,
        ),
        interpret=interpret,
        name=name,
    )(*(jnp.reshape(x, (1,)) for x in offsets), *map(lay, q_like),
      *map(lay, kv_like), *stats)
    return [x if kind == "stats" else unlay(x)
            for kind, x in zip(out_kinds, out)]


def _kernel_forward(q, k, v, q_offset, k_offset, *, causal, scale, window,
                    block_q, block_k, interpret):
    """`_tiled_forward` as a kernel: ``o`` [B, Hq, Tq, D] and ``L``
    [B, Hq, Tq] f32."""
    b, h_q, t_q, _ = q.shape
    t_k = k.shape[2]
    block_q, block_k = _kernel_blocks(t_q, t_k, block_q, block_k, interpret)
    o, big_l = _walk_call(
        _walk_fwd_kernel, "attention_walk_fwd", (q_offset, k_offset),
        (_pad_rows(q, block_q),),
        (_pad_rows(k, block_k), _pad_rows(v, block_k)), (),
        ("q", "stats"), interpret, block_q, causal=causal, scale=scale,
        window=window, block_k=block_k, t_k=t_k)
    return o[:, :, :t_q], big_l.reshape(b, h_q, -1)[:, :, :t_q]


def _kernel_bwd(q, k, v, o, big_l, do, q_offset, k_offset, *, causal, scale,
                window, block_q, block_k, interpret):
    """`_tiled_bwd` as a kernel: the same five products a visible tile."""
    b, h_q, t_q, _ = q.shape
    t_k = k.shape[2]
    block_q, block_k = _kernel_blocks(t_q, t_k, block_q, block_k, interpret)
    d_term = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)

    def rows(x):  # [B, Hq, Tq] -> [B, Hq, n_q, block_q]
        return _pad_rows(x, block_q).reshape(b, h_q, -1, block_q)

    dq, dk, dv = _walk_call(
        _walk_bwd_kernel, "attention_walk_bwd", (q_offset, k_offset),
        (_pad_rows(q, block_q), _pad_rows(do, block_q)),
        (_pad_rows(k, block_k), _pad_rows(v, block_k)),
        (rows(big_l), rows(d_term)), ("q", "kv", "kv"), interpret, block_q,
        causal=causal, scale=scale, window=window, block_k=block_k, t_k=t_k,
        group=h_q // k.shape[1])
    return dq[:, :, :t_q], dk[:, :, :t_k], dv[:, :, :t_k]


@functools.lru_cache(maxsize=None)
def _kernel_vjp(causal, scale, window, block_q, block_k, interpret):
    """The walk inside the kernels, one callable per static config."""
    kw = dict(causal=causal, scale=scale, window=window, block_q=block_q,
              block_k=block_k, interpret=interpret)
    return _walk_vjp(functools.partial(_kernel_forward, **kw),
                     functools.partial(_kernel_bwd, **kw))


def _pad_and_flatten(q, k, v, block_q: int, block_k: int):
    """[B, H, T, D] -> [B*H, T_padded, D] with T padded to whole blocks."""
    b, h, t_q, d = q.shape
    t_k = k.shape[2]
    pad_q = (-t_q) % block_q
    pad_k = (-t_k) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        # padded key slots are masked INSIDE the kernel via the k_valid
        # scalar (offset arithmetic can otherwise place them inside the
        # causal horizon)
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    return (
        q.reshape(b * h, t_q + pad_q, d),
        k.reshape(b * h, t_k + pad_k, d),
        v.reshape(b * h, t_k + pad_k, d),
    )


def _flash_forward(
    q, k, v, q_offset, k_offset, causal, scale, block_q, block_k, interpret
) -> jax.Array:
    b, h, t_q, d = q.shape
    t_k = k.shape[2]
    block_q, block_k = _block_sizes(
        t_q, t_k, block_q, block_k, q.dtype, interpret
    )
    qh, kh, vh = _pad_and_flatten(q, k, v, block_q, block_k)
    tq_p, tk_p = qh.shape[1], kh.shape[1]

    qoff = jnp.asarray([q_offset], jnp.int32)
    koff = jnp.asarray([k_offset], jnp.int32)
    kvalid = jnp.asarray([t_k], jnp.int32)

    out = pl.pallas_call(
        functools.partial(_kernel, causal=causal, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b * h, tq_p // block_q, tk_p // block_k),
            in_specs=[
                pl.BlockSpec((None, block_q, d), lambda bh, i, j, *_: (bh, i, 0)),
                pl.BlockSpec((None, block_k, d), lambda bh, i, j, *_: (bh, j, 0)),
                pl.BlockSpec((None, block_k, d), lambda bh, i, j, *_: (bh, j, 0)),
            ],
            out_specs=pl.BlockSpec(
                (None, block_q, d), lambda bh, i, j, *_: (bh, i, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
        ),
        # under shard_map with VMA checking, pallas_call outputs must
        # declare which mesh axes they vary over — the output varies
        # exactly as q does (frozenset() outside shard_map)
        out_shape=jax.ShapeDtypeStruct(
            (b * h, tq_p, d), q.dtype, vma=jax.typeof(q).vma
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_fwd",
    )(qoff, koff, kvalid, qh, kh, vh)
    return out.reshape(b, h, tq_p, d)[:, :, :t_q]


def interpreter_twin(
    q: jax.Array,  # [B, H, Tq, D]
    k: jax.Array,
    v: jax.Array,
    q_offset: jax.Array | int = 0,
    k_offset: jax.Array | int = 0,
    causal: bool = False,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
) -> jax.Array:
    """Pure-jnp re-execution of the Pallas kernel's EXACT op sequence —
    the bit-exactness oracle for ``flash_attention(..., interpret=True)``.

    Each ``(batch*head, q block)`` of ``_flash_forward``'s grid is replayed
    as a Python loop, and its k-block axis as a ``fori_loop`` carrying what
    the kernel keeps in scratch — with the same padding and block sizes
    (``_block_sizes``) and the very same per-tile function
    (``_softmax_step``/``_finish``) the kernel body calls, so the
    comparison is ``==``, not allclose (tests/test_flash_attention.py pins
    it at seq 128 and 1024). CAVEAT: bit-exact against the INTERPRETED
    kernel (CPU, same XLA scalar ops); a real TPU run is validated by the
    allclose oracle instead — MXU accumulation order is hardware-defined
    and not reproducible op-for-op in jnp.
    """
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d**0.5)
    scale = float(scale)
    b, h, t_q, _ = q.shape
    t_k = k.shape[2]
    block_q, block_k = _block_sizes(
        t_q, t_k, block_q, block_k, q.dtype, interpret=True
    )
    qh, kh, vh = _pad_and_flatten(q, k, v, block_q, block_k)
    tq_p, tk_p = qh.shape[1], kh.shape[1]
    qoff = jnp.asarray(q_offset, jnp.int32)
    koff = jnp.asarray(k_offset, jnp.int32)

    def cell(q_blk, kfull, vfull, qi):
        def body(kb, carry):
            k_idx0 = kb * block_k
            return _softmax_step(
                q_blk,
                lax.dynamic_slice(kfull, (k_idx0, 0), (block_k, d)),
                lax.dynamic_slice(vfull, (k_idx0, 0), (block_k, d)),
                *carry,
                qoff + qi * block_q, koff + k_idx0, k_idx0, t_k,
                causal=causal, scale=scale,
            )

        _, l, acc = lax.fori_loop(0, tk_p // block_k, body, (
            jnp.full((block_q, LANES), NEG_INF, jnp.float32),
            jnp.zeros((block_q, LANES), jnp.float32),
            jnp.zeros((block_q, d), jnp.float32),
        ))
        return _finish(l, acc, q.dtype)

    out = jnp.zeros((b * h, tq_p, d), q.dtype)
    for bh in range(b * h):
        for qi in range(tq_p // block_q):
            rows = slice(qi * block_q, (qi + 1) * block_q)
            out = out.at[bh, rows, :].set(
                cell(qh[bh, rows, :], kh[bh], vh[bh], qi)
            )
    return out.reshape(b, h, tq_p, d)[:, :, :t_q]


def _attention_bwd(
    q, k, v, o, do, q_offset, k_offset, causal, scale, block_k: int = 128
):
    """Blockwise softmax-attention VJP with flash-style recompute.

    Nothing from the forward is saved except (q, k, v, o); scores and
    probabilities are recomputed BLOCKWISE over the key axis (lax.scan), so
    peak transient memory is O(Tq * block_k) — linear in sequence length,
    matching the forward kernel's scaling — never the dense [Tq, Tk]. Two
    passes, both f32 regardless of the compute dtype:

      pass 1: online-softmax statistics L = m + log(l)  (no V work)
      pass 2, per key block j, with D = rowsum(do * o):
        P_j = exp(S_j - L);  dV_j = P_j^T do;  dP_j = do V_j^T
        dS_j = P_j * (dP_j - D);  dQ += dS_j K_j * scale;
        dK_j = dS_j^T Q * scale.

    Fully-masked query rows (forward outputs zeros there) have l = 0, so
    every P_j entry underflows to 0 and their gradients vanish, matching
    the forward's zero output.
    """
    b, h, t_q, d = q.shape
    t_k = k.shape[2]
    block_k = min(block_k, t_k)
    pad_k = (-t_k) % block_k
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    n_blocks = (t_k + pad_k) // block_k
    qf, of, dof = (x.astype(jnp.float32) for x in (q, o, do))
    # [n_blocks, B, H, block_k, D] scan inputs
    kb = jnp.moveaxis(
        k.astype(jnp.float32).reshape(b, h, n_blocks, block_k, d), 2, 0
    )
    vb = jnp.moveaxis(
        v.astype(jnp.float32).reshape(b, h, n_blocks, block_k, d), 2, 0
    )
    base = jnp.arange(n_blocks) * block_k
    q_pos = jnp.reshape(q_offset, ()) + jnp.arange(t_q)
    k_off = jnp.reshape(k_offset, ())

    def block_scores(k_j, idx0):
        s = jnp.einsum(
            "bhqd,bhkd->bhqk", qf, k_j, preferred_element_type=jnp.float32
        ) * scale
        k_idx = idx0 + jnp.arange(block_k)
        valid = (k_idx < t_k)[None, :]
        if causal:
            valid = valid & (q_pos[:, None] >= (k_off + k_idx)[None, :])
        return jnp.where(valid[None, None], s, NEG_INF)

    def stat_step(carry, blk):
        m, l = carry
        s = block_scores(*blk)
        m_new = jnp.maximum(m, jnp.maximum(jnp.max(s, -1), -1e20))
        l = l * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(s - m_new[..., None]), -1
        )
        return (m_new, l), None

    m0 = jnp.full((b, h, t_q), NEG_INF, jnp.float32)
    (m, l), _ = lax.scan(stat_step, (m0, jnp.zeros_like(m0)), (kb, base))
    # L normalizer; l == 0 rows (fully masked) keep L = m so P stays 0
    big_l = m + jnp.log(jnp.where(l > 0, l, 1.0))
    d_term = jnp.sum(dof * of, axis=-1)  # [B, H, Tq]

    def bwd_step(dq_acc, blk):
        k_j, v_j, idx0 = blk
        p = jnp.exp(block_scores(k_j, idx0) - big_l[..., None])
        dv_j = jnp.einsum(
            "bhqk,bhqd->bhkd", p, dof, preferred_element_type=jnp.float32
        )
        dp = jnp.einsum(
            "bhqd,bhkd->bhqk", dof, v_j, preferred_element_type=jnp.float32
        )
        ds = p * (dp - d_term[..., None])
        dq_acc = dq_acc + jnp.einsum(
            "bhqk,bhkd->bhqd", ds, k_j, preferred_element_type=jnp.float32
        ) * scale
        dk_j = jnp.einsum(
            "bhqk,bhqd->bhkd", ds, qf, preferred_element_type=jnp.float32
        ) * scale
        return dq_acc, (dk_j, dv_j)

    dq, (dkb, dvb) = lax.scan(
        bwd_step, jnp.zeros((b, h, t_q, d), jnp.float32), (kb, vb, base)
    )
    dk = jnp.moveaxis(dkb, 0, 2).reshape(b, h, t_k + pad_k, d)[:, :, :t_k]
    dv = jnp.moveaxis(dvb, 0, 2).reshape(b, h, t_k + pad_k, d)[:, :, :t_k]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def reference(
    q: jax.Array, k: jax.Array, v: jax.Array,
    q_offset: int = 0, k_offset: int = 0, causal: bool = False,
    scale: float | None = None, window: int | None = None,
) -> jax.Array:
    """jnp oracle in the same [B, H, T, D] layout: dense masked softmax,
    the kv heads repeated where they are fewer than the query heads."""
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d**0.5)
    if k.shape[1] != q.shape[1]:
        k, v = (jnp.repeat(x, q.shape[1] // k.shape[1], axis=1)
                for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[2])
        k_pos = k_offset + jnp.arange(k.shape[2])
        seen = q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            seen = seen & (k_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(seen, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)

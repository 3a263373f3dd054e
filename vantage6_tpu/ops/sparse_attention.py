"""Learned sparse attention: a lightning indexer scores every (query, key)
pair, each query keeps the ``top_k`` keys it scores highest, and attention
runs over those alone (DeepSeek-V3.2's sparse stage, arXiv 2512.02556).

With the indexer's queries ``qI`` [T, H_I, D_I], its one key head ``kI``
[T, D_I] and its head weights ``w`` [T, H_I] (float32, made by the model from
the normed stream), the score of key ``s`` for query ``t`` is

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s] / sqrt(D_I)),   s <= t,

and the query keeps ``S_t``: the ``min(top_k, t + 1)`` keys ``s <= t`` with
the largest ``I[t, s]``, the lower ``s`` first on a tie (`lax.top_k`'s
rule). Three walks over blocks of queries, in XLA but for the indexer's
scores:

- `select`: the scores of a block of query rows against every key, in
  float32 at ``HIGHEST`` (the `indexer` scope; on a TPU the Pallas kernel
  `indexer_scores`, which keeps a tile's per-head products in VMEM and skips
  the key tiles no query of the block sees, where XLA fused the products
  into the weighted sum and ran them off the MXU: 2.3 s of a 7.3 s round of
  `keye.sparse16k-1chip` on a TPU v5e), and each row's selection (the `select` scope) by the
  k-th largest score: a search over the bits of the scores as
  order-preserving int32 keys, one count a bit, that stops once every row
  of the block counts exactly its k; ties at the threshold go to the lower
  keys by a running count. It returns the selection as ``keep``, a bit a
  pair (uint8 [T / rows, B, rows, T / 8]) in the blocks of rows it was made
  in, read a tile at a time by `tile_of`: 64 MB for two stations' 16k x 16k
  pairs, where a byte a pair would hold half a gigabyte in every copy the
  compiler lays out; the log of the sum of ``exp(I)`` over each ``S_t``
  (the indexer's own softmax, for its loss); and how many of the attention
  walk's visible tiles hold a kept key. Nothing of it is differentiated:
  top-k is piecewise constant. Under remat the layer's checkpoint keeps
  the three (`fed_transformer.remat_saved`: 64 MiB a layer at 2 x 16k
  tokens), so the backward pass runs neither the scores nor the search
  again: one selection a layer and round.
- `attend`: `recompute_attention`'s XLA walk over the visible tiles with
  ``keep`` masking each tile, forward and backward (its custom VJP); it
  returns the row statistics ``L`` beside the output.
- `indexer_loss`: ``sum_t KL(p_t || softmax_{s in S_t} I[t, s])`` with
  ``p_t`` the main attention's probabilities over ``S_t`` averaged over its
  heads (rebuilt a tile at a time from q, k and ``L``; its value takes the
  scores from `indexer_scores` on a TPU), a custom VJP whose
  gradient reaches ``qI``, ``kI`` and ``w`` alone: ``dI = softmax(I) - p``
  over ``S_t``. The LM loss reaches the indexer through nothing, and this
  loss reaches nothing else (the `indexer_loss` scope).

The walks visit every visible tile, whether or not it holds a kept key: under
the packed stations' `vmap` a skip would be a select that runs both branches.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vantage6_tpu.ops.flash_attention import (
    Blocks,
    _add_at,
    _key_block_range,
    _tiled_bwd,
    _tiled_forward,
    attention_tile,
)

HIGHEST = lax.Precision.HIGHEST
SELECT_ROWS = 128  # query rows whose scores against every key are live at once
# (query, key) pairs of one tile of the `indexer_scores` kernel: 128 rows
# by 2,048 keys, a float32 MiB
SCORE_TILE = 128 * 2048
SCORE_VMEM = 64 * 2**20  # what a step of it may hold of a v5e's 128 MiB
_LOWEST = jnp.iinfo(jnp.int32).min


def blocks(t: int, head_dim: int, group: int, dtype) -> Blocks:
    """The (query block, key block) of the walks at sequence length ``t``:
    those of `recompute_attention`'s XLA walk (`attention_tile`)."""
    return attention_tile(t, t, head_dim, group, dtype, True)


def indexer_scores(q_idx, k_idx, w):
    """``I`` [..., bq, bk] of queries ``q_idx`` [..., bq, H_I, D_I] and
    weights ``w`` [..., bq, H_I] against keys ``k_idx`` [..., bk, D_I],
    float32 at ``HIGHEST``; no mask."""
    a = jnp.einsum("...qjd,...sd->...qjs", q_idx, k_idx, precision=HIGHEST)
    a = a * (1.0 / math.sqrt(q_idx.shape[-1]))
    return jnp.einsum("...qjs,...qj->...qs", jax.nn.relu(a), w,
                      precision=HIGHEST)


def _scores_kernel(first_ref, q_ref, w_ref, k_ref, o_ref, *, inv):
    """One (row block, key tile) of the scores: ``q_ref`` [H_I, rows, D_I],
    ``w_ref`` [H_I, rows, 1], ``k_ref`` [keys, D_I], ``o_ref`` [rows, keys];
    a tile wholly after the block's last query is written 0 (no row of the
    block sees it)."""
    rows, keys = o_ref.shape
    seen = pl.program_id(1) * keys < first_ref[0] + rows

    @pl.when(seen)
    def _():
        acc = jnp.zeros(o_ref.shape, jnp.float32)
        for j in range(q_ref.shape[0]):
            a = lax.dot_general(q_ref[j], k_ref[...], (((1,), (1,)), ((), ())),
                                precision=HIGHEST,
                                preferred_element_type=jnp.float32)
            acc = acc + w_ref[j] * jnp.maximum(a * inv, 0.0)
        o_ref[...] = acc

    @pl.when(jnp.logical_not(seen))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)


def _score_keys(rows: int, t: int) -> int:
    """Keys of a tile of the kernel for blocks of ``rows`` queries."""
    return min(t, max(128, SCORE_TILE // rows))


def _block_scores(q_blk, k_idx, w_blk, first, interpret):
    """``I`` [B, rows, T] of a block of the indexer's queries ``q_blk`` [B,
    rows, H_I, D_I] (weights ``w_blk`` [B, rows, H_I]) whose first position
    is ``first``, against every key ``k_idx`` [B, T, D_I]. In the Pallas
    kernel (`_scores_kernel`) unless ``interpret``, where the same sums run
    in XLA (`indexer_scores`); the kernel leaves the keys after the block's
    last query 0, which no row of the block sees."""
    if interpret:
        return indexer_scores(q_blk, k_idx, w_blk)
    b, rows, h_i, d_i = q_blk.shape
    t = k_idx.shape[1]
    keys = _score_keys(rows, t)
    return pl.pallas_call(
        functools.partial(_scores_kernel, inv=1.0 / math.sqrt(d_i)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, t // keys),
            in_specs=[
                pl.BlockSpec((None, h_i, rows, d_i),
                             lambda b, j, first: (b, 0, 0, 0)),
                pl.BlockSpec((None, h_i, rows, 1),
                             lambda b, j, first: (b, 0, 0, 0)),
                pl.BlockSpec((None, keys, d_i), lambda b, j, first: (b, j, 0)),
            ],
            out_specs=pl.BlockSpec((None, rows, keys),
                                   lambda b, j, first: (b, 0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, rows, t), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=SCORE_VMEM),
        name="indexer_scores",
    )(jnp.reshape(first, (1,)).astype(jnp.int32),
      q_blk.transpose(0, 2, 1, 3), w_blk.transpose(0, 2, 1)[..., None], k_idx)


# ------------------------------------------------------------------ select
def _order_keys(scores, seen):
    """int32 keys whose order is the scores' (``-0.0`` counted as ``0.0``),
    and the lowest int32 where a key is not seen."""
    bits = lax.bitcast_convert_type(scores + 0.0, jnp.int32)
    keys = jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)
    return jnp.where(seen, keys, _LOWEST)


def _threshold(keys, k):
    """Per row of ``keys`` [..., T], a ``tau`` with at least ``k`` [...]
    keys ``>= tau`` and, where the keys allow it, exactly ``k``: the k-th
    largest key built bit by bit from the sign down, stopping once every row
    counts exactly ``k`` (with no tie at the threshold that happens long
    before the last bit)."""
    def count(tau):
        return jnp.sum(keys >= tau[..., None], -1, dtype=jnp.int32)

    zero = jnp.zeros(k.shape, jnp.int32)
    tau = jnp.where(count(zero) >= k, zero, _LOWEST)

    def unsettled(carry):
        b, _, n = carry
        return (b >= 0) & jnp.any(n != k)

    def one_bit(carry):
        b, tau, n = carry
        cand = tau | jnp.left_shift(jnp.int32(1), b)
        n_cand = count(cand)
        take = n_cand >= k
        return (b - 1, jnp.where(take, cand, tau),
                jnp.where(take, n_cand, n))

    _, tau, _ = lax.while_loop(unsettled, one_bit,
                               (jnp.int32(30), tau, count(tau)))
    return tau


def top_keys(scores, seen, k):
    """The kept pairs [..., T] bool: per row the ``k`` [...] largest
    ``scores`` among those ``seen``, the lower index first on a tie. ``k``
    is at most the row's count of seen keys."""
    keys = _order_keys(scores, seen)
    tau = _threshold(keys, k)[..., None]
    above = keys > tau
    tied = keys == tau
    need = k[..., None] - jnp.sum(above, -1, keepdims=True, dtype=jnp.int32)
    return above | (tied & (jnp.cumsum(tied, -1, dtype=jnp.int32) <= need))


def select(q_idx, k_idx, w, top_k: int, block_q: int, block_k: int,
           interpret: bool = False):
    """Each query's ``S_t`` from the indexer's ``q_idx`` [B, T, H_I, D_I],
    ``k_idx`` [B, T, D_I] and ``w`` [B, T, H_I] (float32, positions from 0).
    Returns ``keep`` [T / rows, B, rows, T / 8] uint8 (bit ``s % 8`` of
    byte ``s // 8`` set: key ``s`` kept), the
    ``log_norm`` [B, T] (the log of the sum of ``exp(I)`` over ``S_t``) and
    the count of (``block_q`` x ``block_k``) tiles of the attention walk
    that hold a kept pair, over the batch. The scores run in the Pallas
    kernel unless ``interpret`` (`_block_scores`). A layer under remat
    keeps the three across its checkpoint rather than select again in the
    backward pass (`fed_transformer.remat_saved`; ``keep`` is 64 MiB a
    layer at two sequences of 16k)."""
    q_idx, k_idx, w = map(lax.stop_gradient, (q_idx, k_idx, w))
    b, t = k_idx.shape[:2]
    block_q, block_k = min(block_q, t), min(block_k, t)
    rows = min(SELECT_ROWS, block_q)
    if (t % block_q or block_q % rows or t % block_k or block_k % 8
            or t % _score_keys(rows, t) or t % _score_keys(block_q, t)):
        raise ValueError(
            f"the sparse walk takes whole blocks: t {t}, blocks {block_q} x "
            f"{block_k}, {rows} rows a selection")
    key_pos = jnp.arange(t)

    def one_block(i):
        q_pos = i * rows + jnp.arange(rows)
        with jax.named_scope("indexer"):
            scores = _block_scores(
                lax.dynamic_slice_in_dim(q_idx, i * rows, rows, 1), k_idx,
                lax.dynamic_slice_in_dim(w, i * rows, rows, 1), i * rows,
                interpret)                                  # [B, rows, T]
        with jax.named_scope("select"):
            seen = key_pos[None, :] <= q_pos[:, None]
            k = jnp.broadcast_to(jnp.minimum(top_k, q_pos + 1), (b, rows))
            keep = top_keys(scores, seen[None], k)
            log_norm = jax.nn.logsumexp(
                jnp.where(keep, scores, -jnp.inf), axis=-1)
            tiles = jnp.any(keep.reshape(b, rows, t // block_k, block_k),
                            axis=(1, 3))                      # [B, n_k]
            return _pack(keep), log_norm, tiles

    keep, log_norm, tiles = lax.map(one_block, jnp.arange(t // rows))
    with jax.named_scope("select"):
        log_norm = jnp.moveaxis(log_norm, 0, 1).reshape(b, t)
        # a tile of the walk holds a kept pair if any of its row blocks does
        tiles = jnp.any(tiles.reshape(t // block_q, block_q // rows, b, -1),
                        axis=1)
        return keep, log_norm, jnp.sum(tiles, dtype=jnp.int32)


_BITS = jnp.arange(8, dtype=jnp.uint8)


def _pack(kept):
    """[..., T] bool -> [..., T / 8] uint8, key ``s`` at bit ``s % 8``."""
    bits = kept.reshape(kept.shape[:-1] + (-1, 8)).astype(jnp.uint8)
    return jnp.sum(bits << _BITS, axis=-1, dtype=jnp.uint8)


def tile_of(keep, block_q: int, block_k: int):
    """``tile(i, j)``: the [B, block_q, block_k] bool of the kept pairs of
    query block ``i`` and key block ``j``, read out of `select`'s ``keep``."""
    _, b, rows, _ = keep.shape
    per = block_q // rows

    def tile(i, j):
        blk = lax.dynamic_slice(keep, (i * per, 0, 0, j * (block_k // 8)),
                                (per, b, rows, block_k // 8))
        bits = (blk[..., None] >> _BITS) & 1
        return jnp.moveaxis(bits, 0, 1).reshape(b, block_q, block_k) != 0

    return tile


# ------------------------------------------------------------------ attend
@functools.lru_cache(maxsize=None)
def _attend_vjp(scale, block_q, block_k):
    kw = dict(causal=True, scale=scale, window=None, block_q=block_q,
              block_k=block_k)

    tile = functools.partial(tile_of, block_q=block_q, block_k=block_k)

    @jax.custom_vjp
    def attend(q, k, v, keep, q_off, k_off):
        return _tiled_forward(q, k, v, q_off, k_off, keep=tile(keep), **kw)

    def fwd(q, k, v, keep, q_off, k_off):
        o, big_l = attend(q, k, v, keep, q_off, k_off)
        return (o, big_l), (q, k, v, o, big_l, keep, q_off, k_off)

    def bwd(res, cotangents):
        q, k, v, o, big_l, keep, q_off, k_off = res
        dq, dk, dv = _tiled_bwd(q, k, v, o, big_l, cotangents[0], q_off,
                                k_off, keep=tile(keep), **kw)
        return dq, dk, dv, None, None, None

    attend.defvjp(fwd, bwd)
    return attend


def attend(q, k, v, keep, q_offset, k_offset, block_q: int, block_k: int):
    """Causal attention of ``q`` [B, Hq, T, D] over ``k``, ``v`` [B, Hkv,
    T, D] restricted to the pairs `select`'s ``keep`` holds: ``o`` [B, Hq, T,
    D] and the row statistics ``L`` [B, Hq, T] float32 (the log of each
    row's softmax sum; its cotangent is not followed)."""
    fn = _attend_vjp(1.0 / math.sqrt(q.shape[-1]), block_q, block_k)
    return fn(q, k, v, keep, jnp.asarray(q_offset, jnp.int32),
              jnp.asarray(k_offset, jnp.int32))


# ------------------------------------------------------------ indexer_loss
def _loss_walk(q, k, big_l, keep, log_norm, q_idx, k_idx, w, g, *, scale,
               block_q, block_k, interpret):
    """The walk of `indexer_loss`: with ``g`` None its value (the sum over
    queries of each KL; a query block's scores against every key from
    `_block_scores`, once), else the gradient of ``g`` times it to
    ``q_idx``, ``k_idx`` and ``w`` (each tile's per-head products again,
    which the gradient needs). A tile's ``p`` is ``exp(S_h - L_h)`` over the
    kept pairs, averaged over the query heads; its ``softmax(I)`` is ``exp(I
    - log_norm)``. Every block is sliced where it lies: nothing is laid out
    in blocks beforehand."""
    b, h_q, t, d = q.shape
    h_kv = k.shape[1]
    tile_kept = tile_of(keep, block_q, block_k)
    inv = 1.0 / math.sqrt(q_idx.shape[-1])

    def rows(x, i, axis):
        return lax.dynamic_slice_in_dim(x, i * block_q, block_q, axis)

    def query_block(i):
        return (rows(q, i, 2).reshape(b, h_kv, h_q // h_kv, block_q, d),
                rows(big_l, i, 2).reshape(b, h_kv, h_q // h_kv, block_q),
                rows(log_norm, i, 1), rows(q_idx, i, 1), rows(w, i, 1))

    def probabilities(blk, i, j):
        """The kept pairs of the tile and their head-averaged ``p``."""
        q_i, l_i = blk[:2]
        k_j = lax.dynamic_slice_in_dim(k, j * block_k, block_k, 2)
        kept = tile_kept(i, j)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", q_i, k_j,
                       preferred_element_type=jnp.float32) * scale
        p = jnp.mean(jnp.exp(s - l_i[..., None]), axis=(1, 2))
        return kept, jnp.where(kept, p, 0.0)                     # [B, bq, bk]

    def key_range(i):
        return _key_block_range(i, block_q, block_k, t // block_k, t, 0, 0,
                                True, None)

    if g is None:
        def one_query_block(i, total):
            blk = query_block(i)
            scores = _block_scores(blk[3], k_idx, blk[4], i * block_q,
                                   interpret) - blk[2][..., None]

            def step(j, total):
                kept, p = probabilities(blk, i, j)
                log_q = lax.dynamic_slice_in_dim(scores, j * block_k,
                                                 block_k, 2)
                kl = jnp.where(kept & (p > 0),
                               p * (jnp.log(jnp.where(p > 0, p, 1.0)) - log_q),
                               0.0)
                return total + jnp.sum(kl)

            return lax.fori_loop(*key_range(i), step, total)

        return lax.fori_loop(0, t // block_q, one_query_block,
                             jnp.float32(0))

    def one_query_block(i, grads):
        blk = query_block(i)
        qi_i, w_i = blk[3], blk[4]

        def step(j, carry):
            dq_i, dw_i, dk_idx = carry
            kept, p = probabilities(blk, i, j)
            ki_j = lax.dynamic_slice_in_dim(k_idx, j * block_k, block_k, 1)
            a = jnp.einsum("bqjd,bsd->bqjs", qi_i, ki_j,
                           precision=HIGHEST) * inv
            log_q = jnp.einsum("bqjs,bqj->bqs", jax.nn.relu(a), w_i,
                               precision=HIGHEST) - blk[2][..., None]
            d_scores = g * jnp.where(kept, jnp.exp(log_q) - p, 0.0)
            dw_i = dw_i + jnp.einsum("bqs,bqjs->bqj", d_scores,
                                     jax.nn.relu(a), precision=HIGHEST)
            da = jnp.where(a > 0, d_scores[:, :, None] * w_i[..., None],
                           0.0) * inv
            dq_i = dq_i + jnp.einsum("bqjs,bsd->bqjd", da, ki_j,
                                     precision=HIGHEST)
            dk_j = jnp.einsum("bqjs,bqjd->bsd", da, qi_i, precision=HIGHEST)
            return dq_i, dw_i, _add_at(1)(dk_idx, dk_j, j * block_k)

        dq_idx, dk_idx, dw = grads
        dq_i, dw_i, dk_idx = lax.fori_loop(*key_range(i), step, (
            jnp.zeros_like(qi_i), jnp.zeros_like(w_i), dk_idx))
        return (_add_at(1)(dq_idx, dq_i, i * block_q), dk_idx,
                _add_at(1)(dw, dw_i, i * block_q))

    return lax.fori_loop(0, t // block_q, one_query_block, (
        jnp.zeros_like(q_idx), jnp.zeros_like(k_idx), jnp.zeros_like(w)))


@functools.lru_cache(maxsize=None)
def _indexer_loss_vjp(scale, block_q, block_k, interpret):
    kw = dict(scale=scale, block_q=block_q, block_k=block_k,
              interpret=interpret)

    @jax.custom_vjp
    def loss(q, k, big_l, keep, log_norm, q_idx, k_idx, w):
        with jax.named_scope("indexer_loss"):
            return _loss_walk(q, k, big_l, keep, log_norm, q_idx, k_idx, w,
                              None, **kw)

    def fwd(*args):
        return loss(*args), args

    def bwd(args, g):
        with jax.named_scope("indexer_loss"):
            grads = _loss_walk(*args, g, **kw)
        return (None,) * 5 + grads

    loss.defvjp(fwd, bwd)
    return loss


def indexer_loss(q, k, big_l, keep, log_norm, q_idx, k_idx, w,
                 block_q: int, block_k: int, interpret: bool = False):
    """``sum_t KL(p_t || softmax_{s in S_t} I[t, s])`` over the batch's
    queries (positions from 0): ``p_t`` the attention's probabilities over
    ``S_t`` (``q`` [B, Hq, T, D], ``k`` [B, Hkv, T, D] and ``big_l`` from
    `attend`), averaged over the query heads; the indexer's scores from
    ``q_idx``, ``k_idx``, ``w`` as in `select`, which gave ``keep`` and
    ``log_norm``. Differentiable in ``q_idx``, ``k_idx`` and ``w`` alone.
    ``t`` is a whole number of blocks (`select` saw to that); the scores of
    the value run in the Pallas kernel unless ``interpret``."""
    fn = _indexer_loss_vjp(1.0 / math.sqrt(q.shape[-1]), block_q, block_k,
                           interpret)
    q, k, big_l, log_norm = map(lax.stop_gradient, (q, k, big_l, log_norm))
    return fn(q, k, big_l, keep, log_norm, q_idx, k_idx, w)

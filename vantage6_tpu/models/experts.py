"""A sparse expert layer that holds ONE CHIP'S SHARE of the experts.

Expert parallelism divides a layer's experts over chips: every chip routes
its tokens over ALL experts (the router keeps its published width and its
experts per token), and computes the part of the layer's result that ITS
experts give. This module is that part, on one chip, without the exchange:
``route`` chooses over all ``n_experts``; ``expert_layer`` is told which
experts it ``held`` and returns ``sum_e w_e * expert_e(h)`` over the chosen
experts ``e`` that are held here. What the absent experts would have added
is left out; nothing stands in for the other chips or their traffic. The
shares of all chips add up to the uncut layer
(tests/test_expert_layer.py).

No capacity and NO DROPPED TOKEN, under any skew: the assignments are
sorted by expert and every one of them is a row of a grouped matrix product
whose row buffer holds the worst case (every choice of every token held
here). The product itself is jax's Pallas TPU grouped matmul
(``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` forward, ``gmm`` and
``tgmm`` backward), whose grid is sized by the row tiles that carry an
assignment, so the empty tail of the buffer costs no product: measured
against ``jax.lax.ragged_dot``, which the TPU compiles natively but NOT
under ``vmap`` (``FedTransformer._round`` walks the stations packed on a
chip with one: "number of batch dimensions should be 0"), while a
``pallas_call`` with a dynamic grid is batched as a loop over the stations.
Off the TPU the kernels run interpreted (``interpret=True``).

Assumed, where SmallThinker's config does not say (stated in
perfbench/configs/smallthinker-21b-ep8-2st.json too): the router's product,
softmax and choice run in float32 at ``Precision.HIGHEST`` so that a bf16
rounding of the product rarely flips a choice; the expert is ReGLU,
``W_down (relu(W_gate h) * (W_up h))``.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as _gmm, tgmm as _tgmm

ROW_TILE = 256  # rows of one grouped-product tile (the m tile)
TOKEN_CHUNK = 2048  # tokens whose assignments share one row buffer


def route(
    x: jax.Array,  # [N, d] the block's input
    w_router: jax.Array,  # [d, n_experts]
    top_k: int,
) -> tuple[jax.Array, jax.Array]:
    """``softmax(x W_r)`` over ALL experts, the ``top_k`` largest, their
    probabilities renormalised to sum 1. float32 throughout, the product at
    ``HIGHEST``. Returns ``choice`` [N, top_k] int32 (expert ids, whether
    held here or not) and ``weights`` [N, top_k] float32 (differentiable
    through the softmax to ``x`` and ``w_router``)."""
    logits = jnp.dot(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )
    top_p, choice = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    weights = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return choice.astype(jnp.int32), weights


# ------------------------------------------------ the grouped matrix product
def _tile(dim: int) -> int:
    """A k or n tile for ``dim``: all of it up to 1024, else its largest
    divisor that is a multiple of 128 and at most 1280."""
    if dim <= 1024:
        return dim
    return max((t for t in range(128, 1281, 128) if dim % t == 0),
               default=1024)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def grouped_matmul(lhs, rhs, group_sizes, out_dtype, interpret):
    """``lhs[rows of group e] @ rhs[e]`` for every group: ``lhs`` [M, K]
    sorted by group, ``rhs`` [E, K, N], ``group_sizes`` [E] int32 with
    ``sum <= M``. Rows past the groups are NOT WRITTEN (they hold whatever
    the buffer held): the caller masks them."""
    m, k = lhs.shape
    n = rhs.shape[2]
    return _gmm(
        lhs, rhs, group_sizes, out_dtype,
        (min(ROW_TILE, m), _tile(k), _tile(n)), interpret=interpret)


def _grouped_matmul_fwd(lhs, rhs, group_sizes, out_dtype, interpret):
    out = grouped_matmul(lhs, rhs, group_sizes, out_dtype, interpret)
    return out, (lhs, rhs, group_sizes)


def _grouped_matmul_bwd(out_dtype, interpret, res, grad):
    lhs, rhs, group_sizes = res
    m, k = lhs.shape
    n = rhs.shape[2]
    tm = min(ROW_TILE, m)
    grad = grad.astype(lhs.dtype)
    d_lhs = _gmm(
        grad, rhs, group_sizes, lhs.dtype, (tm, _tile(n), _tile(k)),
        transpose_rhs=True, interpret=interpret)
    d_rhs = _tgmm(
        lhs.swapaxes(0, 1), grad, group_sizes, rhs.dtype,
        (tm, _tile(k), _tile(n)), num_actual_groups=rhs.shape[0],
        interpret=interpret)
    return d_lhs, d_rhs, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


# ----------------------------------------- rows in and out of sorted order
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_sorted(h, order, inverse, top_k):
    """Row ``r`` of the result is the token of assignment ``order[r]``
    (assignment ``a`` belongs to token ``a // top_k``). ``order`` is a
    permutation, so the cotangent comes back by a GATHER through its
    ``inverse`` and a sum over each token's ``top_k`` slots; no scatter
    (on the chip an unsorted scatter-add of such rows runs 8 times slower
    than the gather: my chip run, PR 30)."""
    return h[jnp.minimum(order // top_k, h.shape[0] - 1)]


def _rows_sorted_fwd(h, order, inverse, top_k):
    return _rows_sorted(h, order, inverse, top_k), (inverse, h.shape[0])


def _rows_sorted_bwd(top_k, res, g):
    inverse, n = res
    by_slot = g[inverse[: n * top_k]]
    return jnp.sum(by_slot.reshape(n, top_k, -1), axis=1), None, None


_rows_sorted.defvjp(_rows_sorted_fwd, _rows_sorted_bwd)


@jax.custom_vjp
def _rows_by_slot(y, inverse, order):
    """``y[inverse]``: sorted rows back in assignment order; the cotangent
    comes back through ``order``, the inverse of ``inverse``."""
    return y[inverse]


_rows_by_slot.defvjp(
    lambda y, inverse, order: (y[inverse], order),
    lambda order, g: (g[order], None, None),
)


def expert_layer(
    h: jax.Array,  # [N, d] the normed stream, in the compute dtype
    choice: jax.Array,  # [N, top_k] int32 expert ids over ALL experts
    weights: jax.Array,  # [N, top_k] float32
    params: dict[str, jax.Array],  # w_gate, w_up [E_held, d, f]; w_down [E_held, f, d]
    held: tuple[int, ...],  # the ids of the experts whose weights these are
    n_experts: int,
    interpret: bool = False,
    token_chunk: int = TOKEN_CHUNK,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """This chip's part of the expert layer: for every token the weighted
    sum over its chosen experts that are ``held``. Returns ``y`` [N, d] and
    the load: ``assignments`` [E_held] int32, the rows each held expert's
    product ran over, and ``routed_here`` (), the choices that named a held
    expert, counted apart from the sort; they agree unless a token was
    dropped.

    More than ``token_chunk`` tokens (a whole number of chunks) go through
    one chunk after another, each RECOMPUTED IN THE BACKWARD PASS, so that
    the worst-case row buffer that is live, and everything of its size, is
    one chunk's (``token_chunk * top_k`` rows) whatever the batch: the
    layer brings its own recomputation, and a caller that recomputes its
    layers leaves this one out of that."""
    n = h.shape[0]
    one = functools.partial(_expert_chunk, params=params, held=held,
                            n_experts=n_experts, interpret=interpret)
    if n <= token_chunk or n % token_chunk:
        return one(h, choice, weights)
    chunks = n // token_chunk
    y, load = lax.map(
        lambda c: jax.checkpoint(one)(*c),
        tuple(x.reshape(chunks, token_chunk, *x.shape[1:])
              for x in (h, choice, weights)))
    return y.reshape(n, -1), jax.tree.map(lambda x: jnp.sum(x, 0), load)


def _expert_chunk(h, choice, weights, *, params, held, n_experts, interpret):
    n, top_k = choice.shape
    n_held = len(held)
    to_local = np.full((n_experts,), n_held, np.int32)  # n_held: not here
    to_local[list(held)] = np.arange(n_held, dtype=np.int32)
    local = jnp.asarray(to_local)[choice]  # [N, top_k]
    here = local < n_held

    # every assignment is a row; the row buffer holds them all, padded to
    # whole tiles with assignments to no expert
    m = n * top_k
    tile = min(ROW_TILE, -(-m // 8) * 8)
    m_rows = -(-m // tile) * tile
    flat = jnp.pad(local.reshape(m), (0, m_rows - m), constant_values=n_held)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    sizes = jnp.bincount(flat, length=n_held + 1)[:n_held].astype(jnp.int32)
    live = (jnp.arange(m_rows) < jnp.sum(sizes))[:, None]

    def product(rows, w, out_dtype):
        return grouped_matmul(rows, w.astype(h.dtype), sizes, out_dtype,
                              interpret)

    rows = jnp.where(live, _rows_sorted(h, order, inverse, top_k), 0)
    gate = product(rows, params["w_gate"], jnp.float32)
    up = product(rows, params["w_up"], jnp.float32)
    mid = jnp.where(live, jax.nn.relu(gate) * up, 0).astype(h.dtype)
    out = jnp.where(live, product(mid, params["w_down"], h.dtype), 0)
    by_slot = _rows_by_slot(out, inverse, order)[:m].reshape(n, top_k, -1)
    w_here = jnp.where(here, weights, 0.0)  # float32: a weight is not rounded
    y = jnp.sum(w_here[..., None] * by_slot, axis=1).astype(h.dtype)
    load = {"assignments": sizes,
            "routed_here": jnp.sum(here).astype(jnp.int32)}
    return y, load


def load_summary(assignments: Any, routed_here: Any) -> dict[str, Any]:
    """What the ``experts.load`` span carries, from counts read off the
    device: ``assignments`` [..., E_held] (any leading axes: rounds, layers)
    and ``routed_here`` [...]."""
    a = np.asarray(assignments, np.int64)
    per_expert = a.reshape(-1, a.shape[-1]).sum(0)
    mean = per_expert.mean()
    return {
        "max_over_mean": float(per_expert.max() / mean) if mean else 0.0,
        "dropped": int(np.asarray(routed_here, np.int64).sum()
                       - per_expert.sum()),
    }

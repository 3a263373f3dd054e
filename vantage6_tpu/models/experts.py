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

No capacity and NO DROPPED TOKEN, under any skew: the assignments of a chunk
of tokens are sorted by expert and every one of them is a row of a grouped
matrix product whose row buffer holds the worst case (every choice of every
token held here). The worst case is the guarantee, not the cost: the
assignments to held experts are the first ``live`` rows of the sorted order,
and everything the layer does to rows (the gather of the tokens in, the
activation, the weighted sum back to the tokens, and each of them again as
a cotangent) walks the buffer in blocks of `row_walk`'s ``row_block`` rows
and stops behind the last block that carries an assignment: a loop whose
trip count is data. The product itself is jax's Pallas TPU grouped matmul
(``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` forward, ``gmm`` and
``tgmm`` backward), whose grid is sized by the row tiles that carry an
assignment: measured against ``jax.lax.ragged_dot``, which the TPU compiles
natively but NOT under ``vmap`` (``FedTransformer._round`` walks the stations
packed on a chip with one: "number of batch dimensions should be 0"), while
a ``pallas_call`` with a dynamic grid is batched as a loop over the
stations. So the empty tail of the buffer costs no product, no gather and no
pass; what it still costs is its allocation, one fill with zeros a buffer
and chunk (nothing reads the tail, but XLA hands out no memory unwritten),
the sort of all ``n * top_k`` assignments, and the weights' gradients, which
each chunk adds to in full whatever its load. Off the TPU the kernels run
interpreted (``interpret=True``).

The stations' ``vmap`` would undo the walk (jax batches a loop with a
per-station bound by running it to the largest and passing a ``select`` over
every carry each trip), so the layer is a ``custom_vjp`` whose forward and
backward are each batched as a loop over the stations (`_one_at_a_time`);
the backward is written out, and where the forward went chunk by chunk it
computes each chunk's products again.

Assumed, where SmallThinker's config does not say (stated in
perfbench/configs/smallthinker-21b-ep8-2st.json too): the router's product,
softmax and choice run in float32 at ``Precision.HIGHEST`` so that a bf16
rounding of the product rarely flips a choice; the expert is ReGLU,
``W_down (relu(W_gate h) * (W_up h))``. A caller that names another gate
(``activation="silu"``: SwiGLU, Keye-VL-2.0's) gets ``W_down (silu(W_gate
h) * (W_up h))`` through the same walk.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as _gmm, tgmm as _tgmm

ROW_TILE = 256  # rows of one grouped-product tile (the m tile)
TOKEN_CHUNK = 2048  # tokens whose assignments share one row buffer
GATES = {"relu": jax.nn.relu, "silu": jax.nn.silu}  # the experts' gates


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


def _product(lhs, rhs, group_sizes, out_dtype, interpret, transposed=False):
    """``lhs[rows of group e] @ rhs[e]`` for every group (``rhs[e].T`` where
    ``transposed``): ``lhs`` [M, K] sorted by group, ``rhs`` [E, K, N],
    ``group_sizes`` [E] int32 with ``sum <= M``. Rows past the groups are
    neither read nor WRITTEN (they hold whatever the buffer held): the
    caller masks them."""
    m, k = lhs.shape
    n = rhs.shape[1 if transposed else 2]
    return _gmm(
        lhs, rhs, group_sizes, out_dtype,
        (min(ROW_TILE, m), _tile(k), _tile(n)), transpose_rhs=transposed,
        interpret=interpret)


def _product_to_rhs(lhs, grad, group_sizes, acc, interpret):
    """``acc[e] + lhs[rows of group e].T @ grad[rows of group e]``: a
    product's cotangent to its weights, added to the sum ``acc`` [E, K, N]
    float32 inside the kernel."""
    k, n = acc.shape[1:]
    # a float32 tile of the sum, of the result and of the kernel's own
    # accumulator share the chip's fast memory: half an n tile
    tn = _tile(n) // 2 if _tile(n) % 256 == 0 else _tile(n)
    return _tgmm(
        lhs.swapaxes(0, 1), grad, group_sizes, acc.dtype,
        (min(ROW_TILE, lhs.shape[0]), _tile(k), tn),
        num_actual_groups=acc.shape[0], existing_out=acc, interpret=interpret)


# ------------------------------------------- one station at a time, batched
def _one_at_a_time(fn):
    """``fn`` whose `vmap` is a loop over the batch (`lax.map`), so that inside
    it nothing is batched: a count read off one station's routing is a
    scalar, a loop bounded by it runs that many times and a carry of a
    buffer's size is updated in place. The Pallas products are a loop over
    the stations already. `jax.custom_batching.custom_vmap` has no transpose
    rule, so this lies where reverse mode does not differentiate: inside a
    `custom_vjp`'s forward and backward (``ops/flash_attention.py::_add_at``
    is the precedent)."""
    one = jax.custom_batching.custom_vmap(fn)

    @one.def_vmap
    def _(axis_size, in_batched, *args):
        leaves, tree = jax.tree.flatten(args)
        batched = jax.tree.leaves(in_batched)

        def station(moving):
            moving = iter(moving)
            return one(*jax.tree.unflatten(tree, [
                next(moving) if b else x for x, b in zip(leaves, batched)]))

        out = lax.map(station, [x for x, b in zip(leaves, batched) if b])
        return out, jax.tree.map(lambda _: True, out)

    return one


# ------------------------------------------------ the walk over row blocks
def row_walk(n_tokens: int, top_k: int,
             token_chunk: int = TOKEN_CHUNK) -> tuple[int, int]:
    """``(row_block, row_blocks)`` for a call of `expert_layer` on
    ``n_tokens`` tokens: the rows of one block of the walk (a row tile of
    the products; all of a buffer smaller than one), and the blocks the call
    would walk if every choice of every token named a held expert. From the
    shapes alone."""
    chunks = 1
    if n_tokens > token_chunk and n_tokens % token_chunk == 0:
        chunks, n_tokens = n_tokens // token_chunk, token_chunk
    m = n_tokens * top_k
    block = min(ROW_TILE, -(-m // 8) * 8)
    return block, chunks * -(-m // block)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("order", "sizes"), meta_fields=("n", "top_k"))
@dataclasses.dataclass(frozen=True)
class _Walk:
    """A chunk's assignments in the order of their expert, and the walk over
    them in blocks: ``order`` [m_rows] (the assignments ``token * top_k +
    slot`` to held experts first, by expert; behind them those to no expert
    here and the padding to whole blocks), ``sizes`` [E_held] the rows of
    each held expert."""
    order: jax.Array
    sizes: jax.Array
    n: int
    top_k: int

    @classmethod
    def sort(cls, local: jax.Array, n_held: int) -> "_Walk":
        """``local`` [n, top_k]: each choice's place among the experts held
        here, ``n_held`` for one held elsewhere."""
        n, top_k = local.shape
        block, blocks = row_walk(n, top_k, n)
        flat = jnp.pad(local.reshape(-1), (0, block * blocks - n * top_k),
                       constant_values=n_held)
        return cls(jnp.argsort(flat, stable=True).astype(jnp.int32),
                   jnp.sum(flat[:, None] == jnp.arange(n_held), axis=0,
                           dtype=jnp.int32), n, top_k)

    @property
    def block(self) -> int:
        return row_walk(self.n, self.top_k, self.n)[0]

    @property
    def live(self) -> jax.Array:
        return jnp.sum(self.sizes)

    @property
    def walked(self) -> jax.Array:
        """The blocks that carry an assignment."""
        return (self.live + self.block - 1) // self.block

    def each(self, body, init):
        """``body(b, carry)`` over the blocks that carry an assignment. The
        rows of every other block of a buffer in ``init`` stay as they are
        and are read by nothing."""
        return lax.fori_loop(0, self.walked, body, init)

    def at(self, b):
        """Block ``b``: its first row, its rows' tokens, their slots in the
        chunk's [n * top_k] weights, and which of its rows carry an
        assignment (all but the last block's last)."""
        lo = b * self.block
        a = lax.dynamic_slice_in_dim(self.order, lo, self.block)
        carries = (lo + jnp.arange(self.block) < self.live)[:, None]
        return (lo, jnp.minimum(a // self.top_k, self.n - 1),
                jnp.minimum(a, self.n * self.top_k - 1), carries)

    def rows(self, x, lo):
        return lax.dynamic_slice_in_dim(x, lo, self.block)

    def put(self, x, rows, lo):
        return lax.dynamic_update_slice_in_dim(x, rows.astype(x.dtype), lo, 0)


def _products(h, walk, w, interpret, activation):
    """A chunk's forward up to the experts' results in sorted order:
    ``rows`` [m_rows, d] (the tokens of the assignments), ``gate`` and ``up``
    [m_rows, f] float32, ``mid`` and ``out`` in ``h``'s dtype. Only the
    blocks that carry an assignment hold anything."""
    m_rows = walk.order.shape[0]

    def rows_in(b, rows):
        lo, tokens, _, _ = walk.at(b)
        return walk.put(rows, h[tokens], lo)

    rows = walk.each(rows_in, jnp.zeros((m_rows, h.shape[1]), h.dtype))
    gate = _product(rows, w["w_gate"], walk.sizes, jnp.float32, interpret)
    up = _product(rows, w["w_up"], walk.sizes, jnp.float32, interpret)

    def act(b, mid):
        lo, _, _, carries = walk.at(b)
        return walk.put(mid, jnp.where(
            carries, GATES[activation](walk.rows(gate, lo))
            * walk.rows(up, lo), 0), lo)

    mid = walk.each(act, jnp.zeros((m_rows, gate.shape[1]), h.dtype))
    out = _product(mid, w["w_down"], walk.sizes, h.dtype, interpret)
    return rows, gate, up, mid, out


def _chunk_forward(h, walk, weights, w, interpret, activation):
    """``y`` [n, d] and the chunk's `_products`."""
    kept = _products(h, walk, w, interpret, activation)
    out = kept[-1]
    slots = weights.reshape(-1)  # float32: a weight is not rounded

    def rows_back(b, y):
        lo, tokens, slot, carries = walk.at(b)
        return y.at[tokens].add(jnp.where(
            carries, slots[slot][:, None] * walk.rows(out, lo), 0))

    y = walk.each(rows_back, jnp.zeros(h.shape, jnp.float32))
    return y.astype(h.dtype), kept


def _chunk_backward(h, walk, weights, w, kept, d_w, dy, interpret,
                    activation):
    """The chunk's cotangents from ``dy`` [n, d]: ``dh``, ``d_weights``, and
    the three matrices' added to ``d_w`` (float32). ``kept``: the chunk's
    `_products`, or None and they are computed again."""
    rows, gate, up, mid, out = kept or _products(
        h, walk, w, interpret, activation)
    slots = weights.reshape(-1)

    def to_lhs(grad, w):  # a product's cotangent to its rows
        return _product(grad, w, walk.sizes, h.dtype, interpret,
                        transposed=True)

    def rows_back_t(b, carry):
        d_out, d_slots = carry
        lo, tokens, slot, carries = walk.at(b)
        dy_rows = dy[tokens].astype(jnp.float32)
        d_slot = jnp.sum(dy_rows * walk.rows(out, lo), axis=-1)
        return (
            walk.put(d_out, jnp.where(
                carries, slots[slot][:, None] * dy_rows, 0), lo),
            d_slots.at[jnp.where(carries[:, 0], slot, slots.shape[0])].set(
                d_slot, mode="drop"))

    d_out, d_slots = walk.each(
        rows_back_t, (jnp.zeros_like(out), jnp.zeros_like(slots)))
    d_mid = to_lhs(d_out, w["w_down"])

    def act_t(b, carry):
        d_gate, d_up = carry
        lo, _, _, carries = walk.at(b)
        g = walk.rows(gate, lo)
        d = walk.rows(d_mid, lo).astype(jnp.float32)
        if activation == "relu":
            d_g = jnp.where(carries & (g > 0), d * walk.rows(up, lo), 0)
        else:  # silu'(g) = sigmoid(g) (1 + g (1 - sigmoid(g)))
            sig = jax.nn.sigmoid(g)
            d_g = jnp.where(
                carries, d * walk.rows(up, lo) * sig * (1 + g * (1 - sig)), 0)
        return (walk.put(d_gate, d_g, lo),
                walk.put(d_up, jnp.where(
                    carries, d * GATES[activation](g), 0), lo))

    d_gate, d_up = walk.each(
        act_t, (jnp.zeros_like(mid), jnp.zeros_like(mid)))
    d_rows_gate = to_lhs(d_gate, w["w_gate"])
    d_rows_up = to_lhs(d_up, w["w_up"])

    def rows_in_t(b, dh):
        lo, tokens, _, carries = walk.at(b)
        return dh.at[tokens].add(jnp.where(
            carries, walk.rows(d_rows_gate, lo).astype(jnp.float32)
            + walk.rows(d_rows_up, lo), 0))

    dh = walk.each(rows_in_t, jnp.zeros(h.shape, jnp.float32))
    d_w = {
        "w_gate": _product_to_rhs(
            rows, d_gate, walk.sizes, d_w["w_gate"], interpret),
        "w_up": _product_to_rhs(
            rows, d_up, walk.sizes, d_w["w_up"], interpret),
        "w_down": _product_to_rhs(
            mid, d_out, walk.sizes, d_w["w_down"], interpret)}
    return dh.astype(h.dtype), d_slots.reshape(weights.shape), d_w


def expert_layer(
    h: jax.Array,  # [N, d] the normed stream, in the compute dtype
    choice: jax.Array,  # [N, top_k] int32 expert ids over ALL experts
    weights: jax.Array,  # [N, top_k] float32
    params: dict[str, jax.Array],  # w_gate, w_up [E_held, d, f]; w_down [E_held, f, d]
    held: tuple[int, ...],  # the ids of the experts whose weights these are
    n_experts: int,
    interpret: bool = False,
    token_chunk: int = TOKEN_CHUNK,
    activation: str = "relu",
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """This chip's part of the expert layer: for every token the weighted
    sum over its chosen experts that are ``held``. Returns ``y`` [N, d] and
    the load: ``assignments`` [E_held] int32, the rows each held expert's
    product ran over, ``routed_here`` (), the choices that named a held
    expert, counted apart from the sort (they agree unless a token was
    dropped), and ``row_blocks_walked`` (), the blocks of `row_walk`'s
    ``row_block`` rows that carried an assignment and were walked (of its
    ``row_blocks``).

    More than ``token_chunk`` tokens (a whole number of chunks) go through
    one chunk after another, each RECOMPUTED IN THE BACKWARD PASS, so that
    the worst-case row buffer that is live, and everything of its size, is
    one chunk's (``token_chunk * top_k`` rows) whatever the batch: the
    layer brings its own recomputation, and a caller that recomputes its
    layers leaves this one out of that. ``activation``: the gate, one of
    `GATES`."""
    part = _held_part(tuple(held), n_experts, interpret, token_chunk,
                      activation)
    return part(h, choice, weights,
                {name: params[name] for name in ("w_gate", "w_up", "w_down")})


@functools.lru_cache(maxsize=None)
def _held_part(held, n_experts, interpret, token_chunk, activation):
    """`expert_layer` for one static configuration: a `custom_vjp` whose
    forward and backward each walk one station at a time (`_one_at_a_time`),
    one chunk after another, and within a chunk the row blocks that carry
    an assignment (`_Walk`)."""
    n_held = len(held)
    to_local = np.full((n_experts,), n_held, np.int32)  # n_held: not here
    to_local[list(held)] = np.arange(n_held, dtype=np.int32)

    def chunks(x):
        """[1, N, ...], or [N / token_chunk, token_chunk, ...] where the
        chunks are walked one after another (and recomputed)."""
        n = x.shape[0]
        several = n > token_chunk and n % token_chunk == 0
        return x.reshape(-1, token_chunk if several else n, *x.shape[1:])

    def cast(params, dtype):
        return {name: w.astype(dtype) for name, w in params.items()}

    @_one_at_a_time
    def forward(h, choice, weights, params):
        """``y``, the load, and for the backward pass each chunk's sorted
        order and, of one chunk alone, its products."""
        w = cast(params, h.dtype)
        local = jnp.asarray(to_local)[choice]

        def chunk(c):
            h, local, weights = c
            walk = _Walk.sort(local, n_held)
            y, kept = _chunk_forward(h, walk, weights, w, interpret,
                                     activation)
            return y, walk, kept

        if chunks(h).shape[0] == 1:
            y, walk, kept = chunk((h, local, weights))
            walks = jax.tree.map(lambda x: x[None], walk)
        else:  # a chunk's products are not kept
            y, walks = lax.map(lambda c: chunk(c)[:2], (
                chunks(h), chunks(local), chunks(weights)))
            kept = None
        load = {"assignments": jnp.sum(walks.sizes, axis=0),
                "routed_here": jnp.sum(local < n_held).astype(jnp.int32),
                "row_blocks_walked": jnp.sum(
                    jax.vmap(lambda walk: walk.walked)(walks)
                ).astype(jnp.int32)}
        return y.reshape(h.shape), load, walks, kept

    @_one_at_a_time
    def backward(h, weights, params, walks, kept, dy):
        w = cast(params, h.dtype)

        def chunk(d_w, c):
            h, weights, walk, dy = c
            dh, d_weights, d_w = _chunk_backward(
                h, walk, weights, w, kept, d_w, dy, interpret, activation)
            return d_w, (dh, d_weights)

        d_w, (dh, d_weights) = lax.scan(
            chunk, jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), w),
            (chunks(h), chunks(weights), walks, chunks(dy)))
        return (dh.reshape(h.shape), d_weights.reshape(weights.shape),
                jax.tree.map(lambda g, p: g.astype(p.dtype), d_w, params))

    @jax.custom_vjp
    def part(h, choice, weights, params):
        return forward(h, choice, weights, params)[:2]

    def part_fwd(h, choice, weights, params):
        y, load, walks, kept = forward(h, choice, weights, params)
        return (y, load), (h, weights, params, walks, kept)

    def part_bwd(res, cotangents):
        dh, d_weights, d_params = backward(*res, cotangents[0])
        return dh, None, d_weights, d_params

    part.defvjp(part_fwd, part_bwd)
    return part


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

"""Rows of a table by sorted indices, the table streamed through VMEM once.

On the v5e an XLA gather costs per index, about 10.5 ns each, whatever the
row's width (`docs/device_speed.md`, "One gather per local step"). Where a
batch names a large share of its table, streaming the whole table through
VMEM in blocks of rows and copying out the rows each block holds costs less:
the table moves at HBM's bandwidth, and inside VMEM a row found by index is
a vector load and a select, not a round trip to HBM.

`stream_gather(table, idx, block_rows)` is ``table[idx]`` for indices
sorted in ascending order, bit for bit. One grid step a block of
``block_rows`` table rows, in table order; the step copies the rows that
its range of the sorted indices names into the output, which stays in VMEM
across the steps and is written back once. Each output tile of 8 rows is
read, filled row by row (a row is loaded onto every sublane and selected
into the one the output wants it on) and stored whole. Under ``vmap`` (the
stations of `FedAvg`) the grid gains the mapped axis, as every
``pallas_call`` does.

On the chip (PERF.md section 6, PR 40; one local step of the engine cell,
32 stations x 32,768 of 262,144 rows of 128 words, with its sort): 9.01 ms
at blocks of 16,384 rows and 9.16 at 8,192, against 13.28 for XLA's gather;
a row loaded as its 8-row tile and rotated into place read 20.2, a row
stored alone 9.39, a stride-0 load 8.92 (which the interpreter does not
read as the chip does).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8  # a 32-bit tile is [8, 128]
VMEM_LIMIT = 100 * 2**20  # what the kernel may hold of a v5e's 128 MiB
SMEM_LIMIT = 512 * 2**10  # what its indices may hold of a v5e's 1 MiB of SMEM


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def padded_width(width: int) -> int:
    """The row width the kernel reads: whole lane tiles."""
    return _round_up(width, LANES)


def kernel_rows(n_rows: int, block_rows: int) -> int:
    """The rows of a block the kernel really uses for a table of
    ``n_rows``: ``block_rows``, or the whole table in whole tiles where it
    is shorter."""
    return min(block_rows, _round_up(n_rows, SUBLANES))


def fits(batch: int, width: int, block_rows: int) -> bool:
    """Whether a batch of ``batch`` rows of ``width`` 32-bit words fits the
    kernel's VMEM (two table blocks and two output buffers: the pipeline
    double-buffers both) and its indices the kernel's SMEM (twice)."""
    vmem = 4 * padded_width(width) * 2 * (
        block_rows + _round_up(batch, SUBLANES))
    return vmem <= VMEM_LIMIT and 2 * 4 * batch <= SMEM_LIMIT


def _copy_kernel(bounds_ref, idx_ref, table_ref, out_ref, *, block_rows):
    p = pl.program_id(0)
    lo, hi = bounds_ref[0, p], bounds_ref[0, p + 1]
    base = p * block_rows
    sublane = jax.lax.broadcasted_iota(
        jnp.int32, (SUBLANES, out_ref.shape[1]), 0)

    def tile(g, carry):
        start = pl.multiple_of(g * SUBLANES, SUBLANES)
        acc = out_ref[pl.ds(start, SUBLANES), :]
        for j in range(SUBLANES):
            i = start + j
            r = idx_ref[0, jnp.clip(i, lo, hi - 1)] - base
            # the row, on every sublane; the output keeps it on sublane j
            row = jnp.broadcast_to(table_ref[pl.ds(r, 1), :], acc.shape)
            keep = (sublane == j) & (i >= lo) & (i < hi)
            acc = jnp.where(keep, row, acc)
        out_ref[pl.ds(start, SUBLANES), :] = acc
        return carry

    @pl.when(hi > lo)
    def _():
        jax.lax.fori_loop(lo // SUBLANES, (hi + SUBLANES - 1) // SUBLANES,
                          tile, 0)


def stream_gather(table: jax.Array, idx: jax.Array, *, block_rows: int,
                  interpret: bool = False) -> jax.Array:
    """``table[idx]`` for ``table`` [n, w] of a 32-bit type and ``idx``
    [b] int32 sorted ascending, each in ``[0, n)``. Rows narrower than a
    lane tile are padded to one here (a caller that holds its table padded
    pays no copy)."""
    n, width = table.shape
    (batch,) = idx.shape
    w = padded_width(width)
    if w != width:
        table = jnp.pad(table, ((0, 0), (0, w - width)))
    rows = kernel_rows(n, block_rows)
    n_blocks = pl.cdiv(n, rows)
    idx = idx.astype(jnp.int32)
    bounds = jnp.searchsorted(
        idx, jnp.arange(n_blocks + 1, dtype=jnp.int32) * rows
    ).astype(jnp.int32)
    b_pad = _round_up(batch, SUBLANES)
    smem = pltpu.MemorySpace.SMEM
    # bounds and indices ride as [1, ...]: under vmap an SMEM block must be
    # whole in its last two dimensions
    out = pl.pallas_call(
        functools.partial(_copy_kernel, block_rows=rows),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec(memory_space=smem),
            pl.BlockSpec(memory_space=smem),
            pl.BlockSpec((rows, w), lambda p: (p, 0)),
        ],
        out_specs=pl.BlockSpec((b_pad, w), lambda p: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((b_pad, w), table.dtype),
        compiler_params=pltpu.CompilerParams(
            # the output is written by the blocks in turn
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
        name="stream_gather",
    )(bounds[None], idx[None], table)
    return out[:batch, :width]

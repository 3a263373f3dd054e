"""`ops.stream_gather`: rows of a table by sorted indices, the table streamed
through VMEM in blocks of rows. In interpret mode it is ``jnp.take`` of the
sorted indices, bit for bit: every width, a table that is not whole blocks,
repeated indices, all indices in one block, the table's first and last rows,
one station and a ``vmap`` over stations. CPU, tiny sizes; the compile for
the chip is in `tests/test_round_schedule.py`, beside the other compiles for
a described chip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vantage6_tpu.ops.stream_gather import stream_gather

# name: (table rows, row width, batch, block rows, indices, stations)
CASES = {
    "width-1": (200, 1, 64, 64, "random", 1),
    "width-101": (200, 101, 64, 64, "random", 1),
    "width-128": (200, 128, 64, 64, "random", 1),
    "width-130": (200, 130, 64, 64, "random", 1),
    "rows-not-whole-blocks": (203, 101, 61, 64, "random", 1),
    "duplicates": (200, 101, 64, 64, "duplicates", 1),
    "all-in-one-block": (256, 128, 40, 64, "one-block", 1),
    "first-and-last-row": (203, 101, 16, 64, "ends", 1),
    "table-shorter-than-a-block": (37, 101, 50, 64, "random", 1),
    "stations-under-vmap": (203, 101, 61, 64, "random", 3),
}


def _indices(rng, kind, n, batch):
    if kind == "duplicates":
        idx = rng.choice(rng.integers(0, n, 5), batch)
    elif kind == "one-block":
        idx = rng.integers(64, 128, batch)
    elif kind == "ends":
        idx = np.concatenate([[0, 0, n - 1, n - 1],
                              rng.integers(0, n, batch - 4)])
    else:
        idx = rng.integers(0, n, batch)
    return np.sort(idx).astype(np.int32)


@pytest.mark.parametrize("case", CASES)
def test_the_kernel_is_take_of_the_sorted_indices_bit_for_bit(case):
    n, width, batch, block_rows, kind, stations = CASES[case]
    rng = np.random.default_rng(40)
    table = rng.integers(0, 2**32, (stations, n, width), dtype=np.uint32)
    idx = np.stack([_indices(rng, kind, n, batch) for _ in range(stations)])

    def fetch(t, i):
        return stream_gather(t, i, block_rows=block_rows, interpret=True)

    if stations == 1:
        got = fetch(jnp.asarray(table[0]), jnp.asarray(idx[0]))[None]
    else:
        got = jax.vmap(fetch)(jnp.asarray(table), jnp.asarray(idx))
    want = np.stack([t[i] for t, i in zip(table, idx)])
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), want)

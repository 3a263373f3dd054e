"""The comparison that decides `correct` for a cell that trains.

The program's first steps (taken through the window's own call, on the
window's own state) are held against the plain reference's on these
numbers, each with a limit of its own from `limits/<cell>.json`:

- ``loss_1`` .. ``loss_n``: each step's loss, |program - reference| over the
  reference's;
- ``grad_norm``: the norm of every leaf of the first gradient as the
  optimizer got it, the worst leaf; ``grad_norm_median``, the median leaf,
  where there is more than one: one large leaf of a sound program can read
  several times the others (GPT-2's embedding, PERF.md section 2), so the
  worst leaf catches the faults and the median leaf the lower precision;
- ``change_norm``: the norm of every leaf of the parameters' change after the
  last step followed, the worst leaf.

A leaf's number is the gap between the program's norm and the reference's
(not the norm of their difference), over the reference's norm of that leaf or
of the median leaf, whichever is larger: some gradients are all but zero.
Leaves whose reference gradient is under a thousandth of the median leaf's
move under Adam by round-off alone and are left out of ``change_norm``, by
that rule and not by name.
"""
from __future__ import annotations

import math
import statistics
from typing import Any

QUIET_LEAF = 1e-3  # of the median leaf's gradient norm


def leaf_name(path: Any) -> str:
    """``(DictKey('layers'), SequenceKey(3), DictKey('qkv'))`` -> ``layers.3.qkv``."""
    parts = []
    for k in path:
        for attr in ("key", "idx", "name"):
            if hasattr(k, attr):
                parts.append(str(getattr(k, attr)))
                break
        else:
            parts.append(str(k))
    return ".".join(parts)


def leaf_norms(tree: Any, scale: float = 1.0) -> dict[str, float]:
    """L2 norm of every leaf of a pytree of arrays, one jitted call."""
    import jax
    import jax.numpy as jnp

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    norms = jax.jit(lambda xs: [
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in xs
    ])([x for _, x in flat])
    return {leaf_name(p): float(n) * scale
            for (p, _), n in zip(flat, jax.device_get(norms))}


def leaf_gaps(
    got: dict[str, float], want: dict[str, float],
    leave_out: set[str] | frozenset[str] = frozenset(),
) -> dict[str, float]:
    """Every leaf's gap of norms: |program's - reference's| over the
    reference's norm of that leaf or of the median leaf, whichever is
    larger."""
    if set(got) != set(want):
        raise ValueError(
            "program and reference name different leaves: "
            f"{sorted(set(got) ^ set(want))[:6]}"
        )
    floor = statistics.median(want.values())
    return {name: abs(got[name] - ref) / max(ref, floor)
            for name, ref in want.items() if name not in leave_out}


def worst(gaps: dict[str, float]) -> float:
    out = 0.0
    for gap in gaps.values():
        if not gap <= out:  # a NaN is the worst there is
            out = gap
    return out


def quiet_leaves(grad_norms: dict[str, float]) -> set[str]:
    floor = QUIET_LEAF * statistics.median(grad_norms.values())
    return {name for name, n in grad_norms.items() if n < floor}


def numbers(observed: dict[str, Any], reference: dict[str, Any]) -> dict[str, float]:
    """Every number compared, by its short name."""
    out: dict[str, float] = {}
    n = min(len(observed["losses"]), len(reference["losses"]))
    for i in range(n):
        ref = reference["losses"][i]
        out[f"loss_{i + 1}"] = abs(observed["losses"][i] - ref) / abs(ref)
    gaps = leaf_gaps(observed["grad_norms"], reference["grad_norms"])
    out["grad_norm"] = worst(gaps)
    if len(gaps) > 1:  # the median of one leaf is that leaf
        out["grad_norm_median"] = statistics.median(gaps.values())
    out["change_norm"] = worst(leaf_gaps(
        observed["change_norms"], reference["change_norms"],
        leave_out=quiet_leaves(reference["grad_norms"])))
    return out


def judge(
    found: dict[str, float], limits: dict[str, float | None]
) -> tuple[bool, dict[str, dict[str, float | None]]]:
    """`correct`, and each number beside its limit. A number whose limit is
    null is printed and not compared; a limit with no number fails."""
    rows: dict[str, dict[str, float | None]] = {}
    correct = True
    for name in sorted(set(found) | set(limits)):
        value, limit = found.get(name), limits.get(name)
        rows[name] = {"value": value, "limit": limit}
        if limit is None:
            continue
        if value is None or not value <= limit:
            correct = False
    return correct, rows

"""What a reference does around each matrix product.

``float32`` leaves everything alone (the plain reference, with the product
itself at ``precision=HIGHEST``). The others are the controls that `correct`
has to fail: the same mathematics as a path in the next precision down would
compute it, the step that would tempt a later PR. Such a path rounds the two
operands of a product on the way forward and the cotangent that arrives at
the product's result on the way back, so that both products of the backward
pass take rounded operands too:

    y = after(matmul(before(a), before(w)))

``before = rounder(p)`` rounds forward and is the identity backward (the
backward products then see the rounded ``a`` and ``w``, which the forward
product kept); ``after = cotangent_rounder(p)`` is the identity forward and
rounds the cotangent backward.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def _scaled(dtype) -> Callable:
    """Round to an 8-bit float with one scale per tensor, as an fp8 product
    is fed."""
    top = float(jnp.finfo(dtype).max)

    def q(x):
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
        return (x / scale).astype(dtype).astype(jnp.float32) * scale

    return q


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


# forward operands, backward cotangents: fp8 training keeps e4m3 for the one
# and e5m2, with its wider range, for the other
_ROUNDINGS = {
    "bfloat16": (_bf16, _bf16),
    "float8": (_scaled(jnp.float8_e4m3fn), _scaled(jnp.float8_e5m2)),
}


def _roundings(precision: str):
    if precision not in _ROUNDINGS:
        raise ValueError(f"no such precision: {precision!r}")
    return _ROUNDINGS[precision]


def rounder(precision: str) -> Callable:
    """Rounds an operand on the way forward; the identity on the way back."""
    if precision == "float32":
        return lambda x: x
    forward, _ = _roundings(precision)

    @jax.custom_vjp
    def before(x):
        return forward(x)

    before.defvjp(lambda x: (forward(x), None), lambda _, g: (g,))
    return before


def cotangent_rounder(precision: str) -> Callable:
    """The identity on the way forward; rounds the cotangent on the way
    back."""
    if precision == "float32":
        return lambda x: x
    _, backward = _roundings(precision)

    @jax.custom_vjp
    def after(x):
        return x

    after.defvjp(lambda x: (x, None), lambda _, g: (backward(g),))
    return after

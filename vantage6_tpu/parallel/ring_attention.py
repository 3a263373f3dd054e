"""Ring attention: exact attention over sequence shards with ICI neighbor
exchange.

Long-context support is first-class in this framework even though the
reference has no sequence models (SURVEY.md §5 "long-context: absent"):
cross-silo NLP (clinical notes, pathology reports) needs context lengths no
single chip can hold. The sequence is sharded over a mesh axis; each step of
a P-hop ring rotates the K/V shard to the next neighbor via
``lax.ppermute`` (pure ICI traffic, overlappable with compute) while queries
stay put, and softmax is accumulated ONLINE (streaming log-sum-exp), so the
result is exact attention — bit-comparable to the monolithic computation —
with O(T/P) memory per device.

References (public technique literature): Liu et al., "Ring Attention with
Blockwise Transformers for Near-Infinite Context" (2023); Milakov & Gimelshein
online softmax (2018). Implementation is original, written for jax shard_map.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from jax.sharding import Mesh, PartitionSpec as P


NEG_INF = -1e30


def _block_attention(
    q: jax.Array,  # [B, Tq, H, D]
    k: jax.Array,  # [B, Tk, H, D]
    v: jax.Array,  # [B, Tk, H, D]
    m: jax.Array,  # [B, H, Tq]     running max
    l: jax.Array,  # [B, H, Tq]     running denominator
    o: jax.Array,  # [B, Tq, H, D]  running numerator
    mask: jax.Array | None,  # [Tq, Tk] additive (0 / NEG_INF)
    scale: float,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One block's contribution folded into the online-softmax accumulators.

    Accumulators (m, l, o) are float32 regardless of the q/k/v dtype: on
    bf16 inputs the two einsums run at the MXU's bf16 rate but accumulate in
    f32 (``preferred_element_type``), and the softmax statistics stay f32 —
    the standard mixed-precision attention recipe.
    """
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if mask is not None:
        scores = scores + mask[None, None, :, :]
    block_max = jnp.max(scores, axis=-1)  # [B, H, Tq]
    # finite floor: a fully-masked block must contribute exp(-huge) = 0,
    # not exp(NEG_INF - NEG_INF) = 1 (the self block arrives first under the
    # current hop order, but correctness must not depend on ordering)
    m_new = jnp.maximum(jnp.maximum(m, block_max), -1e20)
    # correction for previously accumulated terms
    corr = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])  # [B, H, Tq, Tk] f32
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    o_new = o * corr.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = False,
    scale: float | None = None,
) -> jax.Array:
    """Exact attention over a sequence sharded on ``axis_name``.

    Call INSIDE shard_map/jit with ``q, k, v: [B, T_local, H, D]`` (this
    shard's tokens, contiguous block layout: shard i holds global positions
    ``[i*T_local, (i+1)*T_local)``). Returns this shard's ``[B, T_local, H,
    D]`` attention output. P-1 ppermute hops rotate K/V around the ring.
    """
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    if scale is None:
        scale = 1.0 / (d**0.5)

    q_pos = my_idx * t_local + jnp.arange(t_local)  # global query positions

    def step(carry, hop):
        k_cur, v_cur, m, l, o = carry
        src_idx = (my_idx - hop) % axis_size  # whose block we now hold
        if causal:
            k_pos = src_idx * t_local + jnp.arange(t_local)
            mask = jnp.where(q_pos[:, None] >= k_pos[None, :], 0.0, NEG_INF)
        else:
            mask = None
        m, l, o = _block_attention(q, k_cur, v_cur, m, l, o, mask, scale)
        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, m, l, o), None

    # accumulators derive from q so their varying-axis type matches the
    # scan outputs (a plain constant would be 'unvarying' under shard_map's
    # VMA tracking and fail the scan carry type check); f32 regardless of
    # input dtype (see _block_attention)
    qv = q[..., 0].transpose(0, 2, 1).astype(jnp.float32)  # [B, H, Tq]
    m0 = qv * 0 + NEG_INF
    l0 = qv * 0
    o0 = (q * 0).astype(jnp.float32)
    (k_f, v_f, m, l, o), _ = lax.scan(
        step, (k, v, m0, l0, o0), jnp.arange(axis_size)
    )
    del k_f, v_f
    # normalize; fully-masked rows (can't happen for causal contiguous
    # layouts, but guard anyway) yield zeros not NaN
    denom = jnp.where(l > 0, l, 1.0).transpose(0, 2, 1)[..., None]
    return (o / denom).astype(q.dtype)


def reference_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = False,
    scale: float | None = None,
) -> jax.Array:
    """Monolithic attention ([B, T, H, D]) — the correctness oracle."""
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d**0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        t = q.shape[1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def ring_attention_sharded(
    mesh: Mesh,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = False,
) -> jax.Array:
    """Convenience wrapper: full ``[B, T, H, D]`` in, shard_map'd ring inside.

    For use from host-level code/tests; model code calls `ring_attention`
    directly inside its own shard_map.
    """
    spec = P(None, axis_name, None, None)
    fn = jax.shard_map(
        partial(ring_attention, axis_name=axis_name, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)

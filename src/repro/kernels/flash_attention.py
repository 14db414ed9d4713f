"""Pallas TPU kernel: blocked flash attention (forward) with causal and
sliding-window masking — the transformer serving/training compute hotspot.

Classic online-softmax tiling adapted to TPU VMEM: grid over (q blocks,
kv blocks) with the kv axis innermost; running max/denominator and the
output accumulator live in the revisited output blocks.  Causal and
sliding-window (SWA) masks are applied inside the tile; fully-masked kv
blocks are still visited but contribute zero (XLA grid pruning of the
upper triangle is a TPU-runtime optimization we skip in interpret mode).

Shapes: q [Sq, D], k/v [Skv, D] for ONE head — callers vmap over
(batch, head) (GQA mapping handled in ops.py).  D should be a multiple of
128 for MXU alignment; block sizes default to 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.mode import interpret_mode

__all__ = ["flash_attention_pallas"]

_NEG_INF = -1e30


def _kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_ref,
    l_ref,
    *,
    block_q: int,
    block_kv: int,
    causal: bool,
    window: int,
    kv_offset: int,
    scale: float,
    skv_real: int,
):
    qb, kb = pl.program_id(0), pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...].astype(jnp.float32) * scale  # [BQ, D]
    k = k_ref[...].astype(jnp.float32)  # [BK, D]
    v = v_ref[...].astype(jnp.float32)  # [BK, D]
    s = q @ k.T  # [BQ, BK]

    # absolute positions: queries live at kv_offset + qb*BQ + i
    q_pos = kv_offset + qb * block_q + jax.lax.iota(jnp.int32, block_q)[:, None]
    k_pos = kb * block_kv + jax.lax.iota(jnp.int32, block_kv)[None, :]
    mask = k_pos < skv_real  # exclude padded keys
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[...]  # [BQ, 1]
    l_prev = l_ref[...]  # [BQ, 1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)  # [BQ, BK]
    # renormalize previous accumulator
    alpha = jnp.exp(m_prev - m_new)  # [BQ, 1]
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    o_ref[...] = o_ref[...] * alpha + (p @ v).astype(o_ref.dtype)
    m_ref[...] = m_new
    l_ref[...] = l_new


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal",
        "window",
        "kv_offset",
        "block_q",
        "block_kv",
        "interpret",
    ),
)
def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    kv_offset: int = 0,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Single-head attention.  q: [Sq, D]; k, v: [Skv, D].

    ``kv_offset`` is the absolute position of q[0] within the kv sequence
    (decode: Sq=1, kv_offset=cache_len-1).  ``window>0`` = sliding window."""
    sq, d = q.shape
    skv = k.shape[0]
    scale = 1.0 / (d**0.5)
    q_pad = -(-sq // block_q) * block_q
    kv_pad = -(-skv // block_kv) * block_kv
    if q_pad != sq:
        q = jnp.pad(q, ((0, q_pad - sq), (0, 0)))
    if kv_pad != skv:
        k = jnp.pad(k, ((0, kv_pad - skv), (0, 0)))
        v = jnp.pad(v, ((0, kv_pad - skv), (0, 0)))
    grid = (q_pad // block_q, kv_pad // block_kv)
    out, m, l = pl.pallas_call(
        functools.partial(
            _kernel,
            block_q=block_q,
            block_kv=block_kv,
            causal=causal,
            window=window,
            kv_offset=kv_offset,
            scale=scale,
            skv_real=skv,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda qb, kb: (qb, 0)),
            pl.BlockSpec((block_kv, d), lambda qb, kb: (kb, 0)),
            pl.BlockSpec((block_kv, d), lambda qb, kb: (kb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, d), lambda qb, kb: (qb, 0)),
            pl.BlockSpec((block_q, 1), lambda qb, kb: (qb, 0)),
            pl.BlockSpec((block_q, 1), lambda qb, kb: (qb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q_pad, d), jnp.float32),
            jax.ShapeDtypeStruct((q_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((q_pad, 1), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v)
    out = out / jnp.maximum(l, 1e-30)
    return out[:sq].astype(q.dtype)

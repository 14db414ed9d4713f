"""Ragged Pallas kernels for the GNN hot path.

The bucketed inference engine and the training apply() path both reduce
to the same dispatches per layer: gather neighbor rows, segment-sum them
into destination rows, normalize/activate.  This module holds the
aggregation kernels the dispatchers in ``ops.py`` reach:

* :func:`segment_spmm_ragged_pallas` — segment-sum of pre-gathered
  messages on a 1-D grid over edge tiles.  Per-tile valid-edge counts are
  scalar-prefetched into SMEM, so a tile of bucket padding costs one
  predicate instead of a matmul.
* :func:`gat_softmax_aggregate_pallas` — one-pass GAT edge-softmax +
  weighted aggregate (segment-max, exp, normalize, weighted segment-sum in
  a single kernel), replacing the 3-pass ``_seg_softmax`` + ``_seg_sum``
  sequence in ``models/gnn/models.py``.  Uses the flash-attention online
  rescaling trick (running max / denominator in VMEM scratch, accumulator
  as the revisited output block) so segments can span edge tiles.
* :func:`segment_max_pallas` — standalone segment-max (no model calls it:
  with the kernel on, the softmax runs inside the kernel above).

Layout, as the TPU's kernel compiler (Mosaic) needs it: per-edge vectors
(segment ids, logits) are reshaped to ``(tiles, 1, block_edges)`` so each
tile reads one lane-dense ``[1, block_edges]`` row, and membership of edge
``e`` in segment ``r`` is the int32 comparison ``iota[r, e] == seg[0, e]``
over an ``[n, block_edges]`` tile — never a reshaped bool vector.  The
scatter is then ``onehot @ msg`` on the MXU, at HIGHEST precision for f32
messages so the sum is as exact as the jnp oracle's.  The full ``[n, d]``
output is a revisited block; its VMEM footprint bounds ``n`` (the engine's
vertex bucket), and ``compiler_params`` raise the scoped limit to fit it.

``seg == -1`` marks padding.  Every kernel has a same-named ``*_ref``
oracle in ``ref.py`` (glint rule KRN001 enforces this) and takes an
``interpret`` argument; ``None`` picks the mode from the platform
(:func:`repro.kernels.mode.interpret_mode`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import interpret_mode

__all__ = [
    "segment_spmm_ragged_pallas",
    "gat_softmax_aggregate_pallas",
    "segment_max_pallas",
]

# finite stand-in for -inf: exp(_NEG_INF - m) underflows to 0.0 and
# _NEG_INF - _NEG_INF == 0 (a true -inf would produce NaN there)
_NEG_INF = -1e30


def _pad_edges(arrs, m: int, block_edges: int, fills):
    """Pad every 1-D/2-D edge-indexed array up to a whole number of tiles
    (at least one, so the eb==0 init always runs even for m == 0)."""
    m_pad = -(-max(m, 1) // block_edges) * block_edges
    if m_pad == m:
        return arrs, m_pad
    out = []
    for a, fill in zip(arrs, fills):
        pad = ((0, m_pad - m),) + ((0, 0),) * (a.ndim - 1)
        out.append(jnp.pad(a, pad, constant_values=fill))
    return out, m_pad


def edge_rows(x: jax.Array, block_edges: int) -> jax.Array:
    """``[m_pad]`` per-edge vector -> ``(tiles, 1, block_edges)``."""
    return x.reshape(-1, 1, block_edges)


def member(seg_row: jax.Array, n: int, base=0) -> jax.Array:
    """``[n, BM]`` bool: segment ``base + r`` holds edge ``e`` iff
    ``seg_row[0, e] == base + r``; padding (-1) matches no row."""
    rows = base + jax.lax.broadcasted_iota(jnp.int32, (n, seg_row.shape[-1]), 0)
    return rows == seg_row


def onehot(mask: jax.Array, dtype) -> jax.Array:
    """A membership mask as exact 0/1 weights."""
    return jnp.where(mask, 1.0, 0.0).astype(dtype)


def scatter_sum(w: jax.Array, msg: jax.Array) -> jax.Array:
    """``w [n, BM] @ msg [BM, d]`` with f32 accumulation: sums each tile's
    (weighted) messages into their segments."""
    return jax.lax.dot_general(
        w,
        msg,
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST if msg.dtype == jnp.float32 else None,
        preferred_element_type=jnp.float32,
    )


# Scoped-VMEM budget of the resident-output kernels: three quarters of a
# v5e core's 128 MiB.  The default 16 MiB is too small once the revisited
# [n, d] output and the [n, block_edges] tile temporaries grow to the
# engine's 4096-row vertex bucket at d = 256.
VMEM_LIMIT = pltpu.CompilerParams(vmem_limit_bytes=96 << 20)


# -- ragged segment-SpMM (pre-gathered messages) -----------------------------


def _spmm_ragged_kernel(cnt_ref, seg_ref, msg_ref, out_ref):
    eb = pl.program_id(0)

    @pl.when(eb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(cnt_ref[eb] > 0)  # all-padding tiles cost one predicate
    def _compute():
        msg = msg_ref[...]
        mask = member(seg_ref[...], out_ref.shape[0])
        out_ref[...] += scatter_sum(onehot(mask, msg.dtype), msg)


@functools.partial(
    jax.jit, static_argnames=("num_segments", "block_edges", "interpret")
)
def segment_spmm_ragged_pallas(
    msg: jax.Array,
    seg: jax.Array,
    num_segments: int,
    *,
    block_edges: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """out[s] = sum over e with seg[e] == s of msg[e], on a 1-D edge grid:
    the full output is a revisited block and all-padding edge tiles are
    skipped via per-tile valid counts, so the engine's power-of-two bucket
    padding stops costing matmuls."""
    m, d = msg.shape
    n = num_segments
    (msg, seg), m_pad = _pad_edges(
        [msg, seg.astype(jnp.int32)], m, block_edges, [0, -1]
    )
    counts = jnp.sum((seg >= 0).reshape(-1, block_edges), axis=1).astype(jnp.int32)
    out = pl.pallas_call(
        _spmm_ragged_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m_pad // block_edges,),
            in_specs=[
                pl.BlockSpec((None, 1, block_edges), lambda eb, cnt: (eb, 0, 0)),
                pl.BlockSpec((block_edges, d), lambda eb, cnt: (eb, 0)),
            ],
            out_specs=pl.BlockSpec((n, d), lambda eb, cnt: (0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        compiler_params=VMEM_LIMIT,
        interpret=interpret_mode(interpret),
    )(counts, edge_rows(seg, block_edges), msg)
    return out.astype(msg.dtype)


# -- one-pass GAT edge-softmax + aggregate -----------------------------------


def _gat_kernel(seg_ref, logit_ref, msg_ref, out_ref, m_ref, z_ref):
    eb = pl.program_id(0)

    @pl.when(eb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        z_ref[...] = jnp.zeros_like(z_ref)

    mask = member(seg_ref[...], out_ref.shape[0])  # [n, BM]
    logit = logit_ref[...].astype(jnp.float32)  # [1, BM]
    tile_max = jnp.max(jnp.where(mask, logit, _NEG_INF), axis=1, keepdims=True)
    m_prev = m_ref[...]  # [n, 1]
    m_new = jnp.maximum(m_prev, tile_max)
    # online-softmax rescale of the running sums (exp(0)=1 while a segment
    # is still empty; exp(-huge) underflows to 0 once a real max arrives)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(jnp.where(mask, logit - m_new, _NEG_INF))  # [n, BM]
    z_ref[...] = alpha * z_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    out_ref[...] = alpha * out_ref[...] + scatter_sum(
        p, msg_ref[...].astype(jnp.float32)
    )
    m_ref[...] = m_new

    @pl.when(eb == pl.num_programs(0) - 1)
    def _normalize():
        out_ref[...] = out_ref[...] / jnp.maximum(z_ref[...], 1e-9)


@functools.partial(
    jax.jit, static_argnames=("num_segments", "block_edges", "interpret")
)
def gat_softmax_aggregate_pallas(
    logits: jax.Array,
    msg: jax.Array,
    seg: jax.Array,
    num_segments: int,
    *,
    block_edges: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """out[s] = sum_e softmax_{seg==s}(logits)[e] * msg[e] in ONE kernel.

    Replaces the 3-pass segment-max → exp/normalize → segment-sum sequence:
    the running max and denominator live in VMEM scratch, the accumulator
    in the revisited output block, rescaled flash-attention-style as edge
    tiles stream through.  Matches ``alpha = e / max(z, 1e-9)`` from
    ``_seg_softmax`` exactly, so empty segments return 0."""
    m = seg.shape[0]
    d = msg.shape[1]
    n = num_segments
    (seg, logits, msg), m_pad = _pad_edges(
        [seg.astype(jnp.int32), logits, msg], m, block_edges, [-1, 0, 0]
    )
    row = pl.BlockSpec((None, 1, block_edges), lambda eb: (eb, 0, 0))
    out = pl.pallas_call(
        _gat_kernel,
        grid=(m_pad // block_edges,),
        in_specs=[row, row, pl.BlockSpec((block_edges, d), lambda eb: (eb, 0))],
        out_specs=pl.BlockSpec((n, d), lambda eb: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((n, 1), jnp.float32),
        ],
        compiler_params=VMEM_LIMIT,
        interpret=interpret_mode(interpret),
    )(edge_rows(seg, block_edges), edge_rows(logits, block_edges), msg)
    return out.astype(msg.dtype)


# -- segment max -------------------------------------------------------------


def _segmax_kernel(seg_ref, x_ref, out_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, _NEG_INF)

    mask = member(seg_ref[...], out_ref.shape[0])  # [n, BM]
    x = x_ref[...].astype(jnp.float32)  # [1, BM]
    tile_max = jnp.max(jnp.where(mask, x, _NEG_INF), axis=1, keepdims=True)
    out_ref[...] = jnp.maximum(out_ref[...], tile_max)


@functools.partial(
    jax.jit, static_argnames=("num_segments", "block_edges", "interpret")
)
def segment_max_pallas(
    x: jax.Array,
    seg: jax.Array,
    num_segments: int,
    *,
    block_edges: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Per-segment max of x over seg (padding seg=-1 excluded); empty
    segments yield 0.0, matching ``_seg_softmax``'s finite-fix."""
    m = seg.shape[0]
    n = num_segments
    (seg, x), m_pad = _pad_edges(
        [seg.astype(jnp.int32), x], m, block_edges, [-1, 0]
    )
    row = pl.BlockSpec((None, 1, block_edges), lambda eb: (eb, 0, 0))
    out = pl.pallas_call(
        _segmax_kernel,
        grid=(m_pad // block_edges,),
        in_specs=[row, row],
        out_specs=pl.BlockSpec((n, 1), lambda eb: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        compiler_params=VMEM_LIMIT,
        interpret=interpret_mode(interpret),
    )(edge_rows(seg, block_edges), edge_rows(x, block_edges))
    mx = out[:, 0]
    return jnp.where(mx > _NEG_INF * 0.5, mx, 0.0).astype(x.dtype)

"""Pallas TPU kernel: segment-sum SpMM — the GNN aggregation hotspot.

GNN message passing aggregates E gathered neighbor-message rows into B
destination rows (``out[seg[e]] += msg[e]``).  On GPU this is a scatter-add
(cuSPARSE / atomics); scatters are hostile to the TPU's systolic MXU, so we
adapt the paper's aggregation hotspot TPU-natively (DESIGN.md §3):

    the scatter becomes a block-tiled ONE-HOT MATMUL.  For an edge tile of
    BM messages and a row tile of BN segments, ``onehot[bn, bm] =
    (row_ids[bn] == seg[bm])`` and ``out_tile += onehot @ msg_tile`` —
    a (BN × BM) · (BM × D) MXU contraction entirely in VMEM.

Grid is (row_blocks, edge_blocks) with the edge axis innermost; the output
tile is accumulated across the inner axis (revisited output block), written
once zeroed at the first edge block.  ``seg`` must be sorted ascending for
efficiency claims but correctness holds for any order.  Padding rows use
``seg = -1`` (matches no row).  Segment ids are read in the 2-D
``(tiles, 1, BM)`` layout of ``fused_gnn.edge_rows``.

VMEM budget per step: BM·D (msg) + BN·D (out) + BN·BM (onehot) floats —
default BM=BN=128, D tiles of 128..512 keep it well under 16 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_gnn import edge_rows, member, onehot, scatter_sum
from repro.kernels.mode import interpret_mode

__all__ = ["segment_spmm_pallas"]


def _kernel(seg_ref, msg_ref, out_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    block_rows = out_ref.shape[0]
    msg = msg_ref[...]
    mask = member(seg_ref[...], block_rows, base=pl.program_id(0) * block_rows)
    out_ref[...] += scatter_sum(onehot(mask, msg.dtype), msg)


@functools.partial(
    jax.jit, static_argnames=("num_segments", "block_rows", "block_edges", "interpret")
)
def segment_spmm_pallas(
    msg: jax.Array,
    seg: jax.Array,
    num_segments: int,
    *,
    block_rows: int = 128,
    block_edges: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """out[s] = sum over e with seg[e] == s of msg[e].

    msg: [M, D] (M padded to block_edges), seg: [M] int32 (-1 padding).
    num_segments is padded up to block_rows internally; callers slice."""
    m, d = msg.shape
    if seg.shape != (m,):
        raise ValueError(f"seg shape {seg.shape} does not match msg rows {m}")
    m_pad = -(-max(m, 1) // block_edges) * block_edges
    n_pad = -(-num_segments // block_rows) * block_rows
    seg = seg.astype(jnp.int32)
    if m_pad != m:
        msg = jnp.pad(msg, ((0, m_pad - m), (0, 0)))
        seg = jnp.pad(seg, (0, m_pad - m), constant_values=-1)
    grid = (n_pad // block_rows, m_pad // block_edges)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, 1, block_edges), lambda rb, eb: (eb, 0, 0)),
            pl.BlockSpec((block_edges, d), lambda rb, eb: (eb, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda rb, eb: (rb, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, d), jnp.float32),
        interpret=interpret_mode(interpret),
    )(edge_rows(seg, block_edges), msg)
    return out[:num_segments].astype(msg.dtype)

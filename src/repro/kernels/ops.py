"""Jit'd public wrappers around the Pallas kernels.

Each wrapper leaves ``interpret`` unset, so the kernel picks its mode from
the platform when it is traced (:func:`repro.kernels.mode.interpret_mode`):
Mosaic-compiled on a TPU, where a kernel that does not compile raises, and
Pallas interpret mode everywhere else.  Every wrapper has a matching
pure-jnp oracle in ref.py, and tests assert allclose across shape/dtype
sweeps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.autotune import DEFAULT_CONFIG, get_tuned
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.fused_gnn import (
    gat_softmax_aggregate_pallas,
    segment_max_pallas,
    segment_spmm_ragged_pallas,
)
from repro.kernels.ref import (
    attention_ref,
    gat_softmax_aggregate_ref,
    gather_spmm_ref,
    segment_max_ref,
    segment_spmm_ref,
    ssd_scan_ref,
)
from repro.kernels.segment_spmm import segment_spmm_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas

__all__ = [
    "gnn_aggregate",
    "gnn_gather_aggregate",
    "gnn_gat_aggregate",
    "gnn_segment_max",
    "mha_attention",
    "ssd_scan",
    "segment_spmm_pallas",
    "segment_spmm_ragged_pallas",
    "gat_softmax_aggregate_pallas",
    "segment_max_pallas",
    "flash_attention_pallas",
    "ssd_scan_pallas",
    "attention_ref",
    "segment_spmm_ref",
    "gather_spmm_ref",
    "gat_softmax_aggregate_ref",
    "segment_max_ref",
    "ssd_scan_ref",
]


def _blocks(op, shape, dtype, block_rows, block_edges):
    """Resolve block sizes: explicit caller args win, then the autotuner's
    table for this (op, shape-bucket, dtype), then DEFAULT_CONFIG.  Runs at
    trace time (block sizes are static jit args), so a bucket tuned before
    its first trace bakes its winner into the compiled slice."""
    cfg = get_tuned(op, shape, dtype) or DEFAULT_CONFIG
    return (block_rows or cfg.block_rows, block_edges or cfg.block_edges)


def gnn_aggregate(
    msg: jax.Array,
    seg: jax.Array,
    num_segments: int,
    *,
    use_kernel: bool = True,
    block_rows: int | None = None,
    block_edges: int | None = None,
    ragged: bool = True,
) -> jax.Array:
    """Segment-sum of gathered neighbor messages (GNN aggregation hotspot).

    ``ragged=True`` (default) routes to the tile-skipping kernel so the
    engine's power-of-two bucket padding costs mask work, not MXU work."""
    if not use_kernel:
        return segment_spmm_ref(msg, seg, num_segments)
    shape = (msg.shape[0], num_segments, msg.shape[1])
    if ragged:
        _, be = _blocks("segment_spmm_ragged", shape, msg.dtype, None, block_edges)
        return segment_spmm_ragged_pallas(msg, seg, num_segments, block_edges=be)
    br, be = _blocks("segment_spmm", shape, msg.dtype, block_rows, block_edges)
    return segment_spmm_pallas(
        msg, seg, num_segments, block_rows=br, block_edges=be
    )


def gnn_gather_aggregate(
    feats: jax.Array,
    idx: jax.Array,
    seg: jax.Array,
    num_segments: int,
    *,
    use_kernel: bool = True,
    block_edges: int | None = None,
) -> jax.Array:
    """Gather + aggregate: out[s] = sum_{seg[e]==s} feats[idx[e]].

    The gather is XLA's (a row gather inside a Mosaic kernel does not
    compile for the TPU); the aggregation is the ragged segment kernel.
    Edges with ``idx == -1`` or ``seg == -1`` are padding."""
    if not use_kernel:
        return gather_spmm_ref(feats, idx, seg, num_segments)
    ok = (idx >= 0) & (seg >= 0)
    msg = jnp.where(ok[:, None], feats[jnp.maximum(idx, 0)], 0)
    return gnn_aggregate(
        msg, jnp.where(ok, seg, -1), num_segments, block_edges=block_edges
    )


def gnn_gat_aggregate(
    logits: jax.Array,
    msg: jax.Array,
    seg: jax.Array,
    num_segments: int,
    *,
    use_kernel: bool = True,
    block_edges: int | None = None,
) -> jax.Array:
    """One-pass edge-softmax + weighted aggregate (GAT/HGT inner loop)."""
    if not use_kernel:
        return gat_softmax_aggregate_ref(logits, msg, seg, num_segments)
    shape = (seg.shape[0], num_segments, msg.shape[1])
    _, be = _blocks("gat_softmax_aggregate", shape, msg.dtype, None, block_edges)
    return gat_softmax_aggregate_pallas(
        logits, msg, seg, num_segments, block_edges=be
    )


def gnn_segment_max(
    x: jax.Array,
    seg: jax.Array,
    num_segments: int,
    *,
    use_kernel: bool = True,
    block_edges: int | None = None,
) -> jax.Array:
    """Per-segment max with seg=-1 padding excluded; empty segments -> 0."""
    if not use_kernel:
        return segment_max_ref(x, seg, num_segments)
    shape = (seg.shape[0], num_segments, 1)
    _, be = _blocks("segment_max", shape, x.dtype, None, block_edges)
    return segment_max_pallas(x, seg, num_segments, block_edges=be)


def mha_attention(
    q: jax.Array,  # [B, Sq, H, D]
    k: jax.Array,  # [B, Skv, Hkv, D]
    v: jax.Array,  # [B, Skv, Hkv, D]
    *,
    causal: bool = True,
    window: int = 0,
    kv_offset: int = 0,
    use_kernel: bool = True,
) -> jax.Array:
    """Multi-head attention with GQA (H a multiple of Hkv), batched via vmap
    over (batch, head) pairs of the single-head flash kernel."""
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    kr = jnp.repeat(k, rep, axis=2)
    vr = jnp.repeat(v, rep, axis=2)
    qh = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kh = kr.transpose(0, 2, 1, 3).reshape(b * h, -1, d)
    vh = vr.transpose(0, 2, 1, 3).reshape(b * h, -1, d)
    fn = flash_attention_pallas if use_kernel else attention_ref
    kwargs = dict(causal=causal, window=window, kv_offset=kv_offset)
    out = jax.vmap(lambda qq, kk, vv: fn(qq, kk, vv, **kwargs))(qh, kh, vh)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


def ssd_scan(
    x: jax.Array,  # [B, S, H, P]
    dt: jax.Array,  # [B, S, H]
    A: jax.Array,  # [H]
    B_: jax.Array,  # [B, S, G, N]
    C: jax.Array,  # [B, S, G, N]
    *,
    chunk: int = 128,
    use_kernel: bool = True,
) -> jax.Array:
    """Batched multi-head SSD scan; returns y [B, S, H, P]."""
    bsz, S, H, P = x.shape
    G, N = B_.shape[2], B_.shape[3]
    rep = H // G
    if not use_kernel:
        return jax.vmap(lambda xx, dd, bb, cc: ssd_scan_ref(xx, dd, A, bb, cc))(
            x, dt, B_, C
        )
    Bh = jnp.repeat(B_, rep, axis=2)  # [B, S, H, N]
    Ch = jnp.repeat(C, rep, axis=2)
    a = dt * A[None, None, :]  # [B, S, H]

    def one(xx, aa, dd, bb, cc):
        y, _ = ssd_scan_pallas(xx, aa, dd, bb, cc, chunk=chunk)
        return y

    # flatten (batch, head)
    xf = x.transpose(0, 2, 1, 3).reshape(bsz * H, S, P)
    af = a.transpose(0, 2, 1).reshape(bsz * H, S)
    df = dt.transpose(0, 2, 1).reshape(bsz * H, S)
    bf = Bh.transpose(0, 2, 1, 3).reshape(bsz * H, S, N)
    cf = Ch.transpose(0, 2, 1, 3).reshape(bsz * H, S, N)
    yf = jax.vmap(one)(xf, af, df, bf, cf)
    return yf.reshape(bsz, H, S, P).transpose(0, 2, 1, 3)

"""Host-side conversion: SampledSubgraph -> padded fixed-shape GNNBatch.

XLA needs static shapes; sampled subgraphs are ragged.  We bucket-pad the
vertex table and per-layer edge lists to multiples (power-of-two-ish) so jit
recompiles only on bucket changes — this is the TPU adaptation of the
paper's dynamic subgraph feeding (DESIGN.md §3).

Layer-k edge list = concat of hops 0..K-1-k (a vertex first reached at depth
d carries its sampled one-hop edges at hop d; see core/inference/engine.py).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np

from repro import obs
from repro.core.sampling.service import SampledSubgraph
from repro.core.storage import as_feature_source
from repro.utils import round_up

__all__ = ["GNNBatch", "subgraph_to_batch"]


@jax.tree_util.register_dataclass
@dataclass
class GNNBatch:
    feats: np.ndarray  # [V, F] float32, padded
    valid: np.ndarray  # [V] bool
    seed_pos: np.ndarray  # [B] int32 position of seeds in the table
    labels: np.ndarray  # [B] int32
    # per GNN layer k: (dst_pos [Ek], src_pos [Ek], etype [Ek]) padded, -1 pad
    layer_dst: list
    layer_src: list
    layer_etype: list
    # per layer k: [V, 1] float32 valid-edge in-degree per destination —
    # static for the batch, so it's counted ONCE here (host-side bincount)
    # instead of once per GCN/SAGE layer call; None = compute in-model
    layer_cnt: list | None = None
    # [V] int32 vertex type of each row (0 on padding), for models that
    # read it (hgt); None = not carried
    vtype: np.ndarray | None = None

    @property
    def num_vertices(self) -> int:
        return self.feats.shape[0]


def _bucket(n: int, quantum: int = 256) -> int:
    return max(quantum, round_up(n, quantum))


def subgraph_to_batch(
    sub: SampledSubgraph,
    feats,  # [N, F] ndarray or a repro.core.storage.FeatureSource
    labels: np.ndarray | None,
    num_layers: int,
    edge_types_lookup=None,  # optional fn (src_gid, dst_gid) -> etype
    edge_types: np.ndarray | None = None,  # global per-edge type table
    vertex_quantum: int = 256,
    edge_quantum: int = 1024,
    vertex_types: np.ndarray | None = None,  # global per-vertex type table
    index: int | None = None,  # the batch's request index, for its counters
) -> GNNBatch:
    """``vertex_types`` given, the batch carries its rows' types
    (``GNNBatch.vtype``).  While ``repro.obs`` records, the batch's real
    sampled edges and its padded edge slots, each summed over the layers,
    are counted as ``batch.edges`` and ``batch.edge_slots``."""
    src = as_feature_source(feats)
    verts = sub.all_vertices()  # unique sorted gids
    vpad = _bucket(verts.shape[0], vertex_quantum)
    table = np.zeros((vpad, src.dim), dtype=np.float32)
    table[: verts.shape[0]] = src.gather(verts)
    valid = np.zeros(vpad, dtype=bool)
    valid[: verts.shape[0]] = True
    vtype = None
    if vertex_types is not None:
        vtype = np.zeros(vpad, dtype=np.int32)
        vtype[: verts.shape[0]] = vertex_types[verts]

    seed_pos = np.searchsorted(verts, sub.seeds).astype(np.int32)
    lab = (
        labels[sub.seeds].astype(np.int32)
        if labels is not None
        else np.zeros(sub.seeds.shape[0], np.int32)
    )

    K = num_layers
    layer_dst, layer_src, layer_et, layer_cnt = [], [], [], []
    for k in range(K):
        hops = sub.hops[: K - k]
        src = np.concatenate([h.src for h in hops]) if hops else np.zeros(0, np.int64)
        dst = np.concatenate([h.dst for h in hops]) if hops else np.zeros(0, np.int64)
        eid = (
            np.concatenate([h.eid for h in hops])
            if hops and all(h.eid is not None for h in hops)
            else None
        )
        epad = _bucket(src.shape[0], edge_quantum)
        d_pos = np.full(epad, -1, dtype=np.int32)
        s_pos = np.full(epad, -1, dtype=np.int32)
        et = np.zeros(epad, dtype=np.int32)
        d_pos[: src.shape[0]] = np.searchsorted(verts, src)  # aggregation target
        s_pos[: src.shape[0]] = np.searchsorted(verts, dst)  # message source
        if src.shape[0]:
            if edge_types is not None and eid is not None:
                # direct: sampled edge ids index the global edge-type table
                et[: src.shape[0]] = edge_types[eid]
            elif edge_types_lookup is not None:
                et[: src.shape[0]] = edge_types_lookup(src, dst)
        layer_dst.append(d_pos)
        layer_src.append(s_pos)
        layer_et.append(et)
        layer_cnt.append(
            np.bincount(d_pos[d_pos >= 0], minlength=vpad)
            .astype(np.float32)
            .reshape(vpad, 1)
        )
    if obs.tracing():
        obs.count("batch.edges", sum(int((d >= 0).sum()) for d in layer_dst), index)
        obs.count("batch.edge_slots", sum(d.shape[0] for d in layer_dst), index)
    return GNNBatch(
        feats=table,
        valid=valid,
        seed_pos=seed_pos,
        labels=lab,
        layer_dst=layer_dst,
        layer_src=layer_src,
        layer_etype=layer_et,
        layer_cnt=layer_cnt,
        vtype=vtype,
    )

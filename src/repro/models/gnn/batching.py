"""Host-side conversion: SampledSubgraph -> padded fixed-shape GNNBatch.

XLA needs static shapes; sampled subgraphs are ragged.  We bucket-pad the
vertex table and per-layer edge lists to multiples (power-of-two-ish) so jit
recompiles only on bucket changes — this is the TPU adaptation of the
paper's dynamic subgraph feeding (DESIGN.md §3).

The batch is built in the memory it will travel in where the caller gives
it (the forked producer's shared-memory slot, ``into``), else in fresh
arrays; either way the feature rows are gathered straight into the table.

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
    into=None,  # fn(layout) -> GNNBatch of arrays to build in, or None
) -> GNNBatch:
    """``vertex_types`` given, the batch carries its rows' types
    (``GNNBatch.vtype``).  While ``repro.obs`` records, the batch's real
    sampled edges and its padded edge slots, each summed over the layers,
    are counted as ``batch.edges`` and ``batch.edge_slots``.

    The build works out the batch's layout first (a :class:`GNNBatch` of
    ``jax.ShapeDtypeStruct``: the padded sizes, from the sample's vertices
    and edge counts), then fills arrays of that layout.  ``into`` given, it
    is called with the layout and returns the arrays to fill, such as views
    of the shared memory the batch will travel in; every element of them is
    written, so they may hold an earlier batch.  Fresh arrays are filled
    where ``into`` is None or returns None.  The feature rows are gathered
    straight into the table, with no temporary array."""
    src = as_feature_source(feats)
    verts = sub.all_vertices()  # unique sorted gids
    n = verts.shape[0]
    vpad = _bucket(n, vertex_quantum)
    K = num_layers
    hops_of = [sub.hops[: K - k] for k in range(K)]  # layer k's hops
    edges = [sum(h.src.shape[0] for h in hops) for hops in hops_of]
    epads = [_bucket(e, edge_quantum) for e in edges]

    spec = jax.ShapeDtypeStruct
    layout = GNNBatch(
        feats=spec((vpad, src.dim), np.float32),
        valid=spec((vpad,), bool),
        seed_pos=spec(sub.seeds.shape, np.int32),
        labels=spec(sub.seeds.shape, np.int32),
        layer_dst=[spec((e,), np.int32) for e in epads],
        layer_src=[spec((e,), np.int32) for e in epads],
        layer_etype=[spec((e,), np.int32) for e in epads],
        layer_cnt=[spec((vpad, 1), np.float32) for _ in epads],
        vtype=spec((vpad,), np.int32) if vertex_types is not None else None,
    )
    out = into(layout) if into is not None else None
    if out is None:
        out = jax.tree.map(lambda s: np.empty(s.shape, s.dtype), layout)

    src.gather(verts, out=out.feats[:n])
    out.feats[n:] = 0
    out.valid[:n] = True
    out.valid[n:] = False
    if vertex_types is not None:
        out.vtype[:n] = vertex_types[verts]
        out.vtype[n:] = 0
    out.seed_pos[:] = np.searchsorted(verts, sub.seeds)
    out.labels[:] = labels[sub.seeds] if labels is not None else 0

    for k, hops in enumerate(hops_of):
        e = edges[k]
        d_pos, s_pos, et = out.layer_dst[k], out.layer_src[k], out.layer_etype[k]
        if e:
            src_gid = np.concatenate([h.src for h in hops])
            dst_gid = np.concatenate([h.dst for h in hops])
            d_pos[:e] = np.searchsorted(verts, src_gid)  # aggregation target
            s_pos[:e] = np.searchsorted(verts, dst_gid)  # message source
            if edge_types is not None and all(h.eid is not None for h in hops):
                # direct: sampled edge ids index the global edge-type table
                et[:e] = edge_types[np.concatenate([h.eid for h in hops])]
            elif edge_types_lookup is not None:
                et[:e] = edge_types_lookup(src_gid, dst_gid)
            else:
                et[:e] = 0
        d_pos[e:] = -1
        s_pos[e:] = -1
        et[e:] = 0
        out.layer_cnt[k][:, 0] = np.bincount(d_pos[:e], minlength=vpad)
    if obs.tracing():
        obs.count("batch.edges", sum(edges), index)
        obs.count("batch.edge_slots", sum(epads), index)
    return out

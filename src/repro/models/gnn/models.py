"""GNN models in JAX: GCN, GraphSAGE, GAT, HGT — the models GLISP evaluates
(paper Table IV trains all on 3 stacked layers, hidden 256, GAT 4 heads;
the RelNet KGE encoder is a 2-layer HGT).

All layers aggregate over padded edge lists (dst_pos, src_pos, etype) with
-1 padding; the segment-sum hotspot goes through kernels.gnn_aggregate
(Pallas on TPU, jnp oracle otherwise).  Each kind is written once, in
``GNNModel.layer``, which training, the layerwise engine and the server all
run.  It follows the block convention: the destination rows are a prefix
of the table, the layer produces its first ``n_out`` rows (all of them in
training), and messages come from any row.

HGT is the Heterogeneous Graph Transformer (Hu et al., WWW 2020,
arXiv:2003.01332) as PyG's ``HGTConv`` has it (eq. 1-5, no relative
temporal encoding), after a per-type input linear and tanh (pyHGT's
``adapt_ws``), which layer 0 carries.  With τ a row's vertex type and φ an
edge's relation (the stored type of the sampled edge), a layer over H heads of width d computes
``q_t = Q_τ(t) h_t``, ``k_s = K_τ(s) h_s``, ``v_s = V_τ(s) h_s`` (linears
with bias), per edge s -> t and head i ``a = μ_φ,i (q_t,i · k_s,i W^ATT_φ,i)
/ √d`` and the message ``v_s,i W^MSG_φ,i``, a softmax of ``a`` per head over
all of t's in-edges, across relations, ``o_t = A_τ(t) gelu(sum α m)`` and
``h'_t = β o_t + (1 - β) h_t`` with ``β = sigmoid(s_τ(t))``.  Per-type and
per-relation weights are applied as dense products over the rows, each
row or edge taking its own type's result: no array indexed by edge holds
a weight matrix.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels.ops import (
    gather_spmm_ref,
    gnn_aggregate,
    gnn_gat_aggregate,
    gnn_gather_aggregate,
    segment_spmm_ref,
)

GNN_KINDS = ("gcn", "sage", "gat", "hgt")

Params = dict[str, Any]


def _seg_sum(msg, seg, n, use_kernel):
    if use_kernel:
        return gnn_aggregate(msg, seg, n)
    return segment_spmm_ref(msg, seg, n)


def _gather_seg_sum(h, idx, seg, n, use_kernel):
    """out[s] = sum_{seg[e]==s} h[idx[e]] — fused gather+aggregate when the
    kernel is on (no [E, D] message array), masked jnp gather otherwise."""
    if use_kernel:
        return gnn_gather_aggregate(h, idx, seg, n)
    return gather_spmm_ref(h, idx, seg, n)


def _seg_count(seg, n, use_kernel=False):
    ones = (seg >= 0).astype(jnp.float32)[:, None]
    return _seg_sum(ones, seg, n, use_kernel)  # [n,1]


def _seg_softmax(logits, seg, n):
    """Softmax over edges grouped by seg (padding seg=-1 excluded), on jnp:
    with the kernel on, ``gnn_gat_aggregate`` fuses it."""
    neg = jnp.where(seg >= 0, logits, -jnp.inf)
    mx = jax.ops.segment_max(neg, jnp.maximum(seg, 0), num_segments=n)
    mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
    e = jnp.where(seg >= 0, jnp.exp(logits - mx[jnp.maximum(seg, 0)]), 0.0)
    z = _seg_sum(e[:, None], seg, n, False)[:, 0]
    return e / jnp.maximum(z[jnp.maximum(seg, 0)], 1e-9)


def _typed_linear(x, w, b, vt):
    """``x @ w[t] + b[t]`` for each row's type ``t`` (w: [T, din, dout]):
    every type's product over all rows, each row keeping its own."""
    out = x @ w[0] + b[0]
    for t in range(1, w.shape[0]):
        out = jnp.where((vt == t)[:, None], x @ w[t] + b[t], out)
    return out


def _per_relation(x, w, src, rel):
    """``x[src[e], i] @ w[rel[e], i]`` for each edge e and head i (x: [n,
    H, d], w: [R, H, d, d]): every relation's product over the rows, and
    each edge gathers its source row's under its own relation."""
    return jnp.einsum("nhd,rhdf->nrhf", x, w)[src, rel]


def _seg_softmax_aggregate(logits, msg, seg, n, use_kernel):
    """out[s] = sum_e softmax_{seg==s}(logits)[e] * msg[e] — one head of
    the GAT/HGT inner loop.  One Pallas kernel when enabled, the original
    3-pass ``_seg_softmax`` + ``_seg_sum`` otherwise."""
    if use_kernel:
        return gnn_gat_aggregate(logits, msg, seg, n)
    alpha = _seg_softmax(logits, seg, n)
    return _seg_sum(msg * alpha[:, None], seg, n, False)


def _heads_softmax_aggregate(logits, msg, seg, n, use_kernel):
    """:func:`_seg_softmax_aggregate` for each head i of ``logits`` [E, H]
    and ``msg`` [E, H, d], the heads concatenated: one head at a time keeps
    the segment ops 2-D."""
    return jnp.concatenate(
        [_seg_softmax_aggregate(logits[:, i], msg[:, i], seg, n, use_kernel)
         for i in range(logits.shape[1])],
        axis=1,
    )


class GNNModel:
    def __init__(
        self,
        kind: str,
        in_dim: int,
        hidden: int = 256,
        num_layers: int = 3,
        num_classes: int = 16,
        num_heads: int = 4,
        num_etypes: int = 4,
        use_kernel: bool = False,
        num_vtypes: int = 1,
    ):
        """hgt only: ``num_vtypes`` vertex types and ``num_etypes``
        relations, a relation being the stored type of the sampled edge."""
        assert kind in GNN_KINDS
        self.kind = kind
        self.in_dim = in_dim
        self.hidden = hidden
        self.num_layers = num_layers
        self.num_classes = num_classes
        self.num_heads = num_heads
        self.num_etypes = num_etypes
        self.use_kernel = use_kernel
        self.num_vtypes = num_vtypes

    @property
    def reads_vertex_types(self) -> bool:
        """Whether a batch must carry its rows' vertex types (``GNNBatch.vtype``)."""
        return self.kind == "hgt"

    # -- init ---------------------------------------------------------------
    def init(self, key) -> Params:
        dims = [self.in_dim] + [self.hidden] * self.num_layers
        layers = []
        for k in range(self.num_layers):
            lk = jax.random.fold_in(key, k)
            din, dout = dims[k], dims[k + 1]
            scale = (1.0 / din) ** 0.5
            if self.kind == "gcn":
                p = {"w": jax.random.normal(lk, (din, dout)) * scale,
                     "b": jnp.zeros((dout,))}
            elif self.kind == "sage":
                p = {"w": jax.random.normal(lk, (2 * din, dout)) * scale,
                     "b": jnp.zeros((dout,))}
            elif self.kind == "gat":
                h = self.num_heads
                dh = dout // h
                k1, k2, k3 = jax.random.split(lk, 3)
                p = {
                    "w": jax.random.normal(k1, (din, h * dh)) * scale,
                    "a_dst": jax.random.normal(k2, (h, dh)) * 0.1,
                    "a_src": jax.random.normal(k3, (h, dh)) * 0.1,
                }
            elif self.kind == "hgt":
                p = self._hgt_init(lk, din if k == 0 else None)
            layers.append(p)
        ko = jax.random.fold_in(key, 999)
        return {
            "layers": layers,
            "out": jax.random.normal(ko, (self.hidden, self.num_classes))
            * (1.0 / self.hidden) ** 0.5,
        }

    def _hgt_init(self, key, in_dim: int | None) -> Params:
        """One HGT layer from ``key``: per-type Q, K, V and output linears
        ``N(0, 1/hidden)`` with zero bias; per relation and head the
        attention and message matrices ``N(0, 1/d)``; the prior μ and per
        type the gate s at 1 (as PyG); with ``in_dim`` (layer 0) the
        per-type input adapter from the features, ``N(0, 1/in_dim)``."""
        T, R, H = self.num_vtypes, self.num_etypes, self.num_heads
        D, d = self.hidden, self.hidden // self.num_heads
        ks = jax.random.split(key, 7)
        lin = lambda kk, n_in: jax.random.normal(kk, (T, n_in, D)) * (1.0 / n_in) ** 0.5
        rel = lambda kk: jax.random.normal(kk, (R, H, d, d)) * (1.0 / d) ** 0.5
        p = {
            "wq": lin(ks[0], D), "bq": jnp.zeros((T, D)),
            "wk": lin(ks[1], D), "bk": jnp.zeros((T, D)),
            "wv": lin(ks[2], D), "bv": jnp.zeros((T, D)),
            "wa": lin(ks[3], D), "ba": jnp.zeros((T, D)),
            "w_att": rel(ks[4]), "w_msg": rel(ks[5]),
            "mu": jnp.ones((R, H)), "skip": jnp.ones((T,)),
        }
        if in_dim is not None:
            p["w_in"], p["b_in"] = lin(ks[6], in_dim), jnp.zeros((T, D))
        return p

    def _hgt_layer(self, p: Params, h, dst, src, etype, vt, *, n_out, use_kernel: bool):
        """One HGT layer (module docstring) over rows ``h`` of vertex types
        ``vt`` and the padded edge list, producing rows ``[0, n_out)``
        (:meth:`layer`); ``vt`` may be None only where there is one vertex
        type."""
        n, heads = h.shape[0], self.num_heads
        if vt is None:
            if self.num_vtypes > 1:
                raise ValueError(
                    f"hgt over {self.num_vtypes} vertex types needs each row's type"
                )
            vt = jnp.zeros(n, jnp.int32)
        if "w_in" in p:  # layer 0: the input adapter
            with jax.named_scope("hgt.typed_linear"):
                h = jnp.tanh(_typed_linear(h, p["w_in"], p["b_in"], vt))
        m, h_out, vt_out = n, h, vt
        if n_out is not None and n_out != n:
            m, h_out, vt_out = n_out, h[:n_out], vt[:n_out]
        d = h.shape[1] // heads
        with jax.named_scope("hgt.typed_linear"):
            q, k, v = (
                _typed_linear(x, p[w], p[b], t).reshape(-1, heads, d)
                for x, t, w, b in (
                    (h_out, vt_out, "wq", "bq"), (h, vt, "wk", "bk"), (h, vt, "wv", "bv")
                )
            )
        with jax.named_scope("hgt.relation"):
            rel = jnp.maximum(etype, 0)
            s = jnp.maximum(src, 0)
            k_e = _per_relation(k, p["w_att"], s, rel)
            m_e = jnp.where((src >= 0)[:, None, None], _per_relation(v, p["w_msg"], s, rel), 0.0)
        with jax.named_scope("hgt.attention"):
            att = (q[jnp.maximum(dst, 0)] * k_e).sum(-1) * p["mu"][rel] / d**0.5  # [E, H]
            agg = _heads_softmax_aggregate(att, m_e, dst, m, use_kernel)
        with jax.named_scope("hgt.output"):
            o = _typed_linear(jax.nn.gelu(agg, approximate=False), p["wa"], p["ba"], vt_out)
            beta = jax.nn.sigmoid(p["skip"][vt_out])[:, None]
            return beta * o + (1.0 - beta) * h_out

    # -- single layer ---------------------------------------------------------
    def layer(
        self, p: Params, k: int, h: jax.Array, dst, src, etype, cnt=None, vtype=None,
        *, n_out: int | None = None, use_kernel: bool | None = None,
    ) -> jax.Array:
        """Layer ``k`` over the table ``h``: messages come from ``h[src]``
        over the whole table, and the layer produces rows ``[0, n_out)``
        (all rows when None), into which ``dst`` points.  ``cnt`` is the
        optional precomputed in-degree column ([n_out, 1], valid-edge counts
        per destination) — static per batch, so callers with a
        ``GNNBatch.layer_cnt`` pass it instead of recomputing the
        segment-count here on every layer call.  ``vtype`` is each row's
        vertex type, read by hgt; ``use_kernel`` defaults to the model's."""
        use_kernel = self.use_kernel if use_kernel is None else use_kernel
        ok = src >= 0
        if self.kind == "hgt":
            return self._hgt_layer(
                p, h, dst, src, etype, vtype, n_out=n_out, use_kernel=use_kernel
            )
        n, h_out = h.shape[0], h
        if n_out is not None and n_out != n:
            n, h_out = n_out, h[:n_out]
        if self.kind in ("gcn", "sage"):
            # fused path gathers h[src] inside the kernel's edge tiles
            agg = _gather_seg_sum(h, src, dst, n, use_kernel)
            if cnt is None:
                cnt = _seg_count(dst, n, use_kernel)
            if self.kind == "gcn":
                return jax.nn.relu(((agg + h_out) / (cnt + 1.0)) @ p["w"] + p["b"])
            return jax.nn.relu(
                jnp.concatenate([h_out, agg / jnp.maximum(cnt, 1.0)], axis=1) @ p["w"]
                + p["b"]
            )
        if self.kind == "gat":
            heads, dh = p["a_dst"].shape
            z = (h @ p["w"]).reshape(-1, heads, dh)
            zsrc = jnp.where(ok[:, None, None], z[jnp.maximum(src, 0)], 0.0)
            zdst = jnp.where((dst >= 0)[:, None, None], z[jnp.maximum(dst, 0)], 0.0)
            e = jax.nn.leaky_relu(
                (zdst * p["a_dst"]).sum(-1) + (zsrc * p["a_src"]).sum(-1), 0.2
            )  # [E, H]
            return jax.nn.elu(_heads_softmax_aggregate(e, zsrc, dst, n, use_kernel))
        raise ValueError(self.kind)

    def kernel_shapes(self, num_edges: int, num_vertices: int, in_dim: int) -> list:
        """(op, (edges, segments, dim)) tuples a layer with the kernel on
        dispatches over ``num_edges`` edges into ``num_vertices`` rows of
        width ``in_dim`` — the engine hands them to the autotuner before a
        bucket's first jit trace so tuned blocks bake into the compile."""
        if self.kind in ("gcn", "sage"):
            return [
                ("segment_spmm_ragged", (num_edges, num_vertices, in_dim)),
                ("segment_spmm_ragged", (num_edges, num_vertices, 1)),
            ]
        return [("gat_softmax_aggregate", (num_edges, num_vertices, self.hidden // self.num_heads))]

    # -- full apply --------------------------------------------------------------
    def apply(self, params: Params, batch) -> jax.Array:
        """batch: GNNBatch (feats/valid/layer_* as jnp arrays).  When the
        batch carries precomputed per-layer degree columns (``layer_cnt``,
        built host-side in ``subgraph_to_batch``), GCN/SAGE skip the
        per-layer segment-count entirely."""
        h = batch.feats
        cnts = getattr(batch, "layer_cnt", None)
        for k in range(self.num_layers):
            h = self.layer(
                params["layers"][k],
                k,
                h,
                batch.layer_dst[k],
                batch.layer_src[k],
                batch.layer_etype[k],
                cnt=None if cnts is None else cnts[k],
                vtype=getattr(batch, "vtype", None),
            )
            h = h * batch.valid[:, None]
        return h[batch.seed_pos] @ params["out"]

    def loss(self, params: Params, batch) -> jax.Array:
        logits = self.apply(params, batch)
        logz = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, batch.labels[:, None], axis=-1)[:, 0]
        return (logz - tgt).mean()

    def embed_layer_fn(self, params: Params, k: int, *, use_kernel: bool | None = None):
        """Adapter for the layerwise inference engine and the server: layer
        ``k`` of the model as (k, h_self, h_nbr, seg[, etype[, vt_self,
        vt_nbr]]) -> h_new (numpy in/out), with one row of ``h_nbr`` per
        sampled edge and ``seg`` (-1 padding) its self row.

        The slice is :meth:`layer` itself on a block: the table is the self
        rows followed by the neighbour rows, so the destination rows are its
        prefix, ``n_out`` is the number of self rows, and edge e's source is
        row ``len(h_self) + e``.

        The returned callable carries three engine-facing attributes:

        * ``fn.jax(h_self, h_nbr, seg, etype[, vt_self, vt_nbr], *,
          use_kernel=...)`` — the pure traceable slice on jnp arrays, which
          the bucketed engine wraps in ``jax.jit`` so each (layer,
          shape-bucket) pair compiles once and stays device-resident;
          ``use_kernel`` defaults to this call's, else the model's flag.
        * ``fn.typed`` — True for hgt, whose per-relation and per-type
          projections need the sampled edges' type ids and the vertex types
          of the self rows and of the neighbour rows.
        * ``fn.kernel_shapes`` — :meth:`kernel_shapes`."""
        p = params["layers"][k]
        default_kernel = self.use_kernel if use_kernel is None else use_kernel

        def jax_fn(h_self, h_nbr, seg, etype, vt_self=None, vt_nbr=None, *, use_kernel=default_kernel):
            m, e = h_self.shape[0], h_nbr.shape[0]
            seg = seg.astype(jnp.int32)
            src = jnp.where(seg >= 0, m + jnp.arange(e, dtype=jnp.int32), -1)
            vt = None if vt_self is None else jnp.concatenate([vt_self, vt_nbr]).astype(jnp.int32)
            h = jnp.concatenate([h_self, h_nbr])
            return self.layer(
                p, k, h, seg, src, etype.astype(jnp.int32),
                vtype=vt, n_out=m, use_kernel=use_kernel,
            )

        def fn(_k, h_self, h_nbr, seg, etype=None, vt_self=None, vt_nbr=None):
            m = h_nbr.shape[0]
            sg = jnp.asarray(seg, jnp.int32) if m else jnp.zeros(0, jnp.int32)
            et = (
                jnp.asarray(etype, jnp.int32)
                if etype is not None and m
                else jnp.zeros(m, jnp.int32)
            )
            types = [None if t is None else jnp.asarray(t, jnp.int32) for t in (vt_self, vt_nbr)]
            return jax.device_get(
                jax_fn(jnp.asarray(h_self), jnp.asarray(h_nbr), sg, et, *types)
            )

        fn.jax = jax_fn
        fn.typed = self.reads_vertex_types
        fn.kernel_shapes = self.kernel_shapes
        return fn

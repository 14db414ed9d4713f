"""Plain HGT (Hu, Dong, Wang, Sun, WWW 2020, arXiv:2003.01332) in
jax.numpy, float32, over OGB-LSC MAG240M's schema.

The layer is PyG's ``HGTConv``, which follows the paper's eq. 1-5.  With
τ(v) a row's vertex type, φ(e) the relation of the message of edge s -> t,
H heads of width d = hidden / H, and every linear with a bias:
``q_t = Q_τ(t) h_t``, ``k_s = K_τ(s) h_s``, ``v_s = V_τ(s) h_s``, split
into heads; per head i ``a_e,i = μ_φ(e),i (q_t,i · k_s,i W^ATT_φ(e),i) /
√d`` and the message ``m_e,i = v_s,i W^MSG_φ(e),i``; ``α`` the softmax of
``a`` per head over all of t's sampled in-edges, across relations;
``o_t = A_τ(t)(gelu(concat_i sum_e α m))`` (the exact GELU) and
``h'_t = β o_t + (1 - β) h_t`` with ``β = sigmoid(s_τ(t))``.  Layer 0
first maps the features through a per-type linear and tanh, as the
authors' pyHGT does (``adapt_ws``).  The classifier is a bias-free linear
map of the last layer at the seed rows, as in ``sage.py``.

Departures from the paper: no relative temporal encoding (MAG240M's years
are not modelled), no dropout, no layer norm (PyG's ``HGTConv`` has none).

Here a typed linear is every type's product stacked, each row picking its
own, and each edge's key and message go through each relation's heads as
one block-diagonal matrix, the edge keeping its own relation's product.

Relations.  A relation is the stored type of the sampled edge.  MAG240M's
stored edge types are 0 cites (paper -> paper, each citation in both
directions), 1 writes (author -> paper), 2 its reverse (paper -> author),
3 affiliated_with (author -> institution) and 4 its reverse (institution
-> author).  The sampler walks a stored edge from the row that aggregates
to its neighbour, so the message runs against the stored edge: relation 0
carries messages paper -> paper, 1 paper -> author, 2 author -> paper,
3 institution -> author and 4 author -> institution.

Weights, for layer k from ``fold_in(key, k)`` split in 7: the Q, K, V and
output linears, per type, ``N(0, 1/hidden)`` with zero bias; per relation
and head W^ATT and W^MSG ``N(0, 1/d)``; μ and s at 1; in layer 0 the
adapter from the features, per type, ``N(0, 1/in_dim)`` with zero bias.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

def init(key, in_dim: int, model: dict, num_classes: int) -> dict:
    T, R, H = model["num_vtypes"], model["num_etypes"], model["num_heads"]
    D = model["hidden"]
    d = D // H
    layers = []
    for k in range(model["num_layers"]):
        ks = jax.random.split(jax.random.fold_in(key, k), 7)

        def lin(kk, n_in):
            return jax.random.normal(kk, (T, n_in, D)) * (1.0 / n_in) ** 0.5

        p = {
            "wq": lin(ks[0], D), "bq": jnp.zeros((T, D)),
            "wk": lin(ks[1], D), "bk": jnp.zeros((T, D)),
            "wv": lin(ks[2], D), "bv": jnp.zeros((T, D)),
            "wa": lin(ks[3], D), "ba": jnp.zeros((T, D)),
            "w_att": jax.random.normal(ks[4], (R, H, d, d)) * (1.0 / d) ** 0.5,
            "w_msg": jax.random.normal(ks[5], (R, H, d, d)) * (1.0 / d) ** 0.5,
            "mu": jnp.ones((R, H)),
            "skip": jnp.ones((T,)),
        }
        if k == 0:
            p["w_in"], p["b_in"] = lin(ks[6], in_dim), jnp.zeros((T, D))
        layers.append(p)
    out = jax.random.normal(jax.random.fold_in(key, 999), (D, num_classes))
    return {"layers": layers, "out": out * (1.0 / D) ** 0.5}


def _typed(x, w, b, vtype, dot):
    """Row r through its own type's linear: all types' outputs stacked,
    then each row's picked."""
    every = jnp.stack([dot(x, w[t]) + b[t] for t in range(w.shape[0])])  # [T, n, D]
    return jnp.take_along_axis(every, vtype[None, :, None], axis=0)[0]


def _by_relation(x, w, rel, dot):
    """Edge e's heads ``x[e]`` (x: [E, H d]) through its relation's
    matrices ``w[rel[e]]`` (w: [R, H, d, d]): per relation one product with
    its heads' matrices on the diagonal of an [H d, H d] matrix (built by
    exact products with 0 and 1), each edge keeping its own relation's."""
    R, H, d, _ = w.shape
    eye = jnp.eye(H, dtype=w.dtype)
    out = jnp.zeros_like(x)
    for r in range(R):
        block = (w[r][:, :, None, :] * eye[:, None, :, None]).reshape(H * d, H * d)
        out = jnp.where((rel == r)[:, None], dot(x, block), out)
    return out


def layer(p: dict, h, dst, src, n: int, dot, model: dict, *, vtype, etype):
    """``dst``/``src``: per edge slot, the row that aggregates and the row
    whose message it carries; ``vtype`` each row's vertex type, ``etype``
    each edge's stored type."""
    H = model["num_heads"]
    if "w_in" in p:
        h = jnp.tanh(_typed(h, p["w_in"], p["b_in"], vtype, dot))
    d = h.shape[1] // H
    q = _typed(h, p["wq"], p["bq"], vtype, dot)
    k = _typed(h, p["wk"], p["bk"], vtype, dot)
    v = _typed(h, p["wv"], p["bv"], vtype, dot)
    k_e = _by_relation(k[src], p["w_att"], etype, dot).reshape(-1, H, d)
    m_e = _by_relation(v[src], p["w_msg"], etype, dot).reshape(-1, H, d)
    a = (q[dst].reshape(-1, H, d) * k_e).sum(-1) * p["mu"][etype] / jnp.sqrt(jnp.asarray(d, h.dtype))  # [E, H]
    a_max = jax.ops.segment_max(a, dst, num_segments=n)
    w = jnp.exp(a - a_max[dst])
    alpha = w / jax.ops.segment_sum(w, dst, num_segments=n)[dst]
    agg = jax.ops.segment_sum(alpha[:, :, None] * m_e, dst, num_segments=n).reshape(n, -1)
    o = _typed(jax.nn.gelu(agg, approximate=False), p["wa"], p["ba"], vtype, dot)
    beta = jax.nn.sigmoid(p["skip"][vtype])[:, None]
    return beta * o + (1 - beta) * h


def layer_flops(k: int, rows: list, edges: int, din: int, dout: int, model: dict) -> float:
    """Forward and backward operations of layer k (see ``flops.py``), one
    type's and one relation's weights per row and edge: the Q and output
    linears of the rows within K-1-k hops, the K and V linears of those
    within K-k hops, in layer 0 the adapter of those (whose input, the
    features, needs no gradient), and per edge the attention and message
    transforms, the attention score, its softmax and the weighted message."""
    K, D, H = model["num_layers"], model["hidden"], model["num_heads"]
    d = D // H
    out_rows, src_rows = rows[K - 1 - k], rows[K - k]
    dense = 3 * 2.0 * D * D * (2 * out_rows + 2 * src_rows)
    if k == 0:
        dense += 2 * 2.0 * src_rows * din * D
    per_edge = 2 * 2.0 * H * d * d + 2.0 * D + 5.0 * H + 2.0 * D
    return dense + 3 * edges * per_edge

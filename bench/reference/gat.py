"""Plain GAT (Velickovic et al., 2018) in jax.numpy, float32.

One layer with H heads of width d_h = hidden / H: ``z = h W`` split into
heads; per edge u -> v and head, ``e = leaky_relu(a_dst . z_v + a_src .
z_u, 0.2)``; a softmax of e over v's sampled in-edges; ``out_v =
elu(concat_heads sum_u alpha z_u)``.  As configured there is no self-loop
and no bias, so a vertex with no sampled in-edge reads 0.  Weights: for
layer k, ``k1, k2, k3 = split(fold_in(key, k), 3)``, ``W ~ N(0, 1/d_in)``
of shape ``[d_in, hidden]``, ``a_dst, a_src ~ N(0, 0.01)`` of shape
``[H, d_h]``; the classifier as in ``sage.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init(key, in_dim: int, model: dict, num_classes: int) -> dict:
    hidden, heads = model["hidden"], model["num_heads"]
    dims = [in_dim] + [hidden] * model["num_layers"]
    layers = []
    for k in range(model["num_layers"]):
        din = dims[k]
        k1, k2, k3 = jax.random.split(jax.random.fold_in(key, k), 3)
        layers.append(
            {
                "w": jax.random.normal(k1, (din, hidden)) * (1.0 / din) ** 0.5,
                "a_dst": jax.random.normal(k2, (heads, hidden // heads)) * 0.1,
                "a_src": jax.random.normal(k3, (heads, hidden // heads)) * 0.1,
            }
        )
    out = jax.random.normal(jax.random.fold_in(key, 999), (hidden, num_classes))
    return {"layers": layers, "out": out * (1.0 / hidden) ** 0.5}


def layer(p: dict, h, dst, src, n: int, dot, model: dict, *, vtype, etype):
    """As ``sage.layer``; untyped, so ``vtype`` and ``etype`` are not read."""
    heads = model["num_heads"]
    z = dot(h, p["w"]).reshape(n, heads, -1)
    s_dst = (z * p["a_dst"]).sum(-1)  # [n, H]
    s_src = (z * p["a_src"]).sum(-1)
    e = jax.nn.leaky_relu(s_dst[dst] + s_src[src], 0.2)  # [E, H]
    e_max = jax.ops.segment_max(e, dst, num_segments=n)
    w = jnp.exp(e - e_max[dst])
    denom = jax.ops.segment_sum(w, dst, num_segments=n)
    alpha = w / denom[dst]
    out = jax.ops.segment_sum(alpha[:, :, None] * z[src], dst, num_segments=n)
    return jax.nn.elu(out.reshape(n, -1))


def layer_flops(k: int, rows: list, edges: int, din: int, dout: int, model: dict) -> float:
    """Forward and backward operations of layer k (see ``flops.py``):
    ``z = h W`` for every source row, within K-k hops, the two attention
    scores per row, and per edge the logit, softmax and weighted message."""
    grads_in = 2 if k > 0 else 1  # weight gradient, plus the input's past layer 0
    src_rows = rows[model["num_layers"] - k]
    dense = 2.0 * src_rows * din * dout * (1 + grads_in)
    scores = 2.0 * 2 * src_rows * dout
    per_edge = 5.0 * model["num_heads"] + 2.0 * dout
    return dense + 3 * (scores + edges * per_edge)

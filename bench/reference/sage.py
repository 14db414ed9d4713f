"""Plain GraphSAGE-mean (Hamilton et al., 2017) in jax.numpy, float32.

One layer: ``relu(concat[h_v, mean_{u in N(v)} h_u] @ W + b)``, the mean
over the sampled in-edges of v (0 where v has none); every layer has the
ReLU, and the classifier is a bias-free linear map of the last layer.
Weights are drawn as the configuration states: for layer k from
``fold_in(key, k)``, ``W ~ N(0, 1/d_in)`` of shape ``[2 d_in, d_out]``,
``b = 0``; the classifier from ``fold_in(key, 999)``, ``N(0, 1/hidden)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init(key, in_dim: int, model: dict, num_classes: int) -> dict:
    dims = [in_dim] + [model["hidden"]] * model["num_layers"]
    layers = []
    for k in range(model["num_layers"]):
        din, dout = dims[k], dims[k + 1]
        w = jax.random.normal(jax.random.fold_in(key, k), (2 * din, dout))
        layers.append({"w": w * (1.0 / din) ** 0.5, "b": jnp.zeros((dout,))})
    out = jax.random.normal(jax.random.fold_in(key, 999), (model["hidden"], num_classes))
    return {"layers": layers, "out": out * (1.0 / model["hidden"]) ** 0.5}


def layer(p: dict, h, dst, src, n: int, dot, model: dict, *, vtype, etype):
    """``dst``/``src``: per edge, the row aggregated into and the row whose
    message it carries.  The model is untyped: ``vtype`` and ``etype`` are
    not read."""
    agg = jax.ops.segment_sum(h[src], dst, num_segments=n)
    cnt = jax.ops.segment_sum(jnp.ones(dst.shape, h.dtype), dst, num_segments=n)
    mean = agg / jnp.maximum(cnt, 1.0)[:, None]
    return jax.nn.relu(dot(jnp.concatenate([h, mean], axis=1), p["w"]) + p["b"])


def layer_flops(k: int, rows: list, edges: int, din: int, dout: int, model: dict) -> float:
    """Forward and backward operations of layer k (see ``flops.py``):
    the transform of the rows within K-1-k hops, and the mean over the
    layer's edges."""
    grads_in = 2 if k > 0 else 1  # weight gradient, plus the input's past layer 0
    out_rows = rows[model["num_layers"] - 1 - k]
    return 2.0 * out_rows * (2 * din) * dout * (1 + grads_in) + float(edges) * din * grads_in

"""The plain reference of a training cell's first steps.

It re-derives the weights from the run's key, follows the same seed
batches through its own model (``<kind>.py`` beside this file), its own
loss and its own AdamW, in float32 at the highest matmul precision, and
returns what the program's numbers are compared with.  It imports nothing
of the program.  The one thing it takes from the program is the sampled
neighbourhood of each batch, the sampler's random draw, and that it checks
first against the graph: every sampled edge is an edge of the graph, no
frontier vertex has more than its fanout or the same edge twice, and each
hop starts from vertices reached by the hops before it.

``dtype=bfloat16`` turns it into the control: all of it in bfloat16,
the weights, the optimizer's state and its update included.

A model kind's reference is ``<kind>.py`` beside this file, with
``init(key, in_dim, model, num_classes)`` and
``layer(p, h, dst, src, n, dot, model, *, vtype, etype)``.  Per layer it
is given, for each edge slot, the row that aggregates (``dst``) and the
row whose message it carries (``src``), and, for a model on a typed
graph, ``vtype``, the graph's vertex type of each row, and ``etype``, the
graph's type of each sampled edge (0 on padding).  The direction: the
sampler draws a frontier vertex's out-edges, so a sampled edge runs in the
graph from the frontier vertex (``dst`` here) to its neighbour (``src``
here), and the message flows against it, from the neighbour to the
frontier vertex.  ``etype`` is the type of that graph edge as it is
stored; a typed reference maps it to the relation of its message itself.
"""
from __future__ import annotations

import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

HERE = Path(__file__).resolve().parent


def model_module(kind: str):
    path = HERE / f"{kind}.py"
    if not path.is_file():
        raise ValueError(f"no reference for model kind {kind!r}")
    spec = importlib.util.spec_from_file_location(f"bench_ref_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class SampleError(ValueError):
    """A sampled neighbourhood that breaks the sampler's guarantees."""


def validate(sub, seeds: np.ndarray, graph, fanouts) -> None:
    if not np.array_equal(np.asarray(sub.seeds), np.unique(seeds)):
        raise SampleError("the sample's seeds are not the batch's seeds, sorted")
    if len(sub.hops) != len(fanouts):
        raise SampleError(f"{len(sub.hops)} hops for fanouts {list(fanouts)}")
    reached = np.asarray(seeds)
    for d, (hop, fan) in enumerate(zip(sub.hops, fanouts)):
        src, dst, eid = (np.asarray(a) for a in (hop.src, hop.dst, hop.eid))
        if eid.shape != src.shape or dst.shape != src.shape:
            raise SampleError(f"hop {d}: ragged edge arrays")
        if src.size:
            if eid.min() < 0 or eid.max() >= graph.num_edges:
                raise SampleError(f"hop {d}: edge id out of range")
            if not (np.array_equal(graph.src[eid], src) and np.array_equal(graph.dst[eid], dst)):
                raise SampleError(f"hop {d}: a sampled edge is not an edge of the graph")
            if not np.isin(src, reached).all():
                raise SampleError(f"hop {d}: an edge leaves a vertex not yet reached")
            if np.unique(src, return_counts=True)[1].max() > fan:
                raise SampleError(f"hop {d}: a vertex has more than {fan} sampled edges")
            if np.unique(eid).size != eid.size:
                raise SampleError(f"hop {d}: an edge is drawn twice")
        reached = np.union1d(reached, dst)


def block(sub, graph, num_layers: int, vpad: int, epad: int) -> dict:
    """The batch as plain arrays: rows of the sampled vertices, and for
    layer k the edges of hops 0..K-1-k, the ones that reach a seed in
    K-k steps (deeper edges cannot change a seed's output).  Padding edges
    point at one spare row that no real row reads."""
    verts = np.unique(np.concatenate([sub.seeds] + [h.src for h in sub.hops] + [h.dst for h in sub.hops]))
    if verts.size >= vpad:
        raise SampleError(f"{verts.size} vertices do not fit the {vpad} rows of the reference")
    feats = np.zeros((vpad, graph.vertex_feats.shape[1]), np.float32)
    feats[: verts.size] = graph.vertex_feats[verts]
    vtype = np.zeros(vpad, np.int32)
    vtype[: verts.size] = graph.vertex_types[verts]
    out = {
        "feats": feats,
        "vtype": vtype,
        "seed_pos": np.searchsorted(verts, sub.seeds).astype(np.int32),
        "labels": graph.labels[sub.seeds].astype(np.int32),
        "dst": [],
        "src": [],
        "etype": [],
    }
    for k in range(num_layers):
        hops = sub.hops[: num_layers - k]
        s = np.concatenate([h.src for h in hops])
        t = np.concatenate([h.dst for h in hops])
        if s.size > epad:
            raise SampleError(f"{s.size} edges do not fit the {epad} slots of the reference")
        dst = np.full(epad, vpad - 1, np.int32)
        src = np.full(epad, vpad - 1, np.int32)
        etype = np.zeros(epad, np.int32)
        dst[: s.size] = np.searchsorted(verts, s)  # the frontier vertex aggregates
        src[: s.size] = np.searchsorted(verts, t)  # its sampled neighbour's message
        etype[: s.size] = graph.edge_types[np.concatenate([h.eid for h in hops])]
        out["dst"].append(dst)
        out["src"].append(src)
        out["etype"].append(etype)
    return out


def merge(blocks: list) -> dict:
    """Blocks side by side as one: the rows of block j follow those of the
    blocks before it, its edges point at its own rows, and its seeds and
    labels follow theirs, so the mean loss over the merged seeds is the
    mean of the blocks' mean losses where they hold as many seeds each."""
    offsets = np.cumsum([0] + [b["feats"].shape[0] for b in blocks[:-1]]).astype(np.int32)

    def side_by_side(parts, shift: bool):
        return np.concatenate([x + o if shift else x for x, o in zip(parts, offsets)])

    layers = range(len(blocks[0]["dst"]))
    out = {key: side_by_side([b[key] for b in blocks], key == "seed_pos")
           for key in ("feats", "vtype", "seed_pos", "labels")}
    for key in ("dst", "src", "etype"):
        out[key] = [side_by_side([b[key][k] for b in blocks], key != "etype") for k in layers]
    return out


def lr_at(opt: dict, t):
    """The configured schedule: linear warm-up over ``warmup_steps`` (the
    t-th update, counted from 1, takes (t+1)/warmup_steps of the rate), then
    cosine decay to ``min_lr_frac`` of it by ``total_steps``."""
    warm = jnp.minimum(1.0, (t + 1) / max(1, opt["warmup_steps"]))
    prog = jnp.clip(
        (t - opt["warmup_steps"]) / max(1, opt["total_steps"] - opt["warmup_steps"]), 0.0, 1.0
    )
    decay = opt["min_lr_frac"] + (1 - opt["min_lr_frac"]) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return opt["lr"] * warm * decay


def make_step(kind: str, model: dict, opt: dict, dtype, *, half_batch: bool = False):
    """One AdamW step of the reference (decoupled weight decay, gradients
    clipped to a global norm first), jitted, in ``dtype`` throughout.
    ``half_batch`` is a fault: the loss takes the mean over the first half
    of the seeds only."""
    mod = model_module(kind)
    precision = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None

    def dot(a, b):
        return jnp.dot(a, b, precision=precision)

    def loss_fn(params, blk):
        h = blk["feats"].astype(dtype)
        n = h.shape[0]
        for k, pk in enumerate(params["layers"]):
            h = mod.layer(
                pk, h, blk["dst"][k], blk["src"][k], n, dot, model,
                vtype=blk["vtype"], etype=blk["etype"][k],
            )
        logits = dot(h[blk["seed_pos"]], params["out"])
        labels = blk["labels"]
        if half_batch:
            logits, labels = logits[: logits.shape[0] // 2], labels[: labels.shape[0] // 2]
        logz = jax.nn.logsumexp(logits, axis=-1)
        return jnp.mean(logz - jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0])

    def step(params, state, blk):
        loss, grads = jax.value_and_grad(loss_fn)(params, blk)
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9)).astype(dtype)
        grads = jax.tree.map(lambda g: g * scale, grads)
        t = state["t"] + 1
        tf = t.astype(jnp.float32)
        lr = lr_at(opt, tf).astype(dtype)
        b1, b2 = opt["b1"], opt["b2"]
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)
        c1 = (1 - b1**tf).astype(dtype)
        c2 = (1 - b2**tf).astype(dtype)
        params = jax.tree.map(
            lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + opt["eps"]) + opt["weight_decay"] * p),
            params, m, v,
        )
        return params, {"m": m, "v": v, "t": t}, loss, grads

    return jax.jit(step)


def run(kind: str, model: dict, opt: dict, key_seed: int, in_dim: int, num_classes: int,
        blocks: list, dtype=jnp.float32, half_batch: bool = False) -> dict:
    """The reference's losses of the given batches, its clipped gradient of
    the first step, and its parameters before and after them (host arrays)."""
    params = model_module(kind).init(jax.random.PRNGKey(key_seed), in_dim, model, num_classes)
    params = jax.tree.map(lambda x: x.astype(dtype), params)
    start = jax.device_get(params)
    zeros = jax.tree.map(jnp.zeros_like, params)
    state = {"m": zeros, "v": zeros, "t": jnp.zeros((), jnp.int32)}
    step = make_step(kind, model, opt, dtype, half_batch=half_batch)
    losses, first_grad = [], None
    for blk in blocks:
        params, state, loss, grads = step(params, state, jax.device_put(blk))
        losses.append(float(loss))
        if first_grad is None:
            first_grad = jax.device_get(grads)
    return {"losses": losses, "grad": first_grad, "start": start, "end": jax.device_get(params)}


def _leaf_norms(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(np.linalg.norm(np.asarray(x, np.float64))) for p, x in flat}


def compare(prog: dict, ref: dict) -> dict:
    """The compared numbers, each a gap relative to the reference.

    ``loss_gap``: the largest |program - reference| / |reference| over the
    steps.  ``grad_gap``: by the worst leaf, the gap between the program's
    and the reference's norm of the first clipped gradient, over the
    reference's norm of that leaf or of the median leaf, whichever is
    larger.  ``change_gap``: the same for the change of the parameters over
    the steps, leaving out leaves whose reference gradient is under a
    thousandth of the median leaf's (those move by round-off alone); they
    are listed under ``left_out``."""
    lp, lr = np.asarray(prog["losses"], np.float64), np.asarray(ref["losses"], np.float64)
    if lp.shape != lr.shape or not np.all(np.isfinite(lp)):
        loss_gap = math.inf
    else:
        loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    g_ref, g_prog = _leaf_norms(ref["grad"]), _leaf_norms(prog["grad"])
    med = float(np.median(list(g_ref.values())))

    def gap(a: dict, b: dict, keep) -> float:
        scale = float(np.median([b[k] for k in keep]))
        worst = max(abs(a[k] - b[k]) / max(b[k], scale) for k in keep)
        return worst if np.isfinite(worst) else math.inf

    moved = [k for k in g_ref if g_ref[k] >= 1e-3 * med]
    delta = lambda r: jax.tree.map(lambda e, s: np.asarray(e, np.float64) - np.asarray(s, np.float64), r["end"], r["start"])
    return {
        "loss_gap": loss_gap,
        "grad_gap": gap(g_prog, g_ref, list(g_ref)),
        "change_gap": gap(_leaf_norms(delta(prog)), _leaf_norms(delta(ref)), moved),
        # the leaves change_gap leaves out, each with its reference
        # gradient's norm over the median leaf's
        "left_out": {k: g_ref[k] / med for k in g_ref if k not in moved},
    }

"""RelNet-style KGE training (paper §IV-D): a 2-layer HGT encoder over the
GLISP sampling service + feed-forward link-prediction decoder, trained on
positive edges with head/tail-corrupted negatives — the paper's large-scale
scalability workload at laptop scale.

    PYTHONPATH=src python examples/kge_relnet.py --steps 60
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.partition import adadne
from repro.core.sampling import GatherApplyClient, SamplingServer, VertexRouter
from repro.graph import build_partitions, named_dataset
from repro.models.gnn import GNNModel, subgraph_to_batch
from repro.train.optim import AdamWConfig, adamw_init, adamw_update
from repro.launch.compile_cache import setup_compile_cache

setup_compile_cache()

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=60)
ap.add_argument("--batch-edges", type=int, default=128)
ap.add_argument("--hidden", type=int, default=128)
ap.add_argument("--scale", type=float, default=0.08)
args = ap.parse_args()

g = named_dataset("relnet", feat_dim=64, scale=args.scale)
P = 8
print(f"relnet stand-in: {g.num_vertices} vertices, {g.num_edges} edges, {P} partitions")
ep = adadne(g, P, seed=0)
parts = build_partitions(g, ep, P)
client = GatherApplyClient(
    [SamplingServer(p, seed=0) for p in parts], VertexRouter(g, ep, P), seed=0
)

# encoder: 2-layer HGT (paper: hidden 128); decoder: 2-layer FFN on [h_u, h_v]
enc = GNNModel("hgt", 64, hidden=args.hidden, num_layers=2,
               num_classes=args.hidden, num_etypes=g.num_edge_types)
key = jax.random.PRNGKey(0)
params = {
    "enc": enc.init(key),
    "dec": {
        "w1": jax.random.normal(key, (2 * args.hidden, args.hidden)) * 0.05,
        "w2": jax.random.normal(jax.random.fold_in(key, 1), (args.hidden, 1)) * 0.05,
    },
}
opt_state = adamw_init(params)
opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=5, total_steps=args.steps)


def score(dec, hu, hv):
    z = jnp.concatenate([hu, hv], axis=-1)
    return (jax.nn.gelu(z @ dec["w1"]) @ dec["w2"])[:, 0]


def loss_fn(params, batch, pos_u, pos_v, neg_u, neg_v):
    h = enc.apply({"layers": params["enc"]["layers"], "out": params["enc"]["out"]}, batch)
    s_pos = score(params["dec"], h[pos_u], h[pos_v])
    s_neg = score(params["dec"], h[neg_u], h[neg_v])
    # logistic link-prediction loss
    return -(jax.nn.log_sigmoid(s_pos).mean() + jax.nn.log_sigmoid(-s_neg).mean())


@jax.jit
def train_step(params, opt_state, batch, pu, pv, nu, nv):
    loss, grads = jax.value_and_grad(loss_fn)(params, batch, pu, pv, nu, nv)
    params, opt_state, _ = adamw_update(params, grads, opt_state, opt_cfg)
    return params, opt_state, loss


def etype_lookup(src, dst):
    return ((g.vertex_types[src] * 7 + g.vertex_types[dst] * 3) % g.num_edge_types)


rng = np.random.default_rng(0)
t0 = time.perf_counter()
losses = []
for step in range(args.steps):
    eidx = rng.choice(g.num_edges, args.batch_edges, replace=False)
    pos = np.stack([g.src[eidx], g.dst[eidx]], 1)
    # negatives: corrupt head or tail with a random vertex
    neg = pos.copy()
    corrupt_head = rng.random(args.batch_edges) < 0.5
    rand_v = rng.integers(0, g.num_vertices, args.batch_edges)
    neg[corrupt_head, 0] = rand_v[corrupt_head]
    neg[~corrupt_head, 1] = rand_v[~corrupt_head]
    seeds = np.unique(np.concatenate([pos.reshape(-1), neg.reshape(-1)]))
    sub = client.sample_khop(seeds, [10, 5], direction="out")
    batch = subgraph_to_batch(sub, g.vertex_feats, None, 2,
                              edge_types_lookup=etype_lookup)
    verts = sub.all_vertices()
    # hgt returns per-seed outputs; we need full-table embeddings -> use
    # seed_pos covering every vertex we score
    lookup = {int(v): i for i, v in enumerate(verts)}
    batch.seed_pos = np.searchsorted(verts, np.arange(len(verts))[: 1]).astype(np.int32)
    bj = jax.tree.map(jnp.asarray, batch)
    # positions of scored endpoints in the padded table
    pu = jnp.asarray(np.searchsorted(verts, pos[:, 0]))
    pv = jnp.asarray(np.searchsorted(verts, pos[:, 1]))
    nu = jnp.asarray(np.searchsorted(verts, neg[:, 0]))
    nv = jnp.asarray(np.searchsorted(verts, neg[:, 1]))

    # encoder applied over the full table: reuse apply but take hidden states
    def full_loss(params):
        h = bj.feats
        for k in range(enc.num_layers):
            h = enc.layer(params["enc"]["layers"][k], k, h,
                          bj.layer_dst[k], bj.layer_src[k], bj.layer_etype[k])
            h = h * bj.valid[:, None]
        s_pos = score(params["dec"], h[pu], h[pv])
        s_neg = score(params["dec"], h[nu], h[nv])
        return -(jax.nn.log_sigmoid(s_pos).mean()
                 + jax.nn.log_sigmoid(-s_neg).mean())

    loss, grads = jax.value_and_grad(full_loss)(params)
    params, opt_state, _ = adamw_update(params, grads, opt_state, opt_cfg)
    losses.append(float(loss))
    if step % 10 == 0:
        print(f"step {step:3d} loss {losses[-1]:.4f}")

dt = time.perf_counter() - t0
print(f"\n{args.steps} steps in {dt:.1f}s ({args.steps/dt:.2f} steps/s)")
print(f"loss {losses[0]:.4f} -> {np.mean(losses[-5:]):.4f}")
wl = client.server_workloads()
print(f"sampling server balance max/min: {wl.max()/wl.min():.3f}")
assert np.mean(losses[-5:]) < losses[0], "KGE loss must decrease"
print("OK")

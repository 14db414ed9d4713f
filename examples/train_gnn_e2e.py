"""End-to-end driver: train a 3-layer GraphSAGE (~100M-parameter-class
pipeline at configurable scale) for a few hundred steps on the GLISP stack,
with checkpointing and workload-balance reporting.  The full system is
assembled by the facade; ``--prefetch`` controls the background sampling
depth (0 = serial sample-then-step).

    PYTHONPATH=src python examples/train_gnn_e2e.py --steps 200
"""
import argparse
import time

import numpy as np

from repro.api import GLISPConfig, GLISPSystem
from repro.graph import named_dataset
from repro.models.gnn import GNNModel
from repro.train import save_checkpoint
from repro.train.optim import AdamWConfig
from repro.launch.compile_cache import setup_compile_cache

setup_compile_cache()

ap = argparse.ArgumentParser()
ap.add_argument("--dataset", default="ogbn-paper")
ap.add_argument("--scale", type=float, default=0.2)
ap.add_argument("--parts", type=int, default=8)
ap.add_argument("--steps", type=int, default=200)
ap.add_argument("--batch", type=int, default=256)
ap.add_argument("--hidden", type=int, default=256)
ap.add_argument("--prefetch", type=int, default=2)
ap.add_argument("--partitioner", default="adadne")
ap.add_argument("--ckpt", default="/tmp/glisp_sage.npz")
args = ap.parse_args()

g = named_dataset(args.dataset, feat_dim=64, num_classes=0, scale=args.scale)
g.labels = (g.vertex_types % 4).astype(np.int32)
g.vertex_feats[:, :4] = 0
g.vertex_feats[np.arange(g.num_vertices), g.labels] += 2.0
print(f"{args.dataset}: {g.num_vertices} vertices {g.num_edges} edges")

system = GLISPSystem.build(g, GLISPConfig(
    num_parts=args.parts,
    partitioner=args.partitioner,
    fanouts=(15, 10, 5),
    batch_size=args.batch,
    prefetch=args.prefetch,
))
model = GNNModel("sage", 64, hidden=args.hidden, num_layers=3, num_classes=4)
ids = np.arange(g.num_vertices)
n_train = int(0.8 * len(ids))
epochs = max(1, args.steps * args.batch // n_train)
t0 = time.perf_counter()
trainer = system.train(
    model, ids[:n_train], epochs=epochs, log_every=10,
    opt=AdamWConfig(lr=3e-3, warmup_steps=10, total_steps=args.steps),
)
dt = time.perf_counter() - t0
log = trainer.log
acc = trainer.evaluate(ids[n_train:])
wl = system.server_workloads()
print(f"steps={len(log.steps)*10} wall={dt:.1f}s "
      f"(sample {log.sample_time:.1f}s / compute {log.compute_time:.1f}s, "
      f"prefetch={args.prefetch})")
print(f"loss {log.losses[0]:.3f} -> {log.losses[-1]:.3f} | test acc {acc:.3f}")
print(f"server workload balance (max/min): {wl.max()/wl.min():.3f}")
save_checkpoint(args.ckpt, {"params": trainer.params}, step=args.steps)
print("checkpoint ->", args.ckpt)

"""Online GNN serving end-to-end: ``GLISPSystem.server()`` under Zipf load.

    PYTHONPATH=src python examples/serve_gnn.py
    PYTHONPATH=src python examples/serve_gnn.py --requests 200 --window 16

Builds the system, runs layerwise inference once (the offline artifact),
then drives the serving tier with a Zipf-popularity client: continuous
batching into the engine's compiled shape buckets, printed P50/P99, and a
degraded-response demo (a fault plan that drops sampling replicas — the
server answers with ``degraded=True`` instead of failing).
"""
import argparse
import tempfile

import jax
import numpy as np

from repro.api import FaultPlan, FaultSpec, GLISPConfig, GLISPSystem, RetryPolicy
from repro.graph import power_law_graph
from repro.models.gnn import GNNModel
from repro.launch.compile_cache import setup_compile_cache

setup_compile_cache()

ap = argparse.ArgumentParser()
ap.add_argument("--vertices", type=int, default=3000)
ap.add_argument("--requests", type=int, default=100)
ap.add_argument("--window", type=int, default=8, help="in-flight requests")
ap.add_argument("--zipf", type=float, default=1.3)
args = ap.parse_args()

FEAT, HIDDEN, LAYERS = 16, 32, 2

print("== build + offline layerwise inference ==")
g = power_law_graph(args.vertices, avg_degree=8, seed=7, feat_dim=FEAT, num_classes=4)
system = GLISPSystem.build(g, GLISPConfig(num_parts=4, fanouts=(10, 5), seed=0))
model = GNNModel("sage", FEAT, hidden=HIDDEN, num_layers=LAYERS)
params = model.init(jax.random.PRNGKey(0))
fns = [model.embed_layer_fn(params, k) for k in range(LAYERS)]
workdir = tempfile.mkdtemp(prefix="serve_gnn_")
system.infer_layerwise(fns, workdir, out_dims=[HIDDEN, HIDDEN])
print(f"   embeddings on disk under {workdir}")

print("== online serving: Zipf traffic, continuous batching ==")
server = system.server(queue_depth=args.window, max_batch_delay_ms=0.0,
                       deadline_ms=None)
rng = np.random.default_rng(0)
ranks = np.arange(1, g.num_vertices + 1, dtype=np.float64) ** -args.zipf
popularity = ranks / ranks.sum()
requests = [
    np.unique(rng.choice(g.num_vertices, size=rng.integers(1, 9), p=popularity))
    for _ in range(args.requests)
]

inflight, nxt, done = [], 0, 0
while done < len(requests):
    while nxt < len(requests) and len(inflight) < args.window:
        inflight.append(server.submit(requests[nxt]))
        nxt += 1
    server.step(force=True)
    for rid in list(inflight):
        resp = server.response(rid)
        if resp is not None:
            assert resp.status == "ok" and resp.embeddings.shape[1] == HIDDEN
            inflight.remove(rid)
            done += 1

snap = server.stats.snapshot()
lat = snap["latency"]
print(f"   {snap['completed']} responses, {snap['batches']} batches "
      f"(mean {server.stats.mean_batch_requests():.1f} requests/batch)")
print(f"   P50 {lat['p50_ms']:.2f} ms   P99 {lat['p99_ms']:.2f} ms")
print(f"   bucket occupancy {snap['occupancy']:.2f}  "
      f"cache hits {snap['cache_hit_ratios']}")

print("== degraded responses under a fault plan ==")
faulty = GLISPSystem.build(
    g,
    GLISPConfig(
        num_parts=4,
        fanouts=(10, 5),
        seed=0,
        # every sampling replica drops gathers often enough that some
        # dispatches exhaust their retries -> partial (degraded) samples
        fault_plan=FaultPlan(seed=3, sites=(("server.*", FaultSpec(p=0.9)),)),
        retry_policy=RetryPolicy(max_attempts=1),
    ),
)
faulty.infer_layerwise(fns, tempfile.mkdtemp(prefix="serve_gnn_deg_"),
                       out_dims=[HIDDEN, HIDDEN])
deg_server = faulty.server(deadline_ms=None)
degraded = 0
for verts in requests[:20]:
    resp = deg_server.call(verts)
    assert resp.status == "ok"  # degraded, not dead: embeddings still come back
    degraded += resp.degraded
print(f"   {degraded}/20 responses flagged degraded=True "
      f"(partial sampling, explicit — never silent)")
print("done.")

"""Quickstart: the whole GLISP pipeline on a synthetic power-law graph,
driven entirely through the unified facade (repro.api).

    PYTHONPATH=src python examples/quickstart.py

1. generate a power-law graph
2. GLISPSystem.build — AdaDNE vertex-cut partitioning + Gather-Apply
   sampling service, all resolved by registry name from GLISPConfig
3. sample K-hop subgraphs through the async request-plan service
   (submit -> SampleTicket -> result), with in-flight requests
   overlapping hop levels on the shared SamplingService
4. train GraphSAGE with the prefetching batch pipeline (host sampling
   overlaps the jit'd train step; the pipeline keeps `inflight` sample
   requests riding on the service at once)
5. run layerwise full-graph inference with the two-level cache + PDS
6. lint the library with the glint static analyzer (repro.analysis) —
   the same determinism/JAX-hygiene gate CI runs
7. chaos: rebuild the system with replicated servers and a deterministic
   fault plan knocking primaries over — retries and failovers redraw from
   the same keyed RNG, so the sampled subgraph is bit-identical
8. online serving: GLISPSystem.server() batches live "embed these
   vertices" requests into the engine's compiled shape buckets, with
   bounded admission, deadlines and P50/P99 SLO metrics
"""
import tempfile
import time

import numpy as np

from repro.api import GLISPConfig, GLISPSystem, SamplingSpec
from repro.graph import power_law_graph
from repro.models.gnn import GNNModel
from repro.train.optim import AdamWConfig
from repro.launch.compile_cache import setup_compile_cache

setup_compile_cache()

print("== 1. generate graph ==")
g = power_law_graph(8000, avg_degree=10, seed=0, feat_dim=32, num_classes=0)
g.labels = g.vertex_types.astype(np.int32)
g.vertex_feats[:, :3] = 0
g.vertex_feats[np.arange(g.num_vertices), g.labels] += 2.0
print(f"   {g.num_vertices} vertices, {g.num_edges} edges, "
      f"max degree {int((g.out_degrees()+g.in_degrees()).max())}")

print("== 2. build the GLISP system ==")
config = GLISPConfig(
    num_parts=4,
    partitioner="adadne",
    sampler="gather_apply",
    fanouts=(10, 5),
    batch_size=256,
    prefetch=2,          # background sampling overlaps the train step
    reorder="pds",
    cache_policy="fifo",
)
t0 = time.perf_counter()
system = GLISPSystem.build(g, config)
m = system.partition_metrics()
print(f"   RF={m['RF']:.3f} VB={m['VB']:.3f} EB={m['EB']:.3f} "
      f"({time.perf_counter()-t0:.2f}s)")

print("== 3. sample through the async request-plan service ==")
# blocking convenience: submit-and-wait in one call
sub = system.sample(np.arange(64), fanouts=[15, 10, 5])
print(f"   3-hop sample of 64 seeds: {sub.num_edges} edges, "
      f"{sub.all_vertices().shape[0]} vertices")
# the ticket API: several requests ride in flight on the one service;
# the scheduler overlaps their hops and coalesces shared frontier seeds,
# and per-request RNG keys keep every result bit-reproducible
spec = SamplingSpec(fanouts=(15, 10, 5))
tickets = [
    system.submit(np.arange(lo, lo + 64), spec, key=(lo,))
    for lo in (0, 64, 128)
]
print(f"   {system.service.inflight()} requests in flight ...")
subs = [t.result() for t in tickets]
stats = system.service.stats()
print(f"   {sum(s.num_edges for s in subs)} edges over {len(subs)} tickets | "
      f"service stats: {stats.requests} dispatches, "
      f"{stats.seeds} seeds, {stats.edges_returned} edges returned")

print("== 4. train GraphSAGE (prefetching pipeline) ==")
ids = np.arange(g.num_vertices)
model = GNNModel("sage", 32, hidden=64, num_layers=2, num_classes=3)
trainer = system.train(
    model, ids[:6000], epochs=2,
    opt=AdamWConfig(lr=3e-3, warmup_steps=3, total_steps=200),
)
log = trainer.log
acc = trainer.evaluate(ids[6000:])
print(f"   loss {log.losses[0]:.3f} -> {log.losses[-1]:.3f}, test acc {acc:.3f}")
print(f"   host sampling {log.sample_time:.1f}s overlapped with "
      f"device compute {log.compute_time:.1f}s")

print("== 5. layerwise full-graph inference ==")
layer_fns = [model.embed_layer_fn(trainer.params, k) for k in range(2)]
with tempfile.TemporaryDirectory() as td:
    t0 = time.perf_counter()
    res = system.infer_layerwise(
        layer_fns, td, fanouts=[10, 5], chunk_rows=1024, out_dims=[64, 64]
    )
    dt = time.perf_counter() - t0
print(f"   embeddings for all {g.num_vertices} vertices in {dt:.1f}s | "
      f"chunk reads {res.total_chunk_reads()} | "
      f"dynamic hit ratio {res.dynamic_hit_ratio():.2%}")

print("== 6. static analysis (glint) ==")
# The conventions everything above relies on — keyed randomness, stable
# iteration orders, pure-jnp jit bodies, bucketed pad shapes — are
# machine-checked by repro.analysis.  `run_checks` is the library entry
# point behind `python -m repro.analysis src tests benchmarks examples`
# (the CI gate); here we lint the analyzer's own package so the demo works
# from any working directory.
import os

import repro.analysis
from repro.analysis import run_checks

report = run_checks([os.path.dirname(repro.analysis.__file__)])
print(f"   {report.files_checked} files, {len(report.rule_ids)} rules -> "
      f"{len(report.findings)} findings, {len(report.suppressed)} suppressed")
assert report.ok, "\n".join(f.render() for f in report.findings)

print("== 7. chaos: failover without changing a single sample ==")
# Two replicas per partition; a deterministic fault plan takes every
# primary (replica 0) down in bursts.  Dispatch RNG is keyed by
# (request, hop, partition) — not by attempt or replica — so the rerouted
# run redraws the exact same neighbors the clean run drew.
from repro.api import FaultPlan, FaultSpec, RetryPolicy

chaos_cfg = GLISPConfig(
    num_parts=4,
    fanouts=(10, 5),
    server_replicas=2,
    fault_plan=FaultPlan(
        seed=13, sites=(("server.*.0", FaultSpec(p=0.5, burst=4, limit=8)),)
    ),
    retry_policy=RetryPolicy(max_attempts=4, base_delay_s=0.0),
    ticket_timeout=30.0,
)
chaotic = GLISPSystem.build(g, chaos_cfg)
spec = SamplingSpec(fanouts=(10, 5))
clean_sub = system.submit(np.arange(64), spec, key=(0xC4A05,)).result(timeout=30.0)
chaos_sub = chaotic.submit(np.arange(64), spec, key=(0xC4A05,)).result(timeout=30.0)
cstats = chaotic.service.stats()
identical = all(
    np.array_equal(a.src, b.src) and np.array_equal(a.dst, b.dst)
    for a, b in zip(clean_sub.hops, chaos_sub.hops)
)
assert identical and not chaos_sub.degraded
health = chaotic.server_health()
print(f"   {cstats.retries} retries, {cstats.failovers} failovers, "
      f"{sum(1 for s in health.values() if s != 'up')} replicas "
      f"quarantined -> subgraph bit-identical: {identical}")

print("== 8. online serving over the inference artifact ==")
# Serving recomputes only the final layer per request, so it needs the
# layerwise stores on disk — rerun inference into a directory that
# outlives this block (section 5's TemporaryDirectory is already gone).
serve_dir = tempfile.mkdtemp(prefix="quickstart_serve_")
system.infer_layerwise(
    layer_fns, serve_dir, fanouts=[10, 5], chunk_rows=1024, out_dims=[64, 64]
)
server = system.server(max_batch_delay_ms=0.0)
rng = np.random.default_rng(0)
rids = [
    server.submit(rng.choice(g.num_vertices, size=5, replace=False))
    for _ in range(12)
]
server.drain()  # continuous batching: several requests per compiled slice
responses = [server.response(r) for r in rids]
assert all(r.status == "ok" for r in responses)
snap = server.stats.snapshot()
print(f"   {snap['completed']} responses in {snap['batches']} batches "
      f"({responses[0].embeddings.shape[1]}-dim rows) | "
      f"P50 {snap['latency']['p50_ms']:.1f} ms "
      f"P99 {snap['latency']['p99_ms']:.1f} ms | "
      f"bucket occupancy {snap['occupancy']:.2f}")

print("== 9. distributed sampling workers + data-parallel training ==")
# dist_transport="mp" forks one worker process per partition; each owns
# that partition's sampling servers and answers framed dispatches over a
# pipe (dist_transport="socket" runs the same frames over a socketpair).
# Dispatch RNG is keyed by (request, hop, partition) — never by which
# process answers — so the remote system redraws exactly the sample its
# in-process twin draws.
twin_cfg = dict(num_parts=2, fanouts=(10, 5), seed=3)
inproc = GLISPSystem.build(g, GLISPConfig(**twin_cfg))
dist_system = GLISPSystem.build(g, GLISPConfig(dist_transport="mp", **twin_cfg))
local_sub = inproc.submit(np.arange(64), spec, key=(0xD157,)).result(timeout=30.0)
remote_sub = dist_system.submit(np.arange(64), spec, key=(0xD157,)).result(
    timeout=30.0
)
assert all(
    np.array_equal(a.src, b.src)
    and np.array_equal(a.dst, b.dst)
    and np.array_equal(a.eid, b.eid)
    for a, b in zip(local_sub.hops, remote_sub.hops)
)
workers_up = sum(
    1 for k, v in dist_system.server_health().items()
    if k.startswith("worker.") and v == "up"
)
print(f"   {workers_up} worker processes up -> remote sample bit-identical "
      f"to in-process: True")

# the data-parallel trainer shards the train step over the mesh's data
# axis (one sampling client per shard, params replicated); with one host
# device this is a 1-shard mesh — benchmarks/distributed.py forces 4 CPU
# devices via XLA_FLAGS and sweeps 1/2/4 shards.  reference=True runs an
# unsharded twin step on the same stacked batches for an equivalence check.
dp = dist_system.dp_trainer(model, np.arange(256), batch_size=32, reference=True)
dp_log = dp.train(epochs=1, log_every=1, max_steps=4)
assert np.allclose(dp_log.losses, dp_log.ref_losses, rtol=1e-5)
print(f"   {dp.num_shards}-shard dp loss {dp_log.losses[0]:.3f} -> "
      f"{dp_log.losses[-1]:.3f} (matches single-device reference)")
dist_system.close()  # joins the forked workers (bounded, then escalates)
print("done.")

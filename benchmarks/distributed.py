"""Distributed-tier benchmark: forked sampling workers + data parallelism.

Phase 1 sweeps the worker count (1/2/4 forked sampling-server processes,
``dist_transport="mp"``) over a fixed request workload and reports
sampling throughput plus the client-observed dispatch-latency
distribution (P50/P95).  Every remote configuration is checked
bit-identical, request by request, against its in-process twin — the
transport must change WHERE sampling runs, never what it returns.

Phase 2 runs the data-parallel trainer over the remote backend on a
host-device mesh (1/2/4 data shards), reporting step throughput.  The
sharded step is checked against the unsharded single-device reference
step on the same stacked batches (``reference=True``): losses must agree
to float tolerance.

End-of-run asserts, per ISSUE 9:

- every worker count answered bit-identically to in-process sampling;
- the dp train-step losses match the single-device reference.

Results land in ``BENCH_distributed.json`` (``--out``); ``--smoke``
shrinks the workload for CI but keeps the full 1/2/4 sweeps.  Phase 2
sweeps the shard counts that fit the devices JAX sees: on a CPU host, set
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` outside the script
to get four.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from benchmarks.common import dataset, emit, glisp_system
from repro.api import GLISPConfig, GLISPSystem
from repro.core.sampling.service import SampleRequest, SamplingSpec

RESULTS: dict = {}

FANOUTS = (15, 10)
WORKER_SWEEP = (1, 2, 4)
SHARD_SWEEP = (1, 2, 4)


def _emit(name: str, value: float) -> None:
    RESULTS[name] = float(value)
    emit(name, value)


def _flag(name: str, ok: bool) -> None:
    RESULTS[name] = bool(ok)
    emit(name, 1.0 if ok else 0.0)


def _remote_system(g, parts: int, **overrides) -> GLISPSystem:
    """A fresh forked-worker system per call — deliberately NOT the shared
    ``glisp_system`` cache, since this benchmark closes its pools."""
    return GLISPSystem.build(
        g,
        GLISPConfig(
            num_parts=parts,
            partitioner="adadne",
            sampler="gather_apply",
            seed=0,
            dist_transport="mp",
            **overrides,
        ),
    )


def _requests(g, n: int, seeds_per: int):
    rng = np.random.default_rng(42)
    spec = SamplingSpec(fanouts=FANOUTS)
    return [
        SampleRequest(
            seeds=rng.choice(g.num_vertices, size=seeds_per, replace=False),
            spec=spec,
            key=(0xD15B, i),
        )
        for i in range(n)
    ]


def _drive(system, requests) -> tuple[list, float]:
    subs, t0 = [], time.perf_counter()
    for req in requests:
        subs.append(system.backend.submit(req).result(timeout=120.0))
    return subs, time.perf_counter() - t0


def _same_sub(a, b) -> bool:
    if len(a.hops) != len(b.hops) or a.degraded != b.degraded:
        return False
    return all(
        np.array_equal(ha.src, hb.src)
        and np.array_equal(ha.dst, hb.dst)
        and np.array_equal(ha.eid, hb.eid)
        for ha, hb in zip(a.hops, b.hops)
    )


def bench_workers(g, requests) -> None:
    p50s, modeled = {}, {}
    for workers in WORKER_SWEEP:
        local = glisp_system(g, workers)
        baseline, _ = _drive(local, requests)
        remote = _remote_system(g, workers)
        try:
            _drive(remote, requests[: max(2, len(requests) // 8)])  # warmup
            remote.backend.service.dispatcher.drain_latencies()
            remote.reset_stats()
            subs, secs = _drive(remote, requests)
            lat = remote.backend.service.dispatcher.drain_latencies()
            stats = remote.backend.stats()
            tag = f"workers{workers}"
            p50s[workers] = float(np.percentile(lat, 50))
            modeled[workers] = stats.modeled_parallel_work
            _emit(f"{tag}/throughput_req_s", len(requests) / secs)
            _emit(f"{tag}/dispatches", len(lat))
            _emit(f"{tag}/dispatch_p50_ms", p50s[workers])
            _emit(f"{tag}/dispatch_p95_ms", float(np.percentile(lat, 95)))
            _emit(f"{tag}/modeled_parallel_work", stats.modeled_parallel_work)
            _emit(f"{tag}/modeled_total_work", stats.modeled_total_work)
            _flag(
                f"{tag}/bit_identical_vs_inproc",
                all(_same_sub(a, b) for a, b in zip(baseline, subs)),
            )
        finally:
            remote.close()
    # gather-style sampling replicates per-seed work onto every partition
    # holding one of the seed's edges, so the achievable dispatch speedup
    # is P/RF, not P — the work model (modeled_parallel_work, the per-round
    # MAX across servers) predicts it and the measured per-dispatch
    # latency should track that prediction
    base = WORKER_SWEEP[0]
    for workers in WORKER_SWEEP[1:]:
        tag = f"workers{workers}"
        _emit(
            f"{tag}/modeled_dispatch_speedup",
            modeled[base] / modeled[workers] if modeled[workers] else 0.0,
        )
        _emit(
            f"{tag}/measured_dispatch_speedup",
            p50s[base] / p50s[workers] if p50s[workers] else 0.0,
        )


def _shard_sweep() -> tuple:
    return tuple(s for s in SHARD_SWEEP if s <= len(jax.devices()))


def bench_data_parallel(g, steps: int) -> None:
    from repro.launch.mesh import make_local_mesh
    from repro.models.gnn.models import GNNModel

    system = _remote_system(g, 2)
    try:
        ids = np.arange(min(4096, g.num_vertices), dtype=np.int64)
        for shards in _shard_sweep():
            model = GNNModel(
                "sage", g.vertex_feats.shape[1], hidden=32, num_layers=2,
                num_classes=int(g.labels.max()) + 1,
            )
            tr = system.dp_trainer(
                model,
                ids,
                mesh=make_local_mesh(shards),
                batch_size=128,
                reference=True,
            )
            log = tr.train(epochs=1, log_every=1, max_steps=steps)
            tag = f"shards{shards}"
            total = log.sample_time + log.compute_time
            _emit(f"{tag}/steps_per_s", len(log.losses) / total)
            _emit(f"{tag}/final_loss", log.losses[-1])
            _flag(
                f"{tag}/loss_matches_reference",
                bool(
                    np.allclose(log.losses, log.ref_losses, rtol=1e-5, atol=1e-6)
                ),
            )
    finally:
        system.close()


def run(smoke: bool = False, out_json: str | None = "BENCH_distributed.json"):
    # full mode needs per-dispatch sampling work that dwarfs the ~1 ms IPC
    # overhead, or worker parallelism cannot show: a dense graph and large
    # keyed requests (2048 seeds, 15x10 fanout ~ hundreds of ms of numpy
    # sampling per request, split across the workers)
    scale = 0.02 if smoke else 0.25
    num_requests = 12 if smoke else 24
    seeds_per = 48 if smoke else 2048
    dp_steps = 3 if smoke else 10
    name = "wikikg90m" if smoke else "twitter-2010"
    g = dataset(name, scale=scale, feat_dim=16)

    bench_workers(g, _requests(g, num_requests, seeds_per))
    bench_data_parallel(g, dp_steps)

    if out_json:
        with open(out_json, "w") as fh:
            json.dump(RESULTS, fh, indent=2, sort_keys=True)
        print(f"wrote {out_json}")
    for workers in WORKER_SWEEP:
        assert RESULTS[f"workers{workers}/bit_identical_vs_inproc"], (
            f"{workers}-worker remote sampling diverged from in-process"
        )
    if not smoke:
        top = WORKER_SWEEP[-1]
        speedup = RESULTS[f"workers{top}/measured_dispatch_speedup"]
        assert speedup > 1.0, (
            f"{top} workers did not reduce dispatch latency "
            f"(speedup {speedup:.2f}); smoke-sized workloads are exempt"
        )
    for shards in _shard_sweep():
        assert RESULTS[f"shards{shards}/loss_matches_reference"], (
            f"{shards}-shard dp losses diverged from the single-device "
            "reference step"
        )


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny CI workload")
    ap.add_argument("--out", default="BENCH_distributed.json")
    args = ap.parse_args()
    run(smoke=args.smoke, out_json=args.out)

"""Paper Table IV + Fig. 11: test accuracy and end-to-end training speed of
GCN/GraphSAGE/GAT on the GLISP pipeline vs the edge-cut pipeline, plus the
prefetching batch pipeline vs the serial sample-then-step path.

All systems are assembled via ``GLISPSystem.build`` (benchmarks/common.py).
Both sections run in this one process: on a TPU the process that first
touches JAX holds the chip, so a child process could not reach it.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from benchmarks.common import dataset, edgecut_system, emit, glisp_system
from repro.api import GLISPConfig, GLISPSystem
from repro.models.gnn import GNNModel
from repro.train.optim import AdamWConfig


def _prep(g, classes=3):
    """Homophilous learnable labels: community (LDG cluster) id, plus a weak
    per-vertex feature signal — GCN/GAT learn from neighborhoods, SAGE from
    both."""
    from repro.core.partition import ldg_edge_cut

    g.labels = ldg_edge_cut(g, classes, seed=9).astype(np.int32)
    g.vertex_feats[:, :classes] = 0
    g.vertex_feats[np.arange(g.num_vertices), g.labels] += 1.5
    return g


def _opt(steps=200):
    return AdamWConfig(lr=3e-3, warmup_steps=3, total_steps=steps)


def run_system_comparison(g, ids, n_train):
    for model_kind in ("gcn", "sage", "gat"):
        res = {}
        for sys_name, system in (
            ("GLISP", glisp_system(g, 2, fanouts=(15, 10, 5))),
            ("EdgeCut", edgecut_system(g, 2, fanouts=(15, 10, 5))),
        ):
            model = GNNModel(model_kind, g.vertex_feats.shape[1], hidden=64,
                             num_layers=3, num_classes=3)
            tr = system.trainer(model, ids[:n_train], opt=_opt(), prefetch=0)
            system.reset_stats()
            log = tr.train(epochs=1, log_every=10)
            acc = tr.evaluate(ids[n_train:], batches=4)
            client = system.client
            res[sys_name] = (log, client.parallel_work, client.total_work, acc)
            emit(f"table4/{model_kind}/{sys_name}/test_acc", acc)
        # e2e speedup model: common compute time, shared serial cost per work
        # unit, sampling latency = parallel (max-over-servers) work
        (lg, pg, tg, _), (le, pe, te, _) = res["GLISP"], res["EdgeCut"]
        unit = (lg.sample_time + le.sample_time) / max(tg + te, 1e-9)
        compute = 0.5 * (lg.compute_time + le.compute_time)
        t_glisp = compute + pg * unit
        t_ec = compute + pe * unit
        steps = max(1, n_train // 256)
        emit(f"fig11/{model_kind}/GLISP/steps_per_s", steps / t_glisp)
        emit(f"fig11/{model_kind}/EdgeCut/steps_per_s", steps / t_ec)
        emit(f"fig11/{model_kind}/e2e_speedup", t_ec / t_glisp)
        emit(f"fig11/{model_kind}/sampling_speedup", pe / max(pg, 1e-9))


def run_prefetch_comparison(g, ids, n_train, reps=3):
    """Measured wall-clock of one epoch: serial sample-then-step vs the
    double-buffered prefetching pipeline.  Each mode gets a freshly built,
    identically seeded system, so the two batch streams are bit-identical;
    an untimed warm-up epoch excludes XLA compilation from both.  Epochs
    alternate serial/prefetch for ``reps`` rounds and the MIN wall per mode
    is compared — the container shares its host, so min-of-paired-runs
    filters neighbor noise out of both sides equally."""
    trainers = {}
    for mode, depth in (("serial", 0), ("prefetch", 2)):
        system = GLISPSystem.build(g, GLISPConfig(
            num_parts=2, fanouts=(15, 10, 5), batch_size=256,
            prefetch=depth, seed=0,
        ))
        model = GNNModel("sage", g.vertex_feats.shape[1], hidden=64,
                         num_layers=3, num_classes=3)
        tr = system.trainer(model, ids[:n_train], opt=_opt(400))
        tr.train(epochs=1, log_every=10**9)  # warm-up: compile all buckets
        trainers[mode] = tr
    walls = {mode: [] for mode in trainers}
    splits = {}
    for _ in range(reps):
        for mode, tr in trainers.items():
            s0, c0 = tr.pipeline.sample_time, tr.log.compute_time
            t0 = time.perf_counter()
            log = tr.train(epochs=1, log_every=10**9)
            walls[mode].append(time.perf_counter() - t0)
            splits[mode] = (log.sample_time - s0, log.compute_time - c0)
    for mode in trainers:
        emit(f"pipeline/{mode}/wall_s", min(walls[mode]))
        emit(f"pipeline/{mode}/sample_s", splits[mode][0])
        emit(f"pipeline/{mode}/compute_s", splits[mode][1])
    emit(
        "pipeline/prefetch_speedup",
        min(walls["serial"]) / min(walls["prefetch"]),
    )


def _bench_data():
    # power-law dataset with community structure (GCN/GAT need homophily)
    g = _prep(dataset("ogbn-paper", scale=0.12))
    ids = np.arange(g.num_vertices)
    rng = np.random.default_rng(0)
    rng.shuffle(ids)
    return g, ids, int(0.7 * len(ids))


def run():
    g, ids, n_train = _bench_data()
    run_system_comparison(g, ids, n_train)
    run_prefetch_comparison(g, ids, n_train)


if __name__ == "__main__":
    if "--prefetch-only" in sys.argv:
        run_prefetch_comparison(*_bench_data())
    else:
        run()

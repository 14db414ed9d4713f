"""Chip smoke test: GLISP's train -> layerwise-infer -> serve path on a TPU.

    python chip_smoke.py               # one chip: build, train, infer, serve
    python chip_smoke.py --four-chips  # only data-parallel training, 4 chips

It drives the system through the entry points a user calls
(``GLISPSystem.build``, ``system.trainer``, ``system.infer_layerwise``,
``system.server``, ``system.dp_trainer``) at the ``sage-products`` widths,
on the ``ogbn-paper`` stand-in graph generated from a seed, with random
weights from a seed.  Every phase checks its output against a reference
and raises on a mismatch; nothing catches a phase's failure.  Wall clocks
printed along the way include compilation: they are set-up, not
measurements.  Without a TPU it exits non-zero before any phase.  The last
line of its output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

FULL_VERTICES = 150_000  # the ogbn-paper stand-in (repro.graph.generate)
FEAT_DIM, NUM_CLASSES = 64, 16
HIDDEN, LAYERS, FANOUTS, BATCH = 256, 3, (15, 10, 5), 256  # sage-products
TRAIN_STEPS = 20
CPU_CHECKED_STEPS = 3
SERVE_REQUESTS = 48

# Tolerances, as max |got - want| over max |want| of the compared block.
# A TPU f32 matmul at the default precision rounds its operands to bf16
# (8 significant bits: relative error up to 2**-9 per operand) and
# accumulates in f32.  A loss or an embedding passes three sage layers and
# up to one more projection, each such a matmul, so against an f32
# reference on the CPU a few 2**-8 roundings add up: allow 2e-2.
TOL_VS_F32 = 2e-2
# Two runs on the chip at the same precision whose sums differ only in
# order (XLA's segment_sum vs the Pallas kernel's one-hot matmul at
# HIGHEST): a last-bit difference can flip the bf16 rounding of one
# operand of the next matmul, 2**-8 of that operand's term, on any row of
# the store.  Allow 1e-2.
TOL_SAME_PRECISION = 1e-2
# The data-parallel step against its single-device twin on the same
# stacked batches: the same program but for the placement, so the batch-
# mean losses differ only by the order of the cross-shard reduction.
# Readings: 8.12e-8 on four v5e chips at full size; 8.05e-8 on 4 host
# CPU devices at 6,000 vertices, where a sharded step that zeroed shard
# 3's features read 1.76e-2 and one that took shard 0's batch in place of
# shard 3's read 1.15e-2.  Allow 1e-5.
TOL_DP_LOSS = 1e-5


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_diff(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def check(name: str, diff: float, tol: float) -> None:
    log(f"  {name}: max rel diff {diff!r} (tolerance {tol})")
    if not diff <= tol:
        raise AssertionError(f"{name}: {diff!r} exceeds tolerance {tol}")


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def build_system(vertices: int):
    from repro.api import GLISPConfig, GLISPSystem
    from repro.graph import named_dataset

    t0 = time.perf_counter()
    g = named_dataset(
        "ogbn-paper",
        feat_dim=FEAT_DIM,
        num_classes=NUM_CLASSES,
        scale=vertices / FULL_VERTICES,
    )
    cut = "full size" if g.num_vertices == FULL_VERTICES else f"cut from {FULL_VERTICES}"
    system = GLISPSystem.build(
        g,
        GLISPConfig(
            num_parts=2, partitioner="adadne", fanouts=FANOUTS, batch_size=BATCH
        ),
    )
    log(
        f"build: ogbn-paper stand-in, {g.num_vertices} vertices ({cut}), "
        f"{g.num_edges} edges, adadne into 2 parts, "
        f"{time.perf_counter() - t0:.1f}s set-up"
    )
    return g, system


def sage_model():
    from repro.models.gnn import GNNModel

    return GNNModel(
        "sage", FEAT_DIM, hidden=HIDDEN, num_layers=LAYERS, num_classes=NUM_CLASSES
    )


def train_phase(system, g, model):
    """~20 steps through system.trainer with the default (forked-worker)
    prefetch; the first steps re-run on the CPU from the same initial
    state on the same batches (the keyed batch stream is identical with
    and without prefetch)."""
    import jax

    cpu = jax.devices("cpu")[0]
    train_ids = np.random.default_rng(0).permutation(g.num_vertices)[
        : TRAIN_STEPS * BATCH
    ]
    t0 = time.perf_counter()
    trainer = system.trainer(model, train_ids)
    ref_params = jax.device_put(trainer.params, cpu)
    ref_opt = jax.device_put(trainer.opt_state, cpu)
    log(f"train: prefetch={trainer.pipeline.prefetch} workers={trainer.pipeline.workers}")
    losses = trainer.train(epochs=1, log_every=1, max_steps=TRAIN_STEPS).losses
    trainer.pipeline.close()
    log(
        f"  {len(losses)} steps, {trainer._step._cache_size()} step compiles, "
        f"{time.perf_counter() - t0:.1f}s set-up; losses {losses}"
    )
    if len(losses) != TRAIN_STEPS or not np.all(np.isfinite(losses)):
        raise AssertionError(f"expected {TRAIN_STEPS} finite losses, got {losses}")
    children = mp.active_children()
    if children:
        raise AssertionError(f"prefetch worker(s) left after close(): {children}")
    log("  prefetch worker closed, no child process left")

    batches = system.loader(train_ids, LAYERS, prefetch=0).batches(1)
    ref = []
    for _ in range(CPU_CHECKED_STEPS):
        _, batch = next(batches)
        ref_params, ref_opt, loss = trainer._step(
            ref_params, ref_opt, jax.device_put(batch, cpu)
        )
        ref.append(float(loss))
    batches.close()
    log(f"  CPU reference losses {ref}")
    check("train loss vs CPU f32", rel_diff(losses[:CPU_CHECKED_STEPS], ref), TOL_VS_F32)
    return trainer


def infer_and_serve_phase(system, g, model, params, workdir: str) -> None:
    """Whole-graph layerwise inference on the jnp path and with the Pallas
    kernels, a sample checked against the per-vertex reference engine on
    the CPU, then online serving checked against the final-layer store.

    The last hop's fanout covers every vertex's whole neighborhood, so the
    server's own sample of it equals the engine's and a served embedding
    must equal the stored row."""
    import jax

    cpu = jax.devices("cpu")[0]
    full_hop = int(max(g.out_degrees().max(), g.in_degrees().max()))
    fanouts = FANOUTS[:-1] + (full_hop,)
    out_dims = [HIDDEN] * LAYERS
    fns = [model.embed_layer_fn(params, k) for k in range(LAYERS)]
    log(f"infer: fanouts {fanouts} (last hop = max degree), batch 4096")

    def run(tag, layer_fns, **kw):
        t0 = time.perf_counter()
        res = system.infer_layerwise(
            layer_fns, f"{workdir}/{tag}", fanouts=fanouts, out_dims=out_dims, **kw
        )
        rows = res.final_store.read_rows(res.newid[np.arange(g.num_vertices)])
        log(
            f"  {tag}: {res.vertices_computed()} vertex-layers, "
            f"{res.slice_compiles} slice shapes, "
            f"{time.perf_counter() - t0:.1f}s set-up"
        )
        if not np.all(np.isfinite(rows)):
            raise AssertionError(f"{tag}: non-finite embeddings")
        return res, rows

    cpu_params = jax.device_put(params, cpu)
    cpu_fns = [model.embed_layer_fn(cpu_params, k) for k in range(LAYERS)]
    with jax.default_device(cpu):
        _, ref_rows = run("reference-cpu", cpu_fns, mode="reference")
    _, kernel_rows = run("pallas", fns, use_kernel=True)
    res, jnp_rows = run("jnp", fns)
    check("pallas vs jnp store, all rows", rel_diff(kernel_rows, jnp_rows), TOL_SAME_PRECISION)
    sample = np.random.default_rng(2).choice(g.num_vertices, 4096, replace=False)
    check("jnp vs CPU reference, 4096 rows", rel_diff(jnp_rows[sample], ref_rows[sample]), TOL_VS_F32)

    t0 = time.perf_counter()
    server = system.server(deadline_ms=None)
    rng = np.random.default_rng(3)
    by_rank = rng.permutation(g.num_vertices)  # Zipf rank -> vertex
    requests = [
        by_rank[(rng.zipf(1.2, size=int(rng.integers(1, 9))) - 1) % g.num_vertices]
        for _ in range(SERVE_REQUESTS)
    ]
    rids = [server.submit(v) for v in requests]
    server.drain()
    got, want = [], []
    for rid, verts in zip(rids, requests):
        resp = server.response(rid)
        if resp is None or resp.status != "ok":
            raise AssertionError(f"request {rid}: {resp}")
        got.append(resp.embeddings)
        want.append(jnp_rows[verts])
    log(
        f"serve: {len(rids)} Zipf requests, {sum(len(v) for v in requests)} "
        f"vertices, all ok, {time.perf_counter() - t0:.1f}s set-up"
    )
    check("served vs final-layer store", rel_diff(np.concatenate(got), np.concatenate(want)), TOL_SAME_PRECISION)


def one_chip(vertices: int = FULL_VERTICES) -> None:
    g, system = build_system(vertices)
    model = sage_model()
    with system:
        trainer = train_phase(system, g, model)
        with tempfile.TemporaryDirectory() as workdir:
            infer_and_serve_phase(system, g, model, trainer.params, workdir)


def four_chips(vertices: int = FULL_VERTICES) -> None:
    """Data-parallel training on a mesh over every device, checked against
    the unsharded single-device step on the same stacked batches."""
    import jax

    g, system = build_system(vertices)
    train_ids = np.random.default_rng(0).permutation(g.num_vertices)[
        : TRAIN_STEPS * BATCH
    ]
    with system:
        t0 = time.perf_counter()
        tr = system.dp_trainer(sage_model(), train_ids, reference=True)
        dplog = tr.train(epochs=1, log_every=1, max_steps=TRAIN_STEPS // 2)
        batch_devs = tr._batch_sharding.device_set
        param_devs = {d for leaf in jax.tree.leaves(tr.params) for d in leaf.devices()}
        log(f"dp: mesh {dict(tr.mesh.shape)}, {time.perf_counter() - t0:.1f}s set-up")
        log(f"  batch sharding devices {sorted(d.id for d in batch_devs)}")
        log(f"  params devices {sorted(d.id for d in param_devs)}")
        log(f"  sharded losses {dplog.losses}")
        log(f"  reference losses {dplog.ref_losses}")
        n = len(jax.devices())
        if len(batch_devs) != n or len(param_devs) != n:
            raise AssertionError(f"expected all {n} devices in both shardings")
        if len(dplog.losses) != TRAIN_STEPS // 2 or not np.all(np.isfinite(dplog.losses)):
            raise AssertionError(f"bad sharded losses {dplog.losses}")
        check("sharded vs single-device losses", rel_diff(dplog.losses, dplog.ref_losses), TOL_DP_LOSS)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--four-chips",
        action="store_true",
        help="run only data-parallel training on a 4-device mesh",
    )
    args = ap.parse_args(argv)

    info = device_info()
    log(f"device: {json.dumps(info)}")
    if info["platform"] != "tpu":
        log(f"no TPU: JAX found {info['platform']!r}; nothing was run")
        return 1
    if args.four_chips and info["count"] != 4:
        log(f"--four-chips needs 4 devices, JAX found {info['count']}")
        return 1

    from repro.launch.compile_cache import setup_compile_cache

    log(f"compile cache: {setup_compile_cache()}")
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)()
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

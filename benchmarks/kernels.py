"""GNN kernel suite benchmark: wall-clock + achieved-vs-peak roofline.

Measures, at a padded bucket shape like the inference engine dispatches:

* the ragged ``segment_spmm_ragged_pallas`` vs the row-tiled 2-D
  ``segment_spmm_pallas`` on a padding-heavy batch (3/4 padding), where
  all-pad tiles cost one predicate instead of a matmul;
* the one-pass ``gat_softmax_aggregate_pallas`` vs the 3-pass
  segment-max → normalize → weighted-sum kernel sequence it replaces;
* the deterministic autotuner (measured sweep, then in-memory and artifact
  cache hits);
* on a chip whose peaks ``launch.mesh.PEAKS`` knows, each kernel's
  roofline from ``launch.roofline.kernel_roofline``; elsewhere only the
  analytic FLOPs and HBM bytes.

Everything asserts allclose against the jnp oracles in ``kernels/ref.py``.
Kernels run in the platform's mode: compiled on a TPU, interpreted on the
CPU, where the wall-clocks say nothing about the chip.  Results land in
``BENCH_kernels.json``.
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time

import numpy as np

from benchmarks.common import emit

RESULTS: dict = {}


def _emit(name: str, value) -> None:
    RESULTS[name] = value if isinstance(value, (bool, dict, str)) else float(value)
    emit(name, value if not isinstance(value, (dict, str)) else 0.0)


def _bench(fn, *args, reps: int = 3) -> float:
    fn(*args).block_until_ready()  # compile outside timing
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def _inputs(E: int, N: int, D: int, valid: int, rng):
    import jax.numpy as jnp

    msg = jnp.asarray(rng.standard_normal((E, D)).astype(np.float32))
    seg = np.sort(rng.integers(0, N, E)).astype(np.int32)
    seg[valid:] = -1
    logits = jnp.asarray(rng.standard_normal(E).astype(np.float32))
    return msg, jnp.asarray(seg), logits


def run(smoke: bool = False, out_json: str | None = "BENCH_kernels.json"):
    import jax
    import jax.numpy as jnp

    from repro.kernels import autotune as at
    from repro.kernels.fused_gnn import (
        gat_softmax_aggregate_pallas,
        segment_spmm_ragged_pallas,
    )
    from repro.kernels.ref import gat_softmax_aggregate_ref, segment_spmm_ref
    from repro.kernels.segment_spmm import segment_spmm_pallas
    from repro.launch.roofline import kernel_flops, kernel_hbm_bytes, kernel_roofline

    dev = jax.devices()[0]
    RESULTS["device"] = {"platform": dev.platform, "kind": dev.device_kind}

    def _roofline(op, shape, wall):
        if dev.platform == "tpu":
            _emit(f"roofline/{op}", kernel_roofline(op, shape, wall, dev.device_kind))
        else:
            _emit(f"analytic/{op}/flops", kernel_flops(op, shape))
            _emit(f"analytic/{op}/hbm_bytes", kernel_hbm_bytes(op, shape))

    E, N, D = (1024, 128, 16) if smoke else (8192, 1024, 64)
    rng = np.random.default_rng(0)
    shape = {"edges": E, "segments": N, "dim": D}

    # --- ragged vs row-tiled segment-SpMM on a 3/4-padding bucket ---------
    msg_q, seg_q, _ = _inputs(E, N, D, valid=E // 4, rng=rng)
    ref_q = np.asarray(segment_spmm_ref(msg_q, seg_q, N))

    def dense_q(msg, seg):
        return segment_spmm_pallas(msg, seg, N)

    def ragged_q(msg, seg):
        return segment_spmm_ragged_pallas(msg, seg, N)

    for fn in (dense_q, ragged_q):
        assert np.allclose(np.asarray(fn(msg_q, seg_q)), ref_q, rtol=1e-4, atol=1e-5)
    t_dense_q = _bench(dense_q, msg_q, seg_q)
    t_ragged_q = _bench(ragged_q, msg_q, seg_q)
    _emit("ragged/dense_s", t_dense_q)
    _emit("ragged/ragged_s", t_ragged_q)
    _emit("ragged/speedup_on_3quarters_padding", t_dense_q / t_ragged_q)
    _roofline("segment_spmm", shape, t_dense_q)
    _roofline("segment_spmm_ragged", {**shape, "valid_edges": E // 4}, t_ragged_q)

    # --- one-pass GAT softmax+aggregate vs the 3-pass it replaces ---------
    msg, seg, logits = _inputs(E, N, D, valid=E, rng=rng)

    # The pre-fusion kernel path from models.py: jnp segment-max, then two
    # 2-D-grid segment_spmm calls (one for the softmax denominator, one for
    # the weighted sum) — 3 passes over the edge array.
    @jax.jit
    def three_pass(logits, msg, seg):
        ok = seg >= 0
        seg0 = jnp.maximum(seg, 0)
        mx = jax.ops.segment_max(
            jnp.where(ok, logits, -jnp.inf), seg0, num_segments=N
        )
        mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
        e = jnp.where(ok, jnp.exp(logits - mx[seg0]), 0.0)
        z = segment_spmm_pallas(e[:, None], seg, N)[:, 0]
        alpha = e / jnp.maximum(z[seg0], 1e-9)
        return segment_spmm_pallas(msg * alpha[:, None], seg, N)

    def one_pass(logits, msg, seg):
        return gat_softmax_aggregate_pallas(logits, msg, seg, N)

    ref_gat = np.asarray(gat_softmax_aggregate_ref(logits, msg, seg, N))
    for fn in (one_pass, three_pass):
        assert np.allclose(
            np.asarray(fn(logits, msg, seg)), ref_gat, rtol=1e-4, atol=1e-5
        )
    t3 = _bench(three_pass, logits, msg, seg)
    t1 = _bench(one_pass, logits, msg, seg)
    _emit("gat/three_pass_s", t3)
    _emit("gat/one_pass_s", t1)
    _emit("gat/one_pass_speedup", t3 / t1)
    _roofline("gat_softmax_aggregate", shape, t1)

    # --- deterministic autotuner ------------------------------------------
    at.reset()
    tune_shape = (E, N, D)
    op = "segment_spmm_ragged"
    with tempfile.TemporaryDirectory() as td:
        t0 = time.perf_counter()
        cfg1 = at.autotune(op, tune_shape, np.float32, cache_dir=td)
        t_sweep = time.perf_counter() - t0
        cfg2 = at.autotune(op, tune_shape, np.float32, cache_dir=td)
        assert cfg1 == cfg2 and at.stats()["memory_hits"] == 1
        at.reset(clear_stats=False)  # fresh process simulation
        cfg3 = at.autotune(op, tune_shape, np.float32, cache_dir=td)
        assert cfg3 == cfg1 and at.stats()["artifact_hits"] == 1
    _emit("autotune/sweep_s", t_sweep)
    _emit("autotune/chosen_block_edges", cfg1.block_edges)
    _emit("autotune/measured", at.stats()["measured"])
    _emit("autotune/memory_hits", at.stats()["memory_hits"])
    _emit("autotune/artifact_hits", at.stats()["artifact_hits"])
    at.reset()

    # --- acceptance: ragged beats dense on padding; one pass beats three --
    # Perf gates hold at benchmark scale; smoke runs are too small for the
    # wall-clock deltas to clear timer noise, so smoke only checks numerics.
    if not smoke:
        assert t_ragged_q < t_dense_q, (
            f"ragged kernel ({t_ragged_q:.4f}s) must beat dense ({t_dense_q:.4f}s) "
            "on a 3/4-padding bucket"
        )
        assert t1 < t3, (
            f"one-pass GAT kernel ({t1:.4f}s) must beat the 3-pass sequence ({t3:.4f}s)"
        )

    if out_json:
        with open(out_json, "w") as f:
            json.dump(RESULTS, f, indent=2, sort_keys=True)
        print(f"wrote {out_json}")
    return RESULTS


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny scale for CI")
    ap.add_argument("--out", default="BENCH_kernels.json")
    args = ap.parse_args()
    run(smoke=args.smoke, out_json=args.out)

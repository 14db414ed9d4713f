"""GLISP's benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell asks
for.  The cell names a configuration (``configs/<name>.json``) and a
traffic mix (``traffic/<name>.json``); the mix names its kind, the code
that drives the system (``kinds/<kind>.py``); each per-layer metric has a
reader of its own (``metrics/<name>.py``).  Set-up (graph, system,
compiles, warm-up) comes first and is reported as ``setup_s``; then the
window runs for about ``--seconds``; then the program's output is checked
against the plain reference.  With ``--trace 1`` the window runs under the
profiler and the per-layer metrics are reported instead of the end-to-end
ones.  The last line of standard output is one JSON object; the compared
numbers and their limits end standard error.  Without an accelerator, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import multiprocessing as mp  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import common  # noqa: E402
import trace_reduce  # noqa: E402

WINDOW_SPAN = "bench.window"


def run_cell(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    bench_file: Path = common.ROOT / "BENCHMARK.json",
    require_accelerator: bool = True,
    t_start: float = T_START,
) -> dict:
    """One run of one cell; returns the result line as a dict.  Without
    ``require_accelerator`` (a rehearsal on the CPU) it runs on whatever
    JAX has and reports no device metric."""
    spec = common.Spec(workload, bench_file)
    if not (common.ROOT / "src" / "repro").is_dir():
        raise common.BenchError("no system under test: src/repro is missing")
    allowed = os.sched_getaffinity(0)  # the cell may narrow it (common.split_cores)
    import jax

    info = common.device_info()
    on_chip = info["platform"] != "cpu"
    if require_accelerator and not on_chip:
        raise common.BenchError(f"JAX finds no accelerator, only {info['platform']!r}")
    if require_accelerator and info["count"] < spec.chips:
        raise common.BenchError(f"the cell needs {spec.chips} chips, JAX finds {info['count']}")
    peak = common.peak_flops(info["kind"]) if on_chip else None
    if on_chip:
        common.setup_compile_cache()
    compiles = common.CompileCounter()
    cell = spec.kind().Cell(spec, seed, compiles)
    trace_dir = common.CACHE / "trace" / workload
    result: dict = {}
    try:
        cell.setup()
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 1
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        setup_s = common.now() - t_start
        before = compiles.count
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            cell.window(seconds)
        window_compiles = compiles.count - before
        if trace:
            jax.profiler.stop_trace()
        info["memory_peak_bytes"] = common.memory_peak_bytes()
        if trace:
            reduced = trace_reduce.reduce(trace_reduce.find_trace(trace_dir), WINDOW_SPAN)
            shutil.rmtree(trace_dir, ignore_errors=True)
            readings = cell.readings()
            # the peak of all the chips the cell's step runs on
            peak_all = peak * spec.chips if peak else None
            readings.update(window_compiles=window_compiles, peak_flops=peak_all, trace=reduced)
            metrics = {}
            for m in spec.per_layer:
                value = spec.reader(m["name"])(readings)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            if reduced is not None:
                info["busy_s"] = reduced["busy_s"]
                info["window_s"] = reduced["window_s"]
                result["breakdown"] = reduced["breakdown"]
        else:
            values = dict(cell.end_to_end(), setup_s=setup_s)
            metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]} for m in spec.end_to_end}
        cell.release()
        checks = cell.check()
    finally:
        cell.close()
        for child in mp.active_children():
            child.terminate()
            child.join(10)
        common.pin_process(os.getpid(), allowed)
    w = cell.window_stats
    correct = w["failed"] == 0 and all(c["value"] <= c["limit"] for c in checks.values())
    return {
        "correct": bool(correct),
        "attempted": w["steps"],
        "failed": w["failed"],
        "metrics": metrics,
        "device": info,
        **result,
        "checks": checks,
    }


def _json_number(x):
    return x if isinstance(x, (int, float)) and math.isfinite(x) else str(x)


def main(argv=None) -> int:
    # libtpu would log to a fixed path under /tmp, shared by every checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    ap = argparse.ArgumentParser(description="Run one cell of BENCHMARK.json.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        res = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except common.BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    res["checks"] = {k: {kk: _json_number(vv) for kk, vv in c.items()} for k, c in res["checks"].items()}
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""What every cell shares: the benchmark's files found by name, the
cached dataset, the system under test, the device and its compile events.

Nothing here knows a configuration, a traffic mix or a metric by name: it
reads ``BENCHMARK.json`` and the files it points to.
"""
from __future__ import annotations

import hashlib
import importlib.util
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".cache"  # fixed path inside the checkout, ignored by git

sys.path.insert(0, str(ROOT / "src"))


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, unknown name, ...)."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file of the benchmark by path: metric readers and traffic
    kinds are found by name, and a dotted name is not a package path."""
    if not path.is_file():
        raise BenchError(f"no file {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Spec:
    """One cell as ``BENCHMARK.json`` declares it, with its files."""

    def __init__(self, workload: str, bench_file: Path = ROOT / "BENCHMARK.json"):
        if not bench_file.is_file():
            raise BenchError(f"no {bench_file.name} beside {BENCH.name}/")
        b = load_json(bench_file)
        cells = {w["name"]: w for w in b["workloads"]}
        if workload not in cells:
            raise BenchError(f"unknown workload {workload!r}; known: {sorted(cells)}")
        self.workload = cells[workload]
        configs = {c["name"]: c for c in b["configs"]}
        self.config_entry = configs[self.workload["config"]]
        self.config = load_json(ROOT / self.config_entry["file"])
        self.traffic = load_json(BENCH / "traffic" / f"{self.workload['traffic']}.json")
        self.chips = int(self.workload["chips"])
        moved = {
            m["name"]
            for m in b["end_to_end"]
            if workload in m.get("workloads", cells)
        }
        self.end_to_end = [m for m in b["end_to_end"] if m["name"] in moved]
        self.per_layer = [
            m
            for m in b["per_layer"]
            if (workload in m["workloads"] if "workloads" in m else m["moves"] in moved)
        ]

    def kind(self):
        return load_module(BENCH / "kinds" / f"{self.traffic['kind']}.py")

    def reader(self, metric: str):
        return load_module(BENCH / "metrics" / f"{metric}.py").read


# -- the dataset ------------------------------------------------------------

_GRAPH_FIELDS = (
    "src", "dst", "edge_types", "vertex_types", "edge_weights",
    "vertex_feats", "labels",
)


def graph_key(params: dict) -> str:
    """The cache key of a configuration's graph: a hash of its parameters."""
    return hashlib.sha256(json.dumps(params, sort_keys=True).encode()).hexdigest()[:16]


def graph_generator(name: str):
    """The program's generator ``<name>_graph`` in ``repro.graph.generate``,
    so that a configuration can name a generator added there later."""
    from repro.graph import generate

    gen = getattr(generate, f"{name}_graph", None)
    if not callable(gen):
        raise BenchError(f"no graph generator {name!r}: repro.graph.generate has no {name}_graph")
    return gen


def load_graph(params: dict):
    """The configuration's graph, generated once from its own fixed seed
    and kept under ``.cache/graphs``; later runs read it back."""
    from repro.graph import HeteroGraph

    d = CACHE / "graphs" / graph_key(params)
    if (d / "done").is_file():
        arrays = {f: np.load(d / f"{f}.npy") for f in _GRAPH_FIELDS}
        return HeteroGraph(num_vertices=int(params["num_vertices"]), **arrays)
    kw = {k: v for k, v in params.items() if k != "generator"}
    g = graph_generator(params["generator"])(**kw)
    d.mkdir(parents=True, exist_ok=True)
    for f in _GRAPH_FIELDS:
        np.save(d / f"{f}.npy", getattr(g, f))
    (d / "done").write_text("")
    return g


def build_system(config: dict):
    """``GLISPSystem.build`` over the cached graph, with the partition plan
    cached beside it."""
    from repro.api import GLISPConfig, GLISPSystem

    g = load_graph(config["graph"])
    sys_cfg = dict(config["system"])
    sys_cfg["fanouts"] = tuple(sys_cfg["fanouts"])
    system = GLISPSystem.build(
        g,
        GLISPConfig(**sys_cfg, partition_cache_dir=str(CACHE / "partitions")),
    )
    return g, system


# -- the device ---------------------------------------------------------------


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def memory_peak_bytes() -> int | None:
    """Peak bytes in use on the fullest chip, where the backend says."""
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def peak_flops(device_kind: str) -> float:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(
            f"no published peaks for device kind {device_kind!r}; known: {sorted(table)}"
        )
    return float(table[device_kind]["peak_flops_bf16"])


def setup_compile_cache() -> None:
    """JAX's persistent compile cache, for every program however quick to
    compile, so only a cell's first run in a checkout compiles: in the
    directory ``JAX_COMPILATION_CACHE_DIR`` names, where it is set (JAX
    reads it itself), else at a fixed path in the checkout."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


# -- where the processes run ---------------------------------------------------


def _cpu_list(text: str) -> list[int]:
    cpus = []
    for part in text.strip().split(","):
        if part:
            lo, _, hi = part.partition("-")
            cpus.extend(range(int(lo), int(hi or lo) + 1))
    return cpus


def physical_cores() -> list[list[int]]:
    """The CPUs this process may use, grouped by physical core (hyperthread
    siblings together)."""
    cores: dict = {}
    for c in sorted(os.sched_getaffinity(0)):
        sib = Path(f"/sys/devices/system/cpu/cpu{c}/topology/thread_siblings_list")
        key = tuple(_cpu_list(sib.read_text())) if sib.is_file() else (c,)
        cores.setdefault(key, []).append(c)
    return list(cores.values())


def pin_process(pid: int, cpus) -> None:
    """Every thread of ``pid`` to ``cpus``; threads it starts later inherit
    the mask of the thread that starts them."""
    for tid in os.listdir(f"/proc/{pid}/task"):
        try:
            os.sched_setaffinity(int(tid), cpus)
        except (ProcessLookupError, PermissionError):
            pass


def split_cores(children) -> None:
    """The physical cores in three disjoint groups: the main threads of the
    children (the program's forked batch producer), their other threads
    (the queue's feeder, which pickles each batch into the pipe), and this
    process.  Threads started later inherit their starter's group.  It
    keeps where the busy threads run the same from run to run."""
    cores = physical_cores()
    if len(cores) < 3 or not children:
        return
    third = len(cores) // 3
    groups = [[c for core in part for c in core] for part in (cores[:third], cores[third:2 * third], cores[2 * third:])]
    for ch in children:
        for tid in os.listdir(f"/proc/{ch.pid}/task"):
            try:
                os.sched_setaffinity(int(tid), groups[0] if int(tid) == ch.pid else groups[1])
            except (ProcessLookupError, PermissionError):
                pass
    pin_process(os.getpid(), groups[2])
    threads = {ch.pid: len(os.listdir(f"/proc/{ch.pid}/task")) for ch in children}
    log(f"placement: children (threads) {threads}, main threads on CPUs {groups[0]}, "
        f"other threads on {groups[1]}; this process on {groups[2]}")


class CompileCounter:
    """Programs the process had to fetch, compiled or read from the
    persistent cache, counted from JAX's own monitoring events."""

    EVENTS = (
        "/jax/core/compile/backend_compile_duration",
        "/jax/compilation_cache/cache_retrieval_time_sec",
    )

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event in self.EVENTS:
            self.count += 1


def run_seed_key(seed: int) -> tuple[int, np.random.Generator]:
    """A 31-bit PRNG key seed and a host generator, both from ``--seed``
    (which may need more than 32 bits)."""
    rng = np.random.default_rng(int(seed))
    return int(rng.integers(0, 2**31 - 1)), rng


def now() -> float:
    return time.perf_counter()


def log(msg: str) -> None:
    """Progress on standard error, which the check's limits end."""
    print(msg, file=sys.stderr, flush=True)

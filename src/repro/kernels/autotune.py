"""Deterministic block-size autotuner for the GNN Pallas kernels.

Block sizes that win depend on the (edges, segments, dim) shape and dtype;
rather than hardcode 128 everywhere, callers can sweep a small fixed
candidate grid once per shape bucket and cache the winner:

* the key is ``(op, shape-bucket, dtype)`` where every dim is rounded up to
  a power of two — exactly the bucketing the inference engine already uses,
  so one sweep covers every batch that lands in the bucket;
* results live in a process-global table consulted by the ``ops.py``
  wrappers at trace time (block sizes are static jit args), and optionally
  in a **content-addressed JSON artifact**: the filename embeds a hash of
  the tuner version + candidate grid, so a stale artifact from an older
  tuner can never be read back as current;
* measurement inputs are built from a fixed seed and candidates are tried
  in a fixed order with ties going to the earlier candidate, so the same
  machine state yields the same choice — and with a cache artifact the
  choice is byte-stable across processes regardless of timer noise.

The sweep itself costs a few kernel launches per (op, bucket, dtype) and
is opt-in (``GLISPConfig(kernel_autotune=True)`` or direct calls here);
everything falls back to ``DEFAULT_CONFIG`` when untuned.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time

import jax.numpy as jnp
import numpy as np

__all__ = [
    "KernelConfig",
    "DEFAULT_CONFIG",
    "TUNE_VERSION",
    "CANDIDATES",
    "tuned_key",
    "get_tuned",
    "autotune",
    "autotune_for_slice",
    "artifact_path",
    "stats",
    "reset",
]

TUNE_VERSION = 1

# fixed candidate grids (order matters: ties resolve to the earlier entry).
# Only segment_spmm tiles the row axis; the other kernels run a 1-D edge
# grid with the full output resident, so only block_edges is swept there.
_EDGE_CANDIDATES = (64, 128, 256)
_ROW_CANDIDATES = (128, 256)
TUNED_OPS = (
    "segment_spmm",
    "segment_spmm_ragged",
    "gat_softmax_aggregate",
    "segment_max",
)


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    block_rows: int = 128
    block_edges: int = 128


DEFAULT_CONFIG = KernelConfig()


def _candidates(op: str) -> tuple[KernelConfig, ...]:
    if op == "segment_spmm":
        return tuple(
            KernelConfig(br, be) for br in _ROW_CANDIDATES for be in _EDGE_CANDIDATES
        )
    return tuple(KernelConfig(128, be) for be in _EDGE_CANDIDATES)


CANDIDATES = {op: _candidates(op) for op in TUNED_OPS}

_TUNED: dict[str, KernelConfig] = {}
_STATS = {"memory_hits": 0, "artifact_hits": 0, "measured": 0}


def _pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def tuned_key(op: str, shape, dtype) -> str:
    """Cache key: op / pow2-bucketed dims / dtype name."""
    dims = "x".join(str(_pow2(d)) for d in shape)
    return f"{op}/{dims}/{jnp.dtype(dtype).name}"


def get_tuned(op: str, shape, dtype) -> KernelConfig | None:
    """Best known config for this shape bucket, or None if never tuned."""
    return _TUNED.get(tuned_key(op, shape, dtype))


def stats() -> dict:
    return dict(_STATS)


def reset(clear_stats: bool = True) -> None:
    """Drop the in-process table (artifacts on disk survive) — test hook."""
    _TUNED.clear()
    if clear_stats:
        for k in _STATS:
            _STATS[k] = 0


# -- content-addressed artifact ---------------------------------------------


def _identity() -> dict:
    return {
        "version": TUNE_VERSION,
        "candidates": {
            op: [dataclasses.asdict(c) for c in cands]
            for op, cands in CANDIDATES.items()
        },
    }


def artifact_path(cache_dir: str) -> str:
    """The artifact name embeds a digest of the tuner identity (version +
    candidate grid), so incompatible tuners read/write different files."""
    digest = hashlib.sha256(
        json.dumps(_identity(), sort_keys=True).encode()
    ).hexdigest()[:16]
    return os.path.join(cache_dir, f"kernel_tune_v{TUNE_VERSION}_{digest}.json")


def _load_artifact(cache_dir: str) -> dict[str, KernelConfig]:
    path = artifact_path(cache_dir)
    try:
        with open(path) as f:
            raw = json.load(f)
    except (OSError, ValueError):
        return {}
    return {k: KernelConfig(**v) for k, v in raw.get("configs", {}).items()}


def _store_artifact(cache_dir: str, configs: dict[str, KernelConfig]) -> None:
    os.makedirs(cache_dir, exist_ok=True)
    path = artifact_path(cache_dir)
    payload = dict(_identity())
    payload["configs"] = {
        k: dataclasses.asdict(v) for k, v in sorted(configs.items())
    }
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    os.replace(tmp, path)  # atomic: readers never see a torn file


# -- measurement -------------------------------------------------------------


def _inputs(op: str, shape, dtype):
    """Deterministic measurement inputs at the bucketed shape.  The tail
    quarter of edges is padding so ragged ops exercise their tile skip."""
    edges, segments, dim = (_pow2(d) for d in shape)
    rng = np.random.default_rng(0)
    valid = (3 * edges) // 4
    seg = np.sort(rng.integers(0, segments, edges)).astype(np.int32)
    seg[valid:] = -1
    msg = rng.standard_normal((edges, dim)).astype(np.float32)
    logits = rng.standard_normal(edges).astype(np.float32)
    cast = lambda a: jnp.asarray(a, dtype=dtype)  # noqa: E731
    return {
        "seg": jnp.asarray(seg),
        "msg": cast(msg),
        "logits": cast(logits),
        "n": segments,
    }


def _call(op: str, inp: dict, cfg: KernelConfig, interpret: bool | None):
    from repro.kernels import fused_gnn, segment_spmm

    n, be = inp["n"], cfg.block_edges
    if op == "segment_spmm":
        return segment_spmm.segment_spmm_pallas(
            inp["msg"], inp["seg"], n,
            block_rows=cfg.block_rows, block_edges=be, interpret=interpret,
        )
    if op == "segment_spmm_ragged":
        return fused_gnn.segment_spmm_ragged_pallas(
            inp["msg"], inp["seg"], n, block_edges=be, interpret=interpret
        )
    if op == "gat_softmax_aggregate":
        return fused_gnn.gat_softmax_aggregate_pallas(
            inp["logits"], inp["msg"], inp["seg"], n,
            block_edges=be, interpret=interpret,
        )
    if op == "segment_max":
        return fused_gnn.segment_max_pallas(
            inp["logits"], inp["seg"], n, block_edges=be, interpret=interpret
        )
    raise ValueError(f"unknown tuned op {op!r}")


def _measure(op: str, inp: dict, cfg: KernelConfig, repeats: int, interpret) -> float:
    _call(op, inp, cfg, interpret).block_until_ready()  # compile outside timing
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        _call(op, inp, cfg, interpret).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def autotune(
    op: str,
    shape,
    dtype,
    *,
    cache_dir: str | None = None,
    repeats: int = 3,
    interpret: bool | None = None,
) -> KernelConfig:
    """Best block config for (op, shape-bucket, dtype): in-process table
    first, then the cache artifact, then a measured sweep (whose winner is
    merged back into the artifact when ``cache_dir`` is given).  The sweep
    runs the kernels in the platform's mode unless ``interpret`` says
    otherwise."""
    if op not in CANDIDATES:
        raise ValueError(f"unknown tuned op {op!r} (have {sorted(CANDIDATES)})")
    key = tuned_key(op, shape, dtype)
    if key in _TUNED:
        _STATS["memory_hits"] += 1
        return _TUNED[key]
    if cache_dir is not None:
        cached = _load_artifact(cache_dir)
        if key in cached:
            _STATS["artifact_hits"] += 1
            _TUNED[key] = cached[key]
            return cached[key]
    inp = _inputs(op, shape, dtype)
    times = [_measure(op, inp, c, repeats, interpret) for c in CANDIDATES[op]]
    best = CANDIDATES[op][int(np.argmin(times))]  # ties -> earlier candidate
    _STATS["measured"] += 1
    _TUNED[key] = best
    if cache_dir is not None:
        merged = _load_artifact(cache_dir)
        merged[key] = best
        _store_artifact(cache_dir, merged)
    return best


def autotune_for_slice(shapes, dtype, *, cache_dir: str | None = None) -> None:
    """Tune a batch of (op, shape) pairs — the engine calls this with a
    layer slice's kernel shapes before the bucket's first jit trace, so the
    tuned blocks are already in the table when tracing resolves them."""
    for op, shape in shapes:
        autotune(op, shape, dtype, cache_dir=cache_dir)

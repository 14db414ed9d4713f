"""Shared benchmark helpers: dataset prep, system cache, CSV emission.

All pipelines are constructed through the unified facade —
``GLISPSystem.build(g, GLISPConfig(...))`` — never by hand-wiring servers
and routers.  ``glisp_client`` / ``edgecut_client`` return the underlying
``SamplingService`` (the legacy client role) for benchmarks that poke
workload counters directly.  Importing it places JAX's persistent
compilation cache for the benchmark scripts
(``repro.launch.compile_cache``).
"""
from __future__ import annotations

import numpy as np

from repro.api import GLISPConfig, GLISPSystem
from repro.launch.compile_cache import setup_compile_cache

setup_compile_cache()

_CACHE: dict = {}

# display name (CSV rows) -> registry name
PARTITIONERS = {
    "AdaDNE": "adadne",
    "DistributedNE": "dne",
    "Hash2D": "hash2d",
    "Random": "random",
}


def emit(name: str, value: float, derived: str = "") -> None:
    print(f"{name},{value:.3f},{derived}", flush=True)


def dataset(name: str, scale: float = 0.25, feat_dim: int = 32, num_classes: int = 8):
    from repro.graph import named_dataset

    key = ("ds", name, scale, feat_dim, num_classes)
    if key not in _CACHE:
        _CACHE[key] = named_dataset(
            name, feat_dim=feat_dim, num_classes=num_classes, seed=0, scale=scale
        )
    return _CACHE[key]


def glisp_system(
    g, parts: int, alg: str = "AdaDNE", seed: int = 0, **overrides
) -> GLISPSystem:
    key = ("sys", id(g), alg, parts, seed, tuple(sorted(overrides.items())))
    if key not in _CACHE:
        _CACHE[key] = GLISPSystem.build(
            g,
            GLISPConfig(
                num_parts=parts,
                partitioner=PARTITIONERS.get(alg, alg),
                sampler="gather_apply",
                seed=seed,
                **overrides,
            ),
        )
    return _CACHE[key]


def edgecut_system(
    g, parts: int, seed: int = 0, direction: str | None = None, **overrides
) -> GLISPSystem:
    """DistDGL-style baseline system; ``direction`` picks which one-hop the
    owner answers locally (edges follow that endpoint's owner).  Defaults to
    the stack-wide ``DEFAULT_DIRECTION`` so GLISP-vs-baseline comparisons
    sample the SAME neighborhoods; pass ``direction="in"`` for the strict
    DistDGL in-edges-local layout."""
    if direction is None:
        from repro.api import DEFAULT_DIRECTION

        direction = DEFAULT_DIRECTION
    key = ("ecsys", id(g), parts, seed, direction, tuple(sorted(overrides.items())))
    if key not in _CACHE:
        _CACHE[key] = GLISPSystem.build(
            g,
            GLISPConfig(
                num_parts=parts,
                partitioner="ldg",
                sampler="edge_cut",
                direction=direction,
                seed=seed,
                **overrides,
            ),
        )
    return _CACHE[key]


def partition(g, alg: str, parts: int, seed: int = 0):
    """(edge_assignment, seconds) for one partitioner via the registry —
    times the algorithm alone, no servers/routers built."""
    from repro.api import PARTITIONERS as REGISTRY

    key = ("part", id(g), alg, parts, seed)
    if key not in _CACHE:
        import time

        fn = REGISTRY.get(PARTITIONERS.get(alg, alg))
        t0 = time.perf_counter()
        plan = fn(g, parts, seed=seed)
        _CACHE[key] = (plan.edge_parts, time.perf_counter() - t0)
    return _CACHE[key]


def glisp_client(g, parts: int, alg: str = "AdaDNE", seed: int = 0):
    return glisp_system(g, parts, alg, seed).client


def edgecut_client(g, parts: int, seed: int = 0, direction: str | None = None):
    return edgecut_system(g, parts, seed, direction).client

"""Tiered storage subsystem: tier/policy semantics, HybridCache lifecycle,
legacy TwoLevelCache accounting parity, and the FeatureSource training path.
"""
import os

import jax
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.inference import (
    ChunkedEmbeddingStore,
    LayerwiseInferenceEngine,
    TwoLevelCache,
)
from repro.core.inference.cache import CachePolicy
from repro.core.storage import (
    CACHE_POLICIES,
    DFSTier,
    DiskTier,
    HybridCache,
    IOCost,
    LocalityPolicy,
    MemoryTier,
    StoreFeatureSource,
    as_feature_source,
    build_tiers,
    chunk_runs,
    resolve_policy,
)


def _store(path, rows=512, dim=4, chunk_rows=32, **kw) -> DFSTier:
    store = DFSTier(str(path), rows, dim, chunk_rows=chunk_rows, **kw)
    vals = (
        np.arange(rows * dim, dtype=np.float32).reshape(rows, dim)
        / (rows * dim)
    )
    store.write_rows(np.arange(rows), vals.astype(store.dtype))
    return store


def _two_tier(store, policy="fifo", capacity=2) -> HybridCache:
    tiers = [
        MemoryTier(store.chunk_rows, store.dim, capacity=capacity),
        DiskTier(store.chunk_rows, store.dim),
    ]
    return HybridCache(store, tiers, policy=policy)


def _chunk_reads(cache, chunks):
    """Read one row from each chunk id in sequence."""
    for c in chunks:
        cache.read_rows(np.asarray([c * cache.store.chunk_rows]))


# ---------------------------------------------------------------------------
# chunk_runs / store
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(n=st.integers(0, 200), seed=st.integers(0, 10_000))
def test_chunk_runs_assume_sorted_matches_general(n, seed):
    rng = np.random.default_rng(seed)
    rows = np.sort(rng.integers(0, 1000, size=n).astype(np.int64))
    got = [
        (c, pos.tolist(), crows.tolist())
        for c, pos, crows in chunk_runs(rows, 64, assume_sorted=True)
    ]
    want = [
        (c, pos.tolist(), crows.tolist())
        for c, pos, crows in chunk_runs(rows, 64)
    ]
    assert got == want


def test_write_rows_unsorted_input(tmp_path):
    """The single-argsort write path handles shuffled row ids."""
    store = DFSTier(str(tmp_path / "s"), 300, 4, chunk_rows=64)
    rng = np.random.default_rng(3)
    rows = rng.permutation(300)
    vals = rng.standard_normal((300, 4)).astype(np.float32)
    store.write_rows(rows, vals)
    got = store.read_rows(rows)
    np.testing.assert_array_equal(got, vals)


def test_compressed_store_roundtrip(tmp_path):
    """compress=True writes .npz chunks; full and partial writes roundtrip."""
    store = DFSTier(str(tmp_path / "z"), 200, 6, chunk_rows=64, compress=True)
    rng = np.random.default_rng(0)
    vals = rng.standard_normal((200, 6)).astype(np.float32)
    store.write_rows(np.arange(200), vals)
    files = sorted(os.listdir(store.path))
    assert files and all(f.endswith(".npz") for f in files)
    np.testing.assert_array_equal(store.read_rows(np.arange(200)), vals)
    patch = np.full((3, 6), 9.0, np.float32)
    store.write_rows(np.array([0, 70, 199]), patch)  # partial RMW per chunk
    got = store.read_rows(np.arange(200))
    assert (got[[0, 70, 199]] == 9.0).all()
    keep = np.setdiff1d(np.arange(200), [0, 70, 199])
    np.testing.assert_array_equal(got[keep], vals[keep])
    # the deprecation shim constructs the same store
    shim = ChunkedEmbeddingStore(
        str(tmp_path / "z"), 200, 6, chunk_rows=64, compress=True
    )
    np.testing.assert_array_equal(shim.read_rows_direct(np.arange(200)), got)


def test_disk_tier_spills_to_files(tmp_path):
    """DiskTier with a path actually writes chunk files and reloads them."""
    tier = DiskTier(32, 4, path=str(tmp_path / "d"))
    block = np.ones((32, 4), np.float32) * 5
    tier.write_chunk(3, block)
    assert 3 in tier and len(tier) == 1
    assert os.path.exists(os.path.join(tier.path, "tier_000003.npy"))
    np.testing.assert_array_equal(tier.read_chunk(3), block)
    rows = np.arange(3 * 32, 3 * 32 + 8)
    np.testing.assert_array_equal(tier.read_rows(rows), block[:8])
    tier.delete_chunk(3)
    assert 3 not in tier
    assert not os.listdir(tier.path)


# ---------------------------------------------------------------------------
# policies
# ---------------------------------------------------------------------------


def test_lru_and_fifo_hit_counts_differ(tmp_path):
    """A reuse-heavy trace where LRU keeps the hot chunk FIFO ages out:
    A B A C A D A ... — LRU refreshes A on every touch, FIFO evicts it as
    the oldest whenever a new chunk streams in."""
    trace = [0, 1, 0, 2, 0, 3, 0, 4, 0, 5, 0]
    hits = {}
    for policy in ("fifo", "lru"):
        store = _store(tmp_path / policy)
        cache = _two_tier(store, policy=policy, capacity=2)
        cache.fill(cache.plan_fill(np.arange(store.num_rows)))
        _chunk_reads(cache, trace)
        hits[policy] = cache.stats.dynamic_hits
    # LRU: every touch of chunk 0 refreshes its age, so only the streaming
    # chunks age out and 0 hits on every revisit (5).  FIFO: 0 stays the
    # oldest resident, so each new chunk evicts it and half the revisits
    # miss (3).
    assert hits["lru"] > hits["fifo"], hits
    assert (hits["lru"], hits["fifo"]) == (5, 3), hits


def test_locality_policy_protects_fill_window(tmp_path):
    """Locality eviction drops far (boundary) chunks first, so the local
    working set survives one-shot far reads that would cycle FIFO out."""
    # chunks 0-3 are the active partition (focus); 8-15 are far neighbors
    local = [0, 1, 2, 3]
    far = [8, 9, 10, 11, 12, 13, 14, 15]
    trace = []
    for f in far:  # interleave: local sweep, then one far one-shot read
        trace += local + [f]
    trace += local
    hits, modeled = {}, {}
    for policy in ("fifo", "locality"):
        store = _store(tmp_path / policy, rows=512, chunk_rows=32)  # 16 chunks
        cache = _two_tier(store, policy=policy, capacity=5)
        cache.fill(
            cache.plan_fill(
                np.arange(store.num_rows),
                focus_rows=np.arange(4 * 32),  # chunks 0-3
            )
        )
        _chunk_reads(cache, trace)
        hits[policy] = cache.stats.dynamic_hits
        modeled[policy] = cache.stats.modeled_time_ms(IOCost())
    assert hits["locality"] > hits["fifo"], hits
    # identical fills and access counts, so more memory hits must lower the
    # modeled retrieval time
    assert modeled["locality"] < modeled["fifo"], modeled


def test_policy_resolution_forms():
    assert resolve_policy("fifo").name == "fifo"
    assert resolve_policy(CachePolicy.LRU).name == "lru"  # legacy str-enum
    assert resolve_policy(LocalityPolicy).name == "locality"
    pol = LocalityPolicy()
    assert resolve_policy(pol) is pol
    with pytest.raises(ValueError):
        CACHE_POLICIES.get("nope")


# ---------------------------------------------------------------------------
# HybridCache lifecycle + legacy parity
# ---------------------------------------------------------------------------


def test_plan_fill_and_evict_lifecycle(tmp_path):
    store = _store(tmp_path / "s")  # 16 chunks of 32 rows
    cache = _two_tier(store, capacity=3)
    plan = cache.plan_fill(np.arange(0, 256))  # chunks 0-7
    assert plan.chunks.tolist() == list(range(8))
    assert plan.fetch.tolist() == list(range(8))
    assert plan.modeled_ms(IOCost()) == 8 * IOCost().dfs_ms
    cache.fill(plan)
    assert cache.stats.fill_chunks == 8
    assert cache.contains(np.array([0, 255])).all()
    assert not cache.contains(np.array([256])).any()
    # incremental refill: already-resident chunks are not refetched
    plan2 = cache.plan_fill(np.arange(0, 288), reset=False)
    assert plan2.fetch.tolist() == [8]
    cache.fill(plan2)
    assert cache.stats.fill_chunks == 9
    # explicit eviction releases residency without touching the store
    writes_before = store.stats.chunk_writes
    assert cache.evict() > 0
    assert not cache.contains(np.arange(0, 288)).any()
    assert store.stats.chunk_writes == writes_before


def test_write_through_invalidates_cache(tmp_path):
    store = _store(tmp_path / "s")
    cache = _two_tier(store, capacity=4)
    cache.fill(cache.plan_fill(np.arange(64)))  # chunks 0-1
    cache.read_rows(np.arange(64))
    new = np.full((32, store.dim), 7.0, np.float32)
    cache.write_rows(np.arange(32), new)  # chunk 0 rewritten
    np.testing.assert_array_equal(cache.read_rows(np.arange(32)), new)


def test_hybrid_matches_legacy_two_level_accounting(tmp_path):
    """Acceptance: a memory+disk fifo HybridCache reproduces the historic
    fill_chunks/static_reads/dynamic_hits accounting, trace for trace."""
    trace = [0, 1, 2, 0, 1, 2, 3, 3, 0]
    store_a = _store(tmp_path / "a", rows=320, chunk_rows=32)  # 10 chunks
    store_b = _store(tmp_path / "b", rows=320, chunk_rows=32)
    legacy = TwoLevelCache(store_a, CachePolicy.FIFO, dynamic_frac=0.2)
    legacy.fill_static(np.arange(320))
    hybrid = HybridCache(
        store_b,
        build_tiers(("memory", "disk"), 32, store_b.dim),
        policy="fifo",
        dynamic_frac=0.2,
    )
    hybrid.fill(hybrid.plan_fill(np.arange(320)))
    for c in trace:
        rows = np.arange(c * 32, c * 32 + 16)
        np.testing.assert_array_equal(
            legacy.read_rows(rows), hybrid.read_rows(rows)
        )
    ls, hs = legacy.stats, hybrid.stats
    assert (ls.fill_chunks, ls.static_reads, ls.dynamic_hits, ls.rows_served) \
        == (hs.fill_chunks, hs.static_reads, hs.dynamic_hits, hs.rows_served)
    assert hs.fill_chunks == 10
    assert legacy.dynamic_capacity == 2
    assert ls.modeled_time_ms(IOCost()) == hs.modeled_time_ms(IOCost())


def test_fill_free_capacity_grows(tmp_path):
    """The historic bug: without fill_static, dynamic_capacity stayed 0 and
    the memory tier evicted on every insert, deadening LRU-vs-FIFO.  Now
    capacity tracks the chunks admitted below, so fill-free reuse hits."""
    store = _store(tmp_path / "s", rows=320, chunk_rows=32)
    cache = TwoLevelCache(store, CachePolicy.LRU, dynamic_frac=0.5)
    # no fill_static: demand-fault chunks 0-5, then re-read 4 and 5
    for c in [0, 1, 2, 3, 4, 5]:
        cache.read_rows(np.arange(c * 32, c * 32 + 4))
    assert cache.dynamic_capacity == 3  # grew with the 6 faulted chunks
    before = cache.stats.dynamic_hits
    cache.read_rows(np.arange(4 * 32, 6 * 32))  # repopulates chunks 4, 5
    cache.read_rows(np.arange(4 * 32, 6 * 32))  # both now memory hits
    assert cache.stats.dynamic_hits >= before + 3


def test_hybrid_single_memory_tier(tmp_path):
    """A one-tier stack (pure memory cache over DFS) works; demand faults
    count as static (non-memory) serves, never as tier hits, so the hit
    ratio stays honest on a cold trace."""
    store = _store(tmp_path / "s")
    cache = HybridCache(
        store,
        [MemoryTier(store.chunk_rows, store.dim, capacity=4)],
        policy="lru",
    )
    cache.read_rows(np.arange(0, 128))  # chunks 0-3 demand-faulted
    assert cache.stats.fill_chunks == 4
    assert cache.stats.demand_reads == 4
    assert cache.stats.static_reads == 4  # cold pass: all misses
    assert cache.stats.tiers[0].hits == 0
    got = cache.read_rows(np.arange(0, 128))  # warm pass: all memory hits
    np.testing.assert_array_equal(got, store.read_rows(np.arange(0, 128)))
    assert cache.stats.tiers[0].hits == 4
    assert cache.stats.dynamic_hit_ratio == 0.5  # 4 hits / 8 retrievals


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------


def _sum_layer(W):
    def layer(_k, h_self, h_nbr, seg):
        agg = np.zeros_like(h_self)
        if h_nbr.shape[0]:
            np.add.at(agg, seg, h_nbr)
        return np.tanh(np.concatenate([h_self, agg], axis=1) @ W)

    return layer


def test_engine_stores_identical_across_tier_configs(
    small_graph, sampling_client, tmp_path
):
    """Acceptance: the tier stack and policy change WHERE rows come from,
    never their values — final stores agree bit-for-bit across configs."""
    rng = np.random.default_rng(0)
    layers = [_sum_layer(rng.standard_normal((32, 16)).astype(np.float32) * 0.3)]
    BIG = 10**9
    results = {}
    configs = {
        "two_tier_fifo": dict(storage_tiers=("memory", "disk"), policy="fifo"),
        "two_tier_locality": dict(
            storage_tiers=("memory", "disk"), policy="locality"
        ),
        "disk_only": dict(storage_tiers=("disk",), policy="fifo"),
        "tiny_memory": dict(
            storage_tiers=("memory", "disk"),
            tier_capacities=(1, 0),
            policy="lru",
        ),
    }
    for name, kw in configs.items():
        res = LayerwiseInferenceEngine(
            small_graph, sampling_client, layers, small_graph.vertex_feats,
            str(tmp_path / name), fanouts=[BIG], chunk_rows=128,
            out_dims=[16], batch_size=512, **kw,
        ).run()
        ids = np.arange(small_graph.num_vertices)
        results[name] = res.final_store.read_rows(res.newid[ids])
    base = results.pop("two_tier_fifo")
    for name, got in results.items():
        # full fanout visits identical edges, but each run's sample order
        # permutes the float32 accumulation -> allclose, not bit equality
        np.testing.assert_allclose(
            base, got, rtol=1e-4, atol=1e-5, err_msg=name
        )


def test_engine_layer_stats_expose_tiers(
    small_graph, sampling_client, tmp_path
):
    rng = np.random.default_rng(0)
    layers = [_sum_layer(rng.standard_normal((32, 16)).astype(np.float32) * 0.3)]
    res = LayerwiseInferenceEngine(
        small_graph, sampling_client, layers, small_graph.vertex_feats,
        str(tmp_path), fanouts=[5], chunk_rows=128, out_dims=[16],
    ).run()
    tiers = res.layer_stats[0].tiers
    assert [t.kind for t in tiers] == ["memory", "disk"]
    # legacy CacheStats rollup mirrors the tier view (two-tier fifo config)
    assert res.layer_stats[0].cache.dynamic_hits == tiers[0].hits
    assert res.layer_stats[0].cache.static_reads == tiers[1].hits


# ---------------------------------------------------------------------------
# FeatureSource — the training path
# ---------------------------------------------------------------------------


def test_as_feature_source_shapes(small_graph):
    src = as_feature_source(small_graph.vertex_feats)
    assert src.shape == small_graph.vertex_feats.shape
    assert src is as_feature_source(src)
    rows = np.array([0, 5, 3])
    np.testing.assert_array_equal(
        src.gather(rows), small_graph.vertex_feats[rows]
    )


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 1_000), chunk_rows=st.sampled_from([64, 100, 256]))
def test_disk_backed_features_bit_identical_batches(
    seed, chunk_rows, small_graph, sampling_client, tmp_path_factory
):
    """Acceptance property: training batches built over a disk-backed
    feature store equal the in-memory ones bit for bit."""
    from repro.models.gnn.batching import subgraph_to_batch

    td = tmp_path_factory.mktemp(f"feats_{seed}_{chunk_rows}")
    rng = np.random.default_rng(seed)
    seeds = np.sort(
        rng.choice(small_graph.num_vertices, size=64, replace=False)
    )
    sub = sampling_client.sample_khop(seeds, [10, 5])
    src = StoreFeatureSource.from_array(
        small_graph.vertex_feats, str(td), chunk_rows=chunk_rows,
        policy="lru", dynamic_frac=0.3,
    )
    a = subgraph_to_batch(
        sub, small_graph.vertex_feats, small_graph.labels, 2,
        edge_types=small_graph.edge_types,
    )
    b = subgraph_to_batch(
        sub, src, small_graph.labels, 2, edge_types=small_graph.edge_types,
    )
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(x, y)
    assert src.stats.rows_served > 0  # the tiered path actually served
    # gathered into a given array: the same rows as the in-memory path's
    rows = sub.all_vertices()
    out = np.full((rows.shape[0], src.dim), np.nan, np.float32)
    assert src.gather(rows, out=out) is out
    np.testing.assert_array_equal(out, as_feature_source(small_graph.vertex_feats).gather(rows))


def test_pipeline_feature_source_end_to_end(
    small_graph, sampling_client, tmp_path
):
    """BatchPipeline with an out-of-core FeatureSource streams the same
    batches as the in-memory default (serial mode, same request keys)."""
    from repro.api.pipeline import BatchPipeline

    seeds = np.arange(128)
    BIG = 10**9  # full fanout: sampling is deterministic across pipelines
    kw = dict(
        fanouts=[BIG, BIG], num_layers=2, batch_size=64, prefetch=0, seed=0
    )
    mem = BatchPipeline(sampling_client, small_graph, seeds, **kw)
    src = StoreFeatureSource.from_array(
        small_graph.vertex_feats, str(tmp_path / "f"), chunk_rows=256
    )
    disk = BatchPipeline(
        sampling_client, small_graph, seeds, feature_source=src, **kw
    )
    for (sa, ba), (sb, bb) in zip(mem.batches(1), disk.batches(1)):
        np.testing.assert_array_equal(sa, sb)
        np.testing.assert_array_equal(np.asarray(ba.feats), np.asarray(bb.feats))
        np.testing.assert_array_equal(
            np.asarray(ba.labels), np.asarray(bb.labels)
        )

"""Layerwise inference engine: equivalence with samplewise, cache semantics,
reorder effect on chunk reads, bucketed-vs-reference engine equivalence, and
the CSR-offset gather property."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.inference import (
    ChunkedEmbeddingStore,
    LayerwiseInferenceEngine,
    TwoLevelCache,
    assign_inference_owners,
    csr_gather,
    samplewise_inference,
)
from repro.core.inference.cache import CachePolicy


def _mean_layer(W):
    def layer(_k, h_self, h_nbr, seg):
        agg = np.zeros_like(h_self)
        cnt = np.zeros(h_self.shape[0])
        if h_nbr.shape[0]:
            np.add.at(agg, seg, h_nbr)
            np.add.at(cnt, seg, 1.0)
        agg = agg / np.maximum(cnt, 1)[:, None]
        return np.tanh(np.concatenate([h_self, agg], axis=1) @ W)
    return layer


@pytest.fixture(scope="module")
def layers():
    rng = np.random.default_rng(0)
    return [
        _mean_layer(rng.standard_normal((32, 16)).astype(np.float32) * 0.3)
        for _ in range(2)
    ]


def test_layerwise_equals_samplewise_full_fanout(
    small_graph, sampling_client, layers, tmp_path
):
    BIG = 10**9
    eng = LayerwiseInferenceEngine(
        small_graph, sampling_client, layers, small_graph.vertex_feats,
        str(tmp_path), fanouts=[BIG, BIG], chunk_rows=128, out_dims=[16, 16],
    )
    res = eng.run()
    targets = np.arange(48)
    sw, _ = samplewise_inference(
        small_graph, sampling_client, layers, small_graph.vertex_feats,
        targets, fanouts=[BIG, BIG],
    )
    lw = res.final_store.read_rows_direct(res.newid[targets])
    np.testing.assert_allclose(lw, sw, rtol=1e-4, atol=1e-5)


def test_samplewise_redundancy(small_graph, sampling_client, layers):
    """Samplewise recomputes shared neighbors: vertex-layer computations for
    all N targets exceed the layerwise count (K·N)."""
    targets = np.arange(small_graph.num_vertices)[:500]
    _, st = samplewise_inference(
        small_graph, sampling_client, layers, small_graph.vertex_feats,
        targets, fanouts=[10, 10], batch_size=32,
    )
    layerwise_cost_for_targets = 2 * targets.shape[0]
    assert st["vertices_computed"] > 1.5 * layerwise_cost_for_targets


def test_owner_assignment(small_graph, sampling_client):
    owner = assign_inference_owners(sampling_client.router.mask, 4)
    assert owner.shape == (small_graph.num_vertices,)
    assert owner.min() >= 0 and owner.max() < 4
    counts = np.bincount(owner, minlength=4)
    # interior vertices are pinned to their partition; greedy balancing of the
    # boundary bounds the skew by the partition vertex balance
    assert counts.max() / counts.min() < 3.0


def test_store_roundtrip(tmp_path):
    store = ChunkedEmbeddingStore(str(tmp_path / "s"), 1000, 8, chunk_rows=64)
    rows = np.arange(0, 1000, 3)
    vals = np.random.default_rng(0).standard_normal((rows.shape[0], 8)).astype(np.float32)
    store.write_rows(rows, vals)
    got = store.read_rows_direct(rows)
    np.testing.assert_array_equal(got, vals)


def test_static_cache_guarantee(tmp_path):
    """After fill_static, reads never touch the DFS store again."""
    store = ChunkedEmbeddingStore(str(tmp_path / "s"), 512, 4, chunk_rows=32)
    store.write_rows(np.arange(512), np.ones((512, 4), np.float32))
    cache = TwoLevelCache(store, CachePolicy.FIFO, dynamic_frac=0.2)
    need = np.arange(0, 512, 2)
    cache.fill_static(need)
    dfs_reads_after_fill = store.stats.chunk_reads
    for _ in range(5):
        cache.read_rows(need)
    assert store.stats.chunk_reads == dfs_reads_after_fill  # 100% static hit
    # repeated reads of a working set within the dynamic capacity -> mem hits
    for _ in range(5):
        cache.read_rows(np.arange(0, 64))  # chunks 0-1, capacity is 3
    assert cache.stats.dynamic_hits >= 8


def test_fifo_eviction(tmp_path):
    store = ChunkedEmbeddingStore(str(tmp_path / "s"), 320, 4, chunk_rows=32)
    store.write_rows(np.arange(320), np.zeros((320, 4), np.float32))
    cache = TwoLevelCache(store, CachePolicy.FIFO, dynamic_frac=0.2)  # cap = 2
    cache.fill_static(np.arange(320))
    assert cache.dynamic_capacity == 2
    cache.read_rows(np.arange(0, 32))     # chunk 0
    cache.read_rows(np.arange(32, 64))    # chunk 1
    cache.read_rows(np.arange(64, 96))    # chunk 2 -> evicts 0
    st0 = cache.stats.static_reads
    cache.read_rows(np.arange(0, 32))     # chunk 0 again -> miss
    assert cache.stats.static_reads == st0 + 1


@settings(max_examples=20, deadline=None)
@given(n=st.integers(0, 40), seed=st.integers(0, 10_000))
def test_csr_gather_matches_naive(n, seed):
    """Property: the vectorized CSR-offset gather equals the naive
    per-segment slice-and-concatenate gather."""
    rng = np.random.default_rng(seed)
    values = rng.integers(0, 1000, size=500)
    starts = np.sort(rng.integers(0, 400, size=n))
    ends = np.minimum(starts + rng.integers(0, 20, size=n), values.shape[0])
    counts = ends - starts
    got = csr_gather(values, starts, counts)
    want = (
        np.concatenate([values[a:b] for a, b in zip(starts, ends)])
        if n
        else values[:0]
    )
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("kind", ["gcn", "sage", "gat", "hgt"])
def test_bucketed_engine_matches_reference(
    kind, small_graph, sampling_client, tmp_path
):
    """The device-resident shape-bucketed jit engine produces the same
    embeddings as the pre-optimization reference engine for every evaluated
    model kind (full fanout makes sampling deterministic across runs)."""
    import jax

    from repro.models.gnn import GNNModel

    model = GNNModel(kind, 16, hidden=16, num_layers=2, num_heads=2)
    params = model.init(jax.random.PRNGKey(0))
    fns = [model.embed_layer_fn(params, k) for k in range(2)]
    BIG = 10**9
    kw = dict(fanouts=[BIG, BIG], chunk_rows=128, out_dims=[16, 16])
    ref = LayerwiseInferenceEngine(
        small_graph, sampling_client, fns, small_graph.vertex_feats,
        str(tmp_path / "ref"), mode="reference", **kw,
    ).run()
    new = LayerwiseInferenceEngine(
        small_graph, sampling_client, fns, small_graph.vertex_feats,
        str(tmp_path / "new"), mode="bucketed", batch_size=512, **kw,
    ).run()
    ids = np.arange(small_graph.num_vertices)
    a = ref.final_store.read_rows_direct(ref.newid[ids])
    b = new.final_store.read_rows_direct(new.newid[ids])
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    assert new.slice_compiles > 0  # the jit path actually ran


def test_full_chunk_write_skips_read_modify_write(tmp_path):
    """A write covering every row of a chunk stores the values directly;
    partial writes still preserve the untouched rows."""
    store = ChunkedEmbeddingStore(str(tmp_path / "s"), 100, 4, chunk_rows=32)
    rng = np.random.default_rng(0)
    vals = rng.standard_normal((100, 4)).astype(np.float32)
    store.write_rows(np.arange(100), vals)  # full chunks incl. ragged last
    np.testing.assert_array_equal(store.read_rows_direct(np.arange(100)), vals)
    patch = np.full((2, 4), 7.0, np.float32)
    store.write_rows(np.array([1, 5]), patch)  # partial -> RMW path
    got = store.read_rows_direct(np.arange(100))
    assert (got[[1, 5]] == 7.0).all()
    keep = np.setdiff1d(np.arange(100), [1, 5])
    np.testing.assert_array_equal(got[keep], vals[keep])


def test_pds_reduces_chunk_reads(small_graph, sampling_client, layers, tmp_path):
    """Fig. 14b: PDS ordering reads no more chunks than natural order."""
    reads = {}
    for alg in ("NS", "PDS"):
        eng = LayerwiseInferenceEngine(
            small_graph, sampling_client, layers, small_graph.vertex_feats,
            str(tmp_path / alg), fanouts=[10, 10], chunk_rows=64,
            out_dims=[16, 16], reorder_alg=alg, batch_size=256,
            dynamic_frac=0.1,
        )
        res = eng.run()
        reads[alg] = res.total_chunk_reads() + sum(
            s.cache.fill_chunks for s in res.layer_stats
        )
    assert reads["PDS"] <= reads["NS"], reads


def test_engine_reuse_no_recompile_across_calls(small_graph, tmp_path):
    """Repeat ``infer_layerwise`` calls with identical arguments reuse one
    engine (GLISPSystem caches it by resolved-parameter signature), so the
    second call re-runs entirely out of the jit caches: zero retraces,
    which ``recompile_guard`` asserts against the (layer, bucket) bound."""
    import jax

    from repro.analysis import recompile_guard
    from repro.api import GLISPConfig, GLISPSystem
    from repro.models.gnn import GNNModel

    system = GLISPSystem.build(
        small_graph, GLISPConfig(num_parts=4, fanouts=(8, 4))
    )
    model = GNNModel("sage", 16, hidden=16, num_layers=2)
    params = model.init(jax.random.PRNGKey(0))
    fns = [model.embed_layer_fn(params, k) for k in range(2)]
    wd = str(tmp_path / "emb")
    kw = dict(chunk_rows=128, out_dims=[16, 16], batch_size=512)
    assert system.infer_engine is None
    with recompile_guard(system) as rec:
        system.infer_layerwise(fns, wd, **kw)
        engine = system.infer_engine
        assert engine is not None and engine.jit_trace_count() > 0
        with recompile_guard(system) as rec2:
            system.infer_layerwise(fns, wd, **kw)
        assert system.infer_engine is engine  # same engine, same jit caches
        assert (rec2.compiles, rec2.new_shapes) == (0, 0)
    assert rec.compiles == rec.new_shapes > 0

    # a different resolved signature must NOT reuse the cached engine
    system.infer_layerwise(fns, str(tmp_path / "emb2"), **kw)
    assert system.infer_engine is not engine


def test_layer_stats_padding_counters(small_graph, sampling_client, tmp_path):
    """The bucketed engine accounts real vs padded rows per layer: the
    waste the ragged kernels' tile skip saves is visible in LayerStats."""
    import jax

    from repro.models.gnn import GNNModel

    model = GNNModel("sage", 16, hidden=16, num_layers=2)
    params = model.init(jax.random.PRNGKey(0))
    fns = [model.embed_layer_fn(params, k) for k in range(2)]
    eng = LayerwiseInferenceEngine(
        small_graph, sampling_client, fns, small_graph.vertex_feats,
        str(tmp_path), fanouts=[10, 10], chunk_rows=128, out_dims=[16, 16],
        batch_size=256,
    )
    res = eng.run()
    for s in res.layer_stats:
        assert 0 < s.batch_rows <= s.padded_rows
        assert 0 < s.batch_edges <= s.padded_edges
        assert 0.0 < s.occupancy() <= 1.0
        assert 0.0 < s.edge_occupancy() <= 1.0
        # batches land in (vertex-bucket, edge-bucket) bins; the bin counts
        # must add up to the dispatched batches and every bin is a padded
        # shape (at least as large as one real row)
        assert sum(s.bucket_batches.values()) >= 1
        for bp, ep in s.bucket_batches:
            assert bp >= 1 and ep >= 1 and bp <= eng.batch_size


def test_engine_kernel_autotune_before_first_trace(
    small_graph, sampling_client, tmp_path
):
    """kernel_autotune=True sweeps each advertised (op, shape) before the
    bucket's first jit trace, so tuned blocks bake into the one compile per
    (layer, bucket) — recompile_guard still holds with kernels enabled."""
    import os

    import jax

    from repro.analysis import recompile_guard
    from repro.kernels import autotune as at
    from repro.models.gnn import GNNModel

    at.reset()
    try:
        model = GNNModel("sage", 16, hidden=16, num_layers=2)
        params = model.init(jax.random.PRNGKey(0))
        fns = [model.embed_layer_fn(params, k) for k in range(2)]
        cache = str(tmp_path / "tune")
        eng = LayerwiseInferenceEngine(
            small_graph, sampling_client, fns, small_graph.vertex_feats,
            str(tmp_path / "emb"), fanouts=[8, 4], chunk_rows=128,
            out_dims=[16, 16], batch_size=512, use_kernel=True,
            kernel_autotune=True, kernel_cache_dir=cache,
        )
        with recompile_guard(eng) as rec:
            res = eng.run()
        assert res.slice_compiles > 0
        assert rec.compiles == rec.new_shapes  # one compile per (layer, bucket)
        assert at.stats()["measured"] > 0
        assert os.path.exists(at.artifact_path(cache))
        import json as _json

        configs = _json.load(open(at.artifact_path(cache)))["configs"]
        assert any(k.startswith("segment_spmm_ragged/") for k in configs)
        # a second run re-uses both the tuned table and the jit caches
        with recompile_guard(eng) as rec2:
            eng.run()
        assert (rec2.compiles, rec2.new_shapes) == (0, 0)
    finally:
        at.reset()

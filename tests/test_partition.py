"""Partitioner quality and invariants (paper Table II claims at small scale),
the ``Partitioner`` protocol / ``PartitionPlan`` scorecard, the lockstep-vs-
loop AdaDNE equivalence gate, and the cached partition pipeline."""
import functools

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.partition import (
    PARTITIONERS,
    NEConfig,
    NeighborExpansionPartitioner,
    Partitioner,
    PartitionPipeline,
    PartitionPlan,
    adadne,
    distributed_ne,
    hash2d_partition,
    ldg_edge_cut,
    random_edge_partition,
)
from repro.core.partition.dne import _flush_sequence, _iteration_budgets
from repro.graph import power_law_graph
from repro.graph.metrics import (
    metrics_from_edge_assignment,
    metrics_from_vertex_assignment,
)


@pytest.fixture(scope="module")
def g():
    return power_law_graph(8000, avg_degree=10, seed=11)


def test_all_edges_assigned(g):
    for fn in (adadne, distributed_ne, hash2d_partition, random_edge_partition):
        ep = fn(g, 8, seed=0)
        assert ep.shape == (g.num_edges,)
        assert ep.min() >= 0 and ep.max() < 8


def test_adadne_balance(g):
    m = metrics_from_edge_assignment(g, adadne(g, 8, seed=0), 8)
    assert m["VB"] < 1.5, m
    assert m["EB"] < 1.4, m
    assert 1.0 <= m["RF"] < 4.0, m


def test_adadne_beats_random_rf(g):
    m_ada = metrics_from_edge_assignment(g, adadne(g, 8, seed=0), 8)
    m_rnd = metrics_from_edge_assignment(g, random_edge_partition(g, 8, 0), 8)
    assert m_ada["RF"] < m_rnd["RF"]


def test_adadne_vb_eb_vs_dne(g):
    """Paper Table II: AdaDNE suppresses VB/EB relative to DistributedNE
    (averaged over seeds to avoid flakiness)."""
    vb_a, eb_a, vb_d, eb_d = [], [], [], []
    for s in range(3):
        ma = metrics_from_edge_assignment(g, adadne(g, 8, seed=s), 8)
        md = metrics_from_edge_assignment(g, distributed_ne(g, 8, seed=s), 8)
        vb_a.append(ma["VB"]); eb_a.append(ma["EB"])
        vb_d.append(md["VB"]); eb_d.append(md["EB"])
    assert np.mean(vb_a) <= np.mean(vb_d) * 1.1
    assert np.mean(eb_a) <= np.mean(eb_d) * 1.1


def test_edge_cut_metrics(g):
    vp = ldg_edge_cut(g, 4, seed=0)
    assert vp.shape == (g.num_vertices,)
    m = metrics_from_vertex_assignment(g, vp, 4)
    assert m["RF"] >= 1.0


def test_hash2d_replication_bound(g):
    """2D hash: RF bounded by rows + cols - 1."""
    m = metrics_from_edge_assignment(g, hash2d_partition(g, 16, 0), 16)
    assert m["RF"] <= 4 + 4 - 1 + 0.01


# ---------------------------------------------------------------------------
# Partitioner protocol + PartitionPlan scorecard
# ---------------------------------------------------------------------------


def test_registry_entries_implement_protocol(g):
    expected = {"adadne", "adadne_loop", "dne", "dne_loop", "ldg", "hash2d", "random"}
    assert expected <= set(PARTITIONERS.names())
    for name in expected:
        entry = PARTITIONERS.get(name)
        assert isinstance(entry, Partitioner), name
        assert entry.name == name


def test_plan_scorecard_matches_metrics(g):
    for name in ("adadne", "ldg", "hash2d"):
        plan = PARTITIONERS.get(name).partition(g, 4, seed=0)
        assert isinstance(plan, PartitionPlan)
        assert plan.num_parts == 4 and plan.partitioner == name
        m = metrics_from_edge_assignment(g, plan.edge_parts, 4)
        assert plan.replication_factor == pytest.approx(m["RF"])
        assert plan.vertex_balance == pytest.approx(m["VB"])
        assert plan.edge_balance == pytest.approx(m["EB"])
        assert plan.edge_counts.tolist() == m["edges"]
        assert plan.vertex_counts.tolist() == m["vertices"]
        assert plan.metrics()["RF"] == plan.replication_factor
    # instances stay callable like the old registry functions
    plan = PARTITIONERS.get("random")(g, 4, seed=1)
    assert isinstance(plan, PartitionPlan)


def test_ldg_plan_has_vertex_owner_and_direction(g):
    plan = PARTITIONERS.get("ldg").partition(g, 4, seed=0, direction="out")
    assert plan.vertex_owner is not None
    np.testing.assert_array_equal(
        plan.edge_parts, plan.vertex_owner[g.src].astype(np.int16)
    )
    plan_in = PARTITIONERS.get("ldg").partition(g, 4, seed=0, direction="in")
    np.testing.assert_array_equal(
        plan_in.edge_parts, plan_in.vertex_owner[g.dst].astype(np.int16)
    )


def test_adadne_iteration_trace(g):
    plan = PARTITIONERS.get("adadne").partition(g, 4, seed=0)
    tr = plan.iteration_trace
    assert tr is not None
    iters = tr["remaining"].shape[0]
    assert iters > 1
    assert tr["edge_counts"].shape == (iters, 4)
    assert tr["lam"].shape == (iters, 4)
    # remaining decreases to 0 and edge counts grow monotonically
    assert tr["remaining"][-1] == 0 or tr["remaining"][-1] < tr["remaining"][0]
    assert (np.diff(tr["edge_counts"], axis=0) >= 0).all()
    assert tr["edge_counts"][-1].sum() <= g.num_edges


# ---------------------------------------------------------------------------
# lockstep vs loop: determinism + statistical equivalence gate
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def _prop_graph():
    return power_law_graph(4000, avg_degree=8, seed=23)


@settings(max_examples=3, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**16), parts=st.integers(2, 8))
def test_property_adadne_quality_and_determinism(seed, parts):
    """Both implementations: all edges assigned, balance within the soft
    bounds, bit-identical across runs at a fixed seed — and the two
    implementations statistically equivalent (the refactor's gate)."""
    gg = _prop_graph()
    plans = {}
    for mode in ("lockstep", "loop"):
        part = NeighborExpansionPartitioner(adaptive=True, mode=mode)
        plan = part.partition(gg, parts, seed=seed)
        again = part.partition(gg, parts, seed=seed)
        np.testing.assert_array_equal(
            plan.edge_parts, again.edge_parts
        ), f"{mode} nondeterministic"
        assert plan.edge_parts.shape == (gg.num_edges,)
        assert plan.edge_parts.min() >= 0 and plan.edge_parts.max() < parts
        assert plan.vertex_balance < 1.8, (mode, plan.metrics())
        assert plan.edge_balance < 1.6, (mode, plan.metrics())
        assert 1.0 <= plan.replication_factor < parts
        plans[mode] = plan
    a, b = plans["lockstep"], plans["loop"]
    assert a.vertex_balance == pytest.approx(b.vertex_balance, abs=0.35)
    assert a.edge_balance == pytest.approx(b.edge_balance, abs=0.35)
    assert a.replication_factor == pytest.approx(b.replication_factor, rel=0.15)


def test_legacy_shims_match_registry(g):
    np.testing.assert_array_equal(
        adadne(g, 4, seed=3),
        PARTITIONERS.get("adadne").partition(g, 4, seed=3).edge_parts,
    )
    np.testing.assert_array_equal(
        distributed_ne(g, 4, seed=3, mode="loop"),
        PARTITIONERS.get("dne_loop").partition(g, 4, seed=3).edge_parts,
    )


def test_ne_config_legacy_call_style(g):
    """Old style — cfg carries num_parts/seed, partition(g) — still works."""
    part = NeighborExpansionPartitioner(NEConfig(num_parts=4, adaptive=True, seed=5))
    plan = part.partition(g)
    assert plan.num_parts == 4 and plan.seed == 5
    np.testing.assert_array_equal(plan.edge_parts, adadne(g, 4, seed=5))


# ---------------------------------------------------------------------------
# budgets fix + vectorized stall flush
# ---------------------------------------------------------------------------


def test_iteration_budgets_zero_for_terminated():
    lam = np.full(4, 0.1)
    bsize = np.array([10, 0, 500, 20], dtype=np.int64)
    term = np.array([False, True, True, False])
    budgets = _iteration_budgets(lam, bsize, term, E=100_000, budget_frac=0.01)
    assert (budgets[term] == 0).all()  # hard threshold honored exactly
    assert (budgets[~term] >= 16).all()
    # un-terminated vector reproduces the original proportional split
    none = np.zeros(4, dtype=bool)
    b2 = _iteration_budgets(lam, bsize, none, E=100_000, budget_frac=0.01)
    w = lam * np.maximum(bsize, 1.0)
    want = np.maximum(16, 0.01 * 100_000 * w / w.sum()).astype(np.int64)
    np.testing.assert_array_equal(b2, want)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**16), parts=st.integers(1, 12), k=st.integers(0, 400))
def test_flush_sequence_matches_naive_greedy(seed, parts, k):
    rng = np.random.default_rng(seed)
    nE = rng.integers(0, 50, size=parts).astype(np.int64)
    seq = _flush_sequence(nE.copy(), k)
    # naive replay: each edge to the current argmin (lowest index on ties)
    cur = nE.copy()
    want = np.empty(k, dtype=np.int16)
    for i in range(k):
        p = int(np.argmin(cur))
        want[i] = p
        cur[p] += 1
    np.testing.assert_array_equal(seq, want)
    if k:
        np.testing.assert_array_equal(
            np.bincount(seq, minlength=parts) + nE, cur
        )


# ---------------------------------------------------------------------------
# chunked LDG
# ---------------------------------------------------------------------------


def test_ldg_chunked_determinism_and_balance(g):
    a = ldg_edge_cut(g, 4, seed=9)
    b = ldg_edge_cut(g, 4, seed=9)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (g.num_vertices,)
    assert a.min() >= 0 and a.max() < 4
    sizes = np.bincount(a, minlength=4)
    cap = 1.05 * g.num_vertices / 4
    # within-chunk placements can't see each other, so the hard cap can
    # drift by at most one chunk
    assert sizes.max() <= cap + 256
    # locality objective: most neighbors co-located vs a random assignment
    same = (a[g.src] == a[g.dst]).mean()
    rng = np.random.default_rng(0)
    rand = rng.integers(0, 4, g.num_vertices).astype(np.int16)
    assert same > (rand[g.src] == rand[g.dst]).mean()


# ---------------------------------------------------------------------------
# the cached partition -> reorder -> materialize pipeline
# ---------------------------------------------------------------------------


def test_pipeline_stages_no_cache(g):
    pipe = PartitionPipeline("adadne", 4, reorder="pds", seed=0)
    res = pipe.run(g)
    assert not res.cache_hit and res.cache_key is None
    assert len(res.partitions) == 4
    assert sum(p.num_edges for p in res.partitions) == g.num_edges
    assert sorted(res.perm.tolist()) == list(range(g.num_vertices))
    assert set(res.seconds) == {"partition", "reorder", "materialize"}
    np.testing.assert_array_equal(
        res.plan.edge_parts, adadne(g, 4, seed=0)
    )


def test_pipeline_cache_roundtrip(g, tmp_path):
    cache = str(tmp_path / "pcache")
    pipe = PartitionPipeline("adadne", 4, reorder="pds", seed=0, cache_dir=cache)
    first = pipe.run(g)
    assert not first.cache_hit
    second = pipe.run(g)
    assert second.cache_hit and second.cache_key == first.cache_key
    np.testing.assert_array_equal(first.plan.edge_parts, second.plan.edge_parts)
    np.testing.assert_array_equal(first.perm, second.perm)
    assert second.plan.replication_factor == pytest.approx(
        first.plan.replication_factor
    )
    assert second.plan.edge_counts.tolist() == first.plan.edge_counts.tolist()
    # a config change must miss (different content address)
    other = PartitionPipeline("adadne", 4, reorder="pds", seed=1, cache_dir=cache)
    assert other.cache_key(g) != pipe.cache_key(g)
    assert not other.run(g).cache_hit


def test_pipeline_cache_key_covers_hyperparameters(g, tmp_path):
    """Differently-configured instances of one algorithm never share an
    artifact: the instance's cache_token (name + hyperparameters) is part
    of the content address."""
    cache = str(tmp_path / "pcache")
    default = PartitionPipeline("adadne", 4, seed=0, cache_dir=cache)
    default.run(g)
    custom = PartitionPipeline(
        NeighborExpansionPartitioner(adaptive=True, lam0=0.9, alpha=3.0),
        4,
        seed=0,
        cache_dir=cache,
    )
    assert custom.cache_key(g) != default.cache_key(g)
    assert not custom.run(g).cache_hit


def test_pipeline_corrupt_artifact_recomputes(g, tmp_path):
    cache = str(tmp_path / "pcache")
    pipe = PartitionPipeline("adadne", 4, seed=0, cache_dir=cache)
    first = pipe.run(g)
    path = pipe._cache_path(pipe.cache_key(g))
    with open(path, "r+b") as fh:
        fh.seek(10)
        fh.write(b"\xde\xad\xbe\xef" * 8)
    again = pipe.run(g)  # must not raise BadZipFile
    assert not again.cache_hit
    np.testing.assert_array_equal(first.plan.edge_parts, again.plan.edge_parts)
    assert pipe.run(g).cache_hit  # the recompute republished a good artifact


def test_pipeline_cache_keeps_vertex_owner(g, tmp_path):
    cache = str(tmp_path / "pcache")
    pipe = PartitionPipeline("ldg", 4, seed=0, cache_dir=cache)
    first = pipe.run(g)
    second = pipe.run(g)
    assert second.cache_hit
    np.testing.assert_array_equal(first.plan.vertex_owner, second.plan.vertex_owner)


def test_system_build_reports_cache_hit(g, tmp_path):
    from repro.api import GLISPConfig, GLISPSystem

    cfg = GLISPConfig(
        num_parts=4,
        fanouts=(4,),
        partition_cache_dir=str(tmp_path / "syscache"),
    ).validate()
    s1 = GLISPSystem.build(g, cfg)
    assert not s1.partition_cache_hit
    s2 = GLISPSystem.build(g, cfg)
    assert s2.partition_cache_hit
    # near-zero partition stage on the hit: loading beats repartitioning
    assert s2.partition_seconds < max(0.25, 0.5 * s1.partition_seconds)
    np.testing.assert_array_equal(s1.plan.edge_parts, s2.plan.edge_parts)
    np.testing.assert_array_equal(s1.reorder_perm, s2.reorder_perm)
    # identically-seeded systems sample identically whichever path built them
    a = s1.sample(np.arange(32), fanouts=[4])
    b = s2.sample(np.arange(32), fanouts=[4])
    for ha, hb in zip(a.hops, b.hops):
        np.testing.assert_array_equal(ha.src, hb.src)
        np.testing.assert_array_equal(ha.dst, hb.dst)


def test_config_validates_cache_dir():
    from repro.api import GLISPConfig

    with pytest.raises(ValueError, match="partition_cache_dir"):
        GLISPConfig(partition_cache_dir="").validate()
    GLISPConfig(partition_cache_dir=None).validate()

"""Sampling service: algorithm distributions, Gather-Apply correctness,
load-balance accounting."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from scipy.stats import chisquare

from repro.core.sampling import EdgeCutClient, SamplingServer
from repro.core.sampling.algorithms import algorithm_a_es, algorithm_d, uniform_sample


def test_algorithm_d_marginals():
    """Every index equally likely (chi-square, n=30 k=6)."""
    rng = np.random.default_rng(0)
    counts = np.zeros(30)
    trials = 3000
    for _ in range(trials):
        idx = algorithm_d(30, 6, rng)
        assert idx.shape == (6,)
        assert (np.diff(idx) > 0).all()  # increasing order, no repeats
        counts[idx] += 1
    _, p = chisquare(counts)
    assert p > 1e-4, (p, counts)


def test_algorithm_d_edge_cases():
    rng = np.random.default_rng(1)
    assert algorithm_d(5, 0, rng).shape == (0,)
    assert (algorithm_d(5, 5, rng) == np.arange(5)).all()
    assert (algorithm_d(5, 9, rng) == np.arange(5)).all()
    for _ in range(50):
        out = algorithm_d(100, 1, rng)
        assert 0 <= out[0] < 100


def test_uniform_sample_matches_vitter_distribution():
    """Vectorized path and Vitter's Algorithm D draw the same distribution."""
    rng1, rng2 = np.random.default_rng(2), np.random.default_rng(3)
    c1, c2 = np.zeros(20), np.zeros(20)
    for _ in range(3000):
        c1[uniform_sample(20, 4, rng1, use_vitter=False)] += 1
        c2[uniform_sample(20, 4, rng2, use_vitter=True)] += 1
    # both uniform: compare each against uniform expectation
    for c in (c1, c2):
        _, p = chisquare(c)
        assert p > 1e-4


def test_a_es_top1_frequencies():
    """P(top-1 = i) == w_i / Σw for A-ES."""
    rng = np.random.default_rng(4)
    w = np.array([1.0, 2.0, 4.0, 8.0])
    counts = np.zeros(4)
    trials = 20000
    for _ in range(trials):
        idx, _ = algorithm_a_es(w, 1, rng)
        counts[idx[0]] += 1
    expected = w / w.sum() * trials
    _, p = chisquare(counts, expected)
    assert p > 1e-4, (counts, expected)


def test_a_es_zero_weight_excluded():
    rng = np.random.default_rng(5)
    w = np.array([0.0, 1.0, 0.0, 1.0])
    for _ in range(100):
        idx, sc = algorithm_a_es(w, 2, rng)
        assert set(idx.tolist()) == {1, 3}


def test_full_fanout_returns_all_neighbors(small_graph, sampling_client):
    """fanout >= global degree => every neighbor returned exactly once per
    edge (the Gather-Apply merge is lossless)."""
    rng = np.random.default_rng(6)
    seeds = rng.choice(small_graph.num_vertices, 40, replace=False)
    sub = sampling_client.sample_khop(seeds, [10**9], direction="out")
    hop = sub.hops[0]
    for v in seeds:
        got = sorted(hop.dst[hop.src == v].tolist())
        want = sorted(small_graph.neighbors(int(v), "out").tolist())
        assert got == want, f"vertex {v}"


def test_weighted_full_fanout(small_graph, sampling_client):
    seeds = np.arange(30)
    sub = sampling_client.sample_khop(seeds, [10**9], weighted=True, direction="out")
    hop = sub.hops[0]
    for v in seeds:
        got = sorted(hop.dst[hop.src == v].tolist())
        want = sorted(small_graph.neighbors(int(v), "out").tolist())
        assert got == want


def test_fanout_respected(small_graph, sampling_client):
    seeds = np.arange(100)
    for weighted in (False, True):
        sub = sampling_client.sample_khop(seeds, [5, 3], weighted=weighted)
        for f, hop in zip([5, 3], sub.hops):
            if hop.src.shape[0] == 0:
                continue
            _, counts = np.unique(hop.src, return_counts=True)
            assert counts.max() <= f


def test_sampled_edges_are_real(small_graph, sampling_client):
    seeds = np.arange(50)
    sub = sampling_client.sample_khop(seeds, [8, 4])
    edge_set = set(zip(small_graph.src.tolist(), small_graph.dst.tolist()))
    for hop in sub.hops:
        for s, d in zip(hop.src.tolist(), hop.dst.tolist()):
            assert (s, d) in edge_set


def test_in_direction_sampling(small_graph, sampling_client):
    seeds = np.arange(30)
    sub = sampling_client.sample_khop(seeds, [10**9], direction="in")
    hop = sub.hops[0]
    for v in seeds[:10]:
        got = sorted(hop.dst[hop.src == v].tolist())
        want = sorted(small_graph.neighbors(int(v), "in").tolist())
        assert got == want


def test_workload_accounting(sampling_client):
    sampling_client.reset_stats()
    sampling_client.sample_khop(np.arange(200), [10, 5], weighted=True)
    wl = sampling_client.server_workloads()
    assert (wl > 0).all()
    sampling_client.reset_stats()
    assert sampling_client.server_workloads().sum() == 0


def test_glisp_balances_better_than_edge_cut(small_graph):
    """Fig. 10: normalized workload spread of the Gather-Apply client is
    tighter than the DistDGL-style edge-cut client on a power-law graph."""
    from repro.core.partition import adadne, ldg_edge_cut, edge_cut_to_edge_assignment
    from repro.core.sampling import GatherApplyClient, VertexRouter
    from repro.graph import build_partitions

    g = small_graph
    P = 4
    ep = adadne(g, P, seed=1)
    parts = build_partitions(g, ep, P)
    glisp = GatherApplyClient(
        [SamplingServer(p, seed=0) for p in parts], VertexRouter(g, ep, P), seed=0
    )
    vp = ldg_edge_cut(g, P, seed=1)
    # strict DistDGL layout: in-edges local to the owner, sampled with "in"
    ec_parts = build_partitions(
        g, edge_cut_to_edge_assignment(g, vp, local_direction="in"), P
    )
    ec = EdgeCutClient(
        [SamplingServer(p, seed=0) for p in ec_parts], vp.astype(np.int64), seed=0
    )
    rng = np.random.default_rng(0)
    seeds = rng.choice(g.num_vertices, 512, replace=False)
    glisp.sample_khop(seeds, [15, 10, 5], weighted=True, direction="out")
    ec.sample_khop(seeds, [15, 10, 5], weighted=True, direction="in")
    wl_g = glisp.server_workloads()
    wl_e = ec.server_workloads()
    imb_g = wl_g.max() / wl_g.min()
    imb_e = wl_e.max() / wl_e.min()
    assert imb_g < imb_e, (imb_g, imb_e)


@settings(max_examples=15, deadline=None)
@given(f=st.integers(1, 20), seed=st.integers(0, 100))
def test_property_weighted_topk_merge(f, seed):
    """Distributed A-ES == single-machine A-ES given identical scores: global
    top-f of per-server top-f equals top-f of the union."""
    rng = np.random.default_rng(seed)
    n = 50
    scores = rng.random(n)
    shards = np.array_split(np.arange(n), 3)
    local_top = []
    for sh in shards:
        order = sh[np.argsort(-scores[sh])][:f]
        local_top.extend(order.tolist())
    merged = sorted(local_top, key=lambda i: -scores[i])[:f]
    want = np.argsort(-scores)[:f].tolist()
    assert merged == want

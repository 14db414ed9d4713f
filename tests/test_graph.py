"""Graph substrate: partition structure invariants, queries, persistence."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.partition import random_edge_partition
from repro.graph import GraphPartition, build_partitions, power_law_graph
from repro.graph.graph import HeteroGraph
from repro.graph.metrics import metrics_from_edge_assignment
from repro.graph.reorder import reorder_permutation


def test_partition_edge_conservation(small_graph, partitioned):
    ep, parts = partitioned
    assert sum(p.num_edges for p in parts) == small_graph.num_edges


def test_global_local_roundtrip(partitioned):
    _, parts = partitioned
    for p in parts:
        lids = np.arange(p.num_vertices)
        gids = p.local_to_global(lids)
        assert (p.global_to_local(gids) == lids).all()
        # missing ids return -1
        missing = np.array([10**12])
        assert p.global_to_local(missing)[0] == -1


def test_partition_neighbors_match_graph(small_graph, partitioned):
    """Union of per-partition out-neighbors == true out-neighbors."""
    ep, parts = partitioned
    rng = np.random.default_rng(0)
    for v in rng.choice(small_graph.num_vertices, 20, replace=False):
        true_nbrs = sorted(small_graph.neighbors(int(v), "out").tolist())
        got = []
        for p in parts:
            lid = p.global_to_local(np.array([v]))[0]
            if lid < 0:
                continue
            nbrs, _ = p.out_neighbors(int(lid))
            got.extend(p.local_to_global(nbrs).tolist())
        assert sorted(got) == true_nbrs


def test_edge_type_query(partitioned, small_graph):
    """edge_type_of (O(log) aggregated index) matches a direct recompute."""
    _, parts = partitioned
    p = parts[0]
    n = min(500, p.num_edges)
    et = p.edge_type_of(np.arange(n))
    # recompute: for each vertex the CSR slice is sorted by type with counts
    # in the aggregated index; check types are sorted within each vertex
    for lid in range(min(50, p.num_vertices)):
        s, e = p.out_indptr[lid], p.out_indptr[lid + 1]
        if e - s < 2 or e > n:
            continue
        tv = et[s:e]
        assert (np.diff(tv) >= 0).all()


def test_etype_filtered_neighbors(partitioned):
    _, parts = partitioned
    p = parts[0]
    for lid in range(min(30, p.num_vertices)):
        all_nbrs, all_eids = p.out_neighbors(lid)
        per_type = []
        ts, te = p.out_et_indptr[lid], p.out_et_indptr[lid + 1]
        for t in p.out_et_types[ts:te]:
            nbrs, eids = p.out_neighbors(lid, etype=int(t))
            per_type.extend(nbrs.tolist())
        assert sorted(per_type) == sorted(all_nbrs.tolist())


def test_save_load_roundtrip(tmp_path, partitioned):
    _, parts = partitioned
    p = parts[1]
    p.save(str(tmp_path / "p1"))
    q = GraphPartition.load(str(tmp_path / "p1"))
    for f in ("global_id", "out_indptr", "out_dst", "in_src", "partition_bits"):
        assert (getattr(p, f) == getattr(q, f)).all()


def test_memory_accounting(partitioned):
    _, parts = partitioned
    for p in parts:
        assert p.memory_bytes() > 0
        assert p.memory_bytes() < 50 * (p.num_edges + p.num_vertices) * 8


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(20, 200),
    e=st.integers(30, 400),
    parts=st.integers(2, 6),
    seed=st.integers(0, 1000),
)
def test_property_partition_invariants(n, e, parts, seed):
    """Any vertex-cut edge assignment yields a consistent structure."""
    rng = np.random.default_rng(seed)
    g = HeteroGraph(
        num_vertices=n,
        src=rng.integers(0, n, e),
        dst=rng.integers(0, n, e),
        edge_types=rng.integers(0, 3, e).astype(np.int16),
        vertex_types=rng.integers(0, 2, n).astype(np.int16),
        edge_weights=rng.random(e).astype(np.float32),
    )
    ep = random_edge_partition(g, parts, seed)
    built = build_partitions(g, ep, parts)
    assert sum(p.num_edges for p in built) == e
    m = metrics_from_edge_assignment(g, ep, parts)
    assert m["RF"] >= 1.0 or g.num_vertices > sum(m["vertices"])
    for p in built:
        # CSR consistent
        assert p.out_indptr[-1] == p.num_edges
        assert p.in_indptr[-1] == p.num_edges
        assert (np.sort(p.in_edge_id) == np.arange(p.num_edges)).all()
        # global degrees >= local degrees
        assert (p.local_out_degree(np.arange(p.num_vertices)) <= p.out_degrees).all()


def test_reorder_permutations(small_graph):
    deg = small_graph.out_degrees() + small_graph.in_degrees()
    gids = np.arange(small_graph.num_vertices)
    pid = np.random.default_rng(0).integers(0, 4, small_graph.num_vertices)
    for alg in ("NS", "DS", "PS", "PDS"):
        perm = reorder_permutation(alg, global_ids=gids, degrees=deg, partition_ids=pid)
        assert sorted(perm.tolist()) == list(range(small_graph.num_vertices))
    pds = reorder_permutation("PDS", global_ids=gids, degrees=deg, partition_ids=pid)
    # PDS: partition ids non-decreasing; degree non-increasing within groups
    assert (np.diff(pid[pds]) >= 0).all()


def _assert_bfs_visit_order(indptr, indices, members, order):
    """``order`` must be a real BFS of the induced (symmetrized) subgraph:
    components contiguous, and within a component the visit order follows
    non-decreasing BFS layers from that component's first-visited vertex."""
    assert sorted(order.tolist()) == sorted(members.tolist())
    mset = set(int(v) for v in members)
    adj = {v: set() for v in mset}
    for v in mset:
        for u in indices[indptr[v] : indptr[v + 1]]:
            u = int(u)
            if u in mset:
                adj[v].add(u)
                adj[u].add(v)
    i, n = 0, len(order)
    while i < n:
        start = int(order[i])
        level = {start: 0}
        frontier = [start]
        while frontier:
            nxt = []
            for v in frontier:
                for u in adj[v]:
                    if u not in level:
                        level[u] = level[v] + 1
                        nxt.append(u)
            frontier = nxt
        comp = set(level)
        chunk = [int(v) for v in order[i : i + len(comp)]]
        assert set(chunk) == comp, "BFS component not contiguous in order"
        layers = [level[v] for v in chunk]
        assert layers == sorted(layers), "visit order violates BFS layers"
        i += len(comp)


def test_bfs_reorder_within_partitions(small_graph):
    """The within-partition reorder is a REAL induced-subgraph BFS (the old
    code hub-first degree-sorted each group)."""
    g = small_graph
    indptr, order = g.out_csr()
    indices = g.dst[order]
    deg = g.out_degrees() + g.in_degrees()
    pid = np.random.default_rng(3).integers(0, 4, g.num_vertices)
    perm = reorder_permutation(
        "BFS",
        global_ids=np.arange(g.num_vertices),
        degrees=deg,
        partition_ids=pid,
        indptr=indptr,
        indices=indices,
        seed=0,
    )
    assert sorted(perm.tolist()) == list(range(g.num_vertices))
    # groups appear in ascending partition order
    assert (np.diff(pid[perm]) >= 0).all()
    for p in np.unique(pid):
        members = np.flatnonzero(pid == p)
        group = perm[pid[perm] == p]
        _assert_bfs_visit_order(indptr, indices, members, group)

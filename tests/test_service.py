"""The asynchronous request-plan sampling service: concurrency & determinism
(bit-identical results under interleaving / sharing / coalescing / window
depth), SamplingSpec + config validation, service-level stats aggregation."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.api import (
    GLISPConfig,
    GLISPSystem,
    SampleRequest,
    SamplingSpec,
)
from repro.core.sampling import ServerStats


@pytest.fixture(scope="module")
def svc_graph():
    from repro.graph import power_law_graph

    g = power_law_graph(1200, avg_degree=8, seed=11, feat_dim=16, num_classes=4)
    g.labels = g.vertex_types.astype(np.int32)
    return g


def _build(g, **overrides):
    base = dict(num_parts=4, fanouts=(8, 4), batch_size=128)
    base.update(overrides)
    return GLISPSystem.build(g, GLISPConfig(**base))


def _assert_same_subgraph(a, b):
    np.testing.assert_array_equal(a.seeds, b.seeds)
    assert len(a.hops) == len(b.hops)
    for ha, hb in zip(a.hops, b.hops):
        np.testing.assert_array_equal(ha.src, hb.src)
        np.testing.assert_array_equal(ha.dst, hb.dst)
        if ha.eid is not None or hb.eid is not None:
            np.testing.assert_array_equal(ha.eid, hb.eid)


EC = dict(partitioner="ldg", sampler="edge_cut", num_parts=3)


# ---------------------------------------------------------------------------
# SamplingSpec + GLISPConfig validation
# ---------------------------------------------------------------------------


def test_sampling_spec_validation():
    SamplingSpec(fanouts=(5, 3)).validate()
    with pytest.raises(ValueError, match="fanouts"):
        SamplingSpec(fanouts=()).validate()
    with pytest.raises(ValueError, match="fanouts"):
        SamplingSpec(fanouts=(5, 0)).validate()
    with pytest.raises(ValueError, match="direction"):
        SamplingSpec(direction="sideways").validate()
    with pytest.raises(ValueError, match="replace"):
        SamplingSpec(weighted=True, replace=True).validate()


def test_config_positivity_checks():
    for field in (
        "chunk_rows",
        "infer_batch_size",
        "vertex_quantum",
        "edge_quantum",
        "batch_size",
        "inflight",
    ):
        with pytest.raises(ValueError, match=field):
            GLISPConfig(**{field: 0}).validate()
    with pytest.raises(ValueError, match="max_server_batch"):
        GLISPConfig(max_server_batch=-1).validate()
    # spec fields are validated through the config too
    with pytest.raises(ValueError, match="replace"):
        GLISPConfig(weighted=True, sample_replace=True).validate()
    GLISPConfig(coalesce=False, max_server_batch=64, inflight=4).validate()


def test_config_spec_roundtrip():
    cfg = GLISPConfig(fanouts=(15, 10), weighted=True, direction="in")
    spec = cfg.sampling_spec()
    assert spec == SamplingSpec(fanouts=(15, 10), weighted=True, direction="in")
    assert cfg.sampling_spec(fanouts=[3], weighted=False).fanouts == (3,)


# ---------------------------------------------------------------------------
# ticket lifecycle
# ---------------------------------------------------------------------------


def test_ticket_lifecycle_and_request_object(svc_graph):
    system = _build(svc_graph)
    spec = SamplingSpec(fanouts=(6, 3))
    req = SampleRequest(
        seeds=np.arange(40), spec=spec, key=(1, 2)
    )
    ticket = system.service.submit(req)
    assert not ticket.done()
    assert system.service.inflight() == 1
    sub = ticket.result()
    assert ticket.done()
    assert system.service.inflight() == 0
    assert len(sub.hops) == 2
    # a second result() call returns the same finished object, no re-run
    assert ticket.result() is sub
    with pytest.raises(ValueError, match="SamplingSpec"):
        system.service.submit(np.arange(5))


def test_ticket_cancel(svc_graph):
    system = _build(svc_graph)
    spec = SamplingSpec(fanouts=(6, 3))
    keep = system.submit(np.arange(40), spec, key=(1,))
    drop = system.submit(np.arange(40, 80), spec, key=(2,))
    drop.cancel()
    assert system.service.inflight() == 1
    sub = keep.result()  # cancelled request consumes no further rounds
    assert len(sub.hops) == 2
    with pytest.raises(RuntimeError, match="cancelled"):
        drop.result()
    # a kept request is bit-identical to a run that never saw the cancelled
    # sibling (per-request RNG keys make cancellation invisible)
    want = _build(svc_graph).submit(np.arange(40), spec, key=(1,)).result()
    _assert_same_subgraph(sub, want)


def test_spec_and_overrides_conflict(svc_graph):
    system = _build(svc_graph)
    spec = SamplingSpec(fanouts=(4,))
    with pytest.raises(ValueError, match="not both"):
        system.sample(np.arange(10), fanouts=[5], spec=spec)
    with pytest.raises(ValueError, match="not both"):
        system.submit(np.arange(10), spec, weighted=True)
    with pytest.raises(ValueError, match="not both"):
        system.loader(np.arange(10), fanouts=(5,), spec=spec)


def test_submit_key_normalization(svc_graph):
    system = _build(svc_graph)
    spec = SamplingSpec(fanouts=(4,))
    a = system.submit(np.arange(30), spec, key=7).result()
    b = _build(svc_graph).submit(np.arange(30), spec, key=(7,)).result()
    _assert_same_subgraph(a, b)  # int keys normalize to 1-tuples
    with pytest.raises(TypeError, match="key"):
        system.submit(np.arange(5), spec, key="nope")


# ---------------------------------------------------------------------------
# concurrency: in-flight requests are bit-identical to serial submission
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("overrides", [{}, EC])
def test_concurrent_inflight_matches_serial(svc_graph, overrides):
    spec = SamplingSpec(fanouts=(8, 4))
    seedsets = [np.arange(100), np.arange(50, 150), np.arange(120, 220)]
    keys = [(11,), (12,), (13,)]

    serial = _build(svc_graph, **overrides)
    want = [
        serial.submit(s, spec, key=k).result() for s, k in zip(seedsets, keys)
    ]

    conc = _build(svc_graph, **overrides)
    tickets = [conc.submit(s, spec, key=k) for s, k in zip(seedsets, keys)]
    assert conc.service.inflight() == 3  # >= 2 concurrent in-flight requests
    got = [t.result() for t in reversed(tickets)][::-1]
    for a, b in zip(got, want):
        _assert_same_subgraph(a, b)
    # overlapping the requests lowers modeled parallel latency, never raises
    assert conc.service.parallel_work <= serial.service.parallel_work + 1e-9


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), resolve_first=st.integers(0, 2))
def test_property_interleaving_invariance(seed, resolve_first):
    """Any submission/resolution interleaving of 3 requests is bit-identical
    to serial one-at-a-time submission with the same keys."""
    from repro.graph import power_law_graph

    g = power_law_graph(500, avg_degree=6, seed=3, feat_dim=4, num_classes=2)
    rng = np.random.default_rng(seed)
    spec = SamplingSpec(fanouts=(5, 3), weighted=bool(seed % 2))
    seedsets = [
        np.sort(rng.choice(g.num_vertices, 60, replace=False)) for _ in range(3)
    ]
    keys = [(seed, i) for i in range(3)]

    serial = _build(g, num_parts=3)
    want = [
        serial.submit(s, spec, key=k).result() for s, k in zip(seedsets, keys)
    ]

    conc = _build(g, num_parts=3)
    tickets = [conc.submit(s, spec, key=k) for s, k in zip(seedsets, keys)]
    order = [resolve_first] + [i for i in range(3) if i != resolve_first]
    got = [None] * 3
    for i in order:
        got[i] = tickets[i].result()
    for a, b in zip(got, want):
        _assert_same_subgraph(a, b)


# ---------------------------------------------------------------------------
# coalescing: dispatch accounting only — results bit-equivalent
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("overrides", [{}, EC])
@pytest.mark.parametrize("weighted", [False, True])
def test_coalesced_vs_uncoalesced_equivalence(svc_graph, overrides, weighted):
    spec = SamplingSpec(fanouts=(8, 4), weighted=weighted)
    seedsets = [np.arange(100), np.arange(50, 150)]  # shared frontier seeds
    keys = [(5,), (6,)]

    def run(coalesce):
        system = _build(svc_graph, coalesce=coalesce, **overrides)
        tickets = [
            system.submit(s, spec, key=k) for s, k in zip(seedsets, keys)
        ]
        return [t.result() for t in tickets], system.service.stats()

    got_on, stats_on = run(True)
    got_off, stats_off = run(False)
    for a, b in zip(got_on, got_off):
        _assert_same_subgraph(a, b)
    # duplicated frontier seeds across the in-flight requests are charged
    # once when coalescing; payload counters are identical either way
    assert stats_on.seeds < stats_off.seeds
    assert stats_on.edges_returned == stats_off.edges_returned
    assert stats_on.bytes_out == stats_off.bytes_out


def test_max_server_batch_split(svc_graph):
    """Splitting bounds per-dispatch size; results stay deterministic and
    respect fanouts, and full fanout stays lossless."""
    spec = SamplingSpec(fanouts=(8, 4))
    a = _build(svc_graph, max_server_batch=16).submit(
        np.arange(120), spec, key=(3,)
    ).result()
    b = _build(svc_graph, max_server_batch=16).submit(
        np.arange(120), spec, key=(3,)
    ).result()
    _assert_same_subgraph(a, b)
    for f, hop in zip((8, 4), a.hops):
        if hop.src.shape[0]:
            assert np.unique(hop.src, return_counts=True)[1].max() <= f
    # chunked dispatch raises the per-server request count
    sys_split = _build(svc_graph, max_server_batch=16, coalesce=False)
    sys_whole = _build(svc_graph, coalesce=False)
    sys_split.sample(np.arange(200), fanouts=[6])
    sys_whole.sample(np.arange(200), fanouts=[6])
    assert sys_split.service.stats().requests > sys_whole.service.stats().requests
    # lossless at full fanout even with chunking
    sub = _build(svc_graph, max_server_batch=8).sample(
        np.arange(20), fanouts=[10**9]
    )
    hop = sub.hops[0]
    for v in range(20):
        got = sorted(hop.dst[hop.src == v].tolist())
        assert got == sorted(svc_graph.neighbors(v, "out").tolist())


# ---------------------------------------------------------------------------
# loaders: window depth / prefetch depth / sharing never change the stream
# ---------------------------------------------------------------------------


def _collect(pipeline, epochs=1):
    return [(s, b) for s, b in pipeline.batches(epochs)]


def _assert_same_stream(a, b):
    assert len(a) == len(b) > 0
    for (s1, x1), (s2, x2) in zip(a, b):
        np.testing.assert_array_equal(s1, s2)
        np.testing.assert_array_equal(x1.feats, x2.feats)
        np.testing.assert_array_equal(x1.labels, x2.labels)
        for k in range(len(x1.layer_dst)):
            np.testing.assert_array_equal(x1.layer_dst[k], x2.layer_dst[k])
            np.testing.assert_array_equal(x1.layer_src[k], x2.layer_src[k])


def test_loader_invariant_to_inflight_depth(svc_graph):
    ids = np.arange(800)
    runs = [
        _collect(
            _build(svc_graph).loader(
                ids, num_layers=2, prefetch=0, seed=5, inflight=w
            )
        )
        for w in (1, 2, 5)
    ]
    _assert_same_stream(runs[0], runs[1])
    _assert_same_stream(runs[0], runs[2])


def test_loader_invariant_to_prefetch_with_window(svc_graph):
    ids = np.arange(800)
    serial = _collect(
        _build(svc_graph).loader(ids, num_layers=2, prefetch=0, seed=5, inflight=3)
    )
    prefetched = _collect(
        _build(svc_graph).loader(ids, num_layers=2, prefetch=3, seed=5, inflight=3)
    )
    _assert_same_stream(serial, prefetched)


@pytest.mark.parametrize("overrides", [{}, EC])
def test_shared_service_loaders_match_private(svc_graph, overrides):
    """Two loaders sharing ONE SamplingService produce streams bit-identical
    to the same loaders on private services, even with their requests
    interleaved in flight (per-request RNG keys carry the whole contract)."""
    ids_a, ids_b = np.arange(400), np.arange(400, 800)
    shared = _build(svc_graph, **overrides)
    la = shared.loader(ids_a, num_layers=2, prefetch=0, seed=3, inflight=2)
    lb = shared.loader(ids_b, num_layers=2, prefetch=0, seed=3, inflight=2)
    ita, itb = la.batches(1), lb.batches(1)
    out_a, out_b = [], []
    while True:  # interleave consumption so both loaders' requests coexist
        nxt_a, nxt_b = next(ita, None), next(itb, None)
        if nxt_a is None and nxt_b is None:
            break
        if nxt_a is not None:
            out_a.append(nxt_a)
        if nxt_b is not None:
            out_b.append(nxt_b)
    priv_a = _collect(
        _build(svc_graph, **overrides).loader(
            ids_a, num_layers=2, prefetch=0, seed=3, inflight=2
        )
    )
    priv_b = _collect(
        _build(svc_graph, **overrides).loader(
            ids_b, num_layers=2, prefetch=0, seed=3, inflight=2
        )
    )
    _assert_same_stream(out_a, priv_a)
    _assert_same_stream(out_b, priv_b)


# ---------------------------------------------------------------------------
# replace policy
# ---------------------------------------------------------------------------


def test_replace_sampling(svc_graph):
    system = _build(svc_graph)
    sub = system.sample(np.arange(200), fanouts=[12], replace=True)
    hop = sub.hops[0]
    _, counts = np.unique(hop.src, return_counts=True)
    assert counts.max() <= 12
    # every sampled edge is real
    np.testing.assert_array_equal(svc_graph.src[hop.eid], hop.src)
    np.testing.assert_array_equal(svc_graph.dst[hop.eid], hop.dst)
    # with replacement a low-degree seed's draws must repeat eventually
    assert len(set(zip(hop.src.tolist(), hop.dst.tolist()))) < hop.src.shape[0]


# ---------------------------------------------------------------------------
# stats: service-level aggregation + raw client reset discipline
# ---------------------------------------------------------------------------


def test_service_stats_merge(svc_graph):
    system = _build(svc_graph)
    system.sample(np.arange(100))
    merged = system.service.stats()
    assert isinstance(merged, ServerStats)
    per = [s.stats for s in system.service.servers]
    assert merged.requests == sum(p.requests for p in per) > 0
    assert merged.seeds == sum(p.seeds for p in per)
    assert merged.work_units == pytest.approx(sum(p.work_units for p in per))
    assert merged.edges_returned == sum(p.edges_returned for p in per)
    system.reset_stats()
    z = system.service.stats()
    assert z.requests == z.seeds == z.edges_returned == 0
    assert system.service.parallel_work == 0.0


def test_raw_client_reset_clears_work(svc_graph):
    from repro.core.partition import adadne
    from repro.core.sampling import (
        GatherApplyClient,
        SamplingServer,
        VertexRouter,
    )
    from repro.graph import build_partitions

    ep = adadne(svc_graph, 3, seed=1)
    parts = build_partitions(svc_graph, ep, 3)
    client = GatherApplyClient(
        [SamplingServer(p, seed=0) for p in parts],
        VertexRouter(svc_graph, ep, 3),
        seed=0,
    )
    client.sample_khop(np.arange(100), [6, 3])
    assert client.parallel_work > 0 and client.total_work > 0
    client.reset_stats()  # clears counters AND the work accumulators
    assert client.parallel_work == 0.0
    assert client.total_work == 0.0
    assert client.server_workloads().sum() == 0


# ---------------------------------------------------------------------------
# training through the windowed service path
# ---------------------------------------------------------------------------


def test_trainer_inflight_matches_blocking(svc_graph):
    from repro.models.gnn import GNNModel
    from repro.train.optim import AdamWConfig

    g = svc_graph
    model = GNNModel("sage", 16, hidden=16, num_layers=2, num_classes=4)
    losses = []
    for w in (1, 3):
        tr = _build(g).trainer(
            model,
            np.arange(600),
            opt=AdamWConfig(lr=3e-3),
            prefetch=0,
            inflight=w,
        )
        log = tr.train(epochs=1, log_every=1)
        losses.append(log.losses)
    np.testing.assert_allclose(losses[0], losses[1])

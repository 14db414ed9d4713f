"""Online serving tier: batching determinism, SLO semantics, admission.

The load-bearing property: a request's embeddings are **bit-identical**
whether it was served solo or packed into any batch mix — per-request
sampling keys plus row-independent padded slices make batch composition
unobservable in the results.  The property test drives one request set
through randomized interleavings/windows/delays and compares every
response bitwise against a solo-served reference.
"""
import threading
import time

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.serve import ContinuousBatcher, P2Quantile, RequestQueue, ServeRequest


# ---------------------------------------------------------------------------
# components
# ---------------------------------------------------------------------------


def test_request_queue_bounds_and_rejects():
    q = RequestQueue(2)
    assert q.push("a") and q.push("b")
    assert not q.push("c")  # full: explicit rejection, no side effect
    assert len(q) == 2
    assert q.pop() == "a"
    assert q.push("c")  # a pop frees a slot
    assert [q.pop(), q.pop(), q.pop()] == ["b", "c", None]
    with pytest.raises(ValueError):
        RequestQueue(0)


def test_batcher_size_and_delay_triggers():
    b = ContinuousBatcher(max_rows=10, max_delay_ms=50.0)
    b.add("r0", 4, now=0.0)
    assert not b.ready(now=0.0) and b.take(now=0.0) is None
    b.add("r1", 6, now=0.01)  # 10 rows: size trigger
    assert b.ready(now=0.01)
    assert b.take(now=0.01) == ["r0", "r1"] and len(b) == 0
    b.add("r2", 1, now=1.0)
    assert not b.ready(now=1.04)  # 40 ms: timer not yet fired
    assert b.ready(now=1.06)  # 60 ms: oldest waited out the delay
    assert b.take(now=1.06) == ["r2"]
    b.add("r3", 2, now=2.0)
    assert b.take(now=2.0, force=True) == ["r3"]  # force flushes a partial


def test_batcher_splits_at_budget_and_admits_oversized_head():
    b = ContinuousBatcher(max_rows=8, max_delay_ms=0.0)
    for i, rows in enumerate([5, 5, 99]):
        b.add(f"r{i}", rows, now=0.0)
    assert b.take(now=0.0) == ["r0"]  # r1 would spill the budget
    assert b.take(now=0.0) == ["r1"]
    assert b.take(now=0.0) == ["r2"]  # oversized head still ships alone


def test_serve_request_validation_and_ordering():
    req = ServeRequest.make(7, np.array([5, 3, 5, 9]), None, 0.0)
    np.testing.assert_array_equal(req.unique, [3, 5, 9])
    np.testing.assert_array_equal(req.vertices, [5, 3, 5, 9])
    assert req.deadline_at(100.0) == pytest.approx(0.1)
    assert req.deadline_at(None) is None
    with pytest.raises(ValueError):
        ServeRequest.make(0, np.array([]), None, 0.0)
    with pytest.raises(ValueError):
        ServeRequest.make(0, np.eye(2), None, 0.0)


def test_p2_quantile_tracks_exact_percentiles():
    rng = np.random.default_rng(3)
    xs = rng.gamma(2.0, 10.0, size=2000)
    for q in (0.5, 0.95, 0.99):
        est = P2Quantile(q)
        for x in xs:
            est.add(x)
        exact = float(np.percentile(xs, 100 * q))
        assert abs(est.value() - exact) <= 0.1 * exact + 1.0
    small = P2Quantile(0.5)
    for x in [3.0, 1.0, 2.0]:
        small.add(x)
    assert small.value() == 2.0  # exact below five samples


def test_config_serve_knobs_validate():
    from repro.api import GLISPConfig

    GLISPConfig().validate()
    for bad in (
        dict(serve_queue_depth=0),
        dict(serve_max_batch_delay_ms=-1.0),
        dict(serve_deadline_ms=0.0),
    ):
        with pytest.raises(ValueError):
            GLISPConfig(**bad).validate()
    GLISPConfig(serve_deadline_ms=None).validate()  # explicit no-deadline


# ---------------------------------------------------------------------------
# the served system
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served_system(tmp_path_factory):
    import jax

    from repro.api import GLISPConfig, GLISPSystem
    from repro.graph import power_law_graph
    from repro.models.gnn import GNNModel

    g = power_law_graph(800, avg_degree=6, seed=3, feat_dim=16, num_classes=4)
    system = GLISPSystem.build(g, GLISPConfig(num_parts=2, fanouts=(6, 4)))
    model = GNNModel("sage", 16, hidden=8, num_layers=2)
    params = model.init(jax.random.PRNGKey(0))
    fns = [model.embed_layer_fn(params, k) for k in range(2)]
    wd = str(tmp_path_factory.mktemp("serve_emb"))
    system.infer_layerwise(fns, wd, out_dims=[8, 8], batch_size=256)
    return system


REQUESTS = None


def _requests(g):
    global REQUESTS
    if REQUESTS is None:
        rng = np.random.default_rng(11)
        REQUESTS = [
            rng.choice(g.num_vertices, size=int(rng.integers(1, 9)), replace=False)
            for _ in range(8)
        ]
    return REQUESTS


@pytest.fixture(scope="module")
def solo_reference(served_system):
    """Every request served alone — the bit-identity ground truth."""
    server = served_system.server(max_batch_delay_ms=0.0, deadline_ms=None)
    return [server.call(v).embeddings for v in _requests(served_system.graph)]


def test_server_requires_inference_artifact(served_system):
    from repro.api import GLISPConfig, GLISPSystem

    fresh = GLISPSystem.build(
        served_system.graph, GLISPConfig(num_parts=2, fanouts=(6, 4))
    )
    with pytest.raises(ValueError, match="infer_layerwise"):
        fresh.server()


@settings(max_examples=8, deadline=None)
@given(
    window=st.integers(min_value=1, max_value=8),
    queue_depth=st.integers(min_value=8, max_value=32),
    delay_ms=st.sampled_from([0.0, 0.5, 1e6]),
    steps_between=st.integers(min_value=0, max_value=3),
)
def test_batching_is_bit_identical_to_solo(
    served_system, solo_reference, window, queue_depth, delay_ms, steps_between
):
    """Any admission window / queue depth / flush-delay interleaving must
    return exactly the solo embeddings for every request id."""
    server = served_system.server(
        queue_depth=queue_depth, max_batch_delay_ms=delay_ms, deadline_ms=None
    )
    reqs = _requests(served_system.graph)
    rids, nxt = [], 0
    while nxt < len(reqs):
        for _ in range(window):
            if nxt < len(reqs):
                rids.append(server.submit(reqs[nxt]))
                nxt += 1
        for _ in range(steps_between):
            server.step()  # un-forced: flushes only if a trigger fired
    server.drain()
    for rid, want in zip(rids, solo_reference):
        resp = server.response(rid)
        assert resp is not None and resp.status == "ok"
        assert resp.embeddings.dtype == want.dtype
        assert np.array_equal(resp.embeddings, want), (
            f"request {rid} diverged under window={window} "
            f"delay={delay_ms} steps={steps_between}"
        )
    assert server.stats.completed == len(reqs)
    assert server.stats.rejected == 0


def test_batched_occupancy_beats_solo(served_system):
    reqs = _requests(served_system.graph)
    solo = served_system.server(max_batch_delay_ms=0.0, deadline_ms=None)
    for v in reqs:
        solo.call(v)
    batched = served_system.server(max_batch_delay_ms=0.0, deadline_ms=None)
    rids = [batched.submit(v) for v in reqs]
    batched.drain()
    assert all(batched.response(r).status == "ok" for r in rids)
    assert batched.stats.occupancy() > solo.stats.occupancy()
    assert batched.stats.mean_batch_requests() > 1.0


def test_queue_full_rejects_explicitly(served_system):
    server = served_system.server(queue_depth=2, max_batch_delay_ms=1e6)
    rids = [server.submit(np.array([i])) for i in range(5)]
    statuses = [
        server.response(r, pop=False) and server.response(r, pop=False).status
        for r in rids
    ]
    assert statuses == [None, None, "rejected", "rejected", "rejected"]
    assert server.stats.rejected == 3
    server.drain()  # the two admitted requests still complete
    assert server.response(rids[0]).status == "ok"
    assert server.response(rids[1]).status == "ok"


def test_missed_deadline_times_out_and_server_survives(served_system):
    """A request whose deadline passed completes with an explicit timeout
    response — and the serving loop keeps answering later requests."""
    server = served_system.server(max_batch_delay_ms=0.0, deadline_ms=1e-6)
    rid = server.submit(np.array([1, 2, 3]))
    server.drain()
    resp = server.response(rid)
    assert resp.status == "timeout" and resp.embeddings is None
    assert server.stats.timed_out == 1
    # per-request deadline override: the next request is generous and lands
    rid2 = server.submit(np.array([4, 5]), deadline_ms=60_000.0)
    server.drain()
    assert server.response(rid2).status == "ok"
    assert server.stats.completed == 2


def test_blocked_service_times_out_within_deadline(served_system):
    """Sampling stuck behind a held scheduler lock must surface as a
    timeout response in ~deadline time, not wedge the serving loop."""
    server = served_system.server(max_batch_delay_ms=0.0, deadline_ms=50.0)
    svc = served_system.service
    held = threading.Event()

    def hold():
        with svc._lock:
            held.set()
            time.sleep(0.4)

    th = threading.Thread(target=hold)
    th.start()
    held.wait()
    try:
        rid = server.submit(np.array([1, 2, 3]))
        t0 = time.monotonic()
        server.drain()
        elapsed = time.monotonic() - t0
    finally:
        th.join()
    resp = server.response(rid)
    assert resp.status == "timeout"
    assert elapsed < 0.3, f"deadline wait not deadline-aware: {elapsed:.3f}s"
    # the server is not wedged: the same vertices serve fine afterwards
    assert server.call(np.array([1, 2, 3])).status == "ok"


def test_ticket_result_timeout_is_deadline_aware(served_system):
    """Regression (PR 8): ``SampleTicket.result(timeout=0.01)`` returns
    within a small multiple of 10 ms even while another thread holds the
    service's scheduler lock mid-round."""
    from repro.api import SampleTimeout

    svc = served_system.service
    ticket = served_system.submit(np.arange(8), key=(0x9E8, 0))
    held = threading.Event()

    def hold():
        with svc._lock:
            held.set()
            time.sleep(0.4)

    th = threading.Thread(target=hold)
    th.start()
    held.wait()
    t0 = time.monotonic()
    with pytest.raises(SampleTimeout):
        ticket.result(timeout=0.01)
    elapsed = time.monotonic() - t0
    th.join()
    assert elapsed < 0.25, f"10 ms timeout took {elapsed * 1e3:.0f} ms"
    assert ticket.result(timeout=5.0).hops  # still completes afterwards


def test_degraded_sampling_yields_degraded_responses(served_system):
    """Under a fault plan that exhausts sampling retries, responses come
    back ``status="ok"`` with ``degraded=True`` — explicit, never silent."""
    import jax

    from repro.api import FaultPlan, FaultSpec, GLISPConfig, GLISPSystem, RetryPolicy
    from repro.models.gnn import GNNModel

    g = served_system.graph
    faulty = GLISPSystem.build(
        g,
        GLISPConfig(
            num_parts=2,
            fanouts=(6, 4),
            fault_plan=FaultPlan(seed=5, sites=(("server.*", FaultSpec(p=0.95)),)),
            retry_policy=RetryPolicy(max_attempts=1),
        ),
    )
    model = GNNModel("sage", 16, hidden=8, num_layers=2)
    params = model.init(jax.random.PRNGKey(0))
    fns = [model.embed_layer_fn(params, k) for k in range(2)]
    import tempfile

    faulty.infer_layerwise(
        fns, tempfile.mkdtemp(), out_dims=[8, 8], batch_size=256
    )
    server = faulty.server(deadline_ms=None)
    rids = [server.submit(v) for v in _requests(g)]
    server.drain()
    responses = [server.response(r) for r in rids]
    assert all(r.status == "ok" for r in responses)
    assert any(r.degraded for r in responses)
    assert server.stats.degraded == sum(r.degraded for r in responses)

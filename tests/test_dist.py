"""The distributed sampling tier: wire-protocol roundtrips (property
tests), malformed-frame rejection, cross-mode bit-identity (inproc vs
forked workers over pipes/sockets), fault parity, crash->respawn
determinism, and the data-parallel trainer's equivalence to its
single-device reference step."""
import os
import time

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.api import GLISPConfig, GLISPSystem
from repro.core.faults import FaultPlan, FaultSpec, RetryPolicy
from repro.core.sampling.service import (
    SampleRequest,
    SamplingSpec,
    ServiceStats,
)
from repro.dist.transport import (
    MESSAGE_TYPES,
    PROTOCOL_VERSION,
    ChannelClosed,
    DispatchResult,
    HealthRequest,
    HealthResponse,
    ProtocolError,
    ResetStatsAck,
    ResetStatsRequest,
    SampleDispatch,
    ShutdownAck,
    ShutdownRequest,
    StatsRequest,
    StatsResponse,
    TruncatedFrame,
    VersionMismatch,
    channel_pair,
    decode_frame,
    encode_frame,
    messages_equal,
)

pytestmark = pytest.mark.skipif(
    os.name != "posix", reason="dist workers fork (POSIX only)"
)


def _system(graph, **over):
    base = dict(num_parts=2, fanouts=(4, 3), batch_size=32, seed=5)
    base.update(over)
    return GLISPSystem.build(graph, GLISPConfig(**base))


def _sample(system, seeds, key, **spec_over):
    cfg = dict(fanouts=(4, 3))
    cfg.update(spec_over)
    spec = SamplingSpec(**cfg)
    ticket = system.backend.submit(
        SampleRequest(seeds=seeds, spec=spec, key=key)
    )
    return ticket.result(timeout=30.0)


def _assert_same_sub(a, b):
    np.testing.assert_array_equal(a.seeds, b.seeds)
    assert a.degraded == b.degraded
    assert a.lost_dispatches == b.lost_dispatches
    assert len(a.hops) == len(b.hops)
    for ha, hb in zip(a.hops, b.hops):
        np.testing.assert_array_equal(ha.src, hb.src)
        np.testing.assert_array_equal(ha.dst, hb.dst)
        np.testing.assert_array_equal(ha.eid, hb.eid)


# ---------------------------------------------------------------------------
# wire protocol: property roundtrips over every message type
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(min_value=0, max_value=40),
    hop=st.integers(min_value=0, max_value=5),
    part=st.integers(min_value=0, max_value=63),
    chunk=st.integers(min_value=0, max_value=7),
    fanout=st.integers(min_value=1, max_value=20),
    key_hi=st.integers(min_value=0, max_value=2**63 - 1),
    weighted=st.booleans(),
    replace=st.booleans(),
    direction=st.sampled_from(["out", "in"]),
)
def test_sample_dispatch_roundtrip(
    n, hop, part, chunk, fanout, key_hi, weighted, replace, direction
):
    msg = SampleDispatch(
        key=(key_hi, 3),
        hop=hop,
        part=part,
        chunk=chunk,
        seeds=np.arange(n, dtype=np.int64) * 7,
        fanout=fanout,
        direction=direction,
        weighted=weighted,
        replace=replace,
    )
    back = decode_frame(encode_frame(msg))
    assert type(back) is SampleDispatch
    assert messages_equal(msg, back)
    assert back.seeds.dtype == np.int64
    assert back.key == (key_hi, 3)


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(min_value=0, max_value=50),
    lost=st.booleans(),
    retries=st.integers(min_value=0, max_value=9),
    failovers=st.integers(min_value=0, max_value=3),
    weighted=st.booleans(),
    wall=st.floats(min_value=0.0, max_value=50.0),
)
def test_dispatch_result_roundtrip(n, lost, retries, failovers, weighted, wall):
    if lost:
        n = 0  # degraded results carry empty arrays, like the real worker
    msg = DispatchResult(
        part=1,
        chunk=0,
        lost=lost,
        src=np.arange(n, dtype=np.int64),
        dst=np.arange(n, dtype=np.int64)[::-1].copy(),
        eid=np.arange(n, dtype=np.int64) + 1000,
        scores=np.linspace(0.0, 1.0, n) if weighted else None,
        retries=retries,
        failovers=failovers,
        wall_ms=wall,
        state={
            "replicas": {"server.1.0": {"requests": retries, "work_units": 1.5}},
            "breakers": [
                {"consecutive_failures": failovers, "opens": 0,
                 "cooldown_left": 0, "half_open": False}
            ],
            "injector": {"invocations": {"server.1.0": n}, "failures": {}},
        },
    )
    back = decode_frame(encode_frame(msg))
    assert type(back) is DispatchResult
    assert messages_equal(msg, back)
    assert back.state["replicas"]["server.1.0"]["work_units"] == 1.5


def test_control_frames_roundtrip():
    msgs = [
        StatsRequest(),
        StatsResponse(part=3, replicas={"server.3.0": {"requests": 7}}),
        HealthRequest(),
        HealthResponse(part=0, health={"server.0.0": "up"}),
        ResetStatsRequest(),
        ResetStatsAck(part=2),
        ShutdownRequest(),
        ShutdownAck(part=1),
    ]
    seen_types = {type(m) for m in msgs} | {SampleDispatch, DispatchResult}
    assert seen_types == set(MESSAGE_TYPES.values()), (
        "roundtrip tests must cover every registered message type"
    )
    for msg in msgs:
        back = decode_frame(encode_frame(msg))
        assert type(back) is type(msg)
        assert messages_equal(msg, back)


def test_version_mismatch_rejected():
    frame = bytearray(encode_frame(StatsRequest()))
    frame[4:6] = (PROTOCOL_VERSION + 1).to_bytes(2, "little")
    with pytest.raises(VersionMismatch):
        decode_frame(bytes(frame))


def test_malformed_frames_rejected():
    frame = encode_frame(
        DispatchResult(part=0, chunk=0, src=np.arange(5, dtype=np.int64))
    )
    with pytest.raises(TruncatedFrame):
        decode_frame(frame[:8])  # inside the header
    with pytest.raises(TruncatedFrame):
        decode_frame(frame[:-3])  # payload shorter than the header claims
    with pytest.raises(ProtocolError):
        decode_frame(b"NOPE" + frame[4:])  # bad magic
    bad_type = bytearray(frame)
    bad_type[6:8] = (999).to_bytes(2, "little")
    with pytest.raises(ProtocolError):
        decode_frame(bytes(bad_type))


@pytest.mark.parametrize("kind", ["mp", "socket"])
def test_channel_roundtrip_and_close(kind):
    a, b = channel_pair(kind)
    msg = SampleDispatch(
        key=(1, 2), hop=0, part=0, chunk=0,
        seeds=np.array([5, 9], dtype=np.int64),
        fanout=4, direction="out", weighted=False, replace=False,
    )
    a.send(msg)
    assert messages_equal(b.recv(), msg)
    b.send(ShutdownAck(part=0))
    assert a.poll(1.0)
    assert type(a.recv()) is ShutdownAck
    a.close()
    with pytest.raises(ChannelClosed):
        b.recv()
    b.close()


# ---------------------------------------------------------------------------
# cross-mode determinism: forked workers answer bit-identically
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("transport", ["mp", "socket"])
def test_remote_bit_identical_to_inproc(small_graph, transport):
    local = _system(small_graph)
    remote = _system(small_graph, dist_transport=transport)
    try:
        for i in range(4):
            seeds = np.arange(10 + 5 * i, dtype=np.int64) * 13 % 2000
            a = _sample(local, seeds, key=(77, i))
            b = _sample(remote, seeds, key=(77, i))
            _assert_same_sub(a, b)
        # weighted sampling threads scores through the wire too
        wa = _sample(local, np.arange(20, dtype=np.int64), key=(78, 0),
                     weighted=True)
        wb = _sample(remote, np.arange(20, dtype=np.int64), key=(78, 0),
                     weighted=True)
        _assert_same_sub(wa, wb)
    finally:
        remote.close()


def test_remote_stats_health_workloads(small_graph):
    local = _system(small_graph)
    remote = _system(small_graph, dist_transport="mp")
    try:
        seeds = np.arange(30, dtype=np.int64)
        _sample(local, seeds, key=(1, 0))
        _sample(remote, seeds, key=(1, 0))
        sl, sr = local.backend.stats(), remote.backend.stats()
        assert isinstance(sr, ServiceStats)
        assert sr.requests == sl.requests
        assert sr.work_units == pytest.approx(sl.work_units)
        # round work accounting must survive the move out of process
        assert sr.modeled_total_work == pytest.approx(sl.modeled_total_work)
        assert sr.modeled_parallel_work > 0
        np.testing.assert_allclose(
            remote.server_workloads(), local.server_workloads()
        )
        health = remote.server_health()
        assert health["worker.0"] == "up"
        assert health["worker.1"] == "up"
        assert all(v == "up" for k, v in health.items())
        remote.reset_stats()
        assert remote.backend.stats().requests == 0
    finally:
        remote.close()


def test_remote_fault_parity(small_graph):
    plan = FaultPlan(
        seed=13,
        sites=(("server.0.0", FaultSpec(p=0.4)),),
    )
    kw = dict(
        server_replicas=2,
        fault_plan=plan,
        retry_policy=RetryPolicy(max_attempts=2, base_delay_s=0.0),
    )
    local = _system(small_graph, **kw)
    remote = _system(small_graph, dist_transport="mp", **kw)
    try:
        for i in range(4):
            seeds = np.arange(25, dtype=np.int64) + 11 * i
            a = _sample(local, seeds, key=(9, i))
            b = _sample(remote, seeds, key=(9, i))
            _assert_same_sub(a, b)
        sl, sr = local.backend.stats(), remote.backend.stats()
        assert (sr.retries, sr.failovers, sr.degraded) == (
            sl.retries, sl.failovers, sl.degraded
        )
        assert sr.retries > 0  # the plan actually injected faults
    finally:
        remote.close()


def test_killed_worker_respawns_deterministically(small_graph):
    local = _system(small_graph)
    remote = _system(small_graph, dist_transport="mp")
    pool = remote.backend.service.dispatcher
    try:
        for i in range(3):
            seeds = np.arange(20, dtype=np.int64) + i
            _assert_same_sub(
                _sample(local, seeds, key=(4, i)),
                _sample(remote, seeds, key=(4, i)),
            )
        victim = pool._workers[1].proc
        victim.kill()
        victim.join(timeout=5.0)
        # post-kill requests respawn the worker from its last snapshot and
        # keep answering bit-identically
        for i in range(3, 6):
            seeds = np.arange(20, dtype=np.int64) + i
            _assert_same_sub(
                _sample(local, seeds, key=(4, i)),
                _sample(remote, seeds, key=(4, i)),
            )
        assert pool.respawn_count == 1
        sl, sr = local.backend.stats(), remote.backend.stats()
        assert sr.requests == sl.requests
    finally:
        remote.close()


def test_exhausted_respawn_budget_degrades(small_graph):
    remote = _system(small_graph, dist_transport="mp", worker_respawns=0)
    try:
        pool = remote.backend.service.dispatcher
        victim = pool._workers[0].proc
        victim.kill()
        victim.join(timeout=5.0)
        sub = _sample(remote, np.arange(12, dtype=np.int64), key=(2, 0))
        assert sub.degraded
        assert sub.lost_dispatches > 0
    finally:
        remote.close()


def test_pipeline_rejects_process_workers_with_remote_backend(small_graph):
    from repro.api import BatchPipeline

    remote = _system(small_graph, dist_transport="mp")
    try:
        with pytest.raises(ValueError, match="process"):
            BatchPipeline(
                remote.backend,
                remote.graph,
                np.arange(64, dtype=np.int64),
                [4, 3],
                2,
                workers="process",
            )
        # auto silently falls back to a thread producer
        pipe = BatchPipeline(
            remote.backend,
            remote.graph,
            np.arange(64, dtype=np.int64),
            [4, 3],
            2,
            batch_size=32,
            workers="auto",
            prefetch=1,
        )
        assert sum(1 for _ in pipe.batches(1)) == 2
    finally:
        remote.close()


# ---------------------------------------------------------------------------
# stats surface: modeled-vs-measured split, deprecated aliases
# ---------------------------------------------------------------------------


def test_service_stats_modeled_and_measured(small_graph):
    system = _system(small_graph)
    _sample(system, np.arange(40, dtype=np.int64), key=(3, 0))
    s = system.backend.stats()
    assert isinstance(s, ServiceStats)
    assert s.modeled_parallel_work > 0
    assert s.modeled_total_work >= s.modeled_parallel_work
    assert s.rounds > 0
    # deprecated read aliases stay observable for one release
    assert s.parallel_work == s.modeled_parallel_work
    assert s.total_work == s.modeled_total_work
    svc = system.backend.service
    assert svc.parallel_work == s.modeled_parallel_work
    svc.parallel_work = 0.0  # legacy writers (benchmarks) still work
    assert svc.modeled_parallel_work == 0.0
    system.reset_stats()
    s2 = system.backend.stats()
    assert s2.rounds == 0


# ---------------------------------------------------------------------------
# data-parallel trainer: sharded step == single-device reference
# ---------------------------------------------------------------------------


def test_dp_trainer_matches_reference(small_graph):
    from repro.launch.mesh import make_local_mesh
    from repro.models.gnn.models import GNNModel

    system = _system(small_graph, fanouts=(4, 4))
    model = GNNModel("sage", 16, hidden=16, num_layers=2, num_classes=4)
    tr = system.dp_trainer(
        model,
        np.arange(96, dtype=np.int64),
        mesh=make_local_mesh(1),
        batch_size=32,
        reference=True,
    )
    log = tr.train(epochs=1, log_every=1, max_steps=3)
    assert len(log.losses) == 3
    np.testing.assert_allclose(log.losses, log.ref_losses, rtol=1e-5)
    assert log.sample_time > 0 and log.compute_time > 0


def test_stack_batches_pads_and_rejects_ragged():
    from repro.models.gnn.batching import GNNBatch
    from repro.train.data_parallel import stack_batches

    def mk(v, e, b):
        return GNNBatch(
            feats=np.ones((v, 4), dtype=np.float32),
            valid=np.ones(v, dtype=bool),
            seed_pos=np.zeros(b, dtype=np.int32),
            labels=np.zeros(b, dtype=np.int32),
            layer_dst=[np.zeros(e, dtype=np.int32)],
            layer_src=[np.zeros(e, dtype=np.int32)],
            layer_etype=[np.zeros(e, dtype=np.int32)],
        )

    stacked = stack_batches([mk(8, 6, 4), mk(5, 9, 4)])
    assert stacked.feats.shape == (2, 8, 4)
    assert stacked.layer_dst[0].shape == (2, 9)
    # padding rows are inert: invalid vertices, -1 edge endpoints
    assert not stacked.valid[1, 5:].any()
    assert (stacked.layer_dst[0][0, 6:] == -1).all()
    with pytest.raises(ValueError, match="seeds per batch"):
        stack_batches([mk(8, 6, 4), mk(8, 6, 3)])

"""Deterministic fault injection, replica failover, tiered-storage
fallback, and crash-safe pipelines/training (the robustness layer).

The central property under test: every recovery path — retry, replica
failover, tier fall-through, worker respawn, checkpoint resume — is
**bit-identical by construction** to the fault-free run, because all
sampling randomness is keyed by request/batch (never by attempt, replica,
or wall clock) and all fault decisions are keyed by ``(seed, site,
invocation)``.  Degraded results are flagged, never silent.
"""
import os
import signal
import time

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.faults import (
    CircuitBreaker,
    FaultInjector,
    FaultPlan,
    FaultSpec,
    InjectedFault,
    RetryPolicy,
    as_injector,
)

FORK = os.name == "posix"


# ---------------------------------------------------------------------------
# FaultPlan / FaultInjector
# ---------------------------------------------------------------------------
def test_fault_plan_deterministic():
    plan = FaultPlan.bernoulli(0.3, site="server.*", seed=42)
    a = [plan.injector().should_fail("server.0.0") for _ in range(1)]  # noqa: F841
    inj1, inj2 = plan.injector(), plan.injector()
    seq1 = [inj1.should_fail("server.0.0") for _ in range(200)]
    seq2 = [inj2.should_fail("server.0.0") for _ in range(200)]
    assert seq1 == seq2
    assert any(seq1) and not all(seq1)  # p=0.3 over 200 draws
    # distinct sites draw independent streams
    inj3 = plan.injector()
    interleaved = []
    for _ in range(200):
        interleaved.append(inj3.should_fail("server.0.0"))
        inj3.should_fail("server.1.0")  # does not perturb server.0.0
    assert interleaved == seq1


def test_fault_spec_burst_and_limit():
    plan = FaultPlan.bernoulli(1.0, burst=3, limit=3, seed=0)
    inj = plan.injector()
    seq = [inj.should_fail("x") for _ in range(6)]
    # one trigger fails 3 consecutive invocations, then the limit is spent
    assert seq == [True, True, True, False, False, False]
    assert inj.total_failures() == 3
    assert inj.counters()["x"] == {"invocations": 6, "failures": 3}


def test_unmatched_site_costs_nothing():
    inj = FaultPlan.bernoulli(1.0, site="disk.*").injector()
    assert not inj.should_fail("server.0.0")
    assert inj.invocations == {}  # unmatched sites are not even counted
    with pytest.raises(InjectedFault) as ei:
        for _ in range(5):
            inj.fire("disk.read")
    assert ei.value.site == "disk.read"


def test_first_match_wins():
    plan = FaultPlan(
        seed=0,
        sites=(
            ("server.0.1", FaultSpec(p=1.0)),
            ("server.*", FaultSpec(p=0.0)),
        ),
    )
    inj = plan.injector()
    assert inj.should_fail("server.0.1")
    assert not inj.should_fail("server.0.0")


def test_fault_plan_validation():
    with pytest.raises(ValueError):
        FaultPlan.bernoulli(1.5)
    with pytest.raises(ValueError):
        FaultPlan.bernoulli(0.5, burst=0)
    with pytest.raises(ValueError):
        FaultPlan.bernoulli(0.5, limit=-1)
    with pytest.raises(TypeError):
        FaultPlan(sites=(("server.*", 0.5),))
    with pytest.raises(TypeError):
        as_injector("not a plan")
    assert as_injector(None) is None
    inj = FaultPlan.bernoulli(0.5).injector()
    assert as_injector(inj) is inj  # pass-through shares counters
    rt = FaultPlan.bernoulli(0.25, site="a.*", seed=3, burst=2, limit=9)
    assert rt.to_dict() == {
        "seed": 3,
        "sites": [["a.*", {"p": 0.25, "burst": 2, "limit": 9}]],
    }


# ---------------------------------------------------------------------------
# RetryPolicy / CircuitBreaker
# ---------------------------------------------------------------------------
def test_retry_policy_backoff():
    pol = RetryPolicy(max_attempts=5, base_delay_s=0.01, max_delay_s=0.05)
    assert [pol.backoff(a) for a in (1, 2, 3, 4, 5)] == [
        0.01,
        0.02,
        0.04,
        0.05,
        0.05,
    ]
    assert RetryPolicy().backoff(3) == 0.0  # default: instant retries
    with pytest.raises(ValueError):
        RetryPolicy(max_attempts=0).validate()
    with pytest.raises(ValueError):
        RetryPolicy(multiplier=0.5).validate()
    with pytest.raises(ValueError):
        RetryPolicy(base_delay_s=-1).validate()
    # a spent deadline skips the sleep entirely
    t0 = time.monotonic()
    pol.sleep(5, deadline=time.monotonic() - 1.0)
    assert time.monotonic() - t0 < 0.04


def test_circuit_breaker_cycle():
    br = CircuitBreaker(threshold=2, cooldown=3)
    assert br.state == "closed" and br.allow()
    br.record_failure()
    assert br.state == "closed"
    br.record_failure()  # 2nd consecutive -> opens
    assert br.state == "open" and br.opens == 1
    assert not br.allow()
    assert not br.allow()
    assert br.allow()  # cooldown spent: half-open probe admitted
    assert br.state == "half_open"
    br.record_failure()  # probe failed -> re-opens immediately
    assert br.state == "open" and br.opens == 2
    for _ in range(2):
        br.allow()
    assert br.allow()
    br.record_success()  # probe succeeded -> closed
    assert br.state == "closed" and br.allow()


# ---------------------------------------------------------------------------
# Sampling failover
# ---------------------------------------------------------------------------
def _service(graph, partitioned, **kw):
    from repro.core.sampling import SamplingServer, VertexRouter
    from repro.core.sampling.service import GatherApplyRouting, SamplingService

    ep, parts = partitioned
    return SamplingService(
        [SamplingServer(p, seed=0) for p in parts],
        GatherApplyRouting(VertexRouter(graph, ep, 4)),
        seed=0,
        **kw,
    )


def _spec(fanouts=(6, 3)):
    from repro.core.sampling.service import SamplingSpec

    return SamplingSpec(fanouts=tuple(fanouts))


def _assert_same_subgraph(a, b):
    np.testing.assert_array_equal(a.seeds, b.seeds)
    assert len(a.hops) == len(b.hops)
    for ha, hb in zip(a.hops, b.hops):
        np.testing.assert_array_equal(ha.src, hb.src)
        np.testing.assert_array_equal(ha.dst, hb.dst)


SEEDS = np.arange(100, 260)


@settings(max_examples=5, deadline=None)
@given(
    p=st.floats(min_value=0.05, max_value=0.6),
    chaos_seed=st.integers(min_value=0, max_value=10_000),
    burst=st.integers(min_value=1, max_value=2),
)
def test_chaos_sampling_bit_identical(small_graph, partitioned, p, chaos_seed, burst):
    """Any Bernoulli fault schedule whose per-site limit stays under the
    breaker threshold recovers by retry alone, bit-identically: the
    per-dispatch RNG is keyed by (request, hop, partition), never by
    attempt, so a redraw after an injected fault is the same draw."""
    clean = _service(small_graph, partitioned)
    want = clean.submit(SEEDS, _spec(), key=(7, 0)).result(timeout=30)

    # limit=2 < CircuitBreaker.threshold=3: no quarantine, and every
    # dispatch recovers within max_attempts=4 on the primary alone
    plan = FaultPlan.bernoulli(
        p, site="server.*", seed=chaos_seed, burst=burst, limit=2
    )
    chaotic = _service(
        small_graph,
        partitioned,
        fault_plan=plan,
        retry_policy=RetryPolicy(max_attempts=4),
    )
    got = chaotic.submit(SEEDS, _spec(), key=(7, 0)).result(timeout=30)
    _assert_same_subgraph(want, got)
    assert not got.degraded and got.lost_dispatches == 0
    stats = chaotic.stats()
    assert stats.retries == chaotic.faults.total_failures()
    assert stats.degraded == 0


def test_failover_to_replica_bit_identical(small_graph, partitioned):
    """A burst long enough to trip the primary's breaker reroutes to the
    replica; replicas share the primary's partition data and the RNG key
    is replica-independent, so the reroute is invisible in the result."""
    clean = _service(small_graph, partitioned)
    want = clean.submit(SEEDS, _spec(), key=(9, 0)).result(timeout=30)

    plan = FaultPlan.bernoulli(0.3, site="server.*.0", seed=5, burst=8, limit=8)
    chaotic = _service(
        small_graph,
        partitioned,
        replicas=2,
        fault_plan=plan,
        retry_policy=RetryPolicy(max_attempts=3),
    )
    got = chaotic.submit(SEEDS, _spec(), key=(9, 0)).result(timeout=30)
    _assert_same_subgraph(want, got)
    assert not got.degraded
    stats = chaotic.stats()
    assert stats.failovers > 0  # replicas actually served dispatches
    assert chaotic.faults.total_failures() > 0


def test_degraded_is_flagged_never_silent(small_graph, partitioned):
    plan = FaultPlan.bernoulli(1.0, site="server.*")  # unlimited failures
    svc = _service(
        small_graph,
        partitioned,
        fault_plan=plan,
        # 4 attempts: the 3rd consecutive failure trips each breaker, so
        # the run also demonstrates quarantine under sustained failure
        retry_policy=RetryPolicy(max_attempts=4),
    )
    sub = svc.submit(SEEDS[:40], _spec((4,)), key=(1, 0)).result(timeout=30)
    assert sub.degraded and sub.lost_dispatches > 0
    assert all(h.src.shape[0] == 0 for h in sub.hops)  # nothing served...
    assert svc.stats().degraded == sub.lost_dispatches  # ...and counted
    health = svc.server_health()
    assert set(health.values()) <= {"up", "quarantined"}
    assert any(v == "quarantined" for v in health.values())


def test_sample_timeout(small_graph, partitioned, monkeypatch):
    from repro.core.sampling.service import SampleTimeout

    svc = _service(small_graph, partitioned, ticket_timeout=0.05)
    ticket = svc.submit(SEEDS[:8], _spec((4,)), key=(2, 0))
    monkeypatch.setattr(
        svc, "_advance_round", lambda deadline=None: time.sleep(0.01)
    )
    with pytest.raises(SampleTimeout):
        ticket.result()  # falls back to the service-level ticket_timeout
    monkeypatch.undo()
    assert ticket.result(timeout=30) is not None  # still completable


# ---------------------------------------------------------------------------
# Storage: checksums, retry, tier fall-through
# ---------------------------------------------------------------------------
def _filled_store(path, rows=256, dim=4, chunk_rows=32, **kw):
    from repro.core.storage import DFSTier

    store = DFSTier(str(path), rows, dim, chunk_rows=chunk_rows, **kw)
    vals = np.arange(rows * dim, dtype=np.float32).reshape(rows, dim)
    store.write_rows(np.arange(rows), vals)
    return store, vals


def test_disk_tier_missing_chunk_error(tmp_path):
    from repro.core.storage import ChunkReadError, DiskTier

    tier = DiskTier(32, 4, path=str(tmp_path / "d"))
    with pytest.raises(ChunkReadError, match=r"tier_000042\.npy"):
        tier.read_chunk(42)


def test_disk_tier_truncated_file_error(tmp_path):
    from repro.core.storage import ChunkReadError, DiskTier

    tier = DiskTier(32, 4, path=str(tmp_path / "d"))
    block = np.ones((32, 4), dtype=np.float32)
    tier.write_chunk(3, block)
    fn = tier._chunk_file(3)
    with open(fn, "r+b") as fh:
        fh.truncate(os.path.getsize(fn) // 2)
    with pytest.raises(ChunkReadError, match="truncated or corrupt"):
        tier.read_chunk(3)


def test_disk_tier_partial_write_cleanup(tmp_path, monkeypatch):
    from repro.core.storage import DiskTier
    from repro.core.storage import tiers as tiers_mod

    tier = DiskTier(32, 4, path=str(tmp_path / "d"))
    good = np.full((32, 4), 7.0, dtype=np.float32)
    tier.write_chunk(1, good)

    def exploding_save(fh, block):
        fh.write(b"\x93NUMPY partial garbage")
        raise OSError("disk full")

    monkeypatch.setattr(tiers_mod.np, "save", exploding_save)
    with pytest.raises(OSError, match="disk full"):
        tier.write_chunk(1, np.zeros((32, 4), dtype=np.float32))
    monkeypatch.undo()
    # no partial temp file left behind, previous good chunk intact
    leftovers = [f for f in sorted(os.listdir(tmp_path / "d")) if f.endswith(".tmp")]
    assert leftovers == []
    np.testing.assert_array_equal(tier.read_chunk(1), good)
    assert 1 in tier  # still accounted as held


def test_disk_tier_checksum_detects_corruption(tmp_path):
    from repro.core.storage import ChunkReadError, DiskTier

    plan = FaultPlan.bernoulli(1.0, site="disk.corrupt", limit=1)
    tier = DiskTier(32, 4, path=str(tmp_path / "d"), faults=plan.injector())
    block = np.arange(128, dtype=np.float32).reshape(32, 4)
    tier.write_chunk(0, block)
    with pytest.raises(ChunkReadError, match="checksum"):
        tier.read_chunk(0)  # bit-flip injected, checksum catches it
    np.testing.assert_array_equal(tier.read_chunk(0), block)  # limit spent


def test_dfs_store_checksum_detects_corruption(tmp_path):
    from repro.core.storage import ChunkCorruptionError

    plan = FaultPlan.bernoulli(1.0, site="dfs.corrupt", limit=1)
    store, vals = _filled_store(tmp_path / "s", faults=plan.injector())
    with pytest.raises(ChunkCorruptionError):
        store.read_chunk(0)
    np.testing.assert_array_equal(store.read_chunk(0), vals[:32])


def test_dfs_store_partial_write_cleanup(tmp_path, monkeypatch):
    from repro.core.storage import store as store_mod

    store, vals = _filled_store(tmp_path / "s")
    monkeypatch.setattr(
        store_mod.np,
        "save",
        lambda fh, block: (_ for _ in ()).throw(OSError("disk full")),
    )
    with pytest.raises(OSError, match="disk full"):
        store.write_chunk(0, np.zeros((32, 4), dtype=np.float32))
    monkeypatch.undo()
    assert not [f for f in sorted(os.listdir(tmp_path / "s")) if f.endswith(".tmp")]
    np.testing.assert_array_equal(store.read_chunk(0), vals[:32])


def test_hybrid_cache_falls_through_dead_tier(tmp_path):
    from repro.core.storage import DiskTier, HybridCache, MemoryTier

    store, vals = _filled_store(tmp_path / "s")
    # disk tier always fails its reads; memory tier is tiny so most reads
    # land on disk first and must fall through to the DFS store
    plan = FaultPlan.bernoulli(1.0, site="disk.read")
    tiers = [
        MemoryTier(32, 4, capacity=1),
        DiskTier(32, 4, path=str(tmp_path / "d"), faults=plan.injector()),
    ]
    cache = HybridCache(
        store, tiers, policy="fifo", retry_policy=RetryPolicy(max_attempts=2)
    )
    cache.fill_for(np.arange(256))
    for c in (0, 3, 5, 7, 2, 6):
        rows = np.arange(c * 32, c * 32 + 8)
        np.testing.assert_array_equal(cache.read_rows(rows), vals[rows])
    s = cache.stats
    assert s.failovers > 0  # dead tier dropped chunks, store served them
    assert s.retries > 0
    assert s.as_dict()["failovers"] == s.failovers


def test_hybrid_cache_retry_recovers_transient(tmp_path):
    from repro.core.storage import DiskTier, HybridCache, MemoryTier

    store, vals = _filled_store(tmp_path / "s")
    # at most 1 failure per plan-limit: the in-tier retry always recovers
    plan = FaultPlan.bernoulli(0.5, site="disk.read", seed=11, limit=1)
    tiers = [
        MemoryTier(32, 4, capacity=1),
        DiskTier(32, 4, path=str(tmp_path / "d"), faults=plan.injector()),
    ]
    cache = HybridCache(
        store, tiers, policy="fifo", retry_policy=RetryPolicy(max_attempts=3)
    )
    cache.fill_for(np.arange(256))
    for c in (0, 3, 5, 7, 2, 6):
        rows = np.arange(c * 32, c * 32 + 8)
        np.testing.assert_array_equal(cache.read_rows(rows), vals[rows])
    assert cache.stats.retries >= 1
    assert cache.stats.failovers == 0  # retry recovered; nothing fell through


def test_store_read_retries_through_cache(tmp_path):
    from repro.core.storage import DiskTier, HybridCache, MemoryTier

    plan = FaultPlan.bernoulli(1.0, site="dfs.read", limit=1)
    store, vals = _filled_store(tmp_path / "s", faults=plan.injector())
    cache = HybridCache(
        store,
        [MemoryTier(32, 4, capacity=2), DiskTier(32, 4)],
        policy="fifo",
        retry_policy=RetryPolicy(max_attempts=2),
    )
    rows = np.arange(8)
    np.testing.assert_array_equal(cache.read_rows(rows), vals[rows])
    assert cache.stats.store_retries == 1


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------
def _tree():
    return {
        "params": {
            "w": np.arange(6, dtype=np.float32).reshape(2, 3),
            "layers": [np.ones(4, dtype=np.float32), np.zeros(2, np.float32)],
        },
        "opt": {"mu": np.full(3, 0.5, dtype=np.float32)},
    }


def test_checkpoint_roundtrip(tmp_path):
    from repro.train.checkpoint import load_checkpoint, save_checkpoint

    path = str(tmp_path / "ck")
    final = save_checkpoint(path, _tree(), step=17)
    assert final.endswith(".npz") and os.path.exists(final)
    tree, step = load_checkpoint(path, _tree())
    assert step == 17
    np.testing.assert_array_equal(
        np.asarray(tree["params"]["w"]), _tree()["params"]["w"]
    )
    np.testing.assert_array_equal(
        np.asarray(tree["params"]["layers"][0]), np.ones(4)
    )


def test_checkpoint_atomic_on_crash(tmp_path, monkeypatch):
    from repro.train import checkpoint as ck

    path = str(tmp_path / "ck.npz")
    ck.save_checkpoint(path, _tree(), step=1)

    monkeypatch.setattr(
        ck.os,
        "replace",
        lambda a, b: (_ for _ in ()).throw(OSError("crash mid-rename")),
    )
    with pytest.raises(OSError, match="crash mid-rename"):
        ck.save_checkpoint(path, _tree(), step=2)
    monkeypatch.undo()
    # the old checkpoint survives untouched; no temp litter
    assert not [f for f in sorted(os.listdir(tmp_path)) if f.endswith(".tmp")]
    _, step = ck.load_checkpoint(path, _tree())
    assert step == 1


def test_checkpoint_errors(tmp_path):
    from repro.train.checkpoint import (
        CheckpointError,
        load_checkpoint,
        save_checkpoint,
    )

    with pytest.raises(CheckpointError, match="no checkpoint file"):
        load_checkpoint(str(tmp_path / "absent"), _tree())

    path = str(tmp_path / "ck")
    save_checkpoint(path, _tree())

    bigger = _tree()
    bigger["params"]["extra"] = np.zeros(3)
    with pytest.raises(CheckpointError, match="missing key 'params/extra'"):
        load_checkpoint(path, bigger)

    smaller = _tree()
    del smaller["opt"]
    with pytest.raises(CheckpointError, match="structure mismatch"):
        load_checkpoint(path, smaller)

    reshaped = _tree()
    reshaped["params"]["w"] = np.zeros((3, 2), dtype=np.float32)
    with pytest.raises(CheckpointError, match="shape mismatch at 'params/w'"):
        load_checkpoint(path, reshaped)

    with open(str(tmp_path / "junk.npz"), "wb") as fh:
        fh.write(b"not an npz")
    with pytest.raises(CheckpointError, match="unreadable"):
        load_checkpoint(str(tmp_path / "junk"), _tree())


# ---------------------------------------------------------------------------
# Crash-safe pipelines
# ---------------------------------------------------------------------------
def _pipeline(graph, partitioned, prefetch, **kw):
    from repro.api.pipeline import BatchPipeline

    svc = _service(graph, partitioned)
    return BatchPipeline(
        svc,
        graph,
        np.arange(0, 500),
        [4, 4],
        2,
        batch_size=64,
        prefetch=prefetch,
        seed=3,
        **kw,
    )


def _collect(pipe, epochs):
    out = []
    for seeds, batch in pipe.batches(epochs):
        out.append((np.asarray(seeds).copy(), np.asarray(batch.feats).copy()))
    return out


@pytest.mark.skipif(not FORK, reason="process-mode pipeline needs fork")
def test_worker_kill_respawn_bit_identical(small_graph, partitioned):
    base = _pipeline(small_graph, partitioned, 0)
    ref = _collect(base, 1) + _collect(base, 1)  # two runs, shared state

    pipe = _pipeline(small_graph, partitioned, 1, workers="process")
    got = _collect(pipe, 1)  # run 1 completes normally
    for i, (seeds, batch) in enumerate(pipe.batches(1)):  # run 2 crashes
        got.append((np.asarray(seeds).copy(), np.asarray(batch.feats).copy()))
        if i == 2:
            pipe._proc.kill()  # simulate an OOM-killed worker mid-epoch
            time.sleep(0.2)
    pipe.close()

    assert pipe.respawn_count == 1
    assert len(got) == len(ref)
    for (s1, f1), (s2, f2) in zip(ref, got):
        np.testing.assert_array_equal(s1, s2)
        np.testing.assert_array_equal(f1, f2)


@pytest.mark.skipif(not FORK, reason="process-mode pipeline needs fork")
def test_worker_crash_budget_exhausted(small_graph, partitioned):
    pipe = _pipeline(
        small_graph, partitioned, 1, workers="process", worker_respawns=0
    )
    with pytest.raises(RuntimeError, match="prefetch worker died"):
        for i, _ in enumerate(pipe.batches(1)):
            if i == 1:
                pipe._proc.kill()
                time.sleep(0.2)
    pipe.close()


@pytest.mark.skipif(not FORK, reason="process-mode pipeline needs fork")
def test_close_escalates_to_kill_on_wedged_worker(small_graph, partitioned):
    from repro.api.pipeline import BatchPipeline

    class WedgedPipeline(BatchPipeline):
        def _worker_loop(self):  # ignores stop commands AND SIGTERM
            signal.signal(signal.SIGTERM, signal.SIG_IGN)
            while True:
                time.sleep(0.2)

    svc = _service(small_graph, partitioned)
    pipe = WedgedPipeline(
        svc,
        small_graph,
        np.arange(0, 128),
        [4],
        1,
        batch_size=64,
        prefetch=1,
        workers="process",
        seed=0,
    )
    pipe._ensure_worker()
    proc = pipe._proc
    assert proc.is_alive()
    time.sleep(0.3)  # let the child install its SIGTERM ignore
    t0 = time.monotonic()
    pipe.close(timeout=0.5)
    elapsed = time.monotonic() - t0
    assert not proc.is_alive()  # SIGKILL got it despite the SIGTERM ignore
    assert elapsed < 5.0  # bounded, not the old indefinite join
    pipe.close()  # idempotent


# ---------------------------------------------------------------------------
# Crash-safe training: checkpoint/resume and chaos bit-identity
# ---------------------------------------------------------------------------
def _trainer(graph, partitioned, **kw):
    from repro.models.gnn import GNNModel
    from repro.train import GNNTrainer

    model = GNNModel(
        "sage", graph.vertex_feats.shape[1], hidden=16, num_layers=2, num_classes=4
    )
    svc = kw.pop("service", None) or _service(graph, partitioned)
    return GNNTrainer(
        model,
        svc,
        graph,
        [4, 4],
        np.arange(0, 512),
        batch_size=128,
        seed=0,
        prefetch=0,
        **kw,
    )


def _leaves(params):
    import jax

    return [np.asarray(x) for x in jax.tree.leaves(params)]


def test_crash_and_resume_bitwise_identical(small_graph, partitioned, tmp_path):
    # uninterrupted reference run: 6 steps
    a = _trainer(small_graph, partitioned)
    a.train(epochs=2, max_steps=6)

    # crashed run: auto-checkpoints every 2 steps, dies after step 3
    b = _trainer(
        small_graph,
        partitioned,
        checkpoint_dir=str(tmp_path),
        checkpoint_every=2,
    )
    b.train(epochs=2, max_steps=3)  # checkpoint on disk holds step 2

    # fresh process: resume from the checkpoint and finish the run
    c = _trainer(
        small_graph,
        partitioned,
        checkpoint_dir=str(tmp_path),
        checkpoint_every=2,
    )
    assert c.resume() == 2
    c.train(epochs=2, max_steps=6)

    for wa, wc in zip(_leaves(a.params), _leaves(c.params)):
        np.testing.assert_array_equal(wa, wc)
    for oa, oc in zip(_leaves(a.opt_state), _leaves(c.opt_state)):
        np.testing.assert_array_equal(oa, oc)


def test_trainer_checkpoint_config_validation(small_graph, partitioned):
    with pytest.raises(ValueError, match="checkpoint_dir"):
        _trainer(small_graph, partitioned, checkpoint_every=2)


def test_chaos_training_bit_identical(small_graph, partitioned):
    a = _trainer(small_graph, partitioned)
    a.train(epochs=1, max_steps=4)

    plan = FaultPlan.bernoulli(0.3, site="server.*", seed=77, limit=2)
    chaotic = _service(
        small_graph,
        partitioned,
        fault_plan=plan,
        retry_policy=RetryPolicy(max_attempts=4),
    )
    b = _trainer(small_graph, partitioned, service=chaotic)
    b.train(epochs=1, max_steps=4)

    assert chaotic.faults.total_failures() > 0  # chaos actually happened
    for wa, wb in zip(_leaves(a.params), _leaves(b.params)):
        np.testing.assert_array_equal(wa, wb)


def test_chaos_inference_bit_identical(small_graph, partitioned, tmp_path):
    """Layerwise inference over a chaotic system (sampling faults with a
    replica + storage-tier faults with retries) matches the clean system's
    embeddings exactly."""
    from repro.api import GLISPConfig, GLISPSystem

    def run(cfg, wd):
        import jax

        from repro.models.gnn import GNNModel

        system = GLISPSystem.build(small_graph, cfg)
        model = GNNModel("sage", 16, hidden=16, num_layers=2)
        params = model.init(jax.random.PRNGKey(0))
        fns = [model.embed_layer_fn(params, k) for k in range(2)]
        res = system.infer_layerwise(fns, wd)
        targets = np.arange(64)
        return res.final_store.read_rows_direct(res.newid[targets]), system

    base = GLISPConfig(num_parts=4, fanouts=(6, 3), chunk_rows=128)
    clean, _ = run(base, str(tmp_path / "clean"))
    plan = FaultPlan(
        seed=13,
        sites=(
            ("server.*", FaultSpec(p=0.2, limit=2)),
            ("disk.read", FaultSpec(p=0.3, limit=4)),
            ("memory.read", FaultSpec(p=0.1, limit=2)),
        ),
    )
    chaotic_cfg = base.replace(
        fault_plan=plan,
        server_replicas=2,
        retry_policy=RetryPolicy(max_attempts=4),
    )
    chaos, system = run(chaotic_cfg, str(tmp_path / "chaos"))
    np.testing.assert_array_equal(clean, chaos)
    assert system.service.faults.total_failures() >= 0  # injector armed


# ---------------------------------------------------------------------------
# Config threading
# ---------------------------------------------------------------------------
def test_config_fault_knobs_validate_and_serialize():
    from repro.api import GLISPConfig

    cfg = GLISPConfig(
        fault_plan=FaultPlan.bernoulli(0.1, site="server.*"),
        retry_policy=RetryPolicy(max_attempts=2),
        ticket_timeout=5.0,
        server_replicas=2,
        checkpoint_every=10,
        checkpoint_dir="/tmp/ck",
    ).validate()
    d = cfg.to_dict()
    assert d["fault_plan"]["sites"] == [
        ["server.*", {"p": 0.1, "burst": 1, "limit": None}]
    ]
    assert d["retry_policy"]["max_attempts"] == 2

    with pytest.raises(ValueError):
        GLISPConfig(server_replicas=0).validate()
    with pytest.raises(ValueError):
        GLISPConfig(ticket_timeout=0.0).validate()
    with pytest.raises(ValueError):
        GLISPConfig(worker_respawns=-1).validate()
    with pytest.raises(ValueError):
        GLISPConfig(checkpoint_every=5).validate()  # no checkpoint_dir
    with pytest.raises(TypeError):
        GLISPConfig(fault_plan="server.*").validate()
    with pytest.raises(TypeError):
        GLISPConfig(retry_policy={"max_attempts": 2}).validate()


def test_system_threads_fault_knobs(small_graph):
    from repro.api import GLISPConfig, GLISPSystem

    plan = FaultPlan.bernoulli(0.05, site="server.*", limit=1)
    system = GLISPSystem.build(
        small_graph,
        GLISPConfig(
            num_parts=4,
            fanouts=(4, 4),
            fault_plan=plan,
            retry_policy=RetryPolicy(max_attempts=5),
            ticket_timeout=60.0,
            server_replicas=2,
        ),
    )
    svc = system.service
    assert svc.retry_policy.max_attempts == 5
    assert svc.ticket_timeout == 60.0
    assert isinstance(svc.faults, FaultInjector)
    assert len(system.server_health()) == 8  # 4 parts x 2 replicas
    sub = system.sample(np.arange(64))
    assert not sub.degraded

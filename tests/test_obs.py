"""The span recorder (``repro.obs``): it records only while JAX's profiler
traces; one offset maps its clock onto the trace's; and each batch's trip,
from the sampler through the forked producer's queue to the trainer's
device copy and step, is recorded under the batch's request index."""
import collections
import multiprocessing as mp
import os
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import obs
from repro.api import GLISPConfig, GLISPSystem

FORK = os.name == "posix" and "fork" in mp.get_all_start_methods()
TRIP = ("sample.wait", "batch.build", "batch.put", "pipeline.recv", "pipeline.h2d")


@pytest.fixture(autouse=True)
def _fresh():
    obs.reset()
    yield
    obs.reset()


@pytest.fixture(scope="module")
def system():
    from repro.graph import power_law_graph

    g = power_law_graph(1200, avg_degree=8, seed=11, feat_dim=16, num_classes=4)
    g.labels = g.vertex_types.astype(np.int32)
    return GLISPSystem.build(g, GLISPConfig(num_parts=4, fanouts=(8, 4), batch_size=128))


def _loader(system, workers, prefetch=2):
    pl = system.loader(np.arange(1000), num_layers=2, prefetch=prefetch, inflight=2, seed=5)
    pl.workers = workers
    return pl


def _events(trace_dir: Path, names) -> dict:
    """Host events of the trace by name, each a list of (start, end) on the
    trace's clock."""
    from jax.profiler import ProfileData

    (path,) = trace_dir.glob("plugins/profile/*/*.xplane.pb")
    out = collections.defaultdict(list)
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in names:
                        out[ev.name].append((ev.start_ns, ev.end_ns))
    return out


def test_records_nothing_while_the_profiler_is_off():
    assert not obs.tracing()
    with obs.span("a", 1) as sp:
        sp.batch = 2
    obs.count("n", 3.0, 1)
    with obs.step("train", 1):
        pass
    assert obs.snapshot() == []
    assert obs.span("a") is obs.span("b")  # one inert object, none made per call


def test_records_under_the_profiler(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        assert obs.tracing()
        with obs.span("outer", 3, depth=2):
            with obs.span("inner") as sp:
                sp.batch = 4
            obs.count("n", 5, 3)
    assert not obs.tracing()
    recs = {r.name: r for r in obs.snapshot()}
    assert set(recs) == {"outer", "inner", "n"}
    outer, inner, n = recs["outer"], recs["inner"], recs["n"]
    assert (outer.batch, outer.parent, outer.attrs) == (3, None, {"depth": 2})
    assert (inner.batch, inner.parent) == (4, "outer")
    assert (n.value, n.parent, n.t0 == n.t1) == (5.0, "outer", True)
    assert outer.t0 <= inner.t0 <= inner.t1 <= n.t0 <= outer.t1
    assert {r.pid for r in recs.values()} == {os.getpid()}
    # a new trace starts a new list
    with jax.profiler.trace(str(tmp_path / "second")):
        with obs.span("again"):
            pass
    assert [r.name for r in obs.snapshot()] == ["again"]


def test_one_offset_maps_every_span_onto_the_trace(tmp_path):
    names = [f"clock.{k}" for k in range(4)]
    with jax.profiler.trace(str(tmp_path)):
        for k, name in enumerate(names):
            with obs.span(name, k):
                time.sleep(0.01)
            time.sleep(0.05)  # spans at least 50 ms apart
    recs = {r.name: r for r in obs.snapshot()}
    events = _events(tmp_path, set(names))
    assert all(len(events[n]) == 1 for n in names)
    offset = events[names[0]][0][0] - recs[names[0]].t0
    for n in names:
        (start, end), r = events[n][0], recs[n]
        assert abs(start - (r.t0 + offset)) < 100_000, n
        assert abs(end - (r.t1 + offset)) < 100_000, n


@pytest.mark.parametrize(
    "workers",
    [pytest.param("process", marks=pytest.mark.skipif(not FORK, reason="needs fork")), "thread"],
)
def test_each_batch_trip_is_recorded_under_its_index(system, workers, tmp_path):
    pl = _loader(system, workers)
    try:
        with jax.profiler.trace(str(tmp_path)):
            it = pl.indexed(1)
            consumed = [next(it) for _ in range(3)]
            it.close()  # stop early, as the trainer does at max_steps
            child = pl._proc.pid if workers == "process" else os.getpid()
    finally:
        pl.close()
    ids = [i for i, _, _ in consumed]
    assert ids == [0, 1, 2]
    by = collections.defaultdict(dict)
    for r in obs.snapshot():
        if r.batch is not None:
            by[r.batch][r.name] = r
    for i, _, batch in consumed:
        assert set(TRIP) <= set(by[i]), (i, sorted(by[i]))
        for name in ("sample.submit", "sample.wait", "batch.build", "batch.put"):
            assert by[i][name].pid == child
        if workers == "process":  # the wait for the slot the batch is built in
            slot = by[i]["batch.slot"]
            assert slot.pid == child
            assert by[i]["sample.wait"].t1 <= slot.t0 <= slot.t1 <= by[i]["batch.build"].t0
        else:
            assert "batch.slot" not in by[i]
        for name in ("pipeline.recv", "pipeline.h2d"):
            assert by[i][name].pid == os.getpid()
        assert by[i]["batch.put"].t0 < by[i]["pipeline.recv"].t1
        assert by[i]["batch.bytes"].value == sum(x.nbytes for x in jax.tree.leaves(batch))
    rounds = [r for r in obs.snapshot() if r.name == "sample.round"]
    assert rounds and all(r.parent == "sample.wait" and r.attrs["inflight"] >= 1 for r in rounds)


def test_serial_pipeline_records_the_same_producer_names(system, tmp_path):
    pl = _loader(system, "thread", prefetch=0)
    with jax.profiler.trace(str(tmp_path)):
        ids = [i for i, _, _ in pl.indexed(1)]
    names = collections.defaultdict(set)
    for r in obs.snapshot():
        names[r.batch].add(r.name)
    for i in ids:
        assert {"sample.submit", "sample.wait", "batch.build", "pipeline.h2d"} <= names[i]


@pytest.mark.skipif(not FORK, reason="process-mode pipeline needs fork")
def test_tracing_leaves_the_batch_stream_bit_identical(system, tmp_path):
    def stream(traced):
        pl = _loader(system, "process")
        try:
            if traced:
                with jax.profiler.trace(str(tmp_path)):
                    return [(i, s, jax.device_get(b)) for i, s, b in pl.indexed(1)]
            return [(i, s, jax.device_get(b)) for i, s, b in pl.indexed(1)]
        finally:
            pl.close()

    off, on = stream(False), stream(True)
    assert obs.snapshot()  # the traced stream was recorded
    assert len(off) == len(on) > 0
    for (i0, s0, b0), (i1, s1, b1) in zip(off, on):
        assert i0 == i1
        np.testing.assert_array_equal(s0, s1)
        for x0, x1 in zip(jax.tree.leaves(b0), jax.tree.leaves(b1)):
            np.testing.assert_array_equal(x0, x1)


def test_trainer_steps_carry_the_request_index(system, tmp_path):
    from repro.models.gnn import GNNModel

    g = system.graph
    model = GNNModel("sage", g.vertex_feats.shape[1], hidden=16, num_layers=2, num_classes=4)
    tr = system.trainer(model, np.arange(1000), prefetch=2)
    try:
        with jax.profiler.trace(str(tmp_path)):
            tr.train(max_steps=2)
            tr.train(max_steps=2)
        steps = [r.batch for r in obs.snapshot() if r.name == "train.step"]
        events = _events(tmp_path, {"train", "train.step"})
    finally:
        tr.pipeline.close()
    # the second call's batches follow the first's in the keyed stream: the
    # index is the producer's, not the trainer's step count
    assert steps[:2] == [0, 1] and len(set(steps)) == 4 and min(steps[2:]) > 1
    assert len(events["train"]) == len(events["train.step"]) == 4

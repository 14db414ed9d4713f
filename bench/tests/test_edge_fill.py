"""The ``train.edge_fill`` reader: the share of the consumed batches' edge
slots that hold a real sampled edge, None outside a recorded training
window or where the program records no edge counters."""
import pytest

import common
from repro import obs
from repro.obs import Record

MS = 1_000_000  # ns
PRODUCER, TRAINER = 11, 22


@pytest.fixture(autouse=True)
def _fresh():
    obs.reset()
    yield
    obs.reset()


def _read(r):
    return common.load_module(common.BENCH / "metrics" / "train.edge_fill.py").read(r)


def _window(edges):
    """Batches 0..len(edges)-1, each with 4096 edge slots of which
    ``edges[i]`` are real (None: no counters); the last one is built
    ahead and not consumed."""
    recs = []
    for i, real in enumerate(edges):
        t = 10 * i
        if real is not None:
            recs.append(Record("batch.edges", i, "batch.build", t * MS, t * MS, PRODUCER, value=real))
            recs.append(Record("batch.edge_slots", i, "batch.build", t * MS, t * MS, PRODUCER, value=4096))
        if i < len(edges) - 1:
            recs.append(Record("train.step", i, None, (t + 5) * MS, (t + 6) * MS, TRAINER))
    return recs


@pytest.mark.parametrize("edges, want", [
    pytest.param([1024, 3072, 4096], 50.0, id="consumed-batches-only"),
    pytest.param([4096, 4096, 0], 100.0, id="no-padding"),
    pytest.param([None, None, None], None, id="no-counters"),
])
def test_share_of_consumed_edge_slots_that_are_real(edges, want):
    obs.extend(_window(edges))
    got = _read({"kind": "train"})
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("r", [{}, {"kind": "infer"}, {"kind": "train"}])
def test_silent_outside_a_recorded_training_window(r):
    if r.get("kind") != "train":
        obs.extend(_window([1024, 2048, 4096]))
    assert _read(r) is None


def test_batch_build_counts_the_edges_and_slots(monkeypatch):
    """``subgraph_to_batch`` records both counters under the batch's index
    while the recorder is on, and nothing while it is off."""
    import numpy as np

    from repro.core.sampling.service import SampledHop, SampledSubgraph
    from repro.models.gnn.batching import subgraph_to_batch

    hop = lambda s, d: SampledHop(src=np.asarray(s), dst=np.asarray(d), eid=np.arange(len(s)))
    sub = SampledSubgraph(seeds=np.array([0, 1]), hops=[hop([0, 1, 1], [2, 3, 4]), hop([2], [5])])
    feats = np.zeros((6, 2), np.float32)
    subgraph_to_batch(sub, feats, None, 2, vertex_quantum=8, edge_quantum=16, index=7)
    assert obs.snapshot() == []
    monkeypatch.setattr(obs._REC, "on", lambda: True)  # as while the profiler traces
    subgraph_to_batch(sub, feats, None, 2, vertex_quantum=8, edge_quantum=16, index=7)
    counts = {r.name: (r.batch, r.value) for r in obs.snapshot()}
    # layer 0 takes hops 0..1 (4 edges), layer 1 hop 0 (3); 16 slots each
    assert counts == {"batch.edges": (7, 7.0), "batch.edge_slots": (7, 32.0)}

"""The per-layer readers of the program's spans: None outside a training
cell or where nothing was recorded, and on a recorded window the numbers
its spans give, counting only the batches the window's steps consumed."""
import json

import pytest

import common
from repro import obs
from repro.obs import Record

BENCHMARK = json.loads((common.ROOT / "BENCHMARK.json").read_text())
SPAN_METRICS = [
    m["name"] for m in BENCHMARK["per_layer"] if m["source"] in ("program_span", "program_counter")
    and m["name"] not in ("train.sample_ms", "train.step_ms", "train.window_compiles")
]
MS = 1_000_000  # ns
PRODUCER, TRAINER = 11, 22


@pytest.fixture(autouse=True)
def _fresh():
    obs.reset()
    yield
    obs.reset()


def _read(name, r):
    return common.load_module(common.BENCH / "metrics" / f"{name}.py").read(r)


def test_the_span_readers_are_declared():
    assert sorted(SPAN_METRICS) == sorted([
        "train.sampler_ms", "train.build_ms", "train.transit_ms", "train.recv_ms",
        "train.h2d_ms", "train.producer_busy", "train.batch_mb", "train.shm_share",
    ])


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_reader_is_silent_outside_a_recorded_training_window(name):
    obs.extend(_window())
    assert _read(name, {}) is None
    assert _read(name, {"kind": "infer"}) is None
    obs.reset()
    assert _read(name, {"kind": "train"}) is None


def _span(name, batch, t0, t1, pid=PRODUCER, parent=None):
    return Record(name, batch, parent, t0 * MS, t1 * MS, pid)


def _window():
    """Two consumed batches (0, 1) and one built ahead (2), in ms:
    producer submit 1, wait 4 with a round of 3 inside, build 2, put, then
    the trainer's recv, h2d and step."""
    recs = []
    for i, t in ((0, 0), (1, 10), (2, 20)):
        recs += [
            _span("sample.submit", i, t, t + 1),
            _span("sample.wait", i, t + 1, t + 5),
            _span("sample.round", None, t + 2, t + 5, parent="sample.wait"),
            _span("batch.build", i, t + 5, t + 7),
            Record("batch.bytes", i, None, (t + 7) * MS, (t + 7) * MS, PRODUCER, value=2e6 + i),
            _span("batch.put", i, t + 7, t + 8),
        ]
        if i < 2:
            recs += [
                _span("pipeline.recv", i, t + 6, t + 10, pid=TRAINER),
                _span("pipeline.h2d", i, t + 10, t + 11, pid=TRAINER),
                _span("train.step", i, t + 11, t + 13, pid=TRAINER),
            ]
    return recs


def test_readers_on_a_recorded_window():
    obs.extend(_window())
    r = {"kind": "train"}
    assert _read("train.sampler_ms", r) == pytest.approx(5.0)
    assert _read("train.build_ms", r) == pytest.approx(2.0)
    assert _read("train.recv_ms", r) == pytest.approx(4.0)
    assert _read("train.h2d_ms", r) == pytest.approx(1.0)
    assert _read("train.transit_ms", r) == pytest.approx(3.0)  # recv end 10 - put start 7
    assert _read("train.batch_mb", r) == pytest.approx(2.0000005)  # batch 2 left out
    # window 0..23 ms; producer works 0..7, 10..17, 20..23 (batch 2, clipped)
    assert _read("train.producer_busy", r) == pytest.approx(17 / 23 * 100)

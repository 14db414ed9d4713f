"""The ``train.shm_share`` reader: the share of the consumed batches' bytes
that went through a shared-memory slot, None outside a recorded training
window or where no slot was used."""
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
    return common.load_module(common.BENCH / "metrics" / "train.shm_share.py").read(r)


def _window(shm):
    """Batches 0..len(shm)-1, each of 2e6 + i bytes with ``shm[i]`` of them
    through a slot (None: no counter); the last one is built ahead and not
    consumed."""
    recs = []
    for i, placed in enumerate(shm):
        t = 10 * i
        recs.append(Record("batch.bytes", i, None, t * MS, t * MS, PRODUCER, value=2e6 + i))
        if placed is not None:
            recs.append(Record("batch.shm_bytes", i, None, t * MS, t * MS, PRODUCER, value=placed))
        if i < len(shm) - 1:
            recs.append(Record("train.step", i, None, (t + 5) * MS, (t + 6) * MS, TRAINER))
    return recs


@pytest.mark.parametrize("shm, want", [
    pytest.param([2e6, 2e6 + 1, 2e6 + 2], 100.0, id="every-batch-through-a-slot"),
    pytest.param([2e6, 0, 2e6 + 2], 2e6 / (4e6 + 1) * 100, id="one-pickled-fallback"),
    pytest.param([2e6, 2e6 + 1, 0], 100.0, id="unconsumed-batch-left-out"),
    pytest.param([None, None, None], None, id="no-slots"),
])
def test_share_of_consumed_bytes_through_a_slot(shm, want):
    obs.extend(_window(shm))
    got = _read({"kind": "train"})
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("r", [{}, {"kind": "infer"}, {"kind": "train"}])
def test_silent_outside_a_recorded_training_window(r):
    if r.get("kind") != "train":
        obs.extend(_window([2e6, 2e6 + 1, 2e6 + 2]))
    assert _read(r) is None

"""The reduction from a profiler trace to busy time, top operations and
named idle gaps, on a small hand-written trace and on one recorded on a
v5e chip."""
from pathlib import Path

import pytest

import trace_reduce

RECORDED = Path(__file__).resolve().parent / "data" / "tiny_tpu.xplane.pb"

# one host span (the window, 100 us) with a Python frame inside it, and a
# device running two overlapping operations, 10-40 us into the window
HANDMADE = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 50000000 duration_ps: 40000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "queue.get" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 20000000 }
    events { metadata_id: 1 offset_ps: 100000000 duration_ps: 5000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "convolution.2" } }
}
"""


def _write(tmp_path, text: str) -> Path:
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return path


def test_handmade_trace(tmp_path):
    r = trace_reduce.reduce(_write(tmp_path, HANDMADE), "bench.window")
    assert r["window_s"] == pytest.approx(100e-6)
    # the union of [10, 30) and [20, 40) us; the op after the window is cut
    assert r["busy_s"] == pytest.approx(30e-6)
    ops = dict(r["breakdown"]["device_ops"])
    assert ops == pytest.approx({"fusion.1": 20e-6, "convolution.2": 20e-6})
    gaps = dict(r["breakdown"]["idle_gaps"])
    # 40-100 us is idle while the host waits in queue.get (40 of 60 us);
    # the first 10 us is a short gap
    assert gaps == pytest.approx({"queue.get": 60e-6, trace_reduce.SHORT_GAP: 10e-6})


def test_no_device_plane_reads_nothing(tmp_path):
    host_only = HANDMADE[: HANDMADE.index("planes {\n  id: 2")]
    assert trace_reduce.reduce(_write(tmp_path, host_only), "bench.window") is None


def test_missing_window_is_an_error(tmp_path):
    with pytest.raises(ValueError, match="no host span"):
        trace_reduce.reduce(_write(tmp_path, HANDMADE), "other.window")


def test_recorded_tpu_trace():
    """Three 1024 x 1024 matmuls on one v5e chip with 2 ms host sleeps
    between them, inside the window span."""
    r = trace_reduce.reduce(RECORDED, "bench.window")
    assert r is not None
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["breakdown"]["device_ops"]
    assert r["breakdown"]["idle_gaps"]
    assert sum(t for _, t in r["breakdown"]["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6
    )


def test_recorded_tpu_trace_reads_as_before():
    """One chip: the numbers the reduction gave this trace before it
    averaged over chips."""
    r = trace_reduce.reduce(RECORDED, "bench.window")
    assert r["busy_s"] == 2.3713e-05
    assert r["window_s"] == pytest.approx(0.00998729, rel=1e-12)
    assert dict(r["breakdown"]["idle_gaps"]) == pytest.approx(
        {"$time sleep": 0.009963572, trace_reduce.SHORT_GAP: 5e-09}, rel=1e-12
    )


def test_chips_are_averaged(tmp_path):
    """Three chips: TPU:0 as in the one-chip trace (busy 30 of 100 us),
    TPU:1 busy 10-20 us, TPU:2 with an operation after the window only.
    Busy is the mean over the three chips, so one minus busy over the
    window is the mean of their idle shares; TPU:2 counts as idle
    throughout."""
    more = """
planes {
  id: 3 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
planes {
  id: 4 name: "/device:TPU:2"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 100000000 duration_ps: 5000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
"""
    r = trace_reduce.reduce(_write(tmp_path, HANDMADE + more), "bench.window")
    assert r["busy_s"] == pytest.approx((30e-6 + 10e-6 + 0.0) / 3)
    idle = 1 - r["busy_s"] / r["window_s"]
    assert idle == pytest.approx((0.7 + 0.9 + 1.0) / 3)
    assert sum(t for _, t in r["breakdown"]["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9
    )
    assert dict(r["breakdown"]["device_ops"]) == pytest.approx(
        {"fusion.1": 30e-6 / 3, "convolution.2": 20e-6 / 3}
    )

"""From a profiler trace (``.xplane.pb``) to the device's busy time, its
busiest operations and its idle gaps named by what the host was doing.

The window is the host span the harness opened around the measured call.
Busy time is the union of the intervals in which an operation ran on a
device (the device plane's ``XLA Ops`` line), inside the window, averaged
over the chips whose trace holds operations; a chip that ran nothing
inside the window counts as busy for none of it.  So ``1 - busy_s /
window_s`` is the mean over the chips of each chip's idle share, and on
one chip that chip's idle share.  An idle gap is a stretch of the
window in which the device ran nothing; it is named by the shortest host
event that covers at least half of it (a Python frame or a runtime
span), else by the host event that overlaps it most; gaps under 20 us
are counted together.  Idle time is summed by that name, over the chips,
and divided by their number, as are the operations' times.
"""
from __future__ import annotations

import collections
from pathlib import Path

import numpy as np

OPS_LINE = "XLA Ops"
# gaps this short lie between one operation and the next that was already
# queued: they are counted together, not named one by one
SHORT_GAP_NS = 20_000
SHORT_GAP = "between queued ops"


def _op_name(hlo: str) -> str:
    """An operation as its HLO instruction and output shape, without the
    operands: ``%fusion.4 = f32[208896,256]`` (a tuple by its first part)."""
    name, _, rest = hlo.partition(" = ")
    shape = rest.lstrip("(").split("{", 1)[0].split(" ", 1)[0]
    return f"{name} = {shape}" if shape else name


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: float, e: float, lo: float, hi: float):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _name_gap(s: float, e: float, hs, he, names) -> str:
    ov = np.minimum(e, he) - np.maximum(s, hs)
    cover = np.flatnonzero(ov >= 0.5 * (e - s))
    if cover.size:
        return names[cover[np.argmin((he - hs)[cover])]]
    if ov.size and ov.max() > 0:
        return names[int(np.argmax(ov))]
    return "no host event"


def reduce(path: str | Path, window: str, top: int = 10) -> dict | None:
    """``busy_s``, ``window_s`` and the ``breakdown`` of one traced window;
    None where the trace holds no device plane with operations (a CPU run)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    host_events: list[tuple[float, float, str]] = []
    span = None
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == window and span is None:
                        span = (ev.start_ns, ev.end_ns)
                    host_events.append((ev.start_ns, ev.end_ns, ev.name))
        elif plane.name.startswith("/device:") and not plane.name.startswith("/device:CPU"):
            ops = [l for l in plane.lines if l.name == OPS_LINE]
            evs = [(ev.start_ns, ev.end_ns, _op_name(ev.name)) for ev in ops[0].events] if ops else []
            if evs:
                devices.append(evs)
    if span is None:
        raise ValueError(f"no host span {window!r} in {path}")
    lo, hi = span
    host_events = [h for h in host_events if h[2] != window]
    busy_total, per_op = 0.0, collections.Counter()
    gaps: list[tuple[float, float]] = []
    ran = 0  # chips that ran an operation inside the window
    for evs in devices:
        clipped = [(c, n) for s, e, n in evs if (c := _clip(s, e, lo, hi))]
        ran += bool(clipped)
        for (s, e), n in clipped:
            per_op[n] += (e - s) * 1e-9
        merged = _union([c for c, _ in clipped])
        busy_total += sum(e - s for s, e in merged) * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    if not ran:
        return None
    used = len(devices)
    hs = np.array([h[0] for h in host_events], np.float64)
    he = np.array([h[1] for h in host_events], np.float64)
    names = [h[2] for h in host_events]
    idle = collections.Counter()
    for s, e in gaps:
        name = SHORT_GAP if e - s < SHORT_GAP_NS else _name_gap(s, e, hs, he, names)
        idle[name] += (e - s) * 1e-9 / used
    return {
        "busy_s": busy_total / used,
        "window_s": (hi - lo) * 1e-9,
        "breakdown": {
            "device_ops": [[n, t / used] for n, t in per_op.most_common(top)],
            "idle_gaps": [[n, t] for n, t in idle.most_common(top)],
        },
    }


def find_trace(log_dir: str | Path) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]

"""Share of the traced training window in which the batch producer worked:
the union of its ``sample.*`` and ``batch.build`` spans, from the first
consumed batch's first sample span to the end of the last ``train.step``.
``batch.put`` is left out, as time blocked on a full queue is no work: a
share under 100% while the trainer waits puts the pace in the batch's
trip, not in the producer."""
from spans import window

WORK = ("sample.submit", "sample.wait", "sample.round", "batch.build")


def read(r: dict):
    w = window(r)
    if w is None:
        return None
    starts = [s.t0 for i in w.consumed for n in ("sample.submit", "sample.wait") for s in w.by_batch[i][n]]
    ends = [s.t1 for i in w.consumed for s in w.by_batch[i]["train.step"]]
    if not starts:
        return None
    lo, hi = min(starts), max(ends)
    busy, reach = 0, lo
    for t0, t1 in sorted((x.t0, x.t1) for x in w.records if x.name in WORK):
        t0, t1 = max(t0, reach), min(t1, hi)
        if t1 > t0:
            busy += t1 - t0
            reach = t1
    return busy / (hi - lo) * 100.0

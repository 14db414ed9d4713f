"""Median over the batches the traced training window consumed of the
batch's trip from the producer to the trainer: the end of its
``pipeline.recv`` (trainer) minus the start of its ``batch.put``
(producer), two processes on one clock.  It holds the wait for room in the
queue, the pickling, the pipe and the unpickling."""
import statistics

from spans import window


def read(r: dict):
    w = window(r)
    if w is None:
        return None
    trips = []
    for i in w.consumed:
        put, recv = w.first(i, "batch.put"), w.first(i, "pipeline.recv")
        if put is not None and recv is not None:
            trips.append((recv.t1 - put.t0) * 1e-6)
    return statistics.median(trips) if trips else None

"""Per step of the traced training window, the trainer's read of the next
batch from the producer's queue, unpickling included (``pipeline.recv``
spans)."""
from spans import window


def read(r: dict):
    w = window(r)
    return w.mean_ms("pipeline.recv") if w else None

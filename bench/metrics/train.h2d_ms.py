"""Per step of the traced training window, the trainer's staging of the
batch's arrays to the device (``pipeline.h2d`` spans): the host's part of
an asynchronous copy."""
from spans import window


def read(r: dict):
    w = window(r)
    return w.mean_ms("pipeline.h2d") if w else None

"""Per batch the traced training window consumed, the producer's build of
the padded batch from its sample (``batch.build`` spans)."""
from spans import window


def read(r: dict):
    w = window(r)
    return w.mean_ms("batch.build") if w else None

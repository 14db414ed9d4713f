"""Per batch the traced training window consumed, the megabytes (10^6
bytes) of its padded arrays as the producer hands it to the queue
(``batch.bytes`` counter): a count that repeats exactly."""
from spans import window


def read(r: dict):
    w = window(r)
    if w is None:
        return None
    sizes = [c.value for i in w.consumed for c in w.by_batch[i]["batch.bytes"]]
    return sum(sizes) / len(sizes) / 1e6 if sizes else None

"""Share of the bytes of the batches the traced training window consumed
that came from the forked producer through a shared-memory slot
(``batch.shm_bytes``) rather than the pickled queue pipe, over their
padded bytes (``batch.bytes``), in %.  None where the program has no
slots, as in thread mode."""
from spans import window


def read(r: dict):
    w = window(r)
    if w is None:
        return None
    shm = [c.value for i in w.consumed for c in w.by_batch[i]["batch.shm_bytes"]]
    total = sum(c.value for i in w.consumed for c in w.by_batch[i]["batch.bytes"])
    return sum(shm) / total * 100.0 if shm and total else None

"""Of the edge slots of the batches the traced training window consumed,
the share that holds a real sampled edge, in %: the consumed batches'
``batch.edges`` over their ``batch.edge_slots`` (each summed over the
layers).  The rest is padding that every per-edge operation of the step
still computes.  None where the program records no such counters."""
from spans import window


def read(r: dict):
    w = window(r)
    if w is None:
        return None
    edges = [c.value for i in w.consumed for c in w.by_batch[i]["batch.edges"]]
    slots = sum(c.value for i in w.consumed for c in w.by_batch[i]["batch.edge_slots"])
    return sum(edges) / slots * 100.0 if edges and slots else None

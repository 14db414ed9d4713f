"""What the program's span recorder (``repro.obs``) holds for a traced
training window, for the per-layer readers that read spans.

A batch is counted where the window consumed it: where its request index
has a ``train.step`` span.  Batches the producer built ahead, or that were
drained when the window ended, are left out.  A program without the
recorder, or a window that recorded nothing, gives None.
"""
from __future__ import annotations

import collections
import statistics


class Window:
    def __init__(self, records):
        self.records = records
        self.by_batch = collections.defaultdict(lambda: collections.defaultdict(list))
        for r in records:
            if r.batch is not None:
                self.by_batch[r.batch][r.name].append(r)
        self.consumed = sorted(i for i, names in self.by_batch.items() if "train.step" in names)

    def per_batch(self, *names) -> list[float] | None:
        """For each consumed batch, the milliseconds of its spans of these
        names; None unless every consumed batch has one of them."""
        out = []
        for i in self.consumed:
            spans = [r for n in names for r in self.by_batch[i][n]]
            if not spans:
                return None
            out.append(sum(r.t1 - r.t0 for r in spans) * 1e-6)
        return out

    def mean_ms(self, *names) -> float | None:
        ms = self.per_batch(*names)
        return statistics.fmean(ms) if ms else None

    def first(self, i: int, name: str):
        spans = self.by_batch[i][name]
        return spans[0] if spans else None


def window(r: dict) -> Window | None:
    if r.get("kind") != "train":
        return None
    try:
        from repro import obs
    except ImportError:  # a program that records no spans
        return None
    w = Window(obs.snapshot())
    return w if w.consumed else None

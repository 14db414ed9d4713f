"""Per batch the traced training window consumed, the producer's sample
requests: its ``sample.submit`` and ``sample.wait`` spans (the wait drives
the sampling service's rounds), from the program's span recorder."""
from spans import window


def read(r: dict):
    w = window(r)
    return w.mean_ms("sample.submit", "sample.wait") if w else None

"""Spans and counters of the program, recorded while JAX's profiler traces.

``span(name, batch)`` times a region and ``count(name, value, batch)`` notes
a number.  Both land, as :class:`Record`, in one in-memory list per process,
on ``time.perf_counter_ns()`` (CLOCK_MONOTONIC on Linux, which a forked
child shares with its parent).  Records of one training batch carry its
request index, the ``i`` of the pipeline's sample key ``(seed, i)``, which
links the batch producer's records to the trainer's.

Nothing is recorded unless the profiler is tracing (``jax.profiler.trace``
or ``start_trace``); otherwise a span costs one check and records nothing.  In
the process that holds the chip each span also enters a
``jax.profiler.TraceAnnotation`` of its name, so it appears in the device
trace's host plane as well: one span present on both clocks gives the offset
from this clock to the trace's for every record.  A forked batch producer
does not hold the chip; it records on the flag its command carries
(:func:`forked`) and ships its records to the trainer (:func:`take`,
:func:`extend`).

:func:`snapshot` returns the records of the newest trace.
"""
from __future__ import annotations

import os
import threading
import time
from typing import NamedTuple

import jax
from jax._src import profiler as _jax_profiler
from jax._src.lib import _profiler

__all__ = [
    "Record", "count", "extend", "forked", "reset", "snapshot", "span", "step",
    "take", "tracing",
]


class Record(NamedTuple):
    name: str
    batch: int | None  # request index of the batch, where one batch owns it
    parent: str | None  # the span open around it in the same thread
    t0: int  # perf_counter_ns; a counter has t0 == t1
    t1: int
    pid: int
    value: float | None = None  # a counter's value
    attrs: dict | None = None  # a span's attributes


class _Recorder:
    def __init__(self):
        self.records: list[Record] = []
        self.on = _profiler.TraceMe.is_enabled  # whether to record now
        self.annotate = True  # this process holds the chip
        self.session = None  # the profiler session the records belong to
        self.local = threading.local()

    def add(self, rec: Record) -> None:
        if self.annotate:
            # a new trace starts a new list: snapshot() is one trace's records
            session = _jax_profiler._profile_state.profile_session
            if session is not None and session is not self.session:
                self.session = session
                self.records = []
        self.records.append(rec)

    def stack(self) -> list:
        st = getattr(self.local, "stack", None)
        if st is None:
            st = self.local.stack = []
        return st


_REC = _Recorder()


class _Span:
    __slots__ = ("name", "batch", "attrs", "parent", "t0", "annotation", "stack")

    def __init__(self, name, batch, attrs):
        self.name, self.batch, self.attrs = name, batch, attrs

    def __enter__(self):
        # a span open across a generator's yield may be closed by another
        # thread: it leaves the stack of the thread that opened it
        st = self.stack = _REC.stack()
        self.parent = st[-1] if st else None
        st.append(self.name)
        self.annotation = None
        if _REC.annotate:
            meta = self.attrs if self.batch is None else dict(self.attrs, batch=self.batch)
            self.annotation = jax.profiler.TraceAnnotation(self.name, **meta)
            self.annotation.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        # the clock is read next to the annotation's own reads, on both ends
        if self.annotation is not None:
            self.annotation.__exit__(*exc)
        t1 = time.perf_counter_ns()
        self.stack.pop()
        _REC.add(Record(self.name, self.batch, self.parent, self.t0, t1, os.getpid(),
                        attrs=self.attrs or None))
        return False


class _Off:
    """What ``span`` returns while nothing records.  ``batch`` may be set on
    it as on a span; nothing reads it."""

    __slots__ = ("batch",)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_OFF = _Off()


def tracing() -> bool:
    """Whether spans and counters are being recorded."""
    return _REC.on()


def span(name: str, batch: int | None = None, **attrs):
    """A context manager that records ``name`` from enter to exit.  Its
    ``batch`` may be set inside the block, where it is known only there."""
    if not _REC.on():
        return _OFF
    return _Span(name, batch, attrs)


def step(name: str, step_num: int):
    """``jax.profiler.StepTraceAnnotation`` while recording in the process
    that holds the chip; else a context manager that does nothing."""
    if not (_REC.annotate and _REC.on()):
        return _OFF
    return jax.profiler.StepTraceAnnotation(name, step_num=step_num)


def count(name: str, value: float, batch: int | None = None) -> None:
    """Record a counter's ``value`` now."""
    if not _REC.on():
        return
    st = _REC.stack()
    t = time.perf_counter_ns()
    _REC.add(Record(name, batch, st[-1] if st else None, t, t, os.getpid(), value=float(value)))


def snapshot() -> list[Record]:
    """The records of the newest trace (records shipped in included)."""
    return list(_REC.records)


def reset() -> None:
    _REC.records = []


def take() -> list[Record]:
    """The records so far, which this process then forgets: a forked
    producer ships them to the trainer."""
    recs, _REC.records = _REC.records, []
    return recs


def extend(records: list[Record]) -> None:
    """Add records another process made (a forked producer's)."""
    for rec in records:
        _REC.add(rec)


def forked(on: bool) -> None:
    """In a forked batch producer, which does not hold the chip: record
    while ``on`` (the trainer's :func:`tracing` when it sent the command),
    enter no profiler annotation, and forget the records the fork copied."""
    _REC.on = (lambda: True) if on else (lambda: False)
    _REC.annotate = False
    _REC.records = []
    _REC.local = threading.local()  # spans the forking thread had open

"""The fused batch pipeline: seed loading -> K-hop sampling -> padded batch.

``BatchPipeline`` composes ``SeedBatchLoader`` + the sampling service +
``subgraph_to_batch`` behind one iterator, with two *independent* overlap
axes:

``prefetch >= 1`` — the host-side producer (sampling + padding) runs ahead
    of the jit'd device step in a forked worker or thread, so the two
    overlap: ``sample_time + compute_time`` per step becomes roughly
    ``max(sample_time, compute_time)``.
``inflight >= 2`` — the producer keeps that many sample *requests* in
    flight on the ``SamplingService`` at once (a submission window), so the
    service's scheduler advances batch k's hop-2 beside batch k+1's hop-1,
    coalescing shared frontier seeds across the window.  Requests carry
    pipeline-owned keys ``(seed, batch_index)``, so the batch stream is
    bit-identical for ANY window depth and even when several pipelines
    share one service.

Two worker modes:

``process`` (default on POSIX) — a persistent forked worker owns the
    sampling state and streams batches through a bounded queue.  CPython's
    GIL makes a *thread* producer serialize against the consumer's Python
    sections (numpy only releases the GIL for a handful of ops), so a
    separate process is the only way host sampling truly runs beside XLA
    compute — the same reason DGL/PyTorch dataloaders use worker processes.
    The batch's arrays do not cross the queue's pipe: the worker builds
    each batch straight into one of ``prefetch + 2`` shared-memory slots
    (anonymous ``memfd`` files made before the fork; what the queue holds,
    the one the consumer holds, the one being filled), gathering the
    feature rows into it with no private table and no copy, and queues
    only a small message, from which the consumer rebuilds the arrays as
    views of the slot.  A slot grows to the largest batch it has carried,
    so the slots cost ``prefetch + 2`` batches of host memory.  The
    consumer returns a slot when it asks for the next batch, once the copy
    to the device has read it; on the CPU backend, where ``jnp.asarray``
    may alias host memory, the staged arrays are copied out of the slot
    first.  Where no slot can be had (no ``memfd``, or a slot cannot grow)
    the batch is built in fresh arrays and goes pickled through the queue
    instead, as in thread mode and with ``prefetch=0``.
``thread`` — in-process double buffering via a daemon thread.  Zero-copy
    hand-off, but overlap is limited to the consumer's GIL-released windows.

Determinism: one persistent producer (process or thread) runs exactly the
serial code path on the same initial state, and sampling randomness is keyed
per request, so the batch stream is bit-identical to ``prefetch=0`` AND to
any ``inflight`` depth (tested in tests/test_api.py and tests/test_service.py).
Note that in process mode the sampling-server stats live in the worker, so
read workload counters with ``prefetch=0`` pipelines.

Tracing: while JAX's profiler traces, each batch's trip is recorded in
``repro.obs`` under its request index: ``sample.submit``, ``sample.wait``,
``batch.build``, ``batch.bytes`` and ``batch.put`` in the producer (the
forked worker records on the flag of its ``produce`` command and ships its
records in the batches' messages; between its ``sample.wait`` and its
``batch.build`` it records ``batch.slot``, the wait for a free slot, which
is no work and is kept off ``sample_time``; its ``batch.put`` is the queue
put of the batch's message, and its ``batch.shm_bytes`` the bytes built in
a slot, 0 for a pickled batch), ``pipeline.recv`` and ``pipeline.h2d`` in
the consumer.  ``prefetch=0`` has no queue, so no put or recv.
"""
from __future__ import annotations

import collections
import logging
import math
import mmap
import multiprocessing as mp
import os
import queue as queue_mod
import time
import traceback
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.sampling.service import DEFAULT_DIRECTION, SamplingSpec
from repro.core.storage import as_feature_source
from repro.data.graph_loader import SeedBatchLoader
from repro.models.gnn.batching import GNNBatch, subgraph_to_batch
from repro.utils import prefetch_iterator

__all__ = ["BatchPipeline"]

_log = logging.getLogger(__name__)

_FORK_AVAILABLE = os.name == "posix" and "fork" in mp.get_all_start_methods()

_KEY_MASK = (1 << 64) - 1


def _stage(batch: GNNBatch, i: int) -> GNNBatch:
    """The batch's arrays as jax arrays (span ``pipeline.h2d``).  The copy
    to the device is asynchronous, so the span is the host-side staging
    only, not the transfer."""
    with obs.span("pipeline.h2d", i):
        return jax.tree.map(jnp.asarray, batch)


_ALIGN = 64  # byte alignment of each array in a slot


class _Slot(NamedTuple):
    """What the worker sends for a batch placed in a slot: enough to
    rebuild its arrays as views of the slot's memory."""

    slot: int
    size: int  # the slot's size when it was filled
    tree: object  # the batch's PyTreeDef (the layer lists)
    leaves: list  # (offset, shape, dtype) per array leaf


class _Cancelled(Exception):
    """The worker's run was cancelled while it waited for a free slot."""


class _SlotRing:
    """Shared-memory slots that the forked worker builds its batches in and
    that carry them to the trainer, so that only a small message crosses
    the queue's pipe.

    Each slot is an anonymous memory file (``memfd``) made before the fork:
    the worker inherits its descriptor, and no name exists that could be
    left behind or that ``resource_tracker`` would register.  The memory is
    freed once both processes have closed the descriptor and unmapped it.
    For each batch the worker claims a free slot (:meth:`claim`), hands the
    build the slot's memory as arrays of the batch's layout (:meth:`place`),
    regrowing the slot (``ftruncate``) when the batch is larger than it, so
    slot sizes follow the observed batch size, and sends the message
    :meth:`handoff` gives.  A slot stays mapped in each process while its
    size holds, so its pages are faulted once, not per batch.  Free slot
    ids flow back to the worker through a pipe; the worker starts holding
    them all.  Where no ``memfd`` exists, or a slot cannot grow, the batch
    is built in fresh arrays and goes through the pickled queue pipe.
    """

    def __init__(self, n: int, ctx):
        try:
            self.fds = [os.memfd_create("glisp-batch") for _ in range(n)]
        except (AttributeError, OSError) as exc:  # no memfd on this platform
            _log.debug("no shared-memory batch slots: %s", exc)
            self.fds = None
        self.maps: list = [None] * n  # this process's mapping of each slot
        self._free_r, self._free_w = ctx.Pipe(duplex=False)
        self._idle = collections.deque(range(n))  # worker side: free slot ids
        self._held = None  # worker side: the slot claimed for the next batch
        self._placed = None  # worker side: its _Slot, once the build has it

    def _map(self, slot: int, size: int) -> mmap.mmap:
        mm = self.maps[slot]
        if mm is None or len(mm) < size:
            # a mapping a consumer's views still use is unmapped by their GC
            self.maps[slot] = mm = mmap.mmap(self.fds[slot], size)
        return mm

    def claim(self, cancel) -> None:
        """Worker side: wait for a free slot and hold it for the next
        batch.  Raises :class:`_Cancelled` where the run is cancelled
        first."""
        if self._idle:
            self._held = self._idle.popleft()
            return
        while not self._free_r.poll(0.05):
            if cancel.is_set():
                raise _Cancelled
        self._held = self._free_r.recv()

    def place(self, layout: GNNBatch) -> GNNBatch | None:
        """Worker side: the held slot's memory as arrays of ``layout``'s
        shapes and dtypes (a :class:`GNNBatch` of ``jax.ShapeDtypeStruct``),
        for the batch to be built in; None where the slot cannot grow to
        fit, and the batch is then built in fresh arrays."""
        leaves, tree = jax.tree.flatten(layout)
        metas, end = [], 0
        for x in leaves:
            off = -(-end // _ALIGN) * _ALIGN
            metas.append((off, x.shape, x.dtype))
            end = off + math.prod(x.shape) * x.dtype.itemsize
        slot = self._held
        size = -(-max(end, 1) // mmap.PAGESIZE) * mmap.PAGESIZE
        try:
            if self.maps[slot] is None or len(self.maps[slot]) < size:
                os.ftruncate(self.fds[slot], size)
            mm = self._map(slot, size)
        except OSError as exc:  # the slot cannot grow: send it pickled
            _log.warning("batch of %d bytes goes through the pickled queue: %s", end, exc)
            return None
        self._placed = _Slot(slot, len(mm), tree, metas)
        return jax.tree.unflatten(tree, [np.ndarray(s, d, mm, off) for off, s, d in metas])

    def handoff(self) -> _Slot | None:
        """Worker side: the message for the batch built in the held slot,
        or None where it was built elsewhere (the slot is then free
        again).  The slot is no longer held."""
        slot, msg = self._held, self._placed
        self._held = self._placed = None
        if msg is None and slot is not None:
            self._idle.append(slot)
        return msg

    def unpack(self, msg: _Slot) -> GNNBatch:
        """Trainer side: the batch's arrays as numpy views of its slot."""
        mm = self._map(msg.slot, msg.size)
        leaves = [np.ndarray(shape, dtype, mm, off) for off, shape, dtype in msg.leaves]
        return jax.tree.unflatten(msg.tree, leaves)

    def release(self, slot: int) -> None:
        """Trainer side: the slot may be filled again."""
        if self.fds is not None:
            self._free_w.send(slot)

    def close(self) -> None:
        fds, self.fds = self.fds, None
        self._free_r.close()
        self._free_w.close()
        for mm in self.maps:
            if mm is not None:
                try:
                    mm.close()
                except BufferError:  # views still alive: unmapped by their GC
                    pass
        for fd in fds or ():
            os.close(fd)


def _release(held) -> None:
    """Return a yielded batch's slot to the worker once the batch's copy
    from the slot has finished (in the trainer, ``float(loss)`` has already
    waited for the step that read it)."""
    if held is not None:
        ring, slot, staged = held
        jax.block_until_ready(staged)
        ring.release(slot)


def _host_aliased(staged: GNNBatch) -> bool:
    """Whether ``jnp.asarray`` may have kept a view of the host arrays: on
    the CPU backend it aliases suitably aligned numpy memory."""
    leaf = jax.tree.leaves(staged)[0]
    return any(d.platform == "cpu" for d in leaf.devices())


class BatchPipeline:
    def __init__(
        self,
        backend,
        graph,
        seeds: np.ndarray,
        fanouts,
        num_layers: int,
        *,
        batch_size: int = 256,
        spec: SamplingSpec | None = None,
        weighted: bool = False,
        direction: str = DEFAULT_DIRECTION,
        prefetch: int = 2,
        inflight: int = 1,
        workers: str = "auto",  # auto | process | thread
        seed: int = 0,
        partition_of: np.ndarray | None = None,
        balance_partitions: bool = False,
        vertex_quantum: int = 256,
        edge_quantum: int = 1024,
        feature_source=None,  # FeatureSource; None = graph.vertex_feats
        ticket_timeout: float | None = None,
        worker_respawns: int = 1,
        vertex_types: bool = False,
    ):
        """``vertex_types``: each batch carries its rows' vertex types
        (``GNNBatch.vtype``), for a model that reads them.

        ``ticket_timeout`` bounds every blocking ``ticket.result()``
        wait (None = wait forever, explicitly).  ``worker_respawns`` is the
        crash budget for the forked prefetch worker: a worker found dead
        mid-run is respawned up to this many times, replaying the keyed
        seed stream past the batches already delivered — the resumed
        stream is bit-identical by construction (see ``_respawn_worker``).
        ``worker_respawns=0`` restores the old fail-fast behavior."""
        if workers not in ("auto", "process", "thread"):
            raise ValueError(
                f"workers must be 'auto', 'process' or 'thread', got {workers!r}"
            )
        if inflight < 1:
            raise ValueError(f"inflight must be >= 1, got {inflight}")
        if worker_respawns < 0:
            raise ValueError(
                f"worker_respawns must be >= 0, got {worker_respawns}"
            )
        self.backend = backend
        # accept a SamplerBackend or a raw GatherApply/EdgeCut client; the
        # async submission window needs `submit` (the service surface)
        self._sample = getattr(backend, "sample", None) or backend.sample_khop
        self._submit = getattr(backend, "submit", None)
        self.graph = graph
        self.spec = (
            spec
            if spec is not None
            else SamplingSpec(
                fanouts=tuple(fanouts), weighted=weighted, direction=direction
            )
        ).validate()
        self.fanouts = list(self.spec.fanouts)
        self.num_layers = num_layers
        self.weighted = self.spec.weighted
        self.direction = self.spec.direction
        if self.spec.replace and self._submit is None:
            raise ValueError(
                "replace-policy sampling needs a SamplingService backend "
                "(raw clients only support without-replacement draws)"
            )
        self.prefetch = prefetch
        self.inflight = inflight
        # a remote-dispatching service (dist_transport != "inproc") cannot
        # sit behind a forked prefetch producer: the fork would duplicate
        # the worker-pool channel fds, and parent + child reading the same
        # pipes interleaves partial frames.  Thread-mode prefetch keeps the
        # pool's fds in one process (the remote workers provide the real
        # parallelism anyway).
        service = getattr(backend, "service", None)
        remote = service is not None and getattr(service, "dispatcher", None) is not None
        if remote and workers == "process":
            raise ValueError(
                "workers='process' cannot wrap a remote-dispatch sampling "
                "service (forked producer would share the worker-pool "
                "channels); use workers='thread' or dist_transport='inproc'"
            )
        self.workers = (
            (("thread" if remote else "process") if _FORK_AVAILABLE else "thread")
            if workers == "auto"
            else workers
        )
        self.vertex_quantum = vertex_quantum
        self.edge_quantum = edge_quantum
        self.vertex_types = vertex_types
        # the training-side feature path: any FeatureSource (e.g. a
        # disk-backed HybridCache) — batches are bit-identical to the
        # in-memory matrix because the cache only changes where rows live
        self.feature_source = as_feature_source(
            graph.vertex_feats if feature_source is None else feature_source
        )
        self.loader = SeedBatchLoader(
            seeds,
            batch_size,
            seed=seed,
            partition_of=partition_of,
            balance_partitions=balance_partitions,
        )
        self.sample_time = 0.0  # producer-side host time (sampling + padding)
        self.ticket_timeout = ticket_timeout
        self.worker_respawns = int(worker_respawns)
        self.respawn_count = 0  # workers respawned over this pipeline's life
        self._respawns_left = self.worker_respawns
        # request keys are pipeline-owned: (loader seed, running index), so
        # the stream is independent of the service's other consumers
        self._key_base = int(seed) & _KEY_MASK
        self._req_counter = 0
        self._pending = collections.deque()  # (seeds, SampleTicket, index) in order
        self._proc = None
        self._cmd_q = None
        self._data_q = None
        self._cancel = None  # mp.Event: stop the worker's current run early
        self._ring = None  # _SlotRing: the worker's batches, in shared memory
        self._slots = None  # the ring, in the forked worker that builds in it
        self._run_history: list[int] = []  # epochs of fully produced runs

    # ------------------------------------------------------------------
    def _next_key(self) -> tuple:
        key = (self._key_base, self._req_counter)
        self._req_counter += 1
        return key

    def _submit_ahead(self, seeds: np.ndarray) -> int:
        key = self._next_key()
        with obs.span("sample.submit", key[1]):
            ticket = self._submit(seeds, self.spec, key=key)
        self._pending.append((seeds, ticket, key[1]))
        return key[1]

    def _take_sample(self, seeds: np.ndarray):
        """``(request index, subgraph)`` for one seed batch: the
        pre-submitted in-flight ticket when the look-ahead window holds one,
        else a fresh request.  Keys are assigned in batch order either way,
        so windowed and unwindowed streams are bit-identical."""
        if self._pending and np.array_equal(self._pending[0][0], seeds):
            _, ticket, i = self._pending.popleft()
        elif self._submit is not None:
            key = self._next_key()
            i = key[1]
            with obs.span("sample.submit", i):
                ticket = self._submit(seeds, self.spec, key=key)
        else:  # a raw client: keys only number its batches
            i = self._next_key()[1]
            with obs.span("sample.wait", i):
                return i, self._sample(
                    seeds, self.fanouts, weighted=self.weighted, direction=self.direction
                )
        with obs.span("sample.wait", i):
            return i, ticket.result(timeout=self.ticket_timeout)

    def make_batch(self, seeds: np.ndarray) -> GNNBatch:
        """One seed batch through sampling + padding (numpy, no prefetch).
        In the forked worker the batch is built in a free shared-memory
        slot, claimed once the sample is in (span ``batch.slot``)."""
        i, sub = self._take_sample(seeds)
        into = None
        if self._slots is not None:
            t0 = time.perf_counter()
            with obs.span("batch.slot", i):
                self._slots.claim(self._cancel)
            # a wait, not work: off the producer clock _produce_np runs
            self.sample_time -= time.perf_counter() - t0
            into = self._slots.place
        with obs.span("batch.build", i):
            return subgraph_to_batch(
                sub,
                self.feature_source,
                self.graph.labels,
                self.num_layers,
                edge_types=self.graph.edge_types,
                vertex_quantum=self.vertex_quantum,
                edge_quantum=self.edge_quantum,
                vertex_types=self.graph.vertex_types if self.vertex_types else None,
                index=i,
                into=into,
            )

    def _seed_stream(self, epochs: int):
        for _ in range(epochs):
            for seeds in self.loader.epoch():
                if self._cancel is not None and self._cancel.is_set():
                    return
                yield seeds

    def _drop_pending(self) -> None:
        """Cancel in-flight window tickets so abandoned requests stop
        consuming scheduler rounds and skewing workload counters."""
        while self._pending:
            self._pending.popleft()[1].cancel()

    def _forward_run(self, epochs: int) -> None:
        """Replay one completed run WITHOUT sampling: consume the seed
        stream (advancing the loader's per-epoch permutation RNG) and burn
        one request key per batch, leaving the producer state exactly
        where a real run would have left it.  Used by a respawned worker
        to fast-forward to the crashed run."""
        for _ in self._seed_stream(epochs):
            self._next_key()

    def _produce_np(self, epochs: int, skip: int = 0):
        """The serial producer: pure numpy, safe inside the forked worker.
        With ``inflight >= 2`` and a service backend it keeps a window of
        sample requests in flight ahead of the batch being padded.
        ``skip`` fast-forwards past the first ``skip`` batches (already
        delivered before a worker crash) without sampling them — stream
        positions and request keys are consumed so batch ``i`` keeps key
        ``(seed, i)`` and the remainder is bit-identical.

        The bit-identity contract (any prefetch/inflight depth, shared or
        private service) applies to runs driven to completion: abandoning a
        run mid-epoch leaves the seed loader — and, pre-dating this PR, any
        prefetch look-ahead — at an implementation-defined position, so a
        SUBSEQUENT run on the same pipeline resumes from wherever the
        producer stopped."""
        self._drop_pending()  # stale tickets from an abandoned run
        stream = self._seed_stream(epochs)
        for _ in range(skip):
            if next(stream, None) is None:
                break
            self._next_key()
        windowed = self.inflight > 1 and self._submit is not None
        # bounded by construction: the refill loop below never grows it past
        # self.inflight (validated positive), so no maxlen is needed
        queue: collections.deque = collections.deque()  # glint: disable=PRJ005 -- see above
        try:
            while True:
                if windowed:
                    while len(queue) < self.inflight:
                        nxt = next(stream, None)
                        if nxt is None:
                            break
                        t0 = time.perf_counter()
                        i = self._submit_ahead(nxt)
                        self.sample_time += time.perf_counter() - t0
                        queue.append((i, nxt))
                    if not queue:
                        return
                    i, seeds = queue.popleft()
                else:
                    seeds = next(stream, None)
                    if seeds is None:
                        return
                    i = self._req_counter  # the key make_batch takes next
                t0 = time.perf_counter()
                batch = self.make_batch(seeds)
                self.sample_time += time.perf_counter() - t0
                if obs.tracing():
                    obs.count("batch.bytes", sum(x.nbytes for x in jax.tree.leaves(batch)), i)
                yield i, seeds, batch
        except _Cancelled:  # stopped while the batch waited for a slot
            return
        finally:
            self._drop_pending()

    def _produce(self, epochs: int):
        for i, seeds, batch in self._produce_np(epochs):
            # host->device staging rides with the producer so the consumer's
            # step loop is nothing but dispatch + block
            yield i, seeds, _stage(batch, i)

    def _thread_batches(self, epochs: int):
        def produce():
            for i, seeds, batch in self._produce(epochs):
                # prefetch_iterator's thread resumes this generator once
                # its bounded put has returned
                with obs.span("batch.put", i):
                    yield i, seeds, batch

        # prefetch_iterator stops and joins its producer when the generator
        # is closed/abandoned, so the shared loader/backend state is never
        # mutated concurrently with a later epoch
        items = prefetch_iterator(produce(), self.prefetch)
        try:
            while True:
                with obs.span("pipeline.recv") as recv:
                    item = next(items, None)
                    if item is not None:
                        recv.batch = item[0]
                if item is None:
                    return
                yield item
        finally:
            items.close()

    def indexed(self, epochs: int = 1):
        """Yield ``(request index, seeds, GNNBatch)`` with arrays staged as
        jax arrays; sampling runs ahead of the consumer when
        ``prefetch >= 1``.  The index is the ``i`` of the batch's sample key
        ``(seed, i)``, which the spans of its trip carry (``repro.obs``)."""
        if self.prefetch <= 0:
            return self._produce(epochs)
        if self.workers == "process" and _FORK_AVAILABLE:
            return self._process_batches(epochs)
        return self._thread_batches(epochs)

    def batches(self, epochs: int = 1):
        """Yield ``(seeds, GNNBatch)``: :meth:`indexed` without the index."""
        return ((seeds, batch) for _, seeds, batch in self.indexed(epochs))

    def __iter__(self):
        return self.batches(1)

    # -- process-mode plumbing -----------------------------------------
    def _worker_loop(self):  # runs in the forked child: numpy only, no XLA
        if self._ring.fds is not None:
            self._slots = self._ring  # make_batch builds in its slots
        while True:
            # glint: disable=PRJ004 -- SimpleQueue has no timeout kwarg; an
            # idle worker is stopped via close(), which escalates to kill()
            cmd = self._cmd_q.get()
            if cmd[0] == "stop":
                return
            if cmd[0] == "forward":
                # replay a prior completed run without sampling (respawn
                # fast-forward); ack so the parent can sequence commands
                self._forward_run(cmd[1])
                self._data_q.put(("fwd",))
                continue
            _, epochs, skip, tracing = cmd
            obs.forked(tracing)
            try:
                for i, seeds, batch in self._produce_np(epochs, skip=skip):
                    msg = self._ring.handoff()  # None: built in fresh arrays
                    if msg is None:
                        payload, placed = batch, 0
                    else:
                        payload, placed = msg, sum(x.nbytes for x in jax.tree.leaves(batch))
                    obs.count("batch.shm_bytes", placed, i)
                    # the message carries what was recorded before this put;
                    # the put's own span rides with the next one
                    with obs.span("batch.put", i):
                        self._data_q.put(("item", i, seeds, payload, obs.take()))
                self._data_q.put(("done", self.sample_time, obs.take()))
            except BaseException as exc:  # noqa: BLE001 - re-raised in parent
                self._data_q.put(
                    ("error", f"{type(exc).__name__}: {exc}\n{traceback.format_exc()}")
                )

    def _ensure_worker(self):
        if self._proc is not None and self._proc.is_alive():
            return
        ctx = mp.get_context("fork")
        self._cmd_q = ctx.SimpleQueue()
        self._data_q = ctx.Queue(maxsize=max(1, self.prefetch))
        self._cancel = ctx.Event()
        if self._ring is not None:  # a dead worker's slots
            self._ring.close()
        # what the queue holds, one slot the consumer holds, one being filled
        self._ring = _SlotRing(max(1, self.prefetch) + 2, ctx)
        with warnings.catch_warnings():
            # jax warns that fork + threads can deadlock; the child touches
            # only numpy state, never XLA, which is the supported pattern
            warnings.simplefilter("ignore", RuntimeWarning)
            self._proc = ctx.Process(target=self._worker_loop, daemon=True)
            self._proc.start()

    def _next_msg(self):
        """Queue read that notices a dead worker instead of hanging."""
        while True:
            try:
                return self._data_q.get(timeout=1.0)
            except queue_mod.Empty:
                if self._proc is None or not self._proc.is_alive():
                    code = self._proc.exitcode if self._proc is not None else None
                    self.close()
                    raise RuntimeError(
                        f"prefetch worker died (exit code {code}) without "
                        "reporting an error — likely killed (OOM?) or crashed "
                        "in native code"
                    )

    def _respawn_worker(self, code, epochs: int, delivered: int) -> None:
        """Fork a fresh worker and fast-forward it to the crashed run.

        The fresh child forks from THIS process's pristine producer state
        (the parent never advances the loader/key state in process mode),
        so it replays every previously completed run via cheap ``forward``
        commands, then re-enters the crashed run skipping the ``delivered``
        batches already yielded.  Because sampling randomness is keyed
        ``(seed, batch_index)`` and the skip path consumes exactly the
        stream positions and keys a real run would, the resumed stream is
        bit-identical to an uncrashed one by construction."""
        self._respawns_left -= 1
        self.respawn_count += 1
        _log.warning(
            "prefetch worker died (exit code %s); respawning (%d left in "
            "crash budget) and replaying %d delivered batch(es)",
            code,
            self._respawns_left,
            delivered,
        )
        self._proc = None  # force a fresh fork (with fresh, empty queues)
        self._ensure_worker()
        self._cancel.clear()
        for past_epochs in self._run_history:
            self._cmd_q.put(("forward", past_epochs))
            try:
                msg = self._data_q.get(timeout=60.0)
            except queue_mod.Empty:
                msg = None
            if msg is None or msg[0] != "fwd":
                self.close()
                raise RuntimeError(
                    "respawned prefetch worker failed to replay run history"
                )
        self._cmd_q.put(("produce", epochs, delivered, obs.tracing()))

    def _read_or_respawn(self, epochs: int, delivered: int):
        """Queue read; a dead worker is respawned (crash budget permitting)
        and told to resume past the batches already delivered."""
        while True:
            try:
                return self._data_q.get(timeout=1.0)
            except queue_mod.Empty:
                if self._proc is not None and self._proc.is_alive():
                    continue
                code = self._proc.exitcode if self._proc is not None else None
                if self._respawns_left <= 0:
                    self.close()
                    raise RuntimeError(
                        f"prefetch worker died (exit code {code}) without "
                        "reporting an error — likely killed (OOM?) or crashed "
                        "in native code"
                        + (
                            f" — crash budget of {self.worker_respawns} "
                            "respawn(s) exhausted"
                            if self.worker_respawns
                            else ""
                        )
                    )
                self._respawn_worker(code, epochs, delivered)

    def _process_batches(self, epochs: int):
        self._ensure_worker()
        self._cancel.clear()
        # the child records while this process traces, and ships its records
        self._cmd_q.put(("produce", epochs, 0, obs.tracing()))
        delivered = 0
        finished = False
        held = None  # (ring, slot, staged batch) of the item last yielded
        try:
            while True:
                with obs.span("pipeline.recv") as recv:
                    msg = self._read_or_respawn(epochs, delivered)
                    if msg[0] == "item":
                        recv.batch = msg[1]
                        ring, payload = self._ring, msg[3]
                        batch = ring.unpack(payload) if isinstance(payload, _Slot) else payload
                if msg[0] == "done":
                    finished = True
                    self.sample_time = msg[1]  # worker's cumulative clock
                    obs.extend(msg[2])
                    self._run_history.append(epochs)
                    return
                if msg[0] == "error":
                    finished = True
                    self.close()
                    raise RuntimeError(f"prefetch worker failed:\n{msg[1]}")
                _, i, seeds, _, records = msg
                obs.extend(records)
                delivered += 1
                staged = _stage(batch, i)
                if isinstance(payload, _Slot):
                    if _host_aliased(staged):
                        staged = jax.tree.map(jnp.array, staged)  # own the memory
                    held = (ring, payload.slot, staged)
                del batch  # views of the slot
                yield i, seeds, staged
                _release(held)  # the consumer asks for the next item
                held = None
        finally:
            _release(held)
            if not finished and self._proc is not None:
                # consumer stopped early (e.g. max_steps): cancel the run
                # and drain the few in-flight items so the worker is idle
                # (not sampling concurrently) before the next command
                self._cancel.set()
                while True:
                    try:
                        msg = self._next_msg()
                    except RuntimeError:
                        # worker died mid-drain: the run was already being
                        # abandoned, nothing left to recover
                        break
                    if msg[0] in ("item", "done"):
                        obs.extend(msg[-1])
                    if msg[0] == "item" and isinstance(msg[3], _Slot):
                        self._ring.release(msg[3].slot)
                    if msg[0] == "done":
                        self.sample_time = msg[1]
                        # an abandoned run still advanced the worker's
                        # loader/key state; record it so a later respawn
                        # replays it (bit-identity is only contracted for
                        # runs driven to completion — see _produce_np)
                        self._run_history.append(epochs)
                        break
                    if msg[0] == "error":
                        self.close()
                        raise RuntimeError(
                            f"prefetch worker failed:\n{msg[1]}"
                        )

    def close(self, timeout: float = 2.0) -> None:
        """Stop the worker process (no-op for thread/serial modes).

        Bounded: a graceful ``stop`` + join escalates to ``terminate()``
        (SIGTERM) and finally ``kill()`` (SIGKILL), so close() returns even
        when the worker is wedged in native code or ignoring SIGTERM."""
        proc, self._proc = self._proc, None
        if proc is not None and proc.is_alive():
            try:
                self._cmd_q.put(("stop",))
                proc.join(timeout=timeout)
            except (OSError, ValueError) as exc:
                # command queue already torn down (closed pipe / released
                # semaphore); fall through to terminate() below
                _log.debug("graceful worker stop failed: %s", exc)
            if proc.is_alive():
                proc.terminate()
                proc.join(timeout=timeout)
            if proc.is_alive():
                proc.kill()
                proc.join(timeout=timeout)
        ring, self._ring = self._ring, None
        if ring is not None:
            ring.close()

    def __del__(self):  # best effort; daemon children die with the parent
        try:
            self.close()
        except Exception:
            pass

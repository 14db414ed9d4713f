"""The process-mode batch pipeline's shared-memory slots, which the worker
builds each batch in: the batch stream stays bit-identical to
``prefetch=0`` while the consumer holds every batch across several wraps of
the ring, while slots regrow, where a smaller batch is built over a larger
one's leftovers, with the rows' vertex types, through an early stop and a
second run, through a worker killed and respawned, and where batches fall
back to the pickled queue; ``close()`` leaves no slot behind."""
import errno
import gc
import multiprocessing as mp
import os
import time

import jax
import numpy as np
import pytest

from repro import obs
from repro.api import pipeline as pipeline_mod
from repro.api.pipeline import BatchPipeline

pytestmark = pytest.mark.skipif(
    not (os.name == "posix" and "fork" in mp.get_all_start_methods() and hasattr(os, "memfd_create")),
    reason="the slot ring needs fork and memfd",
)

PREFETCH = 1  # three slots; 15 batches a run wrap the ring five times


@pytest.fixture(autouse=True)
def _fresh():
    obs.reset()
    yield
    obs.reset()


def _pipeline(graph, partitioned, prefetch, **kw):
    from repro.core.sampling import SamplingServer, VertexRouter
    from repro.core.sampling.service import GatherApplyRouting, SamplingService

    ep, parts = partitioned
    service = SamplingService(
        [SamplingServer(p, seed=0) for p in parts],
        GatherApplyRouting(VertexRouter(graph, ep, 4)),
        seed=0,
    )
    return BatchPipeline(
        service, graph, np.arange(500), [4, 4], 2, batch_size=32, prefetch=prefetch,
        inflight=2, workers="process", seed=3, **kw,
    )


def _segments() -> set:
    """Inodes of the batch slots this process holds open or mapped."""
    found = set()
    for fd in sorted(os.listdir("/proc/self/fd")):
        path = f"/proc/self/fd/{fd}"
        try:
            if os.readlink(path).startswith("/memfd:glisp-batch"):
                found.add(os.stat(path).st_ino)
        except OSError:  # the listing's own descriptor
            continue
    with open("/proc/self/maps") as f:
        found |= {int(line.split()[4]) for line in f if "/memfd:glisp-batch" in line}
    return found


def _drive(pl, case):
    """The runs of ``case`` as lists of ``(i, seeds, batch)``, every yielded
    batch kept as it was yielded."""
    if case == "early_stop":  # train(max_steps=4), then a whole second call
        it = pl.indexed(1)
        first = [next(it) for _ in range(4)]
        it.close()
        return [first, list(pl.indexed(1))]
    run = []
    for item in pl.indexed(1):
        run.append(item)
        if case == "respawn" and len(run) == 3 and pl._proc is not None:
            pl._proc.kill()  # an OOM-killed worker, mid-run
            time.sleep(0.2)
    return [run]


def _assert_padding(batch):
    """The padding a build writes over whatever its memory held."""
    n = int(batch.valid.sum())
    assert batch.valid[:n].all() and not batch.valid[n:].any()
    assert not batch.feats[n:].any()
    if batch.vtype is not None:
        assert not batch.vtype[n:].any()
    for dst, src, et in zip(batch.layer_dst, batch.layer_src, batch.layer_etype):
        e = int((dst >= 0).sum())
        assert (dst[e:] == -1).all() and (src[e:] == -1).all() and not et[e:].any()
        assert (dst[:e] >= 0).all() and (src[:e] >= 0).all()


@pytest.mark.parametrize(
    "case", ["wraps", "regrow", "shrink", "typed", "early_stop", "respawn", "fallback"]
)
def test_slot_stream_is_bit_identical_to_serial(small_graph, partitioned, case, tmp_path, monkeypatch):
    kw = dict(vertex_quantum=16, edge_quantum=16) if case in ("regrow", "shrink") else {}
    if case == "typed":
        kw = dict(vertex_types=True)
    serial = _pipeline(small_graph, partitioned, 0, **kw)
    want = _drive(serial, case)

    filled = []  # (slot, size) of each batch the trainer read from a slot
    unpack = pipeline_mod._SlotRing.unpack
    monkeypatch.setattr(
        pipeline_mod._SlotRing, "unpack",
        lambda ring, msg: filled.append((msg.slot, msg.size)) or unpack(ring, msg),
    )
    if case == "fallback":  # no slot can grow: every batch is pickled
        def no_room(fd, size):
            raise OSError(errno.ENOMEM, "no room")

        monkeypatch.setattr(pipeline_mod.os, "ftruncate", no_room)
    before = _segments()
    pl = _pipeline(small_graph, partitioned, PREFETCH, **kw)
    traced = case in ("regrow", "fallback")
    try:
        if traced:
            with jax.profiler.trace(str(tmp_path)):
                got = _drive(pl, case)
        else:
            got = _drive(pl, case)
        assert len(_segments() - before) == PREFETCH + 2
    finally:
        pl.close()
    gc.collect()
    left = _segments() - before

    assert [len(r) for r in got] == [len(r) for r in want]
    assert len(got[-1]) >= 3 * (PREFETCH + 2)
    for run_got, run_want in zip(got, want):
        for (i, seeds, batch), (i0, seeds0, batch0) in zip(run_got, run_want):
            np.testing.assert_array_equal(seeds, seeds0)
            if case != "early_stop" or run_got is got[0]:
                assert i == i0
            # the serial build of these seeds under this batch's key
            serial._req_counter = i
            host = jax.device_get(batch)
            want_batch = serial.make_batch(seeds)
            assert jax.tree.structure(host) == jax.tree.structure(want_batch)
            for x, y in zip(jax.tree.leaves(host), jax.tree.leaves(want_batch)):
                np.testing.assert_array_equal(x, y)
            _assert_padding(host)

    if case == "fallback":
        assert filled == []
    else:
        assert len(filled) >= len(got[-1])
    if case == "regrow":  # a batch larger than its slot came through it intact
        sizes = {}
        grown = False
        for slot, size in filled:
            grown |= slot in sizes and size > sizes[slot]
            sizes[slot] = size
        assert grown
    if case == "shrink":  # a batch built over a larger one's leftovers
        last = {}  # slot -> (rows, edges) of the batch it last carried
        shrunk = False
        for (slot, _), (_, _, batch) in zip(filled, got[0]):
            host = jax.device_get(batch)
            size = (int(host.valid.sum()), sum(int((d >= 0).sum()) for d in host.layer_dst))
            shrunk |= slot in last and size[0] < last[slot][0] and size[1] < last[slot][1]
            last[slot] = size
        assert shrunk
    if case == "typed":
        for _, _, batch in got[0]:
            host = jax.device_get(batch)
            assert host.vtype is not None and host.vtype.any()
            assert any(et.any() for et in host.layer_etype)
    if case == "respawn":
        assert pl.respawn_count == 1
    if traced:
        by = {}
        for r in obs.snapshot():
            if r.name in ("batch.bytes", "batch.shm_bytes"):
                by.setdefault(r.batch, {})[r.name] = r.value
        for i, _, _ in got[0]:
            assert by[i]["batch.shm_bytes"] == (0 if case == "fallback" else by[i]["batch.bytes"])
    assert not left, "close() left a slot open or mapped"

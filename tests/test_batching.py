"""``subgraph_to_batch`` builds into the arrays it is given (the forked
producer's shared-memory slot) or into fresh ones: arrays full of an
earlier batch's values give the batch a fresh build gives, both equal the
plain build that pads a gathered copy, and a row outside the feature
matrix raises ``IndexError`` either way.  Features of another dtype are
cast into the float32 table."""
import jax
import numpy as np
import pytest

from repro.models.gnn.batching import GNNBatch, subgraph_to_batch


@pytest.fixture(scope="module")
def sample(sampling_client):
    return sampling_client.sample_khop(np.arange(0, 700, 7), [6, 4])


def _dirty(layout):
    """Arrays of the layout holding what no build writes: NaN, True, 7."""
    def fill(s):
        value = np.nan if s.dtype.kind == "f" else True if s.dtype == bool else 7
        return np.full(s.shape, value, s.dtype)

    return jax.tree.map(fill, layout)


def _plain(sub, feats, labels, K, vq, eq, vertex_types=None, edge_types=None, lookup=None):
    """The build as a zeroed table with a gathered copy pasted in."""
    def bucket(n, q):
        return max(q, -(-n // q) * q)

    verts = sub.all_vertices()
    n, vpad = verts.shape[0], bucket(verts.shape[0], vq)
    table = np.zeros((vpad, feats.shape[1]), np.float32)
    table[:n] = feats[verts]
    valid = np.arange(vpad) < n
    vtype = None
    if vertex_types is not None:
        vtype = np.zeros(vpad, np.int32)
        vtype[:n] = vertex_types[verts]
    dsts, srcs, ets, cnts = [], [], [], []
    for k in range(K):
        hops = sub.hops[: K - k]
        s = np.concatenate([h.src for h in hops])
        d = np.concatenate([h.dst for h in hops])
        epad = bucket(s.shape[0], eq)
        dst, src, et = np.full(epad, -1, np.int32), np.full(epad, -1, np.int32), np.zeros(epad, np.int32)
        dst[: s.shape[0]] = np.searchsorted(verts, s)
        src[: s.shape[0]] = np.searchsorted(verts, d)
        if edge_types is not None:
            et[: s.shape[0]] = edge_types[np.concatenate([h.eid for h in hops])]
        elif lookup is not None:
            et[: s.shape[0]] = lookup(s, d)
        dsts.append(dst)
        srcs.append(src)
        ets.append(et)
        cnts.append(np.bincount(dst[dst >= 0], minlength=vpad).astype(np.float32)[:, None])
    return GNNBatch(
        feats=table, valid=valid, seed_pos=np.searchsorted(verts, sub.seeds).astype(np.int32),
        labels=labels[sub.seeds].astype(np.int32), layer_dst=dsts, layer_src=srcs,
        layer_etype=ets, layer_cnt=cnts, vtype=vtype,
    )


@pytest.mark.parametrize("edges", ["untyped", "table", "lookup"])
@pytest.mark.parametrize("typed_rows", [False, True])
def test_build_into_dirty_arrays_equals_a_fresh_build(small_graph, sample, typed_rows, edges):
    g = small_graph
    lookup = lambda s, d: (s + d) % 3  # noqa: E731
    kw = dict(vertex_quantum=64, edge_quantum=128)
    if typed_rows:
        kw["vertex_types"] = g.vertex_types
    if edges == "table":
        kw["edge_types"] = g.edge_types
    elif edges == "lookup":
        kw["edge_types_lookup"] = lookup
    given = []
    fresh = subgraph_to_batch(sample, g.vertex_feats, g.labels, 2, **kw)
    built = subgraph_to_batch(
        sample, g.vertex_feats, g.labels, 2, into=lambda layout: given.append(_dirty(layout)) or given[0], **kw,
    )
    plain = _plain(
        sample, g.vertex_feats, g.labels, 2, 64, 128, vertex_types=kw.get("vertex_types"),
        edge_types=kw.get("edge_types"), lookup=kw.get("edge_types_lookup"),
    )
    assert (built.vtype is not None) == typed_rows
    assert any(et.any() for et in built.layer_etype) == (edges != "untyped")
    assert jax.tree.structure(built) == jax.tree.structure(fresh) == jax.tree.structure(plain)
    for x, y, z, mem in zip(*map(jax.tree.leaves, (built, fresh, plain, given[0]))):
        assert x is mem  # built in the arrays it was given
        np.testing.assert_array_equal(x, y)
        np.testing.assert_array_equal(x, z)
        assert x.dtype == z.dtype


@pytest.mark.parametrize("path", ["fresh", "into"])
def test_a_row_outside_the_features_raises(small_graph, sample, path):
    feats = small_graph.vertex_feats[: int(sample.all_vertices().max())]  # the last row is missing
    with pytest.raises(IndexError):
        subgraph_to_batch(sample, feats, None, 2, into=_dirty if path == "into" else None)


@pytest.mark.parametrize("dtype", [np.float16, np.float64])
def test_features_of_another_dtype_are_cast_into_the_table(small_graph, sample, dtype):
    g = small_graph
    feats = g.vertex_feats.astype(dtype)
    built = subgraph_to_batch(sample, feats, g.labels, 2, vertex_quantum=64, edge_quantum=128, into=_dirty)
    plain = _plain(sample, feats, g.labels, 2, 64, 128)
    assert built.feats.dtype == np.float32
    np.testing.assert_array_equal(built.feats, plain.feats)

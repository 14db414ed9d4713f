"""The training harness takes a model on a typed graph with no edit to
its files: the reference is given each row's vertex type and each sampled
edge's type, the seeds can be drawn from some vertex types only, and a
graph generator is found by name.  For the configurations already in the
benchmark the model, the seeds and the cached graph stay what they were."""
import json
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import common
from reference import train as ref_train

CONFIGS = {
    name: json.loads((common.BENCH / "configs" / f"{name}.json").read_text())
    for name in ("sage-papers", "gat-products")
}


def _train_kind():
    return common.load_module(common.BENCH / "kinds" / "train.py")


@pytest.fixture
def typed(tmp_path, monkeypatch):
    """A power-law graph with 3 vertex and 5 edge types, its system, and a
    CPU-sized configuration over it."""
    monkeypatch.setattr(common, "CACHE", tmp_path / "bench-cache")
    cfg = {
        "graph": {"generator": "power_law", "num_vertices": 1500, "avg_degree": 8.0,
                  "num_vertex_types": 3, "num_edge_types": 5, "feat_dim": 8,
                  "num_classes": 4, "seed": 0},
        "system": {"num_parts": 2, "partitioner": "adadne", "fanouts": [4, 3, 2],
                   "batch_size": 32, "vertex_quantum": 2048, "edge_quantum": 4096},
        "model": {"kind": "standin", "hidden": 8, "num_layers": 3},
        "optimizer": json.loads((common.BENCH / "configs" / "sage-papers.json").read_text())["optimizer"],
    }
    g, system = common.build_system(cfg)
    yield cfg, g, system
    system.close()


def test_typed_reference_sees_the_graphs_types(typed, monkeypatch):
    cfg, g, system = typed
    assert set(np.unique(g.vertex_types)) == {0, 1, 2}
    assert set(np.unique(g.edge_types)) == {0, 1, 2, 3, 4}
    seen = []

    def layer(p, h, dst, src, n, dot, model, *, vtype, etype):
        jax.debug.callback(lambda v, e: seen.append((np.asarray(v), np.asarray(e))), vtype, etype)
        agg = jax.ops.segment_sum(h[src], dst, num_segments=n)
        return jnp.tanh(dot(jnp.concatenate([h, agg], axis=1), p["w"]))

    def init(key, in_dim, model, num_classes):
        dims = [in_dim] + [model["hidden"]] * model["num_layers"]
        return {
            "layers": [{"w": jax.random.normal(jax.random.fold_in(key, k), (2 * dims[k], dims[k + 1]))}
                       for k in range(model["num_layers"])],
            "out": jax.random.normal(jax.random.fold_in(key, 999), (model["hidden"], num_classes)),
        }

    monkeypatch.setattr(ref_train, "model_module", lambda kind: NS(init=init, layer=layer))
    s = cfg["system"]
    seeds = np.random.default_rng(1).choice(g.num_vertices, s["batch_size"], replace=False)
    sub = system.sample(seeds, key=(0, 0))
    ref_train.validate(sub, seeds, g, s["fanouts"])
    blk = ref_train.block(sub, g, 3, s["vertex_quantum"], s["edge_quantum"])
    ref_train.run("standin", cfg["model"], cfg["optimizer"], 0, 8, 4, [blk])

    verts = np.unique(np.concatenate([sub.seeds] + [h.src for h in sub.hops] + [h.dst for h in sub.hops]))
    want_v = np.zeros(s["vertex_quantum"], np.int32)
    want_v[: verts.size] = g.vertex_types[verts]
    assert len(seen) >= 3
    for k in range(3):
        eid = np.concatenate([h.eid for h in sub.hops[: 3 - k]])
        want_e = np.zeros(s["edge_quantum"], np.int32)
        want_e[: eid.size] = g.edge_types[eid]
        # the graph edge runs from the aggregating row to the message's row
        real = slice(0, eid.size)
        np.testing.assert_array_equal(verts[blk["dst"][k][real]], g.src[eid])
        np.testing.assert_array_equal(verts[blk["src"][k][real]], g.dst[eid])
        calls = [(v, e) for v, e in seen if np.array_equal(e, want_e)]
        assert calls, f"layer {k} was not given the sampled edges' types"
        for v, _ in calls:
            np.testing.assert_array_equal(v, want_v)


def test_seed_vertex_types_draw_seeds_of_those_types(typed):
    cfg, g, _ = typed
    rng = np.random.default_rng(5)
    ids = _train_kind().train_seeds(dict(cfg, seed_vertex_types=[0, 2]), g, rng)
    assert set(np.unique(g.vertex_types[ids])) == {0, 2}
    np.testing.assert_array_equal(np.sort(ids), np.flatnonzero(g.vertex_types != 1))


def test_generator_found_by_name(tmp_path, monkeypatch):
    from repro.graph import generate

    assert common.graph_generator("power_law") is generate.power_law_graph
    assert common.graph_generator("erdos_renyi") is generate.erdos_renyi_graph
    with pytest.raises(common.BenchError, match="no graph generator"):
        common.graph_generator("no_such")

    def ring_graph(num_vertices, feat_dim, num_classes, seed):
        return generate.erdos_renyi_graph(num_vertices, 2.0, seed, feat_dim=feat_dim, num_classes=num_classes)

    monkeypatch.setattr(generate, "ring_graph", ring_graph, raising=False)
    monkeypatch.setattr(common, "CACHE", tmp_path)
    params = {"generator": "ring", "num_vertices": 50, "feat_dim": 3, "num_classes": 2, "seed": 4}
    made, cached = common.load_graph(params), common.load_graph(params)
    np.testing.assert_array_equal(made.src, ring_graph(50, 3, 2, 4).src)
    np.testing.assert_array_equal(cached.vertex_feats, made.vertex_feats)


@pytest.mark.parametrize("name, key", [
    ("sage-papers", "348301ace7777f68"),
    ("gat-products", "b9425c2278916f14"),
])
def test_existing_configurations_read_what_they_read(name, key):
    """The model's arguments, the seed order and the graph's cache key of
    the cells that were there before the harness took typed models."""
    cfg = CONFIGS[name]
    m = cfg["model"]
    assert _train_kind().model_kwargs(cfg, cfg["graph"]["feat_dim"]) == {
        "kind": m["kind"], "in_dim": cfg["graph"]["feat_dim"], "hidden": m["hidden"],
        "num_layers": m["num_layers"], "num_classes": cfg["graph"]["num_classes"],
        "num_heads": m["num_heads"],
    }
    n = cfg["graph"]["num_vertices"]
    seed = 2**31 + 77
    got = _train_kind().train_seeds(cfg, NS(num_vertices=n), common.run_seed_key(seed)[1])
    np.testing.assert_array_equal(got, common.run_seed_key(seed)[1].permutation(n))
    assert common.graph_key(cfg["graph"]) == key


def test_merged_blocks_keep_each_block_to_its_rows():
    def blk(rows, seed_rows, labels, vt):
        return {
            "feats": np.arange(rows * 2, dtype=np.float32).reshape(rows, 2),
            "vtype": np.full(rows, vt, np.int32),
            "seed_pos": np.asarray(seed_rows, np.int32),
            "labels": np.asarray(labels, np.int32),
            "dst": [np.array([0, rows - 1], np.int32)],
            "src": [np.array([1, rows - 1], np.int32)],
            "etype": [np.array([vt, 0], np.int32)],
        }

    a, b = blk(4, [0, 2], [5, 6], 1), blk(3, [1, 0], [7, 8], 2)
    m = ref_train.merge([a, b])
    np.testing.assert_array_equal(m["feats"], np.concatenate([a["feats"], b["feats"]]))
    np.testing.assert_array_equal(m["vtype"], [1, 1, 1, 1, 2, 2, 2])
    np.testing.assert_array_equal(m["seed_pos"], [0, 2, 5, 4])
    np.testing.assert_array_equal(m["labels"], [5, 6, 7, 8])
    np.testing.assert_array_equal(m["dst"][0], [0, 3, 4, 6])
    np.testing.assert_array_equal(m["src"][0], [1, 3, 5, 6])
    np.testing.assert_array_equal(m["etype"][0], [1, 0, 2, 0])
    assert all(m[k][0].dtype == np.int32 for k in ("dst", "src", "etype"))

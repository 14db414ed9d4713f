"""HGT on a MAG240M-shaped typed graph: the program's model against the
plain reference (``bench/reference/hgt.py``) on seeded random weights and
a small sampled batch, with the comparison shown to fail on planted
errors; and the shape of ``mag240m_graph``."""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import GLISPConfig, GLISPSystem
from repro.graph.generate import MAG240M_EDGE_TYPES, MAG240M_REVERSE, mag240m_graph
from repro.models.gnn import GNNModel
from repro.models.gnn import models as gnn_models
from repro.models.gnn.batching import subgraph_to_batch

REFERENCE = Path(__file__).resolve().parents[1] / "bench" / "reference"
MODEL = {"kind": "hgt", "hidden": 32, "num_layers": 2, "num_heads": 4,
         "num_vtypes": 3, "num_etypes": 5}
FEAT, CLASSES, VPAD, EPAD = 24, 7, 1024, 4096


def _load(name):
    spec = importlib.util.spec_from_file_location(f"ref_{name}", REFERENCE / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def case():
    """A sampled batch of 16 papers at fanouts 6/4, as the program and the
    reference each see it."""
    g = mag240m_graph(3000, feat_dim=FEAT, num_classes=CLASSES, seed=3)
    system = GLISPSystem.build(g, GLISPConfig(num_parts=2, fanouts=(6, 4)))
    try:
        seeds = np.random.default_rng(0).choice(np.flatnonzero(g.vertex_types == 0), 16, replace=False)
        sub = system.sample(seeds, key=(0, 0))
    finally:
        system.close()
    batch = subgraph_to_batch(sub, g.vertex_feats, g.labels, 2, edge_types=g.edge_types,
                              vertex_quantum=VPAD, edge_quantum=EPAD, vertex_types=g.vertex_types)
    blk = _load("train").block(sub, g, 2, VPAD, EPAD)
    return jax.tree.map(jnp.asarray, batch), jax.tree.map(jnp.asarray, blk)


def _program(params, batch):
    model = GNNModel(in_dim=FEAT, num_classes=CLASSES, **MODEL)
    return model.apply(params, batch), model.loss(params, batch)


def _reference(params, blk):
    ref = _load("hgt")

    def dot(a, b):
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)

    h, n = blk["feats"], blk["feats"].shape[0]
    for k, pk in enumerate(params["layers"]):
        h = ref.layer(pk, h, blk["dst"][k], blk["src"][k], n, dot, MODEL,
                      vtype=blk["vtype"], etype=blk["etype"][k])
    logits = dot(h[blk["seed_pos"]], params["out"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    return logits, jnp.mean(logz - jnp.take_along_axis(logits, blk["labels"][:, None], axis=-1)[:, 0])


def _outputs(f, params, x):
    """``((logits, loss), gradient of the loss)`` of ``f(params, x)``."""
    return jax.jit(lambda p: (f(p, x), jax.grad(lambda q: f(q, x)[1])(p)))(params)


PARAMS_KEY = 11


@pytest.fixture(scope="module")
def reference(case):
    """The reference's logits, loss and gradient on the case's batch."""
    params = _load("hgt").init(jax.random.PRNGKey(PARAMS_KEY), FEAT, MODEL, CLASSES)
    return params, _outputs(_reference, params, case[1])


def _gaps(case, reference, program=_program, perturb=lambda p: p):
    """The largest relative gaps of logits, loss and any gradient leaf."""
    params, ((logits_r, lr), gr) = reference
    (logits_p, lp), gp = _outputs(lambda p, x: program(perturb(p), x), params, case[0])
    leaf = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))), gp, gr)
    return {
        "logits": float(jnp.max(jnp.abs(logits_p - logits_r)) / jnp.max(jnp.abs(logits_r))),
        "loss": abs(float(lp) - float(lr)) / abs(float(lr)),
        "grad": max(jax.tree.leaves(leaf)),
    }


# Both sides compute in float32 (on the CPU the program's default-precision
# matmuls are float32 too); they differ in the order of sums: typed linears
# selected against gathered, relation transforms per edge against per row,
# the softmax's segment sums.  Each gap is relative to the largest
# magnitude of the compared values: about 1e-7 per rounding, grown by two
# layers and the backward pass, so 1e-5 leaves a hundredfold room while a
# bfloat16 rounding (3.9e-3) or any planted error below lies far above.
LIMITS = {"logits": 1e-5, "loss": 1e-5, "grad": 1e-5}


def test_program_matches_reference(case, reference):
    gaps = _gaps(case, reference)
    assert all(gaps[k] <= LIMITS[k] for k in LIMITS), gaps


def test_program_weights_are_the_reference_weights():
    model = GNNModel(in_dim=FEAT, num_classes=CLASSES, **MODEL)
    got = model.init(jax.random.PRNGKey(5))
    want = _load("hgt").init(jax.random.PRNGKey(5), FEAT, MODEL, CLASSES)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    jax.tree.map(np.testing.assert_array_equal, got, want)


def _swap_attention(params):
    layers = [dict(p, w_att=p["w_att"].at[jnp.array([1, 2])].set(p["w_att"][jnp.array([2, 1])]))
              for p in params["layers"]]
    return dict(params, layers=layers)


def _softmax_per_relation(params, batch):
    """The program with its softmax taken over each target's in-edges of
    one relation at a time, not across relations."""
    rel = batch.layer_etype[0]
    real = gnn_models._seg_softmax_aggregate

    def per_relation(logits, msg, seg, n, use_kernel):
        assert seg.shape == rel.shape  # both layers' edge lists have one padded size
        key = jnp.where(seg >= 0, seg * 5 + rel, -1)
        alpha = gnn_models._seg_softmax(logits, key, n * 5)
        return gnn_models._seg_sum(msg * alpha[:, None], seg, n, False)

    gnn_models._seg_softmax_aggregate = per_relation
    try:
        return _program(params, batch)
    finally:
        gnn_models._seg_softmax_aggregate = real


@pytest.mark.parametrize("fault", [
    pytest.param(dict(perturb=_swap_attention), id="relations-1-2-attention-swapped"),
    pytest.param(dict(program=_softmax_per_relation), id="softmax-per-relation"),
])
def test_comparison_fails_on_a_planted_error(case, reference, fault):
    gaps = _gaps(case, reference, **fault)
    assert any(gaps[k] > 10 * LIMITS[k] for k in LIMITS), gaps


def test_untyped_batches_carry_no_row_types():
    """The row types ride only with a model that reads them; a batch built
    without them has the same arrays as before they existed."""
    from repro.core.sampling.service import SampledHop, SampledSubgraph

    assert GNNModel("hgt", FEAT).reads_vertex_types
    assert not any(GNNModel(kind, FEAT).reads_vertex_types for kind in ("gcn", "sage", "gat"))
    sub = SampledSubgraph(seeds=np.array([0, 1]), hops=[
        SampledHop(src=np.array([0, 1]), dst=np.array([2, 3]), eid=np.arange(2)),
        SampledHop(src=np.array([2]), dst=np.array([1]), eid=np.arange(1)),
    ])
    feats, vt = np.ones((4, 3), np.float32), np.array([0, 2, 1, 1])
    kw = dict(vertex_quantum=8, edge_quantum=16)
    plain = subgraph_to_batch(sub, feats, None, 2, **kw)
    typed = subgraph_to_batch(sub, feats, None, 2, vertex_types=vt, **kw)
    assert plain.vtype is None
    assert typed.vtype.tolist() == [0, 2, 1, 1, 0, 0, 0, 0]
    leaves = jax.tree.leaves(plain)
    assert len(jax.tree.leaves(typed)) == len(leaves) + 1
    assert sum(x.nbytes for x in leaves) == sum(x.nbytes for x in jax.tree.leaves(typed)) - 32


@pytest.fixture(scope="module")
def mag():
    return mag240m_graph(20_000, feat_dim=8, num_classes=153, seed=0)


def test_mag240m_type_counts(mag):
    counts = np.bincount(mag.vertex_types)
    assert counts.tolist() == [9973, 10025, 2]  # MAG240M's shares of 20,000
    assert mag.vertex_feats.shape == (20_000, 8) and mag.vertex_feats.dtype == np.float32
    papers = mag.vertex_types == 0
    assert mag.labels[papers].min() >= 0 and mag.labels[papers].max() < 153
    assert (mag.labels[~papers] == -1).all()


def test_mag240m_endpoint_types(mag):
    for t, (name, s, d) in enumerate(MAG240M_EDGE_TYPES):
        sel = mag.edge_types == t
        assert sel.any(), name
        assert set(np.unique(mag.vertex_types[mag.src[sel]])) == {s}, name
        assert set(np.unique(mag.vertex_types[mag.dst[sel]])) == {d}, name
        r = MAG240M_REVERSE[t]
        assert MAG240M_EDGE_TYPES[r][1:] == (d, s), name


def test_mag240m_every_edge_has_its_reverse(mag):
    n = mag.num_vertices
    code = lambda s, d, t: (s * n + d) * 5 + t
    fwd = np.sort(code(mag.src, mag.dst, mag.edge_types.astype(np.int64)))
    rev_t = np.asarray(MAG240M_REVERSE)[mag.edge_types]
    back = np.sort(code(mag.dst, mag.src, rev_t))
    np.testing.assert_array_equal(fwd, back)


def test_mag240m_degrees_and_tail(mag):
    papers = np.flatnonzero(mag.vertex_types == 0)
    cites_in = np.bincount(mag.dst[mag.edge_types == 0], minlength=mag.num_vertices)[papers]
    assert 20.5 < cites_in.mean() < 22.0  # 21.3 stored cites per paper
    assert cites_in.max() >= 50 * cites_in.mean()
    writes = (mag.edge_types == 1).sum()
    assert 3.0 < writes / papers.size < 3.35  # 3.17 authors per paper


def test_layerwise_slice_is_the_training_layer():
    """The inference engine's hgt slice, given the self rows and one row
    per neighbour edge with their vertex types, is the training layer."""
    model = GNNModel(in_dim=FEAT, num_classes=CLASSES, **MODEL)
    params = model.init(jax.random.PRNGKey(2))
    rng = np.random.default_rng(4)
    n, e = 40, 160
    h = rng.standard_normal((n, FEAT)).astype(np.float32)
    vt = rng.integers(0, 3, n).astype(np.int32)
    dst = np.sort(rng.integers(0, n, e)).astype(np.int32)
    src = rng.integers(0, n, e).astype(np.int32)
    et = rng.integers(0, 5, e).astype(np.int32)
    want = model.layer(params["layers"][0], 0, h, dst, src, et, vtype=vt)
    fn = model.embed_layer_fn(params, 0)
    assert fn.typed
    got = fn(0, h, h[src], dst, et, vt, vt[src])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="vertex types"):  # no row types, no guess
        fn(0, h, h[src], dst, et)


def test_layerwise_engine_passes_row_types(small_graph, sampling_client, tmp_path):
    """The bucketed engine (jit slices) and its reference mode (numpy
    calls) both hand every slice its rows' vertex types: they agree on a
    typed HGT."""
    from repro.core.inference import LayerwiseInferenceEngine

    model = GNNModel("hgt", 16, hidden=16, num_layers=2, num_heads=2, num_vtypes=3,
                     num_etypes=small_graph.num_edge_types)
    params = model.init(jax.random.PRNGKey(0))
    fns = [model.embed_layer_fn(params, k) for k in range(2)]
    kw = dict(fanouts=[10**9, 10**9], chunk_rows=128, out_dims=[16, 16])
    runs = {
        mode: LayerwiseInferenceEngine(
            small_graph, sampling_client, fns, small_graph.vertex_feats,
            str(tmp_path / mode), mode=mode, batch_size=512, **kw,
        ).run()
        for mode in ("reference", "bucketed")
    }
    ids = np.arange(small_graph.num_vertices)
    a, b = (r.final_store.read_rows_direct(r.newid[ids]) for r in runs.values())
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_server_serves_the_typed_final_layer(tmp_path):
    """The server hands the final hgt slice its rows' vertex types: at full
    fanout a served vertex reads what layerwise inference stored for it."""
    g = mag240m_graph(1500, feat_dim=16, num_classes=4, seed=5)
    full = (10**9, 10**9)
    system = GLISPSystem.build(g, GLISPConfig(num_parts=2, fanouts=full))
    try:
        model = GNNModel("hgt", 16, hidden=16, num_layers=2, num_heads=2, num_vtypes=3,
                         num_etypes=5)
        params = model.init(jax.random.PRNGKey(1))
        res = system.infer_layerwise([model.embed_layer_fn(params, k) for k in range(2)],
                                     str(tmp_path), out_dims=[16, 16], batch_size=256)
        server = system.server(max_batch_delay_ms=0.0, deadline_ms=None)
        verts = np.concatenate([np.flatnonzero(g.vertex_types == t)[:3] for t in range(3)])
        got = server.call(verts).embeddings
        want = res.final_store.read_rows_direct(res.newid[verts])
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    finally:
        system.close()

"""GNN kernels and dispatchers vs their jnp oracles + the deterministic
autotuner.

Property sweeps cover ragged edge counts (padding tails of every length,
including all-padding and zero-edge inputs), both dtypes the engine
dispatches (f32/bf16), and empty segments; the autotuner tests pin the
determinism contract: same inputs -> same config, memory hit on the second
call, artifact hit after a simulated process restart.
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import autotune as at
from repro.kernels.fused_gnn import (
    gat_softmax_aggregate_pallas,
    segment_max_pallas,
    segment_spmm_ragged_pallas,
)
from repro.kernels.ops import gnn_gather_aggregate
from repro.kernels.ref import (
    gat_softmax_aggregate_ref,
    gather_spmm_ref,
    segment_max_ref,
    segment_spmm_ref,
)

_TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


def _close(got, want, dtype=jnp.float32):
    tol = _TOL[dtype]
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol * 10,
    )


def _edges(m, n, valid, seed, d=None, dtype=jnp.float32):
    """idx/seg with a padding tail (-1) after ``valid`` real edges."""
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, n, max(m, 1)).astype(np.int32)[:m]
    seg = np.sort(rng.integers(0, n, max(m, 1))).astype(np.int32)[:m]
    idx[valid:] = -1
    seg[valid:] = -1
    out = [jnp.asarray(idx), jnp.asarray(seg)]
    if d is not None:
        feats = jnp.asarray(rng.standard_normal((n, d)), dtype=dtype)
        msg = jnp.asarray(rng.standard_normal((m, d)), dtype=dtype)
        logits = jnp.asarray(rng.standard_normal(m), dtype=dtype)
        out += [feats, msg, logits]
    return out


# ---------------------------------------------------------------------------
# property sweeps: ragged edge counts, random segment maps
# ---------------------------------------------------------------------------


@settings(max_examples=12, deadline=None)
@given(
    m=st.integers(1, 120),
    n=st.integers(1, 40),
    d=st.integers(1, 24),
    frac=st.floats(0.0, 1.0),
    seed=st.integers(0, 99),
)
def test_property_gather_spmm(m, n, d, frac, seed):
    valid = int(m * frac)
    idx, seg, feats, _, _ = _edges(m, n, valid, seed, d=d)
    want = gather_spmm_ref(feats, idx, seg, n)
    _close(gnn_gather_aggregate(feats, idx, seg, n, block_edges=32), want)


@settings(max_examples=12, deadline=None)
@given(
    m=st.integers(1, 120),
    n=st.integers(1, 40),
    d=st.integers(1, 24),
    frac=st.floats(0.0, 1.0),
    seed=st.integers(0, 99),
)
def test_property_segment_spmm_ragged(m, n, d, frac, seed):
    valid = int(m * frac)
    _, seg, _, msg, _ = _edges(m, n, valid, seed, d=d)
    want = segment_spmm_ref(msg, seg, n)
    _close(segment_spmm_ragged_pallas(msg, seg, n, block_edges=32), want)


@settings(max_examples=12, deadline=None)
@given(
    m=st.integers(1, 120),
    n=st.integers(1, 40),
    d=st.integers(1, 24),
    frac=st.floats(0.0, 1.0),
    seed=st.integers(0, 99),
)
def test_property_gat_softmax_aggregate(m, n, d, frac, seed):
    valid = int(m * frac)
    _, seg, _, msg, logits = _edges(m, n, valid, seed, d=d)
    want = gat_softmax_aggregate_ref(logits, msg, seg, n)
    got = gat_softmax_aggregate_pallas(logits, msg, seg, n, block_edges=32)
    # softmax-weighted sums amplify error a touch vs plain sums
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


@settings(max_examples=12, deadline=None)
@given(
    m=st.integers(1, 120),
    n=st.integers(1, 40),
    frac=st.floats(0.0, 1.0),
    seed=st.integers(0, 99),
)
def test_property_segment_max(m, n, frac, seed):
    valid = int(m * frac)
    _, seg, _, _, logits = _edges(m, n, valid, seed, d=1)
    want = segment_max_ref(logits, seg, n)
    got = segment_max_pallas(logits, seg, n, block_edges=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=0)


# ---------------------------------------------------------------------------
# dtype sweep + deterministic edge cases
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_kernels_dtypes(dtype):
    m, n, d = 200, 48, 16
    idx, seg, feats, msg, logits = _edges(m, n, 150, seed=7, d=d, dtype=dtype)
    _close(
        gnn_gather_aggregate(feats, idx, seg, n),
        gather_spmm_ref(feats, idx, seg, n),
        dtype,
    )
    _close(
        segment_spmm_ragged_pallas(msg, seg, n), segment_spmm_ref(msg, seg, n), dtype
    )
    _close(
        gat_softmax_aggregate_pallas(logits, msg, seg, n),
        gat_softmax_aggregate_ref(logits, msg, seg, n),
        dtype,
    )
    got = segment_max_pallas(logits, seg, n)
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(segment_max_ref(logits, seg, n), np.float32),
    )
    assert got.dtype == dtype


def test_all_padding_tiles_produce_zeros():
    n, d = 12, 8
    idx, seg, feats, msg, logits = _edges(96, n, 0, seed=3, d=d)
    assert not np.asarray(gnn_gather_aggregate(feats, idx, seg, n, block_edges=32)).any()
    assert not np.asarray(
        segment_spmm_ragged_pallas(msg, seg, n, block_edges=32)
    ).any()
    assert not np.asarray(
        gat_softmax_aggregate_pallas(logits, msg, seg, n, block_edges=32)
    ).any()
    # empty segments: segment-max convention is 0, matching the oracle
    np.testing.assert_array_equal(
        np.asarray(segment_max_pallas(logits, seg, n, block_edges=32)), np.zeros(n)
    )


def test_zero_edge_input():
    n, d = 5, 4
    feats = jnp.ones((n, d), jnp.float32)
    empty_i = jnp.zeros((0,), jnp.int32)
    empty_f = jnp.zeros((0, d), jnp.float32)
    out = gnn_gather_aggregate(feats, empty_i, empty_i, n)
    assert out.shape == (n, d) and not np.asarray(out).any()
    out = gat_softmax_aggregate_pallas(
        jnp.zeros((0,), jnp.float32), empty_f, empty_i, n
    )
    assert out.shape == (n, d) and not np.asarray(out).any()


def test_segment_with_no_edges_stays_zero():
    # segment 1 never appears: its row must be exactly zero, not epsilon
    seg = jnp.array([0, 0, 2, -1], jnp.int32)
    idx = jnp.array([1, 2, 0, -1], jnp.int32)
    feats = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)
    out = np.asarray(gnn_gather_aggregate(feats, idx, seg, 3))
    np.testing.assert_array_equal(out[1], np.zeros(4))
    np.testing.assert_allclose(out, np.asarray(gather_spmm_ref(feats, idx, seg, 3)))


# ---------------------------------------------------------------------------
# autotuner: deterministic choice, memory/artifact cache hits
# ---------------------------------------------------------------------------


@pytest.fixture(autouse=True)
def _clean_tuner():
    at.reset()
    yield
    at.reset()


def test_autotune_same_inputs_same_config(tmp_path):
    shape = (256, 64, 16)
    op = "segment_spmm_ragged"
    cfg1 = at.autotune(op, shape, jnp.float32, cache_dir=str(tmp_path))
    assert at.stats()["measured"] == 1
    cfg2 = at.autotune(op, shape, jnp.float32, cache_dir=str(tmp_path))
    assert cfg2 == cfg1 and at.stats()["memory_hits"] == 1
    assert at.stats()["measured"] == 1  # no re-sweep
    # the chosen config is from the fixed candidate grid
    assert cfg1 in at.CANDIDATES[op]
    # and ops.py can now resolve it for any shape in the same pow2 bucket
    assert at.get_tuned(op, (200, 50, 12), jnp.float32) == cfg1


def test_autotune_artifact_roundtrip(tmp_path):
    shape = (256, 64, 16)
    cfg = at.autotune("segment_max", shape, jnp.float32, cache_dir=str(tmp_path))
    path = at.artifact_path(str(tmp_path))
    assert path.endswith(".json") and "kernel_tune_v" in path
    payload = json.loads(open(path).read())
    key = at.tuned_key("segment_max", shape, jnp.float32)
    assert payload["configs"][key] == {
        "block_rows": cfg.block_rows, "block_edges": cfg.block_edges,
    }
    at.reset(clear_stats=False)  # simulate a fresh process, artifact survives
    cfg2 = at.autotune("segment_max", shape, jnp.float32, cache_dir=str(tmp_path))
    assert cfg2 == cfg and at.stats()["artifact_hits"] == 1
    assert at.stats()["measured"] == 1  # artifact hit: no re-sweep


def test_autotune_key_buckets_pow2():
    op = "segment_spmm_ragged"
    k1 = at.tuned_key(op, (200, 50, 12), jnp.float32)
    k2 = at.tuned_key(op, (256, 64, 16), jnp.float32)
    assert k1 == k2 == "segment_spmm_ragged/256x64x16/float32"
    assert at.tuned_key(op, (300, 50, 12), jnp.float32) != k1
    assert at.tuned_key(op, (200, 50, 12), jnp.bfloat16) != k1


def test_autotune_unknown_op_raises():
    with pytest.raises(ValueError, match="unknown tuned op"):
        at.autotune("not_a_kernel", (64, 16, 8), jnp.float32)


def test_autotune_for_slice_tunes_each_shape(tmp_path):
    shapes = [
        ("segment_spmm_ragged", (128, 32, 8)),
        ("gat_softmax_aggregate", (128, 32, 8)),
    ]
    at.autotune_for_slice(shapes, jnp.float32, cache_dir=str(tmp_path))
    assert at.stats()["measured"] == 2
    for op, shape in shapes:
        assert at.get_tuned(op, shape, jnp.float32) in at.CANDIDATES[op]

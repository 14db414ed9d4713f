"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU): shape/dtype
sweeps + hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ref import attention_ref, segment_spmm_ref, ssd_scan_ref
from repro.kernels.segment_spmm import segment_spmm_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


# ---------------------------------------------------------------------------
# segment spmm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,d,n", [(64, 32, 16), (200, 64, 100), (513, 128, 257)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_spmm_shapes(m, d, n, dtype):
    rng = np.random.default_rng(m)
    msg = jnp.asarray(rng.standard_normal((m, d)), dtype=dtype)
    seg = jnp.asarray(np.sort(rng.integers(0, n, m)).astype(np.int32))
    out_k = segment_spmm_pallas(msg, seg, n, block_rows=64, block_edges=64)
    out_r = segment_spmm_ref(msg, seg, n)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32), np.asarray(out_r, np.float32),
        rtol=tol, atol=tol * 10,
    )


def test_segment_spmm_padding_ignored():
    msg = jnp.ones((8, 4), jnp.float32)
    seg = jnp.array([0, 0, 1, -1, -1, 2, 2, 2], jnp.int32)
    out = segment_spmm_pallas(msg, seg, 3, block_rows=8, block_edges=8)
    np.testing.assert_allclose(np.asarray(out)[:, 0], [2, 1, 3])


def test_segment_spmm_unsorted_segments():
    rng = np.random.default_rng(0)
    msg = jnp.asarray(rng.standard_normal((100, 16)).astype(np.float32))
    seg = jnp.asarray(rng.integers(0, 20, 100).astype(np.int32))  # unsorted
    out_k = segment_spmm_pallas(msg, seg, 20, block_rows=32, block_edges=32)
    out_r = segment_spmm_ref(msg, seg, 20)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), rtol=1e-5, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(
    m=st.integers(1, 150),
    d=st.integers(1, 40),
    n=st.integers(1, 50),
    seed=st.integers(0, 99),
)
def test_property_segment_spmm(m, d, n, seed):
    rng = np.random.default_rng(seed)
    msg = rng.standard_normal((m, d)).astype(np.float32)
    seg = rng.integers(-1, n, m).astype(np.int32)
    out_k = np.asarray(segment_spmm_pallas(jnp.asarray(msg), jnp.asarray(seg), n,
                                           block_rows=32, block_edges=32))
    # numpy oracle
    want = np.zeros((n, d), np.float32)
    for i in range(m):
        if seg[i] >= 0:
            want[seg[i]] += msg[i]
    np.testing.assert_allclose(out_k, want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sq,skv,d", [(64, 64, 32), (100, 100, 64), (1, 200, 32)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 37), (False, 0)])
def test_flash_attention(sq, skv, d, causal, window):
    rng = np.random.default_rng(sq + d)
    q = jnp.asarray(rng.standard_normal((sq, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((skv, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((skv, d)).astype(np.float32))
    off = skv - sq if sq < skv else 0
    out_k = flash_attention_pallas(
        q, k, v, causal=causal, window=window, kv_offset=off,
        block_q=32, block_kv=32,
    )
    out_r = attention_ref(q, k, v, causal=causal, window=window, kv_offset=off)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), rtol=2e-5, atol=2e-5)


def test_flash_attention_matches_model_blockwise():
    """Kernel == the model's pure-jnp blockwise attention path."""
    from repro.models.transformer.layers import _blockwise_attention

    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 96, 2, 32)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((1, 96, 2, 32)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((1, 96, 2, 32)).astype(np.float32))
    blockwise = _blockwise_attention(q, k, v, causal=True, window=0, q_offset=0)
    for h in range(2):
        out_k = flash_attention_pallas(
            q[0, :, h], k[0, :, h], v[0, :, h], causal=True,
            block_q=32, block_kv=32,
        )
        np.testing.assert_allclose(
            np.asarray(out_k), np.asarray(blockwise[0, :, h]), rtol=2e-5, atol=2e-5
        )


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S,P,N,chunk", [(64, 16, 8, 16), (100, 32, 16, 32), (33, 8, 4, 16)])
def test_ssd_scan(S, P, N, chunk):
    rng = np.random.default_rng(S)
    x = jnp.asarray(rng.standard_normal((S, P)).astype(np.float32))
    dt = jnp.asarray((rng.random(S) * 0.5 + 0.01).astype(np.float32))
    A = -0.7
    B = jnp.asarray(rng.standard_normal((S, N)).astype(np.float32))
    C = jnp.asarray(rng.standard_normal((S, N)).astype(np.float32))
    y_k, state = ssd_scan_pallas(x, A * dt, dt, B, C, chunk=chunk)
    y_r = ssd_scan_ref(
        x[:, None, :], dt[:, None], jnp.array([A]), B[:, None, :], C[:, None, :]
    )[:, 0, :]
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), rtol=2e-4, atol=2e-4)


def test_ssd_chunked_jnp_matches_ref():
    """The model's chunked SSD == sequential recurrence oracle (multi-head,
    grouped B/C)."""
    from repro.models.transformer.ssm import ssd_chunked_jnp

    rng = np.random.default_rng(3)
    Bz, S, H, P, G, N = 2, 48, 4, 8, 2, 6
    x = jnp.asarray(rng.standard_normal((Bz, S, H, P)).astype(np.float32))
    dt = jnp.asarray((rng.random((Bz, S, H)) * 0.5 + 0.01).astype(np.float32))
    A = jnp.asarray(-rng.random(H).astype(np.float32) - 0.1)
    Bg = jnp.asarray(rng.standard_normal((Bz, S, G, N)).astype(np.float32))
    Cg = jnp.asarray(rng.standard_normal((Bz, S, G, N)).astype(np.float32))
    a = dt * A[None, None, :]
    y, state = ssd_chunked_jnp(x, a, dt, Bg, Cg, chunk=16)
    for b in range(Bz):
        y_ref = ssd_scan_ref(x[b], dt[b], A, Bg[b], Cg[b])
        np.testing.assert_allclose(np.asarray(y[b]), np.asarray(y_ref), rtol=3e-4, atol=3e-4)

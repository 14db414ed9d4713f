"""Pallas TPU kernel: Mamba-2 SSD chunked scan (state-space duality).

The SSD recurrence  state_s = exp(a_s)·state_{s-1} + dt_s·B_s⊗x_s,
y_s = C_s·state_s  is block-decomposed into chunks of length L (Dao & Gu
2024): within a chunk the token-token interaction is the L×L matrix
``M[i,j] = (C_i·B_j)·exp(csum_i − csum_j)·dt_j  (j ≤ i)`` — a dense
MXU matmul — while the inter-chunk contribution flows through the carried
[P, N] state.  The grid iterates chunks sequentially (TPU grid order is
sequential, so the state lives in a revisited output block), giving O(S·L)
work in MXU-friendly tiles instead of an elementwise scan.

Single (batch, head) slice per call: x [S, P], a=dt·A [S, 1], dt [S, 1],
B, C [S, N]; vmap over batch/heads in ops.py.  a must be ≤ 0 (A < 0,
dt > 0) so every exp() here is ≤ 1 — no overflow.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.mode import interpret_mode

__all__ = ["ssd_scan_pallas"]


def _kernel(a_ref, dt_ref, x_ref, b_ref, c_ref, y_ref, state_ref, *, chunk: int):
    cb = pl.program_id(0)

    @pl.when(cb == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    a = a_ref[...][:, 0].astype(jnp.float32)  # [L]
    dt = dt_ref[...][:, 0].astype(jnp.float32)  # [L]
    x = x_ref[...].astype(jnp.float32)  # [L, P]
    b = b_ref[...].astype(jnp.float32)  # [L, N]
    c = c_ref[...].astype(jnp.float32)  # [L, N]
    s0 = state_ref[...].astype(jnp.float32)  # [P, N]

    csum = jnp.cumsum(a)  # [L], decreasing (a <= 0)
    # intra-chunk: M[i, j] = (C_i · B_j) * exp(csum_i - csum_j) * dt_j, j <= i
    cb_mat = c @ b.T  # [L, L]
    seg = csum[:, None] - csum[None, :]
    ii = jax.lax.iota(jnp.int32, chunk)
    causal = ii[:, None] >= ii[None, :]
    m = jnp.where(causal, cb_mat * jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    m = m * dt[None, :]
    y = m @ x  # [L, P]
    # inter-chunk: y_i += exp(csum_i) * C_i · state0^T
    y += jnp.exp(csum)[:, None] * (c @ s0.T)  # [L, P]
    # state update: S = exp(csum[-1])·S0 + Σ_j exp(csum[-1]-csum_j)·dt_j·x_j⊗B_j
    w = jnp.exp(csum[-1] - csum) * dt  # [L]
    s_new = jnp.exp(csum[-1]) * s0 + jax.lax.dot_general(
        x * w[:, None],
        b,
        dimension_numbers=(((0,), (0,)), ((), ())),  # x^T @ B -> [P, N]
        preferred_element_type=jnp.float32,
    )
    y_ref[...] = y.astype(y_ref.dtype)
    state_ref[...] = s_new.astype(state_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(
    x: jax.Array,  # [S, P]
    a: jax.Array,  # [S]  (= A * dt, <= 0)
    dt: jax.Array,  # [S]
    B: jax.Array,  # [S, N]
    C: jax.Array,  # [S, N]
    *,
    chunk: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y [S, P], final_state [P, N])."""
    S, P = x.shape
    N = B.shape[1]
    s_pad = -(-S // chunk) * chunk
    if s_pad != S:
        # pad with a=0 (no decay), dt=0 (no input) => state preserved, y junk
        x = jnp.pad(x, ((0, s_pad - S), (0, 0)))
        a = jnp.pad(a, (0, s_pad - S))
        dt = jnp.pad(dt, (0, s_pad - S))
        B = jnp.pad(B, ((0, s_pad - S), (0, 0)))
        C = jnp.pad(C, ((0, s_pad - S), (0, 0)))
    grid = (s_pad // chunk,)
    y, state = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((chunk, 1), lambda cb: (cb, 0)),
            pl.BlockSpec((chunk, 1), lambda cb: (cb, 0)),
            pl.BlockSpec((chunk, P), lambda cb: (cb, 0)),
            pl.BlockSpec((chunk, N), lambda cb: (cb, 0)),
            pl.BlockSpec((chunk, N), lambda cb: (cb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((chunk, P), lambda cb: (cb, 0)),
            pl.BlockSpec((P, N), lambda cb: (0, 0)),  # revisited carry
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s_pad, P), x.dtype),
            jax.ShapeDtypeStruct((P, N), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(a[:, None], dt[:, None], x, B, C)
    return y[:S], state

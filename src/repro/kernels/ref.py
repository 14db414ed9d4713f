"""Pure-jnp oracles for every Pallas kernel (tests assert allclose)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "segment_spmm_ref",
    "segment_spmm_ragged_ref",
    "gather_spmm_ref",
    "gat_softmax_aggregate_ref",
    "segment_max_ref",
    "attention_ref",
    "flash_attention_ref",
    "ssd_scan_ref",
]


def segment_spmm_ref(msg: jax.Array, seg: jax.Array, num_segments: int) -> jax.Array:
    """out[s] = sum_{e: seg[e]==s} msg[e]; seg==-1 rows are dropped."""
    valid = (seg >= 0)[:, None].astype(msg.dtype)
    return jax.ops.segment_sum(
        msg * valid, jnp.maximum(seg, 0), num_segments=num_segments
    )


def segment_spmm_ragged_ref(
    msg: jax.Array, seg: jax.Array, num_segments: int
) -> jax.Array:
    """The ragged kernel skips all-padding tiles, which contribute zero —
    semantics are identical to the dense segment-SpMM."""
    return segment_spmm_ref(msg, seg, num_segments)


def gather_spmm_ref(
    feats: jax.Array, idx: jax.Array, seg: jax.Array, num_segments: int
) -> jax.Array:
    """out[s] = sum_{e: seg[e]==s} feats[idx[e]]; edges with idx or seg
    equal to -1 are dropped (``gnn_gather_aggregate``'s padding
    convention)."""
    ok = (idx >= 0) & (seg >= 0)
    msg = jnp.where(ok[:, None], feats[jnp.maximum(idx, 0)], 0)
    return jax.ops.segment_sum(
        msg, jnp.maximum(seg, 0), num_segments=num_segments
    )


def segment_max_ref(x: jax.Array, seg: jax.Array, num_segments: int) -> jax.Array:
    """Per-segment max (padding seg=-1 excluded); empty segments yield 0.0,
    matching the models' ``_seg_softmax`` finite-fix."""
    neg = jnp.where(seg >= 0, x, -jnp.inf)
    mx = jax.ops.segment_max(neg, jnp.maximum(seg, 0), num_segments=num_segments)
    return jnp.where(jnp.isfinite(mx), mx, 0.0).astype(x.dtype)


def gat_softmax_aggregate_ref(
    logits: jax.Array, msg: jax.Array, seg: jax.Array, num_segments: int
) -> jax.Array:
    """3-pass oracle for the one-pass kernel: segment-max, exp/normalize
    with the ``max(z, 1e-9)`` guard from ``_seg_softmax``, weighted
    segment-sum.  Empty segments return 0."""
    ok = seg >= 0
    seg0 = jnp.maximum(seg, 0)
    mx = segment_max_ref(logits.astype(jnp.float32), seg, num_segments)
    e = jnp.where(ok, jnp.exp(logits.astype(jnp.float32) - mx[seg0]), 0.0)
    z = jax.ops.segment_sum(e, seg0, num_segments=num_segments)
    alpha = e / jnp.maximum(z[seg0], 1e-9)
    weighted = jnp.where(ok[:, None], msg.astype(jnp.float32), 0.0) * alpha[:, None]
    return jax.ops.segment_sum(
        weighted, seg0, num_segments=num_segments
    ).astype(msg.dtype)


def attention_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    kv_offset: int = 0,
) -> jax.Array:
    """Dense single-head attention oracle with causal/window masks."""
    sq, d = q.shape
    skv = k.shape[0]
    s = (q.astype(jnp.float32) @ k.astype(jnp.float32).T) / (d**0.5)
    q_pos = kv_offset + jnp.arange(sq)[:, None]
    k_pos = jnp.arange(skv)[None, :]
    mask = jnp.ones_like(s, dtype=bool)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return (p @ v.astype(jnp.float32)).astype(q.dtype)


# KRN001 pairs every ``<base>_pallas`` kernel with a ``<base>_ref`` oracle;
# the flash kernel's oracle predates that convention under its dense name.
flash_attention_ref = attention_ref


def ssd_scan_ref(
    x: jax.Array,  # [S, H, P]   inputs per head
    dt: jax.Array,  # [S, H]      softplus'd timestep
    A: jax.Array,  # [H]         negative decay rate
    B: jax.Array,  # [S, G, N]   input projection (G state groups)
    C: jax.Array,  # [S, G, N]   output projection
) -> jax.Array:
    """Sequential SSD (Mamba-2) recurrence oracle:

        state_s = exp(A h dt_s) * state_{s-1} + dt_s * B_s ⊗ x_s
        y_s     = C_s · state_s

    Shapes follow Mamba-2: H heads, P head dim, N state dim, G B/C groups
    (heads per group = H // G).  Runs a lax.scan over time (exact)."""
    S, H, P = x.shape
    G, N = B.shape[1], B.shape[2]
    heads_per_group = H // G
    Bh = jnp.repeat(B, heads_per_group, axis=1)  # [S, H, N]
    Ch = jnp.repeat(C, heads_per_group, axis=1)

    decay = jnp.exp(A[None, :] * dt)  # [S, H]

    def step(state, inp):
        dec, dt_s, x_s, b_s, c_s = inp
        state = state * dec[:, None, None] + (
            dt_s[:, None, None] * x_s[:, :, None] * b_s[:, None, :]
        )  # [H, P, N]
        y = jnp.einsum("hpn,hn->hp", state, c_s)
        return state, y

    init = jnp.zeros((H, P, N), dtype=jnp.float32)
    _, ys = jax.lax.scan(
        step,
        init,
        (
            decay.astype(jnp.float32),
            dt.astype(jnp.float32),
            x.astype(jnp.float32),
            Bh.astype(jnp.float32),
            Ch.astype(jnp.float32),
        ),
    )
    return ys.astype(x.dtype)  # [S, H, P]

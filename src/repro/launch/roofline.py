"""Roofline extraction from a compiled dry-run artifact.

Three terms per (arch × shape × mesh), all per-chip seconds:

    compute_s    = analytic_model_flops / chips / peak_bf16
    memory_s     = max(cost_analysis bytes, analytic traffic) / HBM_bw
    collective_s = collective bytes parsed from the post-SPMD HLO / ICI_bw

Why analytic FLOPs: XLA's HloCostAnalysis counts a `while` body ONCE — a
24-layer lax.scan (or a 32-block flash loop) is undercounted by its trip
count.  We therefore count model FLOPs analytically (the standard MFU
accounting, including the attention S² terms, MoE capacity and SSD chunk
terms) and report the raw cost_analysis number alongside for transparency.

Collective bytes ARE taken from the compiled HLO (that's the real compiled
schedule), with while-loop trip counts recovered from the loop-condition
constants and multiplied through nested bodies.
"""
from __future__ import annotations

import re
from dataclasses import dataclass

from repro.launch.mesh import peaks
from repro.launch.specs import SHAPES
from repro.models.transformer.config import ArchConfig

__all__ = [
    "analytic_flops",
    "analytic_hbm_bytes",
    "parse_collectives",
    "roofline",
    "kernel_flops",
    "kernel_hbm_bytes",
    "kernel_roofline",
]


# ---------------------------------------------------------------------------
# analytic FLOPs
# ---------------------------------------------------------------------------


def _avg_context(S: int, window: int) -> float:
    """Mean causal context length over positions 0..S-1 (window-capped)."""
    if window <= 0 or window >= S:
        return S / 2
    # mean of min(t, w) over t in [0, S)
    return (window * (window - 1) / 2 + (S - window) * window) / S


def _mixer_flops_seq(cfg: ArchConfig, kind: str, S: int, decode_ctx: int | None):
    """FLOPs for one mixer layer over a sequence of S tokens (decode: S=1 and
    attention context = decode_ctx)."""
    d, dh = cfg.d_model, cfg.resolved_head_dim
    h, hkv = cfg.num_heads, cfg.num_kv_heads
    if kind in ("attn", "local_attn"):
        window = cfg.local_window if kind == "local_attn" else cfg.window
        if cfg.kv_lora_rank:
            r, rd = cfg.kv_lora_rank, cfg.rope_head_dim
            proj = S * 2 * d * (h * (dh + rd) + r + rd) + S * 2 * h * dh * d
            if decode_ctx is None:
                up = S * 2 * r * 2 * h * dh
                ctx = _avg_context(S, window)
            else:
                ctx = min(decode_ctx, window) if window else decode_ctx
                up = 2 * ctx * r * 2 * h * dh  # non-absorbed MLA decode
            attn = 2 * S * ctx * h * (dh + rd) + 2 * S * ctx * h * dh
            return proj + up + attn
        proj = S * (2 * d * h * dh + 4 * d * hkv * dh + 2 * h * dh * d)
        ctx = (
            _avg_context(S, window)
            if decode_ctx is None
            else (min(decode_ctx, window) if window else decode_ctx)
        )
        attn = 4 * S * ctx * h * dh
        return proj + attn
    if kind == "ssm":
        s = cfg.ssm
        d_in = s.expand * d
        nh = s.num_heads or d_in // s.head_dim
        g, n, p, L = s.num_groups, s.state_dim, s.head_dim, s.chunk
        proj = S * 2 * d * (2 * d_in + 2 * g * n + nh)
        conv = S * 2 * s.conv_width * (d_in + 2 * g * n)
        if decode_ctx is None:
            ssd = S * nh * (2 * L * n + 2 * L * p + 4 * n * p)
        else:
            ssd = S * nh * 6 * n * p  # single recurrence step
        out = S * 2 * d_in * d
        return proj + conv + ssd + out
    if kind == "rglru":
        return S * (2 * d * 2 * d + 4 * d * d + 2 * d * d + 12 * d)
    raise ValueError(kind)


def _mlp_flops_seq(cfg: ArchConfig, kind: str, S: int):
    d = cfg.d_model
    if kind == "ssm":
        return 0
    if cfg.moe is not None:
        e = cfg.moe
        dff = e.expert_d_ff or cfg.d_ff
        return S * (
            2 * d * e.num_experts
            + e.top_k * e.capacity_factor * 6 * d * dff
            + e.num_shared * 6 * d * dff
        )
    mats = 2 if cfg.activation == "gelu" else 3
    return S * mats * 2 * d * cfg.d_ff


def analytic_flops(cfg: ArchConfig, shape_name: str) -> dict:
    """Global (all-chips) FLOPs for one step of this shape."""
    sh = SHAPES[shape_name]
    B, S, kind = sh["batch"], sh["seq"], sh["kind"]
    decode = kind == "decode"
    s_tok = 1 if decode else S
    ctx = S if decode else None

    fwd = 0.0
    for lk in cfg.layer_kinds():
        fwd += _mixer_flops_seq(cfg, lk, s_tok, ctx)
        fwd += _mlp_flops_seq(cfg, lk, s_tok)
    head_tokens = s_tok if kind == "train" else 1
    fwd += head_tokens * 2 * cfg.d_model * cfg.vocab_size
    fwd *= B
    total = 3 * fwd if kind == "train" else fwd
    # 6·N·D convention for cross-checking (active params for MoE)
    n_active = cfg.num_params()
    if cfg.moe is not None:
        e = cfg.moe
        dff = e.expert_d_ff or cfg.d_ff
        n_active -= cfg.num_layers * (e.num_experts - e.top_k) * 3 * cfg.d_model * dff
    model_flops_6nd = (6 if kind == "train" else 2) * n_active * B * s_tok
    return {"total": total, "fwd": fwd, "6nd": model_flops_6nd}


# ---------------------------------------------------------------------------
# analytic HBM traffic (documented lower-bound model, per device)
# ---------------------------------------------------------------------------


def analytic_hbm_bytes(cfg: ArchConfig, shape_name: str, mesh_shape: dict) -> float:
    sh = SHAPES[shape_name]
    B, S, kind = sh["batch"], sh["seq"], sh["kind"]
    msize = mesh_shape.get("model", 1)
    dsize = 1
    for a in ("data", "pod"):
        dsize *= mesh_shape.get(a, 1)
    n_params = cfg.num_params()
    p_dev = 4 * n_params / msize  # fp32 master weights, model-sharded only
    b_dev = max(1, B // dsize)

    if kind == "train":
        # params: fwd read + remat read + bwd read; grads w+r; adam m,v r+w;
        # saved layer inputs (bf16) w+r; logits fp32 few passes
        act = cfg.num_layers * b_dev * S * cfg.d_model * 2 * 2
        logits = 3 * b_dev * S * (cfg.vocab_size / msize) * 4
        return 3 * p_dev + 2 * p_dev + 4 * p_dev + act + logits
    if kind == "prefill":
        act = cfg.num_layers * b_dev * S * cfg.d_model * 2 * 2
        cache = _cache_bytes_dev(cfg, S, b_dev, msize)
        return p_dev + act + cache
    # decode: weights once (fp32 read), cache read+write
    cache = _cache_bytes_dev(cfg, S, b_dev, msize)
    return p_dev + 2 * cache


def _cache_bytes_dev(cfg: ArchConfig, S: int, b_dev: int, msize: int) -> float:
    total = 0.0
    for lk in cfg.layer_kinds():
        if lk in ("attn", "local_attn"):
            L = S
            if lk == "local_attn":
                L = min(S, cfg.local_window)
            elif cfg.window:
                L = min(S, cfg.window)
            if cfg.kv_lora_rank:
                per_tok = (cfg.kv_lora_rank + cfg.rope_head_dim) * 2
            else:
                per_tok = 2 * cfg.num_kv_heads * cfg.resolved_head_dim * 2
            # kv-head (or sequence) dim is model-sharded when divisible
            total += b_dev * L * per_tok / msize
        elif lk == "ssm":
            s = cfg.ssm
            nh = s.num_heads or s.expand * cfg.d_model // s.head_dim
            total += b_dev * nh * s.head_dim * s.state_dim * 4
        elif lk == "rglru":
            total += b_dev * cfg.d_model * 4
    return total


# ---------------------------------------------------------------------------
# collective parsing with while-loop trip counts
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8, "c128": 16,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}
_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_COMP_START = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_WHILE_RE = re.compile(r"\bwhile\(.*condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_COLL_RE = re.compile(
    r"=\s+(\(?[a-z0-9\[\],{}\s:]*?\)?)\s*(all-gather|all-reduce|reduce-scatter|"
    r"all-to-all|collective-permute)(-start)?\("
)


def _shape_bytes_of(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for x in dims.split(","):
                n *= int(x)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class _Comp:
    colls: dict
    counts: dict
    whiles: list  # (cond_name, body_name)


def parse_collectives(hlo_text: str) -> dict:
    comps: dict[str, _Comp] = {}
    cur: _Comp | None = None
    cur_name = None
    entry = None
    for line in hlo_text.splitlines():
        m = _COMP_START.match(line.strip()) if line.rstrip().endswith("{") else None
        if m and not line.startswith(" "):
            cur_name = m.group(1)
            cur = _Comp({k: 0 for k in _COLL_OPS}, {k: 0 for k in _COLL_OPS}, [])
            comps[cur_name] = cur
            if line.lstrip().startswith("ENTRY"):
                entry = cur_name
            continue
        if cur is None:
            continue
        s = line.strip()
        if s == "}":
            cur = None
            continue
        wm = _WHILE_RE.search(s)
        if wm:
            cur.whiles.append((wm.group(1), wm.group(2)))
            continue
        cm = _COLL_RE.search(s)
        if cm and not s.startswith("ROOT %get"):
            if "-done(" in s:
                continue
            op = cm.group(2)
            out_bytes = _shape_bytes_of(cm.group(1))
            cur.colls[op] += out_bytes
            cur.counts[op] += 1

    def trip(cond_name: str) -> int:
        # crude but effective: the loop bound is the largest integer constant
        # in the condition computation (induction comparisons vs trip count)
        comp_text = _comp_texts.get(cond_name, "")
        consts = [int(x) for x in _CONST_RE.findall(comp_text)]
        return max(consts) if consts else 1

    # second pass to capture raw text per computation (for trip counts)
    _comp_texts: dict[str, str] = {}
    name = None
    buf: list[str] = []
    for line in hlo_text.splitlines():
        m = _COMP_START.match(line.strip()) if line.rstrip().endswith("{") else None
        if m and not line.startswith(" "):
            if name:
                _comp_texts[name] = "\n".join(buf)
            name = m.group(1)
            buf = []
        elif name:
            buf.append(line)
    if name:
        _comp_texts[name] = "\n".join(buf)

    memo: dict[str, tuple[dict, dict]] = {}

    def total(comp_name: str) -> tuple[dict, dict]:
        if comp_name in memo:
            return memo[comp_name]
        c = comps.get(comp_name)
        if c is None:
            return ({k: 0 for k in _COLL_OPS}, {k: 0 for k in _COLL_OPS})
        memo[comp_name] = (dict(c.colls), dict(c.counts))  # break cycles
        bytes_, counts_ = dict(c.colls), dict(c.counts)
        for cond, body in c.whiles:
            t = trip(cond)
            bb, bc = total(body)
            for k in _COLL_OPS:
                bytes_[k] += t * bb[k]
                counts_[k] += t * bc[k]
        memo[comp_name] = (bytes_, counts_)
        return memo[comp_name]

    if entry is None:
        entry = next(iter(comps), None)
    b, c = total(entry) if entry else ({k: 0 for k in _COLL_OPS},) * 2
    return {"bytes": b, "counts": c, "total_bytes": sum(b.values())}


# ---------------------------------------------------------------------------
# per-kernel analytic models (GNN Pallas suite; see repro.kernels.fused_gnn)
# ---------------------------------------------------------------------------
#
# Shape dict keys: edges E (padded), segments N, dim D, and optionally
# valid_edges Ev (ragged ops, default
# E; padding assumed to be a suffix as the engine lays it out), block_rows
# BN / block_edges BM (default 128), dtype_bytes b (default 4).
#
# FLOPs count the one-hot contraction as a dense (BN×BM)·(BM×D) matmul per
# tile — that IS what the MXU executes, so achieved-vs-peak is an honest
# hardware fraction even though most one-hot entries are zero.  Bytes model
# HBM traffic under the kernels' actual block residency:
#   * segment_spmm (2-D grid) re-reads each edge tile once per ROW block;
#   * the 1-D-grid kernels keep the output resident and read each edge
#     tile once;
#   * ragged variants only touch the ~ceil(Ev/BM) non-empty tiles.


def _kshape(shape: dict) -> tuple:
    e = float(shape["edges"])
    n = float(shape["segments"])
    d = float(shape["dim"])
    ev = float(shape.get("valid_edges", e))
    bn = float(shape.get("block_rows", 128))
    bm = float(shape.get("block_edges", 128))
    b = float(shape.get("dtype_bytes", 4))
    tiles = -(-e // bm)  # total edge tiles
    active = min(tiles, -(-ev // bm)) if ev > 0 else 0.0  # non-empty tiles
    return e, n, d, ev, bn, bm, b, tiles, active


KERNEL_OPS = (
    "segment_spmm",
    "segment_spmm_ragged",
    "gat_softmax_aggregate",
    "segment_max",
)


def kernel_flops(op: str, shape: dict) -> float:
    e, n, d, ev, bn, bm, b, tiles, active = _kshape(shape)
    matmul = 2.0 * n * d  # per edge row fed to the MXU
    if op == "segment_spmm":
        return e * matmul
    if op == "segment_spmm_ragged":
        return active * bm * matmul
    if op == "gat_softmax_aggregate":
        # matmul + membership/max/exp/rescale vector work per (edge, row)
        return e * (matmul + 8.0 * n)
    if op == "segment_max":
        return 2.0 * e * n  # compare + select
    raise ValueError(f"unknown kernel op {op!r}")


def kernel_hbm_bytes(op: str, shape: dict) -> float:
    e, n, d, ev, bn, bm, b, tiles, active = _kshape(shape)
    row_blocks = -(-n // bn)
    out = n * d * b
    if op == "segment_spmm":
        # each edge tile (msg + seg) re-read once per row block
        return row_blocks * e * (d * b + 4) + out
    if op == "segment_spmm_ragged":
        return active * bm * (d * b + 4) + 4 * tiles + out
    if op == "gat_softmax_aggregate":
        return e * (d * b + b + 4) + n * (d + 2) * 4
    if op == "segment_max":
        return e * (b + 4) + n * 4
    raise ValueError(f"unknown kernel op {op!r}")


def kernel_roofline(
    op: str, shape: dict, wall_s: float, device_kind: str, dtype: str = "f32"
) -> dict:
    """Achieved-vs-peak for one kernel wall-clock measured on a chip of
    ``device_kind`` (whose published peaks must be in ``mesh.PEAKS``).

    Peak FLOP/s follows the dtype (the MXU's f32 rate is half its bf16
    rate); ``bound`` names the limiting resource at these shapes and
    ``frac_of_*`` are the hardware fractions ``benchmarks/kernels.py``
    reports."""
    hw = peaks(device_kind)
    fl = kernel_flops(op, shape)
    by = kernel_hbm_bytes(op, shape)
    peak = hw["peak_flops_bf16"] * (0.5 if dtype in ("f32", "float32") else 1.0)
    compute_s = fl / peak
    memory_s = by / hw["hbm_bw"]
    bound_s = max(compute_s, memory_s)
    achieved_flops = fl / wall_s if wall_s > 0 else 0.0
    achieved_bw = by / wall_s if wall_s > 0 else 0.0
    return {
        "op": op,
        "flops": fl,
        "hbm_bytes": by,
        "arithmetic_intensity": fl / by if by else 0.0,
        "compute_s": compute_s,
        "memory_s": memory_s,
        "bound_s": bound_s,
        "bound": "compute" if compute_s >= memory_s else "memory",
        "wall_s": wall_s,
        "achieved_flops_per_s": achieved_flops,
        "frac_of_peak_flops": achieved_flops / peak if peak else 0.0,
        "achieved_bytes_per_s": achieved_bw,
        "frac_of_hbm_bw": achieved_bw / hw["hbm_bw"],
        "frac_of_bound": bound_s / wall_s if wall_s > 0 else 0.0,
    }


# ---------------------------------------------------------------------------
# combined roofline
# ---------------------------------------------------------------------------


def roofline(
    cfg: ArchConfig,
    shape_name: str,
    mesh_shape: dict,
    num_chips: int,
    cost: dict,
    coll: dict,
    device_kind: str,
) -> dict:
    """Roofline terms per chip of ``device_kind`` (an entry of
    ``mesh.PEAKS``)."""
    hw = peaks(device_kind)
    fl = analytic_flops(cfg, shape_name)
    flops_dev = fl["total"] / num_chips
    hlo_flops_dev = float(cost.get("flops", 0.0))
    hlo_bytes_dev = float(cost.get("bytes accessed", 0.0))
    analytic_bytes = analytic_hbm_bytes(cfg, shape_name, mesh_shape)
    bytes_dev = max(hlo_bytes_dev, analytic_bytes)
    compute_s = flops_dev / hw["peak_flops_bf16"]
    memory_s = bytes_dev / hw["hbm_bw"]
    collective_s = coll["total_bytes"] / hw["ici_bw"]
    terms = {"compute_s": compute_s, "memory_s": memory_s, "collective_s": collective_s}
    dominant = max(terms, key=terms.get)
    return {
        **terms,
        "dominant": dominant,
        "step_time_bound_s": max(terms.values()),
        "analytic_flops_global": fl["total"],
        "model_flops_6nd_global": fl["6nd"],
        "useful_flops_ratio": fl["6nd"] / fl["total"] if fl["total"] else 0.0,
        "hlo_flops_per_device_raw": hlo_flops_dev,
        "hlo_bytes_per_device_raw": hlo_bytes_dev,
        "analytic_bytes_per_device": analytic_bytes,
        "collective_bytes_per_device": coll["total_bytes"],
    }

"""Launch layer: sharding rules, input specs, roofline parsing.  These run on
1 CPU device — the full 512-device lowering is exercised by dryrun.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.launch.roofline import analytic_flops, parse_collectives
from repro.launch.specs import SHAPES, input_specs, params_shapes, resolve_config


class FakeMesh:
    """Just enough of a Mesh for the sharding rule functions."""

    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_specs_cover_all_leaves(arch):
    from repro.launch.shardings import param_specs

    cfg = get_config(arch)
    shapes = params_shapes(cfg)
    mesh = FakeMesh({"data": 16, "model": 16})
    specs = param_specs(cfg, shapes, mesh)
    flat_shapes = jax.tree.leaves(shapes)
    flat_specs = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_shapes) == len(flat_specs)
    # every sharded dim must divide
    for sh, sp in zip(flat_shapes, flat_specs):
        for dim, axis in zip(sh.shape, tuple(sp) + (None,) * 8):
            if axis == "model":
                assert dim % 16 == 0, (arch, sh.shape, sp)


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_input_specs_and_flops(arch, shape):
    cfg0 = get_config(arch)
    cfg = resolve_config(cfg0, shape)
    assert cfg is not None  # no skipped combinations in this pool
    ins = input_specs(cfg, shape)
    sh = SHAPES[shape]
    b = sh["batch"]
    s = sh["seq"] if sh["kind"] != "decode" else 1
    assert ins["inputs"].shape[0] == b
    assert ins["inputs"].shape[1] == s
    fl = analytic_flops(cfg, shape)
    assert fl["total"] > 0
    # 6ND cross-check within a loose band for token-input training shapes
    if sh["kind"] == "train" and cfg.input_mode == "tokens":
        ratio = fl["total"] / fl["6nd"]
        assert 0.5 < ratio < 6.0, (arch, shape, ratio)


def test_parse_collectives_with_while_loop():
    hlo = """
HloModule test

%body (p: (s32[], f32[128])) -> (s32[], f32[128]) {
  %p = (s32[], f32[128]) parameter(0)
  %ar = f32[128]{0} all-reduce(%x), replica_groups={}
  ROOT %t = (s32[], f32[128]) tuple(%i, %ar)
}

%cond (p: (s32[], f32[128])) -> pred[] {
  %p = (s32[], f32[128]) parameter(0)
  %c = s32[] constant(24)
  ROOT %cmp = pred[] compare(%i, %c), direction=LT
}

ENTRY %main (a: f32[128]) -> f32[128] {
  %ag = f32[256]{0} all-gather(%a), dimensions={0}
  %w = (s32[], f32[128]) while(%init), condition=%cond, body=%body
  ROOT %r = f32[128] get-tuple-element(%w), index=1
}
"""
    out = parse_collectives(hlo)
    assert out["counts"]["all-gather"] == 1
    assert out["counts"]["all-reduce"] == 24  # multiplied by trip count
    assert out["bytes"]["all-reduce"] == 24 * 128 * 4
    assert out["bytes"]["all-gather"] == 256 * 4


def test_mesh_constants():
    from repro.launch.mesh import PRODUCTION_DEVICE_KIND, peaks

    hw = peaks(PRODUCTION_DEVICE_KIND)
    assert hw["peak_flops_bf16"] == 197e12
    assert hw["hbm_bw"] == 819e9
    with pytest.raises(ValueError, match="no published peak rates"):
        peaks("cpu")


def test_long_context_resolution():
    cfg = get_config("gemma-2b")
    lc = resolve_config(cfg, "long_500k")
    assert lc.window == cfg.long_context_window  # windowed variant
    native = resolve_config(get_config("mamba2-130m"), "long_500k")
    assert native.window == 0  # unchanged: natively sub-quadratic


def test_pad_heads_for_mesh():
    from repro.launch.specs import pad_heads_for_mesh

    cfg = get_config("llava-next-34b")  # 56H, kv=8
    padded = pad_heads_for_mesh(cfg, 16)
    assert (padded.padded_q_heads, padded.padded_kv_heads) == (64, 8)
    cfg = get_config("internlm2-1.8b")  # 16H kv=8: already tiles (no pad)
    padded = pad_heads_for_mesh(cfg, 16)
    assert padded.q_head_pad == 0 and padded.tp_size == 16
    cfg = get_config("gemma-2b")  # 8H kv=1: pad ratio 2.0 > 1.5 -> skipped
    padded = pad_heads_for_mesh(cfg, 16)
    assert padded.q_head_pad == 0
    cfg = get_config("musicgen-medium")  # 24H MHA -> pad to 32/32
    padded = pad_heads_for_mesh(cfg, 16)
    assert (padded.padded_q_heads, padded.padded_kv_heads) == (32, 32)


def test_moe_grouped_dispatch_matches_global_routing_shape():
    """Grouped dispatch preserves shapes/finite outputs (semantics differ by
    per-group capacity, which is the point)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.models.transformer.config import ArchConfig, MoEConfig
    from repro.models.transformer.model import forward, init_params

    cfg = ArchConfig(name="gm", family="moe", num_layers=2, d_model=64,
                     num_heads=4, num_kv_heads=2, d_ff=0, vocab_size=256,
                     moe=MoEConfig(num_experts=4, top_k=2, expert_d_ff=64,
                                   capacity_factor=2.0),
                     dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    inp = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 256)
    lg1, aux1, _ = forward(params, cfg, inp)
    cfg2 = dataclasses.replace(cfg, moe_dispatch_groups=4)
    lg2, aux2, _ = forward(params, cfg2, inp)
    assert lg1.shape == lg2.shape
    assert bool(jnp.isfinite(lg2).all()) and float(aux2) > 0

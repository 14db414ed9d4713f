"""Compile checks for a described (not attached) TPU v5e chip.

Every Pallas kernel the GNN dispatchers in ``repro.kernels.ops`` reach is
compiled with ``interpret=False`` at engine bucket sizes, one f32 and one
bf16 case each, and so is the jitted sage training step at the
``sage-products`` widths.  What the chip's compiler refuses here (an
unaligned block, a vector layout Mosaic cannot lower, a kernel over its
scoped VMEM) fails the test at no chip time.  Nothing runs: a compile
that passes says nothing about results or speed.

The topology is described inside a fixture, never at import, because only
one process at a time may load the TPU library and every test worker
imports this file.  The persistent compilation cache stays off here: a
compile for a described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_gnn import (
    gat_softmax_aggregate_pallas,
    segment_max_pallas,
    segment_spmm_ragged_pallas,
)
from repro.kernels.segment_spmm import segment_spmm_pallas

# (edges, vertex bucket, width, dtype): the engine pads a layer slice to a
# power-of-two edge bucket and at most infer_batch_size = 4096 vertices
BUCKETS = [(16384, 4096, 256, jnp.float32), (65536, 4096, 128, jnp.bfloat16)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_args(kernel, sharding, e, n, d, dtype):
    seg = _spec(sharding, (e,), jnp.int32)
    vec = _spec(sharding, (e,), dtype)
    msg = _spec(sharding, (e, d), dtype)
    return {
        "segment_spmm_ragged": (segment_spmm_ragged_pallas, (msg, seg)),
        "segment_spmm": (segment_spmm_pallas, (msg, seg)),
        "gat_softmax_aggregate": (gat_softmax_aggregate_pallas, (vec, msg, seg)),
        "segment_max": (segment_max_pallas, (vec, seg)),
    }[kernel]


@pytest.mark.parametrize(
    "e,n,d,dtype", BUCKETS, ids=[f"{b[0]}x{b[1]}x{b[2]}-{b[3].__name__}" for b in BUCKETS]
)
@pytest.mark.parametrize(
    "kernel",
    ["segment_spmm_ragged", "segment_spmm", "gat_softmax_aggregate", "segment_max"],
)
def test_gnn_kernel_compiles_for_v5e(one_chip, kernel, e, n, d, dtype):
    fn, args = _kernel_args(kernel, one_chip, e, n, d, dtype)
    compiled = fn.lower(*args, n, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sage_train_step_compiles_for_v5e(one_chip):
    """GNNTrainer._step for sage at hidden 256, 3 layers, 64-wide features,
    batch 256, on a padded batch of 10,240 vertices whose layer edge lists
    hold the three hops of fanouts 15/10/5."""
    from repro.api import GLISPConfig, GLISPSystem
    from repro.graph import power_law_graph
    from repro.models.gnn import GNNModel
    from repro.models.gnn.batching import GNNBatch

    g = power_law_graph(600, avg_degree=6, seed=0, feat_dim=64, num_classes=16)
    system = GLISPSystem.build(g, GLISPConfig(num_parts=2, fanouts=(15, 10, 5)))
    model = GNNModel("sage", 64, hidden=256, num_layers=3, num_classes=16)
    trainer = system.trainer(model, np.arange(256), prefetch=0)

    v, b = 10240, 256
    edges = [81920, 40960, 4096]  # layer k aggregates hops 0..2-k
    spec = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    batch = GNNBatch(
        feats=spec((v, 64), jnp.float32),
        valid=spec((v,), jnp.bool_),
        seed_pos=spec((b,), jnp.int32),
        labels=spec((b,), jnp.int32),
        layer_dst=[spec((e,), jnp.int32) for e in edges],
        layer_src=[spec((e,), jnp.int32) for e in edges],
        layer_etype=[spec((e,), jnp.int32) for e in edges],
        layer_cnt=[spec((v, 1), jnp.float32) for _ in edges],
    )
    shapes = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: spec(a.shape, a.dtype), tree
    )
    compiled = trainer._step.lower(
        shapes(trainer.params), shapes(trainer.opt_state), batch
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16e9  # one v5e chip holds 16 GB of HBM

"""Data-parallel GNN training over a ``jax.sharding`` mesh.

``DataParallelGNNTrainer`` finally wires the so-far-unused
``launch/mesh.py`` + ``launch/shardings.py`` machinery to training: the
train step runs with the batch sharded over the mesh's data axis and the
params/optimizer replicated, which on one host's ``make_local_mesh`` CPU
devices is the exact program a multi-host deployment runs per pod.

Layout per step, for a mesh with ``S``-way data parallelism:

- ``train_ids`` are dealt round-robin into ``S`` shard streams, each with
  its own sampling client (``BatchPipeline``) over the SAME shared
  backend — per-host sampling clients, one submission window each, with
  pipeline-owned request keys so every shard's batch stream is
  deterministic no matter how the service interleaves them;
- each step takes one padded batch per shard, pads them to a common
  bucket shape (:func:`stack_batches`) and stacks a leading shard axis;
- the stacked batch is ``device_put`` with ``PartitionSpec(data_axes)``
  on dim 0 — shard ``i``'s rows land on data-slice ``i`` — while params
  and optimizer state are replicated (``PartitionSpec()``);
- the jit'd step ``vmap``s the per-shard loss over the shard axis and
  takes the mean, so the gradient is the average of per-shard gradients
  and XLA inserts the cross-shard reduction itself.

``reference=True`` runs a second, unsharded single-device step (its own
params/optimizer replica, same init) on the very same stacked batches and
records its losses — benchmarks assert the sharded step matches it, which
is the acceptance check that data parallelism changed the placement and
nothing else.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.api.pipeline import BatchPipeline
from repro.launch.mesh import make_local_mesh
from repro.launch.shardings import data_axes
from repro.models.gnn.batching import GNNBatch
from repro.train.optim import AdamWConfig, adamw_init, adamw_update

__all__ = ["DataParallelGNNTrainer", "DPTrainLog", "stack_batches"]

# per-shard pipeline seeds must differ (distinct seed permutations and
# request-key bases) but be derived from one trainer seed; a prime stride
# keeps them disjoint from the service's own replica seeding
_SHARD_SEED_STRIDE = 7919


@dataclass
class DPTrainLog:
    steps: list = field(default_factory=list)
    losses: list = field(default_factory=list)
    # single-device reference losses (reference=True), same positions
    ref_losses: list = field(default_factory=list)
    sample_time: float = 0.0
    compute_time: float = 0.0


def stack_batches(batches: list[GNNBatch]) -> GNNBatch:
    """Stack per-shard ``GNNBatch``es along a new leading shard axis.

    Shards sample independently, so their padded bucket shapes may
    differ; every array is first padded to the max bucket across shards
    using the batching pads (zero feature rows, ``valid=False``, edge
    positions ``-1``, edge type ``0``) — semantically inert by the same
    argument as the original padding.  Seed counts must match (the
    caller drops ragged tails); stacking never changes any shard's rows.
    """
    bs = {b.seed_pos.shape[0] for b in batches}
    if len(bs) != 1:
        raise ValueError(f"shards disagree on seeds per batch: {sorted(bs)}")
    vmax = max(b.feats.shape[0] for b in batches)
    num_layers = len(batches[0].layer_dst)
    emax = [
        max(b.layer_dst[k].shape[0] for b in batches)
        for k in range(num_layers)
    ]

    def pad0(arr, n, fill):
        if arr.shape[0] == n:
            return arr
        out = np.full((n,) + arr.shape[1:], fill, dtype=arr.dtype)
        out[: arr.shape[0]] = arr
        return out

    return GNNBatch(
        feats=np.stack([pad0(b.feats, vmax, 0.0) for b in batches]),
        valid=np.stack([pad0(b.valid, vmax, False) for b in batches]),
        seed_pos=np.stack([b.seed_pos for b in batches]),
        labels=np.stack([b.labels for b in batches]),
        layer_dst=[
            np.stack([pad0(b.layer_dst[k], emax[k], -1) for b in batches])
            for k in range(num_layers)
        ],
        layer_src=[
            np.stack([pad0(b.layer_src[k], emax[k], -1) for b in batches])
            for k in range(num_layers)
        ],
        layer_etype=[
            np.stack([pad0(b.layer_etype[k], emax[k], 0) for b in batches])
            for k in range(num_layers)
        ],
        # degree columns are per-vertex-row, so the vertex pad (zero
        # count) keeps them consistent with the -1-padded edge lists
        layer_cnt=(
            [
                np.stack([pad0(b.layer_cnt[k], vmax, 0.0) for b in batches])
                for k in range(num_layers)
            ]
            if all(b.layer_cnt is not None for b in batches)
            else None
        ),
        vtype=(
            np.stack([pad0(b.vtype, vmax, 0) for b in batches])
            if all(b.vtype is not None for b in batches)
            else None
        ),
    )


class DataParallelGNNTrainer:
    def __init__(
        self,
        model,
        backend,
        graph,
        train_ids: np.ndarray,
        *,
        mesh=None,
        spec=None,
        fanouts=None,
        batch_size: int = 256,  # GLOBAL batch: split evenly across shards
        opt: AdamWConfig | None = None,
        seed: int = 0,
        prefetch: int = 0,
        inflight: int = 1,
        vertex_quantum: int = 256,
        edge_quantum: int = 1024,
        ticket_timeout: float | None = None,
        reference: bool = False,
    ):
        if spec is None and fanouts is None:
            raise ValueError("pass a SamplingSpec or fanouts")
        self.model = model
        self.mesh = mesh if mesh is not None else make_local_mesh()
        da = data_axes(self.mesh)
        names = da if isinstance(da, tuple) else (da,)
        self.num_shards = int(np.prod([self.mesh.shape[a] for a in names]))
        if batch_size % self.num_shards != 0:
            raise ValueError(
                f"global batch_size {batch_size} must divide evenly over "
                f"{self.num_shards} data shard(s)"
            )
        self._batch_sharding = NamedSharding(self.mesh, P(da))
        self._replicated = NamedSharding(self.mesh, P())
        # one sampling client per shard over the SHARED backend; thread-mode
        # prefetch (the pool's channel fds must stay in this process, and
        # the shards' real parallelism is the remote workers / XLA anyway)
        self.pipelines = [
            BatchPipeline(
                backend,
                graph,
                np.asarray(train_ids)[i :: self.num_shards],
                list(spec.fanouts) if spec is not None else list(fanouts),
                model.num_layers,
                batch_size=batch_size // self.num_shards,
                spec=spec,
                prefetch=prefetch,
                inflight=inflight,
                workers="thread",
                seed=seed + _SHARD_SEED_STRIDE * i,
                vertex_quantum=vertex_quantum,
                edge_quantum=edge_quantum,
                ticket_timeout=ticket_timeout,
                vertex_types=model.reads_vertex_types,
            )
            for i in range(self.num_shards)
        ]
        self.opt_cfg = opt or AdamWConfig(lr=1e-3, weight_decay=1e-4)
        self.params = model.init(jax.random.PRNGKey(seed))
        self.opt_state = adamw_init(self.params)
        self.log = DPTrainLog()
        self.reference = reference

        def loss_fn(params, batch):
            # per-shard loss over the leading shard axis; the mean makes
            # the gradient the shard-average — textbook data parallelism
            return jax.vmap(lambda b: model.loss(params, b))(batch).mean()

        def step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            params, opt_state, _ = adamw_update(
                params, grads, opt_state, self.opt_cfg
            )
            return params, opt_state, loss

        self._step = jax.jit(step)
        if reference:
            # an independent jit instance: compiled for the unsharded
            # (single-device) input layout, with its own replica of the
            # same initial params/optimizer
            self._ref_step = jax.jit(step)
            self.ref_params = model.init(jax.random.PRNGKey(seed))
            self.ref_opt_state = adamw_init(self.ref_params)

    def _place(self) -> None:
        self.params = jax.device_put(self.params, self._replicated)
        self.opt_state = jax.device_put(self.opt_state, self._replicated)

    def train(
        self,
        epochs: int = 1,
        log_every: int = 10,
        max_steps: int | None = None,
    ) -> DPTrainLog:
        self._place()
        streams = [pl.batches(epochs) for pl in self.pipelines]
        step = 0
        try:
            while max_steps is None or step < max_steps:
                t0 = time.perf_counter()
                items = [next(s, None) for s in streams]
                if any(it is None for it in items):
                    break  # a shard ran dry: drop the ragged tail
                shard_batches = [
                    jax.tree.map(np.asarray, b) for _, b in items
                ]
                if len({b.seed_pos.shape[0] for b in shard_batches}) != 1:
                    break  # unequal final partial batches: ragged tail
                stacked = stack_batches(shard_batches)
                t1 = time.perf_counter()
                self.log.sample_time += t1 - t0
                sharded = jax.device_put(stacked, self._batch_sharding)
                self.params, self.opt_state, loss = self._step(
                    self.params, self.opt_state, sharded
                )
                loss = float(loss)
                self.log.compute_time += time.perf_counter() - t1
                if step % log_every == 0:
                    self.log.steps.append(step)
                    self.log.losses.append(loss)
                    if self.reference:
                        dev_batch = jax.tree.map(jnp.asarray, stacked)
                        self.ref_params, self.ref_opt_state, ref_loss = (
                            self._ref_step(
                                self.ref_params, self.ref_opt_state, dev_batch
                            )
                        )
                        self.log.ref_losses.append(float(ref_loss))
                step += 1
        finally:
            for s in streams:
                close = getattr(s, "close", None)
                if close is not None:
                    close()
        return self.log

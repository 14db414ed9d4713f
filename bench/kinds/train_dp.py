"""Traffic kind ``train_dp``: data-parallel sampled-minibatch training
through ``GLISPSystem.dp_trainer(...).train`` over a mesh of the cell's
chips, the way a user trains on one host with several chips.

Each step takes one seed batch per chip, each from its own shard of the
training seeds with its own batch pipeline over the shared sampler, and
runs one step sharded over the chips, the gradient averaged across them.
Set-up, warm-up, the window and the check are ``train.Cell``'s; what
differs is the trainer, its log's clocks, and the replay the reference
follows.

The replay.  ``DataParallelGNNTrainer`` has no checkpoint, and each of its
``train`` calls starts every shard's stream anew
(``BatchPipeline.batches`` runs ``_produce_np`` with ``skip=0``): the
shard's loader draws its next permutation, and its request keys go on from
where the call before stopped, which is past the batches that call's
thread-mode producer had sampled ahead and then dropped, a number that
depends on timing.  So the checked steps are two calls, one step and then
two, and before each the next request key of every shard is read; the
reference replays shard i's stream (``train_ids[i::S]``, shard seed
``seed + 7919 i``) from that key, in the permutation of that call, and
merges the four shards' blocks of a step into one.
"""
from __future__ import annotations

import numpy as np

import common
from kinds import train
from reference import train as ref_train

SHARD_SEED_STRIDE = 7919  # shard i's pipeline seed is seed + 7919 i (train/data_parallel.py)


class Cell(train.Cell):
    def __init__(self, spec: common.Spec, *args, **kwargs):
        self.shards = spec.chips
        super().__init__(spec, *args, **kwargs)
        self.call_keys: list = []  # per checked call, each shard's first request key

    def make_trainer(self, opt):
        import jax

        from repro.launch.mesh import make_local_mesh

        if len(jax.devices()) < self.shards:
            raise common.BenchError(f"{self.shards} devices needed, JAX finds {len(jax.devices())}")
        tr = self.system.dp_trainer(
            self.model, self.train_ids, mesh=make_local_mesh(self.shards), opt=opt,
            batch_size=self.batch_size, prefetch=self.traffic["prefetch"],
        )
        for pl in tr.pipelines:
            pl.inflight = self.traffic["inflight"]
        self.call_keys = [self.next_keys(tr)]
        return tr

    @staticmethod
    def next_keys(tr) -> list:
        """Each shard pipeline's next request index (``BatchPipeline`` has
        no public reading of it)."""
        return [pl._req_counter for pl in tr.pipelines]

    def pipelines(self) -> list:
        return self.trainer.pipelines

    def sample_time(self) -> float:
        """``DPTrainLog.sample_time``: the pull of every shard's batch and
        their stacking, on the trainer's clock."""
        return self.trainer.log.sample_time

    def between_checked_calls(self) -> int:
        self.call_keys.append(self.next_keys(self.trainer))
        return train.CHECKED_STEPS - 1

    def shard_stream(self, i: int, n: int, epoch: int = 0, first_key: int = 0):
        seed = self.cfg["system"].get("seed", 0) + SHARD_SEED_STRIDE * i
        return self.stream(self.train_ids[i :: self.shards], seed, n, epoch, first_key)

    def flops_per_step(self) -> float:
        """The operations of one batch of every shard: each shard's first
        batches, as ``train.Cell`` counts them, summed over the shards."""
        from flops import train_step_flops

        n = self.traffic["flops_batches"]
        return float(sum(
            np.mean([train_step_flops(sub, self.cfg) for _, sub in self.shard_stream(i, n)])
            for i in range(self.shards)
        ))

    def shard_blocks(self) -> list:
        """Per checked step, each shard's block: step 1 is the first call's
        first batch, steps 2..3 the second call's first two."""
        if not hasattr(self, "_shard_blocks"):
            s, m = self.cfg["system"], self.cfg["model"]
            steps = [[] for _ in range(train.CHECKED_STEPS)]
            calls = [(0, 0, 1), (1, 1, train.CHECKED_STEPS - 1)]  # (call, epoch, steps)
            for i in range(self.shards):
                t = 0
                for call, epoch, n in calls:
                    for seeds, sub in self.shard_stream(i, n, epoch, self.call_keys[call][i]):
                        ref_train.validate(sub, seeds, self.graph, s["fanouts"])
                        steps[t].append(ref_train.block(
                            sub, self.graph, m["num_layers"], s["vertex_quantum"], s["edge_quantum"]
                        ))
                        t += 1
            self._shard_blocks = steps
        return self._shard_blocks

    def reference_blocks(self, shards=None) -> list:
        """One merged block per checked step, of every shard or of those
        listed."""
        keep = range(self.shards) if shards is None else shards
        return [ref_train.merge([blocks[i] for i in keep]) for blocks in self.shard_blocks()]

    def faults(self, blocks: list) -> dict:
        """``train.Cell``'s, and the last shard's loss left out of the mean:
        the gradient averaged over the other chips only."""
        return dict(
            super().faults(blocks),
            dropped_shard=self.reference(self.reference_blocks(range(self.shards - 1))),
        )

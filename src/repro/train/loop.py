"""Training loops.

``GNNTrainer`` — the paper's end-to-end pipeline: the GLISP batch pipeline
(``repro.api.pipeline.BatchPipeline``) feeds padded minibatches into a jit'd
AdamW step (the Fig. 11 workload).  With ``prefetch >= 1`` host-side
sampling runs on a background thread and overlaps the device step.
``checkpoint_every > 0`` auto-saves an atomic checkpoint every N steps;
``resume()`` restores it and ``train()`` fast-forwards the (deterministic,
keyed) batch stream to the saved step, so a crashed-and-resumed run ends
with bit-identical weights to an uninterrupted one.
``LMTrainer`` — causal-LM training for the assigned architecture pool
(synthetic token stream), used by smoke tests and the quickstart.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.api.pipeline import BatchPipeline
from repro.core.sampling.service import DEFAULT_DIRECTION
from repro.data.graph_loader import SeedBatchLoader
from repro.data.tokens import SyntheticTokenStream
from repro.models.gnn.models import GNNModel
from repro.models.transformer.config import ArchConfig
from repro.models.transformer.model import forward, init_params, lm_loss
from repro.train.checkpoint import load_checkpoint, save_checkpoint
from repro.train.optim import AdamWConfig, adamw_init, adamw_update

__all__ = ["GNNTrainer", "LMTrainer"]


@dataclass
class TrainLog:
    steps: list = field(default_factory=list)
    losses: list = field(default_factory=list)
    sample_time: float = 0.0
    compute_time: float = 0.0


class GNNTrainer:
    def __init__(
        self,
        model: GNNModel,
        client,  # SamplerBackend, SamplingService, or a raw blocking client
        g,
        fanouts,
        train_ids: np.ndarray,
        batch_size: int = 256,
        opt: AdamWConfig | None = None,
        direction: str = DEFAULT_DIRECTION,
        seed: int = 0,
        weighted: bool = False,
        prefetch: int = 0,
        inflight: int = 1,  # in-flight sample requests on the service
        spec=None,  # SamplingSpec; overrides fanouts/weighted/direction
        partition_of: np.ndarray | None = None,
        balance_partitions: bool = False,
        feature_source=None,  # FeatureSource; None = g.vertex_feats
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 0,  # steps between auto-checkpoints; 0 = off
        ticket_timeout: float | None = None,
        worker_respawns: int = 1,
    ):
        self.model = model
        self.client = client
        self.g = g
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = int(checkpoint_every)
        if self.checkpoint_every > 0 and checkpoint_dir is None:
            raise ValueError("checkpoint_every > 0 requires a checkpoint_dir")
        self._resume_step = 0
        self.pipeline = BatchPipeline(
            client,
            g,
            train_ids,
            fanouts,
            model.num_layers,
            batch_size=batch_size,
            spec=spec,
            weighted=weighted,
            direction=direction,
            prefetch=prefetch,
            inflight=inflight,
            seed=seed,
            partition_of=partition_of,
            balance_partitions=balance_partitions,
            feature_source=feature_source,
            ticket_timeout=ticket_timeout,
            worker_respawns=worker_respawns,
            vertex_types=model.reads_vertex_types,
        )
        self.fanouts = self.pipeline.fanouts
        self.direction = self.pipeline.direction
        self.loader = self.pipeline.loader
        self.opt_cfg = opt or AdamWConfig(lr=1e-3, weight_decay=1e-4)
        self.params = model.init(jax.random.PRNGKey(seed))
        self.opt_state = adamw_init(self.params)
        self.log = TrainLog()

        def step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(model.loss)(params, batch)
            params, opt_state, info = adamw_update(
                params, grads, opt_state, self.opt_cfg
            )
            return params, opt_state, loss

        self._step = jax.jit(step)

        def acc_fn(params, batch):
            logits = model.apply(params, batch)
            return (jnp.argmax(logits, -1) == batch.labels).mean()

        self._acc = jax.jit(acc_fn)

    def make_batch(self, seeds):
        return self.pipeline.make_batch(seeds)

    # -- checkpoint / resume -------------------------------------------------
    @property
    def checkpoint_path(self) -> str:
        if self.checkpoint_dir is None:
            raise ValueError("trainer has no checkpoint_dir")
        return os.path.join(self.checkpoint_dir, "gnn_checkpoint.npz")

    def save(self, path: str | None = None, step: int = 0) -> str:
        """Atomic checkpoint of params + optimizer state (+ step)."""
        return save_checkpoint(
            path or self.checkpoint_path,
            {"params": self.params, "opt": self.opt_state},
            step,
        )

    def resume(self, path: str | None = None) -> int:
        """Restore the latest checkpoint; returns the restored step count.

        The next ``train()`` call fast-forwards its (deterministic, keyed)
        batch stream past the restored steps, so resuming reproduces the
        uninterrupted run bit-for-bit: the skipped batches are never
        recomputed, only their stream positions are consumed."""
        tree, step = load_checkpoint(
            path or self.checkpoint_path,
            {"params": self.params, "opt": self.opt_state},
        )
        self.params = tree["params"]
        self.opt_state = tree["opt"]
        self._resume_step = int(step or 0)
        return self._resume_step

    def train(
        self,
        epochs: int = 1,
        log_every: int = 10,
        max_steps: int | None = None,
    ):
        step = 0
        skip = self._resume_step  # batches already trained before resume()
        for i, _, batch in self.pipeline.indexed(epochs):
            if max_steps is not None and step >= max_steps:
                break
            if step < skip:
                # replay: consume the stream position without recomputing
                # (the batch itself is identical by keyed construction)
                step += 1
                continue
            t1 = time.perf_counter()
            # i is the batch's request index, which its producer spans carry
            with obs.span("train.step", i), obs.step("train", i):
                self.params, self.opt_state, loss = self._step(
                    self.params, self.opt_state, batch
                )
                loss = float(loss)
            t2 = time.perf_counter()
            self.log.compute_time += t2 - t1
            if step % log_every == 0:
                self.log.steps.append(step)
                self.log.losses.append(loss)
            step += 1
            if self.checkpoint_every and step % self.checkpoint_every == 0:
                self.save(step=step)
        self._resume_step = 0
        # producer-side host clock: equals the old serial sample_time when
        # prefetch=0; with prefetch it is the OVERLAPPED sampling time
        self.log.sample_time = self.pipeline.sample_time
        return self.log

    def evaluate(self, test_ids: np.ndarray, batches: int = 8) -> float:
        loader = SeedBatchLoader(test_ids, self.loader.batch, seed=123)
        accs = []
        for i, seeds in enumerate(loader.epoch()):
            if i >= batches:
                break
            batch = jax.tree.map(jnp.asarray, self.make_batch(seeds))
            accs.append(float(self._acc(self.params, batch)))
        return float(np.mean(accs)) if accs else 0.0


class LMTrainer:
    def __init__(
        self,
        cfg: ArchConfig,
        batch: int,
        seq_len: int,
        opt: AdamWConfig | None = None,
        seed: int = 0,
        remat: bool = True,
    ):
        self.cfg = cfg
        self.stream = SyntheticTokenStream(cfg.vocab_size, batch, seq_len, seed)
        self.opt_cfg = opt or AdamWConfig(lr=3e-4)
        self.params = init_params(cfg, jax.random.PRNGKey(seed))
        self.opt_state = adamw_init(self.params)
        self.log = TrainLog()

        def step(params, opt_state, inputs, targets):
            (loss, (nll, aux)), grads = jax.value_and_grad(
                lambda p: lm_loss(p, cfg, inputs, targets, remat=remat),
                has_aux=True,
            )(params)
            params, opt_state, info = adamw_update(params, grads, opt_state, self.opt_cfg)
            return params, opt_state, loss, nll

        self._step = jax.jit(step)

    def train(self, steps: int, log_every: int = 10):
        for s in range(steps):
            inp, tgt = self.stream.next_batch()
            t0 = time.perf_counter()
            self.params, self.opt_state, loss, nll = self._step(
                self.params, self.opt_state, jnp.asarray(inp), jnp.asarray(tgt)
            )
            nll = float(nll)
            self.log.compute_time += time.perf_counter() - t0
            if s % log_every == 0 or s == steps - 1:
                self.log.steps.append(s)
                self.log.losses.append(nll)
        return self.log

    def save(self, path: str, step: int = 0):
        save_checkpoint(path, {"params": self.params, "opt": self.opt_state}, step)

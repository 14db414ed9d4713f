"""Traffic kind ``train``: sampled-minibatch training through
``GLISPSystem.trainer(...).train``, the way a user trains.

Set-up builds one trainer and drives it through the checked first steps,
then through warm-up calls until one compiles nothing.  The
window is one ``train(max_steps=N)`` call on that same trainer, N set
from the warm-up's times so that it lasts about ``--seconds``.  Afterwards the
program's state is freed and the plain reference follows the first steps
(``reference/train.py``).
"""
from __future__ import annotations

import gc
import inspect
import math
import multiprocessing as mp
import threading

import jax
import jax.numpy as jnp
import numpy as np

import common
from reference import train as ref_train

CHECKED_STEPS = 3  # the steps the reference follows


def model_kwargs(cfg: dict, in_dim: int) -> dict:
    """``GNNModel``'s arguments: every key of the configuration's ``model``
    that its constructor names, the input width and the classes."""
    from repro.models.gnn import GNNModel

    named = inspect.signature(GNNModel).parameters
    kw = {k: v for k, v in cfg["model"].items() if k in named}
    return dict(kw, in_dim=in_dim, num_classes=cfg["graph"]["num_classes"])


def train_seeds(cfg: dict, graph, rng: np.random.Generator) -> np.ndarray:
    """The training seeds in the order of ``--seed``: a permutation of all
    vertices, or, where the configuration lists ``seed_vertex_types``, of
    the vertices of those types."""
    types = cfg.get("seed_vertex_types")
    if types is None:
        return rng.permutation(graph.num_vertices)
    return rng.permutation(np.flatnonzero(np.isin(graph.vertex_types, types)))


class Cell:
    shards = 1  # seed batches per step, one per chip of a data-parallel step

    def __init__(self, spec: common.Spec, seed: int, compiles: common.CompileCounter, built=None):
        self.spec = spec
        self.cfg = spec.config
        self.traffic = spec.traffic
        self.compiles = compiles
        self.key_seed, self.rng = common.run_seed_key(seed)
        self.window_stats: dict = {}
        self.built = built  # (graph, system) shared by several seeds in one process
        self.batch_size = self.cfg["system"]["batch_size"] * self.shards  # seeds per step

    # -- set-up -------------------------------------------------------------
    def setup(self) -> None:
        self.checked_steps()
        common.split_cores(mp.active_children())
        self.warm_up()

    def checked_steps(self) -> None:
        """Build the trainer and run the steps the reference follows."""
        from repro.models.gnn import GNNModel
        from repro.train.optim import AdamWConfig, adamw_init

        cfg = self.cfg
        t0 = common.now()
        self.graph, self.system = self.built or common.build_system(cfg)
        common.log(f"setup: graph and system {common.now() - t0:.1f}s")
        t0 = common.now()
        g = self.graph
        self.train_ids = train_seeds(cfg, g, self.rng)
        self.model = GNNModel(**model_kwargs(cfg, g.vertex_feats.shape[1]))
        tr = self.make_trainer(AdamWConfig(**cfg["optimizer"]))
        tr.params = jax.jit(self.model.init)(jax.random.PRNGKey(self.key_seed))
        tr.opt_state = jax.jit(adamw_init)(tr.params)
        self.trainer = tr
        start = jax.device_get(tr.params)

        # step 1, then the optimizer's state after it; then steps 2..3 in a
        # second call, on batches the reference can replay
        log = tr.train(epochs=1, max_steps=1, log_every=1)
        b1 = cfg["optimizer"]["b1"]
        first_grad = jax.tree.map(lambda m: np.asarray(m) / (1 - b1), jax.device_get(tr.opt_state["mu"]))
        max_steps = self.between_checked_calls()
        t1 = common.now()
        tr.train(epochs=1, max_steps=max_steps, log_every=1)
        self.checked_call_s = common.now() - t1
        self.prog = {
            "losses": list(log.losses[:CHECKED_STEPS]),
            "grad": first_grad,
            "start": start,
            "end": jax.device_get(tr.params),
        }
        common.log(f"setup: trainer and {CHECKED_STEPS} checked steps {common.now() - t0:.1f}s")

    def make_trainer(self, opt):
        tr = self.system.trainer(
            self.model, self.train_ids, opt=opt,
            prefetch=self.traffic["prefetch"], inflight=self.traffic["inflight"],
        )
        # system.trainer does not pass the configuration's padding quanta on
        tr.pipeline.vertex_quantum = self.cfg["system"]["vertex_quantum"]
        tr.pipeline.edge_quantum = self.cfg["system"]["edge_quantum"]
        return tr

    def pipelines(self) -> list:
        return [self.trainer.pipeline]

    def sample_time(self) -> float:
        """The producer's clock of sampling and batch build so far."""
        return self.trainer.pipeline.sample_time

    def between_checked_calls(self) -> int:
        """Steps 2..3 resume from a checkpoint on a fresh producer, so that
        their batches are the keyed stream's next ones, as the reference
        replays them.  Returns the second call's ``max_steps``, which
        counts the resumed step."""
        tr = self.trainer
        ckpt = common.CACHE / "checkpoints" / self.spec.workload["name"] / "step.npz"
        ckpt.parent.mkdir(parents=True, exist_ok=True)
        tr.save(str(ckpt), step=1)
        tr.pipeline.close()
        tr.resume(str(ckpt))
        tr.params, tr.opt_state = jax.device_put((tr.params, tr.opt_state))
        return CHECKED_STEPS

    def warm_up(self) -> None:
        """Calls of w steps until one compiles nothing.  The steps of the
        last one are timed as they finish: their steady interval, and what
        the call takes before its first step and after its last (the
        producer's first batch; the batch pulled past ``max_steps`` and the
        drain of those in flight), size the window to ``--seconds``.  w
        makes the call last about ``warm_seconds`` more than the last
        checked call, which pulled CHECKED_STEPS + 1 batches."""
        tr, pulled = self.trainer, CHECKED_STEPS + 1
        per_batch = self.checked_call_s / pulled  # an upper guess: it holds the fixed part
        w = max(pulled + 1, pulled + math.ceil(self.traffic["warm_seconds"] / per_batch) - 1)
        for call in range(1, self.traffic["max_warm_calls"] + 1):
            before = self.compiles.count
            t0, done = common.now(), self._step_times(w)
            t1 = common.now()
            compiled = self.compiles.count - before
            common.log(f"setup: warm-up {call}, {w} steps {t1 - t0:.1f}s, {compiled} compiles")
            if not compiled:
                break
        self.step_s = (done[-1] - done[0]) / (len(done) - 1)
        if self.step_s <= 0:  # steps quicker than the watch can tell apart
            self.step_s = (t1 - t0) / (w + 1)
        self.call_s = (done[0] - t0) + (t1 - done[-1])
        common.log(f"setup: a step every {self.step_s:.3f}s, {self.call_s:.2f}s of a call besides")

    def _step_times(self, steps: int) -> list:
        """One ``train`` call of ``steps`` steps; when each step finished, as
        a thread sees the trainer's log grow."""
        log, done, stop = self.trainer.log, [], threading.Event()
        n0 = len(log.losses)

        def watch():
            while not stop.wait(0.002):
                while len(done) < len(log.losses) - n0:
                    done.append(common.now())

        th = threading.Thread(target=watch, daemon=True)
        th.start()
        try:
            self.trainer.train(epochs=1, max_steps=steps, log_every=1)
        finally:
            stop.set()
            th.join()
        while len(done) < len(log.losses) - n0:
            done.append(common.now())
        return done

    # -- the window ---------------------------------------------------------
    def window(self, seconds: float) -> None:
        tr, log = self.trainer, self.trainer.log
        steps = max(CHECKED_STEPS, int(round((seconds - self.call_s) / self.step_s)) + 1)
        per_epoch = len(self.train_ids) // self.batch_size
        n_losses, compute0, sample0 = len(log.losses), log.compute_time, self.sample_time()
        t0 = common.now()
        tr.train(epochs=steps // per_epoch + 1, max_steps=steps, log_every=1)
        t1 = common.now()
        losses = np.asarray(log.losses[n_losses:])
        self.window_stats = {
            "window_s": t1 - t0,
            "steps": steps,
            "done": int(losses.size),
            "failed": int(np.sum(~np.isfinite(losses))) + steps - int(losses.size),
            "seeds": losses.size * self.batch_size,
            "compute_s": log.compute_time - compute0,
            "sample_s": log.sample_time - sample0,
        }

    def end_to_end(self) -> dict:
        w = self.window_stats
        return {"train_seeds_per_s": w["seeds"] / w["window_s"]}

    def readings(self) -> dict:
        """What the per-layer readers of a training cell read."""
        w = dict(self.window_stats)
        w["flops_per_step"] = self.flops_per_step()
        w["kind"] = "train"
        return w

    def flops_per_step(self) -> float:
        """The window's batches come from a stream position that depends
        on timing; the keyed stream's first batches have the same sizes in
        law, so the work per step is their mean."""
        from flops import train_step_flops

        subs = [sub for _, sub in self._replay(self.traffic["flops_batches"])]
        return float(np.mean([train_step_flops(s, self.cfg) for s in subs]))

    # -- the check ----------------------------------------------------------
    def release(self) -> None:
        """Free the program's state on the device before the reference."""
        for pl in self.pipelines():
            pl.close()
        self.trainer.params = self.trainer.opt_state = None
        gc.collect()

    def _replay(self, n: int):
        """The keyed batch stream's first ``n`` seed batches and samples: the
        seeds the trainer's loader draws first, each with its request key
        ``(seed, batch index)``."""
        seed = self.cfg["system"].get("seed", 0)
        return self.stream(self.train_ids, seed, n)

    def stream(self, ids: np.ndarray, seed: int, n: int, epoch: int = 0, first_key: int = 0):
        """A batch pipeline's keyed stream, replayed: seed batches 0..n-1
        of the ``epoch``-th permutation its loader (over ``ids``, from
        ``seed``) draws, batch j sampled with the request key
        ``(seed, first_key + j)``."""
        from repro.data.graph_loader import SeedBatchLoader

        loader = SeedBatchLoader(ids, self.cfg["system"]["batch_size"], seed=seed)
        for _ in range(epoch):
            next(loader.epoch())  # draws that epoch's permutation
        for j, seeds in zip(range(n), loader.epoch()):
            yield seeds, self.system.sample(seeds, key=(seed, first_key + j))

    def reference_blocks(self) -> list:
        s, m = self.cfg["system"], self.cfg["model"]
        blocks = []
        for seeds, sub in self._replay(CHECKED_STEPS):
            ref_train.validate(sub, seeds, self.graph, s["fanouts"])
            blocks.append(ref_train.block(sub, self.graph, m["num_layers"], s["vertex_quantum"], s["edge_quantum"]))
        return blocks

    def reference(self, blocks: list, dtype=jnp.float32, half_batch: bool = False) -> dict:
        m = self.cfg["model"]
        return ref_train.run(
            m["kind"], m, self.cfg["optimizer"], self.key_seed, self.graph.vertex_feats.shape[1],
            self.cfg["graph"]["num_classes"], blocks, dtype=dtype, half_batch=half_batch,
        )

    def faults(self, blocks: list) -> dict:
        """What the control and each planted fault give in the program's
        place, for the upper readings of the limits (``calibrate.py``): the
        reference wholly in bfloat16, and with half the batch left out of
        the loss."""
        return {
            "control": self.reference(blocks, dtype=jnp.bfloat16),
            "half_batch": self.reference(blocks, half_batch=True),
        }

    def check(self) -> dict:
        """Each compared number with its limit."""
        limits = self.cfg["limits"]
        try:
            gaps = ref_train.compare(self.prog, self.reference(self.reference_blocks()))
            common.log(f"change_gap leaves out (gradient over the median leaf's): {gaps['left_out']}")
        except ref_train.SampleError as e:
            print(f"sample check failed: {e}", flush=True)
            gaps = {k: float("inf") for k in limits}
        return {k: {"value": gaps[k], "limit": limits[k]} for k in limits}

    def close(self) -> None:
        if hasattr(self, "trainer"):
            for pl in self.pipelines():
                pl.close()
        if self.built is None and hasattr(self, "system"):
            self.system.close()

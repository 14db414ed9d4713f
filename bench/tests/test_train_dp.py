"""The data-parallel cell rehearsed on the CPU at a tiny size, on as many
virtual devices as it asks for chips: a sound run is correct, and the
check fails where the timed path is broken: the control (the reference in
bfloat16 in the program's place), a step that leaves its state unchanged,
a loss over half of each shard's batch, and one shard's loss left out of
the mean, as if its chip took no part in the exchange.

The cell is not in ``BENCHMARK.json`` yet (it has not run on four chips);
the rehearsal declares it in its own copy.  The rehearsals run in a child
process, whose device count is set before JAX starts there; run as a
script, this file is that child.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
CASES = ["sound", "control", "unchanged_state", "half_batch", "dropped_shard"]
# the data-parallel cell, as a later BENCHMARK.json would declare it
CELL = {
    "name": "sage-papers.train-dp4", "config": "sage-papers", "traffic": "train-dp4", "chips": 4,
    "why": "GraphSAGE data-parallel on 4 chips, 1024 seeds per chip from four thread-mode shard pipelines",
}


def _with_cell(bench_file: Path, out: Path) -> Path:
    """A copy of the benchmark that declares ``CELL`` where the one-chip
    cell of its configuration reports its end-to-end metrics."""
    b = json.loads(Path(bench_file).read_text())
    b["workloads"].append(CELL)
    for m in b["end_to_end"]:
        if "sage-papers.train" in m.get("workloads", []):
            m["workloads"].append(CELL["name"])
    path = out / "BENCHMARK.json"
    path.write_text(json.dumps(b))
    return path


@pytest.fixture(scope="module")
def rehearsals(tiny_bench, tmp_path_factory):
    """Each case's result line, per multi-chip cell."""
    out = {}
    tiny_bench = _with_cell(tiny_bench, tmp_path_factory.mktemp("dp-bench"))
    b = json.loads(tiny_bench.read_text())
    for w in [w for w in b["workloads"] if w["chips"] > 1]:
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            XLA_FLAGS=f"{os.environ.get('XLA_FLAGS', '')} --xla_force_host_platform_device_count={w['chips']}",
            PYTHONPATH=os.pathsep.join([str(BENCH.parent / "src"), os.environ.get("PYTHONPATH", "")]),
        )
        cache = tmp_path_factory.mktemp("dp-cache")
        proc = subprocess.run(
            [sys.executable, __file__, str(tiny_bench), str(cache), w["name"]],
            env=env, capture_output=True, text=True, timeout=600,
        )
        assert proc.returncode == 0, proc.stderr[-4000:]
        lines = [json.loads(x) for x in proc.stdout.splitlines() if x.startswith("{")]
        out[w["name"]] = {r["case"]: r for r in lines}
    return out


def test_the_mix_names_the_data_parallel_kind():
    traffic = json.loads((BENCH / "traffic" / f"{CELL['traffic']}.json").read_text())
    assert traffic["kind"] == "train_dp" and (BENCH / "kinds" / "train_dp.py").is_file()


@pytest.mark.parametrize("case", CASES)
def test_rehearsal_on_virtual_devices(rehearsals, case):
    for cell, results in rehearsals.items():
        res = results[case]
        over = {k: c for k, c in res["checks"].items() if c["value"] > c["limit"]}
        if case == "sound":
            assert res["correct"] is True, (cell, res["checks"])
            assert res["failed"] == 0 and res["attempted"] >= 3
            assert res["device"]["count"] >= 4
            assert set(res["metrics"]) == {"train_seeds_per_s", "setup_s"}
        else:
            assert res["correct"] is False and over, (cell, case, res["checks"])
        if case == "unchanged_state":
            assert res["checks"]["grad_gap"]["value"] == pytest.approx(1.0)
            assert res["checks"]["change_gap"]["value"] == pytest.approx(1.0)


# -- the child process ---------------------------------------------------------


def _plant(case: str, kind) -> list:
    """Break the timed path underneath the run for one case; returns what
    was replaced, as ``(owner, name, original)``."""
    import jax
    import jax.numpy as jnp

    import repro.train.data_parallel as dp
    from repro.models.gnn import GNNModel

    if case == "control":
        real = kind.Cell.checked_steps

        def checked_steps(self):
            real(self)
            self.prog = self.reference(self.reference_blocks(), dtype=jnp.bfloat16)

        owner, name, new = kind.Cell, "checked_steps", checked_steps
    elif case == "unchanged_state":
        owner, name, new = dp, "adamw_update", lambda p, g, s, cfg: (p, s, {})
    elif case == "half_batch":
        def half_loss(self, params, batch):
            logits = self.apply(params, batch)
            half = logits.shape[0] // 2
            logits, labels = logits[:half], batch.labels[:half]
            logz = jax.nn.logsumexp(logits, axis=-1)
            return jnp.mean(logz - jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0])

        owner, name, new = GNNModel, "loss", half_loss
    elif case == "dropped_shard":
        real_init = dp.DataParallelGNNTrainer.__init__

        def init(self, model, *args, **kwargs):
            real_init(self, model, *args, **kwargs)

            def step(params, opt_state, batch):
                def loss_fn(p):
                    losses = jax.vmap(lambda b: model.loss(p, b))(batch)
                    kept = jnp.arange(losses.shape[0]) < losses.shape[0] - 1
                    return jnp.sum(jnp.where(kept, losses, 0.0)) / (losses.shape[0] - 1)

                loss, grads = jax.value_and_grad(loss_fn)(params)
                params, opt_state, _ = dp.adamw_update(params, grads, opt_state, self.opt_cfg)
                return params, opt_state, loss

            self._step = jax.jit(step)

        owner, name, new = dp.DataParallelGNNTrainer, "__init__", init
    else:
        return []
    old = getattr(owner, name)
    setattr(owner, name, new)
    return [(owner, name, old)]


def _child(bench_file: str, cache: str, cell: str) -> None:
    sys.path.insert(0, str(BENCH))
    import common
    import run

    common.CACHE = Path(cache)
    kind = common.Spec(cell, Path(bench_file)).kind()
    common.Spec.kind = lambda self: kind
    for case in CASES:
        planted = _plant(case, kind)
        try:
            res = run.run_cell(cell, 2**31 + 4321, 1.0, False, bench_file=Path(bench_file),
                               require_accelerator=False, t_start=common.now())
        finally:
            for owner, name, old in planted:
                setattr(owner, name, old)
        res["checks"] = {k: {kk: float(vv) for kk, vv in c.items()} for k, c in res["checks"].items()}
        print(json.dumps(dict(res, case=case)), flush=True)


if __name__ == "__main__":
    _child(*sys.argv[1:4])

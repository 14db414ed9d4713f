"""The harness finds every cell's files by name, rehearses each one-chip
cell on the CPU at a tiny size, and its check fails where the timed path
is broken: the control (the reference in bfloat16 in the program's
place), a step that leaves its state unchanged, one leaf left unmoved,
and a loss over half the batch.  Cells on several chips are rehearsed on
as many virtual CPU devices, in a process of their own
(``test_train_dp.py``)."""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import common
import run

BENCHMARK = json.loads((common.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
# the rehearsals in this process, which has one CPU device
ONE_CHIP = [w["name"] for w in BENCHMARK["workloads"] if w["chips"] == 1]


@pytest.fixture(autouse=True)
def _cache(tmp_path_factory, monkeypatch):
    monkeypatch.setattr(common, "CACHE", tmp_path_factory.getbasetemp() / "bench-cache")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_found_by_name(cell):
    spec = common.Spec(cell)
    kind = spec.kind()
    assert hasattr(kind, "Cell")
    assert spec.end_to_end and any(m["name"] == "setup_s" for m in spec.end_to_end)
    assert spec.per_layer
    for m in spec.per_layer:
        assert callable(spec.reader(m["name"]))
    entry, cfg = spec.config_entry, spec.config
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert set(cfg["limits"]) == {"loss_gap", "grad_gap", "change_gap"}


def test_unknown_names_are_refused():
    with pytest.raises(common.BenchError, match="unknown workload"):
        common.Spec("no-such.cell")
    with pytest.raises(common.BenchError, match="no published peaks"):
        common.peak_flops("TPU v0 imaginary")


def test_cli_refuses_the_cpu(capsys):
    assert run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


def _rehearse(tiny_bench, cell, trace=False, seed=2**31 + 12345):
    return run.run_cell(
        cell, seed, 1.0, trace, bench_file=tiny_bench, require_accelerator=False,
        t_start=common.now(),
    )


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("cell", ONE_CHIP)
def test_rehearsal_on_cpu(tiny_bench, cell, trace):
    res = _rehearse(tiny_bench, cell, trace)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 3
    spec = common.Spec(cell, tiny_bench)
    if trace:
        # host clocks and counters only: no device metric from a CPU run
        assert set(res["metrics"]) == {
            m["name"] for m in spec.per_layer if m["source"] != "device_trace"
        }
        assert "busy_s" not in res["device"] and "breakdown" not in res
    else:
        assert set(res["metrics"]) == {m["name"] for m in spec.end_to_end}
        assert all(m["value"] > 0 for m in res["metrics"].values())
    for c in res["checks"].values():
        assert 0 <= c["value"] <= c["limit"]


def _plant(monkeypatch, after_steps):
    kind = common.Spec(CELLS[0]).kind()
    real = kind.Cell.checked_steps

    def checked_steps(self):
        real(self)
        after_steps(self)

    monkeypatch.setattr(kind.Cell, "checked_steps", checked_steps)
    monkeypatch.setattr(common.Spec, "kind", lambda self: kind)


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_control_is_not_correct(tiny_bench, monkeypatch, cell):
    def control(self):
        self.prog = self.reference(self.reference_blocks(), dtype=jnp.bfloat16)

    _plant(monkeypatch, control)
    res = _rehearse(tiny_bench, cell)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_unchanged_state_is_not_correct(tiny_bench, monkeypatch):
    import repro.train.loop as loop

    monkeypatch.setattr(loop, "adamw_update", lambda p, g, s, cfg: (p, s, {}))
    res = _rehearse(tiny_bench, CELLS[0])
    assert res["correct"] is False
    assert res["checks"]["grad_gap"]["value"] == pytest.approx(1.0)
    assert res["checks"]["change_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_unmoved_leaf_is_not_correct(tiny_bench, monkeypatch, cell):
    """The leaf with the smallest first gradient, left where it started,
    fails the check: the rule that leaves round-off leaves out of
    ``change_gap`` does not hide it."""
    def unmoved(self):
        flat = jax.tree_util.tree_flatten_with_path(self.prog["grad"])[0]
        norms = {jax.tree_util.keystr(p): float(np.linalg.norm(x)) for p, x in flat}
        smallest = min(norms, key=norms.get)
        self.prog["end"] = jax.tree_util.tree_map_with_path(
            lambda p, e, s: s if jax.tree_util.keystr(p) == smallest else e,
            self.prog["end"], self.prog["start"],
        )

    _plant(monkeypatch, unmoved)
    res = _rehearse(tiny_bench, cell)
    assert res["correct"] is False
    assert res["checks"]["change_gap"]["value"] > res["checks"]["change_gap"]["limit"]


def test_half_batch_is_not_correct(tiny_bench, monkeypatch):
    from repro.models.gnn import GNNModel

    def half_loss(self, params, batch):
        logits = self.apply(params, batch)
        half = logits.shape[0] // 2
        logits, labels = logits[:half], batch.labels[:half]
        logz = jnp.log(jnp.sum(jnp.exp(logits), axis=-1))
        return jnp.mean(logz - jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0])

    monkeypatch.setattr(GNNModel, "loss", half_loss)
    res = _rehearse(tiny_bench, CELLS[0])
    assert res["correct"] is False
    assert res["checks"]["loss_gap"]["value"] > res["checks"]["loss_gap"]["limit"]


def test_reference_rejects_a_forged_sample(tiny_bench):
    from reference import train as ref_train

    spec = common.Spec(CELLS[0], tiny_bench)
    g, system = common.build_system(spec.config)
    seeds = np.arange(32)
    sub = system.sample(seeds, key=(0, 0))
    ref_train.validate(sub, seeds, g, spec.config["system"]["fanouts"])
    sub.hops[1].dst = sub.hops[1].dst[::-1].copy()
    with pytest.raises(ref_train.SampleError):
        ref_train.validate(sub, seeds, g, spec.config["system"]["fanouts"])
    assert math.isinf(ref_train.compare(
        {"losses": [math.nan], "grad": {"w": np.ones(2)}, "start": {"w": np.zeros(2)}, "end": {"w": np.ones(2)}},
        {"losses": [1.0], "grad": {"w": np.ones(2)}, "start": {"w": np.zeros(2)}, "end": {"w": np.ones(2)}},
    )["loss_gap"])

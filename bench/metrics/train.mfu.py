"""The training window's share of the chip's peak: the operations its
steps need (``flops.train_step_flops`` on unpadded batch sizes) over the
window's time and the published bf16 peak of all the chips the step runs
on (``peak_flops``: one chip's peak times the cell's chips)."""


def read(r: dict):
    if r.get("kind") != "train" or not r.get("peak_flops") or not r.get("done"):
        return None
    return r["flops_per_step"] * r["done"] / (r["window_s"] * r["peak_flops"]) * 100.0

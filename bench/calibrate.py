"""Readings that the limits of a cell's check are set from.

    python3 bench/calibrate.py --workload <cell> --seeds <n,n,...> [--control-seeds <n,...>]

For each seed, in one process over one built system: the program's
compared numbers against the reference (the sound runs: their largest is
the lower reading of each limit), and for the control seeds the same
numbers of the control (the reference in bfloat16) and of the planted
faults the cell's kind names (``Cell.faults``: the reference with half the
batch left out of the loss; in a data-parallel cell also with one chip's
shard left out), the upper readings, beside the program's.  One JSON line
per seed and kind.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import common  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from reference import train as ref_train

    spec = common.Spec(args.workload)
    print(json.dumps({"device": common.device_info()}), flush=True)
    common.setup_compile_cache()
    compiles = common.CompileCounter()
    built = common.build_system(spec.config)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")] + sorted(controls):
        cell = spec.kind().Cell(spec, seed, compiles, built=built)
        try:
            cell.checked_steps()
            cell.release()
            blocks = cell.reference_blocks()
            ref = cell.reference(blocks)
            rows = {"program": cell.prog}
            if seed in controls:
                rows.update(cell.faults(blocks))
            for kind, got in rows.items():
                gaps = ref_train.compare(got, ref)
                print(json.dumps({"seed": seed, "kind": kind, **gaps, "losses": got["losses"], "ref_losses": ref["losses"]}), flush=True)
        finally:
            cell.close()
    built[1].close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("REPRO_EXTRA_XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production mesh WITHOUT real hardware, then extract the roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b \
        --shape train_4k --mesh single --out experiments/dryrun

The two XLA_FLAGS lines above MUST run before any other jax import — jax
locks the device count at first init (hence 512 host placeholder devices
exist only inside this process; tests and benches see 1).
"""
import argparse
import json
import re
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import PRODUCTION_DEVICE_KIND, make_production_mesh
from repro.launch.shardings import (
    batch_specs,
    cache_specs,
    data_axes,
    named,
    opt_state_specs,
    param_specs,
)
from repro.launch.specs import (
    SHAPES,
    cache_shapes,
    input_specs,
    make_decode_step,
    make_prefill_step,
    make_train_step,
    opt_shapes,
    params_shapes,
    resolve_config,
)
from jax.sharding import PartitionSpec as P

from repro.launch.roofline import parse_collectives, roofline

def run_one(arch: str, shape_name: str, multi_pod: bool, out_dir: str, verbose=True, unroll=False):
    cfg0 = get_config(arch)
    cfg = resolve_config(cfg0, shape_name, model_axis=16)
    if cfg is not None and cfg.moe is not None and cfg.moe.num_experts % 16:
        # grouped per-data-shard dispatch ONLY when experts don't divide the
        # model axis (mixtral 8/16): expert-divisible archs (deepseek 64/16)
        # get natural expert-parallel propagation from the sharded weights,
        # and the group constraints fight it (measured: 23s -> 155s coll).
        import dataclasses as _dc

        dsize = 32 if multi_pod else 16
        if SHAPES[shape_name]["batch"] * SHAPES[shape_name]["seq"] % dsize == 0:
            axes = ("pod", "data") if multi_pod else ("data",)
            cfg = _dc.replace(
                cfg, moe_dispatch_groups=dsize, data_axis_names=axes
            )
    if cfg is None:
        return {"arch": arch, "shape": shape_name, "skipped": True}
    mesh = make_production_mesh(multi_pod=multi_pod)
    num_chips = mesh.devices.size
    sh = SHAPES[shape_name]
    kind = sh["kind"]

    pshapes = params_shapes(cfg)
    pspecs = param_specs(cfg, pshapes, mesh)
    ins = input_specs(cfg, shape_name)
    bspecs = batch_specs(cfg, sh["batch"], mesh)

    t0 = time.perf_counter()
    with mesh:
        if kind == "train":
            step = make_train_step(cfg, unroll=unroll)
            oshapes = opt_shapes(cfg)
            ospecs = opt_state_specs(pspecs)
            metr_specs = {k: P() for k in ("loss", "nll", "aux", "lr", "grad_norm")}
            jitted = jax.jit(
                step,
                in_shardings=named(mesh, (pspecs, ospecs, {k: bspecs[k] for k in ins})),
                out_shardings=named(mesh, (pspecs, ospecs, metr_specs)),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(pshapes, oshapes, ins)
        else:
            cache_len = sh["seq"]
            cshapes = cache_shapes(cfg, sh["batch"], cache_len)
            cspecs = cache_specs(cfg, cshapes, mesh)
            logits_spec = P(None, "model") if cfg.vocab_size % mesh.shape["model"] == 0 else P(None, None)
            if kind == "prefill":
                step = make_prefill_step(cfg, unroll=unroll)
                jitted = jax.jit(
                    step,
                    in_shardings=named(
                        mesh, (pspecs, cspecs, {"inputs": bspecs["inputs"]})
                    ),
                    out_shardings=named(mesh, (logits_spec, cspecs)),
                    donate_argnums=(1,),
                )
                lowered = jitted.lower(pshapes, cshapes, {"inputs": ins["inputs"]})
            else:
                step = make_decode_step(cfg, unroll=unroll)
                jitted = jax.jit(
                    step,
                    in_shardings=named(
                        mesh,
                        (pspecs, cspecs, {"inputs": bspecs["inputs"]}, None),
                    ),
                    out_shardings=named(mesh, (logits_spec, cspecs)),
                    donate_argnums=(1,),
                )
                pos = jax.ShapeDtypeStruct((), jnp.int32)
                lowered = jitted.lower(pshapes, cshapes, {"inputs": ins["inputs"]}, pos)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = parse_collectives(compiled.as_text())
    rf = roofline(
        cfg, shape_name, dict(mesh.shape), num_chips, cost, coll,
        PRODUCTION_DEVICE_KIND,
    )

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "num_chips": int(num_chips),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "peak_bytes_per_device": int(
                mem.argument_size_in_bytes + mem.temp_size_in_bytes
            ),
        },
        "collectives": coll,
        "roofline": rf,
    }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch}_{shape_name}_{'multi' if multi_pod else 'single'}"
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(result, f, indent=2)
    if verbose:
        print(
            f"[dryrun] {arch} × {shape_name} × {result['mesh']}: "
            f"compile {t_compile:.1f}s | "
            f"mem/dev {result['memory']['peak_bytes_per_device']/2**30:.2f} GiB | "
            f"compute {rf['compute_s']*1e3:.2f} ms, memory {rf['memory_s']*1e3:.2f} ms, "
            f"collective {rf['collective_s']*1e3:.2f} ms -> {rf['dominant']}",
            flush=True,
        )
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", choices=list(SHAPES) + ["all"])
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--unroll", action="store_true",
                    help="fully unroll layer scans (accurate cost_analysis)")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    failures = []
    for a in archs:
        for s in shapes:
            for mp in meshes:
                try:
                    run_one(a, s, mp, args.out, unroll=args.unroll)
                except Exception as e:  # noqa: BLE001 — report and continue
                    failures.append((a, s, mp, repr(e)))
                    print(f"[dryrun] FAIL {a} × {s} × {'multi' if mp else 'single'}: {e}",
                          flush=True)
    if failures:
        print(f"\n{len(failures)} failures:")
        for f in failures:
            print("  ", f)
        sys.exit(1)
    print("\nAll dry-runs compiled successfully.")


if __name__ == "__main__":
    main()

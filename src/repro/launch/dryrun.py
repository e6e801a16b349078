import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("REPRO_EXTRA_XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count="
    + os.environ.get("REPRO_DRYRUN_DEVICES", "512")
).strip()

"""Multi-pod dry-run driver (deliverable e).

Lowers + compiles every requested (arch x input-shape x mesh) combination
against 512 forced host devices, records memory_analysis / cost_analysis /
collective bytes, and emits one JSON blob per combo for §Dry-run and
§Roofline. MUST set XLA_FLAGS before any other import (above) — jax locks
the device count at first init.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
      --shape train_4k --mesh single --out results/
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import pathlib
import time
import traceback

import jax
import numpy as np

# TPU v5e hardware model (targets; container runs the compiler only)
PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # bytes/s / chip
ICI_BW = 50e9  # bytes/s / link


def run_one(arch: str, shape: str, mesh_kind: str, out_dir: pathlib.Path,
            *, donate: bool = True) -> dict:
    from repro.configs import get_config, supported_shapes
    from repro.launch.hlo_stats import collective_stats, op_histogram
    from repro.launch.input_specs import build
    from repro.launch.mesh import make_production_mesh
    from repro.sharding.ctx import use_mesh

    def _write(rec):
        out_dir.mkdir(parents=True, exist_ok=True)
        fname = out_dir / f"{arch.replace('.', '_')}__{shape}__{mesh_kind}.json"
        fname.write_text(json.dumps(rec, indent=2, default=str))
        return rec

    cfg = get_config(arch)
    if shape not in supported_shapes(cfg):
        return _write({
            "arch": arch, "shape": shape, "mesh": mesh_kind,
            "status": "skipped",
            "reason": "shape unsupported for this family (DESIGN.md §4.2)",
        })

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = int(np.prod(mesh.devices.shape))
    t0 = time.perf_counter()
    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind, "chips": n_chips}
    try:
        with use_mesh(mesh):
            spec = build(arch, shape, mesh)
            jitted = jax.jit(
                spec.fn,
                in_shardings=spec.in_shardings,
                out_shardings=spec.out_shardings,
                donate_argnums=spec.donate_argnums if donate else (),
            )
            lowered = jitted.lower(*spec.args)
            t_lower = time.perf_counter() - t0
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0 - t_lower

            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
        coll = collective_stats(hlo)

        flops_total = float(cost.get("flops", 0.0))
        # cost_analysis flops are per-device under SPMD
        bytes_total = float(cost.get("bytes accessed", 0.0))
        coll_bytes_per_dev = coll["total_bytes"]

        compute_s = flops_total / PEAK_FLOPS
        memory_s = bytes_total / HBM_BW
        collective_s = coll_bytes_per_dev / ICI_BW
        terms = {"compute_s": compute_s, "memory_s": memory_s,
                 "collective_s": collective_s}
        dominant = max(terms, key=terms.get)

        rec.update({
            "status": "ok",
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "n_params": spec.n_params,
            "n_active_params": spec.n_active_params,
            "model_flops_global": spec.model_flops,
            "model_flops_per_chip": spec.model_flops / n_chips,
            "hlo_flops_per_chip": flops_total,
            "hlo_bytes_per_chip": bytes_total,
            "collective_bytes_per_chip": coll_bytes_per_dev,
            "collectives": coll["per_kind"],
            "roofline": {
                **{k: float(v) for k, v in terms.items()},
                "dominant": dominant,
                "useful_flops_ratio": (
                    spec.model_flops / n_chips / flops_total
                    if flops_total else None
                ),
            },
            "memory": {
                "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
                "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
                "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
                "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
                "peak_bytes_estimate": int(
                    getattr(mem, "argument_size_in_bytes", 0)
                    + getattr(mem, "output_size_in_bytes", 0)
                    + getattr(mem, "temp_size_in_bytes", 0)
                    - getattr(mem, "alias_size_in_bytes", 0)
                ),
            },
            "top_ops": op_histogram(hlo, top=15),
        })
    except Exception as e:  # noqa: BLE001 — a failed combo is a bug to record
        rec.update({
            "status": "error",
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        })
    return _write(rec)


def main() -> None:
    from repro.configs import _ALIASES, INPUT_SHAPES

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    out_dir = pathlib.Path(args.out)
    archs = list(_ALIASES) if (args.all or args.arch is None) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                t0 = time.perf_counter()
                rec = run_one(arch, shape, mesh_kind, out_dir)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f" dom={r['dominant']}"
                             f" comp={r['compute_s']:.3e}s"
                             f" mem={r['memory_s']:.3e}s"
                             f" coll={r['collective_s']:.3e}s")
                elif status == "error":
                    extra = " " + rec["error"][:160]
                print(f"[{time.perf_counter()-t0:7.1f}s] {arch:22s} {shape:12s} "
                      f"{mesh_kind:6s} {status}{extra}", flush=True)


if __name__ == "__main__":
    main()

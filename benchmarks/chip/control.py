"""Readings that the limits of ``correct`` are set from. Not part of a
benchmark run.

    python3 benchmarks/chip/control.py --workload <name> --seeds 1,2,3 \
        [--variants program,bf16,unchanged,half] [--rounds R] \
        [--control-seeds N]

After one set-up of the cell (data, problem), each seed gets one
``run_rounds`` call of ``R`` rounds under the seed a benchmark run would
drive (``harness.run_seed``), and for each variant one JSON line with
the numbers ``correct`` compares:

* ``program``: the call itself against the float32 reference (a sound
  run: its numbers set the lower reading);
* ``bf16``: the control, the reference computed in bfloat16 in the
  program's place;
* ``unchanged`` / ``half``: the reference in the program's place with a
  planted fault: a round that returns its state unchanged, or the
  aggregation over the first half of the clients only.
"""
import argparse
import json
import os
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent.parent / "src"), str(HERE.parent.parent)]

VARIANTS = ("program", "bf16", "unchanged", "half")


def readings(name: str, seeds, *, variants=VARIANTS, rounds: int = 0,
             control_seeds: "int | None" = None, sizes=None, out=None):
    """Yield one dict per (seed, variant); only the first
    ``control_seeds`` seeds (all by default) get the variants other than
    ``program``."""
    import jax
    import jax.numpy as jnp

    from benchmarks.chip import harness

    jax.config.update("jax_enable_x64", False)
    cell = harness.load_cell(name, sizes=sizes)
    harness.find_chips(cell.chips)
    harness._compile_cache()
    dev = jax.devices()[0]
    out = harness.ROOT / ".bench_out" if out is None else out
    records = out / f"{name}.control.jsonl"
    X, y = harness.make_data(cell.config)
    system = harness.build_system(cell, X, y)
    rounds = rounds or int(cell.traffic["compare_steps"]) + 2
    for i, seed in enumerate(seeds):
        seed_run = harness.run_seed(cell, seed, rounds)
        hist, t_ret = harness.run_call(system, rounds, seed_run, records)
        call = harness.read_call(hist, t_ret, records)
        for variant in variants:
            if (variant != "program" and control_seeds is not None
                    and i >= control_seeds):
                continue
            numbers = harness.check(
                cell, call, X, y, seed_run,
                dtype=jnp.bfloat16 if variant == "bf16" else None,
                fault=variant if variant in ("unchanged", "half") else None,
                per_round=True)
            yield {"workload": name, "seed": seed, "run_seed": seed_run,
                   "variant": variant,
                   "device": dev.device_kind, **numbers}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default=",".join(VARIANTS))
    ap.add_argument("--rounds", type=int, default=0)
    ap.add_argument("--precision", default=None,
                    help="JAX's default matmul precision for the program's "
                         "calls (default: JAX's own)")
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="how many of the seeds also get the control and "
                         "the faults (default: all)")
    args = ap.parse_args(argv)
    # the TPU runtime's logs stay inside the checkout
    os.environ.setdefault("TPU_LOG_DIR",
                          str(HERE.parent.parent / ".bench_out" / "tpu_logs"))
    if args.precision:
        import jax

        jax.config.update("jax_default_matmul_precision", args.precision)
    seeds = [int(s) % (1 << 32) for s in args.seeds.split(",")]
    variants = tuple(args.variants.split(","))
    for rec in readings(args.workload, seeds, variants=variants,
                        rounds=args.rounds,
                        control_seeds=args.control_seeds):
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Run one benchmark cell once on the chip this process finds.

    python3 benchmarks/chip/bench.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json`` at the root of the
checkout; its configuration, traffic mix, per-layer metrics and limits
are files under ``benchmarks/chip/`` found by name (see ``harness``).
The last line of standard output is the result as one JSON object; the
numbers that decide ``correct`` close standard error, each beside its
limit. With no TPU, fewer chips than the cell asks for, or a device
kind missing from ``peaks.json``, it exits non-zero and prints no
result.
"""
import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent.parent / "src"), str(HERE.parent.parent)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the TPU runtime's logs stay inside the checkout
    os.environ.setdefault("TPU_LOG_DIR",
                          str(HERE.parent.parent / ".bench_out" / "tpu_logs"))

    from benchmarks.chip import harness

    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())

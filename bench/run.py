"""The benchmark's one command: one run of one cell on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is a ``workloads`` entry of ``BENCHMARK.json``.  With ``--trace 0``
the last line of standard output is the result object with the cell's
end-to-end metrics; with ``--trace 1`` the timed window is traced and the
object carries the per-layer metrics, the device's busy and window seconds
and a breakdown of the trace.  Earlier lines are diagnostics: set-up in
parts, and the count of programs compiled inside the window.  The last lines
of standard error give each number the output check compared, beside its
limit.  With no TPU, too few chips, or a device kind that ``peaks.json``
lacks, it exits with code 2 before any phase and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    import harness
    cell = harness.load_cell(args.workload)
    import jax  # noqa: F401
    t_jax = time.perf_counter()
    try:
        harness.find_chip(cell.chips)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    print(f"start-up: imports {t_jax - T_PROCESS:.3f} s, TPU runtime start "
          f"{time.perf_counter() - t_jax:.3f} s", flush=True)
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t_process=T_PROCESS)
    checks = result.pop("checks")
    result["checks"] = checks            # the compared numbers come last
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

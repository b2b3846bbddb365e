"""The readings a cell's limits are set from (``limits/<cell>.json``), in
one process on the chip:

* the program: the cell's entry at its exact shapes over the checked
  rounds, against the reference, on each of ``--seeds`` (the lower
  readings);
* the control: the reference in bfloat16 put in the program's place,
  against the float32 reference, on the first ``--control`` seeds;
* the faults: the reference with each planted fault in the program's place
  (``unchanged``: theta never updated; ``half_batch``: half of every
  mini-batch left out; ``altered``: one validation loss raised by 1% where
  it is produced; ``wrong_pick``: the worst candidate selected), on the
  same seeds.

    python bench/calibrate.py --workload <cell> --seeds 101 102 ... --control 3

Prints one JSON line per reading and writes them all to
``chiprun_out/calibrate_<cell>.json``.
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

FAULTS = ("unchanged", "half_batch", "altered", "wrong_pick")


def readings_for(cell, seed, kinds, check_rounds, drive_program=None):
    """{kind: numbers} for one seed; kinds among 'program', 'control' and
    the fault names."""
    import check
    import gen
    import harness
    import reference
    data = gen.make_data(cell.kind, cell.cfg, seed)
    jobs = gen.make_jobs(cell.cfg, cell.traffic, seed)
    every = int(cell.traffic["eval_every"])
    out = {}
    for kind in kinds:
        t0 = time.perf_counter()
        if kind == "program":
            program = drive_program(data, jobs)
        else:
            dtype = "bfloat16" if kind == "control" else "float32"
            fault = None if kind == "control" else kind
            program = [reference.run(cell.kind, cell.cfg, data, job,
                                     check_rounds, every, dtype=dtype,
                                     fault=fault)
                       for job in jobs]
        refs = [reference.run(cell.kind, cell.cfg, data, job, check_rounds,
                              every, follow=[r["selected"] for r in prog])
                for job, prog in zip(jobs, program)]
        out[kind] = check.readings(program, refs,
                                   cell.kind.eval_units(data))
        out[kind]["seconds"] = time.perf_counter() - t0
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3,
                    help="seeds (the first ones) that also read the control "
                         "and the faults")
    ap.add_argument("--kinds", nargs="+", default=None,
                    help="read only these kinds (program, control, faults)")
    args = ap.parse_args()

    import harness
    cell = harness.load_cell(args.workload)
    try:
        harness.find_chip(cell.chips)
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    from repro.core import enable_compile_cache
    from repro.telemetry import Telemetry
    enable_compile_cache(str(harness.ROOT / ".jax-compile-cache"))
    module = cell.kind.build_module(cell.cfg)
    rounds = harness.warm_rounds(cell)

    def drive_program(data, jobs):
        hists = harness.drive(cell, module, harness._client_data(data), jobs,
                              rounds, Telemetry(spans=False))
        return [[dict(r) for r in h.rounds[:harness.CHECK_ROUNDS]]
                for h in hists]

    rows = []
    for i, seed in enumerate(args.seeds):
        kinds = ["program"]
        if i < args.control:
            kinds += ["control", *FAULTS]
        if args.kinds:
            kinds = [k for k in kinds if k in args.kinds]
        got = readings_for(cell, seed, kinds, harness.CHECK_ROUNDS,
                           drive_program)
        for kind, numbers in got.items():
            row = {"cell": cell.name, "seed": seed, "kind": kind, **numbers}
            rows.append(row)
            print(json.dumps(row), flush=True)
    out = harness.ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / f"calibrate_{cell.name}.json").write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

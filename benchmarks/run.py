"""Benchmark entrypoint: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]

Prints ``name,us_per_call,derived`` CSV rows (us_per_call = wall-us per
global protocol round; derived = headline metric) and writes full curves to
experiments/*.json.
"""
import argparse
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale Table II parameters (hours on CPU)")
    ap.add_argument("--only", default=None,
                    help="table1|fig3|fig4|fig5|ablation|roofline|robustness|"
                         "robustness_quant|pipeline|placements|fusion|pool")
    ap.add_argument("--compile-cache", default=None, metavar="DIR",
                    help="JAX's persistent compilation cache directory "
                         "($JAX_COMPILATION_CACHE_DIR wins when set; default "
                         ".jax-compile-cache/ in the checkout); repeated "
                         "grid cells and re-runs then load compiled round "
                         "programs from disk instead of re-compiling")
    ap.add_argument("--selection", default=None,
                    help="comma-separated selection policies for the "
                         "robustness matrix's policy axis (default: "
                         "argmin,loss_plus_distance)")
    ap.add_argument("--quant", default=None,
                    help="comma-separated cut-layer wire formats for the "
                         "robustness_quant matrix's format axis "
                         "(default: int8; e.g. int8,fp8_e4m3)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a JSONL telemetry trace (round spans + "
                         "per-round metrics + provenance) of the table1 "
                         "accounting runs to PATH")
    args = ap.parse_args()

    from repro.core import enable_compile_cache
    enable_compile_cache(args.compile_cache)

    telemetry = None
    if args.trace:
        if args.only not in (None, "table1"):
            ap.error("--trace only applies to the table1 accounting runs; "
                     f"it has no effect on --only {args.only}")
        from repro.telemetry import Telemetry
        telemetry = Telemetry(jsonl=args.trace, jit_stats=True)

    selections = None
    if args.selection:
        if args.only not in (None, "robustness", "robustness_quant"):
            ap.error("--selection only applies to the robustness matrices; "
                     f"it has no effect on --only {args.only}")
        from repro.selection import resolve_policy
        selections = tuple(s.strip() for s in args.selection.split(",") if s.strip())
        if not selections:
            ap.error(f"--selection {args.selection!r} parses to no policy names")
        for s in selections:
            resolve_policy(s)        # fail fast on typos, like --only

    formats = None
    if args.quant:
        if args.only not in (None, "robustness_quant"):
            ap.error("--quant only applies to the robustness_quant matrix; "
                     f"it has no effect on --only {args.only}")
        from repro.core import resolve_quant
        formats = tuple(q.strip() for q in args.quant.split(",") if q.strip())
        if not formats:
            ap.error(f"--quant {args.quant!r} parses to no format names")
        formats = tuple(resolve_quant(q) for q in formats)  # fail fast

    from . import (ablation_shared_set, fig3_mnist_attacks, fig4_cifar_attacks,
                   fig5_fig6_vary_n, job_throughput, pipeline_overlap,
                   placement_grid, robustness_matrix, roofline_report,
                   round_fusion, table1_overhead)

    benches = {
        "table1": lambda: table1_overhead.run(args.full, telemetry=telemetry),
        "fig3": lambda: fig3_mnist_attacks.run(args.full),
        "fig4": lambda: fig4_cifar_attacks.run(args.full),
        "fig5": lambda: fig5_fig6_vary_n.run(args.full),
        "ablation": lambda: ablation_shared_set.run(args.full),
        "roofline": lambda: roofline_report.run(markdown=False),
        "robustness": lambda: robustness_matrix.run(
            args.full, selections if selections is not None
            else robustness_matrix.DEFAULT_SELECTIONS),
        "robustness_quant": lambda: robustness_matrix.run_quant(
            args.full,
            selections if selections is not None
            else robustness_matrix.DEFAULT_SELECTIONS,
            formats if formats is not None
            else robustness_matrix.DEFAULT_QUANT_FORMATS),
        "pipeline": lambda: pipeline_overlap.run(args.full),
        "placements": lambda: placement_grid.run(args.full),
        "fusion": lambda: round_fusion.run(args.full),
        "pool": lambda: job_throughput.run(args.full),
    }
    if args.only and args.only not in benches:
        # an unknown name used to silently skip every benchmark and exit 0
        ap.error(f"--only {args.only!r} matches no benchmark; "
                 f"choose from {'|'.join(benches)}")
    print("name,us_per_call,derived")
    failures = []
    for name, fn in benches.items():
        if args.only and args.only != name:
            continue
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            failures.append(name)
            print(f"{name},0,ERROR:{type(e).__name__}:{e}")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"benchmarks failed: {failures}")


if __name__ == "__main__":
    main()

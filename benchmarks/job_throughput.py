"""Job-pool megabatching: J pooled jobs vs the serial per-job loop.

The production regime the ROADMAP targets is many concurrent *small* jobs.
Run serially, each job pays its own per-block dispatch, (K, 2R+3) host fetch
and host bookkeeping; the job pool (``repro.core.jobs.run_job_pool``) stacks
J compatible jobs onto a leading lane of ONE shared ``accept_block`` program
— one dispatch and one stacked (J, K, 2R+3) fetch per pool block — so the
overhead amortises J-fold on top of round-block fusion's K-fold.

Same measurement regime as ``round_fusion``: the tiny one-matmul-per-half
split MLP at E=1, B=4, where per-round wall time is dispatch/fetch/assembly
bound rather than FLOPs bound.  Every pooled job's History is asserted
bit-identical to its solo run before any timing is trusted, so the jobs/sec
column is a pure execution-schedule measurement.

Writes ``experiments/job_throughput.json`` with the throughput fields
(jobs/sec, rounds/sec, dispatches/round) from ``benchmarks.common``.
"""
from __future__ import annotations

import dataclasses
import gc

from repro.core import ProtocolConfig, run_pigeon
from repro.core.jobs import JobSpec, run_job_pool
from repro.core.protocol import ClientData
from repro.data import synthetic

from .common import RoundTimer, csv_row, save_result, throughput_fields
from .round_fusion import CLASSES, IMG, _assert_bit_identical, tiny_split_mlp


def _make_specs(module, data, n_jobs: int, t: int, m: int, n: int,
                seed0: int):
    specs = []
    for s in range(n_jobs):
        pcfg = ProtocolConfig(M=m, N=n, T=t, E=1, B=4, lr=0.03,
                              seed=seed0 + s, eval_every=10 * t)
        specs.append(JobSpec(name=f"job{s}", module=module, data=data,
                             pcfg=pcfg))
    return specs


def run(full: bool = False, seed: int = 0):
    m, n = 4, 1
    n_jobs = 12 if full else 8
    block = 2
    timed_rounds = 128 if full else 64
    repeats = 5
    d_m = 64

    arrs = synthetic.make_classification_data(seed, CLASSES, IMG, 1, m,
                                              d_m, 16, 32)
    x, y, x0, y0, xt, yt = arrs
    data = ClientData(x=x, y=y, x0=x0, y0=y0, x_test=xt, y_test=yt)
    module = tiny_split_mlp()
    specs = _make_specs(module, data, n_jobs, timed_rounds, m, n, seed)
    solo_kw = dict(engine="batched", placement="vmap", block=block)

    # correctness first: every pooled job's History == its solo run
    pooled = run_job_pool(specs, block=block)
    solos = {}
    for s in specs:
        solos[s.name] = run_pigeon(s.module, s.data, s.pcfg, **solo_kw)
        _assert_bit_identical(solos[s.name], pooled[s.name],
                              f"pool_vs_solo_{s.name}")

    # compile warmup for both paths at the timed shapes (T=2*block keeps the
    # warm run to two blocks while hitting every (J, K) signature)
    warm_specs = [dataclasses.replace(s, pcfg=dataclasses.replace(
        s.pcfg, T=2 * block)) for s in specs]
    run_job_pool(warm_specs, block=block)
    for s in warm_specs:
        run_pigeon(s.module, s.data, s.pcfg, **solo_kw)

    best_serial = float("inf")
    best_pool = float("inf")
    gc.collect()
    gc.disable()
    try:
        for _ in range(repeats):
            with RoundTimer() as timer:
                for s in specs:
                    run_pigeon(s.module, s.data, s.pcfg, **solo_kw)
            best_serial = min(best_serial, timer.elapsed)
            with RoundTimer() as timer:
                run_job_pool(specs, block=block)
            best_pool = min(best_pool, timer.elapsed)
    finally:
        gc.enable()

    total_rounds = n_jobs * timed_rounds
    blocks_per_job = -(-timed_rounds // block)          # ceil
    serial = dict(
        wall_s=best_serial,
        **throughput_fields(best_serial, total_rounds, n_jobs,
                            dispatches=n_jobs * blocks_per_job))
    pool = dict(
        wall_s=best_pool,
        **throughput_fields(best_pool, total_rounds, n_jobs,
                            dispatches=blocks_per_job))
    speedup = serial["wall_s"] / pool["wall_s"]

    csv_row("job_throughput_serial", best_serial / total_rounds * 1e6,
            f"jobs_per_sec={serial['jobs_per_sec']:.2f}")
    csv_row("job_throughput_pool", best_pool / total_rounds * 1e6,
            f"jobs_per_sec={pool['jobs_per_sec']:.2f} "
            f"speedup={speedup:.2f}x")

    out = {"params": dict(n_jobs=n_jobs, block=block, T=timed_rounds,
                          M=m, N=n, E=1, B=4, d_m=d_m, img=IMG,
                          repeats=repeats, placement="vmap"),
           "bit_identical": True,
           "rows": {"serial": serial, "pool": pool},
           "speedup": speedup}
    save_result("job_throughput", out)
    return out


if __name__ == "__main__":
    from .common import main
    main(run)

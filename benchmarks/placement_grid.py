"""Placement grid: every protocol driver x every device placement.

PR 3 gave ``run_pigeon`` a placement-aware RoundRunner; this PR extends the
same bindings to ``run_splitfed`` (FedAvg-within-cluster as the RoundSpec
``combine`` hook) and ``run_pigeon_sweep`` (S x R replicas over a 2-D
``(seed, pod)`` mesh).  This benchmark times one full protocol run per
(driver, placement) cell — pigeon / splitfed under vmap vs sharded (plus the
prefetch pipeline), and the multi-seed sweep under vmap vs the 2-D sharded
placement — and writes ``experiments/placement_grid.json``.

On the CPU container the sharded cells collapse to a 1-device mesh unless
run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``, so the
interesting single-host readout is the *overhead* of the shard_map plumbing
relative to vmap; on a real pod mesh the same cells scale out.
"""
from __future__ import annotations

import dataclasses
import time

from repro.core import (ProtocolConfig, from_cnn, run_pigeon,
                        run_pigeon_sweep, run_splitfed)
from repro.data import build_image_task

from .common import csv_row, save_result


def _time_best(fn, t_rounds: int, repeats: int) -> float:
    """Best-of-N wall-ms per protocol round (vs scheduler noise)."""
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, (time.perf_counter() - t0) / t_rounds * 1e3)
    return best


def run(full: bool = False, seed: int = 0):
    m = 8
    d_m = 400 if not full else 2000
    data, cnn_cfg = build_image_task("mnist", m_clients=m, d_m=d_m, d_o=64,
                                     n_test=32, seed=seed)
    module = from_cnn(cnn_cfg)
    t_rounds = 6 if not full else 20
    repeats = 3
    pcfg = ProtocolConfig(M=m, N=3, T=t_rounds, E=2, B=32, lr=0.03, seed=seed,
                          eval_every=10 * t_rounds)
    warm = dataclasses.replace(pcfg, T=1)
    seeds = (0, 1)

    cells = {}
    for name, runner in (("pigeon", run_pigeon), ("splitfed", run_splitfed)):
        for placement, prefetch in (("vmap", 0), ("sharded", 0), ("vmap", 1)):
            cell = f"{name}/{placement}" + ("+prefetch" if prefetch else "")
            kw = dict(malicious=set(), engine="batched",
                      placement=placement, prefetch=prefetch)
            runner(module, data, warm, **kw)               # compile warm-up
            cells[cell] = _time_best(
                lambda: runner(module, data, pcfg, **kw), t_rounds, repeats)
    for placement in ("vmap", "sharded"):
        cell = f"sweep/{placement}"
        kw = dict(malicious=set(), seeds=seeds, placement=placement)
        run_pigeon_sweep(module, data, warm, **kw)
        cells[cell] = _time_best(
            lambda: run_pigeon_sweep(module, data, pcfg, **kw),
            t_rounds, repeats)

    for name in ("pigeon", "splitfed"):
        csv_row(f"placement_grid_{name}", cells[f"{name}/vmap"] * 1e3,
                f"vmap_ms={cells[name + '/vmap']:.1f};"
                f"sharded_ms={cells[name + '/sharded']:.1f};"
                f"prefetch_ms={cells[name + '/vmap+prefetch']:.1f}")
    csv_row("placement_grid_sweep", cells["sweep/vmap"] * 1e3,
            f"vmap_ms={cells['sweep/vmap']:.1f};"
            f"sharded_ms={cells['sweep/sharded']:.1f};seeds={len(seeds)}")

    import jax
    out = {"params": dict(M=m, N=3, d_m=d_m, E=2, B=32, rounds=t_rounds,
                          repeats=repeats, seeds=list(seeds),
                          devices=jax.device_count()),
           "cells_ms_per_round": cells}
    save_result("placement_grid", out)
    return out


if __name__ == "__main__":
    from .common import main
    main(run)

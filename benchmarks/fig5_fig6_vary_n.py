"""Figs. 5-6: vanilla SL vs Pigeon-SL+ for varying N (number of tolerated
malicious clients).  Paper: MNIST N in {1,3,5} (M=12), CIFAR N in {1,4,9}
(M=20); reduced mode uses M=8/N in {1,3} and M=10/N in {1,4}.

Reduced-mode Pigeon runs use the batched cluster-parallel engine
(equivalence with the sequential reference is CI-tested, so the curves are
unchanged); --full runs stay on the sequential engine to bound memory; the
multi-seed variance band comes from ``run_pigeon_sweep``, which vmaps whole
protocol replicas over a seed axis."""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core import (Attack, LABEL_FLIP, from_cnn, run_pigeon,
                        run_pigeon_sweep, run_vanilla_sl)
from repro.data import build_image_task

from .common import (RoundTimer, cifar_scale, csv_row, mnist_scale, pcfg_from,
                     save_result)


def _run_dataset(name: str, scale, n_values, seed: int, engine: str = "batched"):
    data, cnn_cfg = build_image_task(name if name != "cifar" else "cifar10",
                                     m_clients=scale.m, d_m=scale.d_m,
                                     d_o=scale.d_o, n_test=scale.n_test,
                                     seed=seed)
    module = from_cnn(cnn_cfg)
    curves = {}
    attack = Attack(LABEL_FLIP)
    us = 0.0
    for n in n_values:
        if scale.m % (n + 1) != 0:
            continue            # paper: R must divide M
        pcfg = pcfg_from(scale, seed, n=n)
        malicious = set(range(n))
        with RoundTimer() as t:
            h_p = run_pigeon(module, data, pcfg, malicious, attack, plus=True,
                             engine=engine)
        us = t.us_per(pcfg.T)
        h_v = run_vanilla_sl(module, data, pcfg, malicious, attack)
        curves[f"pigeon_plus_N{n}"] = h_p.series("test_acc")
        curves[f"vanilla_N{n}"] = h_v.series("test_acc")
    return curves, us


def _seed_sweep(name: str, scale, n: int, seeds) -> dict:
    """Final-accuracy mean/std across vmapped protocol replicas (Pigeon-SL,
    selection phase only — the sweep entry point trains S x R clusters per
    compiled round call)."""
    data, cnn_cfg = build_image_task(name if name != "cifar" else "cifar10",
                                     m_clients=scale.m, d_m=scale.d_m,
                                     d_o=scale.d_o, n_test=scale.n_test,
                                     seed=seeds[0])
    module = from_cnn(cnn_cfg)
    pcfg = pcfg_from(scale, seeds[0], n=n)
    with RoundTimer() as t:
        hists = run_pigeon_sweep(module, data, pcfg, malicious=set(range(n)),
                                 attack=Attack(LABEL_FLIP), seeds=seeds)
    finals = [h.rounds[-1]["test_acc"] for h in hists]
    return dict(seeds=list(seeds), final_accs=finals,
                mean=float(np.mean(finals)), std=float(np.std(finals)),
                variant="pigeon_sl_selection_only",
                us_per_round=t.us_per(pcfg.T))


def run(full: bool = False, seed: int = 0):
    out = {}
    # The batched engine materialises the whole round's (R, M_bar, E, B, ...)
    # batch stack at once; at the paper's --full CIFAR scale that is hundreds
    # of MB per round, so full mode stays on the sequential reference engine.
    engine = "sequential" if full else "batched"
    scale_m = mnist_scale(full)
    n_vals_m = (1, 3, 5) if full else (1, 3)
    curves_m, us_m = _run_dataset("mnist", scale_m, n_vals_m, seed, engine)
    out["mnist"] = curves_m
    finals = {k: v[-1] for k, v in curves_m.items()}
    csv_row("fig5_mnist_vary_n", us_m,
            ";".join(f"{k}={v:.3f}" for k, v in sorted(finals.items())))

    # multi-seed variance band for the headline MNIST N (vmapped replicas;
    # plain Pigeon-SL selection phase, not the plus variant the curves use).
    # Always at reduced scale: the sweep stacks (S, R, M_bar, E, B, ...)
    # batches per compiled round, which at paper scale would dwarf the
    # footprint the sequential fallback above bounds.
    sweep_seeds = tuple(range(3)) if full else (0, 1)
    sweep = _seed_sweep("mnist", mnist_scale(False), n_vals_m[0], sweep_seeds)
    out["mnist_seed_sweep"] = sweep
    csv_row("fig5_mnist_seed_sweep", sweep["us_per_round"],
            f"N={n_vals_m[0]};variant={sweep['variant']};"
            f"mean={sweep['mean']:.3f};std={sweep['std']:.3f};"
            f"seeds={len(sweep_seeds)}")

    scale_c = cifar_scale(full)
    if not full:
        # need M divisible by both R=2 and R=5 for the N sweep
        scale_c = dataclasses.replace(scale_c, m=10, t=4, e=3)
    n_vals_c = (1, 4, 9) if full else (1, 4)
    curves_c, us_c = _run_dataset("cifar", scale_c, n_vals_c, seed, engine)
    out["cifar"] = curves_c
    finals = {k: v[-1] for k, v in curves_c.items()}
    csv_row("fig6_cifar_vary_n", us_c,
            ";".join(f"{k}={v:.3f}" for k, v in sorted(finals.items())))
    save_result("fig5_fig6_vary_n", out)
    return out


if __name__ == "__main__":
    from .common import main
    main(run)

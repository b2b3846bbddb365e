"""Table I: communication/computation overhead — measured message counts
from the protocol's CommMeter vs the paper's closed-form formulas.

  vanilla SL   : M*Dt*d_c + M*d_CL            | M*Dt*F_CL
  Pigeon-SL    : (M*Dt + 2R*Do)*d_c + M*d_CL  | (M*Dt + 2R*Do)*F_CL
  Pigeon-SL+   : ((2M-Mb)*Dt + 2R*Do)*d_c + (2M-Mb)*d_CL
                                              | ((2M-Mb)*Dt + 2R*Do)*F_CL
(Dt = E*B samples per client turn, Mb = M/R, F_CL = one client fwd+bwd.)

Also measures wall-clock round time of the sequential reference engine vs the
batched cluster-parallel engine (``engine_speedup``): both engines run the
same protocol from the same seeds (equivalence is CI-tested), so the ratio is
a pure execution-strategy comparison.  The win comes from collapsing the
R x M_bar per-client dispatch/sync chain into one compiled program, so it
grows with R and shrinks as per-client compute grows.
"""
from __future__ import annotations

import dataclasses

import jax

from repro.core import (HONEST, ProtocolConfig, from_cnn, run_pigeon,
                        run_vanilla_sl)
from repro.core.protocol import _count_params, cut_width
from repro.data import build_image_task
from repro.telemetry import Stopwatch

from .common import RoundTimer, csv_row, save_result


def run(full: bool = False, seed: int = 0, telemetry=None):
    """``telemetry`` (an optional :class:`repro.telemetry.Telemetry`) traces
    the three accounting runs only — never the ``engine_speedup`` timing
    loops, whose numbers must not absorb sink I/O."""
    data, cnn_cfg = build_image_task("mnist", m_clients=8, d_m=300, d_o=150,
                                     n_test=500, seed=seed)
    module = from_cnn(cnn_cfg)
    pcfg = ProtocolConfig(M=8, N=3, T=1, E=5, B=32, lr=0.03, seed=seed)
    gamma0, _ = module.init(jax.random.PRNGKey(0))
    d_cl = _count_params(gamma0)
    d_c = cut_width(module, gamma0, data.x0)
    d_o = data.x0.shape[0]
    dt = pcfg.E * pcfg.B
    m, r = pcfg.M, pcfg.R
    mb = m // r

    rows = []
    with RoundTimer() as t:
        h = run_vanilla_sl(module, data, pcfg, malicious=set(),
                           telemetry=telemetry)
    c = h.rounds[0]["comm"]
    rows.append(("vanilla_sl",
                 dict(measured_comm=c["activation_floats"] + c["param_floats"]
                      + c["validation_floats"],
                      formula_comm=m * dt * d_c + m * d_cl,
                      measured_comp=c["client_passes"],
                      formula_comp=m * dt)))
    us = t.us_per(1)

    h = run_pigeon(module, data, pcfg, malicious=set(), telemetry=telemetry)
    c = h.rounds[0]["comm"]
    rows.append(("pigeon_sl",
                 dict(measured_comm=c["activation_floats"] + c["param_floats"]
                      + c["validation_floats"],
                      formula_comm=(m * dt + 2 * r * d_o) * d_c + m * d_cl,
                      measured_comp=c["client_passes"],
                      formula_comp=m * dt + 2 * r * d_o)))

    h = run_pigeon(module, data, pcfg, malicious=set(), plus=True,
                   telemetry=telemetry)
    c = h.rounds[0]["comm"]
    rows.append(("pigeon_sl_plus",
                 dict(measured_comm=c["activation_floats"] + c["param_floats"]
                      + c["validation_floats"],
                      formula_comm=((2 * m - mb) * dt + 2 * r * d_o) * d_c
                      + (2 * m - mb) * d_cl,
                      measured_comp=c["client_passes"],
                      formula_comp=(2 * m - mb) * dt + 2 * r * d_o)))

    out = {"params": dict(M=m, R=r, E=pcfg.E, B=pcfg.B, d_c=d_c, d_cl=d_cl,
                          D_o=d_o), "rows": dict(rows)}
    for name, row in rows:
        match = (row["measured_comm"] == row["formula_comm"]
                 and row["measured_comp"] == row["formula_comp"])
        csv_row(f"table1_{name}", us,
                f"comm_measured={row['measured_comm']};"
                f"comm_formula={row['formula_comm']};match={match}")
    out["engine_speedup"] = engine_speedup(full=full, seed=seed)
    save_result("table1_overhead", out)
    return out


def engine_speedup(full: bool = False, seed: int = 0):
    """Sequential vs batched round time across an R-sweep (the CommMeter
    columns above are engine-independent; this is the wall-clock column).

    The configs scan the protocol-simulation regime the paper's figures run
    in: many clusters, modest per-client compute.  The dispatch-bound corner
    (large R, small E) is where the batched engine clears 2x on CPU.

    ``run_pigeon`` unavoidably evaluates at t=0 and t=T-1; a tiny test set
    keeps that engine-independent cost out of the measured round times.
    """
    data, cnn_cfg = build_image_task("mnist", m_clients=16, d_m=150, d_o=64,
                                     n_test=32, seed=seed)
    module = from_cnn(cnn_cfg)
    timed_rounds = 6 if not full else 16
    repeats = 3
    grid = [  # (N, E, B) with M=16; R = N+1
        (3, 2, 8),
        (7, 2, 8),
        (15, 2, 8),
        (15, 1, 4),      # dispatch-bound corner: many clusters, small batches
    ]
    results = {}
    for n, e, b in grid:
        pcfg = ProtocolConfig(M=16, N=n, T=timed_rounds, E=e, B=b, lr=0.03,
                              seed=seed, eval_every=10 * timed_rounds)
        ms = {}
        for engine in ("sequential", "batched"):
            warm = dataclasses.replace(pcfg, T=2)
            run_pigeon(module, data, warm, malicious=set(), engine=engine)
            best = float("inf")
            for _ in range(repeats):     # best-of-N vs scheduler noise
                with Stopwatch() as sw:
                    run_pigeon(module, data, pcfg, malicious=set(),
                               engine=engine)
                best = min(best, sw.elapsed / pcfg.T * 1e3)
            ms[engine] = best
        speedup = ms["sequential"] / ms["batched"]
        results[f"R{n + 1}_E{e}_B{b}"] = dict(
            sequential_ms=ms["sequential"], batched_ms=ms["batched"],
            speedup=speedup)
        csv_row(f"engine_speedup_R{n + 1}_E{e}_B{b}", ms["batched"] * 1e3,
                f"seq_ms={ms['sequential']:.1f};bat_ms={ms['batched']:.1f};"
                f"speedup={speedup:.2f}x")
    return results


if __name__ == "__main__":
    from .common import main
    main(run)

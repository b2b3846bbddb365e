"""Round-block fusion: K scanned rounds per host sync vs per-round dispatch.

The per-round batched engine already fuses everything *inside* one round —
training, validation and the acceptance cascade are one compiled program
with one stacked host fetch — but each round still pays a Python-side
dispatch, a device->host sync for its (2R+3,) fetch and the host bookkeeping
between rounds.  Round-block execution (``run_pigeon(block=K)``) scans K
rounds inside one ``lax.scan`` program with the theta carry donated, so a
block pays ONE dispatch and ONE stacked (K, 2R+3) fetch for K rounds.

The measurement regime is *small per-round compute*: a one-hidden-layer
split MLP over 8x8 synthetic images, E=1, B=4 — the corner edge deployments
with many cheap rounds live in, where per-round wall time is dominated by
dispatch + fetch + assembly overhead rather than FLOPs.  (With the paper's
CNNs at full batch sizes the device program dominates and fusion is
throughput-neutral — see ``pipeline_overlap`` for that regime's knob.)

Grid: R ∈ {2, 3} x block ∈ {1, 2, 4, 8}, written to
``experiments/round_fusion.json``.  Every measured cell is checked
bit-identical to its block=1 baseline — same selected-cluster sequence,
same History floats, same CommMeter counts — so the speedup column is a
pure execution-schedule measurement, not a numerics change.
"""
from __future__ import annotations

import dataclasses
import gc

import jax
import jax.numpy as jnp

from repro.core import ProtocolConfig, run_pigeon
from repro.core.protocol import ClientData
from repro.core.split import SplitModule, _xent
from repro.data import synthetic

from .common import RoundTimer, csv_row, save_result

BLOCKS = (1, 2, 4, 8)
IMG, HIDDEN, CLASSES = 8, 16, 10


def tiny_split_mlp(d_in: int = IMG * IMG, hidden: int = HIDDEN,
                   n_classes: int = CLASSES) -> SplitModule:
    """One matmul per half: the cheapest SplitModule that still exercises
    the full protocol structure (client chain, validation, cascade)."""

    def init(key):
        k1, k2 = jax.random.split(key)
        gamma = {"w": jax.random.normal(k1, (d_in, hidden)) * 0.1}
        phi = {"v": jax.random.normal(k2, (hidden, n_classes)) * 0.1,
               "b": jnp.zeros(n_classes)}
        return gamma, phi

    def client_forward(gamma, x):
        return jnp.maximum(x.reshape(x.shape[0], -1) @ gamma["w"], 0.0)

    return SplitModule(
        init=init, client_forward=client_forward,
        ap_loss=lambda phi, a, y: _xent(a @ phi["v"] + phi["b"], y),
        predict=lambda g, p, x: client_forward(g, x) @ p["v"] + p["b"],
        n_classes=n_classes)


def _assert_bit_identical(h_ref, h_blk, cell: str) -> None:
    assert len(h_ref.rounds) == len(h_blk.rounds), cell
    for ra, rb in zip(h_ref.rounds, h_blk.rounds):
        assert ra.keys() == rb.keys(), (cell, set(ra) ^ set(rb))
        for k in ra:
            assert ra[k] == rb[k], (cell, ra.get("round"), k)


def run(full: bool = False, seed: int = 0):
    grid = [(4, 1), (9, 2)]                  # (M, N) -> R = N+1 in {2, 3}
    timed_rounds = 64 if not full else 256
    repeats = 7
    d_m = 64

    results = {}
    for m, n in grid:
        arrs = synthetic.make_classification_data(seed, CLASSES, IMG, 1, m,
                                                  d_m, 16, 32)
        x, y, x0, y0, xt, yt = arrs
        data = ClientData(x=x, y=y, x0=x0, y0=y0, x_test=xt, y_test=yt)
        module = tiny_split_mlp()
        pcfg = ProtocolConfig(M=m, N=n, T=timed_rounds, E=1, B=4, lr=0.03,
                              seed=seed, eval_every=10 * timed_rounds)
        kw = dict(malicious=set(), engine="batched", placement="vmap")
        for block in BLOCKS:                 # compile every cell up front
            warm = dataclasses.replace(pcfg, T=2 * block)
            run_pigeon(module, data, warm, block=block, **kw)
        # Interleave the repeats across blocks so scheduler drift on the
        # shared-core container hits every cell, then take per-cell minima;
        # GC off while timing (a collection mid-run swamps ms-scale rounds).
        best = {b: float("inf") for b in BLOCKS}
        hists = {}
        gc.collect()
        gc.disable()
        try:
            for _ in range(repeats):
                for block in BLOCKS:
                    with RoundTimer() as timer:
                        hists[block] = run_pigeon(module, data, pcfg,
                                                  block=block, **kw)
                    best[block] = min(best[block], timer.us_per(pcfg.T))
        finally:
            gc.enable()
        rows = {}
        for block in BLOCKS:
            if block > 1:
                _assert_bit_identical(hists[1], hists[block],
                                      f"R{n + 1}_block{block}")
            rows[f"block{block}"] = dict(
                us_per_round=best[block],
                speedup=best[1] / best[block] if block > 1 else 1.0,
                selected=[r["selected"] for r in hists[block].rounds])
            csv_row(f"round_fusion_R{n + 1}_block{block}", best[block],
                    f"speedup={rows[f'block{block}']['speedup']:.2f}x")
        results[f"R{n + 1}"] = rows

    out = {"params": dict(grid=[list(g) for g in grid], blocks=list(BLOCKS),
                          T=timed_rounds, E=1, B=4, d_m=d_m, img=IMG,
                          hidden=HIDDEN, repeats=repeats),
           "rows": results}
    save_result("round_fusion", out)
    return out


if __name__ == "__main__":
    from .common import main
    main(run)

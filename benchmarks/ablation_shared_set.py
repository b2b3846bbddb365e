"""Beyond-paper ablation: selection reliability vs shared-set size D_o and
client heterogeneity (non-IID Dirichlet shards).

The paper fixes D_o = 3000 and assumes i.i.d. clients. Two questions it
leaves open:
  1. How small can D_o be before the argmin selection starts picking
     malicious clusters? (D_o is pure communication overhead — Table I's
     2R*D_o*d_c term — so smaller is cheaper.)
  2. Does the honest-cluster guarantee survive non-IID clients, where an
     honest-but-skewed cluster can have a high validation loss?
"""
from __future__ import annotations

import dataclasses

from repro.core import Attack, LABEL_FLIP, ProtocolConfig, from_cnn, run_pigeon
from repro.data import build_image_task, dirichlet_relabel

from .common import RoundTimer, csv_row, save_result


def run(full: bool = False, seed: int = 0):
    t_rounds = 8 if full else 4
    out = {"do_sweep": {}, "noniid_sweep": {}}

    us = 0.0
    for d_o in ([25, 100, 400, 1600] if full else [10, 50, 200]):
        data, cnn_cfg = build_image_task("mnist", m_clients=4, d_m=300,
                                         d_o=d_o, n_test=500, seed=seed)
        module = from_cnn(cnn_cfg)
        pcfg = ProtocolConfig(M=4, N=1, T=t_rounds, E=5, B=32, lr=0.05,
                              seed=seed)
        with RoundTimer() as t:
            h = run_pigeon(module, data, pcfg, malicious={1},
                           attack=Attack(LABEL_FLIP))
        us = t.us_per(pcfg.T)
        honest_rate = sum(r["selected_honest"] for r in h.rounds) / len(h.rounds)
        out["do_sweep"][d_o] = dict(
            honest_selection_rate=honest_rate,
            final_acc=h.rounds[-1]["test_acc"])

    for alpha in ([0.1, 0.5, 100.0] if full else [0.2, 100.0]):
        data, cnn_cfg = build_image_task("mnist", m_clients=4, d_m=300,
                                         d_o=150, n_test=500, seed=seed)
        data = dirichlet_relabel(data, alpha, seed=seed)
        module = from_cnn(cnn_cfg)
        pcfg = ProtocolConfig(M=4, N=1, T=t_rounds, E=5, B=32, lr=0.05,
                              seed=seed)
        h = run_pigeon(module, data, pcfg, malicious={1},
                       attack=Attack(LABEL_FLIP))
        honest_rate = sum(r["selected_honest"] for r in h.rounds) / len(h.rounds)
        out["noniid_sweep"][alpha] = dict(
            honest_selection_rate=honest_rate,
            final_acc=h.rounds[-1]["test_acc"])

    derived = ";".join(
        [f"Do{k}_honest={v['honest_selection_rate']:.2f}"
         for k, v in out["do_sweep"].items()]
        + [f"a{k}_acc={v['final_acc']:.2f}" for k, v in out["noniid_sweep"].items()])
    csv_row("ablation_shared_set", us, derived)
    save_result("ablation_shared_set", out)
    return out


if __name__ == "__main__":
    from .common import main
    main(run)

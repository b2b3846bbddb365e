"""Fig. 4: CIFAR-10 test accuracy under the three attacks (N=4 in the paper).
Same protocol grid as Fig. 3 with the deeper CNN and CIFAR Table II params."""
from __future__ import annotations

import dataclasses

from repro.core import (ACTIVATION, GRADIENT, LABEL_FLIP, Attack,
                        from_cnn, run_pigeon, run_splitfed, run_vanilla_sl)
from repro.data import build_image_task

from .common import RoundTimer, cifar_scale, csv_row, pcfg_from, save_result

ATTACKS = [("label_flip", Attack(LABEL_FLIP)),
           ("activation", Attack(ACTIVATION)),
           ("gradient", Attack(GRADIENT))]


def run(full: bool = False, seed: int = 0):
    scale = cifar_scale(full)
    data, cnn_cfg = build_image_task("cifar10", m_clients=scale.m,
                                     d_m=scale.d_m, d_o=scale.d_o,
                                     n_test=scale.n_test, seed=seed)
    module = from_cnn(cnn_cfg)
    pcfg = pcfg_from(scale, seed)
    malicious = set(range(scale.n))
    out = {"scale": dataclasses.asdict(scale), "curves": {}}

    for attack_name, attack in ATTACKS:
        curves = {}
        with RoundTimer() as t:
            h = run_vanilla_sl(module, data, pcfg, malicious, attack)
        curves["vanilla_sl"] = h.series("test_acc")
        us = t.us_per(pcfg.T)
        with RoundTimer() as t:
            h = run_splitfed(module, data,
                             dataclasses.replace(pcfg, lr=scale.lr_sfl),
                             malicious, attack)
        curves["splitfed"] = h.series("test_acc")
        with RoundTimer() as t:
            h = run_pigeon(module, data, pcfg, malicious, attack, plus=True)
        curves["pigeon_sl_plus"] = h.series("test_acc")
        out["curves"][attack_name] = curves
        final = {k: v[-1] for k, v in curves.items()}
        csv_row(f"fig4_cifar_{attack_name}", us,
                f"pigeon+={final['pigeon_sl_plus']:.3f};"
                f"vanilla={final['vanilla_sl']:.3f};"
                f"sfl={final['splitfed']:.3f}")
    save_result("fig4_cifar_attacks", out)
    return out


if __name__ == "__main__":
    from .common import main
    main(run)

"""Operation counts of one protocol round, from a configuration's shapes
alone.  They are the yardstick for ``round_mfu``, so they live with the
benchmark and not in the program.

A multiply-add is two operations.  Training counts three forward passes per
sample (forward, and a backward pass of twice the forward's work); the
shared-set validation, the test-set eval and the handoff re-check count one
forward each.
"""
from __future__ import annotations

from typing import Dict, List, Tuple


def cnn_layer_macs(model: Dict) -> Tuple[List[int], List[int]]:
    """Per-sample multiply-adds of each layer of the split CNN:
    (client-side layers: convs then the cut FC, AP-side FC layers)."""
    s, c_in, k = model["image_size"], model["in_channels"], model["kernel"]
    pad = model["padding"]
    client = []
    for c_out in model["conv_channels"]:
        s_out = s + 2 * pad - k + 1          # stride 1
        client.append(s_out * s_out * c_out * k * k * c_in)
        s, c_in = s_out // 2, c_out          # 2x2 max-pool
    d = s * s * c_in
    fc = list(model["fc_sizes"]) + [model["n_classes"]]
    client.append(d * fc[0])                 # the cut layer
    ap = [fc[i] * fc[i + 1] for i in range(len(fc) - 1)]
    return client, ap


def forward_flops(model: Dict) -> Tuple[int, int]:
    """(client-side, whole-model) forward operations per sample."""
    client, ap = cnn_layer_macs(model)
    return 2 * sum(client), 2 * (sum(client) + sum(ap))


def round_flops(cfg: Dict, *, eval_round: bool, recheck_visits: int) -> int:
    """Model operations of one protocol round of a configuration: every
    client's E mini-batches of B (3 forwards), R shared-set validations over
    D_o, the test-set eval when ``eval_round``, and ``recheck_visits``
    client-side forwards over D_o for the handoff tamper check."""
    client_fwd, fwd = forward_flops(cfg["model"])
    r = cfg["N"] + 1
    train = cfg["M"] * cfg["E"] * cfg["B"] * 3 * fwd
    val = r * cfg["D_o"] * fwd
    ev = cfg["n_test"] * fwd if eval_round else 0
    recheck = recheck_visits * cfg["D_o"] * client_fwd
    return train + val + ev + recheck


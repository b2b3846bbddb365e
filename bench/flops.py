"""Operation counts of one protocol round, from a configuration's shapes
alone.  They are the yardstick for ``round_mfu``, so they live with the
benchmark and not in the program; a model kind counts its own forward pass
(``kinds/<kind>.py::forward_flops``).

A multiply-add is two operations.  Training counts three forward passes per
sample (forward, and a backward pass of twice the forward's work); the
shared-set validation, the test-set eval and the handoff re-check count one
forward each.
"""
from __future__ import annotations

from typing import Dict


def round_flops(kind, cfg: Dict, *, eval_round: bool,
                recheck_visits: int) -> int:
    """Model operations of one protocol round of a configuration whose model
    is of ``kind``: every client's E mini-batches of B (3 forwards), R
    shared-set validations over D_o, the test-set eval when ``eval_round``,
    and ``recheck_visits`` client-side forwards over D_o for the handoff
    tamper check."""
    client_fwd, fwd = kind.forward_flops(cfg["model"])
    r = cfg["N"] + 1
    train = cfg["M"] * cfg["E"] * cfg["B"] * 3 * fwd
    val = r * cfg["D_o"] * fwd
    ev = cfg["n_test"] * fwd if eval_round else 0
    recheck = recheck_visits * cfg["D_o"] * client_fwd
    return train + val + ev + recheck

"""A cell cut to a size the CPU runs in seconds, for the benchmark's tests:
the same files and code paths, with fewer clients, steps and rows.  The
fault tests raise the learning rate, so that one round moves the model
visibly."""
import json
import time

import _paths

import harness


# traffic mixes kept for later cells (PERF.md, Open questions), run here
# with the limits of their configuration's paper cell
OPEN_CELLS = {"mnist_t2.pool8": ("pool8_mixed", "mnist_t2.paper"),
              "cifar10_t2.fused": ("fused_gradient", "cifar10_t2.paper")}


def _load(name: str):
    if name not in OPEN_CELLS:
        return harness.load_cell(name)
    traffic, like = OPEN_CELLS[name]
    cell = harness.load_cell(like)
    cell.name = name
    cell.traffic = json.loads(
        (_paths.BENCH / "traffic" / f"{traffic}.json").read_text())
    return cell


def shrink(cell, lr=None):
    """The cell at the tiny size, in place."""
    r = cell.cfg["N"] + 1
    cell.cfg.update(M=2 * r, E=3, B=16, d_m=80, D_o=60, n_test=100)
    if lr is not None:
        cell.cfg["lr"] = lr
    return cell


def tiny_cell(name: str, lr=None):
    return shrink(_load(name), lr)


def run_tiny(name: str, seed: int = 2**31 + 11, trace: bool = False,
             lr=None):
    return harness.run_cell(tiny_cell(name, lr), seed, 1.0, trace,
                            t_process=time.perf_counter(), require_chip=False)

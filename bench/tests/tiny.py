"""A cell cut to a size the CPU runs in seconds, for the benchmark's tests:
the same files and code paths, with fewer clients, steps and rows, and the
model cut by its kind (``kinds/<kind>.py::tiny_model``).  The fault tests
raise the learning rate, so that one round moves the model visibly."""
import json
import time

import _paths

import flops
import harness

# the most model operations a tiny round may hold: cifar10_t2.paper's tiny
# round with an eval, the largest the CPU tests ran when the cap was set
MAX_TINY_ROUND_FLOPS = 40_067_133_440


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


def _cut(cell):
    r = cell.cfg["N"] + 1
    cell.cfg.update(M=2 * r, E=3, B=16, d_m=80, D_o=60, n_test=100)
    cell.cfg["model"] = cell.kind.tiny_model(cell.cfg["model"])
    return cell


def round_flops(cell) -> int:
    """Model operations of one round of the cell at its size, with an eval."""
    return flops.round_flops(cell.kind, cell.cfg, eval_round=True,
                             recheck_visits=0)


def shrink(cell, lr=None):
    """The cell at the tiny size, in place.  Raises ValueError where its
    round outgrows ``MAX_TINY_ROUND_FLOPS``, as a model left at published
    widths by its kind's ``tiny_model`` does: that cell fails here at once,
    not after hours of CPU time."""
    n = round_flops(_cut(cell))
    if n > MAX_TINY_ROUND_FLOPS:
        raise ValueError(
            f"{cell.name}: a tiny round of {n:,} operations, over the cap "
            f"of {MAX_TINY_ROUND_FLOPS:,}; cut the model in "
            f"kinds/{cell.cfg['model']['kind']}.py::tiny_model")
    if lr is not None:
        cell.cfg["lr"] = lr
    return cell


def tiny_cell(name: str, lr=None):
    return shrink(_load(name), lr)


def run_tiny(name: str, seed: int = 2**31 + 11, trace: bool = False,
             lr=None):
    return harness.run_cell(tiny_cell(name, lr), seed, 1.0, trace,
                            t_process=time.perf_counter(), require_chip=False)

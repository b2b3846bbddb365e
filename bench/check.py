"""The comparison that decides ``correct``: the program's History against
the plain reference (``reference.py``) over each job's first rounds.

Numbers compared, each the worst over the compared rounds, clusters and
jobs:

* ``val_loss_rel``   |program - reference| / |reference| of every
                     cluster's shared-set validation loss;
* ``train_loss_rel`` the same for every cluster's mean client training loss;
* ``select_excess``  how far the reference loss of the program's selected
                     cluster lies above the reference's best, relative;
* ``cascade_diff``   rounds where the program's accepted flag or detection
                     count differs from the reference's (exact);
* ``test_acc_gap``   |program - reference| test accuracy on the eval rounds
                     among them, in the model kind's units of the test set
                     (``kinds/<kind>.py::eval_units``: images for ``cnn``).

The numbers a cell compares, and their limits, are the keys of its
``limits/<cell>.json``; the run is correct when each of them is at or below
its limit.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

NUMBERS = ("val_loss_rel", "train_loss_rel", "select_excess", "cascade_diff",
           "test_acc_gap")


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-12)


def readings(program: Sequence[Sequence[Dict]],
             reference: Sequence[Sequence[Dict]], units: int
             ) -> Dict[str, float]:
    """Worst case of each number over jobs and rounds.  ``program[j]`` and
    ``reference[j]`` are the records of job j, rounds aligned; ``units`` is
    what a test accuracy of 1 counts."""
    out = dict.fromkeys(NUMBERS, 0.0)
    for prog, ref in zip(program, reference):
        if len(prog) < len(ref):
            raise ValueError(f"program has {len(prog)} rounds, the reference "
                             f"{len(ref)}")
        for p, r in zip(prog, ref):
            out["val_loss_rel"] = max(out["val_loss_rel"], max(
                _rel(a, b) for a, b in zip(p["val_losses"], r["val_losses"])))
            out["train_loss_rel"] = max(out["train_loss_rel"], max(
                _rel(a, b) for a, b in zip(p["train_losses"],
                                           r["train_losses"])))
            out["select_excess"] = max(out["select_excess"], r["select_excess"])
            if (bool(p["accepted"]) != bool(r["accepted"])
                    or int(p["detections"]) != int(r["detections"])):
                out["cascade_diff"] += 1
            if "test_acc" in r and "test_acc" in p:
                out["test_acc_gap"] = max(out["test_acc_gap"], round(
                    abs(p["test_acc"] - r["test_acc"]) * units))
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(numbers[k] <= limits[k] for k in limits)


def lines(numbers: Dict[str, float], limits: Dict[str, float]) -> List[str]:
    return [f"check {k} {numbers[k]!r} limit {limits[k]!r}" for k in limits]

"""The benchmark's one traffic generator: a cell's configuration and traffic
files, plus ``--seed``, give the data and the jobs of a run.

The data are drawn by the configuration's model kind
(``kinds/<kind>.py::make_data``) from one ``numpy`` generator seeded by
``--seed``: the same seed gives the same bytes.  A traffic file lists jobs;
each job's protocol seed is ``--seed`` plus its ``seed_offset``, and its
malicious set is named, not listed (``first_N``: clients ``0..N-1`` of the
configuration's N).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np


@dataclasses.dataclass
class Data:
    """Per-client shards, the shared validation set D_o and the test set."""
    x: np.ndarray        # (M, d_m, ...): each client's inputs
    y: np.ndarray        # (M, d_m, ...): their labels
    x0: np.ndarray       # (D_o, ...)
    y0: np.ndarray
    x_test: np.ndarray
    y_test: np.ndarray


def make_data(kind, cfg: Dict, seed: int) -> Data:
    """The cell's whole data set from ``seed`` at the configuration's
    shapes, as its model kind draws it."""
    return Data(**kind.make_data(cfg, np.random.default_rng(seed)))


@dataclasses.dataclass(frozen=True)
class Job:
    seed: int
    malicious: Tuple[int, ...]
    attack: str          # an attack family name of the paper ("none" = honest)


def make_jobs(cfg: Dict, traffic: Dict, seed: int) -> List[Job]:
    jobs = []
    for spec in traffic["jobs"]:
        who = spec["malicious"]
        if who == "none":
            mal: Tuple[int, ...] = ()
        elif who == "first_N":
            mal = tuple(range(cfg["N"]))
        else:
            raise ValueError(f"unknown malicious set {who!r}")
        if spec["attack"] not in ("none", "label_flip", "activation",
                                  "gradient"):
            raise ValueError(f"unknown attack {spec['attack']!r}")
        jobs.append(Job(seed + int(spec["seed_offset"]), mal, spec["attack"]))
    return jobs

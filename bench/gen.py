"""The benchmark's one traffic generator: a cell's configuration and traffic
files, plus ``--seed``, give the data and the jobs of a run.

Data are class-template images (each class a smooth random template, each
sample ``amp * template + noise * N(0, 1)``), the scheme of the program's
``data/synthetic.py::make_classification_data`` kept here so that no change
to the program can move the yardstick.  Draws are float32 from one
``numpy`` generator seeded by ``--seed``: the same seed gives the same
bytes.  A traffic file lists jobs; each job's protocol seed is ``--seed``
plus its ``seed_offset``, and its malicious set is named, not listed
(``first_N``: clients ``0..N-1`` of the configuration's N).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np


def _smooth(img: np.ndarray, k: int = 3, iters: int = 2) -> np.ndarray:
    """Box blur, so that the class templates are low-frequency images."""
    for _ in range(iters):
        pad = np.pad(img, (((k - 1) // 2, k // 2), ((k - 1) // 2, k // 2),
                           (0, 0)), mode="edge")
        acc = np.zeros_like(img)
        for dy in range(k):
            for dx in range(k):
                acc += pad[dy:dy + img.shape[0], dx:dx + img.shape[1], :]
        img = acc / (k * k)
    return img


def _sample(rng: np.random.Generator, templates: np.ndarray, n: int,
            noise: float) -> Tuple[np.ndarray, np.ndarray]:
    y = rng.integers(0, templates.shape[0], size=n).astype(np.int32)
    amp = rng.uniform(0.7, 1.3, size=(n, 1, 1, 1)).astype(np.float32)
    x = rng.standard_normal((n,) + templates.shape[1:], dtype=np.float32)
    x *= np.float32(noise)
    x += amp * templates[y]
    return x, y


@dataclasses.dataclass
class Data:
    """Per-client shards, the shared validation set D_o and the test set."""
    x: np.ndarray        # (M, d_m, H, W, C)
    y: np.ndarray        # (M, d_m)
    x0: np.ndarray       # (D_o, H, W, C)
    y0: np.ndarray
    x_test: np.ndarray
    y_test: np.ndarray


def make_data(cfg: Dict, seed: int) -> Data:
    """The cell's whole data set from ``seed`` at the configuration's
    shapes (``model``, ``M``, ``d_m``, ``D_o``, ``n_test``)."""
    m = cfg["model"]
    rng = np.random.default_rng(seed)
    s, c, k = m["image_size"], m["in_channels"], m["n_classes"]
    t = rng.standard_normal((k, s, s, c), dtype=np.float32)
    t = np.stack([_smooth(v) for v in t])
    t /= np.maximum(np.abs(t).max(axis=(1, 2, 3), keepdims=True), 1e-6)
    noise = cfg["data"]["noise"]
    xs = np.empty((cfg["M"], cfg["d_m"], s, s, c), np.float32)
    ys = np.empty((cfg["M"], cfg["d_m"]), np.int32)
    for i in range(cfg["M"]):
        xs[i], ys[i] = _sample(rng, t, cfg["d_m"], noise)
    x0, y0 = _sample(rng, t, cfg["D_o"], noise)
    xt, yt = _sample(rng, t, cfg["n_test"], noise)
    return Data(xs, ys, x0, y0, xt, yt)


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

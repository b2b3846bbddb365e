"""Shared benchmark plumbing.

Every benchmark runs at REDUCED scale by default (this container is one CPU
core); ``--full`` switches to the paper's Table II parameters.  Results are
written to experiments/ as JSON and summarised on stdout as
``name,us_per_call,derived`` CSV rows (us_per_call = wall-microseconds per
global round; derived = the benchmark's headline metric)."""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from repro.core import Attack, ProtocolConfig
from repro.telemetry import Stopwatch, provenance

EXP_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "experiments")


@dataclasses.dataclass
class BenchScale:
    m: int
    n: int
    t: int
    e: int
    b: int
    d_m: int
    d_o: int
    n_test: int
    lr: float
    lr_sfl: float


def mnist_scale(full: bool) -> BenchScale:
    if full:   # Table II
        return BenchScale(m=12, n=3, t=60, e=79, b=64, d_m=5000, d_o=3000,
                          n_test=7000, lr=1e-3, lr_sfl=1e-2)
    return BenchScale(m=8, n=3, t=10, e=6, b=32, d_m=400, d_o=200,
                      n_test=1000, lr=0.03, lr_sfl=0.3)


def cifar_scale(full: bool) -> BenchScale:
    if full:   # Table II
        return BenchScale(m=20, n=4, t=60, e=40, b=64, d_m=2500, d_o=3000,
                          n_test=7000, lr=2e-4, lr_sfl=2e-3)
    # the 128-filter CIFAR CNN is ~40x the MNIST model per update on this
    # 1-core container: keep the reduced grid small
    return BenchScale(m=5, n=4, t=5, e=4, b=16, d_m=150, d_o=80,
                      n_test=300, lr=0.05, lr_sfl=0.5)


def pcfg_from(scale: BenchScale, seed: int = 0, n: Optional[int] = None) -> ProtocolConfig:
    return ProtocolConfig(M=scale.m, N=scale.n if n is None else n, T=scale.t,
                          E=scale.e, B=scale.b, lr=scale.lr, seed=seed,
                          eval_every=1)


def moving_average(xs: List[float], w: int) -> List[float]:
    out = []
    for i in range(len(xs)):
        lo = max(0, i - w + 1)
        out.append(float(np.mean(xs[lo : i + 1])))
    return out


def save_result(name: str, payload: Dict[str, Any]) -> str:
    # every result JSON carries a provenance stamp (jax/jaxlib versions,
    # backend, device kind, git sha, timestamp) so numbers in experiments/
    # stay attributable after the environment moves on
    payload.setdefault("provenance", provenance())
    os.makedirs(EXP_DIR, exist_ok=True)
    path = os.path.join(EXP_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path


def main(run: Callable[[], Any]) -> None:
    """Command-line entry of one benchmark module: turn on the persistent
    compilation cache (``repro.core.compile_cache``), then run it."""
    from repro.core import enable_compile_cache
    enable_compile_cache()
    run()


def csv_row(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.0f},{derived}", flush=True)


def throughput_fields(elapsed_s: float, rounds: int, jobs: int = 1,
                      dispatches: int = 0) -> Dict[str, float]:
    """Comparable throughput fields for ``experiments/*.json`` result
    records: rounds/sec and jobs/sec over the timed window, plus the mean
    device dispatches per round (1.0 = per-round execution; 1/K under
    round-block fusion; 1/(J*K) per job-round under the job pool) — so the
    perf trajectory stays comparable across PRs."""
    e = max(elapsed_s, 1e-12)
    return {"rounds_per_sec": rounds / e,
            "jobs_per_sec": jobs / e,
            "dispatches_per_round": dispatches / max(rounds, 1)}


class RoundTimer(Stopwatch):
    """A :class:`repro.telemetry.Stopwatch` (monotonic ``perf_counter`` —
    wall-clock ``time.time()`` can step under NTP) reporting per-round us."""

    def us_per(self, rounds: int) -> float:
        return self.elapsed / max(rounds, 1) * 1e6

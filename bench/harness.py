"""One run of one benchmark cell: set-up, the timed window, the traced
reading of the per-layer metrics, and the comparison that decides
``correct``.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Everything that
belongs to it is found by name: its configuration ``configs/<config>.json``,
its model kind ``kinds/<model.kind>.py``, its traffic
``traffic/<traffic>.json``, its limits ``limits/<cell>.json`` and each
per-layer metric's reader ``metrics/<metric>.py``.  A new cell is files and
an entry, and a new model kind is one more file; this module does not
change.

A model kind holds everything of the benchmark that depends on the model:

* ``build_module(cfg)``: the program's ``SplitModule``, through its entry;
* ``make_data(cfg, rng)``: a dict of the ``gen.Data`` fields, drawn from the
  one ``numpy`` generator that ``gen.make_data`` seeds;
* the reference's model, in plain ``jax.numpy``, importing nothing of the
  program: ``init_params(key, model, dtype)`` giving (gamma, phi),
  ``client_forward(gamma, x, model, prec)``,
  ``ap_logits(phi, acts, model, prec)``, ``loss(logits, y)``,
  ``count_correct(logits, y)`` and ``n_classes(model)``;
* ``forward_flops(model)``: (client-side, whole-model) forward operations
  per sample;
* ``eval_units(data)``: what a test accuracy of 1 counts;
* ``tiny_model(model)``: the configuration's ``model`` block cut to a size
  at which the CPU runs a tiny round in seconds, keeping every kind of
  layer of the model, in its pattern.  Only the benchmark's CPU tests call
  it (``tests/tiny.py::shrink``); a run on the chip never does.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import math
import resource
import shutil
import statistics
import sys
import threading
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CHECK_ROUNDS = 3             # rounds of each job the reference follows
MIN_ROUNDS = 10              # fewest rounds of a window that reports a tail
TRACE_S = 2.0                # about how long the traced stretch lasts
STEADY_ROUNDS = 3            # warm-up rounds that time the steady round


class NoChip(RuntimeError):
    """No TPU, too few chips, or a device kind the peaks table lacks."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: Dict[str, Any]
    kind: Any                 # the model kind's module (kinds/<kind>.py)
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def blocked(self) -> bool:
        return int(self.traffic["block"]) > 1


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    found = [w for w in spec["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = found[0]
    bench = root / "bench"
    cfg = json.loads((bench / "configs" / f"{w['config']}.json").read_text())
    traffic = json.loads((bench / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    limits = json.loads((bench / "limits" / f"{name}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), cfg=cfg,
                kind=load_kind(cfg["model"]["kind"], root),
                traffic=traffic, limits=limits["limits"],
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


def load_peaks(kind: str) -> Dict[str, float]:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def find_chip(chips: int):
    """The devices of a TPU host with at least ``chips`` chips whose kind
    the peaks table holds; raises NoChip otherwise."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    load_peaks(devs[0].device_kind)
    return devs


# ---------------------------------------------------------------------------
# counters the harness keeps itself
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts every program JAX compiles or loads from its persistent cache
    (one ``backend_compile_duration`` event each), and the persistent
    cache's hits and misses."""

    def __init__(self) -> None:
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class ProfileWindow:
    """Traces the device over the last stretch of the timed call: from the
    ``start_at``-th driver-round record to the ``stop_at``-th, so that the
    trace holds whole rounds of the steady state and stays small.  The
    profiler slows the rounds it traces (a CIFAR-10 round about threefold),
    so the span and rate readers read the rounds before it."""

    def __init__(self, start_at: int, stop_at: int, log_dir: Path) -> None:
        self.start_at, self.stop_at, self.log_dir = start_at, stop_at, log_dir
        self.t_start = self.t_stop = None

    def tick(self, n_records: int) -> None:
        import jax
        if n_records == self.start_at and self.t_start is None:
            shutil.rmtree(self.log_dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.log_dir),
                                     profiler_options=profiler_options())
            self.t_start = time.perf_counter()
        elif n_records == self.stop_at and self.t_stop is None \
                and self.t_start is not None:
            self.t_stop = time.perf_counter()
            jax.profiler.stop_trace()

    def close(self) -> None:
        """Stops a trace the call left open (it ended before ``stop_at``)."""
        if self.t_start is not None and self.t_stop is None:
            import jax
            self.t_stop = time.perf_counter()
            jax.profiler.stop_trace()


def _round_clock_sink(pooled: bool, window: Optional[ProfileWindow] = None):
    """A telemetry sink that stamps each driver-round record on the host
    clock (a job's ``round`` event; a pool's ``pool_block`` event), keeps
    the spans, and moves the profiler window along."""
    from repro.telemetry.sinks import Sink
    mark = "pool_block" if pooled else "round"

    class RoundClock(Sink):
        def __init__(self) -> None:
            self.stamps: List[float] = []
            self.spans: List[Dict[str, Any]] = []
            self._lock = threading.Lock()

        def emit(self, event: Dict[str, Any]) -> None:
            now = time.perf_counter()
            kind = event.get("event")
            if kind == "span":
                with self._lock:
                    self.spans.append(dict(event, end=now))
            elif kind == mark:
                self.stamps.append(now)
                if window is not None:
                    window.tick(len(self.stamps))

    return RoundClock()


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def _client_data(data):
    from repro.core import ClientData
    return ClientData(x=data.x, y=data.y, x0=data.x0, y0=data.y0,
                      x_test=data.x_test, y_test=data.y_test)


def drive(cell: Cell, module, cdata, jobs, rounds: int, telemetry) -> List:
    """One call of the cell's entry for ``rounds`` rounds of every job;
    returns the jobs' Histories in job order."""
    from repro.core import Attack, HONEST, ProtocolConfig, run_pigeon
    from repro.core.jobs import JobSpec, run_job_pool
    cfg, tr = cell.cfg, cell.traffic

    def pcfg(job):
        return ProtocolConfig(
            M=cfg["M"], N=cfg["N"], T=rounds, E=cfg["E"], B=cfg["B"],
            lr=cfg["lr"], seed=job.seed, tamper_check=True,
            tamper_tol=cfg["tamper_tol"], eval_every=tr["eval_every"],
            eval_batch=cfg["eval_batch"])

    def threat(job):
        if not job.malicious:
            return None, HONEST
        return set(job.malicious), Attack(job.attack)

    if tr["entry"] == "run_pigeon":
        (job,) = jobs
        mal, att = threat(job)
        return [run_pigeon(module, cdata, pcfg(job), malicious=mal,
                           attack=att, engine="batched", placement="vmap",
                           prefetch=tr["prefetch"], block=tr["block"],
                           selection="argmin", telemetry=telemetry)]
    if tr["entry"] == "run_job_pool":
        specs = []
        for i, job in enumerate(jobs):
            mal, att = threat(job)
            specs.append(JobSpec(name=f"job{i}", module=module, data=cdata,
                                 pcfg=pcfg(job), malicious=mal, attack=att))
        out = run_job_pool(specs, block=tr["block"], placement="vmap",
                           lanes=tr["lanes"], prefetch=tr["prefetch"],
                           telemetry=telemetry)
        return [out[s.name] for s in specs]
    raise ValueError(f"unknown entry {tr['entry']!r}")


def warm_rounds(cell: Cell) -> int:
    """Rounds of the warm-up call: enough that every program the window
    runs is built (a block cell needs a 1-round block and two K-round
    ones), and that its last stretch runs after the feeder's head start
    is used up, so that it times the steady round."""
    k = int(cell.traffic["block"])
    return 2 * k + 1 if k > 1 else 2 * STEADY_ROUNDS


def steady_round_s(cell: Cell, stamps: List[float]) -> float:
    """Seconds per round over the warm-up's last stretch: its last block,
    or its last ``STEADY_ROUNDS`` rounds."""
    w = int(cell.traffic["block"]) if cell.blocked else STEADY_ROUNDS
    return (stamps[-1] - stamps[-1 - w]) / w


def window_rounds(cell: Cell, round_s: float, seconds: float) -> int:
    """Rounds of each job in the timed call, sized so that it lasts about
    ``seconds``.  A block cell's count is ``eval_every * n + 1``, so that
    every block has a length the warm-up built."""
    n = max(1.0, seconds / max(round_s, 1e-6))
    if cell.blocked:
        every = int(cell.traffic["eval_every"])
        return every * max(1, round(n / every)) + 1
    return max(MIN_ROUNDS, int(round(n)))


# ---------------------------------------------------------------------------
# per-layer metrics
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LayerContext:
    """What a per-layer metric's reader may read: the stretch of the timed
    call that the profiler leaves alone, from its start at host time ``t0``
    to the record of the last round before the profiler starts, ``t1``; the
    program's spans (each stamped with its end on the same clock); and the
    reduction of the trace of the rounds after it."""
    cell: Cell
    spans: List[Dict[str, Any]]
    t0: float
    t1: float
    driver_rounds: int        # driver rounds (pool rounds) in the stretch
    rounds: List[int]         # each job's rounds that ended in the stretch
    rounds_per_job: int       # rounds of each job in the whole call
    jobs: int
    chips: int
    peaks: Dict[str, float]
    trace: Optional[Dict[str, Any]]

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def span_total(self, *names: str, thread: Optional[str] = "MainThread",
                   depth: Optional[int] = None) -> float:
        """Seconds of the named spans inside the stretch (clipped to it)."""
        total = 0.0
        for s in self.spans:
            if (s["name"] in names
                    and (thread is None or s.get("thread") == thread)
                    and (depth is None or s.get("depth") == depth)):
                total += max(0.0, min(s["end"], self.t1)
                             - max(s["end"] - s["dur_s"], self.t0))
        return total

    def span_count(self, *names: str) -> int:
        return sum(1 for s in self.spans if s["name"] in names
                   and self.t0 < s["end"] <= self.t1)


@functools.lru_cache(maxsize=None)
def _load_file(path: Path):
    """The module in ``path``, loaded once per process: the reference's
    programs take a model kind as a static argument, so one kind is one
    object."""
    name = f"bench_{path.parent.name}_{path.stem.replace('.', '_')}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str) -> Callable[[LayerContext], Optional[float]]:
    return _load_file(BENCH / "metrics" / f"{name}.py").read


def load_kind(name: str, root: Path = ROOT):
    """The model kind ``name``: the module ``bench/kinds/<name>.py`` under
    ``root``."""
    return _load_file((root / "bench" / "kinds" / f"{name}.py").resolve())


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def _log(msg: str) -> None:
    print(msg, flush=True)


def host_peak_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


def p90(values: List[float]) -> float:
    return statistics.quantiles(values, n=10)[8]


def profiler_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, require_chip: bool = True) -> Dict[str, Any]:
    """Set up, run the timed window, read the metrics and check the
    outputs; returns the result object (the last line of the command)."""
    import jax
    devices = find_chip(cell.chips) if require_chip else jax.devices()
    dev = devices[0]
    peaks = (load_peaks(dev.device_kind) if require_chip
             else {"bf16_flops_per_s": float("nan"),
                   "hbm_bytes_per_s": float("nan")})
    from repro.core import enable_compile_cache
    cache_dir = enable_compile_cache(str(ROOT / ".jax-compile-cache"))
    counter = CompileCounter()
    import gen
    t_import = time.perf_counter()
    _log(f"device: {dev.platform} / {dev.device_kind} x {len(devices)}, "
         f"jax {jax.__version__}, compile cache {cache_dir}")

    data = gen.make_data(cell.kind, cell.cfg, seed)
    jobs = gen.make_jobs(cell.cfg, cell.traffic, seed)
    module = cell.kind.build_module(cell.cfg)
    cdata = _client_data(data)
    t_data = time.perf_counter()

    from repro.telemetry import Telemetry
    warm = warm_rounds(cell)
    pooled = cell.traffic["entry"] == "run_job_pool"
    clock = _round_clock_sink(pooled)
    drive(cell, module, cdata, jobs, warm,
          Telemetry(sinks=(clock,), spans=False))
    t_warm = time.perf_counter()
    round_s = steady_round_s(cell, clock.stamps)
    rounds = window_rounds(cell, round_s, seconds)
    _log(f"setup parts: import {t_import - t_process:.3f} s, data "
         f"{t_data - t_import:.3f} s, compile or load "
         f"{counter.compile_s:.3f} s, warm-up "
         f"{t_warm - t_data - counter.compile_s:.3f} s; "
         f"{counter.compiles} programs compiled or loaded, persistent cache "
         f"{counter.hits} hits {counter.misses} misses; steady round "
         f"{round_s:.4f} s; host peak {host_peak_gb():.2f} GB")

    # -- the timed window ---------------------------------------------------
    window = None
    if trace:
        n = max(2, min(rounds // 2, int(math.ceil(TRACE_S / round_s))))
        window = ProfileWindow(rounds - n, rounds,
                               ROOT / ".bench-trace" / cell.name)
    clock = _round_clock_sink(pooled, window)
    tel = Telemetry(sinks=(clock,), spans=trace)
    compiles0 = counter.compiles
    t0 = time.perf_counter()
    try:
        hists = drive(cell, module, cdata, jobs, rounds, tel)
    finally:
        if window is not None:
            window.close()
    t1 = time.perf_counter()
    in_window = counter.compiles - compiles0
    window_s = t1 - t0
    setup_s = t0 - t_process
    n_jobs = len(jobs)
    job_rounds = rounds * n_jobs
    _log(f"window: {rounds} rounds x {n_jobs} jobs in {window_s:.3f} s; "
         f"compiles in the window: {in_window}; host peak "
         f"{host_peak_gb():.2f} GB")

    stamps = clock.stamps
    intervals = [b - a for a, b in zip([t0] + stamps[:-1], stamps)]
    values = {"rounds_per_s": job_rounds / window_s, "setup_s": setup_s}
    if not cell.blocked:
        values["round_p90_s"] = p90(intervals)
    try:
        mem = dev.memory_stats() or {}
    except Exception:  # noqa: BLE001 — a backend without memory stats
        mem = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}

    result: Dict[str, Any] = {"correct": False, "attempted": job_rounds,
                              "failed": job_rounds - sum(len(h.rounds)
                                                         for h in hists)}
    if trace:
        from trace_reduce import find_xplane, reduce_trace
        red = None
        if window.t_stop is not None:
            red = reduce_trace(find_xplane(str(window.log_dir)))
            shutil.rmtree(window.log_dir, ignore_errors=True)
        before = window.start_at
        ctx = LayerContext(cell=cell, spans=clock.spans, t0=t0,
                           t1=stamps[before - 1], driver_rounds=before,
                           rounds=list(range(before)),
                           rounds_per_job=rounds, jobs=n_jobs,
                           chips=cell.chips, peaks=peaks, trace=red)
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        traced_s = (window.t_stop or t0) - (window.t_start or t0)
        device["busy_s"] = red["busy_s"] if red else 0.0
        device["window_s"] = traced_s
        _log(f"untraced stretch: driver rounds 0..{before - 1}, "
             f"{ctx.window_s:.3f} s; traced stretch: driver rounds {before}.."
             f"{window.stop_at - 1}, {traced_s:.3f} s; kernels "
             + "; ".join(f"{k}: {red['op_count'][k]} x "
                         f"{red['op_text'][k][:160]}"
                         for k in (red or {}).get("op_text", {})
                         if "custom-call" in red["op_text"][k]))
        if red:
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in values}
    result["metrics"] = metrics
    result["device"] = device

    # -- the comparison that decides `correct` -------------------------------
    program = [[dict(r) for r in h.rounds[:CHECK_ROUNDS]] for h in hists]
    del hists
    import check
    try:
        numbers = compare(cell, data, jobs, program)
    except Exception as e:  # noqa: BLE001 — an answer the reference cannot
        # follow (a selected index out of range, a missing record) is wrong
        print(f"check failed to run: {e!r}", file=sys.stderr, flush=True)
        numbers = {k: math.inf for k in check.NUMBERS}
    result["correct"] = (check.verdict(numbers, cell.limits)
                         and in_window == 0)
    result["checks"] = {k: {"value": numbers[k], "limit": cell.limits[k]}
                        for k in cell.limits}
    result["checks"]["compiles_in_window"] = {"value": in_window, "limit": 0}
    for line in check.lines(numbers, cell.limits):
        print(line, file=sys.stderr, flush=True)
    print(f"check compiles_in_window {in_window} limit 0", file=sys.stderr,
          flush=True)
    return result


def compare(cell: Cell, data, jobs, program) -> Dict[str, float]:
    """The reference run over each job's first rounds, following the
    program's selections, and the numbers of ``check.readings``."""
    import check
    import reference
    refs = [reference.run(cell.kind, cell.cfg, data, job, CHECK_ROUNDS,
                          int(cell.traffic["eval_every"]),
                          follow=[r["selected"] for r in prog])
            for job, prog in zip(jobs, program)]
    return check.readings(program, refs, cell.kind.eval_units(data))

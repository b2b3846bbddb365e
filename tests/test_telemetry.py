"""Telemetry subsystem: span tracing, sinks, metrics and the no-op-on-math
contract.

The load-bearing guarantees pinned here (see ``repro/telemetry/__init__``):

* spans nest per thread on the monotonic clock and fence device work at
  exit;
* the JSONL event log survives torn writes (crash mid-line) — reopening
  heals the tail and the reader skips unparseable lines;
* per-round metrics are populated from values the drivers already fetched —
  the ``round`` events mirror the History records exactly;
* telemetry is bit-identical-off on the math: enabling every sink and span
  changes neither the History nor the CommMeter across engines x placements
  x prefetch;
* the enabled batched path stays within a few percent of the disabled one.
"""
import dataclasses
import json
import os
import threading
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (HONEST, Attack, LABEL_FLIP, ProtocolConfig, Telemetry,
                        run_pigeon, run_splitfed, run_vanilla_sl)
from repro.telemetry import (DISABLED, NULL_SESSION, ConsoleSink, JSONLSink,
                             MemorySink, NullSession, Stopwatch,
                             TelemetrySession, provenance, read_jsonl,
                             resolve_telemetry)
from repro.telemetry.session import _BorrowedSession


def session_with_memory(**cfg_kwargs):
    mem = MemorySink()
    tel = Telemetry(sinks=(mem,), **cfg_kwargs).session("test")
    return tel, mem


# ---------------------------------------------------------------------------
# spans + timer
# ---------------------------------------------------------------------------

def test_stopwatch_elapsed_nonnegative():
    with Stopwatch() as sw:
        pass
    assert sw.elapsed >= 0.0


def test_span_nesting_paths_and_depth():
    tel, mem = session_with_memory()
    with tel.span("outer", round=3):
        with tel.span("inner"):
            pass
    tel.close()
    spans = mem.of("span")
    # children exit (and emit) before parents
    assert [s["name"] for s in spans] == ["inner", "outer"]
    inner, outer = spans
    assert inner["path"] == "outer/inner" and inner["depth"] == 1
    assert outer["path"] == "outer" and outer["depth"] == 0
    assert outer["round"] == 3
    assert inner["dur_s"] <= outer["dur_s"]


def test_span_fence_accepts_pytrees():
    tel, mem = session_with_memory()
    x = jnp.arange(8.0)
    with tel.span("step") as sp:
        y = x * 2
        sp.fence({"out": y, "nested": [y, x]})
    tel.close()
    (span,) = mem.of("span")
    assert span["name"] == "step" and span["dur_s"] >= 0


def test_span_error_annotated():
    tel, mem = session_with_memory()
    with pytest.raises(ValueError):
        with tel.span("doomed"):
            raise ValueError("boom")
    tel.close()
    (span,) = mem.of("span")
    assert span["error"] == "ValueError"


def test_spans_nest_independently_per_thread():
    tel, mem = session_with_memory()
    ready = threading.Event()

    def worker():
        with tel.span("worker.task"):
            ready.wait(5.0)

    th = threading.Thread(target=worker, name="feeder-sim")
    with tel.span("main.outer"):
        th.start()
        # the worker's span is open on ITS stack; ours must not see it
        with tel.span("main.inner"):
            pass
        ready.set()
        th.join(5.0)
    tel.close()
    by_name = {s["name"]: s for s in mem.of("span")}
    assert by_name["main.inner"]["path"] == "main.outer/main.inner"
    assert by_name["worker.task"]["path"] == "worker.task"
    assert by_name["worker.task"]["thread"] == "feeder-sim"


def test_spans_config_off_leaves_round_events():
    tel, mem = session_with_memory(spans=False)
    with tel.span("invisible"):
        pass
    tel.record_round(0, {"test_acc": 0.5})
    tel.close()
    assert mem.of("span") == []
    assert len(mem.of("round")) == 1


# ---------------------------------------------------------------------------
# sinks
# ---------------------------------------------------------------------------

def test_jsonl_roundtrip_and_torn_write_tolerance(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    sink = JSONLSink(path)
    sink.emit({"event": "a", "i": 0})
    sink.emit({"event": "b", "i": 1})
    sink.close()
    # simulate a crash mid-write: torn final line without a newline
    with open(path, "a") as f:
        f.write('{"event": "c", "i":')
    # the tolerant reader skips the torn record
    assert [e["event"] for e in read_jsonl(path)] == ["a", "b"]
    # reopening heals the tail so appended events stay parseable
    sink2 = JSONLSink(path)
    sink2.emit({"event": "d", "i": 3})
    sink2.close()
    assert [e["event"] for e in read_jsonl(path)] == ["a", "b", "d"]


def test_jsonl_flushes_per_line(tmp_path):
    path = str(tmp_path / "live.jsonl")
    sink = JSONLSink(path)
    sink.emit({"event": "x"})
    # readable BEFORE close — the crash-tolerance contract
    assert [e["event"] for e in read_jsonl(path)] == ["x"]
    sink.close()


def test_console_sink_round_line(capsys):
    sink = ConsoleSink()
    sink.emit({"event": "round", "run": "pigeon", "t": 4, "test_acc": 0.875,
               "selected": 1, "selected_honest": True, "accepted": True,
               "detections": 0, "val_losses": [2.1, 2.2]})
    sink.emit({"event": "span", "name": "round.step", "dur_s": 0.1})
    out = capsys.readouterr().out
    lines = out.strip().splitlines()
    assert len(lines) == 1                      # spans don't hit the console
    assert "[pigeon] t=  4" in lines[0]
    assert "acc=0.8750" in lines[0] and "sel=1" in lines[0]
    assert "vloss=[2.1000,2.2000]" in lines[0]


def test_memory_sink_filters_by_kind():
    tel, mem = session_with_memory()
    tel.record_round(0, {"selected": 2})
    tel.close()
    assert [e["event"] for e in mem.events] == ["run_start", "round",
                                                "run_end"]
    assert mem.of("round")[0]["selected"] == 2


# ---------------------------------------------------------------------------
# session resolution / lifecycle
# ---------------------------------------------------------------------------

def test_resolve_disabled_returns_shared_null():
    assert resolve_telemetry(None) is NULL_SESSION
    assert resolve_telemetry(DISABLED) is NULL_SESSION
    assert resolve_telemetry(NULL_SESSION) is NULL_SESSION


def test_resolve_verbose_is_console_alias(capsys):
    tel = resolve_telemetry(None, verbose=True, run="x")
    assert isinstance(tel, TelemetrySession)
    tel.record_round(0, {"test_acc": 0.5})
    tel.close()
    assert "[x] t=  0 acc=0.5000" in capsys.readouterr().out


def test_resolve_borrowed_session_survives_driver_close():
    tel, mem = session_with_memory()
    borrowed = resolve_telemetry(tel)
    assert isinstance(borrowed, _BorrowedSession)
    borrowed.close()                      # driver-side close: must be a no-op
    tel.record_round(0, {})
    tel.close()
    kinds = [e["event"] for e in mem.events]
    assert kinds == ["run_start", "round", "run_end"]


def test_session_close_idempotent_and_emits_metrics():
    tel, mem = session_with_memory()
    tel.record_round(0, {"accepted": True, "selected_honest": True,
                         "detections": 2})
    tel.close()
    tel.close()
    (end,) = mem.of("run_end")
    counters = end["metrics"]["counters"]
    assert counters == {"rounds": 1, "rounds_accepted": 1, "detections": 2,
                        "honest_selections": 1}


def test_null_session_is_inert():
    s = NullSession()
    with s.span("x") as sp:
        sp.fence(jnp.zeros(2))
    s.record_round(0, {})
    s.profile_tick(0)
    s.close()
    assert not s.enabled


def test_provenance_stamp_keys():
    p = provenance(extra_key="v")
    for k in ("jax", "jaxlib", "python", "platform", "backend", "device_kind",
              "device_count", "cpu_count", "git_sha", "timestamp",
              "timestamp_utc"):
        assert k in p, k
    assert p["extra_key"] == "v"
    assert json.dumps(p)                  # JSON-serialisable throughout


# ---------------------------------------------------------------------------
# metrics from the stacked fetch: round events mirror History records
# ---------------------------------------------------------------------------

def test_round_events_mirror_history(tiny_task, tiny_pcfg):
    data, module = tiny_task
    mem = MemorySink()
    tel = Telemetry(sinks=(mem,))
    h = run_pigeon(module, data, tiny_pcfg, malicious={0},
                   attack=Attack(LABEL_FLIP), engine="batched", prefetch=1,
                   telemetry=tel)
    rounds = mem.of("round")
    assert len(rounds) == len(h.rounds) == tiny_pcfg.T
    for ev, rec in zip(rounds, h.rounds):
        assert ev["t"] == rec["round"]
        for k in ("selected", "accepted", "detections", "selected_honest",
                  "val_losses"):
            assert ev[k] == rec[k], k
        assert ev["comm"] == rec["comm"]
        assert ev["feeder_depth"] >= 0
    # spans cover the protocol phases the issue names
    names = {s["name"] for s in mem.of("span")}
    assert {"feeder.assemble", "round.feeder_wait", "round.step",
            "round.fetch", "round.select", "round.eval"} <= names


def test_trace_jsonl_from_three_round_run(tiny_task, tmp_path):
    data, module = tiny_task
    path = str(tmp_path / "run.jsonl")
    pcfg = ProtocolConfig(M=4, N=1, T=3, E=2, B=16, lr=0.05, seed=0)
    run_pigeon(module, data, pcfg, engine="batched", prefetch=1,
               telemetry=Telemetry(jsonl=path, jit_stats=True))
    evs = read_jsonl(path)
    assert evs[0]["event"] == "run_start"
    assert "git_sha" in evs[0]["provenance"]
    assert evs[-1]["event"] == "run_end"
    rounds = [e for e in evs if e["event"] == "round"]
    assert [r["t"] for r in rounds] == [0, 1, 2]
    jit = rounds[0]["jit"]
    assert jit["runners"] >= 1 and jit["programs"] >= 1
    assert jit["trace_compile_s"] >= 0


# ---------------------------------------------------------------------------
# bit-identity: telemetry on == telemetry off
# ---------------------------------------------------------------------------

def assert_history_identical(h_on, h_off):
    assert len(h_on.rounds) == len(h_off.rounds)
    for a, b in zip(h_on.rounds, h_off.rounds):
        assert a == b                    # bit-identical, comm dicts included


FULL_TELEMETRY = [
    pytest.param(lambda tmp: Telemetry(sinks=(MemorySink(),), jit_stats=True,
                                       jsonl=str(tmp / "t.jsonl")),
                 id="all-sinks"),
]


@pytest.mark.parametrize("engine,placement,prefetch", [
    ("sequential", "vmap", 0),
    ("batched", "vmap", 0),
    ("batched", "vmap", 1),
    ("batched", "sharded", 1),
])
def test_bit_identity_pigeon(tiny_task, tiny_pcfg, tmp_path, engine,
                             placement, prefetch):
    data, module = tiny_task
    kw = dict(malicious={0}, attack=Attack(LABEL_FLIP), engine=engine,
              placement=placement, prefetch=prefetch)
    h_off = run_pigeon(module, data, tiny_pcfg, **kw)
    h_on = run_pigeon(module, data, tiny_pcfg,
                      telemetry=Telemetry(sinks=(MemorySink(),),
                                          jit_stats=True,
                                          jsonl=str(tmp_path / "t.jsonl")),
                      **kw)
    assert_history_identical(h_on, h_off)


@pytest.mark.parametrize("engine,prefetch", [
    ("sequential", 0), ("batched", 1),
])
def test_bit_identity_splitfed(tiny_task, tiny_pcfg, tmp_path, engine,
                               prefetch):
    data, module = tiny_task
    kw = dict(malicious={0}, attack=Attack(LABEL_FLIP), engine=engine,
              prefetch=prefetch)
    h_off = run_splitfed(module, data, tiny_pcfg, **kw)
    h_on = run_splitfed(module, data, tiny_pcfg,
                        telemetry=Telemetry(sinks=(MemorySink(),)), **kw)
    assert_history_identical(h_on, h_off)


def test_bit_identity_vanilla(tiny_task, tiny_pcfg):
    data, module = tiny_task
    h_off = run_vanilla_sl(module, data, tiny_pcfg)
    h_on = run_vanilla_sl(module, data, tiny_pcfg,
                          telemetry=Telemetry(sinks=(MemorySink(),)))
    assert_history_identical(h_on, h_off)


def test_bit_identity_via_protocol_config(tiny_task, tiny_pcfg):
    """The ProtocolConfig.telemetry field is an equivalent plumbing route."""
    import dataclasses
    data, module = tiny_task
    h_off = run_pigeon(module, data, tiny_pcfg, engine="batched")
    pcfg_tel = dataclasses.replace(tiny_pcfg,
                                   telemetry=Telemetry(sinks=(MemorySink(),)))
    h_on = run_pigeon(module, data, pcfg_tel, engine="batched")
    assert_history_identical(h_on, h_off)


# ---------------------------------------------------------------------------
# overhead guard: enabled batched round within 5% of disabled
# ---------------------------------------------------------------------------

def _best_per_call(fn, n=200, repeats=25):
    """Seconds per call of ``fn``: the best, over ``repeats`` batches, of a
    batch of ``n`` calls' mean.  The best batch is the one the machine's
    other load disturbed least."""
    best = float("inf")
    for _ in range(repeats):
        with Stopwatch() as sw:
            for _ in range(n):
                fn()
        best = min(best, sw.elapsed / n)
    return best


def test_telemetry_overhead_batched(tiny_task):
    """What telemetry adds to a batched run stays under 5% of the run: the
    spans and round records one run emits, each priced at its in-process
    enter/exit cost, against the disabled run's best wall time.  (Timing
    the enabled run against the disabled one measured the machine's other
    load, which moved each by more than 5%.)"""
    data, module = tiny_task
    pcfg = ProtocolConfig(M=4, N=1, T=6, E=2, B=16, lr=0.05, seed=0,
                          eval_every=100)
    kw = dict(engine="batched", prefetch=1)
    mem = MemorySink()
    # warm both paths (compile + allocator) before timing
    run_pigeon(module, data, pcfg, **kw)
    h = run_pigeon(module, data, pcfg, telemetry=Telemetry(sinks=(mem,)),
                   **kw)
    n_spans, n_records = len(mem.of("span")), len(mem.of("round"))
    assert n_spans >= 7 * pcfg.T and n_records == pcfg.T

    t_off = float("inf")
    for _ in range(3):
        with Stopwatch() as sw:
            run_pigeon(module, data, pcfg, **kw)
        t_off = min(t_off, sw.elapsed)

    tel = Telemetry(sinks=(MemorySink(),)).session("overhead")
    x = jnp.arange(8.0)

    def one_span():
        with tel.span("round.step", round=1) as sp:
            sp.fence(x)

    span_s = _best_per_call(one_span)
    record_s = _best_per_call(
        lambda: tel.record_round(0, h.rounds[0], feeder_depth=1))
    tel.close()
    cost = n_spans * span_s + n_records * record_s
    assert cost <= 0.05 * t_off, (cost, t_off, span_s, record_s)


# ---------------------------------------------------------------------------
# profiler annotations and transfer counters
# ---------------------------------------------------------------------------

PROGRAM_SPANS = {"round.feeder_wait", "feeder.assemble", "assemble.gather",
                 "assemble.put", "round.step", "round.eval"}


def test_profiler_trace_holds_program_spans(tiny_task, tiny_pcfg, tmp_path):
    """Every span is a ``jax.profiler`` annotation: a traced run's
    ``.xplane.pb`` holds the program's phases on its host plane, with
    their attrs as stats."""
    import jax
    from jax.profiler import ProfileData
    data, module = tiny_task
    kw = dict(engine="batched", prefetch=1,
              telemetry=Telemetry(sinks=(MemorySink(),)))
    run_pigeon(module, data, tiny_pcfg, **kw)          # compile outside
    with jax.profiler.trace(str(tmp_path)):
        run_pigeon(module, data, tiny_pcfg, **kw)
    (path,) = tmp_path.glob("**/*.xplane.pb")
    host = [p for p in ProfileData.from_file(str(path)).planes
            if p.name == "/host:CPU"]
    assert host
    events = {}
    for plane in host:
        for line in plane.lines:
            for ev in line.events:
                events.setdefault(ev.name, {k: v for k, v in ev.stats})
    assert PROGRAM_SPANS <= set(events), sorted(events)
    assert events["round.step"]["round"] in range(tiny_pcfg.T)
    assert events["assemble.put"]["h2d_bytes"] > 0


def _batch_bytes(data, pcfg, rounds=1, jobs=1):
    """Bytes of the stacked (R, M_bar, E, B, ...) mini-batches of
    ``rounds`` rounds of ``jobs`` jobs."""
    samples = jobs * rounds * pcfg.M * pcfg.E * pcfg.B
    return samples * (data.x[0, 0].nbytes + data.y[0, 0].nbytes)


def _idx_bytes(pcfg, rounds=1, jobs=1):
    """Bytes of the int32 (R, M_bar, E, B) index buffers of ``rounds``
    rounds of ``jobs`` jobs."""
    return 4 * jobs * rounds * pcfg.M * pcfg.E * pcfg.B


@pytest.mark.parametrize("path", ["feeder", "inline", "block", "pool"])
def test_transfer_bytes_on_put_and_eval(tiny_task, path):
    """``assemble.put`` carries the bytes of the indices it copies (one
    round, a K-round block or a J-lane pool block), the device gather's
    ``assemble.gather`` the bytes of the batches it writes, each round's
    index draw an ``assemble.gather`` of its own, ``assemble.resident`` the
    one put of the client shards per ClientData, and ``round.eval`` the
    test set's bytes."""
    from repro.core.jobs import JobSpec, run_job_pool
    tiny, module = tiny_task
    data = dataclasses.replace(tiny)        # a ClientData with no copy yet
    pcfg = ProtocolConfig(M=4, N=1, T=4, E=2, B=16, lr=0.05, seed=0,
                          eval_every=2)
    mem = MemorySink()
    tel = Telemetry(sinks=(mem,))
    if path == "pool":
        run_job_pool([JobSpec(name=f"job{s}", module=module, data=data,
                              pcfg=dataclasses.replace(pcfg, seed=s))
                      for s in range(2)], block=2, prefetch=1,
                     telemetry=tel)
    else:
        run_pigeon(module, data, pcfg, engine="batched",
                   prefetch=0 if path == "inline" else 1,
                   block=2 if path == "block" else 1, telemetry=tel)
    spans = mem.of("span")
    puts = [s for s in spans if s["name"] == "assemble.put"]
    gathers = [s for s in spans if s["name"] == "assemble.gather"
               and "device_bytes" in s]
    draws = [s for s in spans if s["name"] == "assemble.gather"
             and "device_bytes" not in s]
    evals = [s for s in spans if s["name"] == "round.eval"]
    resident = [s for s in spans if s["name"] == "assemble.resident"]
    jobs = 2 if path == "pool" else 1
    assert puts and evals and len(puts) == len(gathers)
    for put, gather in zip(puts, gathers):
        k = put.get("k", 1)
        assert put["h2d_bytes"] == _idx_bytes(pcfg, k, jobs)
        assert gather["device_bytes"] == _batch_bytes(data, pcfg, k, jobs)
    assert sum(s["h2d_bytes"] for s in puts) == _idx_bytes(pcfg, pcfg.T,
                                                           jobs)
    assert sum(s["device_bytes"] for s in gathers) == _batch_bytes(
        data, pcfg, pcfg.T, jobs)
    assert len(draws) == jobs * pcfg.T
    assert [s["h2d_bytes"] for s in resident] == [data.x.nbytes
                                                   + data.y.nbytes]
    assert {s["h2d_bytes"] for s in evals} == {
        data.x_test.nbytes + data.y_test.nbytes}
    # the sub-spans sit inside the assembly span of their thread; the
    # shards' put comes before the first round, outside any
    outer = ("feeder.assemble", "round.assemble")
    assert all(s["path"].split("/")[0] in outer
               for s in puts + gathers + draws)
    assert resident[0]["path"] == "assemble.resident"


@pytest.mark.parametrize("path", ["sequential", "round", "block", "pool"])
def test_step_spans_carry_patch_dw_layers(tiny_task, tiny_pcfg, path):
    """Every round-step span, on each engine and execution mode, carries
    how many of the model's conv layers take the patch filter gradient:
    the MNIST CNN's conv1."""
    from repro.core.jobs import JobSpec, run_job_pool
    data, module = tiny_task
    assert module.patch_dw_layers == 1
    pcfg = dataclasses.replace(tiny_pcfg, T=2, eval_every=10)
    mem = MemorySink()
    tel = Telemetry(sinks=(mem,))
    if path == "pool":
        run_job_pool([JobSpec(name="job0", module=module, data=data,
                              pcfg=pcfg)], block=2, telemetry=tel)
    else:
        run_pigeon(module, data, pcfg, telemetry=tel,
                   engine="sequential" if path == "sequential" else "batched",
                   block=2 if path == "block" else 1)
    name = {"pool": "pool.step", "block": "block.step"}.get(path,
                                                            "round.step")
    steps = [s for s in mem.of("span") if s["name"] == name]
    assert steps
    assert {s["patch_dw_layers"] for s in steps} == {1}


def test_resident_copy_is_put_once_per_client_data(tiny_task, tiny_pcfg):
    """A second run on the same ClientData gathers from the copy the first
    one put; a different ClientData object gets a copy of its own."""
    tiny, module = tiny_task
    data = dataclasses.replace(tiny)

    def resident_puts(d):
        mem = MemorySink()
        run_pigeon(module, d, tiny_pcfg, engine="batched", prefetch=1,
                   telemetry=Telemetry(sinks=(mem,)))
        return [s for s in mem.of("span") if s["name"] == "assemble.resident"]

    assert len(resident_puts(data)) == 1
    copy = data._resident[2:]
    assert resident_puts(data) == []
    assert all(a is b for a, b in zip(data._resident[2:], copy))
    other = dataclasses.replace(data)
    assert len(resident_puts(other)) == 1
    assert other._resident[2] is not copy[0]


def test_span_events_carry_their_start(tiny_task, tiny_pcfg):
    """Each span event carries ``start_s``, its own clock at entry: start
    and end both come from the program, before the sink sees the event,
    and a child starts no earlier than its parent."""
    import time
    from repro.telemetry import Sink

    class Arrival(Sink):
        def __init__(self):
            self.events = []

        def emit(self, event):
            self.events.append((time.perf_counter(), event))

    sink = Arrival()
    data, module = tiny_task
    run_pigeon(module, data, tiny_pcfg, engine="batched", prefetch=1,
               telemetry=Telemetry(sinks=(sink,)))
    spans = [(now, e) for now, e in sink.events if e["event"] == "span"]
    assert spans
    for now, e in spans:
        assert e["start_s"] <= e["start_s"] + e["dur_s"] <= now
    by_path = {(e["thread"], e["path"]): e for _, e in spans}
    for (thread, path), e in by_path.items():
        if "/" in path:
            parent = by_path.get((thread, path.rsplit("/", 1)[0]))
            if parent is not None:
                assert parent["start_s"] <= e["start_s"]


@pytest.mark.parametrize("telemetry,annotated", [
    pytest.param(None, False, id="no-telemetry"),
    pytest.param(Telemetry(spans=False), False, id="spans-off"),
    pytest.param(Telemetry(spans=True), True, id="spans-on"),
])
def test_trace_annotation_only_with_spans(tiny_task, tiny_pcfg, monkeypatch,
                                          telemetry, annotated):
    """With spans off the hot path opens no profiler annotation."""
    import jax
    entered = []

    class Recorder:
        def __init__(self, name, **attrs):
            self.name = name

        def __enter__(self):
            entered.append(self.name)
            return self

        def __exit__(self, *exc):
            pass

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    data, module = tiny_task
    run_pigeon(module, data, tiny_pcfg, engine="batched", prefetch=1,
               telemetry=telemetry)
    if annotated:
        assert PROGRAM_SPANS <= set(entered)
    else:
        assert entered == []


# ---------------------------------------------------------------------------
# launch-layer helpers
# ---------------------------------------------------------------------------

def test_instrument_step_passthrough_when_disabled():
    from repro.launch.steps import instrument_step
    fn = lambda x: x + 1  # noqa: E731
    assert instrument_step(fn, None, "s") is fn
    assert instrument_step(fn, NULL_SESSION, "s") is fn


def test_instrument_step_emits_span_per_call():
    from repro.launch.steps import instrument_step
    tel, mem = session_with_memory()
    step = instrument_step(lambda x: x * 2, tel, "serve.decode")
    assert float(step(jnp.float32(3))) == 6.0
    assert float(step(jnp.float32(4))) == 8.0
    tel.close()
    spans = mem.of("span")
    assert [s["name"] for s in spans] == ["serve.decode"] * 2
    assert [s["call"] for s in spans] == [0, 1]


def test_feeder_qsize_gauge(tiny_task, tiny_pcfg):
    from repro.data.pipeline import RoundFeeder
    with RoundFeeder(lambda t: t * 10, start=0, stop=0, depth=1) as f:
        assert f.qsize() == 0            # nothing scheduled
    with RoundFeeder(lambda t: t * 10, start=0, stop=4, depth=0) as f:
        assert f.qsize() == 0            # synchronous fallback
        assert f.get(0) == 0


# ---------------------------------------------------------------------------
# round-block execution: per-round events survive block-cadence host sync
# ---------------------------------------------------------------------------

def test_block_round_events_mirror_per_round(tiny_task, tiny_pcfg):
    """block=K still emits ONE round event per protocol round (replayed from
    the stacked block fetch), with the same payload the per-round loop
    records — telemetry consumers cannot tell the execution modes apart."""
    import dataclasses as _dc

    data, module = tiny_task
    pcfg = _dc.replace(tiny_pcfg, T=4, eval_every=10)
    kw = dict(malicious={1}, attack=Attack(LABEL_FLIP), engine="batched")

    mem_1, mem_4 = MemorySink(), MemorySink()
    run_pigeon(module, data, pcfg, telemetry=Telemetry(sinks=(mem_1,)),
               block=1, **kw)
    run_pigeon(module, data, pcfg, telemetry=Telemetry(sinks=(mem_4,)),
               block=4, **kw)

    rounds_1, rounds_4 = mem_1.of("round"), mem_4.of("round")
    assert [e["t"] for e in rounds_4] == [e["t"] for e in rounds_1] \
        == list(range(pcfg.T))
    for e1, e4 in zip(rounds_1, rounds_4):
        for k in ("selected", "accepted", "detections", "selected_honest",
                  "val_losses", "comm"):
            assert e1[k] == e4[k], k
    # block mode swaps the per-round step/fetch spans for block-grained ones
    names_4 = {s["name"] for s in mem_4.of("span")}
    assert {"block.assemble", "block.step", "block.fetch"} <= names_4


def test_block_recorded_in_run_start(tiny_task, tiny_pcfg, tmp_path):
    """The effective block size lands in the run_start provenance payload."""
    import dataclasses as _dc

    data, module = tiny_task
    pcfg = _dc.replace(tiny_pcfg, T=2, eval_every=10)
    path = str(tmp_path / "t.jsonl")
    run_pigeon(module, data, pcfg, engine="batched", block=2,
               telemetry=Telemetry(jsonl=path))
    evs = read_jsonl(path)
    start = [e for e in evs if e["event"] == "run_start"][0]
    assert start["block"] == 2


def test_compile_cache_stats_surface_in_jit_stats(tmp_path, monkeypatch):
    """enable_compile_cache wires JAX's persistent cache; after clearing the
    in-process jit caches a re-jit loads from disk and the hit counters
    surface through telemetry's jit_cache_stats."""
    import jax

    from repro.core import enable_compile_cache
    from repro.core import compile_cache as cc
    from repro.telemetry.metrics import jit_cache_stats

    monkeypatch.delenv(cc.ENV_VAR, raising=False)

    prev_dir, prev_hits, prev_misses = (cc._state["dir"], cc._state["hits"],
                                        cc._state["misses"])
    d = str(tmp_path / "xla_cache")
    try:
        assert enable_compile_cache(d) == d
        f = jax.jit(lambda x: x * 3 + 1)
        jax.block_until_ready(f(jnp.arange(4.0)))
        jax.clear_caches()                     # drop in-process executables
        f2 = jax.jit(lambda x: x * 3 + 1)
        jax.block_until_ready(f2(jnp.arange(4.0)))
        stats = jit_cache_stats()
        assert stats["persistent_cache_dir"] == d
        assert stats["persistent_cache_entries"] >= 1
        assert stats["persistent_cache_hits"] >= 1
        assert stats["persistent_cache_misses"] >= 1
    finally:
        jax.config.update("jax_compilation_cache_dir", None)
        cc._state["dir"] = prev_dir
        cc._state["hits"], cc._state["misses"] = prev_hits, prev_misses


def test_enable_compile_cache_disabled_without_dir(monkeypatch, tmp_path):
    """Without an explicit directory the cache goes to the fixed in-checkout
    ``.jax-compile-cache/``; ``$JAX_COMPILATION_CACHE_DIR`` takes precedence
    over both the default and an explicit directory."""
    import jax

    from repro.core import compile_cache as cc
    repo_root = Path(__file__).resolve().parents[1]
    assert cc.ENV_VAR == "JAX_COMPILATION_CACHE_DIR"
    assert Path(cc.DEFAULT_DIR) == repo_root / ".jax-compile-cache"
    prev = cc._state["dir"]
    prev_cfg = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv(cc.ENV_VAR, raising=False)
        assert cc.enable_compile_cache(None) == cc.DEFAULT_DIR
        assert cc.compile_cache_stats()["persistent_cache_dir"] == cc.DEFAULT_DIR
        assert jax.config.jax_compilation_cache_dir == cc.DEFAULT_DIR
        env_dir = str(tmp_path / "from_env")
        monkeypatch.setenv(cc.ENV_VAR, env_dir)
        for explicit in (None, str(tmp_path / "explicit")):
            assert cc.enable_compile_cache(explicit) == env_dir
            assert jax.config.jax_compilation_cache_dir == env_dir
        assert not (tmp_path / "explicit").exists()
        stats = cc.compile_cache_stats()
        assert stats["persistent_cache_dir"] == env_dir
        assert stats["persistent_cache_entries"] == 0
    finally:
        from jax.experimental.compilation_cache import compilation_cache
        jax.config.update("jax_compilation_cache_dir", prev_cfg)
        compilation_cache.reset_cache()
        cc._state["dir"] = prev

"""The host-assembly readers ``gather_ms``, ``h2d_ms`` and ``h2d_mb`` on a
hand-made span list, and in a traced run at a tiny size on the CPU."""
import pytest

import _paths  # noqa: F401
from tiny import run_tiny

import harness


def _ctx(spans, t0=10.0, t1=20.0, driver_rounds=2):
    return harness.LayerContext(
        cell=None, spans=spans, t0=t0, t1=t1, driver_rounds=driver_rounds,
        rounds=list(range(driver_rounds)), rounds_per_job=driver_rounds,
        jobs=1, chips=1, peaks={}, trace=None)


def _span(name, end, dur, thread="pigeon-round-feeder", depth=1, **attrs):
    return dict(event="span", name=name, end=end, dur_s=dur, thread=thread,
                depth=depth, **attrs)


SPANS = [
    # a put that started before the stretch: only its part inside counts
    _span("assemble.put", 10.5, 1.0, h2d_bytes=3_000_000),
    _span("assemble.gather", 12.0, 0.4, host_bytes=3_000_000),
    _span("assemble.put", 12.5, 0.5, h2d_bytes=3_000_000),
    _span("round.eval", 13.0, 0.25, thread="MainThread", depth=0,
          h2d_bytes=1_000_000),
    _span("assemble.gather", 15.0, 0.2, thread="MainThread",
          host_bytes=3_000_000),
    # ends after the stretch: left out
    _span("assemble.put", 20.5, 1.0, h2d_bytes=3_000_000),
    _span("round.step", 14.0, 1.0, thread="MainThread", depth=0),
]


def test_gather_ms_sums_every_thread_per_driver_round():
    assert harness.load_reader("gather_ms")(_ctx(SPANS)) == \
        pytest.approx((0.4 + 0.2) / 2 * 1e3)


def test_h2d_ms_clips_to_the_stretch():
    assert harness.load_reader("h2d_ms")(_ctx(SPANS)) == \
        pytest.approx((0.5 + 0.5 + 0.5) / 2 * 1e3)


def test_h2d_mb_sums_the_bytes_of_spans_ending_in_the_stretch():
    assert harness.load_reader("h2d_mb")(_ctx(SPANS)) == \
        pytest.approx((3 + 3 + 1) / 2)


@pytest.mark.parametrize("name", ["gather_ms", "h2d_ms", "h2d_mb"])
def test_no_span_reads_nothing(name):
    """A program without the assembly spans (one that predates them) gives
    no reading, and the reader does not raise."""
    plain = [s for s in SPANS if s["name"] == "round.step"]
    assert harness.load_reader(name)(_ctx(plain)) is None
    assert harness.load_reader(name)(_ctx([])) is None


def test_traced_run_reads_the_assembly_metrics():
    res = run_tiny("mnist_t2.paper", trace=True)
    assert res["correct"]
    m = res["metrics"]
    for name in ("gather_ms", "h2d_ms", "h2d_mb", "assemble_ms"):
        assert m[name]["value"] > 0, name
    assert m["gather_ms"]["value"] + m["h2d_ms"]["value"] \
        <= m["assemble_ms"]["value"]

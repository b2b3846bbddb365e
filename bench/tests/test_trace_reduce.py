"""The trace reduction on a small trace recorded on one TPU v5e chip
(``data/tpu_small.xplane.pb``: three calls of the vmapped tamper-check
kernel at CIFAR-10's D_o x d_c and three of a small matmul program), and
its interval arithmetic on hand-made intervals."""
import _paths

import trace_reduce

TRACE = _paths.BENCH / "tests" / "data" / "tpu_small.xplane.pb"


def test_union_merges_overlaps_and_keeps_gaps():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [
        (0, 3), (5, 9)]


def test_device_planes_are_numbered_tpus():
    assert trace_reduce._is_device_plane("/device:TPU:0")
    assert not trace_reduce._is_device_plane("/device:TPU:0 SparseCore 0")
    assert not trace_reduce._is_device_plane("/host:CPU")


def _plain_busy_ns(path):
    """Busy time by brute force: mark each nanosecond-free boundary."""
    from jax.profiler import ProfileData
    ivs = []
    for plane in ProfileData.from_file(str(path)).planes:
        if trace_reduce._is_device_plane(plane.name):
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    ivs += [(int(e.start_ns), int(e.start_ns + e.duration_ns))
                            for e in line.events]
    points = sorted({p for iv in ivs for p in iv})
    busy = 0
    for a, b in zip(points, points[1:]):
        if any(s <= a and b <= e for s, e in ivs):
            busy += b - a
    return busy


def test_recorded_trace():
    red = trace_reduce.reduce_trace(str(TRACE))
    assert red["devices"] == 1
    assert 0 < red["busy_s"] <= red["span_s"]
    assert abs(red["busy_s"] - _plain_busy_ns(TRACE) * 1e-9) < 1e-9
    tamper = [k for k in red["op_s"] if "tamper" in k.lower()]
    assert tamper and sum(red["op_count"][k] for k in tamper) == 3
    assert 0 < len(red["device_ops"]) <= 10
    assert len(red["idle_gaps"]) <= 10
    assert all(g > 0 for _, g in red["idle_gaps"])

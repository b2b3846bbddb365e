"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to the device numbers
the benchmark reports: busy time (the union of the intervals in which an
operation ran on a device, averaged over the devices), the summed device
time of each operation name, the operations that took most time, and the
longest idle gaps, each labelled with what the host was doing then.

Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per executed HLO operation, named by the operation's HLO text.
An operation is reported by its instruction name, the text before `` = ``
(``%fusion.12``), with the start of its text kept beside it (``op_text``)
so that a reader can find a kernel by its custom call.  A host event that
overlaps a gap (the runtime's own trace events on ``/host:CPU``) labels
it; a gap that no host event overlaps is ``unattributed``.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
TEXT_CHARS = 400


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _is_device_plane(name: str) -> bool:
    rest = name[len(DEVICE_PREFIX):] if name.startswith(DEVICE_PREFIX) else ""
    return rest.isdigit()


def reduce_trace(path: str, top: int = 10) -> Optional[Dict]:
    """Device numbers of one trace file, or None when it holds no device
    operation."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    per_dev_busy: List[int] = []
    op_ns: Dict[str, int] = defaultdict(int)
    op_n: Dict[str, int] = defaultdict(int)
    op_text: Dict[str, str] = {}
    busy_all: List[Tuple[int, int]] = []
    host: List[Tuple[int, int, str]] = []
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            ivs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s, d = int(ev.start_ns), int(ev.duration_ns)
                    ivs.append((s, s + d))
                    name = ev.name.split(" = ", 1)[0]
                    op_ns[name] += d
                    op_n[name] += 1
                    if name not in op_text:
                        op_text[name] = ev.name[:TEXT_CHARS]
            if ivs:
                u = _union(ivs)
                per_dev_busy.append(sum(e - s for s, e in u))
                if not busy_all:
                    busy_all = u
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    s, d = int(ev.start_ns), int(ev.duration_ns)
                    if d > 0:
                        host.append((s, s + d, ev.name))
    if not per_dev_busy:
        return None
    gaps = [(busy_all[i][1], busy_all[i + 1][0])
            for i in range(len(busy_all) - 1)
            if busy_all[i + 1][0] > busy_all[i][1]]
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = []
    for s, e in gaps[:top]:
        best, best_ov = "unattributed", 0
        for hs, he, name in host:
            ov = min(e, he) - max(s, hs)
            if ov > best_ov:
                best, best_ov = name, ov
        labelled.append([best, (e - s) * 1e-9])
    ops = sorted(op_ns.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": sum(per_dev_busy) / len(per_dev_busy) * 1e-9,
        "devices": len(per_dev_busy),
        "span_s": (busy_all[-1][1] - busy_all[0][0]) * 1e-9,
        "op_s": {k: v * 1e-9 for k, v in op_ns.items()},
        "op_count": dict(op_n),
        "op_text": op_text,
        "device_ops": [[k, v * 1e-9] for k, v in ops[:top]],
        "idle_gaps": labelled,
    }

"""Telemetry sinks: where events go.

Every sink consumes plain-dict events (spans, per-round metric records,
run start/end markers).  Three implementations:

* :class:`JSONLSink` — one JSON object per line, crash-tolerant append: each
  event is flushed as a complete line, an existing file whose tail was torn
  by a crash is newline-healed before new events are appended, and the
  reader (:func:`read_jsonl`) skips torn/unparseable lines instead of
  failing — the same durability posture as the checkpoint layer, adapted to
  an append-only log.
* :class:`MemorySink` — in-process event list, for tests and programmatic
  inspection.
* :class:`ConsoleSink` — one uniform human-readable line per protocol round;
  the replacement for the drivers' historical ad-hoc ``verbose`` prints.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

import numpy as np


def materialize(event: Any) -> Any:
    """One up-front host materialization of an event tree.

    Array-like values (numpy or device arrays) are pulled with a **single**
    ``np.asarray`` each and converted to nested Python lists/scalars here,
    before serialization — the encoder never walks a device array
    element-by-element (the historical ``.item()``-per-scalar default
    encoder issued one device sync per element mid-``json.dumps``)."""
    if isinstance(event, dict):
        return {k: materialize(v) for k, v in event.items()}
    if isinstance(event, (list, tuple)):
        return [materialize(v) for v in event]
    if isinstance(event, (str, bool, int, float)) or event is None:
        return event
    if isinstance(event, np.generic):
        return event.item()
    if isinstance(event, np.ndarray) or hasattr(event, "__array__"):
        arr = np.asarray(event)
        return arr.item() if arr.ndim == 0 else arr.tolist()
    return event


def _jsonable(o: Any) -> Any:
    """Last-resort encoder for exotic types that survive materialization."""
    if hasattr(o, "item") and not hasattr(o, "__len__"):
        return o.item()
    if hasattr(o, "tolist"):
        return o.tolist()
    return str(o)


class Sink:
    """Event consumer.  ``emit`` must tolerate being called from multiple
    threads *in sequence* (the session serialises calls under its lock)."""

    def emit(self, event: Dict[str, Any]) -> None:
        raise NotImplementedError

    def close(self) -> None:
        pass


class MemorySink(Sink):
    """Collects events in a list (``sink.events``)."""

    def __init__(self) -> None:
        self.events: List[Dict[str, Any]] = []

    def emit(self, event: Dict[str, Any]) -> None:
        self.events.append(event)

    def of(self, kind: str) -> List[Dict[str, Any]]:
        """Events of one kind (``event == kind``)."""
        return [e for e in self.events if e.get("event") == kind]


class JSONLSink(Sink):
    """Append-only JSONL event log.

    Durability: every event is written as one complete line and flushed, so
    a crash can tear at most the line in flight.  On open, a pre-existing
    file that does not end in a newline (a torn tail) is healed with a
    single ``"\\n"`` so the next event starts on a fresh line — the torn
    line stays in the file (the reader skips it) but cannot corrupt events
    written after the restart.
    """

    def __init__(self, path: str, fsync: bool = False):
        self.path = path
        self._fsync = fsync
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        needs_heal = False
        if os.path.exists(path) and os.path.getsize(path) > 0:
            with open(path, "rb") as f:
                f.seek(-1, os.SEEK_END)
                needs_heal = f.read(1) != b"\n"
        self._f = open(path, "a", encoding="utf-8")
        if needs_heal:
            self._f.write("\n")
            self._f.flush()

    def emit(self, event: Dict[str, Any]) -> None:
        self._f.write(json.dumps(materialize(event), default=_jsonable) + "\n")
        self._f.flush()
        if self._fsync:
            os.fsync(self._f.fileno())

    def close(self) -> None:
        if not self._f.closed:
            self._f.flush()
            self._f.close()


def read_jsonl(path: str) -> List[Dict[str, Any]]:
    """Parse a JSONL event log, skipping torn/unparseable lines (a crash can
    leave at most one mid-write tear per process generation; healed files
    keep the torn fragment as its own line).  Returns the complete events in
    file order."""
    events: List[Dict[str, Any]] = []
    with open(path, "r", encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                events.append(json.loads(line))
            except json.JSONDecodeError:
                continue            # torn line — tolerated by contract
    return events


class ConsoleSink(Sink):
    """One uniform line per protocol round — the ``verbose=True``
    replacement.  Fields missing from a driver's record (e.g. vanilla SL has
    no selection) are simply omitted, so every driver shares one format
    instead of the historical three ad-hoc prints."""

    def __init__(self, stream=None):
        self._stream = stream

    def emit(self, event: Dict[str, Any]) -> None:
        if event.get("event") != "round":
            return
        parts = [f"[{event.get('run', '?')}] t={int(event.get('t', -1)):3d}"]
        acc = event.get("test_acc")
        parts.append(f"acc={acc:.4f}" if acc is not None else "acc=nan")
        if "selected" in event:
            parts.append(f"sel={event['selected']}")
        if "selected_honest" in event:
            parts.append(f"honest={event['selected_honest']}")
        if "accepted" in event:
            parts.append(f"accepted={event['accepted']}")
        if "detections" in event:
            parts.append(f"det={event['detections']}")
        if "train_loss" in event:
            parts.append(f"tloss={event['train_loss']:.4f}")
        if "val_losses" in event:
            vl = ",".join(f"{v:.4f}" for v in event["val_losses"])
            parts.append(f"vloss=[{vl}]")
        print(" ".join(parts), flush=True, file=self._stream)


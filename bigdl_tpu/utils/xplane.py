"""Minimal XSpace (xplane.pb) reader — no tensorflow/tensorboard dep.

``jax.profiler.trace`` writes its device timeline as an ``XSpace``
protobuf (``plugins/profile/<run>/<host>.xplane.pb``). The only offline
consumers of that format are tensorboard plugins this container doesn't
ship, so ``scripts/backward_roofline.py`` needs a reader of its own. The
schema is tiny and stable (tsl/profiler/protobuf/xplane.proto), so this
module hand-decodes the protobuf wire format for exactly the fields the
roofline join needs: planes → lines → events, with per-plane event
metadata (op/fusion names) and durations in picoseconds.

Wire-format background: a protobuf message is a stream of
(tag, payload) pairs; ``tag = field_number << 3 | wire_type`` with
wire_type 0 = varint, 1 = fixed64, 2 = length-delimited (submessages,
strings, packed repeated), 5 = fixed32. Unknown fields are skipped, so
schema additions can't break the reader.
"""

from __future__ import annotations

import os
from typing import Dict, Iterator, List, Tuple

__all__ = ["parse_xspace", "find_xplane_pb", "device_planes", "op_totals",
           "XPlane", "XLine", "XEvent"]


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7
        if shift > 70:
            raise ValueError("malformed varint")


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """Yield (field_number, wire_type, value) over one message's bytes.
    Length-delimited values come back as memoryview-sliced bytes."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        fno, wt = tag >> 3, tag & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 1:
            val, i = buf[i:i + 8], i + 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wt == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield fno, wt, val


class XEvent:
    __slots__ = ("metadata_id", "offset_ps", "duration_ps",
                 "num_occurrences")

    def __init__(self):
        self.metadata_id = 0
        self.offset_ps = 0
        self.duration_ps = 0
        self.num_occurrences = 0


class XLine:
    __slots__ = ("name", "display_name", "events")

    def __init__(self):
        self.name = ""
        self.display_name = ""
        self.events: List[XEvent] = []


class XPlane:
    __slots__ = ("name", "lines", "event_names")

    def __init__(self):
        self.name = ""
        self.lines: List[XLine] = []
        # metadata id -> display_name or name (fusion/op label)
        self.event_names: Dict[int, str] = {}


def _parse_event(buf: bytes) -> XEvent:
    ev = XEvent()
    for fno, wt, val in _fields(buf):
        if fno == 1 and wt == 0:
            ev.metadata_id = val
        elif fno == 2 and wt == 0:
            ev.offset_ps = val
        elif fno == 3 and wt == 0:
            ev.duration_ps = val
        elif fno == 5 and wt == 0:
            ev.num_occurrences = val
    return ev


def _parse_line(buf: bytes) -> XLine:
    ln = XLine()
    for fno, wt, val in _fields(buf):
        if fno == 2 and wt == 2:
            ln.name = bytes(val).decode("utf-8", "replace")
        elif fno == 11 and wt == 2:
            ln.display_name = bytes(val).decode("utf-8", "replace")
        elif fno == 4 and wt == 2:
            ln.events.append(_parse_event(val))
    return ln


def _parse_event_metadata(buf: bytes) -> Tuple[int, str]:
    mid, name, display = 0, "", ""
    for fno, wt, val in _fields(buf):
        if fno == 1 and wt == 0:
            mid = val
        elif fno == 2 and wt == 2:
            name = bytes(val).decode("utf-8", "replace")
        elif fno == 4 and wt == 2:
            display = bytes(val).decode("utf-8", "replace")
    return mid, (display or name)


def _parse_plane(buf: bytes) -> XPlane:
    pl = XPlane()
    for fno, wt, val in _fields(buf):
        if fno == 2 and wt == 2:
            pl.name = bytes(val).decode("utf-8", "replace")
        elif fno == 3 and wt == 2:
            pl.lines.append(_parse_line(val))
        elif fno == 4 and wt == 2:
            # map<int64, XEventMetadata> entry: key=1, value=2
            key, meta = 0, None
            for efno, ewt, eval_ in _fields(val):
                if efno == 1 and ewt == 0:
                    key = eval_
                elif efno == 2 and ewt == 2:
                    meta = _parse_event_metadata(eval_)
            if meta is not None:
                mid, name = meta
                pl.event_names[mid or key] = name
    return pl


def parse_xspace(path: str) -> List[XPlane]:
    """Parse one ``*.xplane.pb`` file into its planes."""
    with open(path, "rb") as f:
        buf = f.read()
    planes = []
    for fno, wt, val in _fields(buf):
        if fno == 1 and wt == 2:
            planes.append(_parse_plane(val))
    return planes


def find_xplane_pb(profile_dir: str) -> "str | None":
    """Newest ``*.xplane.pb`` under a ``jax.profiler.trace`` output dir
    (the nested ``plugins/profile/<run>/`` layout), or None."""
    newest, newest_m = None, -1.0
    for root, _dirs, files in os.walk(profile_dir):
        for fn in files:
            if fn.endswith(".xplane.pb"):
                p = os.path.join(root, fn)
                m = os.path.getmtime(p)
                if m > newest_m:
                    newest, newest_m = p, m
    return newest


def device_planes(planes: List[XPlane]) -> List[XPlane]:
    """The accelerator planes (``/device:TPU:0`` etc.), host plane
    excluded; falls back to every plane carrying events when no name
    matches (so a renamed plane degrades to noise, not emptiness)."""
    dev = [p for p in planes
           if "TPU" in p.name.upper() or "GPU" in p.name.upper()]
    if dev:
        return dev
    return [p for p in planes
            if "HOST" not in p.name.upper()
            and any(ln.events for ln in p.lines)]


def op_totals(planes: List[XPlane]) -> Dict[str, Dict[str, float]]:
    """Aggregate event durations by op/fusion label across the given
    planes: label -> {"total_ps", "count"}. Events whose metadata id has
    no registered name fall under "<unnamed:ID>". Every line of a plane is
    walked, and a TPU plane carries the step, the program and the ops as
    separate lines (and a ``while`` covers its body): a label's own total
    is sound, a sum across labels counts time twice or more. Busy and idle
    time come from ``benchmark/lib/trace.py`` (union of intervals on the
    op line)."""
    totals: Dict[str, Dict[str, float]] = {}
    for pl in planes:
        for ln in pl.lines:
            for ev in ln.events:
                name = pl.event_names.get(
                    ev.metadata_id, f"<unnamed:{ev.metadata_id}>")
                ent = totals.setdefault(name, {"total_ps": 0.0,
                                               "count": 0})
                ent["total_ps"] += float(ev.duration_ps)
                ent["count"] += max(1, int(ev.num_occurrences))
    return totals

"""Capture a profiler trace of a slice of the window and reduce it to the
numbers the per-layer metrics read.

The reduction works on plain data, so it can be tested on a synthetic
plane: a trace is a list of planes ``{"name", "lines": [{"name",
"events": [(name, start_ns, dur_ns), ...]}]}``. Busy time is the UNION of
the intervals on a device's op line (``obs/attrib.py`` sums durations over
every line, which counts a step once per line it appears on).
"""

import glob
import math
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
HOST_TAG = "bench:"  # the benchmark's own TraceAnnotation names


def op_label(text):
    """A short name for a device op. The TPU's op line names an event by
    its whole HLO instruction (``%fusion.359 = (f32[3072]{...}, ...)
    fusion(...), kind=kOutput, calls=...``): keep the instruction's name
    without its number (a Pallas kernel's is its ``name=``), the fusion
    kind and the largest output shape, so that the same op of every layer
    falls under one label."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text
    name = re.sub(r"\.\d+$", "", head.lstrip("%"))
    outputs = re.split(r" [a-z][\w\-]*\(", rest, maxsplit=1)[0]
    big = max(re.finditer(r"\w+\[([\d,]*)\]", outputs), default=None,
              key=lambda m: math.prod(int(d) for d in m.group(1).split(",")
                                      if d))
    kind = re.search(r"kind=(\w+)", rest)
    return (name + ("/" + kind.group(1) if kind else "")
            + (" " + big.group(0) if big else ""))


# ---------------------------------------------------------------- capture
def start(log_dir):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host spans come from TraceAnnotation
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop_and_load(log_dir):
    import jax

    jax.profiler.stop_trace()
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return []
    data = jax.profiler.ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            name = op_label if line.name == OP_LINE else str
            events = [(name(e.name), int(e.start_ns), int(e.duration_ns))
                      for e in line.events]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


def annotate(name):
    """A host span in the profiler's own trace, beside jax's own."""
    import jax

    return jax.profiler.TraceAnnotation(HOST_TAG + name)


# -------------------------------------------------------------- reduction
def union(intervals):
    """Merged, sorted ``[(start, end), ...]`` of possibly overlapping or
    nested intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals):
    return sum(e - s for s, e in union(intervals))


def overlap(a, b):
    """Total length of the intersection of two interval sets."""
    a, b = union(a), union(b)
    i = j = 0
    out = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        out += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def device_planes(planes):
    return [p for p in planes if p["name"].startswith(DEVICE_PREFIX)]


def line_events(plane, line_name):
    return [ev for ln in plane["lines"] if ln["name"] == line_name
            for ev in ln["events"]]


def _spans(events):
    return [(s, s + d) for _, s, d in events]


def is_collective(name):
    return any(c in name for c in COLLECTIVES)


def reduce(planes):
    """Everything the metrics read, from one trace. Seconds throughout;
    per-device quantities are averaged over the device planes."""
    devs = device_planes(planes)
    ops = [line_events(p, OP_LINE) for p in devs]
    if not devs or not any(ops):
        return None
    t0 = min(s for evs in ops for _, s, _ in evs)
    t1 = max(s + d for evs in ops for _, s, d in evs)
    n = len(devs)
    out = {"n_devices": n, "window_s": (t1 - t0) / 1e9}
    out["busy_s"] = sum(total(_spans(evs)) for evs in ops) / n / 1e9
    by_name, exposed = {}, 0
    for evs in ops:
        for name, _, d in evs:
            by_name[name] = by_name.get(name, 0) + d
        coll = _spans([e for e in evs if is_collective(e[0])])
        comp = _spans([e for e in evs if not is_collective(e[0])])
        exposed += total(coll) - overlap(coll, comp)
    out["op_seconds"] = {k: v / n / 1e9 for k, v in by_name.items()}
    out["collective_exposed_s"] = exposed / n / 1e9
    modules = {}
    for p in devs:
        for name, _, d in line_events(p, MODULE_LINE):
            m = modules.setdefault(name, {"count": 0, "seconds": 0.0})
            m["count"] += 1
            m["seconds"] += d / 1e9
    out["modules"] = {k: {"count": v["count"] / n,
                          "seconds": v["seconds"] / n}
                      for k, v in modules.items()}
    out["idle_gaps"] = idle_gaps(planes, _spans(ops[0]), t0, t1)
    return out


def kernel_seconds(reduced, patterns):
    """Device seconds of the ops whose name contains any of
    ``patterns`` (a Pallas kernel shows under its ``name=``)."""
    return sum(v for k, v in reduced["op_seconds"].items()
               if any(p in k for p in patterns))


def module_stats(reduced, pattern):
    """(executions, seconds) of the compiled programs whose name contains
    ``pattern``, per device."""
    hits = [v for k, v in reduced["modules"].items() if pattern in k]
    return (sum(v["count"] for v in hits), sum(v["seconds"] for v in hits))


def idle_gaps(planes, busy, t0, t1, top=10, named=200):
    """The idle time of the first device, by what the host was doing: each
    of the ``named`` longest gaps between busy intervals goes to the
    innermost host span (the shortest one covering the gap's middle; the
    benchmark's own annotations and jax's), the rest to one bucket.
    ``[[name, seconds], ...]``, longest first."""
    host = sorted((s, s + d, name) for p in planes
                  if not p["name"].startswith(DEVICE_PREFIX)
                  for ln in p["lines"] for name, s, d in ln["events"])
    merged = union(busy)
    edges = [t0] + [x for s, e in merged for x in (s, e)] + [t1]
    gaps = sorted(((ge - gs, gs) for gs, ge in zip(edges[0::2], edges[1::2])
                   if ge > gs), reverse=True)
    by_name = {}
    for length, gs in gaps[:named]:
        mid = gs + length // 2
        cover = [(e - s, name) for s, e, name in host if s <= mid < e]
        name = min(cover)[1] if cover else "no_host_span"
        name = name[len(HOST_TAG):] if name.startswith(HOST_TAG) else name
        by_name[name] = by_name.get(name, 0) + length
    rest = sum(length for length, _ in gaps[named:])
    if rest:
        by_name[f"gaps_beyond_the_{named}_longest"] = rest
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / 1e9] for k, v in ranked]


def breakdown(reduced, top=10):
    ops = sorted(reduced["op_seconds"].items(), key=lambda kv: -kv[1])
    return {"device_ops": [[k, v] for k, v in ops[:top]],
            "idle_gaps": reduced["idle_gaps"]}

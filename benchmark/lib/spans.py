"""Read the program's own spans and counters.

``bigdl_tpu.obs.spans.span(name)`` is a profiler annotation, so a traced
run finds it in the planes ``trace.stop_and_load`` returns, on the device
trace's clock, as ``bigdl:<name>`` on the line of the thread that ran it.
Spans are found by exact name in the non-device planes; a child is a span
inside its parent's interval on the same line. Lines of different threads
can carry the same line name, so nothing here groups by line name. The
profiler keeps only spans that began and ended inside the session.

The reduction works on the plain plane lists of ``trace.py``, so it is
tested on synthetic ones. A program without these spans or counters (the
parent of the PR that added them) gives ``None`` everywhere.
"""

from .trace import DEVICE_PREFIX

TAG = "bigdl:"


def host_lines(planes):
    """The event list of every host thread line."""
    return [ln["events"] for p in planes or ()
            if not p["name"].startswith(DEVICE_PREFIX) for ln in p["lines"]]


def _named(events, name):
    """Sorted ``[(start_ns, end_ns), ...]`` of one line's spans ``name``."""
    return sorted((s, s + d) for n, s, d in events if n == TAG + name)


def mean(values):
    return sum(values) / len(values) if values else None


def durations_ms(planes, name):
    """Milliseconds of every span ``name``, all lines."""
    return [(e - s) / 1e6 for events in host_lines(planes)
            for s, e in _named(events, name)]


def self_ms(planes, parent, child):
    """For every span ``parent`` that holds a span ``child``: its
    milliseconds less those of the ``child`` spans inside it."""
    out = []
    for events in host_lines(planes):
        kids = _named(events, child)
        for s, e in _named(events, parent):
            inner = [ke - ks for ks, ke in kids if s <= ks and ke <= e]
            if inner:
                out.append((e - s - sum(inner)) / 1e6)
    return out


def since_ms(planes, first, last):
    """For every span ``last``: milliseconds from the start of the nearest
    span ``first`` that began before it on its line, to its end."""
    out = []
    for events in host_lines(planes):
        starts = [s for s, _ in _named(events, first)]
        for s, e in _named(events, last):
            before = [t for t in starts if t <= s]
            if before:
                out.append((e - max(before)) / 1e6)
    return out


def counter_ratio(numerator, denominator):
    """``numerator / denominator`` of two counters of the program's
    registry (the engine's, which ``/metrics`` shows), over the whole
    process; ``None`` while the denominator has counted nothing."""
    from bigdl_tpu.obs.metrics import get_registry

    reg = get_registry()
    den = reg.counter(denominator).value
    return reg.counter(numerator).value / den if den else None

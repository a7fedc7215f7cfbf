"""Command A+'s decode step's share of its memory roofline: the least
seconds the chip could take for the bytes any bf16 step has to move
(``lib/command_a_counts.py``: attention, router and shared-expert weights
and the head's rows once; each held expert that a live token chose, once;
the live rows of the global layer's cache and min(pos, window) rows of
every ring) at the peak HBM rate, over the device seconds of one execution
of the decode-step program, %. Experts touched, live positions and ring
rows a step are the engine's counters over the whole process.
Memory-bound: at 32 tokens a step every weight read is used for at most 64
operations an element. A program without the counters reads nothing."""
from benchmark.lib import command_a_counts as counts, trace


def read(run):
    r = run["reduced"]
    if r is None or run["peaks"] is None:
        return None
    name = run["config"]["serve"]["programs"]["decode_step"]
    count, seconds = trace.module_stats(r, name)
    least = counts.mean_step_bytes(run["config"]["model"])
    if least is None or not count:
        return None
    return (100.0 * sum(least.values()) / run["peaks"]["hbm_bytes_per_s"]
            * count / seconds)

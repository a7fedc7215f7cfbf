"""The SambaY decode step's share of its memory roofline: the least
seconds the chip could take for the bytes any bf16 step has to move
(``lib/sambay_counts.py``: every matmul weight once; the shared cache's
live rows for the layer that writes it and for each cross layer; each
window layer's valid ring rows; the live slots' state read and written)
at the peak HBM rate, over the device seconds of one execution of the
decode-step program, %. Positions, ring rows and slots a step are the
engine's counters over the whole process. Memory-bound: two operations
for each 2-byte element read. A program without the window counter (any
before the PR that added it, or a model without window layers) reads
nothing."""
from benchmark.lib import sambay_counts, trace


def read(run):
    r = run["reduced"]
    if r is None or run["peaks"] is None:
        return None
    name = run["config"]["serve"]["programs"]["decode_step"]
    count, seconds = trace.module_stats(r, name)
    least = sambay_counts.mean_step_bytes(run["config"]["model"])
    if least is None or not count:
        return None
    return (100.0 * sum(least) / run["peaks"]["hbm_bytes_per_s"]
            * count / seconds)

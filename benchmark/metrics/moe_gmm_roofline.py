"""The grouped matmuls' share of their roofline in a training step of
routed experts: the least time the chip could take for the held rows the
model's counters counted (``lib/glm_counts.py``: the rows a step, summed
over routed layers; forward w13 and w2 at every forward call, twice a step
under remat, and the backward's dx and dW; bytes of each call's operands
and result once; the larger of FLOPs over peak FLOP/s and bytes over peak
bytes/s) over the device seconds of jax's ``gmm`` and ``tgmm`` ops in the
traced steps, %. A program without the counters reads nothing."""
from benchmark.lib import glm_counts as counts, trace


def read(run):
    r = run["reduced"]
    if r is None or run["peaks"] is None or not run.get("traced_steps"):
        return None
    model = run["config"]["model"]
    rows = counts.rows_per_step(run.get("train_counters"),
                                run.get("counted_steps"))
    seconds = trace.kernel_seconds(r, counts.GMM_KERNELS)
    if not rows or not seconds:
        return None
    calls = counts.forward_calls(run["config"])
    least = max(counts.gmm_step_flops(model, rows, calls)
                / run["peaks"]["bf16_flops"],
                counts.gmm_step_bytes(model, rows, calls)
                / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * run["traced_steps"] / seconds

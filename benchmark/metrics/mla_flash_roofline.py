"""The flash kernels' share of their roofline at latent attention's
heads (20 x 256 in GLM-4.7-Flash): the least time the chip could take for
the operations and bytes the kernels are asked for in the traced steps
(``lib/glm_counts.py``: per (row, head) and layer, causal-half matmuls of
seq^2 x width, two a forward call and five in the backward; every layer's
forward runs twice a step under remat, and both calls are counted; the
larger of FLOPs over peak FLOP/s and bytes over peak bytes/s) over the
device seconds of the ops ``flash_fwd``, ``flash_dq``, ``flash_dkv``, %."""
from benchmark.lib import glm_counts as counts, trace


def read(run):
    r = run["reduced"]
    if r is None or run["peaks"] is None or not run.get("traced_steps"):
        return None
    seconds = trace.kernel_seconds(r, counts.FLASH_KERNELS)
    if not seconds:
        return None
    model, batch = run["config"]["model"], run["batch"] // run["chips"]
    calls = counts.forward_calls(run["config"])
    least = max(
        counts.flash_step_flops(model, batch, run["seq_len"], calls)
        / run["peaks"]["bf16_flops"],
        counts.flash_step_bytes(model, batch, run["seq_len"], calls)
        / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * run["traced_steps"] / seconds

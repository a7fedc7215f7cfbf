"""The flash kernels' share of their roofline: the least time the chip
could take for the operations and bytes the algorithm needs in the traced
steps (lib/flops.py: the larger of FLOPs over peak FLOP/s and bytes over
peak bytes/s; compute-bound at 4,096 tokens) over the kernels' device
time, %."""
from benchmark.lib import flops, trace


def read(run):
    r = run["reduced"]
    if r is None or run["peaks"] is None or not run.get("traced_steps"):
        return None
    seconds = trace.kernel_seconds(r, flops.FLASH_KERNELS)
    if not seconds:
        return None
    model, per_chip = run["config"]["model"], run["batch"] // run["chips"]
    least = max(
        flops.flash_train_flops(model, per_chip, run["seq_len"])
        / run["peaks"]["bf16_flops"],
        flops.flash_train_bytes(model, per_chip, run["seq_len"])
        / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * run["traced_steps"] / seconds

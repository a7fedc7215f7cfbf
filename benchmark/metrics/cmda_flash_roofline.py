"""The flash forward kernels' share of their roofline in Command A+'s
prefill, %: the least seconds the chip could take for the attention's
operations and bytes at 128 query heads of 128 (``lib/command_a_counts.py``:
4 x 16,384 x the band s x w - w^2 / 2 on each window layer and the
triangle s^2 / 2 on the global layer, a prompt of s real tokens; q, k, v
and the output once; the larger of FLOPs over peak FLOP/s and bytes over
peak bytes/s) for the prompts the traced slice prefilled, over the device
seconds of the ops named ``flash_fwd`` (``flash_fwd_window`` among them)
in the slice. The prompts are those of the engine's ``prefill_tokens_<n>``
spans; a program without the kernels or the span reads nothing."""
from benchmark.lib import command_a_counts as counts, trace

KERNEL = "flash_fwd"


def read(run):
    r = run["reduced"]
    if r is None or run["peaks"] is None:
        return None
    seconds = trace.kernel_seconds(r, (KERNEL,))
    prompts = counts.slice_prompt_tokens(run.get("planes"))
    model, peaks = run["config"]["model"], run["peaks"]
    least = sum(max(counts.flash_flops(model, s) / peaks["bf16_flops"],
                    counts.flash_bytes(model, s) / peaks["hbm_bytes_per_s"])
                for s in prompts)
    if not seconds or not least:
        return None
    return 100.0 * least / seconds

"""The windowed flash forward kernel's share of its roofline, %: the least
seconds the chip could take for the band's operations and bytes
(``lib/smallthinker_counts.py``: 4 x 128 x 28 x (s x w - w^2 / 2) a window
layer and prompt of s > w real tokens, q, k, v and the output once; the
larger of FLOPs over peak FLOP/s and bytes over peak bytes/s) for the
prompts the traced slice prefilled, over the device seconds of
``flash_fwd_window`` in the slice. The prompts are those of the engine's
``prefill_tokens_<n>`` spans; a program without the kernel or the span
reads nothing."""
from benchmark.lib import smallthinker_counts as counts, trace

KERNEL = "flash_fwd_window"


def read(run):
    r = run["reduced"]
    if r is None or run["peaks"] is None:
        return None
    seconds = trace.kernel_seconds(r, (KERNEL,))
    prompts = counts.slice_prompt_tokens(run.get("planes"))
    model, peaks = run["config"]["model"], run["peaks"]
    least = sum(max(counts.window_band_flops(model, s) / peaks["bf16_flops"],
                    counts.window_band_bytes(model, s)
                    / peaks["hbm_bytes_per_s"]) for s in prompts)
    if not seconds or not least:
        return None
    return 100.0 * least / seconds

"""Closed-form parameter and byte counts of the SambaY block
(``bigdl_tpu.models.sambay_lm``), from the configuration file's
constructor arguments alone; tests/benchmark_tests hold them to the sizes
of the program's own trees. Kept with the benchmark so that no PR that
claims a gain can change the numerator of a utilization. The last
function puts the engine's counters into them."""

import math

from . import spans

BF16 = 2
F32 = 4


def _dims(model):
    d = model["d_model"]
    head = d // model["num_heads"]
    return {"d": d, "ff": model["d_ff"], "inner": 2 * d,
            "state": model.get("d_state", 16), "conv": model.get("d_conv", 4),
            "rank": math.ceil(d / 16), "kv": model["num_kv_heads"] * head}


def layer_kinds(model):
    """The mixer of every layer, as the model builds them: Mamba on even
    layers up to L/2 (which emits the memory), window attention on odd
    layers below it, full attention at L/2 + 1 (the shared cache), then
    gated memory units (even) and Q-only cross-attention (odd)."""
    half = model["num_layers"] // 2
    kinds = []
    for l in range(model["num_layers"]):
        if l > half + 1:
            kinds.append("cross" if l % 2 else "gmu")
        elif l % 2 == 0:
            kinds.append("mamba")
        else:
            kinds.append("full" if l == half + 1 else "window")
    return kinds


def mixer_params(model, kind, matmul_only=False):
    """Parameters of one mixer; with ``matmul_only`` those that sit in a
    matrix multiplication (no bias, norm, conv tap, pole, skip or lambda
    vector)."""
    m = _dims(model)
    d, inner, kv = m["d"], m["inner"], m["kv"]
    head = d // model["num_heads"]
    if kind == "mamba":
        mat = (d * 2 * inner + inner * (m["rank"] + 2 * m["state"])
               + m["rank"] * inner + inner * d)
        rest = (m["conv"] * inner + inner   # conv taps and bias
                + inner                     # b_dt
                + m["state"] * inner        # a_log
                + inner)                    # d
    elif kind == "gmu":
        mat, rest = 2 * d * inner, 0
    else:
        lam_and_norm = 4 * head + 2 * head
        if kind == "cross":
            mat, rest = 2 * d * d, 2 * d + lam_and_norm
        else:
            mat = 2 * d * d + 2 * d * kv
            rest = 2 * d + 2 * kv + lam_and_norm
    return mat if matmul_only else mat + rest


def params(model, matmul_only=False):
    """Every parameter of the model (the tied embedding once); with
    ``matmul_only`` every weight a decode step multiplies by: the
    mixers' and the MLPs' matrices and the output head."""
    d, ff = model["d_model"], model["d_ff"]
    total = d * model["vocab"] + (0 if matmul_only else 2 * d)  # + ln_f
    for kind in layer_kinds(model):
        total += mixer_params(model, kind, matmul_only) + 3 * d * ff
        if not matmul_only:
            total += 4 * d  # ln1, ln2: weight and bias
    return total


def cache_row_bytes(model):
    """One position of one attention layer's cache: K and V of every KV
    head, bf16."""
    return 2 * _dims(model)["kv"] * BF16


def slot_bytes_by_kind(model, max_len):
    """What one decode slot holds, by kind of leaf."""
    m, kinds = _dims(model), layer_kinds(model)
    n_mamba = kinds.count("mamba")
    return {"kv_full": cache_row_bytes(model) * max_len,
            "kv_window": (kinds.count("window") * cache_row_bytes(model)
                          * model["window"]),
            "ssm_state": n_mamba * m["state"] * m["inner"] * F32,
            "conv_state": n_mamba * (m["conv"] - 1) * m["inner"] * BF16}


def step_cache_bytes(model, live_positions, window_positions, live_slots):
    """The cache and state bytes any decode step must move: the shared
    cache's live rows once for the layer that writes it and once for each
    cross layer, each window layer's valid ring rows, and the live slots'
    scan state and convolution history read and written."""
    kinds = layer_kinds(model)
    state = slot_bytes_by_kind(model, 0)
    return (cache_row_bytes(model)
            * ((1 + kinds.count("cross")) * live_positions
               + kinds.count("window") * window_positions)
            + 2 * live_slots * (state["ssm_state"] + state["conv_state"]))


def step_weight_bytes(model):
    """Every matmul weight once, bf16."""
    return BF16 * params(model, matmul_only=True)


def mean_step_bytes(model):
    """(weight, cache and state) bytes a mean decode step of this process
    had to move, from the engine's counters over its steps: live
    positions, ring rows and live slots (tokens emitted) a step. ``None``
    where a counter has counted nothing: a program from before the window
    counter, or a model without window layers."""
    per_step = [spans.counter_ratio(name, "decode_steps_total")
                for name in ("decode_live_positions_total",
                             "decode_window_positions_total",
                             "generated_tokens_total")]
    if not all(per_step):
        return None
    return step_weight_bytes(model), step_cache_bytes(model, *per_step)

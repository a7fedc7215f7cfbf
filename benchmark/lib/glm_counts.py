"""Closed-form parameter, operation and byte counts of GLM-4.7-Flash's
block as it trains (``bigdl_tpu.models.hybrid_moe_lm`` with ``mixer:
"mla"``): latent attention, a leading dense SwiGLU layer, routed layers of
held experts with a shared one, the MTP module and both heads, from the
configuration file's constructor arguments alone;
tests/benchmark_tests/test_benchmark_glm.py holds them to the sizes of the
program's own tree. Kept with the benchmark so that no PR that claims a
gain can change the numerator of a utilization. The routed rows a token
costs come from the model's training counters (``run["train_counters"]``,
over the ``run["counted_steps"]`` steps whose loss the Optimizer fetched),
not from their expected value."""

BF16 = 2
F32 = 4

# the names of the kernels: the program's three flash kernels, jax's
# grouped matmuls (``tgmm``, the weights' gradient, holds ``gmm``)
FLASH_KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")
GMM_KERNELS = ("gmm",)


def _qk(model):
    return model["qk_nope_dim"] + model["qk_rope_dim"]


def mla_params(model, matmul_only=False):
    """One latent-attention mixer: W_qa, W_qb, W_kva, W_kvb, W_o, and the
    two latent norms."""
    d, h = model["d_model"], model["num_heads"]
    ql, kl = model["q_lora_rank"], model["kv_lora_rank"]
    mat = (d * ql + ql * h * _qk(model) + d * (kl + model["qk_rope_dim"])
           + kl * h * (model["qk_nope_dim"] + model["v_head_dim"])
           + h * model["v_head_dim"] * d)
    return mat if matmul_only else mat + ql + kl


def expert_params(model):
    """One routed expert: the SwiGLU's three matrices."""
    return 3 * model["d_model"] * model["expert_width"]


def dense_ffn_params(model):
    return 3 * model["d_model"] * model["dense_width"]


def routed_beside_params(model, matmul_only=False):
    """A routed layer's FFN outside its routed experts: the shared expert
    and the router (and its selection bias)."""
    d, e = model["d_model"], model["num_experts"]
    mat = 3 * d * model["shared_experts"] * model["expert_width"] + d * e
    return mat if matmul_only else mat + e


def routed_layers(model):
    """Routed layers a token runs through: the main model's after the
    dense ones, and the MTP module's."""
    return model["num_layers"] - model["first_k_dense"] + model["mtp_layers"]


def params(model):
    """Every parameter this chip holds."""
    d, norms = model["d_model"], 2 * model["d_model"]
    routed = (mla_params(model) + routed_beside_params(model)
              + model["experts_held"] * expert_params(model) + norms)
    dense = mla_params(model) + dense_ffn_params(model) + norms
    mtp = model["mtp_layers"] * (routed + 2 * d * d + 3 * d)
    return (2 * model["vocab"] * d + d + model["first_k_dense"] * dense
            + (model["num_layers"] - model["first_k_dense"]) * routed + mtp)


def attention_flops_per_token(model, seq_len):
    """QK^T and PV of one layer for one token, causal (a mean of half the
    row): 2 x 2 x heads x width x seq / 2."""
    return 2 * model["num_heads"] * _qk(model) * seq_len


def forward_flops_per_token(model, seq_len, held_rows):
    """The forward's operations for one trained token: 2 a matmul
    parameter the token runs through, attention by the causal half, and
    ``held_rows`` (routed rows a token and routed layer on the experts
    held: the counters' ``held picks / tokens / routed layers``) expert
    SwiGLUs in every routed layer. Both heads, ``W_eh`` and the MTP
    module's block included."""
    d, layers = model["d_model"], model["num_layers"] + model["mtp_layers"]
    mixers = layers * (2 * mla_params(model, True)
                       + attention_flops_per_token(model, seq_len))
    dense = model["first_k_dense"] * 2 * dense_ffn_params(model)
    routed = routed_layers(model) * 2 * (
        routed_beside_params(model, True) + held_rows * expert_params(model))
    heads = (1 + model["mtp_layers"]) * 2 * d * model["vocab"]
    return mixers + dense + routed + heads + model["mtp_layers"] * 4 * d * d


def train_flops_per_token(model, seq_len, held_rows):
    """Forward and backward: three times the forward. Recomputed
    operations (the layers' remat, the flash backward's second QK^T) are
    not counted."""
    return 3 * forward_flops_per_token(model, seq_len, held_rows)


def held_rows_per_token(model, counters, tokens):
    """Routed rows a token and routed layer cost on the experts held: the
    counters' held picks over the ``tokens`` of the steps they counted;
    None without the counters (a program before them)."""
    held = (counters or {}).get("moe_train_held_picks_total")
    if not held or not tokens:
        return None
    return held / (tokens * routed_layers(model))


def forward_calls(cfg):
    """How often a training step runs each layer's forward: twice under
    remat (the backward recomputes it)."""
    return 2 if cfg["train"].get("remat") else 1


def flash_step_flops(model, batch, seq_len, fwd_calls):
    """The flash kernels' operations in one training step, all layers and
    the MTP block: per (row, head) causal-half matmuls of seq^2 x width,
    two a forward call (QK^T, PV) and five in the backward (QK^T again,
    because P is never stored, dP, dQ, dK, dV)."""
    per_head = (2 * fwd_calls + 5) * seq_len * seq_len * _qk(model)
    layers = model["num_layers"] + model["mtp_layers"]
    return layers * batch * model["num_heads"] * per_head


def flash_step_bytes(model, batch, seq_len, fwd_calls):
    """Bytes the same kernels must move at least once, bf16: a forward
    call reads q, k, v and writes o; the backward reads q, k, v, o, do and
    writes dq, dk, dv."""
    per_head = (4 * fwd_calls + 8) * seq_len * _qk(model) * BF16
    layers = model["num_layers"] + model["mtp_layers"]
    return layers * batch * model["num_heads"] * per_head


def gmm_step_flops(model, rows, fwd_calls):
    """The grouped matmuls' operations in one training step: ``rows``
    held rows (summed over the routed layers), 2 x 3 x d x width a row for
    a forward call (w13 and w2), and 4 x that in the backward (dx and dW
    of both)."""
    d, w = model["d_model"], model["expert_width"]
    return (fwd_calls + 2) * 6 * rows * d * w


def gmm_step_bytes(model, rows, fwd_calls):
    """Bytes the grouped matmuls move at least once in one training step:
    each call's operands and result once, rows and weights bf16, the
    products' f32 outputs and the gradients of them f32. ``rows`` summed
    over the routed layers; each layer's held weights read by each call."""
    d, w = model["d_model"], model["expert_width"]
    weights = model["experts_held"] * expert_params(model) * BF16
    layers = routed_layers(model)
    fwd_rows = (d * BF16 + 2 * w * F32) + (w * BF16 + d * F32)
    bwd_rows = ((d * F32 + w * BF16) + (w * BF16 + d * F32)
                + (2 * w * F32 + d * BF16) + (d * BF16 + 2 * w * F32))
    # each forward call reads both stacks; the backward reads each stack
    # once (dx) and writes each stack's gradient once (dW)
    return (rows * (fwd_calls * fwd_rows + bwd_rows)
            + layers * weights * (fwd_calls + 2))


def rows_per_step(counters, steps):
    """Held rows a step, summed over routed layers: the counters' held
    picks over the ``steps`` they counted."""
    held = (counters or {}).get("moe_train_held_picks_total")
    if not held or not steps:
        return None
    return held / steps

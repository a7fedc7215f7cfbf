"""Closed-form operation and byte counts, from the configuration file's
constructor arguments alone. Kept with the benchmark so that no PR that
claims a gain can change the numerator of a utilization."""


# the names the program gives its three flash-attention kernels
FLASH_KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def matmul_params(model):
    """Parameters that sit in a matrix multiplication: the four attention
    projections and the two MLP matrices of every layer, plus the (tied)
    output head. Biases, norms and the embedding lookup do no matmul."""
    d, ff = model["d_model"], model["d_ff"]
    hd = d // model["num_heads"]
    kv = model["num_kv_heads"] * hd
    layer = 2 * d * d + 2 * d * kv + 2 * d * ff
    return model["num_layers"] * layer + d * model["vocab"]


def train_flops_per_token(model, seq_len):
    """Forward + backward operations one trained token requires: 6 per
    matmul parameter, plus causal attention at half the square (QK^T and
    PV are 2 * 2 * seq * d_model forward over the full square; half of it
    under the causal mask; three times that with the backward).
    Recomputed operations (remat, the flash backward's second QK^T) are
    not counted."""
    attn = model["num_layers"] * 6 * seq_len * model["d_model"]
    return 6 * matmul_params(model) + attn


def flash_train_flops(model, batch, seq_len):
    """Operations the flash-attention algorithm needs for one training
    step, all layers: per (row, head) seven causal-half matmuls of
    seq^2 * head_dim multiply-adds (forward QK^T, PV; backward QK^T again
    because P is never stored, dP, dQ, dK, dV)."""
    hd = model["d_model"] // model["num_heads"]
    per_head = 7 * seq_len * seq_len * hd  # 2 flops per MAC, half square
    return model["num_layers"] * batch * model["num_heads"] * per_head


def flash_train_bytes(model, batch, seq_len, bytes_per_el=2):
    """Bytes the same kernels must move at least once: forward reads
    q, k, v and writes o; backward reads q, k, v, o, do and writes
    dq, dk, dv (k and v at the expanded head count, as the kernel is
    called)."""
    hd = model["d_model"] // model["num_heads"]
    per_head = 12 * seq_len * hd * bytes_per_el
    return model["num_layers"] * batch * model["num_heads"] * per_head

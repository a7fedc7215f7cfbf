"""The plain reference of Solar-Open2-250B's architecture (``model_type:
solar_open2``): Kimi Delta Attention layers (arXiv:2510.26692) after each
gated NoPE GQA layer, a routed feed-forward layer in every block, written
layer by layer for ONE sequence in ``jax.numpy`` at float32 with
``default_matmul_precision("highest")``: the delta rule token by token in a
sequential ``lax.scan`` (no chunks), a full masked softmax for attention,
every held expert applied to every token in a plain loop and weighted by
what the router gave it, no cache, no kernels. It reads the constructor
arguments from the configuration file and the weights by the names of the
program's tree, and shares no code with ``bigdl_tpu``.

Layer l: ``h += Mixer_l(RMSNorm1(h)); h += FFN_l(RMSNorm2(h))``; a softmax
layer iff ``l % (gqa_interval + 1) == 0``. After the last layer a final
RMSNorm and the untied head over the vocabulary rows held.

KDA, token t, head h (keys and values ``head_dim`` wide; ``S`` float32,
``S_0 = 0``)::

    q, k, v = silu(causal_depthwise_conv(x W_qkv)) split three ways
    q = q / |q| * head_dim^-1/2,  k = k / |k|          (eps 1e-6)
    alpha_t = exp(-exp(A_log[h]) softplus((x Wa_down Wa_up)[h, c]
                                          + dt_bias[h, c]))
    beta_t = 2 sigmoid(x Wb)[h]
    S' = diag(alpha_t) S;  u = beta_t (v_t - S'^T k_t)
    S = S' + k_t u^T;      o_t = S^T q_t
    out = (RMSNorm_head(o_t) * sigmoid(x Wg_down Wg_up)) Wo

Routed FFN: ``s = sigmoid(x Wr)`` over all ``num_experts``; the ``top_k``
chosen are the largest of ``s + b``; ``w_e = s_e / sum_chosen s`` times
``routed_scale``; ``out = sum_{chosen e held here} w_e SwiGLU_e(x) +
Shared(x)``. The program's tree holds experts ``share * experts_held ..``
only, so the sum runs over those: what the absent experts would have added
is left out here as there (one chip's share of the deployment).

One expert's weights are cast to f32 at a time, and the head a quarter of
its rows at a time, so the reference fits beside a server that holds
6.6 GB of bf16 weights and 1.9 GB of slots. ``routes`` returns, beside the
logits, the experts each token chose in each layer.
"""

import functools
import math

import jax
import jax.numpy as jnp

HEAD_BLOCKS = 4
_HI = functools.partial(jax.default_matmul_precision, "highest")


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(weight, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda(m, x, n_heads, eps):
    """x (s, d) -> (s, d)."""
    s = x.shape[0]
    taps, n3 = m["conv_w"].shape
    n = n3 // 3
    hd = n // n_heads
    z = x @ m["w_qkv"]
    zp = jnp.concatenate([jnp.zeros((taps - 1, n3)), z], axis=0)
    z = _silu(sum(m["conv_w"][j] * zp[j:j + s] for j in range(taps)))
    q, k, v = (z[:, i * n:(i + 1) * n].reshape(s, n_heads, hd)
               for i in range(3))
    q, k = _unit(q) / math.sqrt(hd), _unit(k)
    a = jnp.logaddexp((x @ m["wa_down"]) @ m["wa_up"] + m["dt_bias"], 0.0)
    alpha = jnp.exp(-jnp.exp(m["a_log"])[None, :, None]
                    * a.reshape(s, n_heads, hd))
    beta = 2.0 * _sigmoid(x @ m["wb"])                      # (s, heads)

    def step(state, row):
        q_t, k_t, v_t, alpha_t, beta_t = row
        state = alpha_t[:, :, None] * state
        u = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((n_heads, hd, hd)),
                        (q, k, v, alpha, beta))
    o = _rms_norm(m["norm"]["weight"], o, eps).reshape(s, n)
    return (o * _sigmoid((x @ m["wg_down"]) @ m["wg_up"])) @ m["wo"]


def _gated_gqa(a, x, n_heads, n_kv, hd):
    s = x.shape[0]
    q = (x @ a["wq"]).reshape(s, n_heads, hd)
    k = jnp.repeat((x @ a["wk"]).reshape(s, n_kv, hd), n_heads // n_kv, 1)
    v = jnp.repeat((x @ a["wv"]).reshape(s, n_kv, hd), n_heads // n_kv, 1)
    sc = jnp.einsum("ihd,jhd->hij", q, k) / math.sqrt(hd)
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hij,jhd->ihd", p, v).reshape(s, n_heads * hd)
    return (o * _sigmoid(x @ a["wg"])) @ a["wo"]


@functools.partial(jax.jit, static_argnums=(2, 3))
def _mix_and_route(p, x, kind, dims):
    """The layer up to its experts: (x after the mixer, the FFN's input,
    the shared expert's output, chosen experts (s, k), their weights)."""
    n_heads, n_kv, hd, kda_heads, top_k, eps, scale = dims
    with _HI():
        p = _f32(p)
        h = _rms_norm(p["ln1"]["weight"], x, eps)
        if kind == "kda":
            x = x + _kda(p["mixer"], h, kda_heads, eps)
        else:
            x = x + _gated_gqa(p["mixer"], h, n_heads, n_kv, hd)
        h = _rms_norm(p["ln2"]["weight"], x, eps)
        f = p["ffn"]
        score = _sigmoid(h @ f["router"]["weight"])
        chosen = jnp.argsort(-(score + f["router"]["bias"]),
                             axis=-1)[:, :top_k]
        w = jnp.take_along_axis(score, chosen, axis=-1)
        w = scale * w / jnp.sum(w, axis=-1, keepdims=True)
        gu = h @ f["shared_w13"]
        half = gu.shape[-1] // 2
        shared = (_silu(gu[:, :half]) * gu[:, half:]) @ f["shared_w2"]
        return x, h, shared, chosen, w


@jax.jit
def _expert(w13, w2, h, coef):
    """One expert on every token, weighted: coef (s,) is the router's
    weight for this expert, 0 where a token did not choose it."""
    with _HI():
        gu = h @ w13.astype(jnp.float32)
        half = gu.shape[-1] // 2
        y = (_silu(gu[:, :half]) * gu[:, half:]) @ w2.astype(jnp.float32)
        return coef[:, None] * y


@jax.jit
def _head(rows, x):
    with _HI():
        return x @ rows.astype(jnp.float32).T


def routes(params, model_args, tokens):
    """(s,) token ids -> ((s, vocab) float32 logits, chosen experts
    (layers, s, top_k) int32 over all ``num_experts``)."""
    a = model_args
    n_heads, hd = a["num_heads"], a["head_dim"]
    dims = (n_heads, a["num_kv_heads"], hd, a.get("kda_heads") or n_heads,
            a["top_k"], a.get("rms_eps", 1e-5), a.get("routed_scale", 1.0))
    x = params["emb"]["weight"][jnp.asarray(tokens, jnp.int32)]
    x = x.astype(jnp.float32)
    all_chosen = []
    for l in range(a["num_layers"]):
        p = params["layers"][str(l)]
        kind = "kda" if l % (a["gqa_interval"] + 1) else "gqa"
        lean = dict(p, ffn={k: v for k, v in p["ffn"].items()
                            if k not in ("w13", "w2")})
        x, h, out, chosen, w = _mix_and_route(lean, x, kind, dims)
        held = p["ffn"]["w13"].shape[0]
        first = a.get("share", 0) * held
        for e in range(held):
            coef = jnp.sum(jnp.where(chosen == first + e, w, 0.0), axis=-1)
            out = out + _expert(p["ffn"]["w13"][e], p["ffn"]["w2"][e], h,
                                coef)
        x = x + out
        all_chosen.append(chosen)
    with _HI():
        x = _rms_norm(params["ln_f"]["weight"].astype(jnp.float32), x,
                      dims[5])
    head = params["head"]["weight"]
    step = -(-head.shape[0] // HEAD_BLOCKS)
    return (jnp.concatenate([_head(head[i:i + step], x)
                             for i in range(0, head.shape[0], step)],
                            axis=1), jnp.stack(all_chosen))


def logits(params, model_args, tokens):
    """(s,) token ids -> (s, vocab) float32 logits of one sequence."""
    return routes(params, model_args, tokens)[0]

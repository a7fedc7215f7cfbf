"""The plain reference of GLM-4.7-Flash's architecture (``model_type:
glm4_moe_lite``): multi-head latent attention in every layer, a dense
SwiGLU FFN in the first ``first_k_dense`` layers and a routed one after,
and one multi-token prediction module, written layer by layer for ONE
sequence in ``jax.numpy`` at float32 with
``default_matmul_precision("highest")``: a full masked softmax for
attention, every held expert applied to every token in a plain loop and
weighted by what the router gave it, no cache, no kernels. It reads the
constructor arguments from the configuration file and the weights by the
names of the program's tree, and shares no code with ``bigdl_tpu``.

Layer l: ``h += MLA(RMSNorm1(h)); h += FFN_l(RMSNorm2(h))``. After the last
layer a final RMSNorm and the untied head over the vocabulary rows held.

MLA (H heads; ranks ``q_lora_rank``, ``kv_lora_rank``; key widths
``qk_nope_dim + qk_rope_dim``, value width ``v_head_dim``)::

    c_q = RMSNorm_q(x W_qa);        q_h = c_q W_qb[h] = [q_nope | q_rope]
    [c_kv | k_rope] = x W_kva;      c_kv = RMSNorm_kv(c_kv)
    [k_nope_h | v_h] = c_kv W_kvb[h]
    q_h = [q_nope | rope(q_rope)],  k_h = [k_nope_h | rope(k_rope)]
    o_h = softmax(q_h k_h^T / sqrt(qk_nope_dim + qk_rope_dim), causal) v_h
    out = concat_h(o_h) W_o

``rope`` rotates at positions 0..s-1 with theta ``rope_theta``, channel
``i`` paired with ``i + rope/2``; ``k_rope`` is one key every head shares.

Routed FFN: ``s = sigmoid(x Wr)`` over all ``num_experts``; the ``top_k``
chosen are the largest of ``s + b`` (``b`` the selection bias, held fixed);
``w_e = routed_scale * s_e / sum_chosen s``; ``out = sum_{chosen e held
here} w_e SwiGLU_e(x) + Shared(x)``. The program's tree holds experts
``share * experts_held ..`` only, so the sum runs over those: what the
absent experts would have added is left out here as there (one chip's
share of the deployment).

MTP (DeepSeek-V3, arXiv:2412.19437 section 2.2, depth one)::

    h'_i = Block(W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)])
    logits_mtp_i = Head(RMSNorm_f(h'_i))              (predicts t_{i+2})

for i = 0..s-2, ``h_i`` the last layer's output (before the final norm),
``Block`` a whole MLA + routed layer attending over those s-1 rows, ``Emb``
and ``Head`` the main model's. ``mean_nll`` is ``CE_main + mtp_weight *
CE_mtp``, each a mean over its positions.

Departures from the published description: none in the mathematics. The
rotary channels pair ``i`` with ``i + rope/2`` where the checkpoint's
layout interleaves them: a permutation of the columns of ``W_qb`` and
``W_kva``, the same function of other weights. The selection bias is not
updated (the aux-loss-free update is a training rule, not the forward).
"""

import functools
import math

import jax
import jax.numpy as jnp

_HI = functools.partial(jax.default_matmul_precision, "highest")


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(weight, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _latent_norm(weight, c_kv, eps):
    """RMSNorm of the key/value latent ``c_kv``."""
    return _rms_norm(weight, c_kv, eps)


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _swiglu(w13, w2, x):
    gu = x @ w13
    half = gu.shape[-1] // 2
    return (_silu(gu[:, :half]) * gu[:, half:]) @ w2


def _rotate(x, theta):
    """x (s, ..., r) rotated at positions 0..s-1."""
    r = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (r // 2,))
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def _mla(a, x, dims):
    heads, nope, rope, vd, rank, theta, eps = dims
    s = x.shape[0]
    c_q = _rms_norm(a["ln_q"]["weight"], x @ a["wq_a"], eps)
    q = (c_q @ a["wq_b"]).reshape(s, heads, nope + rope)
    kv = x @ a["wkv_a"]
    c_kv = _latent_norm(a["ln_kv"]["weight"], kv[:, :rank], eps)
    k_rope = _rotate(kv[:, rank:], theta)                     # (s, rope)
    kv = (c_kv @ a["wkv_b"]).reshape(s, heads, nope + vd)
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], theta)], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_rope[:, None, :], (s, heads, rope))], axis=-1)
    sc = jnp.einsum("ihd,jhd->hij", q, k) / math.sqrt(nope + rope)
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hij,jhd->ihd", p, kv[..., nope:]).reshape(s, heads * vd)
    return o @ a["wo"]


def _routed_scale(model_args):
    return model_args["routed_scale"]


def _dims(model_args):
    m = model_args
    return ((m["num_heads"], m["qk_nope_dim"], m["qk_rope_dim"],
             m["v_head_dim"], m["kv_lora_rank"], float(m["rope_theta"]),
             m["rms_eps"]),
            (m["top_k"], _routed_scale(m), m["rms_eps"]))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _attend(p, x, dims, eps):
    """x after the layer's mixer, and the FFN's input."""
    with _HI():
        p = _f32(p)
        x = x + _mla(p["mixer"], _rms_norm(p["ln1"]["weight"], x, eps), dims)
        return x, _rms_norm(p["ln2"]["weight"], x, eps)


@jax.jit
def _dense(f, h):
    with _HI():
        f = _f32(f)
        return _swiglu(f["w13"], f["w2"], h)


@functools.partial(jax.jit, static_argnums=(2,))
def _route(f, h, route_dims):
    """The shared expert's output, chosen experts (s, k), their weights."""
    top_k, scale, _ = route_dims
    with _HI():
        f = {k: v for k, v in f.items() if k not in ("w13", "w2")}
        f = _f32(f)
        score = _sigmoid(h @ f["router"]["weight"])
        chosen = jnp.argsort(-(score + f["router"]["bias"]),
                             axis=-1)[:, :top_k]
        w = jnp.take_along_axis(score, chosen, axis=-1)
        w = scale * w / jnp.sum(w, axis=-1, keepdims=True)
        return _swiglu(f["shared_w13"], f["shared_w2"], h), chosen, w


def _expert(w13, w2, h, coef):
    """One expert on every token, weighted: coef (s,) is the router's
    weight for this expert, 0 where a token did not choose it."""
    y = _swiglu(w13.astype(jnp.float32), w2.astype(jnp.float32), h)
    return coef[:, None] * y


@functools.partial(jax.jit, static_argnums=(5,))
def _experts(w13, w2, h, chosen, w, first):
    """The sum over the held experts (global ids ``first``.. in the
    stacks ``w13``, ``w2``), in a loop, of each on every token weighted
    by what the router gave it."""
    def add(out, expert):
        j, w13_j, w2_j = expert
        coef = jnp.sum(jnp.where(chosen == first + j, w, 0.0), -1)
        return out + _expert(w13_j, w2_j, h, coef), None

    with _HI():
        return jax.lax.scan(add, jnp.zeros_like(h),
                            (jnp.arange(w13.shape[0]), w13, w2))[0]


def _block(p, x, model_args, dense):
    """(x after the layer, the experts each row chose (s, top_k); None in
    a dense layer)."""
    mla_dims, route_dims = _dims(model_args)
    x, h = _attend(p, x, mla_dims, model_args["rms_eps"])
    f = p["ffn"]
    if dense:
        return x + _dense(f, h), None
    out, chosen, w = _route(f, h, route_dims)
    first = model_args.get("share", 0) * model_args["experts_held"]
    return x + out + _experts(f["w13"], f["w2"], h, chosen, w, first), chosen


@functools.partial(jax.jit, static_argnums=(2,))
def _head(p, x, eps):
    with _HI():
        p = _f32(p)
        return _rms_norm(p["norm"], x, eps) @ p["head"].T


def _hidden(params, model_args, tokens):
    """The last layer's output (s, d), and the routed layers' choices."""
    x = jnp.take(params["emb"]["weight"], jnp.asarray(tokens),
                 axis=0).astype(jnp.float32)
    chosen = []
    for l in range(model_args["num_layers"]):
        x, c = _block(params["layers"][str(l)], x, model_args,
                      l < model_args["first_k_dense"])
        chosen += [] if c is None else [c]
    return x, chosen


def _mtp_input_tokens(tokens):
    """The tokens whose embeddings the MTP module reads, row i: t_{i+1}."""
    return tokens[1:]


@functools.partial(jax.jit, static_argnums=(3,))
def _mtp_input(m, emb_rows, h, eps):
    with _HI():
        m = _f32(m)
        e = _rms_norm(m["ln_e"]["weight"], emb_rows, eps)
        return jnp.concatenate([e, _rms_norm(m["ln_h"]["weight"], h, eps)],
                               axis=-1) @ m["w_eh"]


def _main_head(params, model_args, h):
    return _head({"norm": params["ln_f"]["weight"],
                  "head": params["head"]["weight"]}, h,
                 model_args["rms_eps"])


def _mtp_head(params, model_args, tokens, h):
    m, eps = params["mtp"], model_args["rms_eps"]
    emb = jnp.take(params["emb"]["weight"], _mtp_input_tokens(tokens),
                   axis=0).astype(jnp.float32)
    x = _mtp_input({k: m[k] for k in ("ln_e", "ln_h", "w_eh")}, emb,
                   h[:-1], eps)
    x, chosen = _block(m["layer"], x, model_args, False)
    return _head({"norm": m["ln_f"]["weight"],
                  "head": params["head"]["weight"]}, x, eps), chosen


def logits(params, model_args, tokens):
    """(s,) token ids -> (s, vocab) float32 next-token logits."""
    return _main_head(params, model_args,
                      _hidden(params, model_args, tokens)[0])


def routes(params, model_args, tokens):
    """(logits (s, vocab), MTP logits (s - 1, vocab), the experts each
    row chose in each routed layer: the main model's (s, top_k) each, then
    the MTP block's (s - 1, top_k)) of one sequence: MTP row i predicts
    token i + 2."""
    tokens = jnp.asarray(tokens)
    h, chosen = _hidden(params, model_args, tokens)
    mtp, mtp_chosen = _mtp_head(params, model_args, tokens, h)
    return _main_head(params, model_args, h), mtp, chosen + [mtp_chosen]


def heads(params, model_args, tokens):
    """``routes`` without the choices."""
    return routes(params, model_args, tokens)[:2]


def _nll(z, targets):
    z = z - jnp.max(z, axis=-1, keepdims=True)
    logp = z - jnp.log(jnp.sum(jnp.exp(z), axis=-1, keepdims=True))
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], -1))


def mean_nll(params, model_args, tokens, targets):
    """``CE_main + mtp_weight * CE_mtp`` of one sequence: the main term
    over every position (target ``targets[i]``), the MTP term over
    positions 0..s-2 (target ``targets[i + 1]``)."""
    main, mtp = heads(params, model_args, tokens)
    targets = jnp.asarray(targets)
    return float(_nll(main, targets)
                 + model_args["mtp_weight"] * _nll(mtp, targets[1:]))


# ---------------------------------------------------------------- gradient
# The gradient of ``mean_nll`` is jax's own ``vjp`` of the functions above,
# taken one block at a time: the forward keeps each block's input, and the
# backward recomputes one block from its input, so one block's residuals
# are alive at a time (a whole 4,096-row sequence's would not fit a chip).


def _frozen(model_args):
    return tuple(sorted(model_args.items()))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _block_vjp(p, x, ct, frozen, dense):
    with _HI():
        _, back = jax.vjp(
            lambda p, x: _block(p, x, dict(frozen), dense)[0], p, x)
        return back(ct)


@functools.partial(jax.jit, static_argnums=(5,))
def _head_vjp(norm, head, x, targets, scale, eps):
    """(the NLL of the head over ``x``, its gradient times ``scale`` with
    respect to the norm's scale, the head and ``x``)."""
    with _HI():
        loss, back = jax.vjp(lambda n, w, x: _nll(
            _head({"norm": n, "head": w}, x, eps), targets), norm, head, x)
        return loss, back(scale)


@functools.partial(jax.jit, static_argnums=(4,))
def _mtp_input_vjp(m, emb_rows, h, ct, eps):
    with _HI():
        _, back = jax.vjp(lambda m, e, h: _mtp_input(m, e, h, eps),
                          m, emb_rows, h)
        return back(ct)


def batch_grads(params, model_args, tokens, targets):
    """(the mean of ``mean_nll`` over the rows of ``tokens`` / ``targets``
    (b, s), its gradient with respect to every leaf of ``params``, a tree
    like it in float32): what a training step on that batch
    differentiates. Row by row and block by block (see above), each
    block's part added to the sum as it comes; the selection bias only
    orders the choice, so its gradient is zero."""
    tokens, targets = jnp.asarray(tokens), jnp.asarray(targets)
    rows, eps = tokens.shape[0], model_args["rms_eps"]
    frozen, first = _frozen(model_args), model_args["first_k_dense"]
    names = [str(l) for l in range(model_args["num_layers"])]
    layers, m = params["layers"], params["mtp"]
    w_head = params["head"]["weight"]
    inner = {k: m[k] for k in ("ln_e", "ln_h", "w_eh")}
    sums = {}

    def add(key, part):
        sums[key] = part if key not in sums else jax.tree_util.tree_map(
            jnp.add, sums[key], part)

    loss = 0.0
    for r in range(rows):
        toks, tgt = tokens[r], targets[r]
        x = jnp.take(params["emb"]["weight"], toks, axis=0).astype(
            jnp.float32)
        xs = []
        for l, name in enumerate(names):
            xs.append(x)
            x = _block(layers[name], x, model_args, l < first)[0]
        e_toks = _mtp_input_tokens(toks)
        e = jnp.take(params["emb"]["weight"], e_toks, axis=0).astype(
            jnp.float32)
        u = _mtp_input(inner, e, x[:-1], eps)
        v = _block(m["layer"], u, model_args, False)[0]
        main, (d_ln_f, d_head, dh) = _head_vjp(
            params["ln_f"]["weight"], w_head, x, tgt, 1.0 / rows, eps)
        mtp_w = model_args["mtp_weight"] / rows
        mtp, (d_ln_fm, d_head_m, dv) = _head_vjp(
            m["ln_f"]["weight"], w_head, v, tgt[1:], mtp_w, eps)
        loss += (float(main) + model_args["mtp_weight"] * float(mtp)) / rows
        add("ln_f", {"weight": d_ln_f})
        add("head", {"weight": d_head + d_head_m})
        add("mtp.ln_f", {"weight": d_ln_fm})
        d_layer, du = _block_vjp(m["layer"], u, dv, frozen, False)
        add("mtp.layer", d_layer)
        d_inner, de, dh_tail = _mtp_input_vjp(inner, e, x[:-1], du, eps)
        add("mtp.inner", d_inner)
        dh = dh.at[:-1].add(dh_tail)
        for l in reversed(range(len(names))):
            d_block, dh = _block_vjp(layers[names[l]], xs[l], dh, frozen,
                                     l < first)
            add(names[l], d_block)
        d_emb = jnp.zeros(params["emb"]["weight"].shape, jnp.float32)
        add("emb", {"weight": d_emb.at[toks].add(dh).at[e_toks].add(de)})
    return loss, {
        "emb": sums["emb"], "layers": {n: sums[n] for n in names},
        "ln_f": sums["ln_f"], "head": sums["head"],
        "mtp": dict(sums["mtp.inner"], layer=sums["mtp.layer"],
                    ln_f=sums["mtp.ln_f"])}

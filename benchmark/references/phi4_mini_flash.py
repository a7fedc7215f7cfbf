"""The plain reference of Phi-4-mini-flash-reasoning's architecture: SambaY
with differential attention (arXiv:2507.06607, ``model_type: phi4flash``),
written layer by layer for ONE sequence in ``jax.numpy`` at float32 with
``default_matmul_precision("highest")``: a sequential ``lax.scan`` for the
recurrence, a full masked softmax for attention, no cache, no kernels, no
chunks. It reads the constructor arguments from the configuration file
and the weights by the names of the program's tree, and shares no code
with ``bigdl_tpu``.

Layer l of L, 0-based: ``h += Mixer_l(LN1(h)); h += SwiGLU(LN2(h))``.
Mixer: l <= L/2 + 1 even: Mamba-1 (l = L/2 also emits the memory M);
l <= L/2 - 1 odd: differential attention over the last ``window``
positions; l = L/2 + 1: differential attention over the whole prefix (its
K and V are the shared cache); l >= L/2 + 2 even: gated memory unit on M;
odd: differential cross-attention with its own Q and the shared K, V.
No positional encoding; the embedding is not scaled; the head is the
embedding.

Heads are written here as the paper has them, 64 wide and split into
(q1, q2), (k1, k2), (v1, v2) by adjacent heads, not as the program's
128-wide pairs. The program stores ``a_log`` as (d_state, d_inner): read
that way, ``A[n, c]`` is channel c's n-th pole.

One layer's weights are cast to f32 at a time, and the tied head a quarter
of the vocabulary's rows at a time (the same products, never 2 GB of f32
embedding), so the reference fits beside a server that holds 7.7 GB of
bf16 weights and 2.9 GB of slots.
"""

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
HEAD_BLOCKS = 4  # the tied head in f32, a quarter of the vocabulary at a time
_HI = functools.partial(jax.default_matmul_precision, "highest")


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _layer_norm(p, x):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["weight"] + p["bias"]


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _mamba(m, u):
    """u (s, d) -> (out (s, d), y (s, d_inner): the scan's output before
    the gate, the memory when this is layer L/2; the state after the last
    row, (d_state, d_inner))."""
    s = u.shape[0]
    n, di = m["a_log"].shape
    taps = m["conv_w"].shape[0]
    rank = m["w_dt"].shape[0]
    xz = u @ m["w_in"]
    x, z = xz[:, :di], xz[:, di:]
    xp = jnp.concatenate([jnp.zeros((taps - 1, di)), x], axis=0)
    x = m["conv_b"] + sum(m["conv_w"][j] * xp[j:j + s] for j in range(taps))
    x = _silu(x)
    rbc = x @ m["w_x"]
    r, b, c = rbc[:, :rank], rbc[:, rank:rank + n], rbc[:, rank + n:]
    dt = jnp.logaddexp(r @ m["w_dt"] + m["b_dt"], 0.0)  # softplus
    a = -jnp.exp(m["a_log"])                            # (n, di)

    def step(state, row):
        dt_t, x_t, b_t, c_t = row
        state = (jnp.exp(dt_t[None, :] * a) * state
                 + (dt_t * x_t)[None, :] * b_t[:, None])
        return state, c_t @ state + m["d"] * x_t

    state, y = jax.lax.scan(step, jnp.zeros((n, di)), (dt, x, b, c))
    return (y * _silu(z)) @ m["w_out"], y, state


def _diff_attention(a, x, kv, depth, n_heads, n_kv, window):
    """x (s, d) -> (out, (k, v)). ``kv`` None: K and V are projected here
    from x; else they are the shared layer's (s, n_kv, hd)."""
    s, d = x.shape
    hd = d // n_heads
    q = (x @ a["wq"] + a["bq"]).reshape(s, n_heads, hd)
    if kv is None:
        kv = ((x @ a["wk"] + a["bk"]).reshape(s, n_kv, hd),
              (x @ a["wv"] + a["bv"]).reshape(s, n_kv, hd))
    k, v = kv
    q1, q2 = q[:, 0::2], q[:, 1::2]                    # (s, n_heads/2, hd)
    k1, k2 = k[:, 0::2], k[:, 1::2]                    # (s, n_kv/2, hd)
    vv = jnp.concatenate([v[:, 0::2], v[:, 1::2]], axis=-1)
    group = (n_heads // 2) // (n_kv // 2)  # query pair i reads KV pair i//g
    k1, k2, vv = (jnp.repeat(t, group, axis=1) for t in (k1, k2, vv))
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    seen = j <= i
    if window is not None:
        seen &= j > i - window

    def probs(qh, kh):
        sc = jnp.einsum("ihd,jhd->hij", qh, kh) / math.sqrt(hd)
        return jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)

    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * depth)  # depth is traced
    lam = (jnp.exp(jnp.dot(a["lq1"], a["lk1"]))
           - jnp.exp(jnp.dot(a["lq2"], a["lk2"])) + lam0)
    o = (jnp.einsum("hij,jhd->ihd", probs(q1, k1), vv)
         - lam * jnp.einsum("hij,jhd->ihd", probs(q2, k2), vv))
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + LN_EPS)
    o = o * a["ln_sub"]["weight"] * (1.0 - lam0)
    return o.reshape(s, d) @ a["wo"] + a["bo"], kv


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _layer(p, x, mem, kv, depth, kind, n_heads, n_kv, window):
    """One layer on one sequence x (s, d) -> (x, y of a Mamba layer or
    None, (k, v) of an attention layer that projects them or None)."""
    with _HI():
        p = _f32(p)
        m = p["mixer"]
        h = _layer_norm(p["ln1"], x)
        y = new_kv = None
        if kind == "mamba":
            h, y, _ = _mamba(m, h)
        elif kind == "gmu":
            h = (mem * _silu(h @ m["w1"])) @ m["w2"]
        elif kind == "cross":
            h, _ = _diff_attention(m, h, kv, depth, n_heads, n_kv, None)
        else:
            h, new_kv = _diff_attention(
                m, h, None, depth, n_heads, n_kv,
                window if kind == "window" else None)
        x = x + h
        gu = _layer_norm(p["ln2"], x) @ p["w1"]
        ff = gu.shape[-1] // 2
        return x + (gu[:, ff:] * _silu(gu[:, :ff])) @ p["w2"], y, new_kv


@jax.jit
def _head(rows, x):
    """x (s, d), already normed, against a block of the embedding's rows."""
    with _HI():
        return x @ rows.astype(jnp.float32).T


def logits(params, model_args, tokens):
    """(s,) token ids -> (s, vocab) float32 logits of one sequence."""
    n_layers = model_args["num_layers"]
    n_heads = model_args["num_heads"]
    n_kv = model_args.get("num_kv_heads") or n_heads
    window = model_args["window"]
    half = n_layers // 2
    x = params["emb"]["weight"][jnp.asarray(tokens, jnp.int32)]
    x = x.astype(jnp.float32)
    mem = kv = None
    for l in range(n_layers):
        if l > half + 1:
            kind = "cross" if l % 2 else "gmu"
        elif l % 2 == 0:
            kind = "mamba"
        else:
            kind = "full" if l == half + 1 else "window"
        x, y, new_kv = _layer(params["layers"][str(l)], x,
                              mem if kind == "gmu" else None,
                              kv if kind == "cross" else None,
                              jnp.float32(l), kind, n_heads, n_kv, window)
        if l == half:
            mem = y
        if kind == "full":
            kv = new_kv
    x = _layer_norm(_f32(params["ln_f"]), x)
    emb = params["emb"]["weight"]
    step = -(-emb.shape[0] // HEAD_BLOCKS)
    return jnp.concatenate([_head(emb[i:i + step], x)
                            for i in range(0, emb.shape[0], step)], axis=1)

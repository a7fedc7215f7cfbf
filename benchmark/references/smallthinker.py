"""The plain reference of SmallThinker-21BA3B-Instruct's architecture
(``model_type: smallthinker``): causal GQA in every layer, one layer in four
over the whole prefix without positions and three rotated and held to a
window, a router that reads the layer's input before attention, ReGLU
experts; written layer by layer for ONE sequence in ``jax.numpy`` at
float32 with ``default_matmul_precision("highest")``: explicit cos/sin
rotation, an explicit mask ``j <= i`` and ``j > i - window`` on a full
softmax, ``top_k`` then softmax, every expert applied to every token as a
dense ReGLU and weighted by what the router gave it; no kernel, no cache,
no ring. It reads the constructor arguments from the configuration file and
the weights by the names of the program's tree, and shares no code with
``bigdl_tpu``.

With ``h`` the stream entering layer ``l``::

    r = h Wr                                  (num_experts router logits)
    n = RMSNorm1(h);  h = h + Attn_l(n)
        q = n Wq, k = n Wk, v = n Wv          (no bias, no q/k norm)
        rope_layout[l]: q, k rotated at their positions (half-split
            pairing: dims [0, d/2) with [d/2, d); theta ``rope_theta``)
        sliding_window_layout[l]: query i sees keys j, i - window < j <= i
        else: query i sees keys j <= i
        out = concat_heads(softmax(q k^T / sqrt(head_dim)) v) Wo
    m = RMSNorm2(h)
    E = the top_k largest of r;  w = softmax(r[E])
    h = h + sum_{e in E} w_e (relu(m W1_e) * (m W3_e)) W2_e
    logits = RMSNorm_f(h) Wh^T

Departures from the family's published implementation, none of which
changes a shape or a byte count:

* the router reads the layer's raw input ``h`` (assumed; the catalog says
  "router placed before attention" and not which tensor);
* no bias on any projection and no q/k norm (assumed);
* nothing is built for "secondary" experts or a neuron predictor: the
  catalog's config has no key for them;
* ``W1_e`` and ``W3_e`` are the two halves of the program's ``w13[e]``
  (gate first), as the program's tree stores them.

A layer's weights are cast to f32 an expert at a time, attention walks the
query rows in blocks of ``Q_BLOCK`` and the head the vocabulary in
``HEAD_BLOCKS`` parts that go to the host one by one, so a check of 5,003
tokens fits beside a server that holds 7.9 GB of bf16 weights and 3.8 GB of
slots. ``routes`` returns, beside the logits (a numpy array), the experts
each token chose in each layer.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

HEAD_BLOCKS = 4
Q_BLOCK = 512
_HI = functools.partial(jax.default_matmul_precision, "highest")


def _rms_norm(weight, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _rotate(x, theta):
    """x (s, heads, d) at positions 0..s-1: dims [0, d/2) pair with
    [d/2, d), the pair ``c`` turning by ``p * theta^(-2c/d)``."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _visible(i, j, window):
    """Which keys ``j`` (1, s) the queries ``i`` (rows, 1) see: the
    prefix, and under a ``window`` its last ``window`` positions."""
    seen = j <= i
    return seen if window is None else seen & (j > i - window)


def _router_input(h, m):
    """What the router reads: the layer's input ``h``, not ``m`` =
    RMSNorm2 of the stream after attention."""
    del m
    return h


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _attention(p, n, heads, kv_heads, hd, theta, window):
    """The mixer of one layer on the normalised stream n (s, d)."""
    with _HI():
        f32 = lambda t: t.astype(jnp.float32)
        s = n.shape[0]
        q = (n @ f32(p["wq"])).reshape(s, heads, hd)
        k = (n @ f32(p["wk"])).reshape(s, kv_heads, hd)
        v = (n @ f32(p["wv"])).reshape(s, kv_heads, hd)
        if theta is not None:
            q, k = _rotate(q, theta), _rotate(k, theta)
        k = jnp.repeat(k, heads // kv_heads, axis=1)
        v = jnp.repeat(v, heads // kv_heads, axis=1)
        j = jnp.arange(s)[None, :]
        out = []
        for lo in range(0, s, Q_BLOCK):
            qb = q[lo:lo + Q_BLOCK]
            i = lo + jnp.arange(qb.shape[0])[:, None]
            sc = jnp.einsum("ihd,jhd->hij", qb, k) / jnp.sqrt(float(hd))
            sc = jnp.where(_visible(i, j, window)[None], sc, -jnp.inf)
            sc = sc - jnp.max(sc, axis=-1, keepdims=True)
            pr = jnp.exp(sc)
            pr = pr / jnp.sum(pr, axis=-1, keepdims=True)
            out.append(jnp.einsum("hij,jhd->ihd", pr, v))
        return jnp.concatenate(out).reshape(s, heads * hd) @ f32(p["wo"])


@functools.partial(jax.jit, static_argnums=(3, 4))
def _norm_and_route(lean, h_in, h, top_k, eps):
    """lean: the layer's second norm and router; h_in the layer's input,
    h the stream after attention -> (m, chosen, w)."""
    with _HI():
        m = _rms_norm(lean["ln2"]["weight"].astype(jnp.float32), h, eps)
        r = _router_input(h_in, m) @ lean["ffn"]["router"]["weight"].astype(
            jnp.float32)
        chosen = jnp.argsort(-r, axis=-1)[:, :top_k]
        picked = jnp.take_along_axis(r, chosen, axis=-1)
        e = jnp.exp(picked - jnp.max(picked, axis=-1, keepdims=True))
        return m, chosen, e / jnp.sum(e, axis=-1, keepdims=True)


@jax.jit
def _expert(w13, w2, m, coef):
    """One expert on every token, weighted: coef (s,) is the router's
    weight for this expert, 0 where a token did not choose it."""
    with _HI():
        gu = m @ w13.astype(jnp.float32)
        half = gu.shape[-1] // 2
        y = (jnp.maximum(gu[:, :half], 0.0) * gu[:, half:]) @ w2.astype(
            jnp.float32)
        return coef[:, None] * y


@jax.jit
def _head(rows, x):
    with _HI():
        return x @ rows.astype(jnp.float32).T


def routes(params, model_args, tokens):
    """(s,) token ids -> ((s, vocab) float32 logits as a numpy array,
    chosen experts (layers, s, top_k) int32)."""
    a = model_args
    eps = a.get("rms_eps", 1e-5)
    h = params["emb"]["weight"][jnp.asarray(tokens, jnp.int32)]
    h = h.astype(jnp.float32)
    all_chosen = []
    for l in range(a["num_layers"]):
        p = params["layers"][str(l)]
        with _HI():
            n = _rms_norm(p["ln1"]["weight"].astype(jnp.float32), h, eps)
        h_in = h
        h = h + _attention(
            p["mixer"], n, a["num_heads"], a["num_kv_heads"], a["head_dim"],
            float(a["rope_theta"]) if a["rope_layout"][l] else None,
            a["window"] if a["sliding_window_layout"][l] else None)
        lean = {"ln2": p["ln2"], "ffn": {"router": p["ffn"]["router"]}}
        m, chosen, w = _norm_and_route(lean, h_in, h, a["top_k"], eps)
        w13, w2 = p["ffn"]["w13"], p["ffn"]["w2"]
        for e in range(w13.shape[0]):
            coef = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
            h = h + _expert(w13[e], w2[e], m, coef)
        all_chosen.append(chosen)
    with _HI():
        h = _rms_norm(params["ln_f"]["weight"].astype(jnp.float32), h, eps)
    head = params["head"]["weight"]
    step = -(-head.shape[0] // HEAD_BLOCKS)
    logits = np.concatenate(
        [np.asarray(_head(head[i:i + step], h))
         for i in range(0, head.shape[0], step)], axis=1)
    return logits, jnp.stack(all_chosen)


def logits(params, model_args, tokens):
    """(s,) token ids -> (s, vocab) float32 logits of one sequence."""
    return routes(params, model_args, tokens)[0]

"""The plain reference of Command A+'s architecture (``model_type:
cohere2_moe``): parallel attention and FFN blocks under one LayerNorm,
grouped-query attention with three GPT-J-rotated window layers before each
global layer without positions, a sigmoid router without a selection bias
over 128 experts, top-8 renormalised, four shared experts averaged, a tied
head; written layer by layer for ONE sequence in ``jax.numpy`` at float32
with ``default_matmul_precision("highest")``: explicit cos/sin rotation of
the pairs (2i, 2i + 1), an explicit mask ``j <= i`` and ``j > i - window``
on a full softmax, every held expert applied to every token and weighted
by what the router gave it, every shared expert applied on its own; no
kernel, no cache, no ring. It reads the constructor arguments from the
configuration file and the weights by the names of the program's tree, and
shares no code with ``bigdl_tpu``.

With ``h`` the stream entering layer ``l``::

    n = LayerNorm_l(h) = (h - mean h) / sqrt(var h + eps) * g   (no bias)
    q = n Wq, k = n Wk, v = n Wv              (no bias, no q/k norm)
    window layers: q, k rotated at their positions, dims (2i, 2i + 1)
        turning together by p * theta^(-2i / head_dim); query t sees keys
        t - window < j <= t
    global layers: no rotation; query t sees keys j <= t
    head g reads KV head g // (heads / kv_heads)
    A = concat_heads(softmax(q k^T / sqrt(head_dim)) v) Wo
    s = sigmoid(n Wr);  T = the top_k largest of s;  w = s_T / sum s_T
    R = sum_{e in T, held} w_e W2_e(silu(n W1_e) * n W3_e)
    S = 1/N sum_{j <= N} W2s_j(silu(n W1s_j) * n W3s_j)   (N shared experts)
    h = h + A + R + S
    logits = LayerNorm_f(h) E^T,   E the embedding's rows

The program's tree holds experts ``share * experts_held ..`` of each layer
and ``vocab`` rows of the embedding; the choice is over all
``num_experts``, and what an expert held elsewhere would add is left out
here as there (one chip's share of the deployment).

Departures from the family's published implementation, none of which
changes a shape or a byte count:

* ``shared_expert_combination_strategy: average`` is read as the mean of
  the four shared experts' outputs (assumed; the other reading, half of
  the routed and shared sum, costs the same);
* ``W1`` and ``W3`` of an expert are the two halves of the program's
  ``w13`` (gate first); the four shared experts are one ``shared_w13``
  (d, 2 x 4 x width), gates ``[0, 4 width)`` and ups after, expert ``j``
  the ``j``-th block of each, and ``shared_w2`` their four ``W2`` stacked;
* the vision tower is not built: it is no part of the language model.

Every weight is read through ``_weight`` (float32), one matrix at a time:
attention walks the query rows in blocks of ``Q_BLOCK`` and the head the
vocabulary in ``HEAD_BLOCKS`` parts that go to the host one by one, so a
check of 5,003 tokens fits beside a server that holds 9.5 GB of bf16
weights and 2.8 GB of slots. ``routes`` returns, beside the logits (a numpy
array), the experts each token chose in each layer. The functions a
control replaces are ``_weight``, ``_rotate``, ``_visible``, ``_block`` and
``_combine_shared``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

HEAD_BLOCKS = 4
Q_BLOCK = 64
_HI = functools.partial(jax.default_matmul_precision, "highest")


def _weight(w):
    """What the reference computes with: the stored weight in float32."""
    return w.astype(jnp.float32)


def _layer_norm(weight, x, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * _weight(weight)


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _rotate(x, theta):
    """x (s, heads, d) at positions 0..s-1: dims 2i and 2i + 1 pair, the
    pair turning by ``p * theta^(-2i/d)``."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _visible(i, j, window):
    """Which keys ``j`` (1, s) the queries ``i`` (rows, 1) see: the
    prefix, and under a ``window`` its last ``window`` positions."""
    seen = j <= i
    return seen if window is None else seen & (j > i - window)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _attention(p, n, heads, kv_heads, hd, theta, window):
    """The mixer of one layer on the normalised stream n (s, d): the
    query rows in blocks of ``Q_BLOCK``, each head against its KV head."""
    with _HI():
        s, g = n.shape[0], heads // kv_heads
        q = (n @ _weight(p["wq"])).reshape(s, kv_heads, g, hd)
        k = (n @ _weight(p["wk"])).reshape(s, kv_heads, hd)
        v = (n @ _weight(p["wv"])).reshape(s, kv_heads, hd)
        if theta is not None:
            q = _rotate(q.reshape(s, heads, hd), theta).reshape(q.shape)
            k = _rotate(k, theta)
        blocks = -(-s // Q_BLOCK)
        q = jnp.pad(q, ((0, blocks * Q_BLOCK - s), (0, 0), (0, 0), (0, 0)))
        j = jnp.arange(s)[None, :]

        def block(b):
            qb = jax.lax.dynamic_slice_in_dim(q, b * Q_BLOCK, Q_BLOCK)
            i = b * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None]
            sc = jnp.einsum("ikgd,jkd->kgij", qb, k) / jnp.sqrt(float(hd))
            sc = jnp.where(_visible(i, j, window)[None, None], sc, -jnp.inf)
            sc = sc - jnp.max(sc, axis=-1, keepdims=True)
            pr = jnp.exp(sc)
            pr = pr / jnp.sum(pr, axis=-1, keepdims=True)
            return jnp.einsum("kgij,jkd->ikgd", pr, v)

        out = jax.lax.map(block, jnp.arange(blocks))
        out = out.reshape(blocks * Q_BLOCK, heads * hd)[:s]
        return out @ _weight(p["wo"])


@functools.partial(jax.jit, static_argnums=(2,))
def _route(router, n, top_k):
    """n (s, d) -> (chosen (s, top_k) int32, their weights (s, top_k))."""
    with _HI():
        s = 1.0 / (1.0 + jnp.exp(-(n @ _weight(router["weight"]))))
        chosen = jnp.argsort(-s, axis=-1)[:, :top_k]
        picked = jnp.take_along_axis(s, chosen, axis=-1)
        return chosen, picked / jnp.sum(picked, axis=-1, keepdims=True)


@jax.jit
def _glu(w1, w3, w2, n):
    """One expert, W2(silu(n W1) * n W3), on every token."""
    with _HI():
        return (_silu(n @ _weight(w1)) * (n @ _weight(w3))) @ _weight(w2)


def _combine_shared(outs):
    """The shared experts' outputs, averaged."""
    return sum(outs) / len(outs)


def _block(h, norm, attend, feed):
    """A parallel block: attention and the FFN read the same norm of the
    layer's input, and both are added to it."""
    n = norm(h)
    return h + attend(n) + feed(n)


def _routed(f, n, chosen, w, first):
    """The held experts' part: expert ``first + e`` is ``w13[e]``."""
    out = jnp.zeros_like(n)
    w13, w2 = f["w13"], f["w2"]
    width = w2.shape[1]
    for e in range(w13.shape[0]):
        coef = jnp.sum(jnp.where(chosen == first + e, w, 0.0), axis=-1)
        out = out + coef[:, None] * _glu(w13[e][:, :width],
                                         w13[e][:, width:], w2[e], n)
    return out


def _shared(f, n, count):
    """Each of the ``count`` shared experts on its own, then combined."""
    w13, w2 = f["shared_w13"], f["shared_w2"]
    width = w2.shape[0] // count
    gates, ups = w13[:, :count * width], w13[:, count * width:]
    return _combine_shared([
        _glu(gates[:, j * width:(j + 1) * width],
             ups[:, j * width:(j + 1) * width],
             w2[j * width:(j + 1) * width], n) for j in range(count)])


@jax.jit
def _head(rows, x):
    with _HI():
        return x @ _weight(rows).T


def routes(params, model_args, tokens):
    """(s,) token ids -> ((s, vocab) float32 logits as a numpy array,
    chosen experts (layers, s, top_k) int32, global ids)."""
    a = model_args
    eps = a.get("rms_eps", 1e-5)
    emb = params["emb"]["weight"]
    h = _weight(emb[jnp.asarray(tokens, jnp.int32)])
    all_chosen = []
    for l in range(a["num_layers"]):
        p = params["layers"][str(l)]
        f = p["ffn"]
        held = f["w13"].shape[0]
        window = a["window"] if a["sliding_window_layout"][l] else None
        theta = float(a["rope_theta"]) if a["rope_layout"][l] else None

        def norm(x, p=p):
            with _HI():
                return _layer_norm(p["ln1"]["weight"], x, eps)

        def attend(n, p=p, window=window, theta=theta):
            return _attention(p["mixer"], n, a["num_heads"],
                              a["num_kv_heads"], a["head_dim"], theta,
                              window)

        def feed(n, f=f, held=held):
            chosen, w = _route(f["router"], n, a["top_k"])
            all_chosen.append(chosen)
            return (_routed(f, n, chosen, w, a.get("share", 0) * held)
                    + _shared(f, n, a["shared_experts"]))

        h = _block(h, norm, attend, feed)
    with _HI():
        h = _layer_norm(params["ln_f"]["weight"], h, eps)
    step = -(-emb.shape[0] // HEAD_BLOCKS)
    logits = np.concatenate(
        [np.asarray(_head(emb[i:i + step], h))
         for i in range(0, emb.shape[0], step)], axis=1)
    return logits, jnp.stack(all_chosen)


def logits(params, model_args, tokens):
    """(s,) token ids -> (s, vocab) float32 logits of one sequence."""
    return routes(params, model_args, tokens)[0]

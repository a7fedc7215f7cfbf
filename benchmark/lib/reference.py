"""The plain reference of the configurations' block: a decoder-only
transformer with pre-LayerNorm, grouped-query attention with rotary
positions, a tanh-GELU MLP, biases everywhere and a tied output head
(StarCoder2, arXiv:2402.19173), written layer by layer in ``jax.numpy``
at float32 with ``default_matmul_precision("highest")``: no kernels, no
cache, no batching. It reads the model's constructor arguments from the
configuration file and the weights by the names of the program's tree;
it shares no code with ``bigdl_tpu``.

Departures from the published model, as the program computes them (both
speed-neutral, both listed in the configuration files): the embedding is
scaled by sqrt(d_model), and the rotary base is 10,000.

One layer's weights are cast to f32 at a time, so the reference fits
beside a server that holds 6 GB of bf16 weights.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ROPE_BASE = 10000.0
LN_EPS = 1e-5
_HI = functools.partial(jax.default_matmul_precision, "highest")


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _layer_norm(p, x):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["weight"] + p["bias"]


def _rope(x):
    """x (s, heads, hd): rotate the two halves of each head by position."""
    s, _, hd = x.shape
    inv = 1.0 / (ROPE_BASE ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = np.arange(s, dtype=np.float32)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang))[:, None, :]
    sin = jnp.asarray(np.sin(ang))[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(p, x, n_heads, n_kv):
    """One block on one sequence x (s, d)."""
    with _HI():
        p = _f32(p)
        s, d = x.shape
        hd = d // n_heads
        a = p["mha"]
        h = _layer_norm(p["ln1"], x)
        q = _rope((h @ a["wq"] + a["bq"]).reshape(s, n_heads, hd))
        k = _rope((h @ a["wk"] + a["bk"]).reshape(s, n_kv, hd))
        v = (h @ a["wv"] + a["bv"]).reshape(s, n_kv, hd)
        # grouped queries: head i of n_heads reads kv head i // group
        q = q.reshape(s, n_kv, n_heads // n_kv, hd)
        scores = jnp.einsum("sngh,tnh->ngst", q, k) / math.sqrt(hd)
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        o = jnp.einsum("ngst,tnh->sngh", probs, v).reshape(s, d)
        x = x + o @ a["wo"] + a["bo"]
        h = _layer_norm(p["ln2"], x)
        h = h @ p["w1"] + p["b1"]
        h = 0.5 * h * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (h + 0.044715 * h ** 3)))
        return x + h @ p["w2"] + p["b2"]


@jax.jit
def _embed(emb, tokens):
    return emb[tokens].astype(jnp.float32) * math.sqrt(emb.shape[1])


@jax.jit
def _head(ln_f, emb, x):
    with _HI():
        h = _layer_norm(_f32(ln_f), x)
        return h @ emb.astype(jnp.float32).T


def logits(params, model_args, tokens):
    """(s, vocab) float32 logits of one sequence of token ids."""
    x = _embed(params["emb"]["weight"], jnp.asarray(tokens, jnp.int32))
    for i in range(model_args["num_layers"]):
        x = _layer(params["encoder"][str(i)], x, model_args["num_heads"],
                   model_args["num_kv_heads"])
    return _head(params["ln_f"], params["emb"]["weight"], x)


def mean_nll(params, model_args, tokens, targets):
    """Mean next-token negative log-likelihood over one row."""
    lp = jax.nn.log_softmax(logits(params, model_args, tokens), axis=-1)
    tg = jnp.asarray(targets, jnp.int32)
    return float(-jnp.mean(jnp.take_along_axis(lp, tg[:, None], axis=-1)))

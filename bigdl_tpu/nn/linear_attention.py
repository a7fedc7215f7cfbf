"""Kimi Delta Attention (KDA; Kimi Linear, arXiv:2510.26692): linear
attention whose state is a matrix a head, updated by a delta rule under a
per-channel decay.

For token t, head h, with ``q``, ``k`` L2-normalised (q then scaled by
``head_dim ** -0.5``), ``alpha_t = exp(g_t)`` in (0, 1) a key channel and
``beta_t`` in (0, 2) a head (negative eigenvalues allowed)::

    S'_t = diag(alpha_t) S_{t-1}
    u_t  = beta_t (v_t - S'_t^T k_t)
    S_t  = S'_t + k_t u_t^T
    o_t  = S_t^T q_t

``S`` is (head_dim keys x head_dim values), float32. A decode step is one
step of that recurrence (:func:`kda_step`); a prefill runs the exact chunk
form (:func:`kda_chunked`, chunks of 64) that solves a chunk's ``u`` from a
unit lower-triangular system; :func:`kda_recurrent` is the recurrence token
by token, which the tests hold both to. State, decays and the solve are in
float32 at ``highest`` matmul precision: on the TPU a float32 product is
otherwise rounded to bfloat16 passes.

The mixer (:class:`KDA`) projects q, k and v through one matrix and one
causal depthwise convolution of ``conv`` taps (silu after it), makes the
decay and the output gate through low-rank pairs, and normalises each
head's output (RMSNorm) before the gate. What a serving slot holds:
``{"s": (b, heads, head_dim, head_dim) float32, "conv": (b, conv - 1,
3 * heads * head_dim)}``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.core.module import SimpleModule
from bigdl_tpu.nn.ssm import dot_f32

__all__ = ["kda_step", "kda_recurrent", "kda_chunked", "KDA"]

_mm = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)


def kda_step(s, q, k, v, g, beta):
    """One token: ``s`` (..., dk, dv) float32; ``q``, ``k``, ``g``
    (..., dk); ``v`` (..., dv); ``beta`` (...). Returns (o, s_new). Two
    passes over the state: ``S'^T k`` and ``S'^T q`` are read off the old
    state in one (``o = S'^T q + (k . q) u``), the update in the other."""
    alpha = jnp.exp(g)
    ak, aq = alpha * k, alpha * q
    pred = jnp.sum(s * ak[..., None], axis=-2)
    read = jnp.sum(s * aq[..., None], axis=-2)
    u = beta[..., None] * (v - pred)
    o = read + jnp.sum(k * q, axis=-1, keepdims=True) * u
    return o, alpha[..., None] * s + k[..., None] * u[..., None, :]


def kda_recurrent(q, k, v, g, beta, s0):
    """The recurrence token by token. ``q``, ``k``, ``g`` (b, h, L, dk),
    ``v`` (b, h, L, dv), ``beta`` (b, h, L), ``s0`` (b, h, dk, dv), all
    float32. Returns (o (b, h, L, dv), s_L)."""
    def body(s, row):
        o, s = kda_step(s, *row)
        return s, o

    rows = tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v, g, beta))
    s, o = jax.lax.scan(body, s0, rows)
    return jnp.moveaxis(o, 0, 2), s


def kda_chunked(q, k, v, g, beta, s0, chunk: int = 64):
    """The same numbers chunk by chunk (L a multiple of ``chunk``). With
    ``G`` the running sum of ``g`` inside a chunk and ``S_0`` the state
    entering it::

        A[t, i] = beta_t sum_c k[t, c] k[i, c] exp(G[t, c] - G[i, c]), i < t
        (I + A) U = diag(beta) (V - (K * exp(G)) S_0)
        o_t = (q_t * exp(G_t))^T S_0
              + sum_{i <= t} (sum_c q[t, c] k[i, c] exp(G[t, c] - G[i, c])) u_i
        S_C = diag(exp(G_C)) S_0 + sum_i (k_i * exp(G_C - G_i)) u_i^T

    Every exponent is a difference ``G_t - G_i <= 0`` (or ``G_t`` itself):
    nothing is ever divided by a decay."""
    b, h, length, dk = q.shape
    n = length // chunk
    if n * chunk != length:
        raise ValueError(f"length {length} is not a multiple of the chunk "
                         f"{chunk}")

    def split(t):  # (b, h, L, ...) -> (n, b, h, chunk, ...)
        return jnp.moveaxis(t.reshape(b, h, n, chunk, *t.shape[3:]), 2, 0)

    t_i = jnp.arange(chunk)
    lower = t_i[:, None] >= t_i[None, :]          # i <= t
    strictly = t_i[:, None] > t_i[None, :]        # i <  t

    def body(s, rows):
        qc, kc, vc, gc, bc = rows
        big_g = jnp.cumsum(gc, axis=2)                       # (b, h, C, dk)
        diff = big_g[:, :, :, None, :] - big_g[:, :, None, :, :]
        decay = jnp.exp(jnp.where(lower[:, :, None], diff, -jnp.inf))
        kk = jnp.sum(kc[:, :, :, None, :] * kc[:, :, None, :, :] * decay,
                     axis=-1)                                # (b, h, C, C)
        qk = jnp.sum(qc[:, :, :, None, :] * kc[:, :, None, :, :] * decay,
                     axis=-1)
        a = jnp.where(strictly, bc[..., None] * kk, 0.0)
        e = jnp.exp(big_g)
        rhs = bc[..., None] * (vc - _mm("bhtc,bhcv->bhtv", kc * e, s))
        u = jax.lax.linalg.triangular_solve(
            a + jnp.eye(chunk, dtype=a.dtype), rhs, left_side=True,
            lower=True, unit_diagonal=True)
        o = _mm("bhtc,bhcv->bhtv", qc * e, s) + _mm("bhti,bhiv->bhtv", qk, u)
        g_end = big_g[:, :, -1:, :]                          # (b, h, 1, dk)
        s = (jnp.exp(g_end[:, :, 0, :, None]) * s
             + _mm("bhtc,bhtv->bhcv", kc * jnp.exp(g_end - big_g), u))
        return s, o

    s, o = jax.lax.scan(body, s0, tuple(split(t) for t in
                                        (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 2).reshape(b, h, length, -1), s


def _l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


class KDA(SimpleModule):
    """The KDA mixer. ``d_model -> heads x head_dim`` for q, k and v alike;
    ``gate_rank`` is the width of the two low-rank pairs (decay, output
    gate)."""

    def __init__(self, d_model: int, num_heads: int, head_dim: int,
                 conv: int = 4, gate_rank: Optional[int] = None,
                 chunk: int = 64, eps: float = 1e-5,
                 init_std: float = 0.02, name: Optional[str] = None):
        super().__init__(name)
        self.d_model, self.num_heads, self.head_dim = (d_model, num_heads,
                                                       head_dim)
        self.inner = num_heads * head_dim
        self.conv, self.chunk, self.eps = conv, chunk, eps
        self.gate_rank = gate_rank or head_dim
        self.init_std = init_std

    def init(self, rng):
        """The published-style initialisation (``fla``'s KimiDeltaAttention):
        linear layers N(0, init_std); conv U(+-1/sqrt(conv)); ``a_log =
        log U(1, 16)`` a head; ``dt_bias`` the inverse softplus of a
        log-uniform 1e-3..1e-1 a channel; the head norm's weight 1."""
        ks = jax.random.split(rng, 11)
        d, n, r, h = self.d_model, self.inner, self.gate_rank, self.num_heads
        mk = lambda k, shape: self.init_std * jax.random.normal(k, shape)
        bound = 1.0 / math.sqrt(self.conv)
        dt = jnp.exp(jax.random.uniform(ks[9], (n,))
                     * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        dt = jnp.maximum(dt, 1e-4)
        return {
            "w_qkv": mk(ks[0], (d, 3 * n)),
            "conv_w": jax.random.uniform(ks[1], (self.conv, 3 * n),
                                         minval=-bound, maxval=bound),
            "wa_down": mk(ks[2], (d, r)), "wa_up": mk(ks[3], (r, n)),
            "wb": mk(ks[4], (d, h)),
            "wg_down": mk(ks[5], (d, r)), "wg_up": mk(ks[6], (r, n)),
            "a_log": jnp.log(jax.random.uniform(ks[8], (h,), minval=1.0,
                                                maxval=16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
            "norm": {"weight": jnp.ones((self.head_dim,))},
            "wo": mk(ks[7], (n, d)),
        }

    def init_cache(self, batch: int, dtype=jnp.float32):
        """A slot's state: ``s`` float32 always, ``conv`` the last
        ``conv - 1`` rows of the q|k|v projection."""
        return {"s": jnp.zeros((batch, self.num_heads, self.head_dim,
                                self.head_dim), jnp.float32),
                "conv": jnp.zeros((batch, self.conv - 1, 3 * self.inner),
                                  dtype)}

    # ------------------------------------------------------------- pieces
    def _heads(self, t):  # (b, L, heads * head_dim) -> (b, heads, L, head_dim)
        b, length, _ = t.shape
        return t.reshape(b, length, self.num_heads,
                         self.head_dim).transpose(0, 2, 1, 3)

    def _inputs(self, params, x, history):
        """x (b, L, d) after ``history`` (b, conv - 1, 3 * inner) ->
        float32 (q, k, v, g, beta) in heads, and the rows that entered the
        convolution, history first."""
        f32 = jnp.float32
        z = dot_f32(x, params["w_qkv"], x.dtype)
        zp = jnp.concatenate([history.astype(f32), z], axis=1)
        w, length = params["conv_w"].astype(f32), x.shape[1]
        z = jax.nn.silu(sum(zp[:, j:j + length] * w[j]
                            for j in range(self.conv)))
        q, k, v = (self._heads(t) for t in jnp.split(z, 3, axis=-1))
        q = _l2norm(q) * self.head_dim ** -0.5
        k = _l2norm(k)
        low = dot_f32(x, params["wa_down"], x.dtype)
        a = dot_f32(low, params["wa_up"], x.dtype)
        a = jax.nn.softplus(a + params["dt_bias"].astype(f32))
        g = -jnp.exp(params["a_log"].astype(f32))[None, :, None, None] \
            * self._heads(a)
        beta = 2.0 * jax.nn.sigmoid(dot_f32(x, params["wb"], x.dtype))
        return q, k, v, g, beta.transpose(0, 2, 1), zp

    def _out(self, params, x, o):
        """o (b, heads, L, head_dim) float32 -> head norm, output gate
        (low-rank through ``gate_rank``), Wo."""
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + self.eps)
        o = o * params["norm"]["weight"].astype(jnp.float32)
        b, h, length, d = o.shape
        o = o.transpose(0, 2, 1, 3).reshape(b, length, h * d)
        gate = dot_f32(dot_f32(x, params["wg_down"], x.dtype),
                       params["wg_up"], x.dtype)
        o = (o * jax.nn.sigmoid(gate)).astype(x.dtype)
        return o @ params["wo"].astype(x.dtype)

    # ------------------------------------------------------------ forward
    def _forward(self, params, x, *, training, rng):
        return self.prefill(params, x, self.init_cache(x.shape[0],
                                                       x.dtype))[0]

    def prefill(self, params, x, cache, last=None):
        """Whole-sequence forward from a zero state (chunked) that also
        hands over the slot's state as it stands after token ``last``
        (traced; default the final one): positions past it decay nothing
        and write nothing (alpha 1, beta 0), and the convolution's history
        is the last ``conv - 1`` real rows. Returns (out, cache)."""
        length = x.shape[1]
        q, k, v, g, beta, zp = self._inputs(params, x,
                                            jnp.zeros_like(cache["conv"]))
        if last is None:
            last = length - 1
        else:
            real = jnp.arange(length) <= last
            g = jnp.where(real[None, None, :, None], g, 0.0)
            beta = jnp.where(real[None, None, :], beta, 0.0)
        pad = -length % self.chunk
        if pad:  # g = 0 and beta = 0 there: the state stands still
            q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, 0), (0, pad))
                                        + ((0, 0),) * (t.ndim - 3))
                                for t in (q, k, v, g, beta))
        o, s = kda_chunked(q, k, v, g, beta, jnp.zeros_like(cache["s"]),
                           self.chunk)
        # zp row j is position j - (conv - 1): rows last-2 .. last
        conv = jax.lax.dynamic_slice_in_dim(zp, last + 1, self.conv - 1,
                                            axis=1)
        return (self._out(params, x, o[:, :, :length]),
                {"s": s, "conv": conv.astype(cache["conv"].dtype)})

    def decode_step(self, params, x, cache):
        """One token: x (b, 1, d) against the slot's state."""
        q, k, v, g, beta, zp = self._inputs(params, x, cache["conv"])
        o, s = kda_step(cache["s"], q[:, :, 0], k[:, :, 0], v[:, :, 0],
                        g[:, :, 0], beta[:, :, 0])
        return (self._out(params, x, o[:, :, None]),
                {"s": s, "conv": zp[:, 1:].astype(cache["conv"].dtype)})

"""State-space layers: the Mamba-1 mixer and the gated memory unit.

Mamba-1 (Gu & Dao, arXiv:2312.00752), as SambaY (arXiv:2507.06607) uses
it. For an input row ``u`` of width ``d``::

    [x, z] = u @ W_in                                (d -> 2 x d_inner)
    x      = silu(causal_depthwise_conv(x) + b_conv) (d_conv taps)
    [r, B, C] = x @ W_x                (d_inner -> dt_rank + 2 x d_state)
    dt     = softplus(r @ W_dt + b_dt)               (dt_rank -> d_inner)
    s_t    = exp(dt_t * A) * s_{t-1} + (dt_t * x_t) * B_t,  A = -exp(A_log)
    y_t    = s_t . C_t + D * x_t
    out    = (y * silu(z)) @ W_out

The state ``s`` is held as ``(d_state, d_inner)`` (``A_log`` likewise), so
the wide axis lies on the lanes; state and scan are float32 whatever the
activations are. A serving slot carries two leaves for such a layer: the
state after its last token and the last ``d_conv - 1`` rows that went into
the convolution.

Three forms of one recurrence, held equal by tests/test_ssm.py:
``selective_scan`` (chunks of the sequence in a ``lax.scan``, an
associative scan inside each chunk: the prefill), ``selective_scan_seq``
(one step a token: what the others are checked against) and
``Mamba.decode_step`` (one token against a slot's state).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.core.module import SimpleModule

__all__ = ["selective_scan", "selective_scan_seq", "dot_f32", "Mamba",
           "GatedMemoryUnit"]


# the family's Mamba-1 defaults, which no configuration in reach changes
EXPAND, D_CONV = 2, 4


def _normal(rng, shape, std):
    return std * jax.random.normal(rng, shape, jnp.float32)


def dot_f32(a, w, dtype):
    """a @ w with both operands in ``dtype`` (the activations') and the
    product left in float32, so that what follows rounds once."""
    return jnp.dot(a.astype(dtype), w.astype(dtype),
                   preferred_element_type=jnp.float32)


def _scan_step(s, dt_t, x_t, b_t, c_t, a):
    """One token of the recurrence: s (b, N, di), the rest rows of it."""
    s = (jnp.exp(dt_t[:, None, :] * a) * s
         + (dt_t * x_t)[:, None, :] * b_t[:, :, None])
    return s, jnp.einsum("bnd,bn->bd", s, c_t)


def selective_scan_seq(x, dt, a, b, c, s0):
    """The recurrence one token at a time. x, dt: (batch, L, d_inner)
    float32; a: (N, d_inner); b, c: (batch, L, N); s0: (batch, N,
    d_inner). Returns (y without the D term, the state after token L-1)."""
    def body(s, row):
        s, y = _scan_step(s, *row, a)
        return s, y

    rows = tuple(jnp.moveaxis(v, 1, 0) for v in (dt, x, b, c))
    s, y = jax.lax.scan(body, s0, rows)
    return jnp.moveaxis(y, 0, 1), s


def selective_scan(x, dt, a, b, c, s0, chunk: int = 64):
    """The same recurrence in chunks of ``chunk`` tokens: inside a chunk
    every state is one associative scan over the pairs (decay, input)
    with (a1, b1) o (a2, b2) = (a1 a2, a2 b1 + b2), and the chunks follow
    one another in a ``lax.scan`` that carries the state. A sequence that
    is no multiple of the chunk is padded with rows of dt = 0, which leave
    the state as it is. Peak memory is two (chunk, N, d_inner) float32
    arrays a batch row, whatever the length."""
    bsz, length, di = x.shape
    t = min(chunk, length)
    pad = -length % t
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                       for v in (x, dt, b, c))
    nc = (length + pad) // t

    def split(v):  # (b, L, f) -> (chunks, b, t, f)
        return jnp.moveaxis(v.reshape(bsz, nc, t, v.shape[-1]), 1, 0)

    def combine(left, right):
        a1, b1 = left
        a2, b2 = right
        return a1 * a2, a2 * b1 + b2

    def body(s, rows):
        dt_c, x_c, b_c, c_c = rows
        decay = jnp.exp(dt_c[:, :, None, :] * a)            # (b, t, N, di)
        drive = (dt_c * x_c)[:, :, None, :] * b_c[:, :, :, None]
        cum_a, cum_b = jax.lax.associative_scan(combine, (decay, drive),
                                                axis=1)
        states = cum_a * s[:, None] + cum_b
        y = jnp.einsum("btnd,btn->btd", states, c_c)
        return states[:, -1], y

    s, y = jax.lax.scan(body, s0, tuple(split(v) for v in (dt, x, b, c)))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, nc * t, di)
    return y[:, :length], s


class Mamba(SimpleModule):
    """The Mamba-1 mixer. ``forward`` is the whole-sequence form and
    returns ``(out, y)``: ``y`` is the scan's output before the gate, which
    SambaY's last self-decoder Mamba layer hands to every gated memory
    unit of the cross-decoder."""

    def __init__(self, d_model: int, d_state: int = 16, chunk: int = 64,
                 init_std: float = 0.02, name: Optional[str] = None):
        super().__init__(name)
        self.d_model = d_model
        self.init_std = init_std
        self.d_inner = EXPAND * d_model
        self.d_state = d_state
        self.d_conv = D_CONV
        self.dt_rank = math.ceil(d_model / 16)
        self.chunk = chunk

    def init(self, rng):
        """The published initialisation (``mamba_ssm`` ``Mamba.__init__``):
        conv U(+-1/sqrt(d_conv)), ``A_log = log(1..N)``, ``D = 1``,
        ``b_dt`` the inverse softplus of a log-uniform 1e-3..1e-1, ``W_dt``
        U(+-dt_rank^-1/2); linear layers N(0, init_std)."""
        ks = jax.random.split(rng, 7)
        d, di, n, r = self.d_model, self.d_inner, self.d_state, self.dt_rank
        bound, std = 1.0 / math.sqrt(self.d_conv), self.init_std
        dt = jnp.exp(jax.random.uniform(ks[5], (di,)) *
                     (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        dt = jnp.maximum(dt, 1e-4)
        return {
            "w_in": _normal(ks[0], (d, 2 * di), std),
            "conv_w": jax.random.uniform(ks[1], (self.d_conv, di),
                                         minval=-bound, maxval=bound),
            "conv_b": jax.random.uniform(ks[2], (di,), minval=-bound,
                                         maxval=bound),
            "w_x": _normal(ks[3], (di, r + 2 * n), std),
            "w_dt": jax.random.uniform(ks[4], (r, di), minval=-r ** -0.5,
                                       maxval=r ** -0.5),
            "b_dt": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
            "a_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None],
                (n, di)),
            "d": jnp.ones((di,)),
            "w_out": _normal(ks[6], (di, d), std),
        }

    def init_cache(self, batch: int, dtype=jnp.float32):
        """A slot's recurrent state: ``h`` float32 always, ``conv`` the
        last d_conv - 1 rows that entered the convolution."""
        return {"h": jnp.zeros((batch, self.d_state, self.d_inner),
                               jnp.float32),
                "conv": jnp.zeros((batch, self.d_conv - 1, self.d_inner),
                                  dtype)}

    # ------------------------------------------------------------- pieces
    # between the two projections nothing is rounded that the scan reads
    def _in(self, params, u):
        xz = dot_f32(u, params["w_in"], u.dtype)
        return xz[..., :self.d_inner], xz[..., self.d_inner:]

    def _ssm_inputs(self, params, x, dt_in):
        """x (.., d_inner) float32 after conv and silu -> (dt, B, C)."""
        r, n = self.dt_rank, self.d_state
        rbc = dot_f32(x, params["w_x"], dt_in)
        dt = dot_f32(rbc[..., :r], params["w_dt"], dt_in)
        dt = jax.nn.softplus(dt + params["b_dt"].astype(jnp.float32))
        return dt, rbc[..., r:r + n], rbc[..., r + n:]

    def _out(self, params, y, x, z, dt_in):
        y = y + params["d"].astype(jnp.float32) * x
        out = dot_f32(y * jax.nn.silu(z), params["w_out"], dt_in)
        return out.astype(dt_in), y.astype(dt_in)

    def _a(self, params):
        return -jnp.exp(params["a_log"].astype(jnp.float32))

    def _conv(self, params, x, history):
        """Causal depthwise convolution of x (b, L, di) that follows
        ``history`` (b, d_conv - 1, di): d_conv shifted multiply-adds."""
        k = self.d_conv
        xp = jnp.concatenate([history.astype(x.dtype), x], axis=1)
        w = params["conv_w"].astype(x.dtype)
        length = x.shape[1]
        acc = sum(xp[:, j:j + length] * w[j] for j in range(k))
        return jax.nn.silu(acc + params["conv_b"].astype(x.dtype)), xp

    # ------------------------------------------------------------ forward
    def _forward(self, params, u, *, training, rng):
        out, y, _ = self.prefill(params, u, self.init_cache(u.shape[0],
                                                            u.dtype))
        return out, y

    def prefill(self, params, u, cache, last=None):
        """Whole-sequence forward from a zero state that also hands over
        the slot's state: the recurrent state after token ``last`` (traced;
        default the final one) and the d_conv - 1 rows up to it. Rows
        after ``last`` (the padding of a prompt bucket) get dt = 0, so the
        state stands still there. Returns (out, y, cache)."""
        length = u.shape[1]
        x, z = self._in(params, u)
        x, xp = self._conv(params, x, jnp.zeros_like(cache["conv"]))
        dt, b, c = self._ssm_inputs(params, x, u.dtype)
        if last is None:
            last = length - 1
        else:
            dt = jnp.where((jnp.arange(length) <= last)[None, :, None],
                           dt, 0.0)
        y, h = selective_scan(x, dt, self._a(params), b, c,
                              jnp.zeros_like(cache["h"]), self.chunk)
        # xp row j is position j - (d_conv - 1): rows last-2 .. last
        conv = jax.lax.dynamic_slice_in_dim(xp, last + 1, self.d_conv - 1,
                                            axis=1)
        out, y = self._out(params, y, x, z, u.dtype)
        return out, y, {"h": h, "conv": conv.astype(cache["conv"].dtype)}

    def decode_step(self, params, u, cache):
        """One token: u (b, 1, d) against the slot's state."""
        x, z = self._in(params, u)
        x, xp = self._conv(params, x, cache["conv"])
        dt, b, c = self._ssm_inputs(params, x, u.dtype)
        h, y = _scan_step(cache["h"], dt[:, 0], x[:, 0], b[:, 0], c[:, 0],
                          self._a(params))
        out, y = self._out(params, y[:, None], x, z, u.dtype)
        return out, y, {"h": h,
                        "conv": xp[:, 1:].astype(cache["conv"].dtype)}


class GatedMemoryUnit(SimpleModule):
    """SambaY's gated memory unit: ``(M * silu(x @ W_1)) @ W_2`` with ``M``
    the same-position row of the memory the self-decoder's last Mamba
    layer put out. No bias, no state: the memory is made anew in every
    step and every prefill."""

    def __init__(self, d_model: int, d_mem: int, init_std: float = 0.02,
                 name: Optional[str] = None):
        super().__init__(name)
        self.d_model, self.d_mem, self.init_std = d_model, d_mem, init_std

    def init(self, rng):
        k1, k2 = jax.random.split(rng)
        return {"w1": _normal(k1, (self.d_model, self.d_mem), self.init_std),
                "w2": _normal(k2, (self.d_mem, self.d_model), self.init_std)}

    def _forward(self, params, x, *, training, rng):
        x, mem = x
        gate = jax.nn.silu(x @ params["w1"].astype(x.dtype))
        return (mem.astype(x.dtype) * gate) @ params["w2"].astype(x.dtype)

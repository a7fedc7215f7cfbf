"""Attention and transformer layers.

The reference snapshot predates attention entirely (SURVEY.md §5
"Long-context / sequence parallelism: Absent" — its only sequence model is
the scanned RNN, nn/Recurrent.scala:27-113). This module is therefore
designed TPU-first rather than for parity: batched bf16-friendly matmuls
shaped for the MXU, a pluggable inner attention function so the same layer
can run

* the plain XLA path (``dot_product_attention`` below — XLA fuses the
  softmax chain),
* a Pallas flash-attention kernel (``bigdl_tpu.ops.flash_attention``), or
* ring attention over a ``seq`` mesh axis
  (``bigdl_tpu.parallel.sequence.ring_attention``) for long-context
  sequence parallelism.

Shapes: inputs are (batch, seq, d_model); heads are folded into the batch
dimension for the two attention matmuls so they are large MXU-friendly
contractions.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.core.module import (
    Module,
    SimpleModule,
    Sequential,
    xavier_uniform,
)

__all__ = [
    "dot_product_attention",
    "make_segment_mask",
    "LayerNorm",
    "RMSNorm",
    "grouped_cache_attention",
    "MultiHeadAttention",
    "CausalGQA",
    "GatedAttention", "LatentAttention",
    "PositionalEncoding",
    "TransformerEncoderLayer",
    "TransformerEncoder",
]

AttnFn = Callable[..., jax.Array]

_NEG_INF = -1e30  # finite mask value: a fully-masked query row softmaxes to
                  # uniform-over-garbage instead of NaN, and (below) its
                  # probabilities are re-zeroed explicitly


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Scaled dot-product attention. q,k,v: (..., seq, head_dim).

    Softmax statistics are computed in fp32 regardless of input dtype
    (bf16-safe), the matmuls stay in the input dtype for the MXU.
    """
    head_dim = q.shape[-1]
    scale = 1.0 / math.sqrt(head_dim)
    # bf16 inputs: multiply on the MXU in bf16, accumulate in fp32
    logits = jnp.einsum("...qd,...kd->...qk", q, k,
                        preferred_element_type=jnp.float32) * scale
    valid = None
    if causal:
        q_len, k_len = logits.shape[-2], logits.shape[-1]
        # bottom-right aligned (flash convention): with q_len < k_len the
        # queries are the suffix of the key sequence, so query i sees keys
        # <= (k_len - q_len) + i
        offset = k_len - q_len
        valid = (jnp.arange(q_len)[:, None] + offset
                 >= jnp.arange(k_len)[None, :])
    if mask is not None:
        valid = mask if valid is None else jnp.logical_and(valid, mask)
    if valid is not None:
        logits = jnp.where(valid, logits, _NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    if valid is not None:
        # zero out fully-masked rows rather than leaving uniform noise
        weights = jnp.where(valid, weights, 0.0)
    return jnp.einsum("...qk,...kd->...qd", weights.astype(q.dtype), v)


def make_segment_mask(segments_q, segments_k=None):
    """Block-diagonal attention mask for packed sequences: several short
    documents concatenated into one training row attend only within
    their own segment (the XLA/TPU-friendly alternative to ragged
    batching — static shapes, no padding waste). ``segments``: (b, s)
    int ids, equal id = same document; id 0 marks padding and attends to
    nothing. Returns a (b, 1, s_q, s_k) bool mask (True = attend) that
    threads through ``MultiHeadAttention``/``TransformerEncoder`` as the
    mask input; combine with ``causal=True`` for packed causal LM
    training. Positions restart per document only if the model's
    position encoding is relative (RoPE applies per absolute offset —
    exact packing equivalence holds for unpositioned encoders and
    approximately for long-context relative schemes).
    """
    if segments_k is None:
        segments_k = segments_q
    same = segments_q[:, :, None] == segments_k[:, None, :]
    live = (segments_q != 0)[:, :, None] & (segments_k != 0)[:, None, :]
    return (same & live)[:, None, :, :]


class LayerNorm(SimpleModule):
    """Layer normalization over the last dimension.

    Not in the reference (its normalizations are batch/spatial —
    nn/BatchNormalization.scala); required substrate for transformers.
    Statistics in fp32, output cast back to the input dtype. ``bias=False``:
    a weight and no bias leaf (Cohere's LayerNorm).
    """

    def __init__(self, dim: int, eps: float = 1e-5, bias: bool = True,
                 name: Optional[str] = None):
        super().__init__(name)
        self.dim = dim
        self.eps = eps
        self.bias = bias

    def init(self, rng):
        del rng
        out = {"weight": jnp.ones((self.dim,))}
        if self.bias:
            out["bias"] = jnp.zeros((self.dim,))
        return out

    def _forward(self, params, x, *, training, rng):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + self.eps)
        if not self.bias:
            return (y * params["weight"]).astype(x.dtype)
        y = y * params["weight"] + params["bias"]
        return y.astype(x.dtype)


class RMSNorm(SimpleModule):
    """Root-mean-square normalization over the last dimension, weight
    only. The mean of squares in fp32, output cast back to the input
    dtype."""

    def __init__(self, dim: int, eps: float = 1e-5,
                 name: Optional[str] = None):
        super().__init__(name)
        self.dim = dim
        self.eps = eps

    def init(self, rng):
        del rng
        return {"weight": jnp.ones((self.dim,))}

    def _forward(self, params, x, *, training, rng):
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                               + self.eps)
        return (y * params["weight"]).astype(x.dtype)


def rope_tables(max_len: int, dim: int, base: float = 10000.0):
    """cos/sin tables for rotary position embeddings (RoPE), NeoX-style
    half-split pairing: dims [0:dim/2] rotate with [dim/2:dim]."""
    import numpy as np

    inv = 1.0 / (base ** (np.arange(0, dim, 2).astype(np.float32) / dim))
    ang = np.arange(max_len).astype(np.float32)[:, None] * inv[None, :]
    return np.cos(ang), np.sin(ang)  # each (max_len, dim/2)


def apply_rope(x, cos, sin):
    """Rotate (..., s, d) by per-position tables (s, d/2)."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c = cos.astype(x.dtype)
    s = sin.astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def grouped_cache_attention(q, kc, vc, idx):
    """``q`` (b, h, m, d) at absolute positions idx..idx+m-1 against a
    cache ``kc``, ``vc`` (b, n_kv, S, d) that already holds those rows:
    causal softmax attention read at the cache's own head count (the
    fold ``MultiHeadAttention.decode_chunk`` describes: the heads of a
    group and the chunk's rows are the rows of one contraction a KV
    head), float32 scores, mask and softmax, the probabilities cast to
    the activations' dtype. It is ``ops.cache_attention.attend_rows``
    with rows 0..idx+m-1 live: alone the plain form over all S rows; its
    batching rule bounds a one-token step's read by each slot's live
    rows (an m > 1 chunk, whose rows need a causal mask each, is read
    whole).

    Returns (b, h, m, d) float32."""
    from bigdl_tpu.ops.cache_attention import attend_rows
    b, h, m, d = q.shape
    o = attend_rows(q.reshape(b, kc.shape[1], -1, d), kc, vc, idx + m,
                    1.0 / math.sqrt(d), m)
    return o.reshape(b, h, m, d)


class MultiHeadAttention(SimpleModule):
    """Multi-head (self- or cross-) attention.

    ``attn_impl`` swaps the inner attention: None -> plain XLA path;
    "flash" -> Pallas flash-attention kernel; or any callable with the
    ``dot_product_attention`` signature (ring attention passes a shard_map'd
    callable here). ``rope=True`` rotates q/k by position (RoPE) instead
    of relying on an additive encoding — relative-position attention that
    extrapolates better at long context; self-attention only.
    """

    def __init__(
        self,
        d_model: int,
        num_heads: int,
        causal: bool = False,
        attn_impl: Optional[AttnFn | str] = None,
        num_kv_heads: Optional[int] = None,
        rope: bool = False,
        rope_max_len: int = 8192,
        param_dtype=jnp.float32,
        name: Optional[str] = None,
    ):
        super().__init__(name)
        if d_model % num_heads:
            raise ValueError(f"d_model {d_model} not divisible by "
                             f"num_heads {num_heads}")
        self.d_model = d_model
        self.num_heads = num_heads
        self.head_dim = d_model // num_heads
        # grouped-query attention: K/V projected to num_kv_heads heads
        # and broadcast over num_heads//num_kv_heads query groups
        # (num_kv_heads=1 is multi-query attention); shrinks the KV cache
        # and the K/V projection FLOPs by the group factor
        self.num_kv_heads = num_kv_heads or num_heads
        if num_heads % self.num_kv_heads:
            raise ValueError(f"num_heads {num_heads} not divisible by "
                             f"num_kv_heads {self.num_kv_heads}")
        self.causal = causal
        self.param_dtype = param_dtype
        self.rope = rope
        if rope:
            if self.head_dim % 2:
                raise ValueError("RoPE needs an even head_dim")
            self._rope_cos, self._rope_sin = rope_tables(
                rope_max_len, self.head_dim)
        if attn_impl == "flash":
            from bigdl_tpu.ops import flash_attention
            attn_impl = flash_attention
        elif attn_impl == "blockwise":
            from bigdl_tpu.ops import blockwise_attention
            attn_impl = blockwise_attention
        self.attn_fn: AttnFn = attn_impl or dot_product_attention
        import inspect
        try:
            self._attn_takes_segments = "segments" in inspect.signature(
                self.attn_fn).parameters
        except (TypeError, ValueError):
            self._attn_takes_segments = False

    def init(self, rng):
        ks = jax.random.split(rng, 4)
        d = self.d_model
        dkv = self.num_kv_heads * self.head_dim
        mk = lambda k, dout: xavier_uniform(k, (d, dout), d, dout,
                                            self.param_dtype)
        return {
            "wq": mk(ks[0], d), "wk": mk(ks[1], dkv), "wv": mk(ks[2], dkv),
            "wo": mk(ks[3], d),
            "bq": jnp.zeros((d,), self.param_dtype),
            "bk": jnp.zeros((dkv,), self.param_dtype),
            "bv": jnp.zeros((dkv,), self.param_dtype),
            "bo": jnp.zeros((d,), self.param_dtype),
        }

    def _split_heads(self, x, n_heads: Optional[int] = None):
        b, s, f = x.shape
        n = n_heads or self.num_heads
        return x.reshape(b, s, n, f // n).transpose(0, 2, 1, 3)

    def _expand_kv(self, kv):
        """Copy (b, n_kv, s, d) K/V out to the query head count, for
        ``attn_fn`` alone (``_forward``, ``prefill``): the flash kernel
        takes equal head counts. ``decode_chunk`` reads K/V grouped."""
        g = self.num_heads // self.num_kv_heads
        return kv if g == 1 else jnp.repeat(kv, g, axis=1)

    def _rope(self, x, pos0):
        """Rotate (b, h, s, d) starting at absolute position ``pos0``."""
        s = x.shape[-2]
        cos = jax.lax.dynamic_slice_in_dim(
            jnp.asarray(self._rope_cos), pos0, s, 0)
        sin = jax.lax.dynamic_slice_in_dim(
            jnp.asarray(self._rope_sin), pos0, s, 0)
        return apply_rope(x, cos, sin)

    def _merge_heads(self, x):
        b, h, s, d = x.shape
        return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)

    def _forward(self, params, x, *, training, rng):
        # input forms: tensor (self-attention); (q_in, kv_in) (cross);
        # (q_in, kv_in, mask) where mask is (b, s_k) key-padding bool, a
        # broadcastable (b|1, h|1, s_q, s_k) attention mask, or — when
        # integer-dtyped — (b, s) packed-document segment ids (the flash
        # kernel applies those in-kernel; other impls get the expanded
        # block-diagonal mask)
        mask = None
        segments = None
        if isinstance(x, (tuple, list)):
            q_in, kv_in = x[0], x[1]
            mask = x[2] if len(x) > 2 else None
        else:
            q_in = kv_in = x
        if mask is not None and jnp.issubdtype(mask.dtype, jnp.integer):
            segments, mask = mask, None
            if not self._attn_takes_segments:
                mask = make_segment_mask(segments)
                segments = None
        dt = q_in.dtype
        q = q_in @ params["wq"].astype(dt) + params["bq"].astype(dt)
        k = kv_in @ params["wk"].astype(dt) + params["bk"].astype(dt)
        v = kv_in @ params["wv"].astype(dt) + params["bv"].astype(dt)
        q = self._split_heads(q)
        k = self._split_heads(k, self.num_kv_heads)
        v = self._split_heads(v, self.num_kv_heads)
        if self.rope:
            if q_in is not kv_in:
                raise ValueError("RoPE supports self-attention only")
            q = self._rope(q, 0)
            k = self._rope(k, 0)
        k, v = self._expand_kv(k), self._expand_kv(v)
        if mask is not None and mask.ndim == 2:  # (b, s_k) key-padding
            mask = mask[:, None, None, :]
        if segments is not None:
            o = self.attn_fn(q, k, v, causal=self.causal,
                             segments=segments)
        else:
            o = self.attn_fn(q, k, v, causal=self.causal, mask=mask)
        o = self._merge_heads(o)
        return o @ params["wo"].astype(dt) + params["bo"].astype(dt)

    # ----------------------------------------------- autoregressive decode
    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32):
        # GQA: the cache stores only num_kv_heads heads — the memory win
        shape = (batch, self.num_kv_heads, max_len, self.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def _qkv(self, params, x):
        dt = x.dtype
        q = x @ params["wq"].astype(dt) + params["bq"].astype(dt)
        k = x @ params["wk"].astype(dt) + params["bk"].astype(dt)
        v = x @ params["wv"].astype(dt) + params["bv"].astype(dt)
        return (self._split_heads(q),
                self._split_heads(k, self.num_kv_heads),
                self._split_heads(v, self.num_kv_heads))

    def prefill(self, params, x, cache):
        """Full-prompt forward that also writes K/V into the cache
        (positions 0..s-1; RoPE-rotated K is what gets cached, so decode
        steps never re-rotate history). Returns (out, cache)."""
        q, k, v = self._qkv(params, x)
        if self.rope:
            q, k = self._rope(q, 0), self._rope(k, 0)
        o = self.attn_fn(q, self._expand_kv(k), self._expand_kv(v),
                         causal=True, mask=None)
        cache = {
            "k": jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)),
            "v": jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)),
        }
        dt = x.dtype
        o = self._merge_heads(o)
        return o @ params["wo"].astype(dt) + params["bo"].astype(dt), cache

    def decode_step(self, params, x, cache, idx):
        """One-token step: x (b, 1, d), ``idx`` = tokens already cached.
        Appends this token's K/V at ``idx`` and attends over 0..idx."""
        return self.decode_chunk(params, x, cache, idx)

    def decode_chunk(self, params, x, cache, idx):
        """m-token step: x (b, m, d) at absolute positions idx..idx+m-1.
        Writes the chunk's K/V at those positions FIRST, then attends
        causally within the chunk (row i sees cache 0..idx+i), so the
        chunk is exactly m sequential decode_steps fused into one
        dispatch — the primitive speculative verification and
        prefix-cache suffix prefill are built on. Each query row's
        scores/softmax/weighted-sum are row-independent, so the m=1
        case IS decode_step (and per-row results match the sequential
        path bit-for-bit on the dense CPU path — pinned in tests).

        The cache is read once, at its stored head count: the g heads
        of a group and the m chunk rows fold into one row axis (row
        r = j*m + i: head j, chunk position i), both contractions are
        matmuls of those rows against the group's (S, d) K and V, in the
        activations' dtype with f32 accumulation, and no expanded copy
        of K/V exists. Alone, all S rows are read and the dead ones
        masked; under the engine's ``vmap`` a one-token step reads each
        slot's live rows only (``ops.cache_attention``'s batching rule).

        Caller must keep idx + m <= cache length: ``write_rows`` is a
        dynamic_update_slice, which clamps out-of-range starts and would
        silently shift the write window."""
        q, k, v = self._qkv(params, x)
        if self.rope:
            q, k = self._rope(q, idx), self._rope(k, idx)
        from bigdl_tpu.ops.cache_write import write_rows
        kc = write_rows(cache["k"], k, idx)
        vc = write_rows(cache["v"], v, idx)
        o = grouped_cache_attention(q, kc, vc, idx).astype(x.dtype)
        dt = x.dtype
        o = self._merge_heads(o)
        return (o @ params["wo"].astype(dt) + params["bo"].astype(dt),
                {"k": kc, "v": vc})


class CausalGQA(SimpleModule):
    """Causal softmax GQA with no bias and no q/k norm, ``num_heads *
    head_dim`` free of ``d_model``, and three things a layer may or may
    not have:

    * ``gate``: an elementwise output gate (Qwen3-Next's gated attention,
      as Solar Open 2's softmax layers use it): ``out = (softmax(q k^T /
      sqrt(head_dim)) v * sigmoid(x Wg)) Wo``, the gate on the
      concatenated heads;
    * ``rope_theta``: q and k rotated at their absolute positions
      (angles and rotation in float32), None for no positional encoding;
      ``rope_pairing`` ``"half"`` turns dims ``(i, i + head_dim / 2)``
      together (NeoX), ``"interleaved"`` dims ``(2i, 2i + 1)`` (GPT-J), pair
      ``i`` at ``theta^(-2i / head_dim)`` either way. K is cached
      **rotated**, so the order of rows in a cache is free;
    * ``window``: query ``i`` sees keys ``j`` with ``i - window < j <=
      i``, and a slot holds a ring of ``min(window, max_len)`` rows,
      position ``p`` at row ``p % ring``, of which the first ``min(p + 1,
      ring)`` are live: a validity count is the whole mask.

    Prefill runs ``attn_impl`` (``"flash"``: the Pallas forward kernel,
    with its ``window`` where the prompt's bucket is longer than the
    window; a mask on the dense attention otherwise) on K/V copied out to
    the query head count; a decode step writes its row with ``write_rows``
    and reads the cache grouped (``ops.cache_attention.attend_rows``)."""

    def __init__(self, d_model: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, attn_impl: Optional[AttnFn | str] = None,
                 init_std: float = 0.02, gate: bool = False,
                 rope_theta: Optional[float] = None,
                 window: Optional[int] = None, rope_pairing: str = "half",
                 name: Optional[str] = None):
        super().__init__(name)
        if num_heads % num_kv_heads:
            raise ValueError(f"num_heads {num_heads} not divisible by "
                             f"num_kv_heads {num_kv_heads}")
        if rope_pairing not in ("half", "interleaved"):
            raise ValueError(f"rope_pairing {rope_pairing!r}: half or "
                             "interleaved")
        self.d_model, self.head_dim = d_model, head_dim
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.init_std = init_std
        self.gate, self.rope_theta, self.window = gate, rope_theta, window
        self.rope_pairing = rope_pairing
        if attn_impl == "flash":
            from bigdl_tpu.ops import flash_attention
            attn_impl = flash_attention
        self.attn_fn: AttnFn = attn_impl or dot_product_attention

    def init(self, rng):
        ks = jax.random.split(rng, 5)
        d, hq = self.d_model, self.num_heads * self.head_dim
        hkv = self.num_kv_heads * self.head_dim
        mk = lambda k, shape: self.init_std * jax.random.normal(k, shape)
        out = {"wq": mk(ks[0], (d, hq)), "wk": mk(ks[1], (d, hkv)),
               "wv": mk(ks[2], (d, hkv)), "wo": mk(ks[4], (hq, d))}
        if self.gate:
            out["wg"] = mk(ks[3], (d, hq))
        return out

    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32):
        rows = min(self.window, max_len) if self.window else max_len
        shape = (batch, self.num_kv_heads, rows, self.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def _heads(self, x, n):
        b, s, _ = x.shape
        return x.reshape(b, s, n, self.head_dim).transpose(0, 2, 1, 3)

    def _rotate(self, x, pos):
        """x (b, h, s, d) rotated at absolute positions ``pos`` (s,)."""
        if self.rope_theta is None:
            return x
        half = self.head_dim // 2
        inv = 1.0 / (self.rope_theta ** (
            jnp.arange(0, self.head_dim, 2, dtype=jnp.float32)
            / self.head_dim))
        ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        if self.rope_pairing == "interleaved":
            pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], half, 2)
            x1, x2 = pairs[..., 0], pairs[..., 1]
            return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                             axis=-1).reshape(x.shape).astype(x.dtype)
        x1 = x[..., :half].astype(jnp.float32)
        x2 = x[..., half:].astype(jnp.float32)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1).astype(x.dtype)

    def _qkv(self, params, x, pos):
        """q, k (rotated at ``pos``) and v of x (b, s, d), by heads."""
        dt = x.dtype
        q = self._heads(x @ params["wq"].astype(dt), self.num_heads)
        k = self._heads(x @ params["wk"].astype(dt), self.num_kv_heads)
        v = self._heads(x @ params["wv"].astype(dt), self.num_kv_heads)
        return self._rotate(q, pos), self._rotate(k, pos), v

    def _out(self, params, x, a):
        """a (b, h, s, d) attention output -> gated, through Wo. The gate
        and its product in float32: one rounding on the way into Wo."""
        b, h, s, d = a.shape
        a = a.transpose(0, 2, 1, 3).reshape(b, s, h * d)
        if self.gate:
            gate = jax.nn.sigmoid(jnp.dot(
                x, params["wg"].astype(x.dtype),
                preferred_element_type=jnp.float32))
            a = a.astype(jnp.float32) * gate
        return a.astype(x.dtype) @ params["wo"].astype(x.dtype)

    def _attend_seq(self, params, x):
        s = x.shape[1]
        q, k, v = self._qkv(params, x, jnp.arange(s))
        g = self.num_heads // self.num_kv_heads
        kq, vq = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        if not self.window or s <= self.window:  # the band is the triangle
            a = self.attn_fn(q, kq, vq, causal=True, mask=None)
        elif self.attn_fn is dot_product_attention:
            from bigdl_tpu.ops.attention_kernel import band_mask
            a = self.attn_fn(q, kq, vq, mask=band_mask(s, s, self.window))
        else:
            a = self.attn_fn(q, kq, vq, causal=True, window=self.window)
        return self._out(params, x, a), k, v

    def _forward(self, params, x, *, training, rng):
        return self._attend_seq(params, x)[0]

    def prefill(self, params, x, cache, last=None):
        """Whole-prompt forward that also fills the slot's cache. A full
        layer's K/V go to rows 0..s-1 (rows of a bucket's padding are
        overwritten by decode before they are attended), and so does a
        ring's while the bucket is no longer than the ring. A longer
        bucket leaves in the ring the last rows up to ``last`` (traced;
        default s-1): row r holds the largest position <= ``last`` that
        is congruent to r, so a padded bucket's rows never enter it.
        Returns (out, cache)."""
        out, k, v = self._attend_seq(params, x)
        s, ring = x.shape[1], cache["k"].shape[2]
        if s > ring:
            last = s - 1 if last is None else last
            src = jnp.clip(last - (last - jnp.arange(ring)) % ring, 0, s - 1)
            return out, {n: jnp.take(t, src, axis=2).astype(cache[n].dtype)
                         for n, t in (("k", k), ("v", v))}
        return out, {n: jax.lax.dynamic_update_slice(
            cache[n], t.astype(cache[n].dtype), (0, 0, 0, 0))
            for n, t in (("k", k), ("v", v))}

    def decode_step(self, params, x, cache, idx):
        """x (b, m, d) at absolute positions idx..idx+m-1 (m = 1 in the
        engine's step, and for a ring always): writes its K/V rows (a
        ring's at ``idx % ring``), then attends over what is live: rows
        0..idx, or the ring's first min(idx + 1, ring)."""
        from bigdl_tpu.ops.cache_write import write_rows
        m = x.shape[1]
        q, k, v = self._qkv(params, x, idx + jnp.arange(m))
        if not self.window:
            new = {"k": write_rows(cache["k"], k, idx),
                   "v": write_rows(cache["v"], v, idx)}
            a = grouped_cache_attention(q, new["k"], new["v"], idx)
            return self._out(params, x, a), new
        if m != 1:
            raise NotImplementedError(
                "CausalGQA: a window layer's ring takes one row a step")
        from bigdl_tpu.ops.cache_attention import attend_rows
        ring = cache["k"].shape[2]
        new = {"k": write_rows(cache["k"], k, idx % ring),
               "v": write_rows(cache["v"], v, idx % ring)}
        b, h, _, d = q.shape
        a = attend_rows(q.reshape(b, self.num_kv_heads, -1, d), new["k"],
                        new["v"], jnp.minimum(idx + 1, ring),
                        1.0 / math.sqrt(d))
        return self._out(params, x, a.reshape(b, h, 1, d)), new


class GatedAttention(CausalGQA):
    """:class:`CausalGQA` with the output gate, no positions and no
    window: Solar Open 2's softmax layers."""

    def __init__(self, d_model: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, attn_impl: Optional[AttnFn | str] = None,
                 init_std: float = 0.02, name: Optional[str] = None):
        super().__init__(d_model, num_heads, num_kv_heads, head_dim,
                         attn_impl=attn_impl, init_std=init_std, gate=True,
                         name=name)


class LatentAttention(SimpleModule):
    """Multi-head latent attention (DeepSeek-V2's MLA, as GLM-4.7-Flash's
    ``glm4_moe_lite`` layers have it), the whole sequence at once, for
    training:

    * ``c_q = RMSNorm(x W_qa)`` (``q_lora_rank``), ``q_h = c_q W_qb[h] =
      [q_nope | q_rope]``;
    * ``[c_kv | k_rope] = x W_kva``, ``c_kv = RMSNorm(c_kv)``
      (``kv_lora_rank``); ``k_rope`` (``rope_dim``) is one rotated key
      that every head shares; ``[k_nope_h | v_h] = c_kv W_kvb[h]``;
    * head ``h`` attends causally with ``q = [q_nope | rope(q_rope)]``
      and ``k = [k_nope_h | rope(k_rope)]`` at the scale of their width,
      ``(nope_dim + rope_dim)^-1/2``; ``out = concat_h(o_h) W_o``.

    Rotation at absolute positions, theta ``rope_theta``, half-split
    pairing of the ``rope_dim`` channels, angles and rotation in float32.
    The norm scales are ``ln_q`` and ``ln_kv``. ``attn_impl="flash"``
    runs the Pallas kernels (forward, dq, dk/dv) on the (b, heads, s,
    width) rows; it needs ``v_dim == nope_dim + rope_dim``. There is no
    serving cache: a latent cache is not built."""

    def __init__(self, d_model: int, num_heads: int, q_lora_rank: int,
                 kv_lora_rank: int, nope_dim: int, rope_dim: int,
                 v_dim: int, rope_theta: float = 10000.0,
                 eps: float = 1e-5, attn_impl: Optional[AttnFn | str] = None,
                 init_std: float = 0.02, name: Optional[str] = None):
        super().__init__(name)
        if rope_dim % 2:
            raise ValueError(f"rope_dim {rope_dim}: the pairs need an even "
                             "width")
        self.d_model, self.num_heads = d_model, num_heads
        self.q_lora_rank, self.kv_lora_rank = q_lora_rank, kv_lora_rank
        self.nope_dim, self.rope_dim, self.v_dim = nope_dim, rope_dim, v_dim
        self.rope_theta, self.init_std = rope_theta, init_std
        self.ln_q, self.ln_kv = RMSNorm(q_lora_rank, eps), RMSNorm(
            kv_lora_rank, eps)
        if attn_impl == "flash":
            from bigdl_tpu.ops import flash_attention
            attn_impl = flash_attention
        self.attn_fn: AttnFn = attn_impl or dot_product_attention

    def init(self, rng):
        ks = jax.random.split(rng, 5)
        d, h = self.d_model, self.num_heads
        qk = self.nope_dim + self.rope_dim
        mk = lambda k, shape: self.init_std * jax.random.normal(k, shape)
        return {"wq_a": mk(ks[0], (d, self.q_lora_rank)),
                "ln_q": self.ln_q.init(None),
                "wq_b": mk(ks[1], (self.q_lora_rank, h * qk)),
                "wkv_a": mk(ks[2], (d, self.kv_lora_rank + self.rope_dim)),
                "ln_kv": self.ln_kv.init(None),
                "wkv_b": mk(ks[3], (self.kv_lora_rank,
                                    h * (self.nope_dim + self.v_dim))),
                "wo": mk(ks[4], (h * self.v_dim, d))}

    def _rotate(self, x, pos):
        """x (..., s, rope_dim) rotated at absolute positions ``pos``."""
        half = self.rope_dim // 2
        inv = 1.0 / (self.rope_theta ** (
            jnp.arange(0, self.rope_dim, 2, dtype=jnp.float32)
            / self.rope_dim))
        ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x1 = x[..., :half].astype(jnp.float32)
        x2 = x[..., half:].astype(jnp.float32)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1).astype(x.dtype)

    def qkv(self, params, x):
        """q, k (b, heads, s, nope + rope) and v (b, heads, s, v_dim) of
        x (b, s, d), rotated at positions 0..s-1."""
        dt, (b, s, _) = x.dtype, x.shape
        h, nope = self.num_heads, self.nope_dim
        w = lambda n: params[n].astype(dt)
        c_q = self.ln_q.forward(params["ln_q"], x @ w("wq_a"))
        q = (c_q @ w("wq_b")).reshape(b, s, h, -1).transpose(0, 2, 1, 3)
        kv = x @ w("wkv_a")
        c_kv = self.ln_kv.forward(params["ln_kv"], kv[..., :self.kv_lora_rank])
        pos = jnp.arange(s)
        k_rope = self._rotate(kv[:, None, :, self.kv_lora_rank:], pos)
        kv = (c_kv @ w("wkv_b")).reshape(b, s, h, -1).transpose(0, 2, 1, 3)
        q = jnp.concatenate([q[..., :nope], self._rotate(q[..., nope:], pos)],
                            axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (b, h, s,
                                                       self.rope_dim))],
            axis=-1)
        return q, k, kv[..., nope:]

    def _forward(self, params, x, *, training, rng):
        b, s, _ = x.shape
        q, k, v = self.qkv(params, x)
        a = self.attn_fn(q, k, v, causal=True, mask=None)
        a = a.transpose(0, 2, 1, 3).reshape(b, s, -1)
        return a.astype(x.dtype) @ params["wo"].astype(x.dtype)


class PositionalEncoding(SimpleModule):
    """Sinusoidal positional encoding added to (batch, seq, d_model).

    The table is precomputed once for ``max_len`` positions (a trace-time
    constant — XLA folds the slice); sequences longer than ``max_len``
    raise at trace time.
    """

    def __init__(self, d_model: int, max_len: int = 4096,
                 name: Optional[str] = None):
        super().__init__(name)
        self.d_model = d_model
        self.max_len = max_len
        import numpy as np
        pos = np.arange(max_len)[:, None].astype(np.float32)
        dim = np.arange(0, d_model, 2).astype(np.float32)
        angle = pos / np.power(10000.0, dim / d_model)  # (max_len, ceil(d/2))
        pe = np.zeros((max_len, d_model), np.float32)
        pe[:, 0::2] = np.sin(angle)
        pe[:, 1::2] = np.cos(angle)[:, : d_model // 2]
        self._table = pe

    def _forward(self, params, x, *, training, rng):
        del params, training, rng
        seq = x.shape[-2]
        if seq > self.max_len:
            raise ValueError(f"sequence length {seq} exceeds "
                             f"max_len {self.max_len}")
        return x + jnp.asarray(self._table[:seq]).astype(x.dtype)


class TransformerEncoderLayer(Module):
    """Pre-LN transformer block: x + MHA(LN(x)); x + MLP(LN(x)).

    Pre-LN (not the post-LN of the original paper) — trains stably without
    warmup, the standard choice for TPU LLM stacks.
    """

    def __init__(
        self,
        d_model: int,
        num_heads: int,
        d_ff: Optional[int] = None,
        causal: bool = False,
        dropout: float = 0.0,
        attn_impl: Optional[AttnFn | str] = None,
        num_kv_heads: Optional[int] = None,
        rope: bool = False,
        rope_max_len: int = 8192,
        name: Optional[str] = None,
    ):
        super().__init__(name)
        d_ff = d_ff or 4 * d_model
        self.d_model, self.d_ff = d_model, d_ff
        from bigdl_tpu.nn.structural import Dropout
        self.dropout = dropout
        self.drop = Dropout(dropout) if dropout > 0.0 else None
        self.ln1 = LayerNorm(d_model)
        self.ln2 = LayerNorm(d_model)
        self.mha = MultiHeadAttention(d_model, num_heads, causal=causal,
                                      attn_impl=attn_impl,
                                      num_kv_heads=num_kv_heads,
                                      rope=rope, rope_max_len=rope_max_len)
        # keep the MLP as explicit params (not a Sequential) for stable
        # checkpoint keys
        self._mlp_dims = (d_model, d_ff)

    def children(self):
        return (self.ln1, self.mha, self.ln2)

    def init(self, rng):
        ks = jax.random.split(rng, 5)
        d, f = self._mlp_dims
        return {
            "ln1": self.ln1.init(ks[0]),
            "mha": self.mha.init(ks[1]),
            "ln2": self.ln2.init(ks[2]),
            "w1": xavier_uniform(ks[3], (d, f), d, f),
            "b1": jnp.zeros((f,)),
            "w2": xavier_uniform(ks[4], (f, d), f, d),
            "b2": jnp.zeros((d,)),
        }

    def apply(self, params, state, x, *, training=False, rng=None):
        # (x, mask) threads a key-padding mask through the stack; the same
        # form is returned so Sequential/TransformerEncoder chains it
        mask = None
        if isinstance(x, (tuple, list)):
            x, mask = x[0], x[1]
        dt = x.dtype
        h = self.ln1.forward(params["ln1"], x)
        h = self.mha.forward(params["mha"],
                             h if mask is None else (h, h, mask),
                             training=training, rng=rng)
        if self.drop is not None:
            rng, k = (jax.random.split(rng) if rng is not None
                      else (None, None))
            h = self.drop.forward({}, h, training=training, rng=k)
        x = x + h
        h = self.ln2.forward(params["ln2"], x)
        h = h @ params["w1"].astype(dt) + params["b1"].astype(dt)
        h = jax.nn.gelu(h)
        h = h @ params["w2"].astype(dt) + params["b2"].astype(dt)
        if self.drop is not None:
            rng, k = (jax.random.split(rng) if rng is not None
                      else (None, None))
            h = self.drop.forward({}, h, training=training, rng=k)
        y = x + h
        return (y if mask is None else (y, mask)), state

    # ----------------------------------------------- autoregressive decode
    def init_cache(self, batch, max_len, dtype=jnp.float32):
        return self.mha.init_cache(batch, max_len, dtype)

    def _mlp(self, params, x):
        dt = x.dtype
        h = self.ln2.forward(params["ln2"], x)
        h = h @ params["w1"].astype(dt) + params["b1"].astype(dt)
        h = jax.nn.gelu(h)
        return x + (h @ params["w2"].astype(dt) + params["b2"].astype(dt))

    def prefill(self, params, x, cache):
        h = self.ln1.forward(params["ln1"], x)
        h, cache = self.mha.prefill(params["mha"], h, cache)
        return self._mlp(params, x + h), cache

    def decode_step(self, params, x, cache, idx):
        h = self.ln1.forward(params["ln1"], x)
        h, cache = self.mha.decode_step(params["mha"], h, cache, idx)
        return self._mlp(params, x + h), cache

    def decode_chunk(self, params, x, cache, idx):
        h = self.ln1.forward(params["ln1"], x)
        h, cache = self.mha.decode_chunk(params["mha"], h, cache, idx)
        return self._mlp(params, x + h), cache


class TransformerEncoder(Sequential):
    """Stack of encoder layers with optional remat.

    ``remat`` wraps each layer in ``jax.checkpoint`` — the HBM-for-FLOPs
    trade that long-context training needs. Accepts:

    * ``False`` — no remat (default);
    * ``True`` / ``"full"`` — save nothing, recompute the whole layer in
      the backward (max HBM savings, ~1/3 extra FLOPs);
    * ``"dots"`` — ``jax.checkpoint_policies.dots_with_no_batch_dims_
      saveable``: matmul outputs stay resident, only elementwise/softmax
      recompute. On TPU this is usually the better point: the MXU work
      (the expensive part) is not redone, while the bandwidth-bound
      intermediates (which XLA refuses to keep anyway once HBM is tight)
      are. The reference has no analog — its graph holds every
      intermediate by design (Scala Module.output fields).
    """

    _REMAT_POLICIES = {
        "full": None,   # jax.checkpoint default: nothing saveable
        "dots": "dots_with_no_batch_dims_saveable",
    }

    def __init__(self, num_layers: int, d_model: int, num_heads: int,
                 d_ff: Optional[int] = None, causal: bool = False,
                 dropout: float = 0.0,
                 attn_impl: Optional[AttnFn | str] = None,
                 remat: bool = False,
                 num_kv_heads: Optional[int] = None,
                 rope: bool = False, rope_max_len: int = 8192,
                 name: Optional[str] = None):
        layers = [
            TransformerEncoderLayer(d_model, num_heads, d_ff, causal,
                                    dropout, attn_impl,
                                    num_kv_heads=num_kv_heads,
                                    rope=rope, rope_max_len=rope_max_len)
            for _ in range(num_layers)
        ]
        super().__init__(*layers, name=name)
        if remat is True:
            remat = "full"
        if remat and remat not in self._REMAT_POLICIES:
            raise ValueError(f"remat must be False/True/'full'/'dots', "
                             f"got {remat!r}")
        self.remat = remat

    def apply(self, params, state, x, *, training=False, rng=None):
        if not self.remat:
            return super().apply(params, state, x, training=training, rng=rng)
        policy_name = self._REMAT_POLICIES[self.remat]
        ckpt_kw = {}
        if policy_name is not None:
            ckpt_kw["policy"] = getattr(jax.checkpoint_policies, policy_name)
        new_state = {}
        for i, m in enumerate(self._modules):
            k = str(i)
            fn = jax.checkpoint(
                lambda p, s, h, r, m=m: m.apply(p, s, h, training=training,
                                                rng=r),
                static_argnums=(), **ckpt_kw)
            r = None if rng is None else jax.random.fold_in(rng, i)
            x, s = fn(params[k], state[k], x, r)
            new_state[k] = s
        return x, new_state

    # ----------------------------------------------- autoregressive decode
    def init_cache(self, batch, max_len, dtype=jnp.float32):
        return {str(i): m.init_cache(batch, max_len, dtype)
                for i, m in enumerate(self._modules)}

    def prefill(self, params, x, cache):
        new = {}
        for i, m in enumerate(self._modules):
            k = str(i)
            x, new[k] = m.prefill(params[k], x, cache[k])
        return x, new

    def decode_step(self, params, x, cache, idx):
        new = {}
        for i, m in enumerate(self._modules):
            k = str(i)
            x, new[k] = m.decode_step(params[k], x, cache[k], idx)
        return x, new

    def decode_chunk(self, params, x, cache, idx):
        """m-token decode: x (b, m, d) at positions idx..idx+m-1 — one
        dispatch verifies a speculative draft chunk or prefills a
        prefix-cache suffix (see MultiHeadAttention.decode_chunk)."""
        new = {}
        for i, m in enumerate(self._modules):
            k = str(i)
            x, new[k] = m.decode_chunk(params[k], x, cache[k], idx)
        return x, new


class DifferentialAttention(SimpleModule):
    """Differential attention (Ye et al., arXiv:2410.05258) as SambaY
    (arXiv:2507.06607) uses it: causal, grouped, without positional
    encoding, optionally over a sliding ``window``, optionally with Q
    alone projected here (``cross``: K and V are another layer's).

    Query heads pair up as (q1_i, q2_i) and KV heads as (k1_j, k2_j),
    (v1_j, v2_j), adjacent heads making a pair; query pair i reads KV pair
    i // g. With ``P1 = softmax(q1 k1^T / sqrt(d))``, ``P2`` likewise and
    ``V = [v1 | v2]``::

        o_i = RMSNorm(P1 V - lam * P2 V) * (1 - lam0)
        lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0
        lam0 = 0.8 - 0.6 * exp(-0.3 * depth)

    Everything here works on PAIRS: a pair of 64-wide heads is one
    128-wide row ``[h1 | h2]`` (the plain reshape of the projection's
    output), K and V are cached that way, and the two softmaxes are the
    scores of ``[q1 | 0]`` and ``[0 | q2]`` against the one ``[k1 | k2]``:
    both read K once, at full lane width.

    A slot's cache is ``{"k", "v"}`` of ``(batch, kv_pairs, rows,
    2 * head_dim)``: ``rows = max_len`` for a full layer, and for a window
    layer a ring of ``window`` rows written at ``position % window``.
    Without positional encoding the order inside the ring is free, so the
    count of valid rows is the whole mask.
    """

    def __init__(self, d_model: int, num_heads: int, num_kv_heads: int,
                 depth: int, window: Optional[int] = None,
                 cross: bool = False, attn_impl: Optional[str] = None,
                 eps: float = 1e-5, init_std: float = 0.02,
                 name: Optional[str] = None):
        super().__init__(name)
        if num_heads % 2 or num_kv_heads % 2 or d_model % num_heads:
            raise ValueError("differential attention pairs heads: "
                             f"{num_heads} query and {num_kv_heads} KV "
                             f"heads over d_model {d_model}")
        if (num_heads // 2) % (num_kv_heads // 2):
            raise ValueError(f"{num_heads // 2} query pairs not divisible "
                             f"by {num_kv_heads // 2} KV pairs")
        if attn_impl not in (None, "flash"):
            raise ValueError(f"attn_impl {attn_impl!r} not in (None, "
                             "'flash')")
        self.d_model = d_model
        self.head_dim = d_model // num_heads
        self.pair_dim = 2 * self.head_dim
        self.kv_pairs = num_kv_heads // 2
        self.group = (num_heads // 2) // self.kv_pairs
        self.window = window
        self.cross = cross
        self.flash = attn_impl == "flash"
        self.eps = eps
        self.init_std = init_std
        self.lam0 = 0.8 - 0.6 * math.exp(-0.3 * depth)

    def init(self, rng):
        ks = jax.random.split(rng, 8)
        d, dkv = self.d_model, self.kv_pairs * self.pair_dim
        mk = lambda k, dout: self.init_std * jax.random.normal(k, (d, dout))
        lam = lambda k: 0.1 * jax.random.normal(k, (self.head_dim,))
        p = {"wq": mk(ks[0], d), "bq": jnp.zeros((d,)),
             "wo": mk(ks[1], d), "bo": jnp.zeros((d,)),
             "lq1": lam(ks[2]), "lk1": lam(ks[3]),
             "lq2": lam(ks[4]), "lk2": lam(ks[5]),
             "ln_sub": {"weight": jnp.ones((self.pair_dim,))}}
        if not self.cross:
            p.update(wk=mk(ks[6], dkv), bk=jnp.zeros((dkv,)),
                     wv=mk(ks[7], dkv), bv=jnp.zeros((dkv,)))
        return p

    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32):
        rows = self.window or max_len
        shape = (batch, self.kv_pairs, rows, self.pair_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    # ------------------------------------------------------------- pieces
    def _pairs(self, x, n):
        b, s, _ = x.shape
        return x.reshape(b, s, n, self.pair_dim).transpose(0, 2, 1, 3)

    def project_q(self, params, x):
        """x (b, s, d) -> (b, kv_pairs, group, s, pair_dim)."""
        dt = x.dtype
        q = self._pairs(x @ params["wq"].astype(dt) + params["bq"].astype(dt),
                        self.kv_pairs * self.group)
        return q.reshape(q.shape[0], self.kv_pairs, self.group,
                         *q.shape[2:])

    def project_kv(self, params, x):
        """x (b, s, d) -> K, V of (b, kv_pairs, s, pair_dim)."""
        dt = x.dtype
        k = x @ params["wk"].astype(dt) + params["bk"].astype(dt)
        v = x @ params["wv"].astype(dt) + params["bv"].astype(dt)
        return self._pairs(k, self.kv_pairs), self._pairs(v, self.kv_pairs)

    def _halves(self, q):
        """(b, k, g, m, 2d) -> (b, k, 2g, m, 2d): [q1 | 0] then [0 | q2]
        of every pair, so row c of the new axis is softmax c + 1."""
        first = (jnp.arange(self.pair_dim) < self.head_dim).astype(q.dtype)
        both = jnp.stack([q * first, q * (1 - first)], axis=3)
        return both.reshape(q.shape[0], q.shape[1], -1, *q.shape[3:])

    def _scores_softmax_v(self, q2, k, v, live):
        """q2 (b, k, r, m, 2d) against k, v (b, k, S, 2d) under ``live``
        (broadcastable to (b, k, 1, m, S)): float32 softmax, operands in
        the activations' dtype. -> (b, k, r, m, 2d) float32."""
        s = jnp.einsum("bkrmd,bksd->bkrms", q2, k.astype(q2.dtype),
                       preferred_element_type=jnp.float32)
        s = jnp.where(live, s / math.sqrt(self.head_dim), _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bkrms,bksd->bkrmd", p.astype(q2.dtype),
                          v.astype(q2.dtype),
                          preferred_element_type=jnp.float32)

    def _difference(self, params, a, dt):
        """a (b, k, 2g, m, 2d) float32, the two softmaxes' outputs ->
        (b, m, d_model): difference, sub-norm, merge, output projection."""
        b, k, _, m, d2 = a.shape
        a = a.reshape(b, k, self.group, 2, m, d2)
        f32 = lambda n: params[n].astype(jnp.float32)
        lam = (jnp.exp(jnp.sum(f32("lq1") * f32("lk1")))
               - jnp.exp(jnp.sum(f32("lq2") * f32("lk2"))) + self.lam0)
        o = a[:, :, :, 0] - lam * a[:, :, :, 1]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + self.eps)
        o = o * params["ln_sub"]["weight"].astype(jnp.float32)
        o = (o * (1.0 - self.lam0)).astype(dt)
        o = o.transpose(0, 3, 1, 2, 4).reshape(b, m, self.d_model)
        return o @ params["wo"].astype(dt) + params["bo"].astype(dt)

    def _banded(self, q2, k, v):
        """Window attention over a sequence longer than the window, in
        blocks of ``window`` queries against their own block of keys and
        the one before: O(s x window) scores."""
        w = self.window
        b, kp, r, s, d2 = q2.shape
        pad = -s % w
        if pad:
            q2 = jnp.pad(q2, ((0, 0),) * 3 + ((0, pad), (0, 0)))
            k, v = (jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0)))
                    for t in (k, v))
        nb = (s + pad) // w

        def two_blocks(t):  # (b, k, s, d) -> (b, k * nb, 2w, d)
            t = t.reshape(b, kp, nb, w, d2)
            prev = jnp.concatenate([jnp.zeros_like(t[:, :, :1]),
                                    t[:, :, :-1]], axis=2)
            return jnp.concatenate([prev, t], axis=3).reshape(
                b, kp * nb, 2 * w, d2)

        qb = q2.reshape(b, kp, r, nb, w, d2).transpose(0, 1, 3, 2, 4, 5)
        qb = qb.reshape(b, kp * nb, r, w, d2)
        i = jnp.arange(w)[:, None]
        j = jnp.arange(2 * w)[None, :]
        # key j of the pair of blocks sits at w * (n - 1) + j, query i at
        # w * n + i: seen iff i - w < key <= i, and no block precedes n = 0
        live = (j > i) & (j <= i + w)
        live = live[None] & ((jnp.arange(nb) > 0)[:, None, None] | (j >= w))
        live = jnp.broadcast_to(live[None], (kp, nb, w, 2 * w)).reshape(
            1, kp * nb, 1, w, 2 * w)
        a = self._scores_softmax_v(qb, two_blocks(k), two_blocks(v), live)
        a = a.reshape(b, kp, nb, r, w, d2).transpose(0, 1, 3, 2, 4, 5)
        return a.reshape(b, kp, r, nb * w, d2)[:, :, :, :s]

    def attend_seq(self, params, x, k, v):
        """Causal attention of every row of x (b, s, d) over K, V
        (b, kv_pairs, s, pair_dim) of the same positions."""
        q2 = self._halves(self.project_q(params, x))
        s = x.shape[1]
        if self.window and s > self.window:
            a = self._banded(q2, k, v)
        elif self.flash and not self.window:
            # the kernel scales by 1/sqrt of the padded width
            from bigdl_tpu.ops import flash_attention
            b, kp, r, _, d2 = q2.shape
            qf = (q2 * math.sqrt(2.0)).astype(q2.dtype).reshape(
                b, kp * r, s, d2)
            a = flash_attention(qf, jnp.repeat(k, r, axis=1),
                                jnp.repeat(v, r, axis=1), causal=True)
            a = a.reshape(b, kp, r, s, d2).astype(jnp.float32)
        else:
            i = jnp.arange(s)[:, None]
            j = jnp.arange(s)[None, :]
            live = j <= i
            if self.window:
                live &= j > i - self.window
            a = self._scores_softmax_v(q2, k, v, live)
        return self._difference(params, a, x.dtype)

    def _forward(self, params, x, *, training, rng):
        # a tensor: self-attention; (x, k, v): Q-only cross-attention
        if isinstance(x, (tuple, list)):
            return self.attend_seq(params, *x)
        return self.attend_seq(params, x, *self.project_kv(params, x))

    # ----------------------------------------------- autoregressive decode
    def prefill(self, params, x, cache, last=None):
        """Whole-prompt forward that also fills the slot's cache. A full
        layer's K/V go to rows 0..s-1 (rows after ``last`` hold a padded
        bucket's garbage, which decode overwrites before it attends
        them). A window layer's ring gets the last ``window`` real rows,
        position p at row p % window: row r holds the largest position
        <= ``last`` (traced; default s-1) that is congruent to r.
        Returns (out, cache, (k, v))."""
        k, v = self.project_kv(params, x)
        out = self.attend_seq(params, x, k, v)
        s = x.shape[1]
        if self.window:
            w = self.window
            last = s - 1 if last is None else last
            r = jnp.arange(w)
            src = jnp.clip(last - (last - r) % w, 0, s - 1)
            new = {n: jnp.take(t, src, axis=2).astype(cache[n].dtype)
                   for n, t in (("k", k), ("v", v))}
        else:
            new = {n: jax.lax.dynamic_update_slice(
                       cache[n], t.astype(cache[n].dtype), (0, 0, 0, 0))
                   for n, t in (("k", k), ("v", v))}
        return out, new, (k, v)

    def decode_step(self, params, x, cache, idx):
        """One token x (b, 1, d) at absolute position ``idx`` (traced):
        writes its K/V (at ``idx % window`` of a ring) and attends over
        what is live: positions 0..idx, or the ring's min(idx + 1,
        window) valid rows. A cross layer writes nothing and reads the
        cache it is given."""
        if self.cross:
            new = cache
        else:
            k, v = self.project_kv(params, x)
            at = idx % self.window if self.window else idx
            from bigdl_tpu.ops.cache_write import write_rows
            new = {n: write_rows(cache[n], t, at)
                   for n, t in (("k", k), ("v", v))}
        from bigdl_tpu.ops.cache_attention import attend_rows
        count = jnp.minimum(idx + 1, self.window) if self.window else idx + 1
        q2 = self._halves(self.project_q(params, x))
        a = attend_rows(q2[:, :, :, 0], new["k"], new["v"], count,
                        1.0 / math.sqrt(self.head_dim))
        return self._difference(params, a[:, :, :, None], x.dtype), new


__all__.append("DifferentialAttention")

"""SambaY decoder-decoder language model with differential attention
(Ren et al., arXiv:2507.06607; ``model_type: phi4flash``,
Phi-4-mini-flash-reasoning).

Every layer is ``h += Mixer_l(LN1_l(h)); h += SwiGLU_l(LN2_l(h))``; what
differs from layer to layer is the mixer. With ``L`` layers and
``mb_per_layer = 2``:

* self-decoder, layers ``0 .. L/2 + 1``: even layers are Mamba-1
  (:class:`bigdl_tpu.nn.Mamba`), odd layers differential attention over a
  sliding window, and the last one, ``L/2 + 1``, differential attention
  over the whole prefix. Layer ``L/2``, the last Mamba layer, also emits
  its scan output as the MEMORY ``M``; layer ``L/2 + 1``'s keys and values
  are the one SHARED KV cache;
* cross-decoder, layers ``L/2 + 2 .. L - 1``: even layers are gated memory
  units over ``M`` (:class:`bigdl_tpu.nn.GatedMemoryUnit`), odd layers
  differential cross-attention that projects a query alone and reads the
  shared cache.

No positional encoding anywhere, the embedding is not scaled, the output
head is the embedding. What a serving slot holds is therefore of four
kinds (:meth:`SambaYLM.init_cache`): a ring of ``window`` K/V rows for
each window layer, ``max_len`` K/V rows for the one shared layer, and for
each Mamba layer a float32 state and the convolution's last rows. The
memory is cached nowhere: every step and every prefill makes it anew.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu import nn
from bigdl_tpu.core.module import Module

__all__ = ["SambaYLM", "sambay_lm"]

# mixer kind -> (jax.named_scope of its ops, kind of the cache it keeps)
_KINDS = {"mamba": ("mamba", None), "window": ("attn_window", "kv_window"),
          "full": ("attn_shared", "kv_full"), "gmu": ("gmu", None),
          "cross": ("attn_shared", None)}


class SambaYLM(Module):
    """Decoder-decoder LM: Mamba + window attention, then gated memory
    units + cross-attention over one shared KV cache. Serves through
    ``DecodeEngine``'s dense path (``init_cache`` / ``prefill_logits`` /
    ``decode_logits``); a slot holds recurrent state, which paging, prefix
    sharing, speculation, kv8 and tp serving cannot carry yet."""

    recurrent_state = True

    def __init__(self, vocab: int, d_model: int = 256, num_layers: int = 8,
                 num_heads: int = 4, num_kv_heads: Optional[int] = None,
                 d_ff: Optional[int] = None, window: int = 512,
                 mb_per_layer: int = 2, max_len: int = 4096,
                 attn_impl: Optional[str] = None, remat: bool = False,
                 compute_dtype=None, init_std: float = 0.02,
                 name: Optional[str] = None):
        super().__init__(name or "SambaYLM")
        if mb_per_layer != 2:
            raise ValueError("only the published pattern is built: "
                             f"mb_per_layer 2, got {mb_per_layer}")
        if num_layers < 8 or num_layers % 4:
            raise ValueError("num_layers must be a multiple of 4 and at "
                             "least 8 (the memory layer L/2 is a Mamba "
                             f"layer, and two layers read it), got "
                             f"{num_layers}")
        self.vocab, self.d_model, self.max_len = vocab, d_model, max_len
        self.d_ff = d_ff or 4 * d_model
        self.window = window
        self.compute_dtype = compute_dtype
        self.remat = bool(remat)
        self.init_std = init_std  # the source's initializer_range
        self.memory_layer = num_layers // 2
        self.shared_layer = self.memory_layer + 1
        attn = dict(d_model=d_model, num_heads=num_heads,
                    num_kv_heads=num_kv_heads or num_heads,
                    init_std=init_std)
        self.kinds, self.mixers, self.norms = [], [], []
        for l in range(num_layers):
            if l > self.shared_layer:
                kind = "cross" if l % 2 else "gmu"
            elif l % 2 == 0:
                kind = "mamba"
            else:
                kind = "full" if l == self.shared_layer else "window"
            self.kinds.append(kind)
            if kind == "mamba":
                mixer = nn.Mamba(d_model, init_std=init_std)
            elif kind == "gmu":
                mixer = nn.GatedMemoryUnit(d_model, 2 * d_model, init_std)
            else:
                mixer = nn.DifferentialAttention(
                    depth=l, cross=kind == "cross",
                    window=window if kind == "window" else None,
                    attn_impl=attn_impl, **attn)
            self.mixers.append(mixer)
            self.norms.append((nn.LayerNorm(d_model), nn.LayerNorm(d_model)))
        self.ln_f = nn.LayerNorm(d_model)

    def children(self):
        return (*self.mixers, *(n for pair in self.norms for n in pair),
                self.ln_f)

    def init(self, rng):
        d, ff = self.d_model, self.d_ff
        layers = {}
        for l, mixer in enumerate(self.mixers):
            ks = jax.random.split(jax.random.fold_in(rng, l), 3)
            layers[str(l)] = {
                "ln1": self.norms[l][0].init(None),
                "mixer": mixer.init(ks[0]),
                "ln2": self.norms[l][1].init(None),
                "w1": self.init_std * jax.random.normal(ks[1], (d, 2 * ff)),
                "w2": self.init_std * jax.random.normal(ks[2], (ff, d))}
        emb = self.init_std * jax.random.normal(
            jax.random.fold_in(rng, len(layers)), (self.vocab, d))
        return {"emb": {"weight": emb}, "layers": layers,
                "ln_f": self.ln_f.init(None)}

    # -------------------------------------------------------------- cache
    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32):
        """What ``batch`` slots hold, keyed by layer: ``{"k", "v"}`` rings
        of ``window`` rows (window layers), ``{"k", "v"}`` of ``max_len``
        rows (the shared layer), ``{"h", "conv"}`` (Mamba layers); gated
        memory units and cross layers keep nothing."""
        cache = {}
        for l, (kind, mixer) in enumerate(zip(self.kinds, self.mixers)):
            if kind == "mamba":
                cache[str(l)] = mixer.init_cache(batch, dtype)
            elif kind in ("window", "full"):
                cache[str(l)] = mixer.init_cache(batch, max_len, dtype)
        return cache

    def cache_bytes_by_kind(self, cache) -> dict:
        """Resident bytes of a cache pytree by kind of leaf: ``kv_full``,
        ``kv_window``, ``ssm_state``, ``conv_state``."""
        from bigdl_tpu.obs.memory import tree_bytes
        out = dict.fromkeys(("kv_full", "kv_window", "ssm_state",
                             "conv_state"), 0)
        for l, entry in cache.items():
            kv = _KINDS[self.kinds[int(l)]][1]
            if kv is not None:
                out[kv] += tree_bytes(entry)
            else:
                out["ssm_state"] += tree_bytes(entry["h"])
                out["conv_state"] += tree_bytes(entry["conv"])
        return out

    def prompt_buckets(self, max_len: int, dtype) -> tuple:
        """The prefill's prompt-length ladder: the flash kernel's plans at
        the width of a head pair, which is what the full layers call it
        with."""
        from bigdl_tpu.ops.attention_kernel import serving_prefill_buckets
        return serving_prefill_buckets(
            max_len, self.mixers[self.shared_layer].pair_dim, True, dtype)

    # ------------------------------------------------------------ forward
    def _embed(self, params, tokens):
        h = jnp.take(params["emb"]["weight"], tokens, axis=0)
        return h if self.compute_dtype is None else h.astype(
            self.compute_dtype)

    def _logits(self, params, h):
        h = self.ln_f.forward(params["ln_f"], h)
        return h @ params["emb"]["weight"].astype(h.dtype).T

    def _mlp(self, p, l, x):
        """SwiGLU: ``[g, u] = h @ W1; (u * silu(g)) @ W2``. Operands in the
        activations' dtype, the products and the gate in float32: one
        rounding on the way into W2 and one into the residual stream."""
        h = self.norms[l][1].forward(p["ln2"], x)
        gu = nn.dot_f32(h, p["w1"], h.dtype)
        g, u = gu[..., :self.d_ff], gu[..., self.d_ff:]
        return x + nn.dot_f32(u * jax.nn.silu(g), p["w2"],
                              h.dtype).astype(x.dtype)

    def _layer(self, l, p, x, carry, cache, last, pos):
        """Layer ``l`` on x (b, s, d): the prompt's rows up to ``last``
        when ``pos`` is None (prefill; s = the bucket), else one token at
        position ``pos``. ``carry`` = (memory, shared K/V) as the
        self-decoder left them. Returns (x, carry, this layer's cache)."""
        kind, mixer = self.kinds[l], self.mixers[l]
        mem, shared = carry
        h = self.norms[l][0].forward(p["ln1"], x)
        mp = p["mixer"]
        with jax.named_scope(_KINDS[kind][0]):
            if kind == "mamba":
                if pos is None:
                    h, y, cache = mixer.prefill(mp, h, cache, last)
                else:
                    h, y, cache = mixer.decode_step(mp, h, cache)
                if l == self.memory_layer:
                    mem = y
            elif kind == "gmu":
                h = mixer.forward(mp, (h, mem))
            elif kind == "cross":
                if pos is None:
                    h = mixer.forward(mp, (h, *shared))
                else:
                    h, _ = mixer.decode_step(mp, h, shared, pos)
            elif pos is None:
                h, cache, kv = mixer.prefill(mp, h, cache, last)
                if kind == "full":
                    shared = kv
            else:
                h, cache = mixer.decode_step(mp, h, cache, pos)
                if kind == "full":
                    shared = cache
        return self._mlp(p, l, x + h), (mem, shared), cache

    def _run(self, params, h, cache, last=None, pos=None):
        carry, new = (None, None), {}
        for l in range(len(self.kinds)):
            fn = lambda p, x, carry, c, l=l: self._layer(l, p, x, carry, c,
                                                         last, pos)
            if self.remat and pos is None:
                fn = jax.checkpoint(fn)
            k = str(l)
            h, carry, c = fn(params["layers"][k], h, carry, cache.get(k))
            if c is not None:
                new[k] = c
        return h, new

    def logits(self, params, tokens):
        """(b, s) token ids -> (b, s, vocab) float32 logits: the whole
        forward with no cache to keep."""
        b, s = tokens.shape
        h = self._embed(params, tokens)
        h, _ = self._run(params, h, self.init_cache(b, s, h.dtype))
        return self._logits(params, h).astype(jnp.float32)

    def apply(self, params, state, x, *, training=False, rng=None):
        return jax.nn.log_softmax(self.logits(params, x), axis=-1), state

    # --------------------------------------------- autoregressive decoding
    def prefill_logits(self, params, tokens, cache, last=None):
        """Serving prefill: the prompt (b, s), right-padded to its bucket,
        once through every layer -> the next-token logits (b, vocab) at
        position ``last`` (traced; default s - 1) and the slot's cache as
        it stands after token ``last``: the scan, the convolution's
        history and the rings all stop there, so the padding leaves no
        trace (the shared layer's rows after ``last`` are overwritten by
        decode before they are attended)."""
        h = self._embed(params, tokens)
        h, cache = self._run(params, h, cache, last=last)
        if last is None:
            h_last = h[:, -1:, :]
        else:
            h_last = jax.lax.dynamic_slice_in_dim(h, last, 1, axis=1)
        return self._logits(params, h_last)[:, 0, :], cache

    def decode_logits(self, params, tok, cache, pos):
        """One decode step: ``tok`` (b, 1) int32 at absolute position
        ``pos`` (traced) -> ((b, vocab) logits, cache)."""
        h = self._embed(params, tok)
        h, cache = self._run(params, h, cache, pos=pos)
        return self._logits(params, h)[:, 0, :], cache


def sambay_lm(vocab: int, **kw) -> SambaYLM:
    return SambaYLM(vocab, **kw)

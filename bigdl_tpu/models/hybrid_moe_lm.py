"""Language models of mixed mixers with a routed feed-forward layer in every
block, one class under two configurations.

Every layer is ``h += Mixer_l(RMSNorm1_l(h)); h += FFN_l(RMSNorm2_l(h))``,
the FFN routed (:class:`bigdl_tpu.nn.RoutedFFN`), no bias but a router's
selection bias, the output head untied.

``model_type: solar_open2`` (Solar-Open2-250B; the mixers are Kimi
Linear's, arXiv:2510.26692, the router ``glm4_moe``'s): layer ``l`` is a
softmax layer iff ``l % (gqa_interval + 1) == 0``: causal GQA without
positions and with an elementwise output gate
(:class:`bigdl_tpu.nn.GatedAttention`); the ``gqa_interval`` layers after
it are Kimi Delta Attention (:class:`bigdl_tpu.nn.KDA`). Sigmoid scores
over ``num_experts``, ``top_k`` a token, one shared expert, SwiGLU. No
positional encoding anywhere.

``model_type: smallthinker`` (SmallThinker-21BA3B-Instruct), chosen by
giving the two layout lists: every mixer is plain causal GQA
(:class:`bigdl_tpu.nn.CausalGQA`); layer ``l`` rotates q and k iff
``rope_layout[l]`` and attends the last ``window`` positions iff
``sliding_window_layout[l]`` (a ring of ``window`` rows a slot), the
whole prefix otherwise. The router reads the layer's input, before
attention (``router_input="layer_input"``); the ``top_k`` largest logits,
softmax over the chosen; ReGLU experts, no shared expert.

The model is built as one chip's share of an expert-parallel deployment:
it holds ``experts_held`` of the ``num_experts`` routed experts of each
layer (share ``share``) and ``vocab`` rows of embedding and head, routes
over all ``num_experts``, and computes the part of each layer that its own
experts give; attention, the shared expert and the router are whole. With
``experts_held = num_experts`` it is the uncut model.

What a serving slot holds is of four kinds (:meth:`HybridMoELM.init_cache`):
``max_len`` K/V rows for each full softmax layer, ``window`` K/V rows for
each window layer, and for each KDA layer a float32 matrix state a head
and the convolution's last rows.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu import nn
from bigdl_tpu.core.module import Module

__all__ = ["HybridMoELM", "hybrid_moe_lm", "solar_open2", "smallthinker"]

# mixer kind -> jax.named_scope of its ops
_SCOPES = {"gqa": "attn_gated", "kda": "kda", "window": "attn_window",
           "global": "attn_global"}
# mixer kind -> kind of the slot's K/V leaves (cache_bytes_by_kind)
_KV_KINDS = {"gqa": "kv_full", "global": "kv_full", "window": "kv_window"}


class HybridMoELM(Module):
    """Mixed mixers (KDA + gated NoPE GQA, or window and full GQA by two
    layout lists), routed experts in every layer. Serves through
    ``DecodeEngine``'s dense path (``init_cache`` / ``prefill_logits`` /
    ``decode_logits_stats``); a slot holds recurrent state or rings and
    the layers hold expert stacks, which paging, prefix sharing,
    speculation, quantization and tp serving cannot carry yet. What the
    engine refuses follows from ``recurrent_state``, ``window`` (the
    rings' rows; None without window layers) and ``routed_experts``."""

    routed_experts = True
    # what a decode step counts on the device, summed over layers; the
    # engine registers them and adds what ``count_step`` returns
    step_counters = {
        "moe_picks_total": "token-expert pairs the live slots' decode "
                           "steps routed (slots x top_k x layers)",
        "moe_held_picks_total": "of those, pairs on experts held here "
                                "(computed by this chip)",
        "moe_experts_touched_total": "held experts with at least one live "
                                     "token, a decode step and layer"}

    def __init__(self, vocab: int, d_model: int = 256, num_layers: int = 4,
                 num_heads: int = 4, num_kv_heads: int = 2,
                 head_dim: int = 64, kda_heads: Optional[int] = None,
                 kda_head_dim: Optional[int] = None, conv_kernel: int = 4,
                 gate_rank: Optional[int] = None, gqa_interval: int = 3,
                 num_experts: int = 16, experts_held: Optional[int] = None,
                 share: int = 0, top_k: int = 4, expert_width: int = 128,
                 shared_experts: int = 1, routed_scale: float = 1.0,
                 rms_eps: float = 1e-5, max_len: int = 4096,
                 sliding_window_layout: Optional[Sequence[int]] = None,
                 rope_layout: Optional[Sequence[int]] = None,
                 window: Optional[int] = None, rope_theta: float = 10000.0,
                 router_score: str = "sigmoid", expert_act: str = "silu",
                 router_input: str = "ffn_norm",
                 attn_impl: Optional[str] = None, remat: bool = False,
                 compute_dtype=None, init_std: float = 0.02,
                 name: Optional[str] = None):
        super().__init__(name or "HybridMoELM")
        self.vocab, self.d_model, self.max_len = vocab, d_model, max_len
        self.compute_dtype = compute_dtype
        self.remat = bool(remat)
        self.init_std = init_std
        self.head_dim = head_dim
        self.top_k = top_k
        self.num_experts = num_experts
        self.experts_held = experts_held or num_experts
        self.share = share
        if router_input not in ("ffn_norm", "layer_input"):
            raise ValueError(f"router_input {router_input!r}: ffn_norm or "
                             "layer_input")
        self.router_input = router_input
        layouts = (sliding_window_layout, rope_layout)
        if any(lay is not None and len(lay) != num_layers
               for lay in layouts) or (layouts[0] is None) != (
                   layouts[1] is None):
            raise ValueError("sliding_window_layout and rope_layout: both "
                             f"or neither, one entry a layer ({num_layers})")
        if layouts[0] is not None and any(layouts[0]) and not window:
            raise ValueError("sliding_window_layout names window layers: "
                             "give window")
        self.kinds, self.mixers, self.ffns, self.norms = [], [], [], []
        for l in range(num_layers):
            if layouts[0] is not None:
                kind = "window" if layouts[0][l] else "global"
            else:
                kind = "kda" if l % (gqa_interval + 1) else "gqa"
            self.kinds.append(kind)
            if kind in ("window", "global"):
                mixer = nn.CausalGQA(
                    d_model, num_heads, num_kv_heads, head_dim,
                    attn_impl=attn_impl, init_std=init_std,
                    rope_theta=rope_theta if layouts[1][l] else None,
                    window=window if kind == "window" else None)
            elif kind == "gqa":
                mixer = nn.GatedAttention(d_model, num_heads, num_kv_heads,
                                          head_dim, attn_impl=attn_impl,
                                          init_std=init_std)
            else:
                mixer = nn.KDA(d_model, kda_heads or num_heads,
                               kda_head_dim or head_dim, conv=conv_kernel,
                               gate_rank=gate_rank, eps=rms_eps,
                               init_std=init_std)
            self.mixers.append(mixer)
            self.ffns.append(nn.RoutedFFN(
                d_model, expert_width, num_experts, top_k,
                held=experts_held, share=share,
                shared_width=shared_experts * expert_width,
                scale=routed_scale, init_std=init_std, score=router_score,
                act=expert_act))
            self.norms.append((nn.RMSNorm(d_model, rms_eps),
                               nn.RMSNorm(d_model, rms_eps)))
        self.ln_f = nn.RMSNorm(d_model, rms_eps)
        # what the engine's guard and counters read
        self.recurrent_state = "kda" in self.kinds
        self.window = window if "window" in self.kinds else None

    def children(self):
        return (*self.mixers, *self.ffns,
                *(n for pair in self.norms for n in pair), self.ln_f)

    def init(self, rng):
        layers = {}
        for l, (mixer, ffn) in enumerate(zip(self.mixers, self.ffns)):
            ks = jax.random.split(jax.random.fold_in(rng, l), 2)
            layers[str(l)] = {"ln1": self.norms[l][0].init(None),
                              "mixer": mixer.init(ks[0]),
                              "ln2": self.norms[l][1].init(None),
                              "ffn": ffn.init(ks[1])}
        ks = jax.random.split(jax.random.fold_in(rng, len(layers)), 2)
        shape = (self.vocab, self.d_model)
        return {"emb": {"weight": self.init_std * jax.random.normal(
                    ks[0], shape)},
                "layers": layers, "ln_f": self.ln_f.init(None),
                "head": {"weight": self.init_std * jax.random.normal(
                    ks[1], shape)}}

    # -------------------------------------------------------------- cache
    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32):
        """What ``batch`` slots hold, keyed by layer: ``{"k", "v"}`` of
        ``max_len`` rows (full softmax layers) or of ``min(window,
        max_len)`` (window layers), ``{"s", "conv"}`` (KDA)."""
        return {str(l): (mixer.init_cache(batch, dtype) if kind == "kda"
                         else mixer.init_cache(batch, max_len, dtype))
                for l, (kind, mixer) in enumerate(zip(self.kinds,
                                                      self.mixers))}

    def cache_bytes_by_kind(self, cache) -> dict:
        """Resident bytes of a cache pytree by kind of leaf, the kinds
        this model's layers have of ``kv_full``, ``kv_window``,
        ``kda_state``, ``conv_state``."""
        from bigdl_tpu.obs.memory import tree_bytes
        kinds = [k for k in ("kv_full", "kv_window") if k in {
            _KV_KINDS.get(kind) for kind in self.kinds}]
        if self.recurrent_state:
            kinds += ["kda_state", "conv_state"]
        out = dict.fromkeys(kinds, 0)
        for l, entry in cache.items():
            kind = self.kinds[int(l)]
            if kind == "kda":
                out["kda_state"] += tree_bytes(entry["s"])
                out["conv_state"] += tree_bytes(entry["conv"])
            else:
                out[_KV_KINDS[kind]] += tree_bytes(entry)
        return out

    def prompt_buckets(self, max_len: int, dtype) -> tuple:
        """The prefill's prompt-length ladder: the flash kernel's plans at
        the softmax layers' head width."""
        from bigdl_tpu.ops.attention_kernel import serving_prefill_buckets
        return serving_prefill_buckets(max_len, self.head_dim, True, dtype)

    # ------------------------------------------------------------ forward
    def _embed(self, params, tokens):
        h = jnp.take(params["emb"]["weight"], tokens, axis=0)
        return h if self.compute_dtype is None else h.astype(
            self.compute_dtype)

    def _logits(self, params, h):
        h = self.ln_f.forward(params["ln_f"], h)
        return h @ params["head"]["weight"].astype(h.dtype).T

    def _layer(self, l, p, x, cache, last, pos):
        """Layer ``l`` on x (b, s, d): the prompt's rows up to ``last``
        when ``pos`` is None (prefill; s = the bucket), else one token at
        position ``pos``. Returns (x, this layer's cache, picked)."""
        kind, mixer = self.kinds[l], self.mixers[l]
        # a router placed before attention reads the layer's raw input
        early = x if self.router_input == "layer_input" else None
        h = self.norms[l][0].forward(p["ln1"], x)
        with jax.named_scope(_SCOPES[kind]):
            if pos is None:  # a state, or a ring, stops at ``last``
                h, cache = mixer.prefill(p["mixer"], h, cache, last)
            elif kind == "kda":
                h, cache = mixer.decode_step(p["mixer"], h, cache)
            else:
                h, cache = mixer.decode_step(p["mixer"], h, cache, pos)
        x = x + h
        h, picked = self.ffns[l].forward(
            p["ffn"], self.norms[l][1].forward(p["ln2"], x), early)
        return x + h, cache, picked

    def _run(self, params, h, cache, last=None, pos=None):
        new, picked = {}, []
        for l in range(len(self.kinds)):
            fn = lambda p, x, c, l=l: self._layer(l, p, x, c, last, pos)
            if self.remat and pos is None:
                fn = jax.checkpoint(fn)
            k = str(l)
            h, new[k], pk = fn(params["layers"][k], h, cache[k])
            picked.append(pk)
        return h, new, jnp.stack(picked, axis=-2)

    def logits(self, params, tokens):
        """(b, s) token ids -> (b, s, vocab) float32 logits: the whole
        forward with no cache to keep."""
        b, s = tokens.shape
        h = self._embed(params, tokens)
        h, _, _ = self._run(params, h, self.init_cache(b, s, h.dtype))
        return self._logits(params, h).astype(jnp.float32)

    def apply(self, params, state, x, *, training=False, rng=None):
        return jax.nn.log_softmax(self.logits(params, x), axis=-1), state

    # --------------------------------------------- autoregressive decoding
    def prefill_logits(self, params, tokens, cache, last=None):
        """Serving prefill: the prompt (b, s), right-padded to its bucket,
        once through every layer -> the next-token logits (b, vocab) at
        position ``last`` (traced; default s - 1) and the slot's cache as
        it stands after token ``last``: the KDA state and the
        convolution's history stop there, and so does what a window
        layer's ring takes (the full softmax layers' rows after ``last``
        are overwritten by decode before they are attended)."""
        h = self._embed(params, tokens)
        h, cache, _ = self._run(params, h, cache, last=last)
        if last is None:
            h_last = h[:, -1:, :]
        else:
            h_last = jax.lax.dynamic_slice_in_dim(h, last, 1, axis=1)
        return self._logits(params, h_last)[:, 0, :], cache

    def decode_logits_stats(self, params, tok, cache, pos):
        """One decode step: ``tok`` (b, 1) int32 at absolute position
        ``pos`` (traced) -> ((b, vocab) logits, cache, picked): ``picked``
        (b, layers, words) uint32, the held experts each token chose in
        each layer as a bit set (:class:`bigdl_tpu.nn.RoutedFFN`)."""
        h = self._embed(params, tok)
        h, cache, picked = self._run(params, h, cache, pos=pos)
        return self._logits(params, h)[:, 0, :], cache, picked[:, 0]

    def decode_logits(self, params, tok, cache, pos):
        return self.decode_logits_stats(params, tok, cache, pos)[:2]

    def count_step(self, picked) -> dict:
        """What one decode step adds to ``step_counters``: ``picked``
        (live slots, layers, words), the step's bit sets read back to the
        host, rows of the slots the step advanced."""
        picked = np.asarray(picked, np.uint32)
        touched = np.bitwise_or.reduce(picked, axis=0)
        return {"moe_picks_total": picked.shape[0] * picked.shape[1]
                * self.top_k,
                "moe_held_picks_total": int(np.bitwise_count(picked).sum()),
                "moe_experts_touched_total": int(
                    np.bitwise_count(touched).sum())}


def hybrid_moe_lm(vocab: int, **kw) -> HybridMoELM:
    return HybridMoELM(vocab, **kw)


def solar_open2(vocab: int = 24576, num_layers: int = 4,
                experts_held: int = 40, share: int = 0,
                **kw) -> HybridMoELM:
    """Solar-Open2-250B at its published widths, as one chip's share of an
    eight-way expert-parallel deployment: ``experts_held`` of the 320
    routed experts a layer, ``vocab`` of the 196,608 rows, ``num_layers``
    of the 48 layers (whole periods of one softmax and three KDA
    layers)."""
    return HybridMoELM(vocab, d_model=4096, num_layers=num_layers,
                       num_heads=64, num_kv_heads=8, head_dim=128,
                       kda_heads=64, kda_head_dim=128, conv_kernel=4,
                       gate_rank=128, gqa_interval=3, num_experts=320,
                       experts_held=experts_held, share=share, top_k=8,
                       expert_width=1280, shared_experts=1, **kw)


def smallthinker(vocab: int = 151936, num_layers: int = 8,
                 max_len: int = 16384, **kw) -> HybridMoELM:
    """SmallThinker-21BA3B-Instruct at its published widths, every expert
    and the whole vocabulary held: ``num_layers`` of the 52 layers, whole
    periods of one NoPE full-attention layer and three RoPE layers with a
    4,096-position window."""
    period = [0, 1, 1, 1]
    layout = (period * -(-num_layers // 4))[:num_layers]
    return HybridMoELM(vocab, d_model=2560, num_layers=num_layers,
                       num_heads=28, num_kv_heads=4, head_dim=128,
                       sliding_window_layout=layout, rope_layout=layout,
                       window=4096, rope_theta=1.5e6, num_experts=64,
                       experts_held=64, share=0, top_k=6, expert_width=768,
                       shared_experts=0, router_score="softmax_topk",
                       expert_act="relu", router_input="layer_input",
                       rms_eps=1e-6, max_len=max_len, **kw)

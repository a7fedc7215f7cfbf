"""Language models of mixed mixers with a routed feed-forward layer in every
block, one class under four configurations.

Every layer is ``h += Mixer_l(RMSNorm1_l(h)); h += FFN_l(RMSNorm2_l(h))``,
the FFN routed (:class:`bigdl_tpu.nn.RoutedFFN`), no bias but a router's
selection bias, the output head untied, unless three per-layer keys say
otherwise: ``block="parallel"`` (one norm a layer, ``h += Mixer_l(n) +
FFN_l(n)`` with ``n = Norm_l(h)``), ``norm="layer"`` (every norm a
mean-subtracted LayerNorm with a weight and no bias) and
``tie_embeddings`` (the head is the embedding's rows).

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

``model_type: cohere2_moe`` (Command A+), chosen by the two layout lists
with the three keys above: three window layers whose q and k turn in
GPT-J's interleaved pairs (``rope_pairing="interleaved"``), then a global
layer without positions; 128 query heads over 8 KV heads; the router
``sigmoid`` without a selection bias (``selection_bias=False``) and four
shared experts averaged (``shared_combine="mean"``): ``h += Attn(n) +
sum_chosen w_e GLU_e(n) + 1/4 sum_j GLU^s_j(n)``.

``model_type: glm4_moe_lite`` (GLM-4.7-Flash), chosen by ``mixer="mla"``:
every mixer is multi-head latent attention
(:class:`bigdl_tpu.nn.LatentAttention`), the first ``first_k_dense``
layers have a dense SwiGLU FFN of ``dense_width``
(:class:`bigdl_tpu.nn.DenseGLU`) and the rest the sigmoid router's FFN
with one shared expert and ``routed_scale``. With
``mtp_layers=1`` a multi-token prediction module follows (DeepSeek-V3's,
arXiv:2412.19437 section 2.2): ``h' = Block(W_eh [RMSNorm_e(Emb(t_{i+1}))
; RMSNorm_h(h_i)])``, ``h_i`` the last layer's output, ``Block`` a whole
MLA + routed layer, and ``Head(RMSNorm(h'))`` predicts ``t_{i+2}`` with
the main model's embedding and head. It trains (``apply(...,
training=True)`` and :meth:`HybridMoELM.criterion`) and does not serve:
no latent cache is built.

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

__all__ = ["HybridMoELM", "hybrid_moe_lm", "solar_open2", "smallthinker",
           "glm4_moe_lite", "command_a_plus"]

# mixer kind -> jax.named_scope of its ops
_SCOPES = {"gqa": "attn_gated", "kda": "kda", "window": "attn_window",
           "global": "attn_global", "mla": "mla"}
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
    rings' rows; None without window layers), ``routed_experts`` and
    ``latent_cache`` (MLA layers: it trains and does not serve)."""

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
    # what a training step counts on the device, summed over its routed
    # layers (the MTP module's included); ``apply(..., training=True)``
    # returns them in the state under "train_counts", and the Optimizer
    # fetches them with the loss
    train_counters = {
        "moe_train_held_picks_total": "token-expert pairs on experts held "
                                      "here: the rows of the grouped matmuls",
        "moe_train_busiest_rows_total": "the busiest held expert's rows, "
                                        "summed over routed layers"}

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
                 router_input: str = "ffn_norm", mixer: Optional[str] = None,
                 q_lora_rank: Optional[int] = None,
                 kv_lora_rank: Optional[int] = None,
                 qk_nope_dim: Optional[int] = None,
                 qk_rope_dim: Optional[int] = None,
                 v_head_dim: Optional[int] = None, first_k_dense: int = 0,
                 dense_width: Optional[int] = None, mtp_layers: int = 0,
                 mtp_weight: float = 0.3, block: str = "sequential",
                 norm: str = "rms", tie_embeddings: bool = False,
                 rope_pairing: str = "half", selection_bias: bool = True,
                 shared_combine: str = "sum",
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
        if mixer not in (None, "mla") or mtp_layers not in (0, 1) or (
                mtp_layers and mixer != "mla"):
            raise ValueError(f"mixer {mixer!r}, mtp_layers {mtp_layers}: "
                             "mixer None or 'mla'; one MTP module, after "
                             "MLA layers")
        if (block not in ("sequential", "parallel")
                or norm not in ("rms", "layer")
                or shared_combine not in ("sum", "mean")):
            raise ValueError(f"block {block!r}, norm {norm!r}, "
                             f"shared_combine {shared_combine!r}: sequential "
                             "or parallel, rms or layer, sum or mean")
        self.num_layers, self.first_k_dense = num_layers, first_k_dense
        self.mtp_layers, self.mtp_weight = mtp_layers, mtp_weight
        self.parallel = block == "parallel"
        self.tie_embeddings = tie_embeddings
        # every norm of the model: rms_eps is its epsilon whatever its kind
        make_norm = (lambda: nn.RMSNorm(d_model, rms_eps)) if norm == "rms" \
            else (lambda: nn.LayerNorm(d_model, rms_eps, bias=False))
        self.kinds, self.mixers, self.ffns, self.norms = [], [], [], []
        # the MTP module's block is built as layer ``num_layers``
        for l in range(num_layers + mtp_layers):
            if mixer == "mla":
                kind = "mla"
            elif layouts[0] is not None:
                kind = "window" if layouts[0][l] else "global"
            else:
                kind = "kda" if l % (gqa_interval + 1) else "gqa"
            self.kinds.append(kind)
            if kind == "mla":
                mixer_l = nn.LatentAttention(
                    d_model, num_heads, q_lora_rank, kv_lora_rank,
                    qk_nope_dim, qk_rope_dim, v_head_dim,
                    rope_theta=rope_theta, eps=rms_eps, attn_impl=attn_impl,
                    init_std=init_std)
            elif kind in ("window", "global"):
                mixer_l = nn.CausalGQA(
                    d_model, num_heads, num_kv_heads, head_dim,
                    attn_impl=attn_impl, init_std=init_std,
                    rope_theta=rope_theta if layouts[1][l] else None,
                    window=window if kind == "window" else None,
                    rope_pairing=rope_pairing)
            elif kind == "gqa":
                mixer_l = nn.GatedAttention(
                    d_model, num_heads, num_kv_heads, head_dim,
                    attn_impl=attn_impl, init_std=init_std)
            else:
                mixer_l = nn.KDA(d_model, kda_heads or num_heads,
                                 kda_head_dim or head_dim, conv=conv_kernel,
                                 gate_rank=gate_rank, eps=rms_eps,
                                 init_std=init_std)
            self.mixers.append(mixer_l)
            self.ffns.append(nn.DenseGLU(
                d_model, dense_width, init_std=init_std, act=expert_act)
                if l < first_k_dense else nn.RoutedFFN(
                d_model, expert_width, num_experts, top_k,
                held=experts_held, share=share,
                shared_width=shared_experts * expert_width,
                scale=routed_scale, init_std=init_std, score=router_score,
                act=expert_act, selection_bias=selection_bias,
                shared_scale=1.0 / shared_experts
                if shared_combine == "mean" and shared_experts else 1.0))
            self.norms.append(tuple(
                make_norm() for _ in range(1 if self.parallel else 2)))
        self.ln_f = make_norm()
        # the MTP module's own norms: of the embedding, of the hidden
        # state, and before the shared head
        self.mtp_norms = tuple(make_norm() for _ in range(3 * mtp_layers))
        # what the engine's guard and counters read
        self.recurrent_state = "kda" in self.kinds
        self.window = window if "window" in self.kinds else None
        # a slot of an MLA model would hold the latent cache, not built
        self.latent_cache = "mla" in self.kinds

    def children(self):
        return (*self.mixers, *self.ffns,
                *(n for pair in self.norms for n in pair), self.ln_f,
                *self.mtp_norms)

    def _layer_params(self, l, rng):
        ks = jax.random.split(jax.random.fold_in(rng, l), 2)
        out = {"ln1": self.norms[l][0].init(None),
               "mixer": self.mixers[l].init(ks[0])}
        if not self.parallel:
            out["ln2"] = self.norms[l][1].init(None)
        out["ffn"] = self.ffns[l].init(ks[1])
        return out

    def init(self, rng):
        layers = {str(l): self._layer_params(l, rng)
                  for l in range(self.num_layers)}
        ks = jax.random.split(jax.random.fold_in(rng, len(layers)), 2)
        shape = (self.vocab, self.d_model)
        out = {"emb": {"weight": self.init_std * jax.random.normal(
                   ks[0], shape)},
               "layers": layers, "ln_f": self.ln_f.init(None)}
        if not self.tie_embeddings:
            out["head"] = {"weight": self.init_std * jax.random.normal(
                ks[1], shape)}
        if self.mtp_layers:
            k = jax.random.fold_in(rng, self.num_layers + 1)
            d = self.d_model
            out["mtp"] = {
                "ln_e": self.mtp_norms[0].init(None),
                "ln_h": self.mtp_norms[1].init(None),
                "w_eh": self.init_std * jax.random.normal(k, (2 * d, d)),
                "layer": self._layer_params(self.num_layers, rng),
                "ln_f": self.mtp_norms[2].init(None)}
        return out

    def init_state(self):
        return {"train_counts": {name: jnp.zeros((), jnp.int32)
                                 for name in self.train_counters}}

    # -------------------------------------------------------------- cache
    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32):
        """What ``batch`` slots hold, keyed by layer: ``{"k", "v"}`` of
        ``max_len`` rows (full softmax layers) or of ``min(window,
        max_len)`` (window layers), ``{"s", "conv"}`` (KDA)."""
        return {str(l): (mixer.init_cache(batch, dtype) if kind == "kda"
                         else mixer.init_cache(batch, max_len, dtype))
                for l, (kind, mixer) in enumerate(zip(
                    self.kinds[:self.num_layers], self.mixers))}

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

    def _head(self, params):
        """The output head's (vocab, d) rows: the embedding's when tied."""
        return params["emb" if self.tie_embeddings else "head"]["weight"]

    def _logits(self, params, h):
        h = self.ln_f.forward(params["ln_f"], h)
        return h @ self._head(params).astype(h.dtype).T

    def _layer(self, l, p, x, cache, last, pos):
        """Layer ``l`` on x (b, s, d): the whole sequence with no cache
        when ``cache`` is None; the prompt's rows up to ``last`` when
        ``pos`` is None (prefill; s = the bucket), else one token at
        position ``pos``. Returns (x, this layer's cache, picked; None for
        a dense layer)."""
        kind, mixer = self.kinds[l], self.mixers[l]
        # a router placed before attention reads the layer's raw input
        early = x if self.router_input == "layer_input" else None
        n = self.norms[l][0].forward(p["ln1"], x)
        with jax.named_scope(_SCOPES[kind]):
            if cache is None:
                h = mixer.forward(p["mixer"], n)
            elif pos is None:  # a state, or a ring, stops at ``last``
                h, cache = mixer.prefill(p["mixer"], n, cache, last)
            elif kind == "kda":
                h, cache = mixer.decode_step(p["mixer"], n, cache)
            else:
                h, cache = mixer.decode_step(p["mixer"], n, cache, pos)
        x = x + h
        if not self.parallel:  # a parallel block's FFN reads attention's n
            n = self.norms[l][1].forward(p["ln2"], x)
        ffn = self.ffns[l]
        if l < self.first_k_dense:
            with jax.named_scope("dense_ffn"):
                return x + ffn.forward(p["ffn"], n), cache, None
        h, picked = ffn.forward(p["ffn"], n, early)
        return x + h, cache, picked

    def _run(self, params, h, cache, last=None, pos=None):
        """Every layer of the main model; ``cache`` None: no cache, the
        whole sequence. Returns (h, the new cache, the routed layers'
        bit sets stacked on axis -2)."""
        new, picked = {}, []
        for l in range(self.num_layers):
            fn = lambda p, x, c, l=l: self._layer(l, p, x, c, last, pos)
            if self.remat and pos is None:
                fn = jax.checkpoint(fn)
            k = str(l)
            h, new[k], pk = fn(params["layers"][k], h,
                               None if cache is None else cache[k])
            if pk is not None:
                picked.append(pk)
        return h, new, jnp.stack(picked, axis=-2)

    def logits(self, params, tokens):
        """(b, s) token ids -> (b, s, vocab) float32 logits: the whole
        forward with no cache."""
        h = self._embed(params, tokens)
        h, _, _ = self._run(params, h, None)
        return self._logits(params, h).astype(jnp.float32)

    def _mtp_logits(self, params, h, tokens):
        """The MTP module on the main model's last hidden rows ``h`` (b, s,
        d) of ``tokens`` (b, s): (b, s, vocab) logits, row ``i`` for
        the token after next, read from ``h_i`` and the embedding of
        ``tokens[:, i + 1]`` (the last row reads ``tokens[:, 0]``: causal,
        it moves no other row, and it is no row's target), and the block's
        bit sets (b, s, words)."""
        p, (ln_e, ln_h, ln_f) = params["mtp"], self.mtp_norms
        e = ln_e.forward(p["ln_e"], self._embed(
            params, jnp.roll(tokens, -1, axis=1)))
        x = jnp.concatenate([e, ln_h.forward(p["ln_h"], h)], axis=-1)
        x = x @ p["w_eh"].astype(x.dtype)
        fn = lambda q, x: self._layer(self.num_layers, q, x, None, None,
                                      None)
        if self.remat:
            fn = jax.checkpoint(fn)
        x, _, picked = fn(p["layer"], x)
        x = ln_f.forward(p["ln_f"], x)
        return x @ self._head(params).astype(x.dtype).T, picked

    def train_logits(self, params, tokens):
        """The training forward of ``tokens`` (b, s): (next-token logits
        (b, s, vocab), the MTP module's (b, s, vocab) or None, the routed
        layers' bit sets (b, s, routed layers, words), the MTP block's
        last)."""
        h = self._embed(params, tokens)
        h, _, picked = self._run(params, h, None)
        if not self.mtp_layers:
            return self._logits(params, h), None, picked
        with jax.named_scope("mtp"):
            z, pk = self._mtp_logits(params, h, tokens)
        return (self._logits(params, h), z,
                jnp.concatenate([picked, pk[..., None, :]], axis=-2))

    def apply(self, params, state, x, *, training=False, rng=None):
        """``training=False``: (b, s, vocab) log-probs. ``training=True``:
        the same, or with the MTP module ``{"main": ..., "mtp": ...}``
        (:meth:`criterion` takes it), and the state's ``train_counts`` of
        this step."""
        if not training:
            return jax.nn.log_softmax(self.logits(params, x), axis=-1), state
        logp = lambda z: jax.nn.log_softmax(z.astype(jnp.float32), axis=-1)
        main, mtp, picked = self.train_logits(params, x)
        out = logp(main) if mtp is None else {"main": logp(main),
                                              "mtp": logp(mtp)}
        return out, {"train_counts": self._train_counts(picked)}

    def criterion(self):
        """The training loss of :meth:`apply`'s output: next-token NLL,
        plus ``mtp_weight`` times the MTP module's."""
        if self.mtp_layers:
            return nn.MultiTokenNLLCriterion(self.mtp_weight)
        return nn.TimeDistributedCriterion(nn.ClassNLLCriterion())

    def _train_counts(self, picked):
        """``train_counters`` of one training step, from the routed
        layers' bit sets ``picked`` (b, s, routed layers, words)."""
        e = jnp.arange(self.experts_held)
        bits = (picked[..., e // 32] >> (e % 32).astype(jnp.uint32)) & 1
        rows = jnp.sum(bits, axis=(0, 1), dtype=jnp.int32)  # (layers, held)
        return {"moe_train_held_picks_total": jnp.sum(rows),
                "moe_train_busiest_rows_total": jnp.sum(jnp.max(rows, -1))}

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


def glm4_moe_lite(vocab: int = 19360, num_layers: int = 5,
                  experts_held: int = 8, share: int = 0,
                  **kw) -> HybridMoELM:
    """GLM-4.7-Flash at its published widths, as one chip's share of an
    eight-way expert-parallel training job: MLA at 20 heads (q rank 768,
    kv rank 512, 192 + 64 wide keys, 256 wide values), the dense layer 0
    (SwiGLU 10,240), then routed layers of ``experts_held`` of the 64
    experts (top-4 by sigmoid scores with a selection bias, weights times
    1.8, one shared expert, all 1,536 wide), ``vocab`` of the 154,880
    rows, ``num_layers`` of the 47 layers and the MTP module."""
    return HybridMoELM(vocab, d_model=2048, num_layers=num_layers,
                       num_heads=20, mixer="mla", q_lora_rank=768,
                       kv_lora_rank=512, qk_nope_dim=192, qk_rope_dim=64,
                       v_head_dim=256, first_k_dense=1, dense_width=10240,
                       num_experts=64, experts_held=experts_held,
                       share=share, top_k=4, expert_width=1536,
                       shared_experts=1, routed_scale=1.8, rope_theta=1e6,
                       rms_eps=1e-5, mtp_layers=1, **kw)


def command_a_plus(vocab: int = 32768, num_layers: int = 4,
                   experts_held: int = 16, share: int = 0,
                   max_len: int = 9216, **kw) -> HybridMoELM:
    """Command A+ (``cohere2_moe``) at its published widths, as one chip's
    share of an eight-way expert-parallel deployment: parallel blocks
    under one bias-free LayerNorm; 128 query heads over 8 KV heads of
    128, three window layers of 4,096 positions rotated in GPT-J pairs
    (theta 50,000) before each global layer without positions;
    ``experts_held`` of the 128 routed experts a layer (top-8 by sigmoid
    scores, no selection bias, all 4,096 wide) beside four shared experts
    averaged; ``vocab`` of the 262,144 rows, tied; ``num_layers`` of the
    32 layers."""
    period = [1, 1, 1, 0]  # local_attn_first
    layout = (period * -(-num_layers // 4))[:num_layers]
    return HybridMoELM(vocab, d_model=4096, num_layers=num_layers,
                       num_heads=128, num_kv_heads=8, head_dim=128,
                       sliding_window_layout=layout, rope_layout=layout,
                       window=4096, rope_theta=5e4,
                       rope_pairing="interleaved", num_experts=128,
                       experts_held=experts_held, share=share, top_k=8,
                       expert_width=4096, shared_experts=4,
                       shared_combine="mean", selection_bias=False,
                       block="parallel", norm="layer", tie_embeddings=True,
                       rms_eps=1e-5, max_len=max_len, **kw)

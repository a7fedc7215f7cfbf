"""Decoder-only transformer language model — the long-context flagship.

The reference's only sequence model is SimpleRNN (truncated BPTT,
nn/Recurrent.scala); this is the modern long-context workload the brief
treats as first-class, built from the framework's own pieces: LookupTable
embedding, sinusoidal positions, causal pre-LN TransformerEncoder (flash
or ring attention via ``attn_impl``), weight-tied logits head option, and
``remat`` for HBM-bound contexts.

Scales along every axis the framework ships: dp (batch), tp (Megatron
specs apply to the blocks), sp (ring attention over `seq`), pp
(`PipelineStack` of the same TransformerEncoderLayer blocks). Its MLPs
are dense; the LM with a routed FFN in every layer is
:class:`bigdl_tpu.models.HybridMoELM`.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu import nn
from bigdl_tpu.core.module import Module

__all__ = ["TransformerLM", "transformer_lm", "packed_lm_targets",
           "PackedNLLCriterion"]


def packed_lm_targets(tokens, segments):
    """Next-token targets for a packed row (see
    ``bigdl_tpu.dataset.text.pack_sequences``): target[i] = tokens[i+1],
    with weight 0 wherever the next token belongs to a different document
    (or padding) — the boundary positions a packed causal LM must not be
    trained on. Returns (targets, weights), shapes (b, s) / (b, s) f32."""
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
    nxt = jnp.concatenate(
        [segments[:, 1:], jnp.zeros_like(segments[:, :1])], axis=1)
    weights = ((segments == nxt) & (segments != 0)).astype(jnp.float32)
    return targets, weights


class PackedNLLCriterion:
    """Weighted next-token NLL over (b, s, vocab) log-probs; target is the
    (targets, weights) pair from :func:`packed_lm_targets`. Mean over the
    live positions, so the loss scale matches the unpacked
    TimeDistributed(ClassNLL) path."""

    def __call__(self, logp, target):
        targets, weights = target
        nll = -jnp.take_along_axis(logp, targets[..., None],
                                   axis=-1)[..., 0]
        w = weights.astype(nll.dtype)
        return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


class TransformerLM(Module):
    """Decoder-only LM (the long-context flagship).

    TPU sizing rule, measured on chip (PERF.md §8.2): pick
    ``num_heads`` so that ``d_model // num_heads == 128`` — the MXU
    contracts over the head dim in both attention matmuls and 64-wide
    heads half-fill its 128-lane tiles (+24% tok/s at identical FLOPs
    under the shipped 512-wide flash blocks; +60% under 128-blocks).
    The 1k-context hd128 config measures 96.0k tok/s at 53.7% MFU on
    one v5e chip."""

    def __init__(self, vocab: int, d_model: int = 256, num_layers: int = 4,
                 num_heads: int = 4, d_ff: Optional[int] = None,
                 max_len: int = 2048, dropout: float = 0.0,
                 attn_impl=None, remat: bool = False,
                 tie_embeddings: bool = True, compute_dtype=None,
                 num_kv_heads: Optional[int] = None,
                 pos_encoding: str = "sinusoidal",
                 name: Optional[str] = None):
        super().__init__(name or "TransformerLM")
        if pos_encoding not in ("sinusoidal", "rope"):
            raise ValueError(f"pos_encoding {pos_encoding!r} not in "
                             f"('sinusoidal', 'rope')")
        self.vocab = vocab
        self.d_model = d_model
        self.tie = tie_embeddings
        self.max_len = max_len
        self.rope = pos_encoding == "rope"
        # token input is int, so the Optimizer-level compute_dtype cast
        # never fires for LMs; the cast belongs right after the embedding
        self.compute_dtype = compute_dtype
        self.emb = nn.LookupTable(vocab, d_model)
        # RoPE replaces the additive table (rotation happens on q/k inside
        # every attention layer — relative positions, better long-context
        # extrapolation); self.pos still carries max_len for bounds
        self.pos = nn.PositionalEncoding(d_model, max_len)
        self.encoder = nn.TransformerEncoder(
            num_layers, d_model, num_heads, d_ff, causal=True,
            dropout=dropout, attn_impl=attn_impl, remat=remat,
            num_kv_heads=num_kv_heads, rope=self.rope,
            rope_max_len=max_len)
        self.ln_f = nn.LayerNorm(d_model)
        self.head = None if tie_embeddings else nn.Linear(d_model, vocab)

    def children(self):
        out = [self.emb, self.pos, self.encoder, self.ln_f]
        if self.head is not None:
            out.append(self.head)
        return tuple(out)

    def tp_param_children(self):
        """Param-key -> child mapping so megatron_specs can shard the
        encoder blocks (and embedding) of a TP'd LM."""
        out = {"emb": self.emb, "encoder": self.encoder, "ln_f": self.ln_f}
        if self.head is not None:
            out["head"] = self.head
        return out

    def init(self, rng):
        ks = jax.random.split(rng, 3)
        p = {"emb": self.emb.init(ks[0]),
             "encoder": self.encoder.init(ks[1]),
             "ln_f": self.ln_f.init(ks[2])}
        if self.head is not None:
            p["head"] = self.head.init(jax.random.fold_in(rng, 3))
        return p

    def apply(self, params, state, x, *, training=False, rng=None):
        # x: (batch, seq) int token ids -> (batch, seq, vocab) log-probs;
        # or (tokens, segments) for packed rows (pack_sequences) — the
        # integer segment ids thread to every attention layer, which
        # confines attention per document (in-kernel for the flash impl,
        # via make_segment_mask elsewhere)
        mask = None
        if isinstance(x, (tuple, list)):
            x, segments = x
            mask = segments
        h = self.emb.forward(params["emb"], x)
        if self.compute_dtype is not None:
            h = h.astype(self.compute_dtype)
        h = h * (self.d_model ** 0.5)  # standard embedding scale
        if not self.rope:
            h = self.pos.forward({}, h)
        elif x.shape[-1] > self.max_len:
            raise ValueError(f"sequence length {x.shape[-1]} exceeds "
                             f"max_len {self.max_len}")
        h, _ = self.encoder.apply(params["encoder"],
                                  self.encoder.init_state(),
                                  h if mask is None else (h, mask),
                                  training=training, rng=rng)
        if isinstance(h, (tuple, list)):  # encoder returns (y, mask)
            h = h[0]
        h = self.ln_f.forward(params["ln_f"], h)
        if self.head is not None:
            logits = self.head.forward(params["head"], h)
        else:  # weight tying: logits = h @ E^T
            logits = h @ params["emb"]["weight"].astype(h.dtype).T
        return jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1), state


    # --------------------------------------------- autoregressive decoding
    def _embed_at(self, params, tokens, pos0):
        """Embed (b, s) tokens that sit at absolute positions pos0..pos0+s."""
        h = self.emb.forward(params["emb"], tokens)
        if self.compute_dtype is not None:
            h = h.astype(self.compute_dtype)
        h = h * (self.d_model ** 0.5)
        if self.rope:  # rotation happens inside each attention layer
            return h
        table = jnp.asarray(self.pos._table)
        pe = jax.lax.dynamic_slice_in_dim(table, pos0, tokens.shape[1], 0)
        return h + pe.astype(h.dtype)

    def _logits(self, params, h):
        h = self.ln_f.forward(params["ln_f"], h)
        if self.head is not None:
            return self.head.forward(params["head"], h)
        return h @ params["emb"]["weight"].astype(h.dtype).T

    def prefill_logits(self, params, tokens, cache, last=None):
        """Serving prefill: run the full prompt once, populate the K/V
        ``cache`` (positions 0..s-1), and return the next-token logits —
        ``(b, vocab)`` at position ``last`` (traced index; default the
        final position s-1) — plus the updated cache. With the prompt
        right-padded to a length bucket, ``last`` = true_len - 1 makes
        the result exactly the unpadded prompt's logits: causal
        attention never lets positions > last influence position last,
        and decode steps overwrite the pad K/V slots one position at a
        time before ever attending to them."""
        import jax

        h = self._embed_at(params, tokens, 0)
        h, cache = self.encoder.prefill(params["encoder"], h, cache)
        if last is None:
            h_last = h[:, -1:, :]
        else:
            h_last = jax.lax.dynamic_slice_in_dim(h, last, 1, axis=1)
        return self._logits(params, h_last)[:, 0, :], cache

    def decode_logits(self, params, tok, cache, pos):
        """One decode step: ``tok`` (b, 1) int32 at absolute position
        ``pos`` (traced) -> ((b, vocab) logits, cache). The per-token
        inner loop of :meth:`generate`, exposed for the serving engine's
        continuous-batching decoder (bigdl_tpu.serving.decode)."""
        h = self._embed_at(params, tok, pos)
        h, cache = self.encoder.decode_step(params["encoder"], h, cache,
                                            pos)
        return self._logits(params, h)[:, 0, :], cache

    def verify_logits(self, params, toks, cache, pos):
        """Chunked decode: ``toks`` (b, m) int32 at absolute positions
        ``pos``..``pos+m-1`` (pos traced) -> ((b, m, vocab) logits,
        cache). Row i is the next-token distribution after feeding
        toks[:, :i+1] — the single target dispatch that verifies m
        speculative draft tokens at once, and the suffix prefill a
        shared-prefix-cache hit runs at a page-aligned offset
        (bigdl_tpu.serving.spec_decode / prefix_cache). Row-wise
        bit-identical to m sequential :meth:`decode_logits` calls on
        the dense CPU path (pinned in tests/test_spec_decode.py).
        Caller keeps pos + m <= max_len (positional-table slice and
        cache writes both clamp rather than fail out of range)."""
        h = self._embed_at(params, toks, pos)
        h, cache = self.encoder.decode_chunk(params["encoder"], h, cache,
                                             pos)
        return self._logits(params, h), cache

    def generate(self, params, prompt, max_new_tokens: int,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 rng=None):
        """KV-cache autoregressive decoding (the inference path of the
        long-context flagship — no analog in the reference, whose only
        generative path is SimpleRNN truncated BPTT).

        ``prompt``: (b, s) int32 token ids. One full-prompt prefill builds
        the per-layer K/V cache, then each new token is one O(1)-length
        step against the cache. temperature 0 = greedy; otherwise
        softmax-temperature sampling, optionally top-k truncated.
        Returns (b, max_new_tokens) sampled ids. Jit-compiled; cache size
        is the model's max_len, so prompt+new must fit in it.
        """
        prompt = jnp.asarray(prompt, jnp.int32)
        b, s = prompt.shape
        max_len = self.pos.max_len
        if s + max_new_tokens > max_len:
            raise ValueError(f"prompt ({s}) + max_new_tokens "
                             f"({max_new_tokens}) exceeds max_len {max_len}")
        if rng is None:
            rng = jax.random.PRNGKey(0)

        def sample(logits, key):
            if temperature <= 0.0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            logits = logits / temperature
            if top_k is not None and top_k < self.vocab:
                kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
                logits = jnp.where(logits < kth, -1e30, logits)
            return jax.random.categorical(key, logits).astype(jnp.int32)

        cache_dtype = self.compute_dtype or jnp.float32

        def run(params, prompt, rng):
            cache = self.encoder.init_cache(b, max_len, cache_dtype)
            logits, cache = self.prefill_logits(params, prompt, cache)

            def body(i, carry):
                buf, cache, logits, rng = carry
                rng, key = jax.random.split(rng)
                tok = sample(logits.astype(jnp.float32), key)
                buf = jax.lax.dynamic_update_slice_in_dim(
                    buf, tok[:, None], i, axis=1)
                logits, cache = self.decode_logits(
                    params, tok[:, None], cache, s + i)
                return buf, cache, logits, rng

            buf = jnp.zeros((b, max_new_tokens), jnp.int32)
            buf, _, _, _ = jax.lax.fori_loop(
                0, max_new_tokens, body, (buf, cache, logits, rng))
            return buf

        # one compile per (shape, sampling) config — re-jitting a fresh
        # closure every call would recompile every time
        key = (b, s, max_new_tokens, temperature, top_k)
        cache_attr = getattr(self, "_gen_jit_cache", None)
        if cache_attr is None:
            cache_attr = self._gen_jit_cache = {}
        if key not in cache_attr:
            cache_attr[key] = jax.jit(run)
        return cache_attr[key](params, prompt, rng)

    # ------------------------------------- what the serving engine asks for
    def init_cache(self, batch, max_len, dtype=jnp.float32):
        """What ``batch`` decode slots hold: K and V of ``max_len`` rows
        for every layer."""
        return self.encoder.init_cache(batch, max_len, dtype)

    def prompt_buckets(self, max_len, dtype):
        """The serving prefill's prompt-length ladder: the lengths at
        which the flash kernel keeps its tuned block plan at this model's
        head width."""
        from bigdl_tpu.ops.attention_kernel import serving_prefill_buckets
        head_dim = getattr(self.encoder._modules[0].mha, "head_dim",
                           self.d_model // 4)
        return serving_prefill_buckets(max_len, head_dim, True, dtype)

    def cache_bytes_by_kind(self, cache):
        from bigdl_tpu.obs.memory import tree_bytes
        return {"kv_full": tree_bytes(cache)}


def transformer_lm(vocab: int, **kw) -> TransformerLM:
    return TransformerLM(vocab, **kw)

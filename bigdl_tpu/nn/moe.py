"""Mixture-of-Experts with expert parallelism.

The reference's ``MixtureTable`` (nn/MixtureTable.scala) is a single-node
soft gating layer: every expert runs on every input and a gater blends the
outputs. :class:`MoE` keeps that dense blend available (``dense=True`` —
exact MixtureTable parity) and adds the TPU-scale sparse path the reference
never had: top-k routing with a capacity factor, einsum dispatch/combine
(one-hot matmuls — MXU-friendly, static shapes, no ragged gather), and
optional **expert parallelism**: experts' params stacked on a leading
``[E, ...]`` dim and sharded over an ``expert`` mesh axis, with tokens
moved to their experts by the all-to-all that falls out of resharding the
dispatched tensor (SURVEY.md §2.7: "Expert parallel / MoE — NO" in the
reference).

Load-balancing uses the standard auxiliary loss (mean gate fraction x mean
token fraction per expert); retrieve it from the returned state.

Two classes live here. :class:`MoE` is the MixtureTable parity and its
sparse, capacity-bounded extension over any expert module: softmax gate,
tokens above an expert's capacity dropped. :class:`RoutedFFN` is the
routed feed-forward layer of the language models
(``models/hybrid_moe_lm.py``): sigmoid scores with a selection bias or a
softmax over the chosen logits, top-k with renormalised weights, SwiGLU or
ReGLU experts, an optional shared expert, no capacity and no dropped
token at any token count, and a layer that is told which experts it
holds (one chip's share of an expert-parallel deployment).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu.core.module import Module, SimpleModule

__all__ = ["MoE", "RoutedFFN", "DenseGLU", "glu", "routed_experts"]


class MoE(Module):
    """``MoE(expert, num_experts, d_model, top_k)``: route (batch, seq, d)
    tokens (or (batch, d)) through ``num_experts`` copies of ``expert``.

    ``dense=True`` reproduces the reference MixtureTable exactly: softmax
    gate over ALL experts, every expert computes every token, outputs
    blended. Sparse mode keeps only the top-k experts per token, bounded by
    ``capacity_factor`` (tokens above an expert's capacity are dropped —
    their residual passes through unchanged when used inside a residual
    block).
    """

    def __init__(self, expert: Module, num_experts: int, d_model: int,
                 top_k: int = 1, capacity_factor: float = 1.25,
                 dense: bool = False, name: Optional[str] = None):
        super().__init__(name)
        self._expert_state = expert.init_state()
        if jax.tree_util.tree_leaves(self._expert_state):
            raise ValueError("MoE experts must be stateless")
        self.expert = expert
        self.num_experts = num_experts
        self.d_model = d_model
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.dense = dense

    def init(self, rng):
        ks = jax.random.split(rng, self.num_experts + 1)
        experts = [self.expert.init(k) for k in ks[1:]]
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *experts)
        gate = jax.random.normal(ks[0], (self.d_model, self.num_experts),
                                 jnp.float32) * 0.02
        return {"gate": gate, "experts": stacked}

    def init_state(self):
        # aux_loss is exposed through state so training loops can add it
        return {"aux_loss": jnp.zeros((), jnp.float32)}

    # ----------------------------------------------------------------- apply
    def _run_experts(self, p_experts, xs, training, rng):
        """vmap the expert over its stacked params: xs [E, C, d] -> [E, C, d'].
        Each expert gets its own rng stream (split per expert) so dropout
        masks are decorrelated across experts."""
        if rng is None:
            def one(pb, xb):
                y, _ = self.expert.apply(pb, self._expert_state, xb,
                                         training=training)
                return y
            return jax.vmap(one)(p_experts, xs)

        keys = jax.random.split(rng, self.num_experts)

        def one_k(pb, xb, k):
            y, _ = self.expert.apply(pb, self._expert_state, xb,
                                     training=training, rng=k)
            return y
        return jax.vmap(one_k)(p_experts, xs, keys)

    def apply(self, params, state, x, *, training=False, rng=None):
        orig_shape = x.shape
        tokens = x.reshape(-1, orig_shape[-1])  # [T, d]
        t = tokens.shape[0]
        e = self.num_experts
        logits = tokens @ params["gate"].astype(tokens.dtype)  # [T, E]
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

        if self.dense:
            # exact MixtureTable semantics: blend every expert's output
            ys = self._run_experts(params["experts"],
                                   jnp.broadcast_to(tokens, (e,) + tokens.shape),
                                   training, rng)  # [E, T, d']
            out = jnp.einsum("te,etd->td", probs.astype(ys.dtype), ys)
            new_state = {"aux_loss": jnp.zeros((), jnp.float32)}
            return out.reshape(orig_shape[:-1] + out.shape[-1:]), new_state

        # ---- sparse top-k routing with capacity ----
        cap = max(1, int(self.capacity_factor * t * self.top_k / e))
        gate_vals, gate_idx = jax.lax.top_k(probs, self.top_k)  # [T, k]
        if self.top_k > 1:
            gate_vals = gate_vals / jnp.maximum(
                gate_vals.sum(-1, keepdims=True), 1e-9)
        # top-1 keeps the RAW softmax probability (Switch): renormalizing
        # would make the combine weight identically 1, whose gradient wrt
        # the gate logits is zero — the router would never learn from the
        # task loss

        # position of each (token, k) inside its expert's capacity buffer
        onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)  # [T, k, E]
        flat = onehot.reshape(t * self.top_k, e)
        pos = jnp.cumsum(flat, axis=0) - flat  # arrival order per expert
        pos = (pos * flat).sum(-1).reshape(t, self.top_k)
        keep = pos < cap

        # per-choice dispatch [T, k, E, C]: k-th choice of token t occupies
        # slot (expert gate_idx[t,k], position pos[t,k]) when kept
        disp_k = (jax.nn.one_hot(gate_idx, e, dtype=tokens.dtype)[..., None]
                  * jax.nn.one_hot(pos, cap, dtype=tokens.dtype)[:, :, None, :]
                  * keep[..., None, None].astype(tokens.dtype))
        disp = disp_k.sum(1)                               # [T, E, C] 0/1
        xs = jnp.einsum("tec,td->ecd", disp, tokens)       # [E, C, d]
        ys = self._run_experts(params["experts"], xs, training, rng)
        combine = (disp_k * gate_vals[..., None, None]).sum(1).astype(ys.dtype)
        out = jnp.einsum("tec,ecd->td", combine, ys)

        # load-balance aux loss (Switch-style): E * sum_e f_e * P_e
        frac_tokens = (jax.nn.one_hot(gate_idx[:, 0], e)
                       .mean(0))               # fraction routed (top-1)
        frac_probs = probs.mean(0)
        aux = e * jnp.sum(frac_tokens * frac_probs)
        new_state = {"aux_loss": aux}
        return out.reshape(orig_shape[:-1] + out.shape[-1:]), new_state

    # ------------------------------------------------------------- placement
    def place_expert_parallel(self, mesh: Mesh, params,
                              axis: str = "expert"):
        """Shard the stacked expert params over the expert axis; the gate
        stays replicated. Under jit, XLA inserts the all-to-all that moves
        dispatched tokens to their expert's device."""
        ex = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, NamedSharding(mesh, P(axis))),
            params["experts"])
        gate = jax.device_put(params["gate"], NamedSharding(mesh, P()))
        return {"gate": gate, "experts": ex}


# ------------------------------------------------------ the LM's routed FFN
def _tile(n: int, choices) -> int:
    return next((t for t in choices if n % t == 0), n)


_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}
# tokens a call of the grouped matmuls takes: a longer prompt is walked in
# blocks of this many (the same numbers: a token's row depends on no
# other), so the float32 temporaries of T * k rows stop growing with it
ROW_BLOCK = 4096


def _routed_experts(x, local, wts, w13, w2, act="silu",
                    row_block=ROW_BLOCK):
    """``x`` (T, d) tokens; ``local`` (T, k) int32 the picks as indices
    into the held stack, ``held`` itself for a pick on an absent expert;
    ``wts`` (T, k) float32; ``w13`` (held, d, 2 * width), ``w2`` (held,
    width, d). Returns (T, d) float32: ``sum_j wts[t, j] *
    GLU_{local[t, j]}(x[t])`` over the held picks, the gate's activation
    ``act`` (``silu``: SwiGLU; ``relu``: ReGLU).

    Exact at every T with static shapes: the T * k picks are sorted by
    expert (absent ones last, in a group that is never computed), and one
    grouped matmul a projection (``megablox.gmm``, rows of a group against
    that expert's matrix) walks the tiles of the groups that have rows, so
    an expert nobody chose is never read. The rows go to the sorted order
    and back through two Pallas kernels (``ops/moe_rows.py``), each the
    other's transpose, which never move an absent pick's row. A ``T``
    past ``row_block`` (None: no limit) is walked in blocks of
    ``row_block`` tokens."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from bigdl_tpu.ops import moe_rows
    from bigdl_tpu.ops.attention_kernel import _interpret
    (T, d), k, held = x.shape, local.shape[1], w13.shape[0]
    if row_block and T > row_block and T % row_block == 0:
        blocks = lambda t: t.reshape((T // row_block, row_block)
                                     + t.shape[1:])
        # the barrier keeps a block's sums apart from the loop's stacked
        # output: fused into it, they ask for more fast memory than v5e
        # has (SmallThinker's 8,192- and 16,384-token prefills)
        out = jax.lax.map(
            lambda b: jax.lax.optimization_barrier(
                _routed_experts(*b, w13, w2, act, row_block)),
            (blocks(x), blocks(local), blocks(wts)))
        return out.reshape(T, d)
    width = w2.shape[1]
    m = T * k
    tm = 128 if m >= 128 else -(-m // 16) * 16
    m_pad = -(-m // tm) * tm
    key = jnp.pad(local.reshape(m), (0, m_pad - m), constant_values=held)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=held + 1).astype(jnp.int32)
    pos, n = jnp.argsort(order)[:m].reshape(T, k), jnp.sum(sizes[:held])
    xs = moe_rows.dispatch_rows(x, order, pos, n, _interpret())
    call = functools.partial(gmm, group_sizes=sizes,
                             preferred_element_type=jnp.float32,
                             interpret=_interpret())
    gu = call(xs, w13.astype(x.dtype), tiling=(
        tm, _tile(d, (1024, 1280, 512, 256, 128)),
        _tile(2 * width, (1280, 1024, 512, 256, 128))))
    act = (_ACTS[act](gu[:, :width]) * gu[:, width:]).astype(x.dtype)
    y = call(act, w2.astype(x.dtype), tiling=(
        tm, min(width, 1280), _tile(d, (1024, 512, 256, 128))))
    return moe_rows.combine_rows(y, order, pos, wts, n, _interpret())


@functools.lru_cache(maxsize=None)
def _routed_op(act: str):
    """:func:`_routed_experts` at one activation, with a batching rule of
    its own: under ``vmap`` (``DecodeEngine``'s step is a ``vmap`` over
    slots of a one-token model) the batch's tokens are one token axis of
    one grouped product, so a step reads each chosen expert once for all
    slots. ``jax.grad`` cannot see through a ``custom_vmap``, so the op is
    differentiated by a ``custom_vjp`` whose forward is the plain grouped
    matmuls' own (``megablox.gmm`` has its ``tgmm`` backward): the VJP of
    ``x``, ``wts``, ``w13`` and ``w2``, none for the integer picks. That
    forward takes every token in one block: the backward keeps each row's
    activations whatever the blocks, and a ``lax.map`` over blocks adds
    stacked copies of them (a training step of 2 x 4,096 tokens at
    GLM-4.7-Flash's widths compiles for v5e to 16.35 GB through the map,
    11.89 without). Where nothing differentiates it, the op is the
    ``custom_vmap`` alone."""
    op = jax.custom_batching.custom_vmap(
        functools.partial(_routed_experts, act=act))

    @op.def_vmap
    def _rule(axis_size, in_batched, x, local, wts, w13, w2):
        if in_batched[3] or in_batched[4]:
            raise NotImplementedError(
                "routed_experts: a batch of expert stacks")
        x, local, wts = (
            t if b else jnp.broadcast_to(t, (axis_size,) + t.shape)
            for t, b in zip((x, local, wts), in_batched))
        flat = lambda t: t.reshape((-1,) + t.shape[2:])
        out = op(flat(x), flat(local), flat(wts), w13, w2)
        return out.reshape(x.shape[:2] + out.shape[1:]), True

    whole = functools.partial(_routed_experts, act=act, row_block=None)
    differentiable = jax.custom_vjp(op)
    differentiable.defvjp(lambda *args: jax.vjp(whole, *args),
                          lambda vjp, g: vjp(g))
    return differentiable


def routed_experts(x, local, wts, w13, w2, act: str = "silu"):
    """``sum_j wts[t, j] * GLU_{local[t, j]}(x[t])`` over the held picks
    (:func:`_routed_experts`), batched as :func:`_routed_op` says."""
    return _routed_op(act)(x, local, wts, w13, w2)


class RoutedFFN(SimpleModule):
    """The routed feed-forward layer of a mixture-of-experts LM. With
    ``score="sigmoid"`` (the ``glm4_moe`` / DeepSeek-V3 router): ``s =
    sigmoid(r Wr)`` over all ``num_experts``, in float32; the ``top_k``
    chosen are the largest of ``s + b`` (``b`` a selection bias used for
    the choice only); weights ``s_e / sum_chosen s`` times ``scale``. With
    ``score="softmax_topk"`` (SmallThinker's): the ``top_k`` largest of
    the logits ``r Wr`` themselves, no selection bias leaf, weights the
    softmax over the chosen logits (the softmax over all, renormalised
    over the chosen). ``out = sum_chosen w_e GLU_e(x) + c Shared(x)``, the
    gate's activation ``act`` (``silu``: SwiGLU; ``relu``: ReGLU), the
    shared expert a GLU of ``shared_width`` (0: none). Several shared
    experts are one GLU as wide as all of them, which is their sum; ``c`` =
    ``shared_scale`` (1; ``1 / n`` averages n of them). Under ``sigmoid``,
    ``selection_bias=False`` leaves the bias out: the top ``top_k`` of ``s``
    itself.

    ``r``, what the router reads, is ``x`` unless ``forward`` is given a
    ``router_x`` of its own (a router placed before attention reads the
    layer's input, the experts the normalised stream after it).

    The layer holds experts ``share * held .. share * held + held - 1``
    only (``held`` = ``num_experts``: all of them). Choice and weights are
    over all ``num_experts``; the sum runs over the chosen experts that
    are held, and what the absent ones would have added is left out: one
    chip's part of an expert-parallel layer, without the exchange.

    ``forward`` returns ``(out, picked)``: ``picked`` (..., words) uint32,
    bit ``i % 32`` of word ``i // 32`` set where the token chose held
    expert ``i``."""

    def __init__(self, d_model: int, width: int, num_experts: int,
                 top_k: int, held: Optional[int] = None, share: int = 0,
                 shared_width: int = 0, scale: float = 1.0,
                 init_std: float = 0.02, score: str = "sigmoid",
                 act: str = "silu", selection_bias: bool = True,
                 shared_scale: float = 1.0, name: Optional[str] = None):
        super().__init__(name)
        held = num_experts if held is None else held
        if not 0 < held <= num_experts or num_experts % held:
            raise ValueError(f"held {held} does not divide num_experts "
                             f"{num_experts}")
        if not 0 <= share < num_experts // held:
            raise ValueError(f"share {share}: {num_experts // held} shares "
                             f"of {held} experts")
        if score not in ("sigmoid", "softmax_topk") or act not in _ACTS:
            raise ValueError(f"score {score!r} / act {act!r}: sigmoid or "
                             f"softmax_topk, one of {sorted(_ACTS)}")
        self.d_model, self.width, self.num_experts = (d_model, width,
                                                      num_experts)
        self.top_k, self.held, self.share = top_k, held, share
        self.shared_width, self.scale = shared_width, scale
        self.init_std, self.score, self.act = init_std, score, act
        self.selection_bias = selection_bias and score == "sigmoid"
        self.shared_scale = shared_scale
        self.words = -(-held // 32)

    def init(self, rng):
        ks = jax.random.split(rng, 5)
        d, w, sw = self.d_model, self.width, self.shared_width
        mk = lambda k, shape: self.init_std * jax.random.normal(k, shape)
        out = {"router": {"weight": mk(ks[0], (d, self.num_experts))},
               "w13": mk(ks[1], (self.held, d, 2 * w)),
               "w2": mk(ks[2], (self.held, w, d))}
        if self.selection_bias:
            out["router"]["bias"] = jnp.zeros((self.num_experts,))
        if sw:
            out["shared_w13"] = mk(ks[3], (d, 2 * sw))
            out["shared_w2"] = mk(ks[4], (sw, d))
        return out

    def scores(self, params, x):
        """x (T, d) -> what the choice is made on, (T, num_experts):
        sigmoid scores, or under ``softmax_topk`` the logits; the product
        in float32 at ``highest`` precision whatever ``x``'s dtype."""
        f32 = jnp.float32
        logits = jnp.dot(
            x.astype(f32), params["router"]["weight"].astype(f32),
            precision=jax.lax.Precision.HIGHEST)
        return logits if self.score == "softmax_topk" else jax.nn.sigmoid(
            logits)

    def weights(self, s, idx):
        """The chosen experts' scores, renormalised (under
        ``softmax_topk``: the softmax of the chosen logits), times
        ``scale``."""
        w = jnp.take_along_axis(s, idx, axis=-1)
        if self.score == "softmax_topk":
            return self.scale * jax.nn.softmax(w, axis=-1)
        return self.scale * w / jnp.sum(w, axis=-1, keepdims=True)

    def route(self, params, x):
        """x (T, d) -> (chosen expert ids (T, k) int32, weights (T, k)
        float32), over all ``num_experts``; scores and top-k in float32."""
        s = self.scores(params, x)
        bias = params["router"].get("bias")
        _, idx = jax.lax.top_k(
            s if bias is None else s + bias.astype(jnp.float32), self.top_k)
        return idx, self.weights(s, idx)

    def forward(self, params, x, router_x=None):
        """``(out, picked)`` of ``x`` (..., d); the router reads
        ``router_x`` (..., d) where it is given, else ``x``."""
        lead, dt = x.shape[:-1], x.dtype
        x = x.reshape(-1, self.d_model)
        with jax.named_scope("moe_route"):
            idx, wts = self.route(params, x if router_x is None else
                                  router_x.reshape(-1, self.d_model))
            local = idx - self.share * self.held
            local = jnp.where((local >= 0) & (local < self.held), local,
                              self.held)
            bits = jnp.where(
                (local[..., None] // 32 == jnp.arange(self.words))
                & (local[..., None] < self.held),
                jnp.uint32(1) << (local[..., None] % 32).astype(jnp.uint32),
                jnp.uint32(0))
            picked = jnp.sum(bits, axis=1, dtype=jnp.uint32)
        with jax.named_scope("moe_experts"):
            out = routed_experts(x, local, wts, params["w13"], params["w2"],
                                 self.act)
        if self.shared_width:
            with jax.named_scope("moe_shared"):
                shared = glu(x, params["shared_w13"], params["shared_w2"],
                             self.act)
                if self.shared_scale != 1.0:
                    shared = self.shared_scale * shared
                out = out + shared
        return (out.astype(dt).reshape(*lead, self.d_model),
                picked.reshape(*lead, self.words))

    def _forward(self, params, x, *, training, rng):
        return self.forward(params, x)


def glu(x, w13, w2, act="silu"):
    """``(act(x W1) * x W3) W2`` of x (..., d), ``w13 = [W1 | W3]`` (d, 2
    * width): both products accumulated in float32, the gated rows
    rounded once to ``x``'s dtype. Returns float32."""
    dt = x.dtype
    gu = jnp.dot(x, w13.astype(dt), preferred_element_type=jnp.float32)
    width = w2.shape[0]
    h = (_ACTS[act](gu[..., :width]) * gu[..., width:]).astype(dt)
    return jnp.dot(h, w2.astype(dt), preferred_element_type=jnp.float32)


class DenseGLU(SimpleModule):
    """A dense gated feed-forward layer (SwiGLU under ``act="silu"``):
    :func:`glu` with ``w13`` (d, 2 * width) and ``w2`` (width, d), the
    output in ``x``'s dtype. The leading dense layers of a
    mixture-of-experts LM."""

    def __init__(self, d_model: int, width: int, init_std: float = 0.02,
                 act: str = "silu", name: Optional[str] = None):
        super().__init__(name)
        self.d_model, self.width, self.init_std, self.act = (
            d_model, width, init_std, act)

    def init(self, rng):
        ks = jax.random.split(rng, 2)
        return {"w13": self.init_std * jax.random.normal(
                    ks[0], (self.d_model, 2 * self.width)),
                "w2": self.init_std * jax.random.normal(
                    ks[1], (self.width, self.d_model))}

    def _forward(self, params, x, *, training, rng):
        return glu(x, params["w13"], params["w2"], self.act).astype(x.dtype)

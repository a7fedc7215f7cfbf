"""The decode step's bounded cache read (``ops/cache_attention.py``) on the
CPU, the kernel in interpret mode: the batching rule's one kernel call
against ``vmap`` of the plain form at every kind of count, dead rows that
must not reach the output, which form the rule takes for what it is given,
and ``DecodeEngine`` steps of the three model classes with the bounded
read against the same steps with it switched off."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import models
from bigdl_tpu.models import SambaYLM
from bigdl_tpu.ops import cache_attention as ca
from bigdl_tpu.ops import cache_write as cw
from bigdl_tpu.serving import DecodeEngine

B = ca.BLOCK
S, KH, T, D = 4, 2, 3 * B, 128
SCALE = 1 / math.sqrt(D)
TOL = {jnp.float32: 2e-6, jnp.bfloat16: 2e-2}
DTYPES = pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                                 ids=["f32", "bf16"])
COUNTS = {"one": [1] * S, "below": [B - 1] * S, "at": [B] * S,
          "above": [B + 1] * S, "whole": [T] * S,
          "mixed": [1, 2 * B + 1, T, 37]}


def operands(r, dtype, shape=(S, KH, T, D), seed=0):
    s, kh, t, d = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    draw = lambda k, sh: jax.random.normal(k, sh, jnp.float32).astype(dtype)
    return (draw(ks[0], (s, kh, r, d)), draw(ks[1], shape),
            draw(ks[2], shape))


def a_slot(fn):
    """The engine's shape: a batch of one inside ``vmap`` over slots."""
    return lambda q, k, v, c: fn(q[None], k[None], v[None], c)[0]


def ruled(q, kc, vc, count, m=1, scale=SCALE, **trace):
    """(what the rule returned, the forms it chose) under step_trace."""
    chosen = []
    with cw.step_trace(chosen, **trace):
        out = jax.vmap(a_slot(lambda *a: ca.attend_rows(*a, scale, m)))(
            q, kc, vc, count)
    return out, chosen


def plain(q, kc, vc, count, m=1, scale=SCALE):
    return jax.vmap(a_slot(lambda *a: ca._plain(*a, scale=scale, m=m)))(
        q, kc, vc, count)


def reference(q, kc, vc, count, m, scale):
    """Row by row in float64 numpy: nothing of the op's code."""
    q, kc, vc = (np.asarray(a, np.float64) for a in (q, kc, vc))
    out = np.zeros(q.shape)
    for b, k, i in np.ndindex(*q.shape[:3]):
        n = count - m + i % m + 1
        s = kc[b, k, :n] @ q[b, k, i] * scale
        p = np.exp(s - s.max())
        out[b, k, i] = (p / p.sum()) @ vc[b, k, :n]
    return out


# ------------------------------------------------------------- the op alone
@pytest.mark.parametrize("m", [1, 3])
@pytest.mark.parametrize("count", [3, 17, 40])
def test_alone_it_is_causal_softmax_attention_over_the_live_rows(count, m):
    q, kc, vc = operands(2 * m, jnp.float32, (2, 2, 40, 16))
    got = ca.attend_rows(q, kc, vc, count, 0.25, m)
    np.testing.assert_allclose(got, reference(q, kc, vc, count, m, 0.25),
                               atol=1e-5)


# --------------------------------------------- the rule's kernel, every count
@pytest.mark.parametrize("r", [12, 4, 8])
@DTYPES
@pytest.mark.parametrize("counts", list(COUNTS), ids=list(COUNTS))
def test_the_bounded_form_is_vmap_of_the_plain_form(counts, dtype, r):
    q, kc, vc = operands(r, dtype)
    count = jnp.asarray(COUNTS[counts], jnp.int32)
    got, chosen = ruled(q, kc, vc, count)
    assert chosen == ["bounded"]
    assert got.shape == (S, KH, r, D) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, plain(q, kc, vc, count),
                               atol=TOL[dtype])


@pytest.mark.parametrize("fill", [float("nan"), 1e30], ids=["nan", "1e30"])
@DTYPES
def test_rows_beyond_the_count_do_not_reach_the_output(dtype, fill):
    q, kc, vc = operands(12, dtype)
    count = jnp.asarray(COUNTS["mixed"], jnp.int32)
    dead = jnp.arange(T)[None, None, :, None] >= count[:, None, None, None]
    got, _ = ruled(q, jnp.where(dead, fill, kc).astype(dtype),
                   jnp.where(dead, fill, vc).astype(dtype), count)
    want, _ = ruled(q, kc, vc, count)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_array_equal(got, want)


def test_one_kernel_call_moves_only_the_live_blocks():
    """The call's jaxpr holds the caches once, whole, as operands that stay
    where they are: beside the call nothing makes an array of a cache's
    size but the unit axes a slot's view gains and loses."""
    q, kc, vc = operands(12, jnp.bfloat16)
    count = jnp.asarray(COUNTS["mixed"], jnp.int32)
    jaxpr = jax.make_jaxpr(lambda *a: ruled(*a)[0])(q, kc, vc, count)
    text = str(jaxpr)
    assert text.count("pallas_call") == 1
    assert "name=cache_attend_rows" in text
    big = [e.primitive.name for e in jaxpr.jaxpr.eqns
           if any(getattr(v.aval, "shape", ())[-2:] == (T, D)
                  for v in e.outvars)]
    assert set(big) <= {"reshape", "broadcast_in_dim"}


# ------------------------------------------------- which form the rule takes
def whole_cases():
    f32, count = jnp.float32, jnp.asarray([5, 300, 2, 77], jnp.int32)
    yield "m_3", dict(m=3), operands(6, f32), count + 3
    yield "ragged_T", {}, operands(4, f32, (S, KH, B + 16, D)), count
    yield "one_block", {}, operands(4, f32, (S, KH, B, D)), count
    yield "narrow_rows", {}, operands(4, f32, (S, KH, 2 * B, 64)), count
    yield "no_kernel", dict(kernel=False), operands(4, f32), count
    yield "paged_view", dict(bounded=False), operands(4, f32), count
    q, kc, vc = operands(4, f32, (S, KH, 2 * B, D))
    yield "int8_cache", {}, (q, (kc * 20).astype(jnp.int8),
                             (vc * 20).astype(jnp.int8)), count


@pytest.mark.parametrize("case", list(whole_cases()),
                         ids=[c[0] for c in whole_cases()])
def test_what_the_kernel_does_not_take_is_read_whole_bit_for_bit(case):
    _, kw, (q, kc, vc), count = case
    m = kw.pop("m", 1)
    got, chosen = ruled(q, kc, vc, count, m, **kw)
    assert chosen == ["whole"]
    np.testing.assert_array_equal(got, plain(q, kc, vc, count, m))
    with cw.step_trace(**kw):
        text = str(jax.make_jaxpr(lambda *a: ruled(*a, m, **kw)[0])(
            q, kc, vc, count))
    assert "pallas_call" not in text


def test_a_count_that_is_not_batched_is_the_plain_form():
    """The caller-driven dense path: one position for all slots."""
    q, kc, vc = operands(4, jnp.float32)
    chosen = []
    with cw.step_trace(chosen):
        fn = jax.vmap(a_slot(lambda *a: ca.attend_rows(*a, SCALE)),
                      in_axes=(0, 0, 0, None))
        got = fn(q, kc, vc, 300)
        text = str(jax.make_jaxpr(fn)(q, kc, vc, 300))
    assert chosen == [] and "pallas_call" not in text
    np.testing.assert_array_equal(
        got, plain(q, kc, vc, jnp.full((S,), 300, jnp.int32)))


# ------------------------------------------------------ through DecodeEngine
def transformer():
    return models.transformer_lm(64, d_model=512, num_layers=2, num_heads=4,
                                 num_kv_heads=2, max_len=2 * B), ["bounded"] * 2


def sambay():
    # pairs of 64-wide heads are 128-wide rows; layers 1 and 3 keep rings
    # of B rows (one block: nothing to bound), layer 5 the shared cache,
    # which layer 7 reads too
    return (SambaYLM(init_std=0.125, vocab=64, d_model=256, num_layers=8,
                     num_heads=4, num_kv_heads=2, d_ff=128, window=B,
                     mb_per_layer=2, max_len=2 * B),
            ["whole"] * 2 + ["bounded"] * 2)


def hybrid():
    return models.HybridMoELM(
        vocab=64, d_model=64, num_layers=4, num_heads=2, num_kv_heads=1,
        head_dim=128, gate_rank=16, gqa_interval=3, num_experts=8,
        experts_held=4, share=0, top_k=2, expert_width=32, max_len=2 * B,
        init_std=0.125), ["bounded"]


def drive(model, params, kernel, monkeypatch):
    """Slot 0 deep, slot 1 shallow, slot 2 free; then a third request takes
    over the finished slot 1. The logits before every step, by request."""
    eng = DecodeEngine(model, params, slots=3, prompt_buckets=(16, 64))
    if not kernel:  # what an engine with a mesh asks for
        monkeypatch.setattr(
            cw, "step_trace", lambda chosen=None, *_, real=cw.step_trace:
            real(chosen, kernel=False))
    toks = [int(t) for t in np.random.RandomState(0).randint(0, 64, 64)]
    try:
        futs = [eng.submit(toks[:40], 6), eng.submit(toks[40:45], 3)]
        for _ in range(2):
            eng.step()
        futs.append(eng.submit(toks[50:61], 3))  # waits for slot 1
        seen = {}
        while not all(f.done() for f in futs):
            for slot, req in enumerate(eng._reqs):
                if req is not None:
                    seen.setdefault(id(req), []).append(
                        np.asarray(eng._logits)[slot])
            eng.step()
        return ([f.result() for f in futs],
                [np.stack(v) for v in seen.values()],
                eng.debug_snapshot()["kv"])
    finally:
        eng.close()


@pytest.mark.parametrize("build", [transformer, sambay, hybrid])
def test_engine_steps_with_the_bounded_read_equal_those_without(
        build, monkeypatch):
    model, reads = build()
    params = model.init(jax.random.PRNGKey(2))
    out_b, logits_b, kv_b = drive(model, params, True, monkeypatch)
    out_w, logits_w, kv_w = drive(model, params, False, monkeypatch)
    assert kv_b["cache_read"] == reads
    assert kv_w["cache_read"] == ["whole"] * len(reads)
    assert kv_b["row_write"] == "batched" and kv_w["row_write"] == "scatter"
    assert out_b == out_w
    assert len(logits_b) == 3
    for got, want in zip(logits_b, logits_w):
        np.testing.assert_allclose(got, want, rtol=2e-4,
                                   atol=2e-5 * np.abs(want).max())

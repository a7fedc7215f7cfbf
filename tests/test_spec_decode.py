"""Speculative decoding + sampling-mode tests (ISSUE 14): greedy output
bit-identical with speculation on vs off (self-draft and a distinct
draft), acceptance-rate counters, the rejection-sampling distribution
check under fixed seeds, chunked-verify parity with the sequential
decode path (K/V bitwise, argmax chains equal), warp_logits sentinel
exactness, per-request seed determinism, and shared-prefix-cache hits
staying bit-identical."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import models
from bigdl_tpu.serving import DecodeEngine, MetricsRegistry
from bigdl_tpu.serving import spec_decode as sd


# --------------------------------------------------------------- fixtures
@pytest.fixture(scope="module")
def lm():
    # untied + scaled: a tied random init is a fixed-point attractor
    # (each token's own embedding dominates its logit row, so greedy
    # repeats one token forever); an untied head makes the chain wander
    m = models.transformer_lm(61, d_model=48, num_layers=2, num_heads=4,
                              max_len=96, tie_embeddings=False)
    p = jax.tree_util.tree_map(lambda a: a * 2.0,
                               m.init(jax.random.PRNGKey(7)))
    return m, p


@pytest.fixture(scope="module")
def draft_lm():
    m = models.transformer_lm(61, d_model=32, num_layers=1, num_heads=2,
                              max_len=96, tie_embeddings=False)
    return m, m.init(jax.random.PRNGKey(123))


PROMPTS = [[3, 9, 44, 1, 55, 2], [7, 7, 12], [60, 1, 2, 3, 4, 5, 6, 8]]


def _greedy_ref(lm, prompt, n, **kw):
    model, params = lm
    return DecodeEngine(model, params, slots=2, max_len=96,
                        **kw).generate(prompt, n)


# --------------------------------------------- greedy bit-identity (spec)
def test_spec_greedy_bit_identical_self_draft(lm):
    model, params = lm
    base = [_greedy_ref(lm, p, 20) for p in PROMPTS]
    de = DecodeEngine(model, params, slots=2, max_len=96, speculate=4)
    for prompt, ref in zip(PROMPTS, base):
        assert de.generate(prompt, 20) == ref


def test_spec_greedy_bit_identical_distinct_draft(lm, draft_lm):
    """A mismatched draft changes only the accept RATE — never a token."""
    model, params = lm
    dm, dp = draft_lm
    de = DecodeEngine(model, params, slots=2, max_len=96, speculate=3,
                      draft_model=dm, draft_params=dp)
    for prompt in PROMPTS:
        assert de.generate(prompt, 20) == _greedy_ref(lm, prompt, 20)


def test_spec_concurrent_slots_bit_identical(lm):
    """Requests decoding concurrently in one spec batch each match their
    solo non-speculative output (slot interference would break this)."""
    model, params = lm
    de = DecodeEngine(model, params, slots=3, max_len=96, speculate=4)
    futs = [de.submit(p, 15) for p in PROMPTS]
    while not all(f.done() for f in futs):
        assert de.step() > 0 or all(f.done() for f in futs)
    for prompt, fut in zip(PROMPTS, futs):
        assert fut.result() == _greedy_ref(lm, prompt, 15)


def test_spec_stop_token_truncates_round(lm):
    """A stop token accepted mid-chunk ends the request exactly there —
    tokens speculated past it are discarded."""
    model, params = lm
    ref = _greedy_ref(lm, PROMPTS[0], 20)
    stop = ref[2]
    want = ref[:ref.index(stop) + 1]  # stream up to the first hit
    de = DecodeEngine(model, params, slots=2, max_len=96, speculate=4)
    assert de.generate(PROMPTS[0], 20, stop_token=stop) == want
    dense = DecodeEngine(model, params, slots=2, max_len=96)
    assert dense.generate(PROMPTS[0], 20, stop_token=stop) == want


def test_spec_max_len_boundary(lm):
    """prompt + max_new == max_len: the chunk clamp (m -> tail) path."""
    model, params = lm
    prompt = PROMPTS[0]
    small = DecodeEngine(model, params, slots=1, max_len=32)
    ref = small.generate(prompt, 32 - len(prompt))
    spec = DecodeEngine(model, params, slots=1, max_len=32, speculate=4)
    assert spec.generate(prompt, 32 - len(prompt)) == ref


# ------------------------------------------------------- accept counters
def test_spec_accept_counters_and_dispatch_win(lm):
    model, params = lm
    reg = MetricsRegistry()
    de = DecodeEngine(model, params, slots=2, max_len=96, speculate=4,
                      metrics=reg)
    de.generate(PROMPTS[0], 20)
    g = lambda n: reg._metrics[n].value
    assert g("spec_proposed_total") > 0
    # self-draft: every proposal accepted
    assert g("spec_accepted_total") == g("spec_proposed_total")
    assert g("spec_accept_rate") == 1.0
    # the tentpole win, CPU-checkable as a dispatch-count proxy: >1
    # token emitted per target verify step (here exactly K+1 = 5)
    assert g("spec_accepted_tokens_per_step") > 1.0
    assert g("generated_tokens_total") == 20.0
    assert g("decode_steps_total") < 20.0


@pytest.mark.parametrize("speculate", [0, 2])
def test_live_positions_counter(lm, speculate):
    """``decode_live_positions_total`` rises each step by the live slots'
    positions before it (the cache positions the step had to read, counted
    before the emit loop advances them), on the plain step and on the
    speculative round."""
    model, params = lm
    reg = MetricsRegistry()
    de = DecodeEngine(model, params, slots=3, max_len=96,
                      speculate=speculate, metrics=reg)
    for prompt in PROMPTS[:2]:  # 6 and 3 tokens; the third slot stays free
        de.submit(prompt, 30)
    g = lambda n: reg._metrics[n].value
    assert g("decode_live_positions_total") == 0
    want = []
    for _ in range(3):
        want.append(int(de._pos[:2].sum()))
        assert de.step() == 2
        assert g("decode_live_positions_total") == sum(want)
    assert g("decode_steps_total") == 3
    assert want[0] == 6 + 3
    if not speculate:  # one token a slot a step
        assert want == [9, 11, 13]


def test_spec_low_accept_rate_with_random_draft(lm, draft_lm):
    model, params = lm
    dm, dp = draft_lm
    reg = MetricsRegistry()
    de = DecodeEngine(model, params, slots=2, max_len=96, speculate=4,
                      draft_model=dm, draft_params=dp, metrics=reg)
    de.generate(PROMPTS[0], 20)
    g = lambda n: reg._metrics[n].value
    assert 0.0 <= g("spec_accept_rate") < 1.0
    # even with zero acceptance every round still emits its correction
    assert g("spec_accepted_tokens_per_step") >= 1.0


# -------------------------------------------- rejection-sampling exactness
def test_rejection_sampling_matches_target_distribution():
    """The emitted-token distribution equals the TARGET distribution p,
    not the draft q (Leviathan/Chen exactness), under fixed seeds: draw
    the proposal from q, run accept_chunk, histogram the first emitted
    token over many seeds, compare to p."""
    v = 8
    rng = np.random.RandomState(0)
    t_logits = jnp.asarray(rng.randn(2, v), jnp.float32)  # m=2 chunk
    d_logits = jnp.asarray(rng.randn(v), jnp.float32)     # deliberately != p
    temp, top_k, top_p, pos = jnp.float32(1.0), jnp.int32(0), \
        jnp.float32(1.0), jnp.int32(5)

    @jax.jit
    def one(seed):
        prop, q = sd.draft_propose(d_logits, temp, top_k, top_p, seed, pos)
        emitted, n_emit, _ = sd.accept_chunk(
            t_logits, q[None], prop[None], temp, top_k, top_p, seed, pos)
        return emitted[0]

    n = 4000
    toks = np.array([int(one(jnp.uint32(s))) for s in range(n)])
    freq = np.bincount(toks, minlength=v) / n
    p = np.asarray(jax.nn.softmax(t_logits[0]))
    q = np.asarray(jax.nn.softmax(d_logits))
    # close to p...
    assert np.abs(freq - p).max() < 0.04
    # ...and measurably NOT q (the draft distribution differs from p)
    assert np.abs(p - q).max() > 0.12
    assert np.abs(freq - q).max() > 0.08


def test_rejection_sampling_deterministic_per_seed():
    v = 8
    rng = np.random.RandomState(3)
    t_logits = jnp.asarray(rng.randn(3, v), jnp.float32)
    q = jnp.asarray(jax.nn.softmax(rng.randn(2, v)), jnp.float32)
    props = jnp.asarray([1, 5], jnp.int32)
    args = (t_logits, q, props, jnp.float32(0.9), jnp.int32(0),
            jnp.float32(1.0), jnp.uint32(42), jnp.int32(7))
    a = [np.asarray(x) for x in sd.accept_chunk(*args)]
    b = [np.asarray(x) for x in sd.accept_chunk(*args)]
    for x, y in zip(a, b):
        assert np.array_equal(x, y)


# ------------------------------------- chunked verify vs sequential decode
def test_verify_logits_matches_sequential_decode(lm):
    """The one-dispatch chunked verify is what makes speculation pay; pin
    its contract vs m sequential decode_logits calls: K/V caches equal to
    float noise, per-row argmax IDENTICAL (XLA contracts (m, L) and
    (1, L) differently on CPU, so exact bitwise equality is not the
    contract — token-level greedy identity is, and the engine-level
    bit-identity tests above enforce it end to end)."""
    model, params = lm
    prompt = np.asarray([PROMPTS[0]], np.int32)
    s = prompt.shape[1]
    toks = np.asarray([[11, 29, 3, 41]], np.int32)
    m = toks.shape[1]

    cache_a = model.encoder.init_cache(1, 96, jnp.float32)
    _, cache_a = model.prefill_logits(params, prompt, cache_a,
                                      jnp.int32(s - 1))
    cache_b = jax.tree_util.tree_map(lambda a: a, cache_a)

    lg_chunk, cache_a = model.verify_logits(params, jnp.asarray(toks),
                                            cache_a, jnp.int32(s))
    seq_rows = []
    for j in range(m):
        lg, cache_b = model.decode_logits(params, toks[:, j:j + 1],
                                          cache_b, jnp.int32(s + j))
        seq_rows.append(np.asarray(lg[0]))
    # K/V written by the chunk == K/V written token-by-token (to noise)
    for a, b in zip(jax.tree_util.tree_leaves(cache_a),
                    jax.tree_util.tree_leaves(cache_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-4)
    chunk_rows = np.asarray(lg_chunk[0])
    for j in range(m):
        assert int(np.argmax(chunk_rows[j])) == int(np.argmax(seq_rows[j]))
        np.testing.assert_allclose(chunk_rows[j], seq_rows[j],
                                   rtol=0, atol=1e-4)


# ----------------------------------------------------- warp_logits + seeds
def test_warp_sentinels_are_bitwise_noops():
    lg = jnp.asarray(np.random.RandomState(5).randn(33), jnp.float32)
    out = sd.warp_logits(lg, jnp.float32(2.0), jnp.int32(0),
                         jnp.float32(1.0))
    assert np.array_equal(np.asarray(out), np.asarray(lg / 2.0))


def test_warp_top_k_restricts_support():
    lg = jnp.asarray(np.random.RandomState(6).randn(40), jnp.float32)
    out = np.asarray(sd.warp_logits(lg, jnp.float32(1.0), jnp.int32(5),
                                    jnp.float32(1.0)))
    kept = np.where(out > -1e29)[0]
    top5 = np.argsort(np.asarray(lg))[-5:]
    assert set(kept) == set(top5)


def test_warp_top_p_keeps_minimal_nucleus():
    probs = np.asarray([0.5, 0.3, 0.1, 0.06, 0.04], np.float32)
    lg = jnp.asarray(np.log(probs))
    out = np.asarray(sd.warp_logits(lg, jnp.float32(1.0), jnp.int32(0),
                                    jnp.float32(0.75)))
    assert set(np.where(out > -1e29)[0]) == {0, 1}  # 0.5+0.3 covers 0.75


def test_sampling_deterministic_per_request_seed(lm):
    model, params = lm
    kw = dict(temperature=0.8, top_k=12, top_p=0.9)
    a = DecodeEngine(model, params, slots=2, max_len=96).generate(
        PROMPTS[0], 12, seed=9, **kw)
    b = DecodeEngine(model, params, slots=2, max_len=96).generate(
        PROMPTS[0], 12, seed=9, **kw)
    c = DecodeEngine(model, params, slots=2, max_len=96).generate(
        PROMPTS[0], 12, seed=10, **kw)
    assert a == b
    assert a != c  # different seed, different stream


def test_sampled_engine_respects_top_k(lm):
    """With top_k=1 sampling degenerates to greedy — any temperature."""
    model, params = lm
    ref = _greedy_ref(lm, PROMPTS[0], 12)
    de = DecodeEngine(model, params, slots=2, max_len=96)
    assert de.generate(PROMPTS[0], 12, temperature=1.3, top_k=1,
                       seed=4) == ref


def test_submit_validates_sampling_args(lm):
    model, params = lm
    de = DecodeEngine(model, params, slots=1, max_len=96)
    with pytest.raises(ValueError):
        de.submit([1, 2], 4, top_k=-1)
    with pytest.raises(ValueError):
        de.submit([1, 2], 4, top_p=0.0)
    with pytest.raises(ValueError):
        de.submit([1, 2], 4, top_p=1.5)


def test_parse_draft_dims():
    assert sd.parse_draft_dims("64,2,4") == {
        "d_model": 64, "num_layers": 2, "num_heads": 4}
    with pytest.raises(ValueError):
        sd.parse_draft_dims("64,2")
    with pytest.raises(ValueError):
        sd.parse_draft_dims("65,2,4")  # d_model % heads


# ------------------------------------------------------ shared-prefix cache
def test_prefix_cache_hit_bit_identical(lm):
    """Second request sharing a page-aligned prefix: served via page copy
    + suffix prefill, tokens bit-identical to the cold path, hit
    counters populated."""
    model, params = lm
    reg = MetricsRegistry()
    de = DecodeEngine(model, params, slots=2, max_len=96,
                      kv_page_tokens=8, prefix_cache=True, metrics=reg)
    shared = list(range(1, 20))  # usable prefix 16 = 2 pages
    a = de.generate(shared, 8)
    b = de.generate(shared + [33], 8)
    cold = DecodeEngine(model, params, slots=2, max_len=96)
    assert a == cold.generate(shared, 8)
    assert b == cold.generate(shared + [33], 8)
    assert de._pfx.hits >= 1
    assert reg._metrics["prefix_cache_hits_total"].value >= 1
    assert reg._metrics["prefix_cache_misses_total"].value >= 1


def test_prefix_cache_with_speculation(lm):
    model, params = lm
    de = DecodeEngine(model, params, slots=2, max_len=96,
                      kv_page_tokens=8, prefix_cache=True, speculate=3)
    shared = list(range(2, 25))
    a = de.generate(shared, 10)
    b = de.generate(shared + [7, 8], 10)
    assert a == _greedy_ref(lm, shared, 10)
    assert b == _greedy_ref(lm, shared + [7, 8], 10)
    assert de._pfx.hits >= 1


def test_prefix_cache_requires_paging(lm):
    model, params = lm
    with pytest.raises(ValueError, match="prefix_cache"):
        DecodeEngine(model, params, slots=1, max_len=96,
                     prefix_cache=True)

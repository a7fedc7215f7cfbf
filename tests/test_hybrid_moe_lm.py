"""The hybrid KDA / gated-GQA language model with routed experts
(``models/hybrid_moe_lm.py``; Solar-Open2-250B's architecture) at toy
sizes on the CPU, held to its plain reference
(``benchmark/references/solar_open2.py``): the chunked delta rule against
the token-by-token recurrence, the routed layer against a per-token loop
at every token count, the shares of an expert-parallel layer against the
uncut layer, and prefill + decode through ``DecodeEngine`` against the
reference's full forward."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import models, nn
from bigdl_tpu.nn.linear_attention import (kda_chunked, kda_recurrent,
                                           kda_step)
from bigdl_tpu.serving import DecodeEngine, MetricsRegistry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "reference_solar_open2",
    os.path.join(ROOT, "benchmark", "references", "solar_open2.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

# two periods; 16 experts in 4 shares of 4, this is share 1
ARGS = dict(vocab=128, d_model=64, num_layers=8, num_heads=4,
            num_kv_heads=2, head_dim=16, gate_rank=16, gqa_interval=3,
            num_experts=16, experts_held=4, share=1, top_k=4,
            expert_width=32, max_len=128, init_std=0.125)


@pytest.fixture(scope="module")
def lm():
    model = models.HybridMoELM(**ARGS)
    return model, model.init(jax.random.PRNGKey(3))


def tokens(n, seed=0):
    return [int(t) for t in
            np.random.RandomState(seed).randint(0, ARGS["vocab"], size=n)]


# ------------------------------------------------------------------- KDA
def kda_rows(length, seed=0, b=2, h=3, dk=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, h, length, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, h, length, dk)))
    v = jax.random.normal(ks[2], (b, h, length, dk))
    g = -0.5 * jax.random.uniform(ks[3], (b, h, length, dk))
    beta = 2.0 * jax.random.uniform(ks[4], (b, h, length))  # some > 1
    return q, k, v, g, beta, jax.random.normal(ks[5], (b, h, dk, dk))


@pytest.mark.parametrize("length", [64, 192])
def test_chunk_form_is_the_recurrence(length):
    rows = kda_rows(length)
    assert float(rows[4].max()) > 1.5  # negative-eigenvalue steps present
    o1, s1 = kda_recurrent(*rows)
    o2, s2 = kda_chunked(*rows, chunk=64)
    np.testing.assert_allclose(o2, o1, atol=1e-4)
    np.testing.assert_allclose(s2, s1, atol=1e-4)


def test_chunk_form_survives_equal_keys_at_beta_two():
    """Every key the same and beta 2: the triangular system's off-diagonal
    is all twos, where a series in powers of it would overflow; the solve
    stays at the recurrence."""
    q, k, v, g, beta, s0 = kda_rows(64)
    k = jnp.broadcast_to(k[:, :, :1], k.shape)
    rows = (q, k, v, jnp.zeros_like(g), jnp.full_like(beta, 2.0), s0)
    o1, s1 = kda_recurrent(*rows)
    o2, s2 = kda_chunked(*rows, chunk=64)
    np.testing.assert_allclose(o2, o1, atol=1e-3)
    np.testing.assert_allclose(s2, s1, atol=1e-3)


def test_two_pass_step_is_the_published_step():
    *rows, s = kda_rows(1)
    q, k, v, g, beta = (t[:, :, 0] for t in rows)
    o, s_new = kda_step(s, q, k, v, g, beta)
    s1 = jnp.exp(g)[..., None] * s
    u = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", s1, k))
    s2 = s1 + k[..., None] * u[..., None, :]
    np.testing.assert_allclose(s_new, s2, atol=1e-5)
    np.testing.assert_allclose(o, jnp.einsum("bhkv,bhk->bhv", s2, q),
                               atol=1e-5)


@pytest.mark.parametrize("length", [37, 64, 100, 128])
def test_kda_mixer_prefill_is_the_reference(length):
    """The mixer whole (projections, convolution, chunked rule, head norm,
    gate) on lengths that are and are not multiples of the chunk, against
    the reference's token-by-token scan."""
    kda = nn.KDA(64, 4, 16, gate_rank=16, init_std=0.125)
    p = kda.init(jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, length, 64))
    out, cache = kda.prefill(p, x, kda.init_cache(1))
    want = ref._kda(p, x[0], 4, 1e-5)
    np.testing.assert_allclose(out[0], want, atol=1e-4)
    # a padded bucket: the state and the history stop at `last`
    pad = jnp.concatenate([x, jnp.ones((1, 23, 64))], axis=1)
    out_p, cache_p = kda.prefill(p, pad, kda.init_cache(1), length - 1)
    np.testing.assert_allclose(out_p[0, :length], want, atol=1e-4)
    np.testing.assert_allclose(cache_p["s"], cache["s"], atol=1e-5)
    np.testing.assert_allclose(cache_p["conv"], cache["conv"], atol=1e-6)


def test_kda_decode_steps_continue_the_prefill():
    kda = nn.KDA(64, 4, 16, gate_rank=16, init_std=0.125)
    p = kda.init(jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 70, 64))
    want = ref._kda(p, x[0], 4, 1e-5)
    _, cache = kda.prefill(p, x[:, :66], kda.init_cache(1))
    for i in range(66, 70):
        out, cache = kda.decode_step(p, x[:, i:i + 1], cache)
        np.testing.assert_allclose(out[0, 0], want[i], atol=1e-4)


# ------------------------------------------------------------ routed FFN
def ffn(held=4, share=1, **kw):
    layer = nn.RoutedFFN(64, 32, 16, 4, held=held, share=share,
                         shared_width=32, init_std=0.125, **kw)
    return layer, layer.init(jax.random.PRNGKey(0))


def per_token_loop(layer, p, x):
    """The layer one token and one pick at a time."""
    idx, w = layer.route(p, x)
    half = layer.width
    out = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        for j in range(layer.top_k):
            e = int(idx[t, j]) - layer.share * layer.held
            if 0 <= e < layer.held:
                gu = x[t] @ p["w13"][e]
                out[t] += float(w[t, j]) * np.asarray(
                    (jax.nn.silu(gu[:half]) * gu[half:]) @ p["w2"][e])
        gu = x[t] @ p["shared_w13"]
        out[t] += np.asarray((jax.nn.silu(gu[:half]) * gu[half:])
                             @ p["shared_w2"])
    return out


@pytest.mark.parametrize("n_tokens", [1, 64, 1000])
def test_routed_layer_is_the_per_token_loop(n_tokens):
    layer, p = ffn()
    x = jax.random.normal(jax.random.PRNGKey(n_tokens), (n_tokens, 64))
    out, picked = jax.jit(layer.forward)(p, x)
    np.testing.assert_allclose(out, per_token_loop(layer, p, x), atol=1e-4)
    idx, _ = layer.route(p, x)
    local = np.asarray(idx) - 4
    want = [sum(1 << int(e) for e in row if 0 <= e < 4) for row in local]
    assert picked[:, 0].tolist() == want


def test_no_token_is_dropped_when_all_pick_the_same_experts():
    """A selection bias that sends every token to experts 4..7, all held:
    1,000 tokens on each of four experts, none dropped (``MoE`` would cut
    them at its capacity)."""
    layer, p = ffn()
    p["router"]["bias"] = p["router"]["bias"].at[4:8].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(5), (1000, 64))
    idx, w = layer.route(p, x)
    assert sorted(np.unique(idx).tolist()) == [4, 5, 6, 7]
    assert float(w.max()) < 1.0  # the bias chooses, the scores weigh
    out, picked = layer.forward(p, x)
    np.testing.assert_allclose(out, per_token_loop(layer, p, x), atol=1e-4)
    assert set(picked[:, 0].tolist()) == {0b1111}


def test_batching_rule_makes_one_grouped_product_of_the_slots():
    """Under ``vmap`` (the engine's step) the slots' tokens are one token
    axis: the same numbers as the flat call, and one ``pallas_call`` a
    projection, not one a slot."""
    layer, p = ffn()
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 1, 1, 64))
    step = jax.vmap(lambda row: layer.forward(p, row))
    out, picked = step(x)
    flat, flat_picked = layer.forward(p, x.reshape(6, 64))
    np.testing.assert_allclose(out.reshape(6, 64), flat, atol=1e-6)
    assert (picked.reshape(6, 1) == flat_picked).all()
    text = str(jax.make_jaxpr(step)(x))
    assert text.count("pallas_call") == 2


def test_the_shares_add_up_to_the_uncut_layer_of_the_reference():
    """Eight shares of two experts each: their routed parts, with the
    shared expert counted once, are the reference's layer with all 16
    experts held."""
    whole, p = ffn(held=16, share=0)
    h = jax.random.normal(jax.random.PRNGKey(7), (50, 64))
    # the reference's layer, uncut: its own routing, every expert
    score = ref._sigmoid(h @ p["router"]["weight"])
    chosen = jnp.argsort(-(score + p["router"]["bias"]), axis=-1)[:, :4]
    w = jnp.take_along_axis(score, chosen, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    gu = h @ p["shared_w13"]
    shared = (ref._silu(gu[:, :32]) * gu[:, 32:]) @ p["shared_w2"]
    uncut = shared + sum(
        ref._expert(p["w13"][e], p["w2"][e], h,
                    jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1))
        for e in range(16))
    parts = []
    for share in range(8):
        layer, _ = ffn(held=2, share=share)
        mine = dict(p, w13=p["w13"][2 * share:2 * share + 2],
                    w2=p["w2"][2 * share:2 * share + 2])
        parts.append(layer.forward(mine, h)[0])
    np.testing.assert_allclose(sum(parts) - 7 * shared, uncut, atol=1e-4)
    np.testing.assert_allclose(whole.forward(p, h)[0], uncut, atol=1e-4)


def test_flips_between_bf16_and_f32_inputs_are_few():
    """The router runs in float32 whatever the activations' dtype, so
    rounding the input to bf16 moves the chosen set only where the last
    chosen and the first unchosen score nearly tie."""
    layer = nn.RoutedFFN(256, 32, 320, 8, held=40, share=0, init_std=0.02)
    p = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2000, 256))
    a, _ = layer.route(p, x)
    b, _ = layer.route(p, x.astype(jnp.bfloat16))
    flipped = int((np.sort(a, -1) != np.sort(b, -1)).any(-1).sum())
    print(f"tokens whose chosen set moved under bf16 inputs: {flipped} "
          "of 2000")
    assert flipped < 100  # read: 20-40


# ----------------------------------------------------------- the model
def test_full_forward_is_the_reference(lm):
    model, p = lm
    toks = tokens(45)
    want = np.asarray(ref.logits(p, ARGS, toks))
    got = np.asarray(model.logits(p, jnp.asarray([toks])))[0]
    assert np.abs(got - want).max() / np.abs(want).max() < 1e-4
    assert model.kinds == ["gqa", "kda", "kda", "kda"] * 2


def test_count_step_by_hand(lm):
    model, _ = lm
    picked = np.zeros((3, 8, 1), np.uint32)
    picked[0, 0, 0] = 0b0011
    picked[1, 0, 0] = 0b0110
    picked[2, 5, 0] = 0b1000
    assert model.count_step(picked) == {
        "moe_picks_total": 3 * 8 * 4, "moe_held_picks_total": 5,
        "moe_experts_touched_total": 4}
    assert model.count_step(picked[:0])["moe_experts_touched_total"] == 0


def test_named_scopes_are_in_the_step(lm):
    model, p = lm
    cache = model.init_cache(1, 128)
    text = jax.jit(model.decode_logits).lower(
        p, jnp.zeros((1, 1), jnp.int32), cache, 3).as_text(debug_info=True)
    for scope in ("kda", "attn_gated", "moe_route", "moe_experts",
                  "moe_shared"):
        assert scope in text, scope


# ------------------------------------------------------------ the engine
@pytest.fixture(scope="module")
def engine(lm):
    model, p = lm
    reg = MetricsRegistry()
    return DecodeEngine(model, p, slots=3, metrics=reg), reg


def greedy_of_reference(p, prompt, out):
    want = np.asarray(ref.logits(p, ARGS, prompt + out))
    return want, [int(np.argmax(want[len(prompt) - 1 + i]))
                  for i in range(len(out))]


def test_prefill_then_decode_is_the_references_forward(lm, engine):
    """A prompt of 20, padded to the bucket of 32, then four decode steps:
    the engine's logits at every step are the reference's."""
    (model, p), (eng, _) = lm, engine
    prompt = tokens(20, seed=4)
    fut = eng.submit(prompt, 5)
    slot = next(i for i, r in enumerate(eng._reqs) if r is not None)
    got = [np.asarray(eng._logits)[slot]]
    for _ in range(4):
        eng.step()
        got.append(np.asarray(eng._logits)[slot])
    eng.step()
    out = fut.result(0)
    want, greedy = greedy_of_reference(p, prompt, out)
    assert out == greedy
    for i, g in enumerate(got):
        assert np.abs(g - want[19 + i]).max() / np.abs(want).max() < 1e-4


def test_two_depths_and_a_reused_slot(lm, engine):
    """Two requests at different depths in one step, then a third into a
    slot one of them left: no state, conv row or K/V row survives."""
    (model, p), (eng, reg) = lm, engine
    first, second = tokens(20, seed=1), tokens(37, seed=2)
    f1 = eng.submit(first, 6)
    eng.step()
    eng.step()
    f2 = eng.submit(second, 5)
    while eng.step():
        pass
    for prompt, fut in ((first, f1), (second, f2)):
        out = fut.result(0)
        assert out == greedy_of_reference(p, prompt, out)[1]
    third = tokens(11, seed=3)
    f3 = eng.submit(third, 4)
    while eng.step():
        pass
    out = f3.result(0)
    assert out == greedy_of_reference(p, third, out)[1]
    # the step's own counts reached the registry
    steps = reg.counter("decode_steps_total").value
    picks = reg.counter("moe_picks_total").value
    held = reg.counter("moe_held_picks_total").value
    touched = reg.counter("moe_experts_touched_total").value
    assert steps > 0 and picks % (8 * 4) == 0
    assert 0 < touched <= held < picks
    assert touched <= steps * 8 * 4
    text = reg.render()
    for name in model.step_counters:
        assert name in text
    for kind in ("kv_full", "kda_state", "conv_state"):
        assert f"decode_cache_bytes_{kind}" in text
    assert eng.debug_snapshot()["kv"]["bytes_by_kind"] == {
        "kv_full": 2 * 3 * 2 * 2 * 128 * 16 * 4,
        "kda_state": 6 * 3 * 4 * 16 * 16 * 4,
        "conv_state": 6 * 3 * 3 * 3 * 64 * 4}


@pytest.mark.parametrize("kw,names", [
    ({"kv_page_tokens": 16}, ["kv_page_tokens"]),
    ({"kv_page_tokens": 16, "prefix_cache": True},
     ["kv_page_tokens", "prefix_cache"]),
    ({"speculate": 2}, ["speculate"]),
    ({"quantize": "int8"}, ["quantize", "routed expert stack"]),
    ({"mesh": object()}, ["mesh", "routed expert stack"]),
])
def test_engine_names_each_refused_feature(lm, kw, names):
    model, p = lm
    with pytest.raises(ValueError) as e:
        DecodeEngine(model, p, slots=2, **kw)
    text = str(e.value)
    assert "HybridMoELM keeps recurrent state in its slots and routed " \
           "expert stacks in its layers" in text
    for name in names:
        assert name in text
    refused = {"kv_page_tokens", "prefix_cache", "speculate", "quantize",
               "mesh"}
    for other in refused - set(kw):
        assert other + ":" not in text


# ----------------------------------------------------- published widths
def test_published_widths_by_eval_shape():
    """``solar_open2``: the issue's table at the published widths, nothing
    allocated: 3.31B parameters held, a slot of 16.78 + 12.58 + 0.44 MB."""
    model = models.solar_open2(max_len=4096)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n = sum(v.size for v in jax.tree_util.tree_leaves(params))
    assert 3.30e9 < n < 3.32e9
    ffn_p = params["layers"]["1"]["ffn"]
    assert ffn_p["w13"].shape == (40, 4096, 2560)
    assert ffn_p["router"]["weight"].shape == (4096, 320)
    assert params["layers"]["1"]["mixer"]["w_qkv"].shape == (4096, 24576)
    assert params["layers"]["0"]["mixer"]["wg"].shape == (4096, 8192)
    assert params["head"]["weight"].shape == (24576, 4096)
    cache = jax.eval_shape(lambda: model.init_cache(1, 4096, jnp.bfloat16))
    assert model.cache_bytes_by_kind(cache) == {
        "kv_full": 16_777_216, "kda_state": 12_582_912,
        "conv_state": 442_368}
    assert cache["1"]["s"].dtype == jnp.float32


def test_cli_builds_the_presets():
    from bigdl_tpu.cli.perf import build_model
    model, size = build_model("hybrid_moe_lm")
    assert isinstance(model, models.HybridMoELM) and size == (512,)
    model, size = build_model("solar_open2")
    assert model.ffns[0].held == 40 and model.ffns[0].num_experts == 320
    assert model.vocab == 24576 and size == (4096,)


def test_serve_cli_builds_it_and_refuses_the_unsupported_flags():
    from bigdl_tpu.cli import common, serve as serve_cli
    # the class at the zoo's smoke-test sizes; solar_open2 is the same
    # constructor at the published ones
    argv = ["hybrid_moe_lm", "--randomInit", "--seq", "64", "--slots", "2",
            "--buckets", "1"]
    args = serve_cli.build_parser().parse_args(argv)
    common.apply_platform(args)
    app, eng, in_shape, in_dtype = serve_cli.build_app(args)
    try:
        assert isinstance(app.decoder.model, models.HybridMoELM)
        assert in_shape == (64,)
        out = app.decoder.generate([3, 1, 4, 1, 5, 9, 2, 6, 5, 3], 5)
        assert len(out) == 5 and all(0 <= t < 32000 for t in out)
        assert "moe_held_picks_total" in app.metrics.render()
    finally:
        app.close()
    for flag in (["--kvPageTokens", "16"], ["--speculate", "2"],
                 ["--quantize", "int8"]):
        with pytest.raises(SystemExit, match="routed expert stacks"):
            serve_cli.build_app(
                serve_cli.build_parser().parse_args(argv + flag))

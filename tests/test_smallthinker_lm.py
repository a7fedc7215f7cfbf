"""``HybridMoELM`` under its second configuration (SmallThinker-21BA3B's
architecture: window and full GQA by two layout lists, an early
softmax-top-k router, ReGLU experts) at toy sizes on the CPU, held to its
plain reference (``benchmark/references/smallthinker.py``): the whole
forward, prefill + decode through ``DecodeEngine``'s rings with a prompt
longer than the window in a padded bucket, the windowed flash kernel in
interpret mode against the masked softmax, the router's two readings, and
what the engine refuses each of the four models."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import models, nn
from bigdl_tpu.nn.attention import dot_product_attention
from bigdl_tpu.ops import cache_write as cw
from bigdl_tpu.ops.attention_kernel import (_live_block_pairs, _pair_table,
                                            band_mask, flash_attention)
from bigdl_tpu.serving import DecodeEngine, MetricsRegistry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "reference_smallthinker",
    os.path.join(ROOT, "benchmark", "references", "smallthinker.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

W = 16  # the toy window: prompts and answers outrun it
ARGS = dict(vocab=128, d_model=64, num_layers=8, num_heads=4,
            num_kv_heads=2, head_dim=16,
            sliding_window_layout=[0, 1, 1, 1] * 2,
            rope_layout=[0, 1, 1, 1] * 2, window=W, rope_theta=1.5e6,
            num_experts=16, experts_held=16, top_k=4, expert_width=32,
            shared_experts=0, router_score="softmax_topk",
            expert_act="relu", router_input="layer_input", rms_eps=1e-6,
            max_len=128, init_std=0.125)
# the error a dtype may leave against the float32 reference (read: 5e-7;
# bf16 0.09, nearly all of it the flips of a router that chooses 4 of 16
# at toy sizes: a window left out reads 0.4 to 1.1 there)
TOL = {"float32": 1e-4, "bfloat16": 0.2}


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def lm(request):
    """(model, weights in the dtype they are served in, tolerance)."""
    dtype = jnp.dtype(request.param)
    model = models.HybridMoELM(compute_dtype=dtype, **ARGS)
    params = jax.tree_util.tree_map(
        lambda a: a.astype(dtype), model.init(jax.random.PRNGKey(3)))
    return model, params, TOL[request.param]


def tokens(n, seed=0):
    return [int(t) for t in
            np.random.RandomState(seed).randint(1, ARGS["vocab"], size=n)]


def rel_err(got, want):
    return float(np.abs(np.asarray(got, np.float32) - want).max()
                 / np.abs(want).max())


# ------------------------------------------------------- the whole forward
def test_full_forward_is_the_reference(lm):
    model, p, tol = lm
    toks = tokens(50)
    want = np.asarray(ref.logits(p, ARGS, toks))
    got = model.logits(p, jnp.asarray([toks]))[0]
    assert rel_err(got, want) < tol
    assert model.kinds == ["global", "window", "window", "window"] * 2
    assert model.window == W and not model.recurrent_state
    assert model.routed_experts and model.experts_held == 16


def test_prefill_then_decode_is_the_references_forward(lm):
    """A prompt of 37 (longer than the window, padded to the bucket of 64,
    so the rings take rows 21..36 and none of the padding) and 36 decode
    steps (the rings wrap twice more): the engine's logits at every step
    are the reference's full forward on the same tokens."""
    model, p, tol = lm
    eng = DecodeEngine(model, p, slots=3, metrics=MetricsRegistry())
    prompt, steps = tokens(37, seed=4), 36
    fut = eng.submit(prompt, steps + 1)
    slot = next(i for i, r in enumerate(eng._reqs) if r is not None)
    got = [np.asarray(eng._logits)[slot]]
    for _ in range(steps):
        eng.step()
        got.append(np.asarray(eng._logits)[slot])
    eng.step()
    out = fut.result(0)
    want = np.asarray(ref.logits(p, ARGS, prompt + out))
    worst = max(rel_err(g, want[len(prompt) - 1 + i])
                * np.abs(want[len(prompt) - 1 + i]).max()
                / np.abs(want).max() for i, g in enumerate(got))
    assert worst < tol
    if tol < 1e-3:  # float32: the greedy tokens are the reference's
        assert out == [int(np.argmax(want[len(prompt) - 1 + i]))
                       for i in range(len(out))]
    assert eng.cache_bytes_by_kind() == {
        "kv_full": 2 * 3 * 2 * 2 * 128 * 16 * p["emb"]["weight"].itemsize,
        "kv_window": 6 * 3 * 2 * 2 * W * 16 * p["emb"]["weight"].itemsize}


def test_engine_counts_rings_windows_and_picks():
    model = models.HybridMoELM(**ARGS)
    p = model.init(jax.random.PRNGKey(3))
    reg = MetricsRegistry()
    eng = DecodeEngine(model, p, slots=2, metrics=reg)
    eng.generate(tokens(40, seed=1), 3)   # bucket 64 > window: counted
    eng.generate(tokens(10, seed=2), 3)   # bucket 16: the causal call
    value = lambda name: reg.counter(name).value
    assert value("prefill_window_tokens_total") == 40
    assert value("prompt_tokens_total") == 50
    # three steps a request; ring rows min(pos, 16) before each step
    assert value("decode_window_positions_total") == 3 * W + 10 + 11 + 12
    assert value("decode_live_positions_total") == 40 + 41 + 42 + 33
    assert value("moe_picks_total") == 6 * 8 * 4
    assert value("moe_held_picks_total") == value("moe_picks_total")
    text = reg.render()
    for kind in ("kv_full", "kv_window"):
        assert f"decode_cache_bytes_{kind}" in text
    assert "decode_cache_bytes_kda_state" not in text


def test_named_scopes_are_in_the_step():
    model = models.HybridMoELM(**ARGS)
    p = model.init(jax.random.PRNGKey(3))
    text = jax.jit(model.decode_logits).lower(
        p, jnp.zeros((1, 1), jnp.int32), model.init_cache(1, 128),
        3).as_text(debug_info=True)
    for scope in ("attn_window", "attn_global", "moe_route", "moe_experts"):
        assert scope in text, scope
    assert "moe_shared" not in text and "attn_gated" not in text


# ------------------------------------------------------ the window kernel
def qkv(s, seed=0, h=2, d=8):
    ks = jax.random.split(jax.random.PRNGKey(seed + s), 3)
    return [jax.random.normal(k, (1, h, s, d)) for k in ks]


@pytest.mark.parametrize("s,bq,bk", [
    (W, 8, 8),               # the band is the triangle
    (W + 1, 8, 8),           # ragged: the dense path under band_mask
    (2 * W + 5, 8, 8),       # ragged, past the window
    (4 * W, 8, 8),           # the band walk, blocks aligned
    (4 * W, 16, 8),          # query blocks wider than key blocks
    (4 * W, 8, 32),          # and narrower
])
def test_window_kernel_is_the_masked_softmax(s, bq, bk):
    q, k, v = qkv(s)
    want = dot_product_attention(q, k, v, mask=band_mask(s, s, W))
    got = flash_attention(q, k, v, causal=True, window=W, block_q=bq,
                          block_k=bk)
    np.testing.assert_allclose(got, want, atol=2e-6)
    # by hand: query i sees keys i-W+1..i
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    assert (np.asarray(band_mask(s, s, W)) == ((j <= i) & (j > i - W))).all()


def test_window_grid_walks_the_band_only():
    """At s = 8 windows of 16 with blocks of 8 a query block's steps are
    the (w + bq) / bk = 3 K blocks of its band, not 16, and the grid and
    the cost count the band's block pairs."""
    s = 8 * W
    qt, kt = _pair_table(16, 16, 8, 8, True, 0, W)
    assert max(np.bincount(qt)) == 3 and (qt - kt).max() == 2
    band = _live_block_pairs(s, s, 8, 8, True, 0, W)
    assert band == 1 + 2 + 3 * 14 == len(qt)
    assert _live_block_pairs(s, s, 8, 8, True, 0) == 16 * 17 // 2
    q, k, v = qkv(s)
    text = str(jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=W, block_q=8, block_k=8))(q, k, v))
    assert "flash_fwd_window" in text and "grid=(2, 45)" in text


def test_window_none_is_todays_call():
    """``window=None`` traces the program it traced before (same name,
    same grid, same text as the call without the argument), and a window
    that covers the sequence gives its numbers bit for bit."""
    q, k, v = qkv(64)
    plain = lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            block_q=8, block_k=8)
    none = lambda q, k, v: flash_attention(q, k, v, causal=True,
                                           window=None, block_q=8,
                                           block_k=8)
    text = str(jax.make_jaxpr(plain)(q, k, v))
    assert text == str(jax.make_jaxpr(none)(q, k, v))
    assert "flash_fwd_window" not in text and "grid=(2, 36)" in text
    wide = flash_attention(q, k, v, causal=True, window=64, block_q=8,
                           block_k=8)
    assert (np.asarray(wide) == np.asarray(plain(q, k, v))).all()


def test_window_has_no_backward():
    q, k, v = qkv(32)
    with pytest.raises(NotImplementedError, match="no window"):
        jax.grad(lambda q: flash_attention(
            q, k, v, causal=True, window=8, block_q=8, block_k=8).sum())(q)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=8)


def test_a_ring_of_several_blocks_is_read_bounded():
    """A window layer at a lane-wide head and a ring of two of the read
    kernel's blocks: under the engine's vmap the row write is batched and
    the read bounded by min(pos + 1, ring)."""
    layer = nn.CausalGQA(64, 2, 1, 128, rope_theta=1e4, window=1024)
    p = layer.init(jax.random.PRNGKey(0))
    cache = layer.init_cache(3, 2048)
    assert cache["k"].shape == (3, 1, 1024, 128)

    def one(x, c, pos):
        c = jax.tree_util.tree_map(lambda a: a[None], c)
        return layer.decode_step(p, x[None], c, pos)[0][0]

    chosen = []
    with cw.step_trace(chosen):
        jax.eval_shape(jax.vmap(one), jnp.zeros((3, 1, 64)), cache,
                       jnp.asarray([5, 1500, 1023]))
    assert chosen == ["batched", "batched", "bounded"]


def test_ring_prefill_takes_the_rows_up_to_last():
    """A bucket of 64 with 37 real rows: ring row r holds the largest
    position <= 36 congruent to r, never a padded row."""
    layer = nn.CausalGQA(64, 2, 1, 16, rope_theta=1e4, window=W)
    p = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 64))
    _, k, _ = layer._attend_seq(p, x)
    _, cache = layer.prefill(p, x, layer.init_cache(1, 128), 36)
    want = [36 - (36 - r) % W for r in range(W)]
    assert sorted(want) == list(range(21, 37))
    np.testing.assert_array_equal(cache["k"][0, 0], k[0, 0, want])


# ------------------------------------------------------------- the router
def router(**kw):
    layer = nn.RoutedFFN(64, 32, 64, 6, score="softmax_topk", act="relu",
                         init_std=0.125, **kw)
    return layer, layer.init(jax.random.PRNGKey(0))


def test_topk_then_softmax_is_the_softmax_renormalised():
    layer, p = router()
    assert "bias" not in p["router"] and "shared_w13" not in p
    x = jax.random.normal(jax.random.PRNGKey(1), (200, 64))
    idx, w = layer.route(p, x)
    full = jax.nn.softmax(x @ p["router"]["weight"], axis=-1)
    chosen = jnp.take_along_axis(full, idx, axis=-1)
    np.testing.assert_allclose(
        w, chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-6)
    top = np.argsort(-np.asarray(full), axis=-1)[:, :6]
    assert (np.sort(top, -1) == np.sort(np.asarray(idx), -1)).all()


def test_experts_are_reglu_and_the_router_reads_what_it_is_given():
    layer, p = router()
    x = jax.random.normal(jax.random.PRNGKey(1), (30, 64))
    early = jax.random.normal(jax.random.PRNGKey(2), (30, 64))
    out, picked = layer.forward(p, x, early)
    idx, w = layer.route(p, early)
    want = np.zeros((30, 64), np.float32)
    for t in range(30):
        for j in range(6):
            gu = x[t] @ p["w13"][int(idx[t, j])]
            want[t] += float(w[t, j]) * np.asarray(
                (jnp.maximum(gu[:32], 0.0) * gu[32:])
                @ p["w2"][int(idx[t, j])])
    np.testing.assert_allclose(out, want, atol=1e-4)
    bits = [sum(1 << int(e) for e in row) for row in np.asarray(idx)]
    assert [int(lo) | int(hi) << 32 for lo, hi in picked.tolist()] == bits
    # without router_x it reads x: another choice
    assert not (np.asarray(layer.forward(p, x)[1]) == np.asarray(
        picked)).all()


def test_the_models_router_reads_the_layers_input():
    """A planted router "after attention" (it reads RMSNorm2's output, as
    an ordinary MoE layer's does) is another model."""
    toks = jnp.asarray([tokens(40)])
    early = models.HybridMoELM(**ARGS)
    late = models.HybridMoELM(**dict(ARGS, router_input="ffn_norm"))
    p = early.init(jax.random.PRNGKey(3))
    want = np.asarray(ref.logits(p, ARGS, toks[0]))
    assert rel_err(early.logits(p, toks)[0], want) < 1e-4
    assert rel_err(late.logits(p, toks)[0], want) > 0.05


def test_long_prompts_walk_the_experts_in_row_blocks(monkeypatch):
    """Past ``ROW_BLOCK`` tokens the grouped matmuls take the prompt a
    block at a time: the same numbers."""
    from bigdl_tpu.nn import moe
    layer, p = router()
    x = jax.random.normal(jax.random.PRNGKey(1), (96, 64))
    whole = layer.forward(p, x)[0]
    calls = lambda: str(jax.make_jaxpr(
        lambda x: layer.forward(p, x)[0])(x)).count("pallas_call")
    one = calls()
    monkeypatch.setattr(moe, "ROW_BLOCK", 32)
    np.testing.assert_allclose(layer.forward(p, x)[0], whole, atol=1e-6)
    assert calls() == one  # the same two products, inside one loop


# ------------------------------------------------- published widths, CLI
def test_published_widths_by_eval_shape():
    """``smallthinker``: the issue's table at the published widths,
    nothing allocated: 3.967B parameters, a slot of 67.1 + 50.3 MB."""
    model = models.smallthinker()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n = sum(v.size for v in jax.tree_util.tree_leaves(params))
    assert n == 3_966_937_600
    layer = params["layers"]["1"]
    assert layer["ffn"]["w13"].shape == (64, 2560, 1536)
    assert layer["ffn"]["w2"].shape == (64, 768, 2560)
    assert set(layer["ffn"]) == {"router", "w13", "w2"}
    assert set(layer["ffn"]["router"]) == {"weight"}
    assert set(layer["mixer"]) == {"wq", "wk", "wv", "wo"}
    assert layer["mixer"]["wq"].shape == (2560, 3584)
    assert params["head"]["weight"].shape == (151936, 2560)
    cache = jax.eval_shape(lambda: model.init_cache(1, 16384, jnp.bfloat16))
    assert cache["0"]["k"].shape == (1, 4, 16384, 128)
    assert cache["1"]["k"].shape == (1, 4, 4096, 128)
    assert model.cache_bytes_by_kind(cache) == {
        "kv_full": 67_108_864, "kv_window": 50_331_648}
    assert [m.rope_theta for m in model.mixers[:4]] == [None] + [1.5e6] * 3
    assert model.window == 4096 and model.experts_held == 64
    assert model.num_experts == 64 and model.share == 0


def test_cli_builds_the_preset_and_serve_refuses_by_name(monkeypatch):
    from bigdl_tpu.cli import common, perf, serve as serve_cli
    model, size = perf.build_model("smallthinker")
    assert isinstance(model, models.HybridMoELM) and size == (16384,)
    assert model.kinds[:4] == ["global", "window", "window", "window"]
    # `serve` with the toy model in the preset's place: it generates past
    # the window, and the five flags are refused by what the slots hold
    monkeypatch.setattr(perf, "build_model", lambda name, **kw: (
        models.HybridMoELM(**ARGS), (64,)))
    argv = ["smallthinker", "--randomInit", "--seq", "64", "--slots", "2",
            "--buckets", "1"]
    args = serve_cli.build_parser().parse_args(argv)
    common.apply_platform(args)
    app, _, _, _ = serve_cli.build_app(args)
    try:
        assert len(app.decoder.generate(tokens(20), 20)) == 20
        assert "prefill_window_tokens_total" in app.metrics.render()
    finally:
        app.close()
    for flag in (["--kvPageTokens", "16"], ["--speculate", "2"],
                 ["--quantize", "int8"]):
        with pytest.raises(SystemExit, match="keeps window rings in its "
                           "decode slots and routed expert stacks"):
            serve_cli.build_app(
                serve_cli.build_parser().parse_args(argv + flag))


# ------------------------------------------------ what the engine refuses
SOLAR = dict(vocab=128, d_model=64, num_layers=4, num_heads=4,
             num_kv_heads=2, head_dim=16, gate_rank=16, num_experts=16,
             experts_held=4, share=1, top_k=4, expert_width=32, max_len=128)
ASKED = {"kv_page_tokens": {"kv_page_tokens": 16},
         "prefix_cache": {"kv_page_tokens": 16, "prefix_cache": True},
         "speculate": {"speculate": 2}, "quantize": {"quantize": "int8"},
         "mesh": {"mesh": object()}}
# model -> (how the message begins, {option: its reason, word for word})
REFUSED = {
    "sambay": (
        "SambaYLM keeps recurrent state in its slots and serves on the "
        "dense path only; not supported yet: ", {
            "kv_page_tokens": "kv_page_tokens: page pools hold per-layer "
                              "K/V rows only (serving/kv_pages.py)",
            "prefix_cache": "prefix_cache: a shared prefix is a page copy, "
                            "and the state after the prefix is in no page "
                            "(serving/prefix_cache.py)",
            "speculate": "speculate: a rejected draft token cannot be "
                         "taken out of a scan state "
                         "(serving/spec_decode.py)",
            "quantize": "quantize: no 8-bit form of the state-space "
                        "weights or of the state (serving/quant.py)",
            "mesh": "mesh: no tp layout for the scan "
                    "(serving/sharding.py)"}),
    "solar": (
        "HybridMoELM keeps recurrent state in its slots and routed expert "
        "stacks in its layers and serves on the dense path only; not "
        "supported yet: ", {
            "kv_page_tokens": "kv_page_tokens: page pools hold per-layer "
                              "K/V rows only (serving/kv_pages.py)",
            "prefix_cache": "prefix_cache: a shared prefix is a page copy, "
                            "and the state after the prefix is in no page "
                            "(serving/prefix_cache.py)",
            "speculate": "speculate: a rejected draft token cannot be "
                         "taken out of a scan state "
                         "(serving/spec_decode.py)",
            "quantize": "quantize: no 8-bit form of the state-space "
                        "weights or of the state, nor of the routed expert "
                        "stack (serving/quant.py)",
            "mesh": "mesh: no tp layout for the scan, nor for the routed "
                    "expert stack and its exchange "
                    "(serving/sharding.py)"}),
    "smallthinker": (
        "HybridMoELM keeps window rings in its slots and routed expert "
        "stacks in its layers and serves on the dense path only; not "
        "supported yet: ", {
            "kv_page_tokens": "kv_page_tokens: a ring's rows are in no "
                              "page: page pools hold max_len K/V rows a "
                              "layer (serving/kv_pages.py)",
            "prefix_cache": "prefix_cache: a shared prefix is a page copy, "
                            "and a ring's rows are in no page "
                            "(serving/prefix_cache.py)",
            "speculate": "speculate: a rejected draft token has "
                         "overwritten the row of position pos - window in "
                         "every ring (serving/spec_decode.py)",
            "quantize": "quantize: no 8-bit form of the routed expert "
                        "stack (serving/quant.py)",
            "mesh": "mesh: no tp layout for the routed expert stack and "
                    "its exchange (serving/sharding.py)"}),
}


@pytest.fixture(scope="module")
def four_models():
    built = {
        "transformer": models.transformer_lm(128, d_model=32, num_layers=1,
                                             num_heads=2, max_len=64),
        "sambay": models.sambay_lm(128, d_model=32, num_layers=8,
                                   num_heads=4, num_kv_heads=2, d_ff=64,
                                   window=16, max_len=64),
        "solar": models.HybridMoELM(**SOLAR),
        "smallthinker": models.HybridMoELM(**ARGS)}
    return {k: (m, m.init(jax.random.PRNGKey(0))) for k, m in built.items()}


@pytest.mark.parametrize("option", sorted(ASKED))
@pytest.mark.parametrize("name", sorted(REFUSED))
def test_engine_refuses_by_what_the_model_declares(four_models, name,
                                                   option):
    model, p = four_models[name]
    head, reasons = REFUSED[name]
    with pytest.raises(ValueError) as e:
        DecodeEngine(model, p, slots=2, **ASKED[option])
    on = [o for o in reasons if o in ASKED[option]]
    assert str(e.value) == head + "; ".join(reasons[o] for o in on)


def test_engine_refuses_the_transformer_nothing(four_models):
    from bigdl_tpu.serving.decode import _dense_path_only
    model, p = four_models["transformer"]
    assert _dense_path_only(model, **{o: True for o in ASKED}) is None
    for kw in (ASKED["kv_page_tokens"], ASKED["prefix_cache"],
               ASKED["speculate"], ASKED["quantize"]):
        DecodeEngine(model, p, slots=2, **kw)

"""``HybridMoELM`` under its fourth configuration (Command A+'s
architecture, ``cohere2_moe``: parallel attention and FFN blocks under one
bias-free LayerNorm, GPT-J-rotated window layers before a NoPE global layer,
a bias-free sigmoid router over held experts beside averaged shared experts,
a tied head) at toy sizes on the CPU, held to its plain reference
(``benchmark/references/command_a_plus.py``): the whole forward, prefill +
decode through ``DecodeEngine``'s rings past the window, eight shares of
the routed layer against the uncut one, the rotation, the norm, the tied
head, the engine's counters, the published widths and the CLI. One model
and one engine are built for the module."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import models, nn
from bigdl_tpu.serving import DecodeEngine, MetricsRegistry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "reference_command_a_plus",
    os.path.join(ROOT, "benchmark", "references", "command_a_plus.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

W = 16  # the toy window: prompts and answers outrun it
ARGS = dict(vocab=128, d_model=64, num_layers=4, num_heads=8,
            num_kv_heads=2, head_dim=16, sliding_window_layout=[1, 1, 1, 0],
            rope_layout=[1, 1, 1, 0], window=W, rope_theta=5e4,
            rope_pairing="interleaved", num_experts=32, experts_held=4,
            share=1, top_k=8, expert_width=32, shared_experts=4,
            shared_combine="mean", selection_bias=False, block="parallel",
            norm="layer", tie_embeddings=True, rms_eps=1e-5, max_len=128,
            init_std=0.125)
# float32 against the float32 reference: 3e-7 read; bf16 reads 1e-2 and
# more (rounding and the router's flips), so bf16 fails this tolerance
TOL = 1e-4


def tokens(n, seed=0):
    return [int(t) for t in
            np.random.RandomState(seed).randint(1, ARGS["vocab"], size=n)]


def rel_err(got, want):
    return float(np.abs(np.asarray(got, np.float32) - want).max()
                 / np.abs(want).max())


@pytest.fixture(scope="module")
def lm():
    model = models.HybridMoELM(**ARGS)
    return model, model.init(jax.random.PRNGKey(3))


@pytest.fixture(scope="module")
def served(lm):
    """An engine with a registry, a prompt of 37 (bucket 64, the rings
    taking rows 21..36) and 36 decode steps (the rings wrap twice more),
    and the reference's full forward of the 73 tokens: (prompt, tokens
    out, the engine's logits at each step, registry, engine, reference
    logits)."""
    model, p = lm
    reg = MetricsRegistry()
    eng = DecodeEngine(model, p, slots=3, metrics=reg)
    prompt, steps = tokens(37, seed=4), 36
    fut = eng.submit(prompt, steps + 1)
    slot = next(i for i, r in enumerate(eng._reqs) if r is not None)
    got = [np.asarray(eng._logits)[slot]]
    for _ in range(steps):
        eng.step()
        got.append(np.asarray(eng._logits)[slot])
    eng.step()
    out = fut.result(0)
    want = np.asarray(ref.logits(p, ARGS, prompt + out[:steps]))
    return prompt, out, got, reg, eng, want


# ------------------------------------------------------- the whole forward
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_full_forward_is_the_reference(lm, served, dtype):
    model, p = lm
    prompt, out, _, _, _, want = served
    toks = jnp.asarray([prompt + out[:-1]])
    if dtype == "float32":
        assert rel_err(jax.jit(model.logits)(p, toks)[0], want) < TOL
        assert model.kinds == ["window", "window", "window", "global"]
        assert model.window == W and model.parallel
        assert [m.rope_theta for m in model.mixers] == [5e4] * 3 + [None]
        return
    # the configuration's lower precision: what the tolerance rules out
    low = models.HybridMoELM(compute_dtype=jnp.bfloat16, **ARGS)
    p16 = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), p)
    assert rel_err(jax.jit(low.logits)(p16, toks)[0], want) > 10 * TOL


def test_prefill_then_decode_is_the_references_forward(served):
    """The engine's logits at the prefill and every decode step are the
    reference's full forward on the same tokens, and the greedy tokens
    its argmax."""
    prompt, out, got, _, _, want = served
    rows = want[len(prompt) - 1:]
    assert rel_err(np.stack(got), rows) < TOL
    assert out == [int(np.argmax(r)) for r in rows]


def test_engine_counts_rings_windows_and_picks(served):
    _, _, _, reg, eng, _ = served
    value = lambda name: reg.counter(name).value
    assert value("prefill_window_tokens_total") == 37  # bucket 64 > 16
    # a step a token (the first samples the prefill's logits); before
    # each, ring rows min(pos, 16) and live positions pos
    steps = 37
    assert value("decode_window_positions_total") == steps * W
    assert value("decode_live_positions_total") == sum(
        range(37, 37 + steps))
    assert value("moe_picks_total") == steps * 8 * 4
    assert 0 < value("moe_held_picks_total") < value("moe_picks_total")
    assert 0 < value("moe_experts_touched_total") <= steps * 4 * 4
    assert eng.cache_bytes_by_kind() == {
        "kv_full": 3 * 2 * 2 * 128 * 16 * 4,
        "kv_window": 3 * 3 * 2 * 2 * W * 16 * 4}


def test_named_scopes_are_in_the_step(lm):
    model, p = lm
    text = jax.jit(model.decode_logits).lower(
        p, jnp.zeros((1, 1), jnp.int32), model.init_cache(1, 128),
        3).as_text(debug_info=True)
    for scope in ("attn_window", "attn_global", "moe_route", "moe_experts",
                  "moe_shared"):
        assert scope in text, scope


# ------------------------------------------------------------ the pieces
def test_eight_shares_and_the_shared_experts_once_are_the_layer():
    """Eight chips' shares of a routed layer (4 of 32 experts each, the
    shared experts left to one of them) add up to the uncut reference
    layer: routed experts over all 32 plus the four shared averaged."""
    kw = dict(num_experts=32, top_k=8, init_std=0.125,
              selection_bias=False)
    whole = nn.RoutedFFN(64, 32, shared_width=4 * 32, shared_scale=0.25,
                         **kw)
    p = whole.init(jax.random.PRNGKey(0))
    assert set(p["router"]) == {"weight"}
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 64))

    @jax.jit
    def shares(p, x):
        out = nn.moe.glu(x, p["shared_w13"], p["shared_w2"]) / 4
        for share in range(8):
            part = nn.RoutedFFN(64, 32, held=4, share=share, **kw)
            held = {"router": p["router"],
                    "w13": p["w13"][4 * share:4 * share + 4],
                    "w2": p["w2"][4 * share:4 * share + 4]}
            out = out + part.forward(held, x)[0]
        return out

    chosen, w = ref._route(p["router"], x, 8)
    want = (ref._routed(p, x, chosen, w, 0) + ref._shared(p, x, 4))
    np.testing.assert_allclose(shares(p, x), want, atol=1e-5)
    np.testing.assert_allclose(jax.jit(whole.forward)(p, x)[0], want,
                               atol=1e-5)


def test_the_rotation_turns_gpt_j_pairs():
    layer = nn.CausalGQA(64, 2, 1, 8, rope_theta=100.0,
                         rope_pairing="interleaved")
    x = np.random.RandomState(0).randn(1, 2, 5, 8).astype(np.float32)
    want = x.copy()
    for p in range(5):
        for i in range(4):
            a = p * 100.0 ** (-2 * i / 8)
            c, s = np.cos(a), np.sin(a)
            e, o = x[..., p, 2 * i], x[..., p, 2 * i + 1]
            want[..., p, 2 * i] = e * c - o * s
            want[..., p, 2 * i + 1] = o * c + e * s
    got = layer._rotate(jnp.asarray(x), jnp.arange(5))
    np.testing.assert_allclose(got, want, atol=1e-5)
    half = nn.CausalGQA(64, 2, 1, 8, rope_theta=100.0)
    assert not np.allclose(half._rotate(jnp.asarray(x), jnp.arange(5)),
                           want, atol=1e-3)
    with pytest.raises(ValueError, match="rope_pairing"):
        nn.CausalGQA(64, 2, 1, 8, rope_pairing="neox")


def test_layer_norm_without_bias():
    norm = nn.LayerNorm(16, eps=1e-5, bias=False)
    p = {"weight": jnp.linspace(0.5, 1.5, 16)}
    assert set(norm.init(None)) == {"weight"}
    x = np.random.RandomState(0).randn(3, 16).astype(np.float32) * 3 + 2
    want = ((x - x.mean(-1, keepdims=True))
            / np.sqrt(x.var(-1, keepdims=True) + 1e-5) * np.asarray(
                p["weight"]))
    np.testing.assert_allclose(norm.forward(p, jnp.asarray(x)), want,
                               atol=1e-5)


def test_the_head_is_the_embeddings_rows(lm):
    """Tied: no head leaf; the logits are LayerNorm_f(h) times the held
    embedding rows, so a row changed in the embedding moves its logit."""
    model, p = lm
    assert set(p) == {"emb", "layers", "ln_f"}
    assert set(p["layers"]["0"]) == {"ln1", "mixer", "ffn"}
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 3, 64))
    want = model.ln_f.forward(p["ln_f"], h) @ p["emb"]["weight"].T
    np.testing.assert_allclose(model._logits(p, h), want, atol=1e-6)
    moved = dict(p, emb={"weight": p["emb"]["weight"].at[5].add(
        jnp.linspace(-1.0, 1.0, 64))})
    diff = np.abs(np.asarray(model._logits(moved, h) - want)).max(axis=1)
    assert diff[0, 5] > 0.1 and np.delete(diff[0], 5).max() < 1e-6


# ------------------------------------------------- published widths, CLI
def test_published_widths_by_eval_shape():
    """``command_a_plus``: the issue's table at the published widths,
    nothing allocated: 4,733,292,544 parameters, a slot of 37.7 + 50.3
    MB."""
    model = models.command_a_plus()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(v.size for v in jax.tree_util.tree_leaves(params)) == (
        4_733_292_544)
    layer = params["layers"]["0"]
    assert layer["mixer"]["wq"].shape == (4096, 16384)
    assert layer["mixer"]["wk"].shape == (4096, 1024)
    assert layer["ffn"]["w13"].shape == (16, 4096, 8192)
    assert layer["ffn"]["shared_w13"].shape == (4096, 32768)
    assert layer["ffn"]["router"]["weight"].shape == (4096, 128)
    assert params["emb"]["weight"].shape == (32768, 4096)
    cache = jax.eval_shape(lambda: model.init_cache(1, 9216, jnp.bfloat16))
    assert model.cache_bytes_by_kind(cache) == {
        "kv_full": 37_748_736, "kv_window": 50_331_648}
    assert model.kinds == ["window"] * 3 + ["global"]
    assert 8192 in model.prompt_buckets(9216, jnp.bfloat16)


def test_cli_builds_the_preset_and_serves(monkeypatch):
    from bigdl_tpu.cli import common, perf, serve as serve_cli
    model, size = perf.build_model("command_a_plus")
    assert isinstance(model, models.HybridMoELM) and size == (9216,)
    assert model.parallel and model.tie_embeddings
    monkeypatch.setattr(perf, "build_model", lambda name, **kw: (
        models.HybridMoELM(**ARGS), (64,)))
    argv = ["command_a_plus", "--randomInit", "--seq", "64", "--slots",
            "2", "--buckets", "1"]
    args = serve_cli.build_parser().parse_args(argv)
    common.apply_platform(args)
    app, _, _, _ = serve_cli.build_app(args)
    try:
        assert len(app.decoder.generate(tokens(20), 20)) == 20
    finally:
        app.close()
    with pytest.raises(SystemExit, match="keeps window rings in its "
                       "decode slots and routed expert stacks"):
        serve_cli.build_app(serve_cli.build_parser().parse_args(
            argv + ["--speculate", "2"]))

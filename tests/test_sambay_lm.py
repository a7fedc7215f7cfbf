"""SambaYLM against its plain reference, in logits, on seeded weights:
the whole forward, and prefill + decode through ``DecodeEngine``'s dense
path with a window of 8 (so the ring wraps) and a prompt longer than it.
Once at the model's own initialisation, where every mixer carries weight,
and once through the benchmark's ``seeded_params`` (N(0, 0.02)), where
the Mamba and GMU paths all but vanish: the planted faults therefore have
to fail at the model's own. Float32 on the CPU."""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.models import SambaYLM, TransformerLM
from bigdl_tpu.serving import DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmark.lib.model import load_reference, seeded_params  # noqa: E402

MARGS = dict(vocab=96, d_model=64, num_layers=8, num_heads=4,
             num_kv_heads=2, d_ff=128, window=8, mb_per_layer=2,
             max_len=64)
# The published initializer_range, 0.02, is 1 / sqrt(2,560): a matrix
# product keeps its input's scale at the published width. At this toy
# width the same rule gives 1 / sqrt(64), and every mixer carries weight as
# it does in the published model; at 0.02 the scan's state would be a
# thousandth of the skip path here and no fault in it could show.
OWN_STD = 0.125
# float32 program against float32 reference: the chunked scan, the banded
# window, the 128-wide pairs and the cache differ from the reference in
# the order of sums only. Largest error seen over the reference's largest
# logit, any case below: 1.8e-6. The planted faults read 0.2 to 0.9, but
# the state rounded to bfloat16, which reads 6e-4: thirty times TOL.
TOL = 2e-5
REF = load_reference({"reference": "phi4_mini_flash"})
TOKENS = np.random.RandomState(0).randint(1, 96, size=40).tolist()


def own_init(model):
    return model.init(jax.random.PRNGKey(1))


def benchmark_init(model):
    return seeded_params(model, 2 ** 31 + 11, jnp.float32)


WEIGHTS = pytest.mark.parametrize("weights", [own_init, benchmark_init])


def build():
    return SambaYLM(init_std=OWN_STD, **MARGS)


@pytest.fixture(scope="module")
def model():
    return build()


def rel_err(got, want):
    return float(np.abs(np.asarray(got) - np.asarray(want)).max()
                 / np.abs(np.asarray(want)).max())


def through_engine(model, params, prompt, steps, **kw):
    """Logits of the slot after the prefill and after each decode step,
    and the tokens the engine drew: ``benchmark/lib/serve.py``'s check."""
    eng = DecodeEngine(model, params, slots=3, **kw)
    fut = eng.submit(prompt, steps + 1)
    slot = next(i for i, r in enumerate(eng._reqs) if r is not None)
    got = [np.asarray(eng._logits)[slot]]
    for _ in range(steps):
        eng.step()
        got.append(np.asarray(eng._logits)[slot])
    eng.step()
    return np.stack(got), fut.result(0)


def engine_error(model, params, prompt, steps=8, **kw):
    got, out = through_engine(model, params, prompt, steps, **kw)
    want = REF.logits(params, MARGS, prompt + out[:steps])
    return rel_err(got, want[len(prompt) - 1:])


def test_layer_pattern_and_sizes(model):
    assert model.kinds == ["mamba", "window", "mamba", "window", "mamba",
                           "full", "gmu", "cross"]
    assert (model.memory_layer, model.shared_layer) == (4, 5)
    p = own_init(model)
    assert set(p["layers"]["6"]["mixer"]) == {"w1", "w2"}
    assert "wk" not in p["layers"]["7"]["mixer"]
    assert p["layers"]["0"]["w1"].shape == (64, 256)  # SwiGLU: [g, u]
    assert all("b1" not in p["layers"][str(l)] for l in range(8))
    with pytest.raises(ValueError, match="multiple of 4"):
        SambaYLM(**dict(MARGS, num_layers=6))
    with pytest.raises(ValueError, match="mb_per_layer"):
        SambaYLM(**dict(MARGS, mb_per_layer=3))


@WEIGHTS
def test_whole_forward_is_the_reference(model, weights):
    params = weights(model)
    got = model.logits(params, jnp.asarray([TOKENS]))[0]
    assert rel_err(got, REF.logits(params, MARGS, TOKENS)) < TOL
    logp, _ = model.apply(params, (), jnp.asarray([TOKENS]))
    np.testing.assert_allclose(jnp.exp(logp).sum(-1), 1.0, rtol=1e-5)


@WEIGHTS
def test_engine_prefill_and_eight_steps_are_the_reference(model, weights):
    # 21 tokens: past the window of 8, padded to the bucket of 32
    assert engine_error(model, weights(model), TOKENS[:21]) < TOL


@pytest.mark.parametrize("prompt", [3, 8, 16, 17, 32])
def test_padded_bucket_is_the_exact_bucket(model, prompt):
    """Prompts at and between buckets (16, 32, 64): the padding leaves no
    trace in the logits of the prefill or of the steps after it."""
    assert engine_error(model, own_init(model), TOKENS[:prompt], 4) < TOL


def test_two_slots_at_different_depths_and_a_reused_slot(model):
    """One step advances a slot deep past the window and one still inside
    it; then a third request takes over the finished first slot and must
    see nothing of it."""
    params = own_init(model)
    eng = DecodeEngine(model, params, slots=2)
    first, second, third = TOKENS[:30], TOKENS[30:35], TOKENS[5:19]
    f1 = eng.submit(first, 4)
    f2 = eng.submit(second, 12)
    f3 = eng.submit(third, 5)  # waits for a slot
    seen = {}
    while not (f1.done() and f2.done() and f3.done()):
        for slot, req in enumerate(eng._reqs):
            if req is not None:
                seen.setdefault(id(req), (req, []))[1].append(
                    np.asarray(eng._logits)[slot])
        eng.step()
    assert len(seen) == 3
    for req, rows in seen.values():
        n = len(req.tokens)
        want = REF.logits(params, MARGS, req.tokens + req.out[:len(rows) - 1])
        assert rel_err(np.stack(rows), want[n - 1:]) < TOL
    reused = [req for req, _ in seen.values() if req.tokens == third]
    assert reused and len(reused[0].out) == 5


# ------------------------------------------------------------ planted faults
def zero_carry(monkeypatch, model):
    from bigdl_tpu.nn import ssm

    def chunks_alone(x, dt, a, b, c, s0, chunk=64):
        ys = [ssm.selective_scan_seq(*(v[:, i:i + chunk] for v in (x, dt)),
                                     a, *(v[:, i:i + chunk] for v in (b, c)),
                                     jnp.zeros_like(s0))
              for i in range(0, x.shape[1], chunk)]
        return jnp.concatenate([y for y, _ in ys], 1), ys[-1][1]

    monkeypatch.setattr(ssm, "selective_scan", chunks_alone)
    monkeypatch.setattr(
        ssm, "_scan_step", lambda s, *row, step=ssm._scan_step: step(
            jnp.zeros_like(s), *row))
    for m in model.mixers:
        if isinstance(m, nn.Mamba):
            monkeypatch.setattr(m, "chunk", 8)


def ignore_window(monkeypatch, model):
    for m in model.mixers:
        if getattr(m, "window", None):
            monkeypatch.setattr(m, "window", None)


def drop_lam0(monkeypatch, model):
    for m in model.mixers:
        if isinstance(m, nn.DifferentialAttention):
            monkeypatch.setattr(m, "lam0", 0.0)


def conv_from_padded_tail(monkeypatch, model):
    healthy = nn.Mamba.prefill

    def prefill(self, params, u, cache, last=None):
        out, y, new = healthy(self, params, u, cache, last)
        tail = healthy(self, params, u, cache)[2]["conv"]
        return out, y, dict(new, conv=tail)

    monkeypatch.setattr(nn.Mamba, "prefill", prefill)


def bf16_state(monkeypatch, model):
    def rounded(fn):
        def call(*a, **kw):
            *out, cache = fn(*a, **kw)
            h = cache["h"].astype(jnp.bfloat16).astype(jnp.float32)
            return (*out, dict(cache, h=h))
        return call

    for name in ("prefill", "decode_step"):
        monkeypatch.setattr(nn.Mamba, name, rounded(getattr(nn.Mamba,
                                                            name)))


FAULTS = [zero_carry, ignore_window, drop_lam0, conv_from_padded_tail,
          bf16_state]


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
def test_planted_fault_fails_at_the_models_own_initialisation(
        monkeypatch, fault):
    model = build()  # its own: the faults patch its mixers
    fault(monkeypatch, model)
    err = engine_error(model, own_init(model), TOKENS[:21])
    assert err > 10 * TOL, f"{fault.__name__}: {err:.2e}"


def test_what_the_benchmarks_weights_cannot_see(monkeypatch):
    """Why ``serve_reason_batch`` does not draw by ``seeded_params``
    (PERF.md section 7): under N(0, 0.02) on every leaf, with the
    recurrence's carry zeroed in prefill and decode, the logits stay
    inside any tolerance bfloat16 allows; at the model's own
    initialisation, which the cell draws by, the same fault is far
    outside it."""
    model = build()
    zero_carry(monkeypatch, model)
    assert engine_error(model, benchmark_init(model), TOKENS[:21]) < 0.03
    assert engine_error(model, own_init(model), TOKENS[:21]) > 0.03


# ----------------------------------------------------- the engine's contract
@pytest.mark.parametrize("mode,needs", [
    (dict(kv_page_tokens=16), "kv_page_tokens"),
    (dict(kv_page_tokens=16, prefix_cache=True), "prefix_cache"),
    (dict(speculate=2), "speculate"),
    (dict(quantize="int8"), "quantize"),
    (dict(quantize="kv8", kv_page_tokens=16), "quantize"),
    (dict(mesh=object()), "mesh"),
])
def test_engine_refuses_what_recurrent_state_cannot_do_yet(model, mode,
                                                           needs):
    with pytest.raises(ValueError, match="recurrent state") as e:
        DecodeEngine(model, None, slots=2, **mode)
    assert needs + ":" in str(e.value)


def test_slot_contents_counters_and_snapshot(model):
    from bigdl_tpu.serving import MetricsRegistry
    reg = MetricsRegistry()
    eng = DecodeEngine(model, own_init(model), slots=2, metrics=reg,
                       cache_dtype=jnp.bfloat16)
    kinds = eng.cache_bytes_by_kind()
    # 2 slots: shared layer 64 rows, 2 rings of 8, 3 states and histories
    assert kinds == {"kv_full": 2 * 2 * 64 * 64, "kv_window": 2 * 2 * 2 * 8
                     * 64, "ssm_state": 2 * 3 * 16 * 128 * 4,
                     "conv_state": 2 * 3 * 3 * 128 * 2}
    assert sum(kinds.values()) == eng.kv_bytes()
    assert eng.debug_snapshot()["kv"]["bytes_by_kind"] == kinds
    assert reg.gauge("decode_cache_bytes_ssm_state").value == \
        kinds["ssm_state"]
    eng.generate(TOKENS[:5], 7)  # steps at positions 5 .. 11
    assert reg.counter("decode_live_positions_total").value == sum(
        range(5, 12))
    assert reg.counter("decode_window_positions_total").value == (
        5 + 6 + 7 + 8 * 4)


def test_transformer_lm_keeps_its_buckets_and_cache():
    """The engine now asks the model; for TransformerLM the answers are
    what the engine used to work out itself, and no window is counted."""
    from bigdl_tpu.ops.attention_kernel import serving_prefill_buckets
    from bigdl_tpu.serving import MetricsRegistry
    lm = TransformerLM(50, d_model=32, num_layers=2, num_heads=2,
                       max_len=64, num_kv_heads=1, pos_encoding="rope")
    reg = MetricsRegistry()
    eng = DecodeEngine(lm, lm.init(jax.random.PRNGKey(0)), slots=3,
                       metrics=reg)
    assert eng.prompt_buckets == serving_prefill_buckets(
        64, 16, True, jnp.float32) == (16, 32, 64)
    shapes = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), eng._cache)
    assert shapes == jax.tree_util.tree_map(
        lambda a: (a.shape, a.dtype),
        lm.encoder.init_cache(3, 64, jnp.float32))
    assert shapes["0"]["k"] == ((3, 1, 64, 16), jnp.float32)
    assert eng.cache_bytes_by_kind() == {"kv_full": eng.kv_bytes()}
    eng.generate([1, 2, 3], 4)
    assert reg.counter("decode_window_positions_total").value == 0
    assert reg.counter("decode_live_positions_total").value > 0


def test_serve_cli_builds_it_and_refuses_the_unsupported_flags():
    from bigdl_tpu.cli import common, serve as serve_cli
    # the class at the zoo's smoke-test sizes; phi4_mini_flash is the same
    # constructor at the published ones
    argv = ["sambay_lm", "--randomInit", "--seq", "64", "--slots", "2",
            "--buckets", "1"]
    args = serve_cli.build_parser().parse_args(argv)
    common.apply_platform(args)
    app, eng, in_shape, in_dtype = serve_cli.build_app(args)
    try:
        assert isinstance(app.decoder.model, SambaYLM)
        assert app.decoder.model.window == 64 and in_shape == (64,)
        out = app.decoder.generate([3, 1, 4, 1, 5, 9, 2, 6, 5, 3], 5)
        assert len(out) == 5 and all(0 <= t < 32000 for t in out)
    finally:
        app.close()
    for flag in (["--kvPageTokens", "16"], ["--speculate", "2"],
                 ["--quantize", "int8"]):
        with pytest.raises(SystemExit, match="recurrent state"):
            serve_cli.build_app(
                serve_cli.build_parser().parse_args(argv + flag))

"""The programs every configuration of the benchmark runs, lowered at the
configuration file's rehearsal widths with its own dtype and kernels, are
held to the text they lowered to before the hybrid model gained the options
of a second block form (``block="parallel"``), a norm kind, a tied head, an
interleaved rotation, a bias-free sigmoid router and averaged shared
experts. Each of those options defaults to what the model did before, so a
configuration that does not name one must run the program it ran: a change
to a shared module shows here before it costs a chip run.

The digests are sha256 of ``Lowered.as_text()`` (no debug locations): the
serving configurations' prefill program at their largest prompt bucket and
their greedy decode step, the training configurations' whole step. A change
that means to alter one of these programs updates its digest here and says
why."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")

# program -> sha256 of its lowered text, read before the options existed
DIGESTS = {
    "starcoder2_3b_serve.prefill":
        "5c222b959683636cb6ae79e7b84f5caa81f1d7fa9cb90a772ac581e5e9aad4ea",
    "starcoder2_3b_serve.decode_step":
        "0e422c25c2b515320d9babae55fc565a4646c17ff31523a054c34dddc020ae94",
    "phi4_mini_flash_serve.prefill":
        "468243c2c06aace7075c0a1a95305f636b416e91a4010bc4fd72579efc266f96",
    "phi4_mini_flash_serve.decode_step":
        "9d85e6ca9255ae41894e7b35f1a4e6b737f363457ebcd324bcbc5e139ce1c855",
    "solar_open2_serve.prefill":
        "aa4420ebf012337909160c8e051b0e42e996b7d373757bb5a4e5e90674e7773b",
    "solar_open2_serve.decode_step":
        "69f0c821519ee7778e0ff563c37c2fcbb3d43505bec25b00f36ba48cee6a50f5",
    "smallthinker_21b_serve.prefill":
        "f28e49bb40727737ff11bd68a09d171f985e32c64fb93e196829d2ed193b439b",
    "smallthinker_21b_serve.decode_step":
        "a7d013a7129c31e06ba1d2762c2dd587f405b89db9709fa1e99e6b436f5eae4b",
    "starcoder2_3b_train.train_step":
        "189a78f4796c13a1ceca42f5bbca930ad1f928466beefaa56c8780d86642cbfd",
    "glm47_flash_train.train_step":
        "2d0c32c0be10e4a27612979643b9248fdcd1fc9ff53c1bcd866b348d623f41ea",
}


def _merged(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = (_merged(out[k], v) if isinstance(v, dict)
                  and isinstance(out.get(k), dict) else v)
    return out


def _config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        cfg = json.load(f)
    rehearsal = {k: v for k, v in cfg.get("rehearsal", {}).items()
                 if k != "serve"}  # the chip's dtype, at toy widths
    return _merged(cfg, rehearsal)


def _model(cfg, dtype, remat=False):
    import importlib
    module, _, cls = cfg["builder"].partition(":")
    return getattr(importlib.import_module(module), cls)(
        attn_impl="flash", remat=remat, compute_dtype=dtype, **cfg["model"])


def _sds(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def serve_texts(name):
    """(prefill text, decode-step text) of a serving configuration: the
    engine's own two programs, built on an abstract shell (nothing is
    allocated) at two slots."""
    from bigdl_tpu.serving.decode import DecodeEngine, abstract_decode_engine
    cfg = _config(name)
    dtype = jnp.dtype(cfg["serve"]["dtype"])
    model = _model(cfg, dtype)
    eng = abstract_decode_engine(model, slots=2, cache_dtype=dtype)
    DecodeEngine._build_programs(eng)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, dtype), eng.params)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    bucket = max(model.prompt_buckets(eng.max_len, dtype))
    prefill = eng._prefill_jit.lower(params, i32(1, bucket),
                                     i32()).as_text()
    step = eng._get_step(False).lower(
        params, f32(2, model.vocab), _sds(eng._cache), i32(2), f32(2),
        i32(2), f32(2), jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text()
    return prefill, step


def train_text(name):
    """The whole Optimizer step of a training configuration."""
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import BatchDataSet
    from bigdl_tpu.optim import AdamW, Optimizer
    cfg = _config(name)
    tr = cfg["train"]
    model = _model(cfg, jnp.dtype(tr["compute_dtype"]), tr["remat"])
    seq = cfg["model"]["max_len"]
    x = jnp.zeros((2, seq), jnp.int32)
    crit = (model.criterion() if hasattr(model, "criterion") else
            nn.TimeDistributedCriterion(nn.ClassNLLCriterion()))
    opt = Optimizer(model, BatchDataSet(x, x, 2), crit,
                    optim_method=AdamW(learning_rate=1e-4))
    step, _ = opt._build_step()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return step.lower(
        params, jax.eval_shape(model.init_state),
        jax.eval_shape(opt.optim_method.init, params),
        jax.ShapeDtypeStruct((2, seq), jnp.int32),
        jax.ShapeDtypeStruct((2, seq), jnp.int32),
        jax.eval_shape(lambda: jax.random.PRNGKey(0))).as_text()


SERVING = ["starcoder2_3b_serve", "phi4_mini_flash_serve",
           "solar_open2_serve", "smallthinker_21b_serve"]
TRAINING = ["starcoder2_3b_train", "glm47_flash_train"]


def digests():
    out = {}
    for name in SERVING:
        for prog, text in zip(("prefill", "decode_step"), serve_texts(name)):
            out[f"{name}.{prog}"] = hashlib.sha256(text.encode()).hexdigest()
    for name in TRAINING:
        out[f"{name}.train_step"] = hashlib.sha256(
            train_text(name).encode()).hexdigest()
    return out


@pytest.mark.parametrize("name", SERVING)
def test_serving_programs_lower_as_before(name):
    prefill, step = serve_texts(name)
    for prog, text in (("prefill", prefill), ("decode_step", step)):
        got = hashlib.sha256(text.encode()).hexdigest()
        assert got == DIGESTS[f"{name}.{prog}"], prog


@pytest.mark.parametrize("name", TRAINING)
def test_training_step_lowers_as_before(name):
    got = hashlib.sha256(train_text(name).encode()).hexdigest()
    assert got == DIGESTS[f"{name}.train_step"]


if __name__ == "__main__":
    print(json.dumps(digests(), indent=1))

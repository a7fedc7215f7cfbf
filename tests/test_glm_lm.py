"""GLM-4.7-Flash's block as ``HybridMoELM`` trains it (``mixer="mla"``,
a leading dense layer, routed layers of held experts, the MTP module), on
the CPU at toy sizes in float32, against the plain reference
``benchmark/references/glm4_moe_lite.py``: the latent-attention mixer, both
heads' logits, the loss and the gradient of every leaf; the routed op's
gradient against a dense per-expert computation; the shares of an
expert-parallel layer adding up to the uncut layer; the serving step's
routed op still one grouped product for all slots; the Optimizer's
training counters; the decode engine's refusal."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.models.hybrid_moe_lm import HybridMoELM
from bigdl_tpu.nn.moe import routed_experts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "glm_reference", os.path.join(ROOT, "benchmark", "references",
                                  "glm4_moe_lite.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

ARGS = dict(vocab=96, d_model=64, num_layers=3, num_heads=4, mixer="mla",
            q_lora_rank=24, kv_lora_rank=16, qk_nope_dim=12, qk_rope_dim=4,
            v_head_dim=16, first_k_dense=1, dense_width=80, num_experts=16,
            experts_held=4, share=1, top_k=4, expert_width=32,
            shared_experts=1, routed_scale=1.8, rope_theta=1e6,
            rms_eps=1e-5, mtp_layers=1, mtp_weight=0.3)
S = 24


def seeded(model, seed):
    """N(0, 0.05) on every leaf, norm scales 1 + N(0, 0.05): larger than
    the benchmark's 0.02 so that attention is far from uniform here."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        v = 0.05 * jax.random.normal(jax.random.PRNGKey(seed * 1000 + i),
                                     leaf.shape)
        names = [getattr(p, "key", "") for p in path]
        out.append(1.0 + v if names[-1] == "weight"
                   and names[-2].startswith("ln") else v)
    return jax.tree_util.tree_unflatten(treedef, out)


@pytest.fixture(scope="module")
def glm():
    model = HybridMoELM(**ARGS)
    rs = np.random.RandomState(0)
    toks = rs.randint(0, ARGS["vocab"], (1, S)).astype(np.int32)
    targets = rs.randint(0, ARGS["vocab"], (1, S)).astype(np.int32)
    return model, seeded(model, 1), toks, targets


def rel(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return np.abs(got - want).max() / np.abs(want).max()


def test_latent_attention_mixer_is_the_references(glm):
    model, params, _, _ = glm
    p = params["layers"]["1"]["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, S, 64))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(model.mixers[1].forward)(p, x)
    mla_dims = ref._dims(ARGS)[0]
    for b in range(2):
        want = ref._mla(p, x[b], mla_dims)
        assert rel(got[b], want) < 1e-5
    # the shared rotated key: one rope block for every head
    q, k, v = model.mixers[1].qkv(p, x)
    assert q.shape == k.shape == (2, 4, S, 16) and v.shape == (2, 4, S, 16)
    assert np.allclose(k[:, 0, :, 12:], k[:, 3, :, 12:])


def test_both_heads_are_the_references(glm):
    model, params, toks, _ = glm
    with jax.default_matmul_precision("highest"):
        main, mtp, picked = jax.jit(model.train_logits)(params,
                                                        jnp.asarray(toks))
    want_main, want_mtp = ref.heads(params, ARGS, toks[0])
    assert rel(main[0], want_main) < 1e-5
    # MTP row i predicts token i + 2; the last row is no one's
    assert rel(mtp[0, :-1], want_mtp) < 1e-5
    # two routed layers of the main model and the MTP block's
    assert picked.shape == (1, S, 3, 1)


def test_loss_and_every_leafs_gradient_are_the_references(glm):
    model, params, toks, targets = glm
    crit, state = model.criterion(), model.init_state()

    def ours(p):
        out, _ = model.apply(p, state, jnp.asarray(toks), training=True)
        return crit(out, jnp.asarray(targets))

    def theirs(p):
        main, mtp = ref.heads(p, ARGS, toks[0])
        t = jnp.asarray(targets[0])
        return ref._nll(main, t) + ARGS["mtp_weight"] * ref._nll(mtp, t[1:])

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(ours))(params)
    want_loss, want = jax.value_and_grad(theirs)(params)
    assert float(loss) == pytest.approx(
        ref.mean_nll(params, ARGS, toks[0], targets[0]), rel=1e-6)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        if name.endswith("['router']['bias']"):  # it only orders the choice
            assert not np.any(np.asarray(g)) and not np.any(np.asarray(w))
            continue
        assert rel(g, w) < 1e-4, name


def test_the_references_block_by_block_gradient_is_jax_grad_of_its_loss(
        glm):
    """``batch_grads`` (what the benchmark's gradient check compares the
    timed step's with) is the gradient of the rows' mean ``mean_nll``, and
    so the program's."""
    model, params, toks, targets = glm
    rs = np.random.RandomState(2)
    toks = np.concatenate([toks, rs.randint(0, ARGS["vocab"], (1, S))])
    targets = np.concatenate([targets, rs.randint(0, ARGS["vocab"], (1, S))])
    toks, targets = toks.astype(np.int32), targets.astype(np.int32)

    def theirs(p):
        t = jnp.asarray(targets)
        return sum(ref._nll(main, t[r]) + ARGS["mtp_weight"] * ref._nll(
            mtp, t[r, 1:]) for r, (main, mtp) in enumerate(
                ref.heads(p, ARGS, toks[r]) for r in range(2))) / 2

    loss, got = ref.batch_grads(params, ARGS, toks, targets)
    want_loss, want = jax.value_and_grad(theirs)(params)
    assert loss == pytest.approx(float(want_loss), rel=1e-6)
    crit, state = model.criterion(), model.init_state()
    with jax.default_matmul_precision("highest"):
        ours = jax.jit(jax.grad(lambda p: crit(model.apply(
            p, state, jnp.asarray(toks), training=True)[0],
            jnp.asarray(targets))))(params)
    flat = jax.tree_util.tree_leaves_with_path(got)
    for (path, g), w, o in zip(flat, jax.tree_util.tree_leaves(want),
                               jax.tree_util.tree_leaves(ours)):
        name = jax.tree_util.keystr(path)
        if name.endswith("['router']['bias']"):
            assert not np.any(np.asarray(g)) and not np.any(np.asarray(w))
            continue
        assert rel(g, w) < 1e-5, name
        assert rel(o, g) < 1e-4, name


def test_routed_op_gradient_is_a_dense_per_expert_computation():
    T, d, k, held, w = 40, 32, 4, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (T, d))
    # held experts 0..3; ``held`` itself is a pick on an absent expert
    local = jax.random.randint(ks[1], (T, k), 0, held + 1)
    wts = jax.random.uniform(ks[2], (T, k))
    w13 = 0.2 * jax.random.normal(ks[3], (held, d, 2 * w))
    w2 = 0.2 * jax.random.normal(ks[4], (held, w, d))

    def dense(x, wts, w13, w2):
        out = 0.0
        for e in range(held):
            gu = x @ w13[e]
            y = (jax.nn.silu(gu[:, :w]) * gu[:, w:]) @ w2[e]
            coef = jnp.sum(jnp.where(local == e, wts, 0.0), 1)
            out = out + coef[:, None] * y
        return out

    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(loss(
            lambda x, wts, w13, w2: routed_experts(x, local, wts, w13, w2)),
            argnums=(0, 1, 2, 3)))(x, wts, w13, w2)
        want = jax.grad(loss(dense), argnums=(0, 1, 2, 3))(x, wts, w13, w2)
    for g, wt in zip(got, want):
        assert g.shape == wt.shape
        assert rel(g, wt) < 1e-5


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Eight chips of two experts each: the eight partial layers, the
    shared expert (which every chip computes alike) counted once, are the
    uncut reference layer over all 16 experts."""
    E, held, d = 16, 2, 64
    whole = nn.RoutedFFN(d, 32, E, 4, held=E, shared_width=32, scale=1.8)
    p = seeded(whole, 5)
    x = jax.random.normal(jax.random.PRNGKey(6), (S, d))
    with jax.default_matmul_precision("highest"):
        parts = []
        for share in range(E // held):
            cut = nn.RoutedFFN(d, 32, E, 4, held=held, share=share,
                               shared_width=32, scale=1.8)
            rows = slice(share * held, (share + 1) * held)
            parts.append(cut.forward(dict(p, w13=p["w13"][rows],
                                          w2=p["w2"][rows]), x)[0])
        shared = nn.glu(x, p["shared_w13"], p["shared_w2"])
    # the uncut reference layer: every expert held
    shared_ref, chosen, w = ref._route(p, x, (4, 1.8, 1e-5))
    want = shared_ref
    for e in range(E):
        coef = jnp.sum(jnp.where(chosen == e, w, 0.0), -1)
        want = want + ref._expert(p["w13"][e], p["w2"][e], x, coef)
    got = sum(parts) - (E // held - 1) * shared
    assert rel(got, want) < 1e-5


def test_the_serving_step_is_still_one_grouped_product_for_all_slots():
    """Under ``vmap`` over 8 slots of one token each, the routed op lowers
    to one grouped matmul a projection whose rows are every slot's picks;
    the differentiable wrapper changes nothing there."""
    slots, d, k, held, w = 8, 32, 4, 4, 16
    w13 = jnp.zeros((held, d, 2 * w))
    w2 = jnp.zeros((held, w, d))
    step = jax.vmap(lambda x, l, wt: routed_experts(x, l, wt, w13, w2))
    jaxpr = jax.make_jaxpr(step)(jnp.zeros((slots, 1, d)),
                                 jnp.zeros((slots, 1, k), jnp.int32),
                                 jnp.zeros((slots, 1, k)))
    calls = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name ==
             "pallas_call"]
    rows = [str(e.params["name"]) for e in calls
            if str(e.params["name"]).startswith("moe_")]
    assert rows == ["moe_dispatch_rows", "moe_combine_rows"]
    calls = [e for e in calls if not str(e.params["name"]).startswith("moe_")]
    assert len(calls) == 2
    # slots x k = 32 picks: one call takes the rows of all of them
    assert (slots * k, d) in [v.aval.shape for v in calls[0].invars]


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside its own."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_optimizer_fetches_the_declared_counters_with_the_loss():
    from bigdl_tpu.dataset import BatchDataSet
    from bigdl_tpu.obs.metrics import get_registry
    from bigdl_tpu.optim import AdamW, Optimizer, Trigger

    model = HybridMoELM(**ARGS)
    rs = np.random.RandomState(1)
    x = rs.randint(0, ARGS["vocab"], (4, 16)).astype(np.int32)
    y = rs.randint(0, ARGS["vocab"], (4, 16)).astype(np.int32)
    reg = get_registry()
    before = {n: reg.counter(n).value for n in model.train_counters}
    opt = Optimizer(model, BatchDataSet(x, y, 2), model.criterion(),
                    optim_method=AdamW(learning_rate=1e-3),
                    end_when=Trigger.max_iteration(3))
    trained = opt.optimize()
    got = {n: reg.counter(n).value - before[n] for n in before}
    # of 3 steps x 2 rows x 16 tokens x 4 picks x 3 routed layers, those
    # on the 4 experts held
    assert set(got) == {"moe_train_held_picks_total",
                        "moe_train_busiest_rows_total"}
    assert 0 < got["moe_train_held_picks_total"] < 3 * 2 * 16 * 4 * 3
    assert got["moe_train_held_picks_total"] / 4 <= got[
        "moe_train_busiest_rows_total"] <= got["moe_train_held_picks_total"]
    # the selection bias gets no gradient: AdamW's decay alone moves it
    bias = trained.params["layers"]["1"]["ffn"]["router"]["bias"]
    assert not np.any(np.asarray(bias))


def test_the_decode_engine_refuses_a_latent_model():
    from bigdl_tpu.serving.decode import DecodeEngine
    model = HybridMoELM(**ARGS)
    assert model.latent_cache and model.routed_experts
    with pytest.raises(ValueError, match="latent cache"):
        DecodeEngine(model, model.init(jax.random.PRNGKey(0)), slots=2)


def test_perf_trains_the_configuration_with_its_criterion(monkeypatch):
    """``bigdl-tpu perf glm4_moe_lite`` builds the class by its factory
    and trains it with the model's own loss on token ids of its
    vocabulary; the factory is held to toy sizes here."""
    from bigdl_tpu import models
    from bigdl_tpu.cli import perf

    toy = {k: v for k, v in ARGS.items() if k != "vocab"}
    monkeypatch.setattr(models, "glm4_moe_lite", lambda **kw: HybridMoELM(
        ARGS["vocab"], **dict(toy, **{k: v for k, v in kw.items()
                                      if k == "attn_impl"})))
    out = perf.run("glm4_moe_lite", batch=2, iterations=2,
                   data_type="random", use_bf16=False, seq_len=16)
    assert out["model"] == "glm4_moe_lite"
    assert out["tokens_per_second"] > 0

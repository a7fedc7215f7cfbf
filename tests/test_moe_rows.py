"""The routed layer's row kernels (``ops/moe_rows.py``) in interpret mode:
each against the XLA gathers, masks and sums they replace, each one's
VJP against ``jax.vjp`` of that glue, and ``routed_experts`` against the
same layer built on the glue, value and gradient, in every regime the
cells run it: 8 of 64 experts held, all held, fewer than 128 picks, a
prompt longer than a row block, the engine's ``vmap`` over slots, a token
with no held pick, and token arrays too long for fast memory in one
piece. Last, a dense model's training step never imports the kernels."""

import functools
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas.ops.tpu.megablox import gmm

from bigdl_tpu.nn import moe
from bigdl_tpu.ops import moe_rows

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def routing(T, k, held, experts, seed):
    """``local`` (T, k) picks as the layer makes them (``held`` for a
    pick on an absent expert) and the sort the kernels take."""
    key = jax.random.PRNGKey(seed)
    idx = jnp.argsort(jax.random.uniform(key, (T, experts)), axis=1)[:, :k]
    local = jnp.where(idx < held, idx, held).astype(jnp.int32)
    return local, sort_of(local, held)


def sort_of(local, held):
    """``_routed_experts``' own sort: (order, pos, n, m, m_pad)."""
    (T, k) = local.shape
    m = T * k
    tm = 128 if m >= 128 else -(-m // 16) * 16
    m_pad = -(-m // tm) * tm
    key = jnp.pad(local.reshape(m), (0, m_pad - m), constant_values=held)
    order = jnp.argsort(key, stable=True)
    pos = jnp.argsort(order)[:m].reshape(T, k)
    n = jnp.sum(jnp.bincount(key, length=held + 1)[:held]).astype(jnp.int32)
    return order, pos, n, m, m_pad


def xla_dispatch(x, order, k):
    return jnp.take(x, jnp.minimum(order // k, x.shape[0] - 1), axis=0)


def xla_combine(y, pos, wts, n):
    (T, k), d = pos.shape, y.shape[1]
    y = jnp.take(y, pos.reshape(-1), axis=0).reshape(T, k, d)
    return jnp.sum(jnp.where((pos < n)[..., None], y * wts[..., None], 0.0),
                   axis=1)


def xla_routed(x, local, wts, w13, w2, act="silu"):
    """The layer as it was built before the kernels: XLA's gathers, the
    un-sort of every pick and the masked sum over ``k``."""
    (T, d), k, held = x.shape, local.shape[1], w13.shape[0]
    width = w2.shape[1]
    m = T * k
    tm = 128 if m >= 128 else -(-m // 16) * 16
    m_pad = -(-m // tm) * tm
    key = jnp.pad(local.reshape(m), (0, m_pad - m), constant_values=held)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=held + 1).astype(jnp.int32)
    xs = jnp.take(x, jnp.minimum(order // k, T - 1), axis=0)
    call = functools.partial(gmm, group_sizes=sizes,
                             preferred_element_type=jnp.float32,
                             interpret=True)
    gu = call(xs, w13.astype(x.dtype), tiling=(
        tm, moe._tile(d, (1024, 1280, 512, 256, 128)),
        moe._tile(2 * width, (1280, 1024, 512, 256, 128))))
    a = (moe._ACTS[act](gu[:, :width]) * gu[:, width:]).astype(x.dtype)
    y = call(a, w2.astype(x.dtype), tiling=(
        tm, min(width, 1280), moe._tile(d, (1024, 512, 256, 128))))
    y = jnp.take(y, jnp.argsort(order)[:m], axis=0).reshape(T, k, d)
    return jnp.sum(jnp.where((local < held)[..., None],
                             y * wts[..., None], 0.0), axis=1)


@pytest.fixture
def small_chunks(monkeypatch):
    """Fast memory that holds 40 float32 rows of 128: longer token arrays
    are walked a chunk a call."""
    monkeypatch.setattr(moe_rows, "_RESIDENT_BYTES", 40 * 128 * 4)
    jax.clear_caches()
    yield
    jax.clear_caches()


KERNEL_CASES = {
    # T, k, held, experts, d, dtype
    "held_8_of_64_top4": (96, 4, 8, 64, 128, jnp.bfloat16),
    "all_held_top6": (50, 6, 16, 16, 128, jnp.float32),
    "m_under_128": (7, 4, 4, 16, 128, jnp.bfloat16),
    "decode_slots": (64, 8, 40, 320, 256, jnp.bfloat16),
}


@pytest.mark.parametrize("chunked", [False, True], ids=["whole", "chunked"])
@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_dispatch_is_the_gather_bit_for_bit(case, chunked, request):
    if chunked:
        request.getfixturevalue("small_chunks")
    T, k, held, experts, d, dt = KERNEL_CASES[case]
    local, (order, pos, n, m, m_pad) = routing(T, k, held, experts, 1)
    x = jax.random.normal(jax.random.PRNGKey(2), (T, d)).astype(dt)
    got = moe_rows.dispatch_rows(x, order, pos, n, True)
    want = xla_dispatch(x, order, k)
    assert got.shape == (m_pad, d) and got.dtype == dt
    n = int(n)
    assert 0 < n <= m
    # rows at and past n are the absent group: unspecified
    np.testing.assert_array_equal(np.asarray(got[:n], np.float32),
                                  np.asarray(want[:n], np.float32))


@pytest.mark.parametrize("chunked", [False, True], ids=["whole", "chunked"])
@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_combine_is_the_unsort_mask_and_sum(case, chunked, request):
    if chunked:
        request.getfixturevalue("small_chunks")
    T, k, held, experts, d, _ = KERNEL_CASES[case]
    local, (order, pos, n, m, m_pad) = routing(T, k, held, experts, 3)
    y = jax.random.normal(jax.random.PRNGKey(4), (m_pad, d))
    # what the grouped matmuls leave in the absent group's rows
    y = jnp.where(jnp.arange(m_pad)[:, None] < n, y, jnp.nan)
    wts = jax.random.uniform(jax.random.PRNGKey(5), (T, k))
    got = moe_rows.combine_rows(y, order, pos, wts, n, True)
    want = xla_combine(jnp.nan_to_num(y), pos, wts, n)
    assert got.shape == (T, d) and got.dtype == jnp.float32
    assert np.isfinite(np.asarray(got)).all()
    assert rel(got, want) < 1e-6


@pytest.mark.parametrize("chunked", [False, True], ids=["whole", "chunked"])
@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_each_vjp_is_the_other_kernel(case, chunked, request):
    """``d dispatch / dx`` against the transpose of XLA's gather, ``d
    combine / (y, wts)`` against that of the un-sort, mask and sum; absent
    picks get no cotangent."""
    if chunked:
        request.getfixturevalue("small_chunks")
    T, k, held, experts, d, dt = KERNEL_CASES[case]
    local, (order, pos, n, m, m_pad) = routing(T, k, held, experts, 6)
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (T, d)).astype(dt)
    gx = jax.random.normal(ks[1], (m_pad, d)).astype(dt)
    # the cotangent of the absent group never reaches x
    gx_held = jnp.where(jnp.arange(m_pad)[:, None] < n, gx, 0).astype(dt)
    _, vjp = jax.vjp(lambda x: moe_rows.dispatch_rows(x, order, pos, n,
                                                      True), x)
    _, want = jax.vjp(lambda x: xla_dispatch(x, order, k), x)
    got_dx, = vjp(gx)
    want_dx, = want(gx_held)
    assert got_dx.dtype == dt
    assert rel(got_dx.astype(jnp.float32), want_dx.astype(jnp.float32)) < (
        1e-6 if dt == jnp.float32 else 1e-2)

    y = jax.random.normal(ks[2], (m_pad, d))
    wts = jax.random.uniform(ks[3], (T, k))
    g = jax.random.normal(ks[4], (T, d))
    _, vjp = jax.vjp(lambda y, w: moe_rows.combine_rows(y, order, pos, w, n,
                                                        True), y, wts)
    _, want = jax.vjp(lambda y, w: xla_combine(y, pos, w, n), y, wts)
    (got_dy, got_dw), (want_dy, want_dw) = vjp(g), want(g)
    n = int(n)
    assert rel(got_dy[:n], want_dy[:n]) < 1e-6
    assert rel(got_dw, want_dw) < 1e-6
    assert (np.asarray(got_dw)[np.asarray(pos) >= n] == 0).all()


def layer(T, k, held, experts, d, width, seed, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    local, _ = routing(T, k, held, experts, seed)
    x = jax.random.normal(ks[0], (T, d)).astype(dtype)
    wts = jax.random.uniform(ks[1], (T, k))
    w13 = 0.2 * jax.random.normal(ks[2], (held, d, 2 * width))
    w2 = 0.2 * jax.random.normal(ks[3], (held, width, d))
    return x, local, wts, w13, w2


ROUTED_CASES = {
    # T, k, held, experts, d, width, act
    "held_8_of_64_top4": (128, 4, 8, 64, 128, 64, "silu"),
    "all_held_top6": (64, 6, 16, 16, 128, 64, "relu"),
    "m_under_128": (5, 4, 4, 8, 128, 64, "silu"),
}


@pytest.mark.parametrize("case", sorted(ROUTED_CASES))
def test_routed_experts_is_the_glue_layer(case):
    T, k, held, experts, d, width, act = ROUTED_CASES[case]
    x, local, wts, w13, w2 = layer(T, k, held, experts, d, width, 8)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(functools.partial(moe.routed_experts, act=act))(
            x, local, wts, w13, w2)
        want = jax.jit(functools.partial(xla_routed, act=act))(
            x, local, wts, w13, w2)
        assert rel(got, want) < 1e-6

        def grad(f):
            loss = lambda x, wts, w13, w2: jnp.sum(jnp.sin(
                f(x, local, wts, w13, w2, act=act)))
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))(
                x, wts, w13, w2)
        for g, w in zip(grad(moe.routed_experts), grad(xla_routed)):
            assert g.shape == w.shape
            assert rel(g, w) < 1e-5


def test_a_prompt_longer_than_a_row_block_is_walked_in_blocks():
    """``T`` past ``row_block`` goes through the ``lax.map`` over blocks,
    each block its own sort and its own two row kernels."""
    x, local, wts, w13, w2 = layer(256, 4, 8, 32, 128, 64, 9)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(functools.partial(moe._routed_experts, row_block=64))(
            x, local, wts, w13, w2)
        want = xla_routed(x, local, wts, w13, w2)
    assert rel(got, want) < 1e-6
    text = str(jax.make_jaxpr(functools.partial(
        moe._routed_experts, row_block=64))(x, local, wts, w13, w2))
    assert "while" in text and text.count("name=moe_dispatch_rows") == 1


def test_the_decode_batching_rule_still_matches():
    """The engine's step: ``vmap`` over slots of one token each goes
    through the ``custom_vmap`` rule, one product for all slots."""
    x, local, wts, w13, w2 = layer(12, 8, 10, 80, 128, 64, 10)
    with jax.default_matmul_precision("highest"):
        step = jax.vmap(lambda x, l, w: moe.routed_experts(x, l, w, w13, w2))
        got = jax.jit(step)(x[:, None], local[:, None], wts[:, None])
        want = xla_routed(x, local, wts, w13, w2)
    assert rel(got.reshape(want.shape), want) < 1e-6


def test_a_token_with_no_held_pick_gets_zeros_and_no_gradient():
    x, local, wts, w13, w2 = layer(32, 4, 4, 16, 128, 64, 11)
    local = local.at[3].set(4).at[17].set(4)  # every pick absent
    with jax.default_matmul_precision("highest"):
        out = moe.routed_experts(x, local, wts, w13, w2)
        dx, dw = jax.grad(lambda x, w: jnp.sum(
            moe.routed_experts(x, local, w, w13, w2) ** 2),
            argnums=(0, 1))(x, wts)
        want = xla_routed(x, local, wts, w13, w2)
    assert rel(out, want) < 1e-6
    for t in (3, 17):
        assert not np.asarray(out[t]).any()
        assert not np.asarray(dx[t]).any() and not np.asarray(dw[t]).any()


def test_the_layer_walks_long_token_arrays_in_chunks(small_chunks):
    """Source rows and sums longer than fast memory holds: one call a
    chunk, the same numbers."""
    x, local, wts, w13, w2 = layer(128, 4, 8, 64, 128, 64, 12)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(moe.routed_experts)(x, local, wts, w13, w2)
        want = xla_routed(x, local, wts, w13, w2)
        text = str(jax.make_jaxpr(moe.routed_experts)(x, local, wts, w13,
                                                      w2))
    assert rel(got, want) < 1e-6
    assert text.count("name=moe_combine_rows") == 4  # 128 rows / 40


DENSE_STEP = textwrap.dedent("""
    import sys
    import jax, jax.numpy as jnp, numpy as np
    jax.config.update("jax_platforms", "cpu")
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import BatchDataSet
    from bigdl_tpu.models.transformer_lm import TransformerLM
    from bigdl_tpu.optim import AdamW, Optimizer
    # StarCoder2's layout (GQA, RoPE, tied head) at toy widths
    model = TransformerLM(vocab=256, d_model=64, num_layers=2, num_heads=4,
                          d_ff=256, max_len=128, num_kv_heads=2,
                          pos_encoding="rope", tie_embeddings=True,
                          attn_impl="flash", compute_dtype=jnp.bfloat16)
    x = np.zeros((2, 128), np.int32)
    opt = Optimizer(model, BatchDataSet(x, x, 2),
                    nn.TimeDistributedCriterion(nn.ClassNLLCriterion()),
                    optim_method=AdamW(learning_rate=1e-4))
    step, _ = opt._build_step()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    args = (params, jax.eval_shape(model.init_state),
            jax.eval_shape(opt.optim_method.init, params),
            jax.ShapeDtypeStruct((2, 128), jnp.int32),
            jax.ShapeDtypeStruct((2, 128), jnp.int32),
            jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    text = step.lower(*args).as_text()
    print("imported", "bigdl_tpu.ops.moe_rows" in sys.modules)
    print("moe_calls", text.count("moe_"))
""")


def test_a_dense_training_step_never_imports_the_row_kernels():
    """In a process of its own, so that no other test has imported the
    module: a dense model's training step is built and lowered without
    ``ops/moe_rows.py`` and holds no ``moe_`` kernel."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    out = subprocess.run([sys.executable, "-c", DENSE_STEP], env=env,
                         cwd=ROOT, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "imported False" in out.stdout
    assert "moe_calls 0" in out.stdout

"""Attention layers + ring-attention sequence parallelism.

Ring attention is validated against the dense reference implementation on
the 8-device CPU mesh (the multi-chip-without-hardware strategy of
SURVEY.md §4) — same numerics up to fp32 reassociation.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.nn.attention import (
    LayerNorm,
    MultiHeadAttention,
    PositionalEncoding,
    TransformerEncoder,
    TransformerEncoderLayer,
    dot_product_attention,
)
from bigdl_tpu.parallel import make_mesh
from bigdl_tpu.parallel.sequence import make_ring_attention


def test_layernorm(rng):
    ln = LayerNorm(16)
    p = ln.init(rng)
    x = jax.random.normal(rng, (4, 16)) * 3 + 1
    y = ln.forward(p, x)
    np.testing.assert_allclose(np.mean(y, -1), 0, atol=1e-5)
    np.testing.assert_allclose(np.std(y, -1), 1, atol=1e-3)


def test_dot_product_attention_softmax():
    q = jnp.ones((1, 1, 3, 4))
    k = jnp.zeros((1, 1, 5, 4))
    v = jnp.arange(5.0).reshape(1, 1, 5, 1) * jnp.ones((1, 1, 5, 4))
    # uniform weights -> mean of v
    out = dot_product_attention(q, k, v)
    np.testing.assert_allclose(out[0, 0, 0, 0], 2.0, atol=1e-6)


def test_causal_mask():
    rng = jax.random.PRNGKey(1)
    q = jax.random.normal(rng, (2, 2, 6, 8))
    out = dot_product_attention(q, q, q, causal=True)
    # position 0 attends only to itself -> equals v[0]
    np.testing.assert_allclose(out[:, :, 0, :], q[:, :, 0, :], atol=1e-5)


def test_mha_shapes_and_grad(rng):
    mha = MultiHeadAttention(32, 4, causal=True)
    p = mha.init(rng)
    x = jax.random.normal(rng, (2, 10, 32))
    y = mha.forward(p, x)
    assert y.shape == (2, 10, 32)

    def loss(p):
        return jnp.sum(mha.forward(p, x) ** 2)

    g = jax.grad(loss)(p)
    assert all(jnp.all(jnp.isfinite(v)) for v in jax.tree_util.tree_leaves(g))


def test_mha_cross_attention(rng):
    mha = MultiHeadAttention(16, 2)
    p = mha.init(rng)
    q_in = jax.random.normal(rng, (2, 5, 16))
    kv = jax.random.normal(jax.random.fold_in(rng, 1), (2, 9, 16))
    y = mha.forward(p, (q_in, kv))
    assert y.shape == (2, 5, 16)


def test_positional_encoding():
    pe = PositionalEncoding(8)
    x = jnp.zeros((1, 4, 8))
    y = pe.forward({}, x)
    assert y.shape == x.shape
    # position 0: sin(0)=0, cos(0)=1
    np.testing.assert_allclose(y[0, 0, 0::2], 0.0, atol=1e-6)
    np.testing.assert_allclose(y[0, 0, 1::2], 1.0, atol=1e-6)


def test_transformer_encoder_forward_and_remat(rng):
    enc = TransformerEncoder(2, 16, 2, causal=True)
    enc_r = TransformerEncoder(2, 16, 2, causal=True, remat=True)
    p = enc.init(rng)
    x = jax.random.normal(rng, (2, 7, 16))
    y = enc.forward(p, x)
    y_r = enc_r.forward(p, x)
    assert y.shape == (2, 7, 16)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r), atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(causal):
    mesh = make_mesh({"seq": 8})
    attn = make_ring_attention(mesh, "seq")
    rng = jax.random.PRNGKey(3)
    kq, kk, kv = jax.random.split(rng, 3)
    b, h, s, d = 2, 2, 32, 8  # s=32 over 8 devices -> 4 per device
    q = jax.random.normal(kq, (b, h, s, d))
    k = jax.random.normal(kk, (b, h, s, d))
    v = jax.random.normal(kv, (b, h, s, d))
    got = attn(q, k, v, causal=causal)
    want = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_ring_attention_in_mha_grad():
    mesh = make_mesh({"seq": 8})
    attn = make_ring_attention(mesh, "seq")
    mha = MultiHeadAttention(16, 2, causal=True, attn_impl=attn)
    mha_ref = MultiHeadAttention(16, 2, causal=True)
    p = mha.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16))

    y = mha.forward(p, x)
    y_ref = mha_ref.forward(p, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-5)

    g = jax.grad(lambda p: jnp.sum(mha.forward(p, x) ** 2))(p)
    g_ref = jax.grad(lambda p: jnp.sum(mha_ref.forward(p, x) ** 2))(p)
    for a, b_ in zip(jax.tree_util.tree_leaves(g),
                     jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-4, rtol=1e-4)


def test_causal_cross_attention_bottom_right():
    # q is the 2-suffix of a 6-key sequence: row 0 must see keys 0..4
    rng = jax.random.PRNGKey(5)
    k = jax.random.normal(rng, (1, 1, 6, 4))
    q = k[:, :, 4:, :]
    out = dot_product_attention(q, k, k, causal=True)
    want_row0 = dot_product_attention(q[:, :, :1], k[:, :, :5], k[:, :, :5])
    np.testing.assert_allclose(np.asarray(out[:, :, 0]),
                               np.asarray(want_row0[:, :, 0]), atol=1e-6)


def test_key_padding_mask_ignores_pads():
    rng = jax.random.PRNGKey(6)
    mha = MultiHeadAttention(16, 2)
    p = mha.init(rng)
    x = jax.random.normal(rng, (2, 8, 16))
    mask = jnp.ones((2, 8), bool).at[:, 6:].set(False)
    y_masked = mha.forward(p, (x, x, mask))
    # altering the padded positions must not change the output of valid ones
    x2 = x.at[:, 6:].set(99.0)
    y2 = mha.forward(p, (x2, x2, mask))
    np.testing.assert_allclose(np.asarray(y_masked[:, :6]),
                               np.asarray(y2[:, :6]), atol=1e-5)


def test_encoder_mask_threading(rng):
    enc = TransformerEncoder(2, 16, 2)
    p = enc.init(rng)
    x = jax.random.normal(rng, (2, 8, 16))
    mask = jnp.ones((2, 8), bool).at[:, 5:].set(False)
    y, m = enc.forward(p, (x, mask))
    assert y.shape == x.shape and m is mask


def test_bf16_logits_accumulate_fp32():
    q = (jax.random.normal(jax.random.PRNGKey(7), (1, 1, 4, 8))
         .astype(jnp.bfloat16))
    out = dot_product_attention(q, q, q)
    assert out.dtype == jnp.bfloat16


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_sub_blocked(causal):
    """block_k smaller than the local chunk: each ring hop streams the
    arriving K/V in sub-blocks (bounded memory) — must still equal dense."""
    mesh = make_mesh({"seq": 8})
    attn = make_ring_attention(mesh, "seq", block_k=2)
    rng = jax.random.PRNGKey(5)
    kq, kk, kv = jax.random.split(rng, 3)
    b, h, s, d = 2, 2, 32, 8  # local chunk 4, sub-blocks of 2
    q = jax.random.normal(kq, (b, h, s, d))
    k = jax.random.normal(kk, (b, h, s, d))
    v = jax.random.normal(kv, (b, h, s, d))
    got = attn(q, k, v, causal=causal)
    want = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    # gradients flow through the checkpointed sub-scan
    g = jax.grad(lambda q: jnp.sum(attn(q, k, v, causal=causal) ** 2))(q)
    g_ref = jax.grad(lambda q: jnp.sum(
        dot_product_attention(q, k, v, causal=causal) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               atol=1e-4, rtol=1e-4)


def test_gqa_matches_manually_expanded():
    """GQA (num_kv_heads < num_heads) must equal standard MHA run with
    the K/V heads explicitly repeated over the query groups."""
    mha = MultiHeadAttention(16, 4, causal=True, num_kv_heads=2)
    p = mha.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 10, 16))
    y = mha.forward(p, x)
    assert y.shape == (2, 10, 16)
    assert p["wk"].shape == (16, 2 * 4)  # num_kv_heads * head_dim

    # manual reference: project, split to 2 kv heads, repeat to 4
    q = (x @ p["wq"] + p["bq"]).reshape(2, 10, 4, 4).transpose(0, 2, 1, 3)
    k = (x @ p["wk"] + p["bk"]).reshape(2, 10, 2, 4).transpose(0, 2, 1, 3)
    v = (x @ p["wv"] + p["bv"]).reshape(2, 10, 2, 4).transpose(0, 2, 1, 3)
    k = jnp.repeat(k, 2, axis=1)
    v = jnp.repeat(v, 2, axis=1)
    o = dot_product_attention(q, k, v, causal=True)
    o = o.transpose(0, 2, 1, 3).reshape(2, 10, 16)
    ref = o @ p["wo"] + p["bo"]
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)


def test_gqa_generate_equivalence():
    """GQA KV-cache decode == full re-forward greedy (cache holds only
    num_kv_heads heads)."""
    from bigdl_tpu.models import transformer_lm

    m = transformer_lm(40, d_model=32, num_layers=2, num_heads=4,
                       num_kv_heads=2, max_len=32)
    params = m.init(jax.random.PRNGKey(0))
    cache = m.encoder.init_cache(1, 32)
    assert cache["0"]["k"].shape == (1, 2, 32, 8)  # kv heads only
    prompt = jnp.asarray(
        np.random.RandomState(0).randint(0, 40, (2, 4)), jnp.int32)
    toks = prompt
    ref = []
    for _ in range(6):
        lp, _ = m.apply(params, None, toks)
        nxt = jnp.argmax(lp[:, -1, :], axis=-1).astype(jnp.int32)
        ref.append(nxt)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    out = np.asarray(m.generate(params, prompt, 6, temperature=0.0))
    np.testing.assert_array_equal(out, np.asarray(jnp.stack(ref, axis=1)))


def _decode_chunk_by_expansion(mha, params, x, cache, idx):
    """The plain reference of ``decode_chunk``: K/V copied out to the
    query head count by hand, one query row a head (the formula the
    grouped contractions replaced), at the same operand precision."""
    q, k, v = mha._qkv(params, x)
    if mha.rope:
        q, k = mha._rope(q, idx), mha._rope(k, idx)
    kc = jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, idx, 0))
    vc = jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, idx, 0))
    g = mha.num_heads // mha.num_kv_heads
    ke, ve = jnp.repeat(kc, g, axis=1), jnp.repeat(vc, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, ke,
                   preferred_element_type=jnp.float32)
    s = s / (mha.head_dim ** 0.5)
    rows = idx + jnp.arange(x.shape[1])[None, None, :, None]
    live = jnp.arange(ke.shape[2])[None, None, None, :] <= rows
    p = jax.nn.softmax(jnp.where(live, s, -1e30), axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), ve,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    o = mha._merge_heads(o)
    return o @ params["wo"] + params["bo"], {"k": kc, "v": vc}


# tol is absolute and relative (|got - want| <= tol + tol * |want|).
# f32: the two formulas differ by summation order alone. bf16: the f32
# accumulators agree to that order too, so the outputs differ by the
# rounding of p and o to bf16 (2**-8 relative each) and the wo matmul
# over them: 3e-2 holds a few of those steps, and a wrong row order,
# mask or group moves an output by its own size
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5),
                                       ("bfloat16", 3e-2)])
@pytest.mark.parametrize("m", [1, 4])
@pytest.mark.parametrize("g", [1, 4, 12])
def test_decode_chunk_grouped_contraction(g, m, dtype, tol):
    """``decode_chunk`` attends over the cache at its stored head count:
    under ``vmap`` with a position of its own for every slot (the
    engine's step) it equals the expand-then-contract reference, for
    plain multi-head (g = 1) and grouped heads, one row and a chunk; in
    f32 also the same rows of the full causal forward."""
    n_kv, hd, max_len, slots = 2, 8, 24, 3
    mha = MultiHeadAttention(n_kv * g * hd, n_kv * g, causal=True,
                             num_kv_heads=n_kv, rope=True, rope_max_len=32)
    p = jax.tree_util.tree_map(  # biases that a dropped term would show
        lambda a: (a + 0.1).astype(dtype), mha.init(jax.random.PRNGKey(g)))
    x = jax.random.normal(jax.random.PRNGKey(m),
                          (slots, max_len, mha.d_model)).astype(dtype)
    idx = jnp.asarray([3, 11, max_len - m], jnp.int32)  # the last: to the end
    full, cache = mha.prefill(p, x, mha.init_cache(slots, max_len, dtype))
    chunk = jax.vmap(lambda xi, i: jax.lax.dynamic_slice_in_dim(
        xi, i, m, 0))(x, idx)

    def one(fn):
        def f(xi, ci, i):
            ci = jax.tree_util.tree_map(lambda a: a[None], ci)
            out, new = fn(mha, p, xi[None], ci, i)
            return out[0], jax.tree_util.tree_map(lambda a: a[0], new)
        return jax.jit(jax.vmap(f))(chunk, cache, idx)

    got, new = one(MultiHeadAttention.decode_chunk)
    want, new_ref = one(_decode_chunk_by_expansion)
    assert got.dtype == jnp.dtype(dtype)
    assert new["k"].shape == (slots, n_kv, max_len, hd)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    np.testing.assert_allclose(f32(got), f32(want), rtol=tol, atol=tol)
    for name in ("k", "v"):
        np.testing.assert_array_equal(f32(new[name]), f32(new_ref[name]))
    if dtype == "float32":  # rows idx..idx+m-1 of the whole-sequence forward
        rows = jax.vmap(lambda fi, i: jax.lax.dynamic_slice_in_dim(
            fi, i, m, 0))(full, idx)
        np.testing.assert_allclose(f32(got), f32(rows), rtol=tol, atol=tol)


def test_dense_decode_step_never_expands_the_cache():
    """No array in the engine's dense decode step of a toy GQA model has
    the shape of the cache copied out to the query heads, (slots,
    num_heads, max_len, head_dim) folded or grouped: the copy cannot come
    back unseen by a CPU-only check."""
    from bigdl_tpu.models import transformer_lm
    from bigdl_tpu.serving import DecodeEngine

    slots, heads, n_kv, hd, max_len = 5, 6, 2, 4, 40
    model = transformer_lm(50, d_model=heads * hd, num_layers=2,
                           num_heads=heads, num_kv_heads=n_kv,
                           max_len=max_len)
    de = DecodeEngine(model, model.init(jax.random.PRNGKey(0)),
                      slots=slots, max_len=max_len)

    def shapes(jp):
        for eqn in jp.eqns:
            for v in eqn.outvars:
                yield tuple(v.aval.shape)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from shapes(sub)

    seen = set(shapes(de.trace_step_jaxpr().jaxpr))
    assert (slots, n_kv, max_len, hd) in seen  # the cache itself is there
    expanded = [s for s in seen if max_len in s and hd in s
                and (heads in s or heads // n_kv in s)]
    assert not expanded, expanded


def test_segment_mask_packing_equivalence(rng):
    """Two documents packed into one row with make_segment_mask produce
    exactly the outputs of running each document alone — the packed-LM
    training contract (no positional encoding in TransformerEncoder, so
    equivalence is exact)."""
    d, h = 16, 4
    enc = nn.TransformerEncoder(num_layers=2, d_model=d, num_heads=h,
                                d_ff=32, causal=True)
    params = enc.init(jax.random.PRNGKey(0))
    rs = np.random.RandomState(0)
    a = jnp.asarray(rs.randn(1, 5, d), jnp.float32)
    b = jnp.asarray(rs.randn(1, 7, d), jnp.float32)

    packed = jnp.concatenate([a, b], axis=1)          # (1, 12, d)
    segs = jnp.asarray([[1] * 5 + [2] * 7])
    mask = nn.make_segment_mask(segs)
    assert mask.shape == (1, 1, 12, 12)
    out_packed, _ = enc.apply(params, enc.init_state(), (packed, mask))
    out_packed = out_packed[0] if isinstance(out_packed, tuple) \
        else out_packed

    out_a, _ = enc.apply(params, enc.init_state(), a)
    out_b, _ = enc.apply(params, enc.init_state(), b)
    np.testing.assert_allclose(np.asarray(out_packed[:, :5]),
                               np.asarray(out_a), atol=1e-5)
    np.testing.assert_allclose(np.asarray(out_packed[:, 5:]),
                               np.asarray(out_b), atol=1e-5)


def test_segment_mask_padding_id_zero():
    segs = jnp.asarray([[1, 1, 0, 2]])
    m = np.asarray(nn.make_segment_mask(segs))[0, 0]
    assert m[0, 1] and m[1, 0]          # same doc
    assert not m[0, 3] and not m[3, 0]  # cross-doc
    assert not m[2].any() and not m[:, 2].any()  # pad row+col dead


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_segments(causal):
    """Packed documents + sequence parallelism: segment ids ride the ring
    next to K/V; result == dense with the block-diagonal mask (compared
    on live positions — the 0-padding conventions differ)."""
    mesh = make_mesh({"seq": 8})
    attn = make_ring_attention(mesh, "seq")
    rng = jax.random.PRNGKey(5)
    kq, kk, kv = jax.random.split(rng, 3)
    b, h, s, d = 2, 2, 32, 8
    q = jax.random.normal(kq, (b, h, s, d))
    k = jax.random.normal(kk, (b, h, s, d))
    v = jax.random.normal(kv, (b, h, s, d))
    segs = np.zeros((b, s), np.int32)
    segs[0, :10] = 1
    segs[0, 10:30] = 2          # 2 pad positions
    segs[1, :] = 1
    segs = jnp.asarray(segs)
    live = np.asarray(segs) != 0

    got = attn(q, k, v, causal=causal, segments=segs)
    want = dot_product_attention(q, k, v, causal=causal,
                                 mask=nn.make_segment_mask(segs))
    w = live[:, None, :, None]
    np.testing.assert_allclose(np.asarray(got) * w, np.asarray(want) * w,
                               atol=1e-5, rtol=1e-5)

    # grads through the ring with segments stay finite and match dense on
    # a live-weighted loss
    wj = jnp.asarray(w, jnp.float32)
    g1 = jax.grad(lambda q, k, v: jnp.sum(jnp.square(
        attn(q, k, v, causal=causal, segments=segs) * wj)),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: jnp.sum(jnp.square(
        dot_product_attention(q, k, v, causal=causal,
                              mask=nn.make_segment_mask(segs)) * wj)),
        argnums=(0, 1, 2))(q, k, v)
    for a, c in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   atol=3e-5)

"""Pallas flash-attention kernel vs the dense XLA reference (interpret mode
on CPU — the same kernel code path runs compiled on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.nn.attention import dot_product_attention
from bigdl_tpu.ops import flash_attention


def _qkv(b=2, h=2, s=64, d=16, seed=0):
    rs = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rs.randn(b, h, s, d).astype(np.float32))
    return mk(), mk(), mk()


@pytest.fixture
def fresh_traces():
    """The kernels sit under an inner ``jit`` (one trace a signature, not
    one a layer), which knows nothing of what a test plants in the module:
    such a test drops every cached trace before and after itself."""
    jax.clear_caches()
    yield jax.clear_caches
    jax.clear_caches()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal):
    q, k, v = _qkv()
    ref = dot_product_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_multiblock_online_softmax():
    """Several K blocks exercise the running-max/renormalization path."""
    q, k, v = _qkv(s=128, seed=3)
    ref = dot_product_attention(q, k, v)
    out = flash_attention(q, k, v, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_ragged_and_masked_fall_back():
    q, k, v = _qkv(s=60, seed=4)  # 60 not divisible by block
    ref = dot_product_attention(q, k, v)
    out = flash_attention(q, k, v, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    mask = jnp.ones((2, 1, 1, 60), bool).at[:, :, :, 50:].set(False)
    ref_m = dot_product_attention(q, k, v, mask=mask)
    out_m = flash_attention(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(out_m), np.asarray(ref_m),
                               atol=2e-5)


def test_flash_causal_bottom_right_aligned_sq_ne_sk():
    """Decode-style s_q != s_k: causal must be bottom-right aligned (query
    suffix of the key sequence), matching the dense path."""
    rs = np.random.RandomState(6)
    q = jnp.asarray(rs.randn(2, 2, 16, 8).astype(np.float32))
    k = jnp.asarray(rs.randn(2, 2, 64, 8).astype(np.float32))
    v = jnp.asarray(rs.randn(2, 2, 64, 8).astype(np.float32))
    ref = dot_product_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_gradients_match_dense():
    q, k, v = _qkv(s=32, seed=5)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=16,
                               block_k=16).sum()

    def loss_dense(q, k, v):
        return dot_product_attention(q, k, v, causal=True).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


@pytest.mark.parametrize("causal,sq,sk,dtype,tol", [
    (False, 256, 256, jnp.float32, 1e-4),
    (True, 256, 256, jnp.float32, 1e-4),
    (True, 100, 256, jnp.float32, 1e-4),   # q padding + offset
    (True, 128, 384, jnp.float32, 1e-4),   # cross-length causal
    (True, 256, 256, jnp.bfloat16, 5e-2),
])
def test_flash_backward_kernels_match_dense(causal, sq, sk, dtype, tol):
    """The Pallas dq and dk/dv backward kernels (not the remat fallback:
    these shapes are tileable at the default 128 blocks) against dense
    autodiff, including q-padding, bottom-right causal offset, bf16."""
    rs = np.random.RandomState(12)
    d = 64
    q = jnp.asarray(rs.randn(1, 2, sq, d), dtype)
    k = jnp.asarray(rs.randn(1, 2, sk, d), dtype)
    v = jnp.asarray(rs.randn(1, 2, sk, d), dtype)
    g = jnp.asarray(rs.randn(1, 2, sq, d), dtype)

    def scalar(f):
        return lambda q, k, v: jnp.vdot(
            f(q, k, v).astype(jnp.float32), g.astype(jnp.float32))

    gf = jax.grad(scalar(lambda q, k, v: flash_attention(
        q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(scalar(lambda q, k, v: dot_product_attention(
        q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=tol)


@pytest.mark.tpu
def test_flash_compiled_on_tpu():
    """Non-interpret (Mosaic-compiled) forward+backward parity — runs only
    where a real TPU backend is present (VERDICT r2 item 8: CI otherwise
    never compiles the kernel, so a lowering bug would ship silently)."""
    if jax.default_backend() != "tpu":
        pytest.skip("needs a TPU backend (kernel runs interpret elsewhere)")
    rs = np.random.RandomState(13)
    q = jnp.asarray(rs.randn(2, 4, 512, 64), jnp.bfloat16)
    k = jnp.asarray(rs.randn(2, 4, 512, 64), jnp.bfloat16)
    v = jnp.asarray(rs.randn(2, 4, 512, 64), jnp.bfloat16)

    out = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True))(q, k, v)
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=5e-2)

    gf = jax.jit(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))(q, k, v)
    gd = jax.grad(lambda q, k, v: dot_product_attention(
        q, k, v, causal=True).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-1)

    # packed-segment variant must also lower and agree with the dense
    # block-diagonal mask (fwd + one grad)
    from bigdl_tpu.nn.attention import make_segment_mask

    segs = jnp.asarray(np.repeat([[1, 2, 3, 4]], 128, axis=1)
                       .reshape(1, 512).repeat(2, axis=0))
    out_s = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, segments=segs))(q, k, v)
    ref_s = dot_product_attention(q, k, v, causal=True,
                                  mask=make_segment_mask(segs))
    np.testing.assert_allclose(np.asarray(out_s, np.float32),
                               np.asarray(ref_s, np.float32), atol=5e-2)
    gs = jax.jit(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True, segments=segs).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))(q, k, v)
    for a in gs:
        assert np.isfinite(np.asarray(a, np.float32)).all()


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_matches_dense(causal):
    from bigdl_tpu.ops import blockwise_attention

    q, k, v = _qkv(s=96, seed=8)
    ref = dot_product_attention(q, k, v, causal=causal)
    out = blockwise_attention(q, k, v, causal=causal, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_blockwise_gradients_match_dense():
    from bigdl_tpu.ops import blockwise_attention

    q, k, v = _qkv(s=64, seed=9)
    gb = jax.grad(lambda q, k, v: blockwise_attention(
        q, k, v, causal=True, block_k=16).sum(), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: dot_product_attention(
        q, k, v, causal=True).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gb, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_blockwise_decode_alignment():
    from bigdl_tpu.ops import blockwise_attention

    rs = np.random.RandomState(10)
    q = jnp.asarray(rs.randn(1, 2, 8, 8).astype(np.float32))
    k = jnp.asarray(rs.randn(1, 2, 32, 8).astype(np.float32))
    v = jnp.asarray(rs.randn(1, 2, 32, 8).astype(np.float32))
    ref = dot_product_attention(q, k, v, causal=True)
    out = blockwise_attention(q, k, v, causal=True, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_mha_blockwise_impl(rng):
    mha_d = nn.MultiHeadAttention(32, 4, causal=True)
    mha_b = nn.MultiHeadAttention(32, 4, causal=True,
                                  attn_impl="blockwise")
    p = mha_d.init(rng)
    x = jnp.asarray(np.random.RandomState(11).randn(2, 16, 32), np.float32)
    np.testing.assert_allclose(np.asarray(mha_b.forward(p, x)),
                               np.asarray(mha_d.forward(p, x)), atol=2e-5)


def test_mha_flash_impl_end_to_end(rng):
    """MultiHeadAttention(attn_impl='flash') == default impl."""
    mha_d = nn.MultiHeadAttention(32, 4, causal=True)
    mha_f = nn.MultiHeadAttention(32, 4, causal=True, attn_impl="flash")
    p = mha_d.init(rng)
    x = jnp.asarray(np.random.RandomState(7).randn(2, 16, 32), np.float32)
    np.testing.assert_allclose(np.asarray(mha_f.forward(p, x)),
                               np.asarray(mha_d.forward(p, x)), atol=2e-5)


def test_blockwise_key_padding_mask_matches_dense():
    """Key-padding masks stay on the O(seq) blockwise path (round-3: they
    previously forced the dense fallback) — parity incl. gradients."""
    from bigdl_tpu.ops import blockwise_attention

    rs = np.random.RandomState(14)
    b, h, s, d = 2, 2, 64, 16
    q = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    keep = jnp.asarray(rs.rand(b, s) > 0.3)
    keep = keep.at[:, 0].set(True)  # no fully-masked rows
    ref = dot_product_attention(q, k, v, mask=keep[:, None, None, :])
    for m in (keep, keep[:, None, None, :]):
        out = blockwise_attention(q, k, v, mask=m, block_k=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
    g1 = jax.grad(lambda q: blockwise_attention(
        q, k, v, mask=keep, block_k=16).sum())(q)
    g2 = jax.grad(lambda q: dot_product_attention(
        q, k, v, mask=keep[:, None, None, :]).sum())(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=2e-4)


def test_flash_routes_key_padding_to_blockwise():
    """flash_attention with a key-padding mask must agree with dense
    (routed through the blockwise path, not the dense fallback)."""
    rs = np.random.RandomState(15)
    q = jnp.asarray(rs.randn(1, 2, 64, 16), jnp.float32)
    k = jnp.asarray(rs.randn(1, 2, 64, 16), jnp.float32)
    v = jnp.asarray(rs.randn(1, 2, 64, 16), jnp.float32)
    keep = jnp.asarray(rs.rand(1, 64) > 0.4).at[:, 0].set(True)
    ref = dot_product_attention(q, k, v, mask=keep[:, None, None, :])
    out = flash_attention(q, k, v, mask=keep[:, None, None, :])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_segments_matches_dense(causal):
    """In-kernel segment masking == dense path with make_segment_mask,
    forward and gradients, on live (non-padding) positions."""
    from bigdl_tpu.nn.attention import (dot_product_attention,
                                        make_segment_mask)

    rs = np.random.RandomState(0)
    b, h, s, d = 2, 3, 128, 32
    q = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    segs = np.zeros((b, s), np.int32)
    segs[0, :50] = 1
    segs[0, 50:120] = 2          # row 0: two docs + 8 pad
    segs[1, :] = 1               # row 1: one full doc
    segs = jnp.asarray(segs)
    live = np.asarray(segs) != 0

    out = flash_attention(q, k, v, causal=causal, segments=segs,
                          block_q=32, block_k=32)
    want = dot_product_attention(q, k, v, causal=causal,
                                 mask=make_segment_mask(segs))
    np.testing.assert_allclose(np.asarray(out)[:, :, live[0], :][0],
                               np.asarray(want)[:, :, live[0], :][0],
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(out)[1], np.asarray(want)[1],
                               atol=2e-5)

    # gradients: weight the loss by liveness so padding rows (whose
    # conventions differ between the two paths) don't contribute
    w = jnp.asarray(live, jnp.float32)[:, None, :, None]

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, segments=segs,
                            block_q=32, block_k=32)
        return jnp.sum(jnp.square(o * w))

    def loss_dense(q, k, v):
        o = dot_product_attention(q, k, v, causal=causal,
                                  mask=make_segment_mask(segs))
        return jnp.sum(jnp.square(o * w))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, c, n in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   atol=3e-5, err_msg=f"d{n}")


def test_flash_segments_through_mha_and_lm():
    """Integer mask input routes segments into the flash kernel via MHA,
    and the packed TransformerLM path stays isolated across documents."""
    from bigdl_tpu import nn as bnn

    mha = bnn.MultiHeadAttention(16, 2, causal=True, attn_impl="flash")
    params = mha.init(jax.random.PRNGKey(0))
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(1, 64, 16), jnp.float32)
    segs = jnp.asarray(np.repeat([[1, 2]], 32, axis=1).reshape(1, 64))
    o = mha.forward(params, (x, x, segs))
    # perturb the second document; first document's outputs must not move
    x2 = x.at[:, 32:].add(1.0)
    segs_sorted = jnp.asarray([([1] * 32) + ([2] * 32)])
    o1 = mha.forward(params, (x, x, segs_sorted))
    o2 = mha.forward(params, (x2, x2, segs_sorted))
    np.testing.assert_allclose(np.asarray(o1[:, :32]),
                               np.asarray(o2[:, :32]), atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_segments_matches_dense(causal):
    """O(seq) blockwise path with segments == dense block-diagonal mask
    on live positions (fwd + grads)."""
    from bigdl_tpu.nn.attention import (dot_product_attention,
                                        make_segment_mask)
    from bigdl_tpu.ops import blockwise_attention

    rs = np.random.RandomState(7)
    b, h, s, d = 2, 2, 64, 16
    q = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    segs = np.zeros((b, s), np.int32)
    segs[0, :20] = 1
    segs[0, 20:60] = 2
    segs[1, :] = 1
    segs = jnp.asarray(segs)
    live = np.asarray(segs) != 0
    w = jnp.asarray(live, jnp.float32)[:, None, :, None]

    out = blockwise_attention(q, k, v, causal=causal, segments=segs,
                              block_k=16)
    want = dot_product_attention(q, k, v, causal=causal,
                                 mask=make_segment_mask(segs))
    np.testing.assert_allclose(np.asarray(out * w), np.asarray(want * w),
                               atol=2e-5)

    g1 = jax.grad(lambda q, k, v: jnp.sum(jnp.square(
        blockwise_attention(q, k, v, causal=causal, segments=segs,
                            block_k=16) * w)), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: jnp.sum(jnp.square(
        dot_product_attention(q, k, v, causal=causal,
                              mask=make_segment_mask(segs)) * w)),
        argnums=(0, 1, 2))(q, k, v)
    for a, c in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   atol=3e-5)


def test_flash_segments_with_q_padding():
    """s=160 with block_q=128 pads queries to 256 inside the kernel; a
    row whose segments are all nonzero then has fully-masked padded query
    rows — gradients must stay finite and match dense on live positions
    (the explicit p-re-zeroing after exp() is what keeps inf*0 out)."""
    from bigdl_tpu.nn.attention import (dot_product_attention,
                                        make_segment_mask)

    rs = np.random.RandomState(3)
    b, h, s, d = 2, 2, 160, 32
    q = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    segs = np.ones((b, s), np.int32)     # row 0: one full doc, no padding
    segs[1, :80] = 1
    segs[1, 80:] = 2
    segs = jnp.asarray(segs)

    out = flash_attention(q, k, v, causal=True, segments=segs,
                          block_q=128, block_k=32)
    want = dot_product_attention(q, k, v, causal=True,
                                 mask=make_segment_mask(segs))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5)

    g = jax.grad(lambda q, k, v: jnp.sum(jnp.square(flash_attention(
        q, k, v, causal=True, segments=segs, block_q=128, block_k=32))),
        argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: jnp.sum(jnp.square(
        dot_product_attention(q, k, v, causal=True,
                              mask=make_segment_mask(segs)))),
        argnums=(0, 1, 2))(q, k, v)
    for a, c in zip(g, gd):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   atol=5e-5)


def test_flash_segments_bf16():
    """Segments path in bf16 (the production dtype) stays close to the
    f32 dense reference."""
    from bigdl_tpu.nn.attention import (dot_product_attention,
                                        make_segment_mask)

    rs = np.random.RandomState(9)
    b, h, s, d = 1, 2, 128, 64
    q = jnp.asarray(rs.randn(b, h, s, d), jnp.bfloat16)
    k = jnp.asarray(rs.randn(b, h, s, d), jnp.bfloat16)
    v = jnp.asarray(rs.randn(b, h, s, d), jnp.bfloat16)
    segs = jnp.asarray(np.repeat([[1, 2]], 64, axis=1).reshape(1, 128))
    out = flash_attention(q, k, v, causal=True, segments=segs,
                          block_q=32, block_k=32)
    want = dot_product_attention(
        q.astype(jnp.float32), k.astype(jnp.float32),
        v.astype(jnp.float32), causal=True,
        mask=make_segment_mask(segs))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), atol=5e-2)


def test_block_specs_satisfy_mosaic_tiling(fresh_traces):
    """Static Mosaic tiling lint, no TPU needed: intercept every
    pallas_call the flash kernels make and check each block's last two
    dims are (8k, 128k)-aligned or equal to the array dims — the exact
    rule the first on-chip run failed (interpret mode never checks it)."""
    from unittest import mock

    from jax.experimental import pallas as real_pl

    captured = []
    real_call = real_pl.pallas_call

    def spy(kernel, **kw):
        grid_spec = kw["grid_spec"]
        in_specs = grid_spec.in_specs
        out_specs = grid_spec.out_specs
        out_shape = kw.get("out_shape")
        outs = out_specs if isinstance(out_specs, (list, tuple)) \
            else [out_specs]
        shapes = out_shape if isinstance(out_shape, (list, tuple)) \
            else [out_shape]
        inner = real_call(kernel, **kw)

        def wrapped(*args):
            # the scalar-prefetched pair tables come first and have no spec
            blocked = args[grid_spec.num_scalar_prefetch:]
            for spec, arr in list(zip(in_specs, blocked)) + [
                    (s, sh) for s, sh in zip(outs, shapes)]:
                if spec is None:
                    continue
                captured.append((tuple(spec.block_shape),
                                 tuple(arr.shape)))
            return inner(*args)

        return wrapped

    with mock.patch.object(real_pl, "pallas_call", side_effect=spy):
        rs = np.random.RandomState(0)
        q = jnp.asarray(rs.randn(1, 2, 256, 32), jnp.float32)
        segs = jnp.asarray(np.r_[[1] * 100, [2] * 156][None].repeat(1, 0))
        # block_k=32 gets clamped to the lane-legal 128 for the
        # kv-segment layout; the specs captured here are the clamped ones
        jax.grad(lambda q: flash_attention(
            q, q, q, causal=True, segments=segs, block_q=128,
            block_k=32).sum())(q)
        # small-seq padded-q kernel case (bk == s_k escape, bq pads)
        q2 = jnp.asarray(rs.randn(1, 2, 60, 32), jnp.float32)
        segs2 = jnp.asarray(np.r_[[1] * 40, [2] * 20][None])
        jax.grad(lambda q: flash_attention(
            q, q, q, causal=True, segments=segs2, block_q=32,
            block_k=64).sum())(q2)
        jax.grad(lambda q: flash_attention(
            q, q, q, causal=True, block_q=128, block_k=32).sum())(q)

    assert len(captured) >= 15, f"spy captured too little: {len(captured)}"
    # ONE source of truth for tile-shape legality: the same checker
    # tpulint's tile-min rule evaluates (ISSUE 4 satellite — this loop
    # used to be copied per kernel test file)
    from bigdl_tpu.analysis.rules import assert_blocks_tileable
    assert_blocks_tileable(captured, jnp.float32)


@pytest.mark.parametrize("causal", [False, True])
def test_segment_padding_rows_agree_across_paths(causal):
    """ADVICE r3: the same flash_attention(..., segments=...) call used to
    return different values at id-0 padding positions depending on
    shape-driven path selection (in-kernel: live self-attending rows;
    dense fallback: zeroed rows). All paths must now return ZERO there."""
    from bigdl_tpu.nn.attention import (dot_product_attention,
                                        make_segment_mask)
    from bigdl_tpu.ops import blockwise_attention

    rs = np.random.RandomState(7)
    b, h, s, d = 2, 2, 128, 16
    q = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    segs = np.zeros((b, s), np.int32)
    segs[0, :100] = 1
    segs[1, :64] = 1
    segs[1, 64:90] = 2
    segs = jnp.asarray(segs)
    pad = np.asarray(segs) == 0

    kernel = np.asarray(flash_attention(q, k, v, causal=causal,
                                        segments=segs, block_k=128))
    dense = np.asarray(dot_product_attention(
        q, k, v, causal=causal, mask=make_segment_mask(segs)))
    blockwise = np.asarray(blockwise_attention(q, k, v, causal=causal,
                                               segments=segs, block_k=32))
    # ragged s_k forces flash_attention's dense fallback: same call, other
    # path — use s=120 variant
    q2, k2, v2 = q[:, :, :120], k[:, :, :120], v[:, :, :120]
    fallback = np.asarray(flash_attention(q2, k2, v2, causal=causal,
                                          segments=segs[:, :120],
                                          block_k=33))

    for name, out in [("kernel", kernel), ("dense", dense),
                      ("blockwise", blockwise)]:
        assert np.all(out[:, :, pad[0], :][0] == 0), name
        np.testing.assert_allclose(out, dense, atol=2e-5, err_msg=name)
    pad2 = np.asarray(segs[:, :120]) == 0
    assert np.all(fallback[0][:, pad2[0], :] == 0)

    # backward stays finite through the zeroed rows
    g = jax.grad(lambda a, b_, c: jnp.sum(jnp.square(flash_attention(
        a, b_, c, causal=causal, segments=segs))), argnums=(0, 1, 2))(q, k, v)
    for t in g:
        assert np.all(np.isfinite(np.asarray(t)))


def test_default_blocks_clamp_for_mid_sequences():
    """The 512 defaults must not demote a 128-tileable sequence (768,
    1920, ...) to the dense fallback, and — ADVICE r5 #2 — block_q must
    clamp the same way block_k does, so s=768 runs three real 256-blocks
    instead of padding q 768→1024 (~33% extra q-block work whose padded
    rows the declared CostEstimate used to count). Proven by the causal
    FLOPs count matching the UNPADDED 256-block live-pair formula (the
    dense path would count full s^2; the old padded geometry would count
    q rows 768..1023)."""
    from bigdl_tpu.ops.attention_kernel import (_clamp_block,
                                                _live_block_pairs)
    from bigdl_tpu.utils.flops import fn_flops

    b, h, s, d = 1, 2, 768, 64
    assert _clamp_block(512, s) == 256  # both dims, same rule
    q = jnp.ones((b, h, s, d), jnp.float32)
    got = fn_flops(lambda q: flash_attention(q, q, q, causal=True), q)
    pairs = _live_block_pairs(s, s, 256, 256, True, 0)
    expect = 2 * (2.0 * b * h * pairs * 256 * 256 * d)
    np.testing.assert_allclose(got, expect, rtol=1e-6)
    dense_count = 2 * (2.0 * b * h * s * s * d)
    assert abs(got - dense_count) / dense_count > 0.05
    # padded-geometry count (the pre-fix behavior) must NOT match either
    padded = 2 * (2.0 * b * h * _live_block_pairs(1024, s, 512, 128,
                                                  True, 0) * 512 * 128 * d)
    assert abs(got - padded) / padded > 0.05


# ---------------------------------------------------------------------------
# the kernels follow the causal structure (PR 37): a flattened grid of the
# live block pairs, a masked body only where the mask can touch a pair
# ---------------------------------------------------------------------------
_SHAPES = [(512, 512), (1024, 1024), (512, 1536), (256, 1024)]
_BLOCKS = [(128, 128), (256, 256), (512, 512), (128, 512), (512, 128),
           (256, 128)]
# (causal, segments, window): the calls the kernels serve
_MASKS = [(True, False, None), (False, False, None), (True, True, None),
          (False, True, None), (True, False, 128), (True, False, 384)]


# the row-chunk loop of a step's body: off (the tile is one chunk at these
# blocks) and on, at 64 rows a chunk (``_STEP_TILE`` planted at 64 x bk)
_LOOP_BLOCKS = [(128, 128), (256, 128), (128, 512)]


def _pair_cases(masks):
    cases = [(sq, sk, bq, bk, None) for sq, sk in _SHAPES
             for bq, bk in _BLOCKS]
    cases += [(sq, sk, bq, bk, 64) for sq, sk in _SHAPES
              for bq, bk in _LOOP_BLOCKS]
    # 1,024-blocks: 512 rows a chunk as shipped, and 64
    cases += [(2048, 2048, 1024, 1024, None), (2048, 2048, 1024, 1024, 64)]
    for sq, sk, bq, bk, rows in cases:
        for causal, seg, window in masks:
            if seg and sq != sk:
                continue  # segments are self-attention's
            yield pytest.param(
                sq, sk, bq, bk, rows, causal, seg, window,
                id=f"{sq}x{sk}-b{bq}x{bk}-"
                   f"{'causal' if causal else 'full'}"
                   f"{'-seg' if seg else ''}"
                   f"{'' if window is None else f'-w{window}'}"
                   f"{'' if rows is None else f'-rows{rows}'}")


def _plant_rows(monkeypatch, ak, rows, bk):
    if rows is not None:
        monkeypatch.setattr(ak, "_STEP_TILE", rows * bk)


def _pair_inputs(sq, sk, seg, dtype, d=64, h=1, seed=21):
    """Heads of 64: the scale is 1/8, so no backend's contraction of
    ``s * scale - m`` into one rounding can tell the two bodies apart."""
    rs = np.random.RandomState(seed)
    mk = lambda s: jnp.asarray(rs.randn(1, h, s, d), dtype)
    segs = None
    if seg:
        cuts = [sq // 3, sq // 3 + 77]
        segs = jnp.asarray(np.r_[[1] * cuts[0], [2] * cuts[1],
                                 [3] * (sq - sum(cuts))][None])
    return mk(sq), mk(sk), mk(sk), mk(sq), segs


@pytest.mark.parametrize("sq,sk,bq,bk,rows,causal,seg,window",
                         list(_pair_cases(_MASKS)))
def test_forward_equals_a_body_that_masks_every_pair(
        monkeypatch, fresh_traces, sq, sk, bq, bk, rows, causal, seg,
        window):
    """``out`` and ``lse`` bit for bit against the reference form, in which
    ``_block_valid`` is applied on every live pair over the whole tile: an
    interior pair's mask is all true, so leaving it out changes no bit,
    and rows are independent, so walking them in chunks changes none."""
    from bigdl_tpu.ops import attention_kernel as ak

    q, k, v, _, segs = _pair_inputs(sq, sk, seg, jnp.bfloat16)
    call = lambda: ak._flash_fwd(q, k, v, causal, bq, bk, segments=segs,
                                 window=window)
    with monkeypatch.context() as planted:
        _plant_rows(planted, ak, rows, bk)
        assert ak._chunk_rows(bq, bk) == (rows or min(bq, 512 * 1024 // bk))
        out, lse = call()
    fresh_traces()
    monkeypatch.setattr(ak, "_pair_masked",
                        lambda causal, has_seg, *a: causal or has_seg)
    monkeypatch.setattr(ak, "_STEP_TILE", bq * bk)
    ref_out, ref_lse = call()
    assert np.array_equal(np.asarray(out, np.float32),
                          np.asarray(ref_out, np.float32))
    assert np.array_equal(np.asarray(lse), np.asarray(ref_lse))
    # and the reference form is the dense path's numbers
    mask = None
    if seg:
        from bigdl_tpu.nn.attention import make_segment_mask
        mask = make_segment_mask(segs)
    if window is not None:
        from bigdl_tpu.ops.attention_kernel import band_mask
        mask, causal = band_mask(sq, sk, window), False
    want = dot_product_attention(*(x.astype(jnp.float32) for x in (q, k, v)),
                                 causal=causal, mask=mask)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), atol=5e-2)


def test_forward_split_at_heads_of_128_is_within_one_rounding():
    """At heads of 128 the scale is not a power of two; the CPU backend may
    then round ``s * scale - m`` once in the unmasked body and twice in
    the masked one. On the chip the two are bit-equal (PERF.md §6, PR 37)."""
    from bigdl_tpu.ops import attention_kernel as ak

    q, k, v, _, _ = _pair_inputs(512, 512, False, jnp.bfloat16, d=128)
    out, lse = ak._flash_fwd(q, k, v, True, 128, 128)
    want = dot_product_attention(*(x.astype(jnp.float32) for x in (q, k, v)),
                                 causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), atol=5e-2)
    dense_lse = jax.nn.logsumexp(jnp.where(
        jnp.tril(jnp.ones((512, 512), bool)),
        jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(128.0), -jnp.inf), -1)
    np.testing.assert_allclose(np.asarray(lse).reshape(1, 1, 512),
                               np.asarray(dense_lse), atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("sq,sk,bq,bk,rows,causal,seg,window",
                         list(_pair_cases(_MASKS[:4])))
def test_backward_over_block_pairs_matches_dense_grad(
        monkeypatch, fresh_traces, sq, sk, bq, bk, rows, causal, seg,
        window):
    """dq, dk, dv (the scale applied once at ``_emit``; dk and dv summed
    over a tile's row chunks) against ``jax.grad`` of the dense float32
    reference."""
    from bigdl_tpu.ops import attention_kernel as ak

    _plant_rows(monkeypatch, ak, rows, bk)
    q, k, v, g, segs = _pair_inputs(sq, sk, seg, jnp.float32)
    mask = None
    if seg:
        from bigdl_tpu.nn.attention import make_segment_mask
        mask = make_segment_mask(segs)

    def scalar(f):
        return lambda q, k, v: jnp.vdot(f(q, k, v), g)

    got = jax.grad(scalar(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, segments=segs, block_q=bq, block_k=bk)),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(scalar(lambda q, k, v: dot_product_attention(
        q, k, v, causal=causal, mask=mask)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_backward_bf16_at_heads_of_128_matches_dense_grad():
    """bf16 at the benchmark's head size, where the scale at ``_emit`` is a
    rounding of its own (1/sqrt(128) is no power of two)."""
    q, k, v, g, _ = _pair_inputs(512, 1536, False, jnp.bfloat16, d=128)
    f32 = lambda x: x.astype(jnp.float32)

    def scalar(f):
        return lambda q, k, v: jnp.vdot(f32(f(q, k, v)), f32(g))

    got = jax.grad(scalar(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=256, block_k=512)),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(scalar(lambda q, k, v: dot_product_attention(
        q, k, v, causal=True)), argnums=(0, 1, 2))(f32(q), f32(k), f32(v))
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b), atol=5e-2)


@pytest.mark.parametrize("sq,sk,bq,bk,causal,window", [
    (512, 512, 128, 128, True, None),
    (1024, 1024, 256, 128, True, None),
    (512, 1536, 128, 256, True, None),    # q_offset 1,024
    (256, 1024, 128, 512, True, None),
    (512, 256, 128, 128, True, None),     # s_q > s_k: rows with no key
    (512, 512, 128, 256, False, None),
    (1024, 1024, 128, 128, True, 384),
    (512, 1536, 256, 128, True, 128),
])
def test_live_block_pairs_counts_body_executions(monkeypatch, fresh_traces,
                                                 sq, sk, bq, bk, causal,
                                                 window):
    """``_live_block_pairs`` and the pair table against the kernels as they
    run: every step body calls ``_block_valid`` once, and interpret mode
    runs a host callback planted there."""
    from bigdl_tpu.ops import attention_kernel as ak

    ran = []
    block_valid = ak._block_valid

    def counted(*a, **kw):
        jax.debug.callback(lambda: ran.append(1))
        return block_valid(*a, **kw)

    monkeypatch.setattr(ak, "_block_valid", counted)
    q, k, v, g, _ = _pair_inputs(sq, sk, False, jnp.float32, d=16, h=2)
    out, lse = ak._flash_fwd(q, k, v, causal, bq, bk, window=window)
    jax.block_until_ready(out)
    jax.effects_barrier()
    pairs = ak._live_block_pairs(sq, sk, bq, bk, causal, sk - sq, window)
    assert len(ran) == 2 * pairs  # two (batch, head) rows
    qt, kt = ak._pair_table(sq // bq, sk // bk, bq, bk, causal, sk - sq,
                            window)
    dead = sum(1 for j in range(sq // bq)
               if causal and sk - sq + (j + 1) * bq - 1 < 0)
    assert len(qt) == pairs + dead
    if window is None:
        del ran[:]
        jax.block_until_ready(ak._flash_bwd(q, k, v, out, lse, g, causal,
                                            bq, bk))
        jax.effects_barrier()
        assert len(ran) == 2 * 2 * pairs  # dq and dk/dv, two rows each
        by_key = ak._pair_table(sq // bq, sk // bk, bq, bk, causal, sk - sq,
                                by_key=True)
        live = lambda t: {(j, kk) for j, kk in zip(*t) if ak._pair_live(
            causal, j, kk, bq, bk, sk - sq)}
        assert live(by_key) == live((qt, kt)) and len(live(by_key)) == pairs


@pytest.mark.parametrize("kw,want", [
    (dict(block_q=512, block_k=512), (36, 36, 8)),   # the parent: 64/36/36
    (dict(), (10, 10, 4)),                           # 1,024-blocks
    (dict(causal=False), (16, 16, 0)),
    (dict(s=8192, window=4096, block_q=512, block_k=512), (108, 108, 24)),
    (dict(s=8192, window=4096), (30, 30, 12)),
])
def test_flash_block_plan_counts_steps_and_masked_pairs(kw, want):
    """How often the mechanism engages, without tracing: the forward's grid
    steps, live pairs and masked pairs per (row, head)."""
    from bigdl_tpu.ops.attention_kernel import (_live_block_pairs,
                                                flash_block_plan)

    kw = dict(kw)
    s, causal = kw.pop("s", 4096), kw.pop("causal", True)
    plan = flash_block_plan(s, s, 128, causal, jnp.bfloat16, **kw)
    assert (plan["grid_steps"], plan["live_pairs"],
            plan["masked_pairs"]) == want
    assert plan["live_pairs"] == _live_block_pairs(
        s, s, plan["block_q"], plan["block_k"], causal, 0, kw.get("window"))


@pytest.mark.parametrize("sq,sk,d,want", [
    (4096, 4096, 128, (1024, 1024)),
    (2048, 2048, 64, (1024, 1024)),
    (1024, 3072, 128, (1024, 1024)),
    (4096, 4096, 256, (512, 512)),    # wide heads: under 4% either way
    (512, 2048, 128, (512, 512)),     # a suffix of 512 rows
    (768, 768, 64, (256, 256)),       # mid sequences clamp as they did
    (1536, 1536, 128, (512, 512)),
])
def test_default_blocks_are_1024_where_the_chip_showed_them_to_win(
        sq, sk, d, want):
    from bigdl_tpu.ops.attention_kernel import _resolve_blocks

    assert _resolve_blocks(sq, sk, d, True, jnp.bfloat16, None, None) == want
    # an explicit block still wins
    assert _resolve_blocks(sq, sk, d, True, jnp.bfloat16, 128, 128) == \
        (128, 128)


def test_pair_table_refuses_what_scalar_memory_cannot_hold():
    from bigdl_tpu.ops.attention_kernel import _MAX_PAIRS, _pair_table

    qt, _ = _pair_table(256, 256, 512, 512, False, 0)
    assert len(qt) == _MAX_PAIRS
    with pytest.raises(ValueError, match="block pairs"):
        _pair_table(257, 256, 512, 512, False, 0)


# ---------------------------------------------------------------------------
# what a program pays for the kernels (PR 38): only the bodies a call's pair
# table uses, and one kernel a signature however many layers call it
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("sq,sk,kw,bodies", [
    (512, 512, dict(causal=True), 1),               # one block pair: masked
    (64, 64, dict(causal=True), 1),                 # a 64-row bucket
    (1024, 1024, dict(causal=True, block_q=1024, block_k=256), 1),
    (1024, 1024, dict(causal=True, segments=True, block_q=256,
                      block_k=256), 1),             # packed: all masked
    (1024, 1024, dict(causal=False, segments=True, block_q=256,
                      block_k=256), 1),
    (1024, 1024, dict(causal=False, block_q=256, block_k=256), 1),  # none
    (1024, 1024, dict(causal=True, block_q=256, block_k=256), 2),
    (256, 1024, dict(causal=True, block_q=256, block_k=256), 2),   # suffix
    (2048, 2048, dict(causal=True), 2),             # 1,024-blocks
], ids=["one_pair", "bucket_64", "all_diagonal", "packed", "packed_full",
        "full", "causal", "suffix", "causal_1024"])
def test_a_kernel_holds_only_the_bodies_its_table_uses(sq, sk, kw, bodies):
    """The pair table is static: where every live pair is masked, or none
    is, the kernel is lowered with the one ``_step`` body its table uses.
    Counted in the jaxpr by its matmuls: two a body in ``flash_fwd``, three
    in ``flash_dq``, four in ``flash_dkv``."""
    kw = dict(kw)
    q, k, v, g, segs = _pair_inputs(sq, sk, kw.pop("segments", False),
                                    jnp.float32)
    f = lambda q, k, v: flash_attention(q, k, v, segments=segs, **kw)
    assert str(jax.make_jaxpr(f)(q, k, v)).count("dot_general") == 2 * bodies
    back = jax.grad(lambda q, k, v: (f(q, k, v) * g).sum(),
                    argnums=(0, 1, 2))
    assert str(jax.make_jaxpr(back)(q, k, v)).count("dot_general") == \
        9 * bodies


@pytest.mark.parametrize("window,bodies", [(128, 1), (384, 2), (4096, 2)])
def test_the_windowed_forward_holds_the_bodies_its_band_uses(window, bodies):
    """A window of one block: every pair of the band is crossed by the
    diagonal or the far edge, one body; a wider band has interior pairs."""
    q, k, v, _, _ = _pair_inputs(1024, 1024, False, jnp.float32)
    text = str(jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, block_q=128, block_k=128))(
            q, k, v))
    assert "flash_fwd_window" in text
    assert text.count("dot_general") == 2 * bodies


@pytest.mark.parametrize("sq,sk,bq,bk,causal,seg,window,want", [
    (512, 512, 512, 512, True, False, None, True),
    (1024, 1024, 256, 256, True, False, None, None),
    (1024, 1024, 256, 256, False, False, None, False),
    (1024, 1024, 256, 256, False, True, None, True),
    (1024, 1024, 256, 256, True, True, None, True),
    (512, 256, 128, 128, True, False, None, None),   # rows with no key
    (1024, 1024, 128, 128, True, False, 128, True),  # a band of two edges
    (1024, 1024, 128, 128, True, False, 384, None),
])
def test_table_masked_is_pair_masked_over_the_live_pairs(sq, sk, bq, bk,
                                                         causal, seg, window,
                                                         want):
    from bigdl_tpu.ops import attention_kernel as ak

    off = sk - sq
    for by_key in (False, True) if window is None else (False,):
        tables = ak._pair_table(sq // bq, sk // bk, bq, bk, causal, off,
                                window, by_key=by_key)
        kinds = {bool(ak._pair_masked(causal, seg, j, kk, bq, bk, off,
                                      window))
                 for j, kk in zip(*tables)
                 if ak._pair_live(causal, j, kk, bq, bk, off)}
        assert ak._table_masked(tables, causal, seg, bq, bk, off,
                                window) is want
        assert kinds == ({True, False} if want is None else {want})


@pytest.mark.parametrize("layers", [1, 4])
@pytest.mark.parametrize("wrap", ["plain", "remat", "grad"])
def test_layers_share_one_trace_of_each_kernel(layers, wrap):
    """A Python loop of layers traces each distinct kernel once: the
    jaxpr names one ``_fwd_program`` closed jaxpr however many layers
    call it, under ``jax.checkpoint`` and ``jax.grad`` as under nothing."""
    q, k, v, g, _ = _pair_inputs(512, 512, False, jnp.float32)

    def model(q, k, v):
        x = q
        for _ in range(layers):
            x = flash_attention(x, k, v, causal=True, block_q=128,
                                block_k=128)
        return x

    if wrap == "remat":
        fn = jax.grad(lambda q, k, v: jnp.vdot(
            jax.checkpoint(model)(q, k, v), g), argnums=(0, 1, 2))
    elif wrap == "grad":
        fn = jax.grad(lambda q, k, v: jnp.vdot(model(q, k, v), g),
                      argnums=(0, 1, 2))
    else:
        fn = model
    got = fn(q, k, v)
    lowered = jax.jit(fn).lower(q, k, v).as_text()
    # interpret mode lowers the kernel's body inline: one private function
    # a kernel, a call a layer
    n_fwd = lowered.count("func.func private @_fwd_program")
    n_bwd = lowered.count("func.func private @_bwd_program")
    assert n_fwd == 1 and n_bwd == (0 if wrap == "plain" else 1)
    want = dot_product_attention(q, k, v, causal=True)
    for _ in range(layers - 1):
        want = dot_product_attention(want, k, v, causal=True)
    if wrap == "plain":
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4)
    else:
        assert all(np.isfinite(np.asarray(a)).all() for a in got)

"""Differential attention alone, against the plain reference's own
``_diff_attention`` (which splits 64-wide heads as the paper does, where
the program works on 128-wide pairs): with and without a window, the
banded form against the dense one, decode through a ring and through a
full cache, and the Q-only cross layer reading another layer's cache.
Float32 on the CPU."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmark.lib.model import load_reference  # noqa: E402

REF = load_reference({"reference": "phi4_mini_flash"})
# program and reference differ in the order of float32 sums only (pairs
# against split heads, blocks against one square): 5e-7 seen, of the scale
TOL = 2e-5
D, HEADS, KV = 48, 8, 4  # head_dim 6, pairs of 12, query group 2


def rel(a, b):
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


def layer(depth=3, **kw):
    m = nn.DifferentialAttention(D, HEADS, KV, depth=depth, **kw)
    p = m.init(jax.random.PRNGKey(depth))
    # biases and the sub-norm's weight away from their 0 / 1 defaults, so
    # that a dropped one shows
    ks = jax.random.split(jax.random.PRNGKey(100 + depth), len(p))
    for k, name in zip(ks, sorted(p)):
        if name.startswith("b"):
            p[name] = 0.1 * jax.random.normal(k, p[name].shape)
    p["ln_sub"]["weight"] = 1.0 + 0.1 * jax.random.normal(ks[0], (12,))
    return m, p


def want(p, x, depth, window, kv=None):
    with jax.default_matmul_precision("highest"):
        return REF._diff_attention(p, x, kv, jnp.float32(depth), HEADS, KV,
                                   window)


def test_initialisation_and_lambda():
    m, p = layer(depth=5)
    assert m.lam0 == pytest.approx(0.8 - 0.6 * np.exp(-1.5))
    assert {k: v.shape for k, v in p.items() if k.startswith("l")
            and k != "ln_sub"} == {n: (6,) for n in
                                   ("lq1", "lk1", "lq2", "lk2")}
    assert p["ln_sub"]["weight"].shape == (12,)
    cross = nn.DifferentialAttention(D, HEADS, KV, depth=5, cross=True)
    assert "wk" not in cross.init(jax.random.PRNGKey(0))


@pytest.mark.parametrize("window,length", [(None, 19), (8, 7), (8, 8),
                                           (8, 9), (8, 29), (4, 32)])
def test_whole_sequence_is_the_reference(window, length):
    """Lengths below, at and past the window: past it the program attends
    in bands of two blocks, the reference over the whole square."""
    m, p = layer(window=window)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, length, D))
    got = m.forward(p, x)
    for b in range(2):
        out, _ = want(p, x[b], 3, window)
        assert rel(got[b], out) < TOL


def test_window_masks_what_lies_behind_it():
    """Changing a token more than ``window`` positions back moves
    nothing; the same change inside the window does."""
    m, p = layer(window=8)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 30, D))
    base = m.forward(p, x)[0, -1]
    far = m.forward(p, x.at[0, 30 - 9].add(1.0))[0, -1]
    near = m.forward(p, x.at[0, 30 - 8].add(1.0))[0, -1]
    assert float(jnp.abs(far - base).max()) == 0.0
    assert float(jnp.abs(near - base).max()) > 1e-4


@pytest.mark.parametrize("window,prompt,bucket", [(None, 13, 16),
                                                  (8, 5, 8), (8, 13, 16),
                                                  (8, 21, 32), (8, 16, 16)])
def test_prefill_then_decode_is_the_whole_sequence(window, prompt, bucket):
    """A prompt padded to its bucket, then 11 decode steps through the
    cache (a ring of 8 rows that wraps, or 48 full rows), against the
    reference on the whole sequence."""
    m, p = layer(window=window)
    steps = 11
    x = jax.random.normal(jax.random.PRNGKey(3), (1, bucket + steps, D))
    seq = jnp.concatenate([x[:, :prompt], x[:, bucket:]], 1)
    ref, _ = want(p, seq[0], 3, window)
    cache = m.init_cache(1, 48)
    assert cache["k"].shape == (1, KV // 2, window or 48, 12)
    out, cache, _ = m.prefill(p, x[:, :bucket], cache,
                              jnp.int32(prompt - 1))
    assert rel(out[0, :prompt], ref[:prompt]) < TOL
    for t in range(steps):
        o, cache = m.decode_step(p, seq[:, prompt + t:prompt + t + 1],
                                 cache, jnp.int32(prompt + t))
        assert rel(o[0, 0], ref[prompt + t]) < TOL, t


def test_cross_layer_reads_the_shared_cache():
    """Q alone is projected from x; K and V are the full layer's, whole
    sequence and one decode step against that layer's cache."""
    full, pf = layer(depth=5)
    cross, pc = layer(depth=7, cross=True)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 12, D))
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 12, D))
    _, kv = want(pf, x[0], 5, None)
    ref, _ = want(pc, h[0], 7, None, kv)
    _, cache, (k, v) = full.prefill(pf, x[:, :11], full.init_cache(1, 16))
    assert rel(cross.forward(pc, (h[:, :11], k, v))[0], ref[:11]) < TOL
    _, cache = full.decode_step(pf, x[:, 11:], cache, jnp.int32(11))
    got, same = cross.decode_step(pc, h[:, 11:], cache, jnp.int32(11))
    assert same is cache  # a cross layer writes nothing
    assert rel(got[0, 0], ref[11]) < TOL


def test_flash_path_is_the_dense_one():
    """``attn_impl="flash"`` (the kernel in interpret mode here) on a
    full layer: q scaled by sqrt(2) for the kernel's 1/sqrt(128)."""
    m, p = layer()
    mf = nn.DifferentialAttention(D, HEADS, KV, depth=3, attn_impl="flash")
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 32, D))
    assert rel(mf.forward(p, x), m.forward(p, x)) < TOL


@pytest.mark.parametrize("bad", [dict(num_heads=7), dict(num_kv_heads=3),
                                 dict(num_heads=12, num_kv_heads=8),
                                 dict(attn_impl="ring")])
def test_refuses_what_it_cannot_pair(bad):
    kw = dict(d_model=48, num_heads=8, num_kv_heads=4, depth=0)
    with pytest.raises(ValueError):
        nn.DifferentialAttention(**dict(kw, **bad))

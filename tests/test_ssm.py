"""The state-space mixers alone, against their equations: the Mamba-1
recurrence in its three forms (chunked scan = sequential scan = one
decode step a token), the mixer against the plain reference's own
``_mamba``, the state a padded prefill hands over, and the gated memory
unit. Float32 on the CPU throughout."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.nn import ssm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmark.lib.model import load_reference  # noqa: E402

REF = load_reference({"reference": "phi4_mini_flash"})
# the forms differ in the order of float32 products and sums only; the
# largest difference seen over these sizes is 4e-7 of the output's scale
TOL = 2e-5


def rel(a, b):
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


def scan_inputs(length, di=24, n=4, batch=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (batch, length, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, length, di)))
    a = -jnp.exp(jax.random.normal(ks[2], (n, di)))
    b = jax.random.normal(ks[3], (batch, length, n))
    c = jax.random.normal(ks[4], (batch, length, n))
    s0 = jax.random.normal(ks[5], (batch, n, di))
    return x, dt, a, b, c, s0


@pytest.mark.parametrize("length,chunk", [(64, 16), (37, 8), (5, 64),
                                          (16, 16), (33, 1)])
def test_chunked_scan_is_the_sequential_scan(length, chunk):
    args = scan_inputs(length)
    y_seq, s_seq = ssm.selective_scan_seq(*args)
    y, s = ssm.selective_scan(*args, chunk=chunk)
    assert y.shape == y_seq.shape
    assert rel(y, y_seq) < TOL and rel(s, s_seq) < TOL


def test_rows_of_dt_zero_leave_the_state_as_it_is():
    x, dt, a, b, c, s0 = scan_inputs(24)
    cut = jnp.where((jnp.arange(24) <= 9)[None, :, None], dt, 0.0)
    _, s_cut = ssm.selective_scan(x, cut, a, b, c, s0, chunk=8)
    _, s_ten = ssm.selective_scan_seq(x[:, :10], dt[:, :10], a, b[:, :10],
                                      c[:, :10], s0)
    assert rel(s_cut, s_ten) < TOL


@pytest.fixture(scope="module")
def mamba():
    m = nn.Mamba(32, d_state=4, chunk=8)
    return m, m.init(jax.random.PRNGKey(3))


def test_published_initialisation(mamba):
    m, p = mamba
    assert (m.d_inner, m.dt_rank, m.d_conv) == (64, 2, 4)
    assert p["a_log"].shape == (4, 64)
    np.testing.assert_allclose(np.exp(p["a_log"][:, 0]), [1, 2, 3, 4],
                               rtol=1e-6)
    assert float(p["d"].min()) == 1.0 == float(p["d"].max())
    dt = jax.nn.softplus(p["b_dt"])
    assert 1e-3 * 0.99 <= float(dt.min()) and float(dt.max()) <= 0.1 * 1.01
    assert float(jnp.abs(p["conv_w"]).max()) <= 0.5


def test_mixer_is_the_references_mamba(mamba):
    m, p = mamba
    u = jax.random.normal(jax.random.PRNGKey(4), (1, 29, 32))
    out, y = m.forward(p, u)
    with jax.default_matmul_precision("highest"):
        want_out, want_y, want_state = REF._mamba(p, u[0])
    assert rel(out[0], want_out) < TOL and rel(y[0], want_y) < TOL
    state = m.prefill(p, u, m.init_cache(1))[2]["h"]
    assert rel(state[0], want_state) < TOL


def test_decode_steps_are_the_whole_sequence(mamba):
    m, p = mamba
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 21, 32))
    out, y = m.forward(p, u)
    cache = m.init_cache(2)
    outs, ys = [], []
    for t in range(21):
        o, yt, cache = m.decode_step(p, u[:, t:t + 1], cache)
        outs.append(o)
        ys.append(yt)
    assert rel(jnp.concatenate(outs, 1), out) < TOL
    assert rel(jnp.concatenate(ys, 1), y) < TOL


@pytest.mark.parametrize("last", [0, 1, 2, 12, 23])
def test_padded_prefill_hands_over_the_state_at_last(mamba, last):
    """A prompt of last + 1 tokens, right-padded to 24: the state, the
    convolution's history and every row up to ``last`` are those of the
    unpadded prompt; then one decode step agrees too."""
    m, p = mamba
    u = jax.random.normal(jax.random.PRNGKey(6), (1, 24, 32))
    nxt = jax.random.normal(jax.random.PRNGKey(7), (1, 1, 32))
    out, _, cache = m.prefill(p, u, m.init_cache(1), jnp.int32(last))
    exact = u[:, :last + 1]
    want_out, _, want = m.prefill(p, exact, m.init_cache(1))
    assert rel(out[:, :last + 1], want_out) < TOL
    assert rel(cache["h"], want["h"]) < TOL
    np.testing.assert_allclose(cache["conv"], want["conv"], atol=1e-6)
    got, _, _ = m.decode_step(p, nxt, cache)
    whole, _ = m.forward(p, jnp.concatenate([exact, nxt], 1))
    assert rel(got[:, 0], whole[:, -1]) < TOL


def test_state_is_float32_whatever_the_activations(mamba):
    m, p = mamba
    cache = m.init_cache(3, jnp.bfloat16)
    assert cache["h"].dtype == jnp.float32
    assert cache["h"].shape == (3, 4, 64)
    assert cache["conv"].dtype == jnp.bfloat16
    assert cache["conv"].shape == (3, 3, 64)
    u = jax.random.normal(jax.random.PRNGKey(8), (3, 1, 32), jnp.bfloat16)
    out, y, new = m.decode_step(p, u, cache)
    assert out.dtype == y.dtype == jnp.bfloat16
    assert new["h"].dtype == jnp.float32
    assert new["conv"].dtype == jnp.bfloat16


def test_gated_memory_unit_is_its_equation():
    g = nn.GatedMemoryUnit(16, 32)
    p = g.init(jax.random.PRNGKey(9))
    assert set(p) == {"w1", "w2"}  # no bias
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 5, 16))
    mem = jax.random.normal(jax.random.PRNGKey(11), (2, 5, 32))
    pre = np.asarray(x) @ np.asarray(p["w1"])
    want = (np.asarray(mem) * pre / (1 + np.exp(-pre))) @ np.asarray(p["w2"])
    np.testing.assert_allclose(g.forward(p, (x, mem)), want, rtol=1e-4,
                               atol=1e-6)

"""``ops.cache_write.write_rows``: the batching rule's kernel against
``jax.vmap`` of the plain ``dynamic_update_slice``, bit for bit, and which
form the rule takes for what it is given. CPU, the kernel in interpret
mode, toy sizes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import models
from bigdl_tpu.models import SambaYLM
from bigdl_tpu.ops import cache_write as cw
from bigdl_tpu.serving import DecodeEngine

FULL, RING = (4, 2, 64, 128), (4, 10, 32, 128)


def operands(shape, dtype, m=1, seed=0):
    S, kh, T, d = shape
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    cache = jax.random.normal(k1, (S, 1, kh, T, d), jnp.float32)
    rows = jax.random.normal(k2, (S, 1, kh, m, d), jnp.float32)
    return cache.astype(dtype), rows


def scatter(cache, rows, at):
    return jax.vmap(cw._plain)(cache, rows, at)


def batched(cache, rows, at):
    """``vmap`` of the primitive, and the forms its rule chose."""
    chosen = []
    with cw.step_trace(chosen):
        out = jax.jit(jax.vmap(cw.write_rows))(cache, rows, at)
    return out, chosen


def same(a, b):
    assert a.dtype == b.dtype and a.shape == b.shape
    return bool((np.asarray(a.astype(jnp.float32))
                 == np.asarray(b.astype(jnp.float32))).all())


def positions(kind, dtype, T):
    tile = cw.row_tile(dtype)
    return {"first": [0] * 4, "tile_end": [tile - 1] * 4,
            "tile_start": [tile] * 4, "last": [T - 1] * 4,
            "mixed": [0, tile - 1, tile, T - 1],
            "equal": [5] * 4}[kind]


@pytest.mark.parametrize("where", ["first", "tile_end", "tile_start",
                                   "last", "mixed", "equal"])
@pytest.mark.parametrize("shape", [FULL, RING], ids=["full", "ring"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_kernel_equals_the_scatter(dtype, shape, where):
    cache, rows = operands(shape, dtype)
    at = jnp.asarray(positions(where, dtype, shape[2]), jnp.int32)
    got, chosen = batched(cache, rows, at)
    assert chosen == ["batched"]
    want = scatter(cache, rows, at)
    assert same(got, want)
    # and it wrote: the row is the new one, its neighbours the old
    s, p = 1, int(at[1])
    assert same(got[s, :, :, p], rows[s, :, :, 0].astype(dtype))
    q = p + 1 if p + 1 < shape[2] else p - 1
    assert same(got[s, :, :, q], cache[s, :, :, q])


def test_row_tile_by_dtype():
    assert [cw.row_tile(d) for d in (jnp.float32, jnp.bfloat16, jnp.int8,
                                     jnp.float8_e4m3fn)] == [8, 16, 32, 32]


@pytest.mark.parametrize("at", [[67, -2, 0, 1], [-200, 64, 1000, -1]],
                         ids=["near", "far"])
def test_an_out_of_range_position_lands_where_the_plain_write_puts_it(at):
    cache, rows = operands(FULL, jnp.float32)
    at = jnp.asarray(at, jnp.int32)
    got, chosen = batched(cache, rows, at)
    assert chosen == ["batched"]
    assert same(got, scatter(cache, rows, at))
    for s in range(4):  # which is where one slot's own write puts it
        assert same(got[s], cw._plain(cache[s], rows[s], at[s]))


def test_an_unbatched_position_is_the_plain_write():
    cache, rows = operands(FULL, jnp.bfloat16)
    chosen = []
    with cw.step_trace(chosen):
        fn = jax.vmap(cw.write_rows, in_axes=(0, 0, None))
        jaxpr = str(jax.make_jaxpr(fn)(cache, rows, 7))
        got = fn(cache, rows, 7)
    assert chosen == []  # the rule had nothing to choose
    assert "dynamic_update_slice" in jaxpr
    assert "scatter" not in jaxpr and "cache_write_rows" not in jaxpr
    assert same(got, cw._plain(cache[:, 0], rows[:, 0], 7)[:, None])
    # and with no vmap at all, a traced position included
    one = jax.jit(cw.write_rows)(cache[0], rows[0], jnp.int32(7))
    assert same(one, got[0])


@pytest.mark.parametrize("case", ["chunk_of_3", "ragged_T", "mesh",
                                  "cache_unbatched"])
def test_what_the_kernel_does_not_take_is_the_scatter_as_before(case):
    dtype, m, shape, kw = jnp.bfloat16, 1, FULL, {}
    if case == "chunk_of_3":
        m = 3
    elif case == "ragged_T":
        shape = (4, 2, 24, 128)  # bf16 tiles are 16 rows
    elif case == "mesh":
        kw = {"kernel": False}
    cache, rows = operands(shape, dtype, m=m)
    at = jnp.asarray([0, 15, 16, shape[2] - m], jnp.int32)
    axes = (None, 0, 0) if case == "cache_unbatched" else (0, 0, 0)
    if case == "cache_unbatched":
        cache = cache[0]
    chosen = []
    with cw.step_trace(chosen, **kw):
        fn = jax.vmap(cw.write_rows, in_axes=axes)
        jaxpr = str(jax.make_jaxpr(fn)(cache, rows, at))
        got = fn(cache, rows, at)
    assert chosen == ["scatter"] * 2  # traced twice: the jaxpr, the call
    assert "scatter" in jaxpr and "cache_write_rows" not in jaxpr
    assert same(got, jax.vmap(cw._plain, in_axes=axes)(cache, rows, at))


def test_step_trace_restores_what_it_found():
    outer = []
    with cw.step_trace(outer, kernel=False):
        with cw.step_trace():
            assert cw._trace.kernel and cw._trace.chosen is None
        assert cw._trace.chosen is outer and not cw._trace.kernel
    assert cw._trace.kernel and cw._trace.chosen is None


def primitives(jaxpr):
    """Names of every equation's primitive, sub-jaxprs walked once a
    call (the kernel sits in a jitted function: one body, many calls)."""
    out = []
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += primitives(sub)
    return out


def lm():
    m = models.transformer_lm(50, d_model=32, num_layers=2, num_heads=2,
                              max_len=64)
    return m, m.init(jax.random.PRNGKey(1)), 2 * 2  # K and V of 2 layers


def sambay():
    m = SambaYLM(init_std=0.125, vocab=96, d_model=64, num_layers=8,
                 num_heads=4, num_kv_heads=2, d_ff=128, window=8,
                 mb_per_layer=2, max_len=64)
    # layers 1 and 3 keep rings, layer 5 the shared cache; the cross
    # layers write nothing
    return m, m.init(jax.random.PRNGKey(1)), 2 * 3


def writes(eng):
    """What the write's rule chose (the list also holds the reads')."""
    return [f for f in eng._step_forms if f in ("batched", "scatter")]


@pytest.mark.parametrize("build", [lm, sambay])
def test_a_dense_step_holds_one_kernel_call_a_written_leaf(build):
    model, params, leaves = build()
    eng = DecodeEngine(model, params, slots=3, prompt_buckets=(16,))
    try:
        assert "row_write" not in eng.debug_snapshot()["kv"]  # not traced
        jaxpr = eng.trace_step_jaxpr()
        prims = primitives(jaxpr.jaxpr)
        assert prims.count("pallas_call") == leaves
        assert "name=cache_write_rows" in str(jaxpr)
        assert "scatter" not in prims and "while" not in prims
        assert writes(eng) == ["batched"] * leaves
        assert eng.debug_snapshot()["kv"]["row_write"] == "batched"
    finally:
        eng.close()


def test_a_bf16_ring_of_8_rows_is_ragged_and_says_so():
    model, params, leaves = sambay()
    eng = DecodeEngine(model, params, slots=3, prompt_buckets=(16,),
                       cache_dtype=jnp.bfloat16)
    try:
        prims = primitives(eng.trace_step_jaxpr().jaxpr)
        # the two full-length leaves take the kernel, the four rings of 8
        # rows (half a bf16 tile) the scatter
        assert prims.count("pallas_call") == 2
        assert prims.count("scatter") == 4
        assert sorted(writes(eng)) == ["batched"] * 2 + ["scatter"] * 4
        assert eng.debug_snapshot()["kv"]["row_write"] == "scatter"
    finally:
        eng.close()

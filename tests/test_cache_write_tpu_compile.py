"""The decode step's kernels compiled for a described v5e at the
benchmark's real shapes (``cache_write_rows``, ``cache_attend_rows``, and
the routed experts' grouped matmuls and row kernels), the windowed flash forward kernel
of the long prefills, and the training step's flash kernels and routed
gradient at latent attention's widths: what interpret mode cannot refuse
(tiling, fast memory, the alias). No chip is needed and nothing runs;
where the topology cannot be described here the tests skip. The topology
is described inside a
fixture, never at import (one process at a time may load the TPU's
library, and every xdist worker imports this file)."""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bigdl_tpu.ops import cache_attention as ca
from bigdl_tpu.ops import cache_write as cw


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no TPU backend to compile for (a v5e:2x2 topology "
                    f"cannot be described here): {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32, jnp.int8],
                         ids=["bf16", "f32", "int8"])
@pytest.mark.parametrize("shape", [(48, 2, 4096, 128), (64, 10, 512, 128),
                                   (64, 10, 4096, 128)],
                         ids=["starcoder2", "sambay_ring", "sambay_full"])
def test_the_kernel_compiles_in_place_for_v5e(one_chip, monkeypatch, shape,
                                              dtype):
    monkeypatch.setattr(cw, "_interpret", lambda: False)
    S, kh, T, d = shape
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    compiled = jax.jit(cw._write_batched, donate_argnums=(0,)).lower(
        sds(shape, dtype), sds((S, kh, 1, d), dtype),
        sds((S,), jnp.int32)).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
    mem = compiled.memory_analysis()
    cache_bytes = S * kh * T * d * jnp.dtype(dtype).itemsize
    # the cache goes in and comes out in one buffer, and nothing else of
    # its size exists
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes // 100


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("shape,r", [((48, 2, 4096, 128), 12),
                                     ((64, 10, 4096, 128), 4),
                                     ((64, 10, 512, 128), 4),
                                     ((64, 8, 4096, 128), 8),
                                     ((32, 4, 4096, 128), 7),
                                     ((32, 4, 16384, 128), 7)],
                         ids=["starcoder2", "sambay_full", "sambay_ring",
                              "solar_open2", "smallthinker_ring",
                              "smallthinker_full"])
def test_the_bounded_read_compiles_for_v5e(one_chip, monkeypatch, shape, r,
                                           dtype):
    """``cache_attend_rows`` at the cells' shapes: one Mosaic call that
    leaves the caches where they are (no copy of one among the program's
    temporaries) and fits the VMEM it asks for."""
    monkeypatch.setattr(ca, "_interpret", lambda: False)
    S, kh, T, d = shape
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    compiled = jax.jit(functools.partial(
        ca._attend_bounded, scale=d ** -0.5)).lower(
        sds((S, kh, r, d), dtype), sds(shape, dtype), sds(shape, dtype),
        sds((S,), jnp.int32)).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1
    cache_bytes = S * kh * T * d * jnp.dtype(dtype).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes // 20


@pytest.mark.parametrize("tokens", [64, 512], ids=["decode_64_slots",
                                                   "prefill_512"])
def test_the_routed_experts_compile_for_v5e(one_chip, monkeypatch, tokens):
    """The routed layer's grouped matmuls (``nn/moe.py``, jax's
    ``megablox.gmm``) at Solar-Open2-250B's widths and this chip's 40
    held experts: two Mosaic calls, one a row kernel each way
    (``ops/moe_rows.py``), and no copy of an expert stack."""
    from bigdl_tpu.nn import moe
    from bigdl_tpu.ops import attention_kernel
    monkeypatch.setattr(attention_kernel, "_interpret", lambda: False)
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    compiled = jax.jit(moe._routed_experts).lower(
        sds((tokens, 4096), jnp.bfloat16), sds((tokens, 8), jnp.int32),
        sds((tokens, 8), jnp.float32),
        sds((40, 4096, 2560), jnp.bfloat16),
        sds((40, 1280, 4096), jnp.bfloat16)).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 4
    stacks = 40 * 3 * 4096 * 1280 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < stacks // 10


@pytest.mark.parametrize("tokens", [32, 4096, 8192],
                         ids=["decode_32_slots", "prefill_row_block",
                              "prefill_two_row_blocks"])
def test_the_reglu_experts_compile_for_v5e(one_chip, monkeypatch, tokens):
    """The same grouped matmuls at SmallThinker's widths (64 experts of
    width 768 over a stream of 2,560, six a token, ReGLU): the tiles
    chosen for 2,560 and 1,536 columns fit, beside the two row kernels,
    inside the row-block loop of a longer prompt too (where XLA, left to
    fuse the sums into the loop's stacked output, runs out of fast
    memory)."""
    from bigdl_tpu.nn import moe
    from bigdl_tpu.ops import attention_kernel
    monkeypatch.setattr(attention_kernel, "_interpret", lambda: False)
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    compiled = jax.jit(functools.partial(
        moe._routed_experts, act="relu")).lower(
        sds((tokens, 2560), jnp.bfloat16), sds((tokens, 6), jnp.int32),
        sds((tokens, 6), jnp.float32),
        sds((64, 2560, 1536), jnp.bfloat16),
        sds((64, 768, 2560), jnp.bfloat16)).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 4
    # no copy of an expert stack (755 MB); a row block of 4,096 tokens
    # has 24,576 rows of float32 temporaries (588 MB read), whatever the
    # prompt's length
    limit = {32: 64 << 20, 4096: 640 << 20, 8192: 640 << 20}[tokens]
    assert compiled.memory_analysis().temp_size_in_bytes < limit


def test_the_routed_experts_train_for_v5e(one_chip, monkeypatch):
    """The routed op's gradient at GLM-4.7-Flash's widths and a training
    step's 2 x 4,096 tokens (top-4, 8 held experts): the forward's two
    grouped matmuls and its dispatch of the rows, the backward's ``gmm``
    (dx) and ``tgmm`` (dW) of each and the two row kernels' transposes,
    in one block of 32,768 picks (the sum's gradient never reads the
    forward's combine)."""
    from bigdl_tpu.nn import moe
    from bigdl_tpu.ops import attention_kernel
    monkeypatch.setattr(attention_kernel, "_interpret", lambda: False)
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    loss = lambda x, wts, w13, w2, local: moe.routed_experts(
        x, local, wts, w13, w2).sum()
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        sds((8192, 2048), jnp.bfloat16), sds((8192, 4), jnp.float32),
        sds((8, 2048, 3072), jnp.bfloat16), sds((8, 1536, 2048),
                                                jnp.bfloat16),
        sds((8192, 4), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 9
    assert "tgmm" in text
    assert "moe_dispatch_rows" in text and "moe_combine_rows" in text
    # one block: 1.41 GB of temporaries; through the serving prefill's
    # row-block map of 4,096 tokens the same gradient holds 2.47 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 1.8e9


@pytest.mark.parametrize("T,k,d", [(8192, 4, 2048), (4096, 6, 2560),
                                   (32, 6, 2560), (64, 8, 4096)],
                         ids=["glm_train_32768", "smallthinker_prefill_24576",
                              "smallthinker_decode_192", "solar_decode_512"])
def test_the_row_kernels_compile_for_v5e(one_chip, T, k, d):
    """``moe_dispatch_rows`` and ``moe_combine_rows`` at the cells' rows
    (``T * k`` picks of ``d``), forward and each one's transpose: four
    Mosaic calls (more where the token rows outgrow what is held in
    fast memory, one a chunk) that fit the chip, and no temporaries of their own beyond the
    source rows' float32 copy and the picks' small vectors."""
    from bigdl_tpu.ops import moe_rows as mr
    m = T * k
    m_pad = -(-m // 128) * 128 if m >= 128 else -(-m // 16) * 16
    sds = lambda s, dt=jnp.float32: jax.ShapeDtypeStruct(
        s, dt, sharding=one_chip)

    def both(x, y, wts, gx, g, order, pos, n):
        xs, to_x = jax.vjp(lambda x: mr.dispatch_rows(x, order, pos, n,
                                                      False), x)
        out, to_y = jax.vjp(lambda y, w: mr.combine_rows(y, order, pos, w,
                                                         n, False), y, wts)
        return xs, out, to_x(gx), to_y(g)

    compiled = jax.jit(both).lower(
        sds((T, d), jnp.bfloat16), sds((m_pad, d)), sds((T, k)),
        sds((m_pad, d), jnp.bfloat16), sds((T, d)), sds((m_pad,), jnp.int32),
        sds((T, k), jnp.int32), sds((), jnp.int32)).compile()
    text = compiled.as_text()
    chunks = -(-T * d * 4 // mr._RESIDENT_BYTES)
    assert text.count('custom_call_target="tpu_custom_call"') == 4 * chunks
    assert "moe_dispatch_rows" in text and "moe_combine_rows" in text
    assert compiled.memory_analysis().temp_size_in_bytes <= (
        2 * T * d * 4 + m_pad * 16 + (1 << 20))


def test_the_flash_kernels_train_at_latent_heads_for_v5e(one_chip,
                                                         monkeypatch):
    """``flash_fwd``, ``flash_dq`` and ``flash_dkv`` at GLM-4.7-Flash's
    latent attention, 20 heads of 256 over 2 x 4,096 tokens, with the
    512-blocks ``_resolve_blocks`` picks past heads of 128."""
    from bigdl_tpu.ops import attention_kernel as ak
    monkeypatch.setattr(ak, "_interpret", lambda: False)
    qkv = [jax.ShapeDtypeStruct((2, 20, 4096, 256), jnp.bfloat16,
                                sharding=one_chip)] * 3
    assert ak.flash_block_plan(4096, 4096, 256, True, jnp.bfloat16)[
        "block_k"] == 512
    fn = jax.grad(lambda q, k, v: ak.flash_attention(
        q, k, v, causal=True).astype(jnp.float32).sum(), argnums=(0, 1, 2))
    text = jax.jit(fn).lower(*qkv).compile().as_text()
    assert all(name in text for name in ("flash_fwd", "flash_dq",
                                         "flash_dkv"))
    assert text.count('custom_call_target="tpu_custom_call"') == 3


@pytest.mark.parametrize("s", [8192, 16384])
def test_the_window_kernel_compiles_for_v5e(one_chip, monkeypatch, s):
    """``flash_fwd_window`` at SmallThinker's heads and window: one Mosaic
    call whose cost estimate counts the band and not the triangle."""
    from bigdl_tpu.ops import attention_kernel as ak
    monkeypatch.setattr(ak, "_interpret", lambda: False)
    qkv = [jax.ShapeDtypeStruct((1, 28, s, 128), jnp.bfloat16,
                                sharding=one_chip)] * 3
    compiled = jax.jit(lambda q, k, v: ak.flash_attention(
        q, k, v, causal=True, window=4096)).lower(*qkv).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "flash_fwd_window" in text
    plan = ak.flash_block_plan(s, s, 128, True, jnp.bfloat16, window=4096)
    assert (plan["block_q"], plan["block_k"]) == (1024, 1024)
    # a query block's band is its own K block and the four before it
    assert plan["grid_steps"] == plan["live_pairs"] == \
        sum(min(j + 1, 5) for j in range(s // 1024))
    assert ak._live_block_pairs(s, s, 512, 512, True, 0, 4096) == \
        sum(min(j + 1, 9) for j in range(s // 512))


@pytest.mark.parametrize("case", ["train_4k", "packed_4k", "prefill_2048",
                                  "suffix_1024_of_3072"])
def test_the_flash_kernels_compile_for_v5e(one_chip, monkeypatch, case):
    """``flash_fwd``, ``flash_dq`` and ``flash_dkv`` at StarCoder2's heads
    with the blocks ``_resolve_blocks`` picks (1,024 here): the scalar
    tables, the two bodies of a step and the score tiles fit the chip's
    scalar and fast memory."""
    from bigdl_tpu.ops import attention_kernel as ak
    monkeypatch.setattr(ak, "_interpret", lambda: False)
    sds = lambda s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        s, dt, sharding=one_chip)
    sq, sk = {"prefill_2048": (2048, 2048),
              "suffix_1024_of_3072": (1024, 3072)}.get(case, (4096, 4096))
    q, kv = sds((2, 24, sq, 128)), sds((2, 24, sk, 128))
    assert ak.flash_block_plan(sq, sk, 128, True, jnp.bfloat16)[
        "block_k"] == 1024
    if case in ("train_4k", "packed_4k"):
        seg = [sds((2, sq), jnp.int32)] if case == "packed_4k" else []
        fn = jax.grad(lambda q, k, v, *s: ak.flash_attention(
            q, k, v, causal=True, segments=s[0] if s else None).astype(
                jnp.float32).sum(), argnums=(0, 1, 2))
        text = jax.jit(fn).lower(q, kv, kv, *seg).compile().as_text()
        assert all(name in text for name in ("flash_fwd", "flash_dq",
                                             "flash_dkv"))
        assert text.count('custom_call_target="tpu_custom_call"') == 3
    else:
        text = jax.jit(lambda q, k, v: ak.flash_attention(
            q, k, v, causal=True)).lower(q, kv, kv).compile().as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1


def _flash_layers(layers, train):
    """A Python loop of ``flash_attention`` calls and nothing else, as a
    model's layers make them: forward + backward (``train_4k``'s kernels),
    or a prefill's forward."""
    from bigdl_tpu.ops import attention_kernel as ak

    def model(q, k, v):
        for _ in range(layers):
            q = ak.flash_attention(q, k, v, causal=True)
        return q

    if not train:
        return model
    return jax.grad(lambda q, k, v: model(q, k, v).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))


@pytest.mark.parametrize("layers,train,calls", [(6, True, 3), (30, False, 1)],
                         ids=["train_6_layers", "prefill_30_layers"])
def test_layers_share_one_lowered_kernel(one_chip, monkeypatch, layers,
                                         train, calls):
    """One kernel a signature, not one a layer: the lowered module holds
    each distinct kernel once (forward, dq, dk/dv) and a call a layer, so
    a program traces, lowers and verifies it once. (XLA inlines the calls:
    the executable still holds a copy a layer.)"""
    from bigdl_tpu.ops import attention_kernel as ak
    monkeypatch.setattr(ak, "_interpret", lambda: False)
    qkv = [jax.ShapeDtypeStruct((2 if train else 1, 24, 4096, 128),
                                jnp.bfloat16, sharding=one_chip)] * 3
    lowered = jax.jit(_flash_layers(layers, train)).lower(*qkv)
    assert lowered.as_text().count("tpu_custom_call") == calls


@pytest.mark.parametrize("layers,rows,train,limit_mb", [
    (6, 4096, True, 12.0),     # 11.40 here; the parent's 5.73, PR 37's 18.84
    (30, 4096, False, 15.0),   # 14.32; 6.51, 24.69
    (30, 512, False, 6.7),     # 6.24; 6.10, 10.30 (one block pair: one body)
], ids=["train_6_layers_4096", "prefill_30_layers_4096",
        "prefill_30_layers_512"])
def test_the_flash_kernels_keep_a_program_small(one_chip, monkeypatch,
                                                layers, rows, train,
                                                limit_mb):
    """What a run reads from the compile cache, deserializes and loads onto
    the device, warm or cold: the serialized executable (MB) of a program
    of flash kernels. ISSUE 38 asked for 10.0 / 11.4 / 6.7; the chip put
    the first two out of reach (the 256-row chunks that meet them cost the
    kernels 9-32% of their time, and the 512 x 1,024 unrolled tile named as
    the way back reads 11.09 / 13.04 here), so these hold what 512-row
    chunks give: three fifths of PR 37's. A call of one block pair holds
    one body and stays within 1.1x of the parent's."""
    from jax.experimental.serialize_executable import serialize

    from bigdl_tpu.ops import attention_kernel as ak
    monkeypatch.setattr(ak, "_interpret", lambda: False)
    qkv = [jax.ShapeDtypeStruct((2 if train else 1, 24, rows, 128),
                                jnp.bfloat16, sharding=one_chip)] * 3
    compiled = jax.jit(_flash_layers(layers, train)).lower(
        *qkv).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == layers * (3 if train
                                                             else 1)
    assert len(serialize(compiled)[0]) <= limit_mb * 1e6

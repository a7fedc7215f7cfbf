"""The decode step's kernels compiled for a described v5e at the
benchmark's real shapes (``cache_write_rows``, ``cache_attend_rows``, and
the routed experts' grouped matmuls), and the windowed flash forward
kernel of the long prefills: what interpret mode cannot refuse
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
    held experts: two Mosaic calls, and no copy of an expert stack."""
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
        'custom_call_target="tpu_custom_call"') == 2
    stacks = 40 * 3 * 4096 * 1280 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < stacks // 10


@pytest.mark.parametrize("tokens", [32, 4096], ids=["decode_32_slots",
                                                    "prefill_row_block"])
def test_the_reglu_experts_compile_for_v5e(one_chip, monkeypatch, tokens):
    """The same grouped matmuls at SmallThinker's widths (64 experts of
    width 768 over a stream of 2,560, six a token, ReGLU): the tiles
    chosen for 2,560 and 1,536 columns fit."""
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
        'custom_call_target="tpu_custom_call"') == 2
    # no copy of an expert stack (755 MB); a row block of 4,096 tokens
    # has 24,576 rows of float32 temporaries (588 MB read), whatever the
    # prompt's length
    limit = {32: 64 << 20, 4096: 640 << 20}[tokens]
    assert compiled.memory_analysis().temp_size_in_bytes < limit


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
    n = s // 512
    assert ak._band_width(s, s, 512, 512, 0, 4096) == 9
    assert ak._live_block_pairs(s, s, 512, 512, True, 0, 4096) == \
        sum(min(j + 1, 9) for j in range(n))

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

"""The decode step's read of a slot's K/V cache, with a batching rule of
its own.

A model attends a token's query rows over its cache with
``attend_rows(q, kc, vc, count, scale)``: ``q`` ``(b, kh, r, d)`` (the
``r`` query rows that share a KV head: the heads of a group, or
differential attention's half-queries), ``kc``, ``vc`` ``(b, kh, T, d)``,
``count`` a scalar: rows ``0..count-1`` are live. Alone it is the plain
form: float32 scores from operands in the activations' dtype, ``scale``,
the mask, a float32 softmax over all ``T`` rows, the probabilities cast
to the activations' dtype, float32 accumulation. ``DecodeEngine`` runs
the model under ``jax.vmap`` with a position per slot, and ``vmap`` of the
plain form reads every row of every slot's cache whatever is live: half
of a step and more. So the read is a ``custom_vmap`` whose rule looks at
what it is given, as ``ops.cache_write.write_rows``' does:

* counts batched, one token (``m == 1``), ``T`` more than one of the
  kernel's row blocks and a multiple of it, rows of whole lanes
  (``d % 128 == 0``), a floating cache: one Pallas call
  (``cache_attend_rows``) whose grid is the slots. The counts are
  prefetched scalars and K and V stay in HBM; the kernel walks the live
  row blocks of every slot in order, ``cdiv(count[s], block)`` of slot
  ``s``, copying each into one of a few VMEM buffers while the blocks
  before it are worked on (the copies run ahead across slots, so a slot
  with one live block costs no wait of its own), and keeps a running
  max, sum and float32 accumulator a slot (the flash-decoding
  recurrence). Dead rows of a slot's last block are masked, in the
  scores and in V. ``r`` is padded to the dtype's sublane tile here, not
  in the cache.
* anything else (a count that is not batched: the caller-driven dense
  path; ``m > 1``: speculative verify and the prefix-cache suffix, whose
  rows need a causal mask each; a ragged ``T`` or a cache of one block,
  which has nothing to bound: a window's ring; a step traced for a mesh
  or over a paged view, ``ops.cache_write.step_trace``; an 8-bit cache,
  which the models hand over dequantised or not at all): ``vmap`` of the
  plain form.

The rule appends the form it chose, ``"bounded"`` or ``"whole"``, to the
list ``step_trace`` collects.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bigdl_tpu.ops.attention_kernel import _interpret
from bigdl_tpu.ops.cache_write import _trace, row_tile

__all__ = ["attend_rows", "BLOCK"]

# Chosen on the chip at the cells' shapes (PERF.md §6, PR 35): smaller
# blocks pay the recurrence's fixed cost more often (256 rows: 1.5x the
# time), fewer buffers leave the copies' latency in the open.
BLOCK = 512     # cache rows a copy moves: a free slot, at position 0, costs one
BUFFERS = 3     # blocks in flight or in use
_NEG = -1e30    # finite, as nn.attention's mask value


def _plain(q, kc, vc, count, *, scale, m):
    """q (b, kh, r, d), row ``i`` at position ``count - m + i % m`` (the
    fold of ``MultiHeadAttention.decode_chunk``: r = heads of the group x
    m chunk rows), against kc, vc (b, kh, T, d) -> (b, kh, r, d) f32."""
    # a quotient, as the models' attention has always computed it: by
    # sqrt(d) for scale = 1 / sqrt(d), bit for bit
    s = jnp.einsum("bkrd,bksd->bkrs", q, kc.astype(q.dtype),
                   preferred_element_type=jnp.float32) / (1.0 / scale)
    last = count - m + jnp.arange(q.shape[2]) % m
    live = jnp.arange(kc.shape[2])[None, :] <= last[:, None]
    p = jax.nn.softmax(jnp.where(live, s, _NEG), axis=-1)
    return jnp.einsum("bkrs,bksd->bkrd", p.astype(q.dtype),
                      vc.astype(q.dtype),
                      preferred_element_type=jnp.float32)


def _accumulate(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, live, scale):
    """One block of the flash-decoding recurrence: k_ref, v_ref
    (kh, block, d) hold cache rows of which the first ``live`` count (all,
    where live >= block); m_ref, l_ref (kh, r, 1) and acc_ref (kh, r, d)
    are the running max, sum and weighted sum. Dead rows are masked in
    the scores and in V (0 x NaN is NaN)."""
    kh, block, _ = k_ref.shape
    dt = q_ref.dtype
    live_row = jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0) < live
    live_col = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1) < live
    for h in range(kh):  # unrolled: the heads' chains interleave
        sc = jax.lax.dot_general(
            q_ref[0, h], k_ref[h].astype(dt), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        sc = jnp.where(live_col, sc * scale, _NEG)
        m = m_ref[h]
        m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        m_ref[h] = m_new
        l_ref[h] = alpha * l_ref[h] + p.sum(axis=-1, keepdims=True)
        acc_ref[h] = alpha * acc_ref[h] + jnp.dot(
            p.astype(dt), jnp.where(live_row, v_ref[h], 0).astype(dt),
            preferred_element_type=jnp.float32)


def _kernel(count_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, cur,
            m_ref, l_ref, acc_ref, *, scale: float):
    """One grid step a slot. ``cur`` (SMEM, kept from step to step) is the
    copies' cursor: the next (slot, block) to fetch, and how many blocks
    have been fetched and used so far; a block lives in buffer
    ``index % BUFFERS``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, slots = pl.program_id(0), pl.num_programs(0)
    blocks = lambda i: pl.cdiv(count_ref[i], BLOCK)

    def copies(slot, blk, buf):
        rows = pl.ds(pl.multiple_of(blk * BLOCK, BLOCK), BLOCK)
        return [pltpu.make_async_copy(hbm.at[slot, :, rows, :], vmem.at[buf],
                                      sems.at[j, buf])
                for j, (hbm, vmem) in enumerate(((k_hbm, kbuf),
                                                 (v_hbm, vbuf)))]

    def fetch_next():
        slot, blk = cur[0], cur[1]

        @pl.when(slot < slots)
        def _():
            for c in copies(slot, blk, cur[2] % BUFFERS):
                c.start()
            cur[2] += 1
            end = blk + 1 == blocks(slot)
            cur[0] = jnp.where(end, slot + 1, slot)
            cur[1] = jnp.where(end, 0, blk + 1)

    @pl.when(s == 0)
    def _():
        cur[0] = cur[1] = cur[2] = cur[3] = 0
        for _ in range(BUFFERS - 1):
            fetch_next()

    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def a_block(i, carry):
        fetch_next()
        buf = cur[3] % BUFFERS
        cur[3] += 1
        for c in copies(s, i, buf):
            c.wait()
        _accumulate(q_ref, kbuf.at[buf], vbuf.at[buf], m_ref, l_ref,
                    acc_ref, count_ref[s] - i * BLOCK, scale)
        return carry

    jax.lax.fori_loop(0, blocks(s), a_block, 0)
    o_ref[0] = acc_ref[...] / l_ref[...]


@functools.partial(jax.jit, static_argnames="scale")
def _attend_bounded(q, kc, vc, count, *, scale):
    """q (S, kh, r, d), kc, vc (S, kh, T, d), count (S,) int32 ->
    (S, kh, r, d) f32: slot ``s`` attends rows 0..count[s]-1."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, kh, r, d = q.shape
    T = kc.shape[2]
    pad = -r % row_tile(q.dtype)
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    rows = pl.BlockSpec((1, kh, r + pad, d), lambda s, count: (s, 0, 0, 0))
    buffers = (BUFFERS, kh, BLOCK, d)
    buffered = 2 * BUFFERS * kh * BLOCK * d * kc.dtype.itemsize
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S,),
            in_specs=[rows, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=rows,
            scratch_shapes=[pltpu.VMEM(buffers, kc.dtype),
                            pltpu.VMEM(buffers, vc.dtype),
                            pltpu.SemaphoreType.DMA((2, BUFFERS)),
                            pltpu.SMEM((4,), jnp.int32),
                            pltpu.VMEM((kh, r + pad, 1), jnp.float32),
                            pltpu.VMEM((kh, r + pad, 1), jnp.float32),
                            pltpu.VMEM((kh, r + pad, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((S, kh, r + pad, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(16 << 20, buffered + (8 << 20))),
        interpret=_interpret(),
        name="cache_attend_rows",
    )(jnp.clip(count.astype(jnp.int32), 1, T), q, kc, vc)
    return out[:, :, :r]


@functools.lru_cache(maxsize=None)
def _op(scale: float, m: int):
    plain = functools.partial(_plain, scale=scale, m=m)
    op = jax.custom_batching.custom_vmap(plain)

    @op.def_vmap
    def _rule(axis_size, in_batched, q, kc, vc, count):
        T, d = kc.shape[-2:]
        bounded = (all(in_batched) and _trace.kernel and _trace.bounded
                   and m == 1 and T % BLOCK == 0 and T > BLOCK
                   and d % 128 == 0
                   and jnp.issubdtype(kc.dtype, jnp.floating)
                   and jnp.issubdtype(vc.dtype, jnp.floating))
        # a count that is not batched is the caller-driven dense path
        if in_batched[3] and _trace.chosen is not None:
            _trace.chosen.append("bounded" if bounded else "whole")
        if not bounded:
            axes = tuple(0 if b else None for b in in_batched)
            return jax.vmap(plain, in_axes=axes)(q, kc, vc, count), True
        b = q.shape[1]
        flat = lambda a: a.reshape((axis_size * b,) + a.shape[2:])
        out = _attend_bounded(flat(q), flat(kc), flat(vc),
                              jnp.repeat(count, b), scale=scale)
        return out.reshape(q.shape), True

    return op


def attend_rows(q, kc, vc, count, scale, m: int = 1):
    """``q`` (b, kh, r, d) attends rows ``0..count-1`` of ``kc``, ``vc``
    (b, kh, T, d): softmax(q k^T * scale) v, (b, kh, r, d) float32. With
    ``m > 1`` the r rows are ``m`` consecutive positions a head (row i at
    chunk position ``i % m``), the last of them at ``count - 1``, each
    masked causally."""
    return _op(float(scale), int(m))(q, kc, vc, count)

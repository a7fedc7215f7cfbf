"""Flash attention for TPU — hand-tiled Pallas forward kernel.

The reference has no attention at all (SURVEY.md §2.7); this kernel exists
for the long-context path the new framework treats as first-class. Design
per the TPU Pallas playbook:

* grid = (batch*heads, live block pairs); each run of steps owns one
  (BLOCK_Q, d) query tile in VMEM and streams K/V tiles with an online
  (one-pass) softmax — O(s) memory instead of materializing the (s, s)
  score matrix in HBM. The pairs come from two scalar-prefetched tables
  (:func:`_pair_table`), so a block pair no query can see is neither
  stepped nor copied, and only a pair the mask can touch
  (:func:`_pair_masked`) runs the masked body.
* a program pays for a kernel once: the wrappers sit under an inner
  ``jax.jit`` (one trace and one lowered kernel a signature, not one a
  layer), a call whose table is all masked or all interior holds that one
  body (:func:`_table_masked`), and a step's body walks a tile larger
  than 512 x 1,024 in row chunks under a rolled loop
  (:func:`_chunk_rows`).
* scores accumulate in fp32 (``preferred_element_type``) on the MXU while
  inputs may be bf16 — the same numerics as the XLA dense path.
* On non-TPU backends the kernel runs in interpret mode (tests), so one
  code path serves CPU tests and TPU execution.

Backward: hand-tiled Pallas dq and dk/dv kernels (the standard flash
backward split). The forward kernel emits the per-query logsumexp; the
backward preprocesses ``delta = rowsum(do * o)`` in one cheap jnp pass,
then dq runs on the forward's grid (one q tile per row of steps, streaming
K/V blocks) while dk/dv runs transposed (one k tile per row, streaming
Q/dO blocks), both over live pairs alone. Probabilities are
recomputed from q,k,lse — O(seq) memory end to end. Non-tileable shapes
fall back to :func:`blockwise_attention` (remat-scan) under one
``jax.custom_vjp``.

``nn.MultiHeadAttention(attn_impl="flash")`` routes here.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn import attention as _dense

__all__ = ["flash_attention", "blockwise_attention", "band_mask",
           "online_softmax_update", "flash_block_plan",
           "kv_page_plan", "serving_prefill_buckets"]

_NEG_INF = -1e30

# lse/delta per-query vectors carry a replicated trailing lane dim inside
# the Pallas calls so their blocks satisfy the TPU tiling rules. 8 is legal
# only via the block-dim-equals-array-dim escape (the lane rule is
# otherwise %128 — see _fwd_kernel._emit); it is the cheapest layout that
# escape admits.
_LSE_LANES = 8


def online_softmax_update(q, kb, vb, m, l, acc, scale, valid=None):
    """One block step of the streaming softmax shared by
    :func:`blockwise_attention` and ring attention
    (bigdl_tpu.parallel.sequence): fold K/V block (kb, vb) into the
    running (max m, normalizer l, output accumulator acc) for queries q.
    ``valid`` is an optional (..., s_q, bk) bool mask. Stats (m, l, acc)
    are fp32; q/kb/vb keep their input dtype so bf16 operands take the
    fast MXU path, with fp32 accumulation via ``preferred_element_type``.
    """
    logits = jnp.einsum("...qd,...kd->...qk", q, kb,
                        preferred_element_type=jnp.float32) * scale
    if valid is not None:
        logits = jnp.where(valid, logits, _NEG_INF)
    blk_max = jnp.max(logits, axis=-1, keepdims=True)
    new_m = jnp.maximum(m, blk_max)
    p = jnp.exp(logits - new_m)
    if valid is not None:
        p = jnp.where(valid, p, 0.0)
    corr = jnp.exp(m - new_m)
    l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    # p down to V's dtype (flash-attention convention): both P@V operands
    # bf16 on the MXU, fp32 accumulate; fp32 inputs are untouched
    acc = acc * corr + jnp.einsum("...qk,...kd->...qd", p.astype(vb.dtype),
                                  vb, preferred_element_type=jnp.float32)
    return new_m, l, acc


def _as_key_padding(mask, b, s_k):
    """Normalize a mask to (b, s_k) bool when it is a key-padding mask
    ((b, s_k) or (b|1, 1, 1, s_k)); None when it is something richer."""
    if mask is None:
        return None
    if mask.ndim == 2 and mask.shape == (b, s_k):
        return mask
    if (mask.ndim == 4 and mask.shape[-1] == s_k
            and mask.shape[1] == 1 and mask.shape[2] == 1
            and mask.shape[0] in (1, b)):
        m = mask[:, 0, 0, :]
        return jnp.broadcast_to(m, (b, s_k))
    return None


def blockwise_attention(q, k, v, *, causal: bool = False,
                        mask: Optional[jax.Array] = None,
                        segments: Optional[jax.Array] = None,
                        block_k: int = 128):
    """O(seq) memory attention in pure JAX: ``lax.scan`` over K/V blocks
    with an online softmax, the scan body wrapped in ``jax.checkpoint`` so
    autodiff recomputes each block instead of saving the (s_q, block_k)
    probability tiles — the remat-scan formulation of flash attention.
    Differentiable end-to-end; serves as the flash kernel's backward path
    and as a standalone ``attn_impl``. q,k,v: (b, h, s, d).

    Key-padding masks ((b, s_k) or (b|1,1,1,s_k) bool, True=attend) and
    packed-document ``segments`` ((b, s) int ids, self-attention shapes)
    tile along the scan and stay on this path; richer (s_q, s_k) masks
    fall back to dense.
    """
    s_k = k.shape[-2]
    bk = min(block_k, s_k)
    if segments is not None and mask is not None:
        raise ValueError("segments and mask are mutually exclusive")
    if segments is not None and q.shape[-2] != s_k:
        raise ValueError("segments requires self-attention shapes "
                         f"(s_q={q.shape[-2]} != s_k={s_k})")
    kv_mask = _as_key_padding(mask, q.shape[0], s_k)
    if (mask is not None and kv_mask is None) or s_k % bk:
        # arbitrary masks don't tile; ragged tails aren't worth the
        # complexity — correctness over memory for those cases
        if segments is not None:
            mask = _dense.make_segment_mask(segments)
        return _dense.dot_product_attention(q, k, v, causal=causal,
                                            mask=mask)
    n_blk = s_k // bk
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s_q = q.shape[-2]
    q_offset = s_k - s_q  # bottom-right aligned causal
    q_pos = q_offset + jnp.arange(s_q)

    kb = k.reshape(k.shape[:-2] + (n_blk, bk, k.shape[-1]))
    vb = v.reshape(v.shape[:-2] + (n_blk, bk, v.shape[-1]))
    # scan carries move the block axis to the front
    kb = jnp.moveaxis(kb, -3, 0)
    vb = jnp.moveaxis(vb, -3, 0)
    scan_in = (kb, vb)
    if kv_mask is not None:
        # (b, n_blk, bk) -> (n_blk, b, 1, 1, bk): broadcasts against the
        # (b, h, s_q, bk) logits inside the block update
        mb = jnp.moveaxis(kv_mask.reshape(kv_mask.shape[0], n_blk, bk),
                          1, 0)[:, :, None, None, :]
        scan_in = (kb, vb, mb)
    elif segments is not None:
        # per-block k-segment slices scan alongside K/V; the (b, 1, s_q,
        # bk) equality tile is built inside the (remat'd) body, so only
        # O(s) ids are resident — same packing semantics as the Pallas
        # kernel (segment-0 padding attends itself, keeping rows live)
        sb = jnp.moveaxis(
            segments.astype(jnp.int32).reshape(
                segments.shape[0], n_blk, bk), 1, 0)
        scan_in = (kb, vb, sb)

    seg_q = None if segments is None else segments.astype(jnp.int32)

    @jax.checkpoint
    def body(carry, blk):
        m, l, acc, j = carry
        mj = None
        if kv_mask is not None:
            kj, vj, mj = blk
        elif seg_q is not None:
            kj, vj, sj = blk
            # (b, 1, s_q, 1) == (b, 1, 1, bk) -> (b, 1, s_q, bk)
            mj = (seg_q[:, None, :, None] == sj[:, None, None, :])
        else:
            kj, vj = blk
        valid = None
        if causal:
            k_pos = j * bk + jnp.arange(bk)
            valid = q_pos[:, None] >= k_pos[None, :]
        if mj is not None:
            valid = mj if valid is None else (valid & mj)
        m, l, acc = online_softmax_update(q, kj, vj, m, l, acc, scale,
                                          valid)
        return (m, l, acc, j + 1), None

    m0 = jnp.full(q.shape[:-1] + (1,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros(q.shape[:-1] + (1,), jnp.float32)
    a0 = jnp.zeros(q.shape, jnp.float32)
    (_, l, acc, _), _ = jax.lax.scan(body, (m0, l0, a0, 0), scan_in)
    out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
    if segments is not None:
        # id-0 padding rows stayed live in the scan (finite backward);
        # zero them so this path agrees with the dense fallback above
        out = jnp.where((segments != 0)[:, None, :, None], out, 0)
    return out


def _band_first(j, block_q, block_k, q_offset, window):
    """The first K block that query block ``j`` can see under a window."""
    return max(q_offset + j * block_q - window + 1, 0) // block_k


def _band_last(j, block_q, block_k, q_offset):
    """The last K block that query block ``j`` can see under causality."""
    return (q_offset + (j + 1) * block_q - 1) // block_k


def _pair_live(causal, j, kk, block_q, block_k, q_offset):
    """Whether any query of Q block ``j`` sees a key of K block ``kk``
    (block indices as Python ints, or traced in a kernel)."""
    return not causal or kk * block_k <= q_offset + (j + 1) * block_q - 1


# two int32 tables of this many pairs are half the chip's 1 MB of scalar
# memory: 131,072 tokens at 512-blocks without a mask
_MAX_PAIRS = 1 << 16


def _pair_table(n_q, n_k, bq, bk, causal, q_offset, window=None,
                by_key=False):
    """The (Q block, K block) pairs the kernels' flattened grid walks, as
    two int32 arrays: every Q block's visible K blocks in turn (under a
    ``window``, its band's; ``by_key``: every K block's Q blocks, for
    dk/dv), so a pair no query can see is neither stepped nor copied. A
    row with no live pair (``s_q > s_k``) keeps one entry, which the
    kernels' ``live`` test skips, so that its output is still written."""
    pairs = []
    if by_key:
        for jk in range(n_k):
            first = max((jk * bk - q_offset) // bq, 0) if causal else 0
            pairs += [(qq, jk) for qq in range(min(first, n_q - 1), n_q)]
    else:
        for j in range(n_q):
            first = 0 if window is None else _band_first(
                j, bq, bk, q_offset, window)
            last = n_k - 1 if not causal else min(
                max(_band_last(j, bq, bk, q_offset), first), n_k - 1)
            pairs += [(j, kk) for kk in range(first, last + 1)]
    if len(pairs) > _MAX_PAIRS:
        raise ValueError(
            f"flash_attention: {len(pairs)} block pairs do not fit the "
            f"kernels' scalar tables ({_MAX_PAIRS}); pass larger "
            "block_q / block_k")
    table = np.asarray(pairs, np.int32)
    return table[:, 0], table[:, 1]


def _pair_specs(bq, bk, d, h, has_seg):
    """Block specs of a pair-table grid ``(bh, pairs)``: a ``(bq, d)``
    block at the step's Q block, a ``(bk, d)`` block at its K block, a
    per-query row block (lse, delta), and the two segment-id blocks (per
    batch, so the grid's ``bh`` index divides out ``h``; none without
    segments). Index maps see the grid indices, then the two tables."""
    from jax.experimental import pallas as pl

    at_q = lambda i, t, qt, kt: (i, qt[t], 0)
    seg_specs = [
        pl.BlockSpec((1, bq, _LSE_LANES),
                     lambda i, t, qt, kt: (i // h, qt[t], 0)),
        pl.BlockSpec((1, _LSE_LANES, bk),
                     lambda i, t, qt, kt: (i // h, 0, kt[t])),
    ] if has_seg else []
    return (pl.BlockSpec((1, bq, d), at_q),
            pl.BlockSpec((1, bk, d), lambda i, t, qt, kt: (i, kt[t], 0)),
            pl.BlockSpec((1, bq, _LSE_LANES), at_q), seg_specs)


def _row_edges(row_ref, t):
    """Whether grid step ``t`` is the first / the last of its row of
    steps (``row_ref`` names each step's row: the block whose scratch
    accumulates)."""
    from jax.experimental import pallas as pl

    n = pl.num_programs(1)
    row = row_ref[t]
    return ((t == 0) | (row_ref[jnp.maximum(t - 1, 0)] != row),
            (t == n - 1) | (row_ref[jnp.minimum(t + 1, n - 1)] != row))


def _pair_masked(causal, has_seg, j, kk, block_q, block_k, q_offset,
                 window=None):
    """Whether block pair (Q block ``j``, K block ``kk``) holds an element
    the mask can remove. A causal pair is *interior*, and not masked, when
    its last key is visible to its first query and, under a ``window``,
    its first key is inside its last query's band: integer arithmetic on
    block indices (Python ints, or traced in a kernel). Segment ids are
    data, so under them every pair is masked; with no mask none is."""
    if has_seg or not causal:
        return has_seg
    crossed = kk * block_k + block_k - 1 > q_offset + j * block_q
    if window is not None:
        crossed |= kk * block_k <= q_offset + (j + 1) * block_q - 1 - window
    return crossed


def _table_masked(tables, causal, has_seg, block_q, block_k, q_offset,
                  window=None) -> Optional[bool]:
    """What :func:`_pair_masked` says of a whole pair table, where it says
    one thing: True when every live pair is masked (a call of one block
    pair, segment ids), False when none is (no mask at all). The kernel
    then holds the one body its table uses. None: both bodies, chosen a
    step."""
    kinds = {bool(_pair_masked(causal, has_seg, j, kk, block_q, block_k,
                               q_offset, window))
             for j, kk in zip(*(t.tolist() for t in tables))
             if _pair_live(causal, j, kk, block_q, block_k, q_offset)}
    return None if len(kinds) == 2 else True in kinds


def _on_live_pair(live, masked, step):
    """Run ``step(masked)`` on a live block pair: a pair the mask can touch
    under the mask, an interior pair in a body with no iota, compare or
    select. Two ``pl.when`` bodies of the one ``step`` where ``masked`` is
    decided a step, one where the table decided it (a Python bool)."""
    from jax.experimental import pallas as pl

    if isinstance(masked, bool):
        pl.when(live)(functools.partial(step, masked))
    else:
        pl.when(live & masked)(functools.partial(step, True))
        pl.when(live & ~masked)(functools.partial(step, False))


# Mosaic unrolls a step's body over its score tile, so a program grows with
# the tile: a body works at most this many scores at a time (half a
# 1,024 x 1,024 tile's) and walks a larger tile's query rows under a rolled
# loop. The chip set the size (bf16[48,4096,128], PERF.md §6, PR 38): 512
# rows a chunk cost the forward 2.9%, dq 3.1% and dk/dv nothing against the
# whole tile unrolled and take two fifths off the executable; 256 rows cost
# 17%, 9% and 32% (each latched K or V tile then streams too few rows).
_STEP_TILE = 512 * 1024


def _chunk_rows(block_q: int, block_k: int) -> int:
    """Query rows of a ``(block_q, block_k)`` score tile that a step's body
    works at a time: 512 at 1,024-blocks; the whole tile up to 512 x 1,024,
    and where the chunk does not divide the rows."""
    rows = _STEP_TILE // block_k
    return rows if block_q % rows == 0 else block_q


def _over_row_chunks(block_q, rows, chunk):
    """``chunk(r0)`` for every ``rows`` query rows of a step's tile, ``r0``
    the chunk's first row within the step's blocks and row scratch. Rows
    are independent in all a step does (dk/dv sums over them, into its
    scratch), so a tile of one chunk is the plain body and a larger one
    the same arithmetic under ``lax.fori_loop``: the grid step, its copies
    and its fixed cost stay the large block's, the body a small tile's."""
    from jax.experimental import pallas as pl

    if rows == block_q:
        chunk(0)
        return

    def body(c, carry):
        chunk(pl.multiple_of(c * rows, rows))
        return carry

    jax.lax.fori_loop(0, block_q // rows, body, 0)


def _block_valid(causal, q_ids, k_ids, rows, q_first, k_first, block_k,
                 window=None):
    """(rows, block_k) bool validity tile for the queries from position
    ``q_first`` against the keys from ``k_first``, combining the causal
    triangle (under a ``window``, the band ``q_pos - window < k_pos <=
    q_pos``) and the segment equality mask; None when nothing is masked.
    Padded rows (segment 0) still attend segment-0 keys so no row is fully
    masked — the dense make_segment_mask kills them instead; those outputs
    are loss-masked garbage either way, but a live softmax row keeps the
    backward finite."""
    valid = None
    if causal:
        q_pos = q_first + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 0)
        k_pos = k_first + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 1)
        valid = q_pos >= k_pos
        if window is not None:
            valid = valid & (q_pos - window < k_pos)
    if q_ids is not None:
        seg = q_ids == k_ids  # (rows, 1) == (1, bk) -> (rows, bk)
        valid = seg if valid is None else (valid & seg)
    return valid


def _fwd_kernel(qt_ref, kt_ref, q_ref, k_ref, v_ref, *rest, block_k: int,
                scale: float, causal: bool, block_q: int, q_offset: int,
                has_seg: bool, masked: Optional[bool], rows: int,
                window: Optional[int] = None):
    """Grid (bh, block pairs): step ``t`` is Q block ``qt_ref[t]`` against
    K block ``kt_ref[t]`` (:func:`_pair_table`). K/V stream block-by-block
    from HBM (Pallas double-buffers across the innermost grid dim), online
    softmax state lives in VMEM scratch — O(block) VMEM regardless of
    sequence length, so 128k-token sequences fit. With ``has_seg`` two
    extra refs carry packed-document segment ids (q ids lane-replicated,
    kv ids sublane-replicated — the official TPU kernel's layout). With a
    ``window`` (causal, forward only) a Q block's steps are its band's K
    blocks alone. ``masked`` is :func:`_table_masked`'s word on the whole
    table, ``rows`` :func:`_chunk_rows`'."""
    from jax.experimental import pallas as pl

    if has_seg:
        qs_ref, ks_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
        qs_ref = ks_ref = None

    t = pl.program_id(1)
    j, kk = qt_ref[t], kt_ref[t]
    first, last = _row_edges(qt_ref, t)

    @pl.when(first)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # bottom-right aligned causal (matches dot_product_attention): query i
    # sees keys <= (s_k - s_q) + i. Fully-future K blocks are not in the
    # pair table (half the causal FLOPs); ``live`` skips the one entry a
    # row with no visible key keeps.
    live = _pair_live(causal, j, kk, block_q, block_k, q_offset)

    def _step(masked):
        def chunk(r0):
            r = pl.ds(r0, rows)
            q = q_ref[0, r, :]  # input dtype on the MXU, fp32 accumulate
            kblk = k_ref[0]
            vblk = v_ref[0]
            m, l = m_scr[r, :], l_scr[r, :]
            s = jax.lax.dot_general(
                q, kblk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (rows, BK)
            valid = _block_valid(
                causal and masked,
                None if qs_ref is None else qs_ref[0, r, :1],
                None if ks_ref is None else ks_ref[0][:1, :],
                rows, q_offset + j * block_q + r0, kk * block_k, block_k,
                window)
            if valid is not None:
                s = jnp.where(valid, s, _NEG_INF)
            blk_max = jnp.max(s, axis=-1, keepdims=True)
            new_m = jnp.maximum(m, blk_max)
            p = jnp.exp(s - new_m)
            if valid is not None:
                p = jnp.where(valid, p, 0.0)
            corr = jnp.exp(m - new_m)
            m_scr[r, :] = new_m
            l_scr[r, :] = l * corr + jnp.sum(p, axis=-1, keepdims=True)
            # p cast to V's dtype (flash convention): P@V is a bf16 MXU
            # matmul with fp32 accumulation
            acc_scr[r, :] = acc_scr[r, :] * corr + jax.lax.dot_general(
                p.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        _over_row_chunks(q_ref.shape[1], rows, chunk)

    _on_live_pair(live, _pair_masked(
        causal, has_seg, j, kk, block_q, block_k, q_offset, window)
        if masked is None else masked, _step)

    @pl.when(last)
    def _emit():
        l_safe = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        # per-query logsumexp, saved for the backward kernels' recompute.
        # Replicated across a trailing 8-lane dim: Mosaic requires the last
        # two block dims to be (8k, 128k) or equal to the array dims, so a
        # per-(bh,q) 2-D layout with block (1, bq) cannot lower — same
        # reason jax's own TPU flash kernel stores lse as (..., seq, 128);
        # 8 lanes is the cheapest legal layout (last block dim == array
        # dim escape).
        lse_ref[0] = jnp.broadcast_to(
            m_scr[...] + jnp.log(l_safe), lse_ref.shape[1:])


def _dq_kernel(qt_ref, kt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, *rest, block_k: int, scale: float, causal: bool,
               block_q: int, q_offset: int, has_seg: bool,
               masked: Optional[bool], rows: int):
    from jax.experimental import pallas as pl

    if has_seg:
        qs_ref, ks_ref, dq_ref, dq_scr = rest
    else:
        dq_ref, dq_scr = rest
        qs_ref = ks_ref = None

    t = pl.program_id(1)
    j, kk = qt_ref[t], kt_ref[t]
    first, last = _row_edges(qt_ref, t)

    @pl.when(first)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    live = _pair_live(causal, j, kk, block_q, block_k, q_offset)

    def _step(masked):
        def chunk(r0):
            r = pl.ds(r0, rows)
            q = q_ref[0, r, :]
            do = do_ref[0, r, :]
            lse = lse_ref[0, r, :1]      # (rows, 1) f32 (lanes replicated)
            delta = delta_ref[0, r, :1]  # (rows, 1) f32
            kblk = k_ref[0]
            vblk = v_ref[0]
            s = jax.lax.dot_general(
                q, kblk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            p = jnp.exp(s - lse)  # rows already normalized via lse
            valid = _block_valid(
                causal and masked,
                None if qs_ref is None else qs_ref[0, r, :1],
                None if ks_ref is None else ks_ref[0][:1, :],
                rows, q_offset + j * block_q + r0, kk * block_k, block_k)
            if valid is not None:
                p = jnp.where(valid, p, 0.0)
            dp = jax.lax.dot_general(   # dO @ V^T  (rows, BK)
                do, vblk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta)  # its scale waits for _emit
            dq_scr[r, :] += jax.lax.dot_general(  # dS @ K  (rows, d)
                ds.astype(kblk.dtype), kblk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        _over_row_chunks(q_ref.shape[1], rows, chunk)

    _on_live_pair(live, _pair_masked(
        causal, has_seg, j, kk, block_q, block_k, q_offset)
        if masked is None else masked, _step)

    @pl.when(last)
    def _emit():
        # dS's scale is linear in the accumulator: applied here, in
        # float32, once a block and not once a step
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(qt_ref, kt_ref, k_ref, v_ref, q_ref, do_ref, lse_ref,
                delta_ref, *rest, block_q: int, scale: float, causal: bool,
                block_k: int, q_offset: int, has_seg: bool,
                masked: Optional[bool], rows: int):
    from jax.experimental import pallas as pl

    if has_seg:
        qs_ref, ks_ref, dk_ref, dv_ref, dk_scr, dv_scr = rest
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = rest
        qs_ref = ks_ref = None

    t = pl.program_id(1)
    # the table runs by K block here: a row of steps owns K block j and
    # streams its Q/dO blocks
    qq, j = qt_ref[t], kt_ref[t]
    first, last = _row_edges(kt_ref, t)

    @pl.when(first)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    live = _pair_live(causal, qq, j, block_q, block_k, q_offset)

    def _step(masked):
        def chunk(r0):
            r = pl.ds(r0, rows)
            k = k_ref[0]  # (BK, d)
            v = v_ref[0]
            qblk = q_ref[0, r, :]
            doblk = do_ref[0, r, :]
            lse = lse_ref[0, r, :1]
            delta = delta_ref[0, r, :1]
            s = jax.lax.dot_general(  # Q @ K^T  (rows, BK)
                qblk, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            p = jnp.exp(s - lse)
            valid = _block_valid(
                causal and masked,
                None if qs_ref is None else qs_ref[0, r, :1],
                None if ks_ref is None else ks_ref[0][:1, :],
                rows, q_offset + qq * block_q + r0, j * block_k, block_k)
            if valid is not None:
                p = jnp.where(valid, p, 0.0)
            dv_scr[...] += jax.lax.dot_general(  # P^T @ dO  (BK, d)
                p.astype(doblk.dtype), doblk, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(  # dO @ V^T  (rows, BK)
                doblk, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = (p * (dp - delta)).astype(qblk.dtype)
            dk_scr[...] += jax.lax.dot_general(  # dS^T @ Q  (BK, d)
                ds, qblk, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        _over_row_chunks(q_ref.shape[1], rows, chunk)

    _on_live_pair(live, _pair_masked(
        causal, has_seg, qq, j, block_q, block_k, q_offset)
        if masked is None else masked, _step)

    @pl.when(last)
    def _emit():
        # dS's scale, as in the dq kernel; dv has none
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _live_block_pairs(sq, sk, bq, bk, causal, q_offset,
                      window=None) -> int:
    """Exact number of (q-block, k-block) grid pairs whose matmuls run per
    (b, h) — the Python-side mirror of the kernels' ``live`` predicate
    (fully-future K blocks are skipped under causal; under a ``window``
    the blocks before the band are never visited). Segment masking is
    data-dependent and not reflected here."""
    n_q, n_k = sq // bq, sk // bk
    if not causal:
        return n_q * n_k
    total = 0
    for j in range(n_q):
        q_end = q_offset + (j + 1) * bq - 1
        live = min(n_k, max(0, q_end // bk + 1))
        if window is not None:
            live = max(0, live - _band_first(j, bq, bk, q_offset, window))
        total += live
    return total


def _attn_cost(bh, n_pairs, bq, bk, d, dtype_bytes, units):
    """Author-declared ALGORITHMIC cost for one attention Pallas kernel
    (consumed by ``utils/flops.py``, which prefers it over grid x
    kernel-body counting): ``units`` matmuls of 2*bq*bk*d FLOPs per live
    block pair — the forward's qk+pv, the dq kernel's dP+dQ, the dkv
    kernel's dV+dK. The backward kernels' score RECOMPUTATION is
    deliberately excluded, per the module convention flops.py states for
    remat (algorithmic FLOPs, not executed): a dense-autodiff backward
    reuses stored P and performs exactly these four units, so MFU
    numerators stay comparable across attention implementations. Block
    skipping IS reflected (n_pairs is causal-aware), so causal MFU is no
    longer flattered by counting masked work."""
    from jax.experimental import pallas as pl

    return pl.CostEstimate(
        flops=int(2 * units * bh * n_pairs * bq * bk * d),
        transcendentals=int(bh * n_pairs * bq * bk),
        bytes_accessed=int(dtype_bytes * bh * n_pairs * (bq + 2 * bk) * d))


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def _interpret() -> bool:
    # compiled Mosaic lowering on TPU; interpret mode elsewhere (tests)
    return jax.default_backend() != "tpu"


def pltpu_scratch(shape):
    """fp32 VMEM scratch (online-softmax state carried across the
    innermost grid dimension)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)


def _tileable(s_q, s_k, block_k) -> bool:
    # ragged key length would need a validity mask woven into the online
    # softmax; the remat-scan path handles it (pad_to on K alone would
    # let padded keys win the softmax)
    bk = min(block_k, max(8, s_k))
    return s_k % bk == 0


_DEFAULT_BLOCK = 512  # per-program tile default; mid sequences clamp
# down, the autotuner overrides per shape


def _default_block(s_q: int, s_k: int, d: int) -> int:
    """1,024-blocks where the chip showed them to win: heads of at most
    128 over lengths that are multiples of 1,024. A step's softmax state
    (the ``(bq, 1)`` max and sum, the accumulator's rescale) costs the
    same whatever the K block's width, and that work, not the MXU, sets
    the forward's pace: 37% off the forward, 11% off dq and 16% off dk/dv
    at bf16[48,4096,128]; under 4% either way at heads of 256 (PERF.md
    §6, PR 37). Other lengths keep 512 and its clamps."""
    wide = d <= 128 and s_q % 1024 == 0 and s_k % 1024 == 0
    return 1024 if wide else _DEFAULT_BLOCK


def _clamp_block(block: int, s: int) -> int:
    """Largest standard tiling <= ``block`` that divides ``s`` (falling
    through 256/128), else min(block, s) — applied to BOTH block dims so
    a mid sequence like s=768 runs 256-blocks instead of padding 768→1024
    and burning ~33% extra q-block work (ADVICE r5 #2; block_k already
    clamped this way since round 5)."""
    b = min(block, max(8, s))
    if s % b:
        for cand in (256, 128):
            if cand < b and s % cand == 0:
                return cand
    return b


def _resolve_blocks(s_q: int, s_k: int, d: int, causal: bool, dtype,
                    block_q: "int | None", block_k: "int | None"
                    ) -> "tuple[int, int]":
    """Static block-size resolution: explicit arguments win; otherwise
    consult the autotuner (bigdl_tpu.tuning, a no-op in off mode) and
    fall back to :func:`_default_block`. Both dims are then clamped to a
    standard tiling that divides their sequence."""
    if block_q is None or block_k is None:
        tuned = None
        from bigdl_tpu import tuning
        if tuning.get_mode() != "off":
            tuned = tuning.flash_blocks(s_q, s_k, d, causal, dtype)
        default = _default_block(s_q, s_k, d)
        if block_q is None:
            block_q = tuned[0] if tuned else default
        if block_k is None:
            block_k = tuned[1] if tuned else default
    return _clamp_block(block_q, s_q), _clamp_block(block_k, s_k)


def flash_block_plan(s_q: int, s_k: int, d: int, causal: bool,
                     dtype, *, window: Optional[int] = None,
                     block_q: Optional[int] = None,
                     block_k: Optional[int] = None) -> dict:
    """Static view of what :func:`flash_attention` would do at this
    shape — the block metadata tpulint (bigdl_tpu.analysis) evaluates
    without tracing a kernel:

    * ``block_q``/``block_k`` — the resolved (autotuner-consulted,
      clamped) tile sizes;
    * ``grid_steps``/``live_pairs``/``masked_pairs`` — per (row, head) of
      the forward: the block pairs its grid steps through, those whose
      matmuls run, and those that run the masked body (the diagonal's
      and a window's far edge's; 0 each off the kernel);
    * ``kernel_ok`` — False when the ragged key length knocks the call
      off the Pallas kernel onto the remat-scan fallback;
    * ``q_pad``/``k_pad`` — rows a padded final block would add (the
      pre-round-6 s=768 failure mode: nonzero means wasted grid work);
    * ``clamped`` — blocks sit below the 512 default because the seq
      admits no larger divisor (fine, but worth a note).
    """
    s_q, s_k, causal = int(s_q), int(s_k), bool(causal)
    bq, bk = _resolve_blocks(s_q, s_k, int(d), causal, dtype, block_q,
                             block_k)
    kernel_ok = _tileable(s_q, s_k, bk)
    q_offset = s_k - s_q
    steps = list(zip(*_pair_table(-(-s_q // bq), s_k // bk, bq, bk, causal,
                                  q_offset, window))) if kernel_ok else []
    live = [pair for pair in steps
            if _pair_live(causal, *pair, bq, bk, q_offset)]
    return {
        "block_q": bq, "block_k": bk,
        "grid_steps": len(steps), "live_pairs": len(live),
        "masked_pairs": sum(bool(_pair_masked(
            causal, False, *pair, bq, bk, q_offset, window))
            for pair in live),
        "kernel_ok": kernel_ok,
        "q_pad": (-s_q) % bq,
        "k_pad": (-s_k) % bk,
        "clamped": (bq < _DEFAULT_BLOCK and bq < s_q)
                   or (bk < _DEFAULT_BLOCK and bk < s_k),
    }


def kv_page_plan(page_tokens: int, max_len: int, head_dim: int,
                 dtype, causal: bool = True) -> dict:
    """Static fit of a paged-KV layout (serving/kv_pages) against this
    shape's flash block plan — the metadata the decode tpulint rule
    (bigdl_tpu.analysis.run_decode_rules) evaluates without tracing:

    * ``divides_max_len`` — False is a hard engine error (the gathered
      view must be exactly max_len);
    * ``sublane_ok`` — pages whose token dim is not a multiple of the
      dtype's minimum sublane count (8 for 4-byte, 16 for bf16, 32 for
      int8 — the Mosaic tile rule) break the minimum tile on every pool
      leaf: each page then pays a padded sublane, and gathers re-lay
      the data. 8-bit KV pools (ISSUE 17 kv8) therefore need 32-token
      pages at minimum;
    * ``sublane`` — the minimum applied, for the lint message;
    * ``block_aligned`` — the prefill flash kernel reads K in
      ``block_k`` tiles; when neither divides the other, a single K
      block straddles a page boundary in the gathered view and the
      scatter back to pools splits every tile (misfit finding);
    * ``block_k`` — the plan consulted, for the lint message.
    """
    plan = flash_block_plan(max_len, max_len, head_dim, causal, dtype)
    bk = int(plan["block_k"])
    pt = int(page_tokens)
    sub = {4: 8, 2: 16, 1: 32}.get(np.dtype(dtype).itemsize, 8)
    return {
        "page_tokens": pt,
        "block_k": bk,
        "divides_max_len": max_len % pt == 0,
        "sublane": sub,
        "sublane_ok": pt % sub == 0,
        "block_aligned": (pt % bk == 0) or (bk % pt == 0),
    }


def serving_prefill_buckets(max_len: int, head_dim: int,
                            causal: bool = True, dtype=jnp.float32,
                            min_bucket: int = 16) -> tuple:
    """Prompt-length buckets for the serving prefill: a power-of-two
    ladder from ``min_bucket`` up to (and always including) ``max_len``,
    filtered to lengths whose :func:`flash_block_plan` stays ON the
    Pallas kernel with zero padded rows — so a prefill at any bucket
    reuses the tuned block plan the training benchmarks measured, never
    the remat-scan fallback or a padded grid. Off-TPU (dense attention)
    the same ladder simply bounds the compile cache; the filter is a
    no-op there because power-of-two lengths clamp cleanly."""
    if max_len < 1:
        raise ValueError(f"max_len must be >= 1, got {max_len}")
    ladder = []
    b = max(1, int(min_bucket))
    while b < max_len:
        ladder.append(b)
        b *= 2
    ladder.append(int(max_len))
    out = []
    for s in sorted(set(ladder)):
        plan = flash_block_plan(s, s, head_dim, causal, dtype)
        if plan["kernel_ok"] and plan["q_pad"] == 0 and plan["k_pad"] == 0:
            out.append(s)
    # never return empty: the full max_len bucket always works densely
    return tuple(out) or (int(max_len),)


def _seg_arrays(segments, sq, sk, bq):
    """Segment ids in the kernels' tileable layouts: q ids (b, sq, 8)
    lane-replicated (padded rows get id 0), kv ids (b, 8, sk)
    sublane-replicated — mirroring the lse layout trick."""
    seg = segments.astype(jnp.int32)
    qs = seg
    if qs.shape[1] != sq:  # q padded to a block multiple
        qs = jnp.pad(qs, ((0, 0), (0, sq - qs.shape[1])))
    qs3 = jnp.broadcast_to(qs[..., None], qs.shape + (_LSE_LANES,))
    ks3 = jnp.broadcast_to(seg[:, None, :],
                           (seg.shape[0], _LSE_LANES, sk))
    return qs3, ks3


def _flash_fwd(q, k, v, causal: bool, block_q: int, block_k: int,
               segments=None, window: Optional[int] = None):
    """Pallas forward; returns (out, lse) with lse in (b*h, padded_sq).
    The kernel emits lse lane-replicated (see _LSE_LANES); the replica dim
    is squeezed off here so the custom_vjp residual stores 4 B/query, not
    32 B — the backward re-broadcasts next to its delta broadcast.

    With a ``window`` (causal, no segments) the call is named
    ``flash_fwd_window`` and its pair table holds each query block's band
    alone."""
    return _fwd_program(q, k, v, segments, causal, block_q, block_k, window,
                        _interpret())


# One kernel a signature, not one a layer: a model builds its layers in a
# Python loop, and a bare ``pallas_call`` is traced, lowered and verified
# again by every one of them (0.6-0.9 s of host time for a program of 18-30
# identical kernels, before the compile cache's key exists). Under ``jit``
# the second layer finds the first one's trace, and the outer program's
# module holds one kernel and a call a layer. No shardings of its own:
# inside ``shard_map``, a sharded ``jit`` or ``jax.checkpoint`` it traces
# as the bare call did. ``interpret`` is in the key because tests switch it.
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _fwd_program(q, k, v, segments, causal, block_q, block_k, window,
                 interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s_q, d = q.shape
    s_k = k.shape[-2]
    scale = 1.0 / (d ** 0.5)

    qf = q.reshape(b * h, s_q, d)
    kf = k.reshape(b * h, s_k, d)
    vf = v.reshape(b * h, s_k, d)

    bq = min(block_q, max(8, s_q))
    bk = min(block_k, max(8, s_k))
    qf, pad_q = _pad_to(qf, bq, 1)
    sq, sk = qf.shape[1], kf.shape[1]

    q_offset = s_k - s_q
    has_seg = segments is not None
    tables = _pair_table(sq // bq, sk // bk, bq, bk, causal, q_offset,
                         window)
    kernel = functools.partial(
        _fwd_kernel, block_k=bk, scale=scale, causal=causal, block_q=bq,
        q_offset=q_offset, has_seg=has_seg, window=window,
        masked=_table_masked(tables, causal, has_seg, bq, bk, q_offset,
                             window),
        rows=_chunk_rows(bq, bk))
    q_spec, k_spec, row_spec, seg_specs = _pair_specs(bq, bk, d, h, has_seg)
    args = [qf, kf, vf]
    if has_seg:
        args += _seg_arrays(segments, sq, sk, bq)
    n_pairs = _live_block_pairs(sq, sk, bq, bk, causal, q_offset, window)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b * h, len(tables[0])),
            in_specs=[q_spec, k_spec, k_spec] + seg_specs,
            out_specs=[q_spec, row_spec],
            scratch_shapes=[
                pltpu_scratch((bq, 1)), pltpu_scratch((bq, 1)),
                pltpu_scratch((bq, d)),
            ]),
        cost_estimate=_attn_cost(b * h, n_pairs, bq, bk, d,
                                 q.dtype.itemsize, units=2),
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, _LSE_LANES), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd" if window is None else "flash_fwd_window",
    )(*tables, *args)
    o = out[:, :s_q] if pad_q else out
    return o.reshape(b, h, s_q, d), lse[..., 0]


def _flash_bwd(q, k, v, o, lse, g, causal: bool, block_q: int,
               block_k: int, segments=None, window: Optional[int] = None):
    """Pallas dq + dk/dv kernels over the recomputed probabilities."""
    if window is not None:
        raise NotImplementedError(
            "flash_attention: the backward kernels have no window "
            f"(window={window}); the windowed kernel is forward only")
    return _bwd_program(q, k, v, o, lse, g, segments, causal, block_q,
                        block_k, _interpret())


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10))
def _bwd_program(q, k, v, o, lse, g, segments, causal, block_q, block_k,
                 interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s_q, d = q.shape
    s_k = k.shape[-2]
    scale = 1.0 / (d ** 0.5)

    qf = q.reshape(b * h, s_q, d)
    kf = k.reshape(b * h, s_k, d)
    vf = v.reshape(b * h, s_k, d)
    dof = g.reshape(b * h, s_q, d)
    of = o.reshape(b * h, s_q, d)

    bq = min(block_q, max(8, s_q))
    bk = min(block_k, max(8, s_k))
    # delta_i = sum_d dO_i * O_i — one cheap fused pass in plain XLA;
    # replicated over _LSE_LANES to match lse's TPU-tileable layout
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1)
    qf, pad_q = _pad_to(qf, bq, 1)
    dof, _ = _pad_to(dof, bq, 1)
    delta, _ = _pad_to(delta, bq, 1)
    delta = jnp.broadcast_to(delta[..., None],
                             delta.shape + (_LSE_LANES,))
    lse = jnp.broadcast_to(lse[..., None], lse.shape + (_LSE_LANES,))
    sq, sk = qf.shape[1], kf.shape[1]
    q_offset = s_k - s_q
    has_seg = segments is not None

    q_spec, k_spec, row_spec, seg_specs = _pair_specs(bq, bk, d, h, has_seg)
    seg_args = _seg_arrays(segments, sq, sk, bq) if has_seg else ()
    n_pairs = _live_block_pairs(sq, sk, bq, bk, causal, q_offset)

    def call(kernel, name, by_key, in_specs, out_spec, out_shapes, args):
        tables = _pair_table(sq // bq, sk // bk, bq, bk, causal, q_offset,
                             by_key=by_key)
        return pl.pallas_call(
            functools.partial(
                kernel, block_q=bq, block_k=bk, scale=scale, causal=causal,
                q_offset=q_offset, has_seg=has_seg,
                masked=_table_masked(tables, causal, has_seg, bq, bk,
                                     q_offset),
                rows=_chunk_rows(bq, bk)),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(b * h, len(tables[0])),
                in_specs=in_specs + seg_specs,
                out_specs=[out_spec] * len(out_shapes),
                scratch_shapes=[pltpu_scratch(out_spec.block_shape[1:])
                                for _ in out_shapes]),
            cost_estimate=_attn_cost(b * h, n_pairs, bq, bk, d,
                                     qf.dtype.itemsize, units=2),
            out_shape=out_shapes,
            interpret=interpret,
            name=name,
        )(*tables, *args, *seg_args)

    dq, = call(_dq_kernel, "flash_dq", False,
               [q_spec, k_spec, k_spec, q_spec, row_spec, row_spec], q_spec,
               [jax.ShapeDtypeStruct((b * h, sq, d), q.dtype)],
               [qf, kf, vf, dof, lse, delta])
    dk, dv = call(_dkv_kernel, "flash_dkv", True,
                  [k_spec, k_spec, q_spec, q_spec, row_spec, row_spec],
                  k_spec,
                  [jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, sk, d), v.dtype)],
                  [kf, vf, qf, dof, lse, delta])

    dq = (dq[:, :s_q] if pad_q else dq).reshape(b, h, s_q, d)
    return dq, dk.reshape(b, h, s_k, d), dv.reshape(b, h, s_k, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, block_q, block_k):
    if not _tileable(q.shape[-2], k.shape[-2], block_k):
        return _dense.dot_product_attention(q, k, v, causal=causal,
                                            mask=None)
    return _flash_fwd(q, k, v, causal, block_q, block_k)[0]


def _flash_vjp_fwd(q, k, v, causal, block_q, block_k):
    if not _tileable(q.shape[-2], k.shape[-2], block_k):
        out = _dense.dot_product_attention(q, k, v, causal=causal,
                                           mask=None)
        return out, (q, k, v, None, None)
    out, lse = _flash_fwd(q, k, v, causal, block_q, block_k)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, block_q, block_k, res, g):
    q, k, v, o, lse = res
    if lse is None:
        # non-tileable fallback: blockwise-remat recompute, O(seq) memory
        _, vjp = jax.vjp(
            lambda q_, k_, v_: blockwise_attention(
                q_, k_, v_, causal=causal, block_k=block_k), q, k, v)
        return vjp(g)
    return _flash_bwd(q, k, v, o, lse, g, causal, block_q, block_k)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_window(q, k, v, window, block_q, block_k):
    return _flash_fwd(q, k, v, True, block_q, block_k, window=window)[0]


def _flash_window_vjp_fwd(q, k, v, window, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, True, block_q, block_k, window=window)
    return out, (q, k, v, out, lse)


def _flash_window_vjp_bwd(window, block_q, block_k, res, g):
    q, k, v, o, lse = res
    return _flash_bwd(q, k, v, o, lse, g, True, block_q, block_k,
                      window=window)


_flash_window.defvjp(_flash_window_vjp_fwd, _flash_window_vjp_bwd)


def band_mask(s_q: int, s_k: int, window: int):
    """(s_q, s_k) bool: query ``i`` (the queries are the last ``s_q`` of
    the ``s_k`` positions) sees keys ``j`` with ``i - window < j <= i``."""
    i = jnp.arange(s_q)[:, None] + (s_k - s_q)
    j = jnp.arange(s_k)[None, :]
    return (j <= i) & (j > i - window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_seg(q, k, v, segments, causal, block_q, block_k):
    return _flash_fwd(q, k, v, causal, block_q, block_k,
                      segments=segments)[0]


def _flash_seg_vjp_fwd(q, k, v, segments, causal, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, causal, block_q, block_k,
                          segments=segments)
    return out, (q, k, v, segments, out, lse)


def _flash_seg_vjp_bwd(causal, block_q, block_k, res, g):
    q, k, v, segments, o, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, g, causal, block_q, block_k,
                            segments=segments)
    return dq, dk, dv, None  # integer segment ids carry no cotangent


_flash_seg.defvjp(_flash_seg_vjp_fwd, _flash_seg_vjp_bwd)


def flash_attention(q, k, v, *, causal: bool = False,
                    mask: Optional[jax.Array] = None,
                    segments: Optional[jax.Array] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    window: Optional[int] = None):
    """(b, h, s, d) attention via the Pallas online-softmax kernel.

    ``window``: with ``causal=True``, query ``i`` sees keys ``j`` with
    ``i - window < j <= i``. Forward only (the serving prefill): the
    kernel's inner grid walks the K blocks of the band alone
    (``flash_fwd_window``); differentiating it raises. No mask or
    segments beside it; a shape the kernel cannot tile takes the dense
    path under :func:`band_mask`. ``window=None`` is the call as it was.

    ``segments``: (b, s) int document ids for packed rows (see
    dataset.text.pack_sequences) — the block-diagonal mask is applied
    *inside* the kernel, keeping packed long-context training O(seq)
    (self-attention shapes only; id 0 = padding). Key-padding masks
    route to :func:`blockwise_attention` (same O(seq) memory,
    XLA-fused); richer masks fall back to the dense path; ragged key
    lengths fall back inside the custom_vjp.

    ``block_q``/``block_k``: per-program tile sizes. ``None`` (default)
    asks the autotuner (bigdl_tpu.tuning) for this shape's measured
    decision and falls back to 512; explicit values are honored as
    before. Either way both dims clamp to a standard tiling that divides
    the sequence (no padded q blocks for mid sequences like 768).
    """
    # lazy: parallel/ imports this module (ring attention's softmax step)
    from bigdl_tpu.parallel.hints import shard_over_batch

    s_q, s_k = q.shape[-2], k.shape[-2]
    block_q, block_k = _resolve_blocks(s_q, s_k, q.shape[-1], causal,
                                       q.dtype, block_q, block_k)
    if window is not None:
        if not causal or mask is not None or segments is not None:
            raise ValueError("flash_attention: window needs causal=True "
                             "and neither mask nor segments")
        if s_q % block_q or not _tileable(s_q, s_k, block_k):
            return _dense.dot_product_attention(
                q, k, v, mask=band_mask(s_q, s_k, window))
        return shard_over_batch(
            lambda q, k, v: _flash_window(q, k, v, int(window), block_q,
                                          block_k))(q, k, v)
    if segments is not None:
        if mask is not None:
            raise ValueError("segments and mask are mutually exclusive")
        # the kv-segment block is (1, 8, bk), so Mosaic additionally
        # needs bk lane-aligned: a multiple of 128 or the whole s_k.
        # Clamp small block_k up to 128 when that still tiles; otherwise
        # fall back to the dense block-diagonal mask.
        bk = min(block_k, max(8, s_k))
        legal = s_k % bk == 0 and (bk == s_k or bk % 128 == 0)
        if not legal and s_k % 128 == 0:
            block_k, legal = 128, True
        if s_q != s_k or not legal:
            return _dense.dot_product_attention(
                q, k, v, causal=causal,
                mask=_dense.make_segment_mask(segments))
        out = shard_over_batch(
            lambda q, k, v, segments: _flash_seg(
                q, k, v, segments, causal, block_q, block_k))(
                    q, k, v, segments)
        # in-kernel, id-0 padding rows attend id-0 keys (keeps softmax
        # rows live for a finite backward); the dense fallback above
        # fully masks them to 0 instead. Zero them here so the same call
        # returns the same values regardless of shape-driven path choice.
        return jnp.where((segments != 0)[:, None, :, None], out, 0)
    if mask is not None:
        if _as_key_padding(mask, q.shape[0], k.shape[-2]) is not None:
            return blockwise_attention(q, k, v, causal=causal, mask=mask,
                                       block_k=block_k)
        return _dense.dot_product_attention(q, k, v, causal=causal,
                                            mask=mask)
    # _resolve_blocks already clamped both dims to standard tilings that
    # divide their sequence (so a 512 default never demotes a
    # 128-tileable length like 768 to the dense fallback, and q no
    # longer pads 768→1024); genuinely ragged lengths still fall back
    # inside the custom_vjp
    return shard_over_batch(
        lambda q, k, v: _flash(q, k, v, causal, block_q, block_k))(q, k, v)

"""The routed layer's rows between token order and expert order: two
Pallas kernels, each the other's transpose.

A routed layer (``nn/moe.py _routed_experts``) sorts its ``m = T * k``
picks by expert, absent experts last, and runs the grouped matmuls over
the sorted rows. ``order`` (m_pad,) int32 is the sort (sorted row ``q``
is pick ``order[q]``, pick ``t * k + j`` being token ``t``'s ``j``-th),
``pos`` (T, k) int32 its inverse, and ``n`` the number of picks on held
experts: the sorted rows ``0 .. n - 1``. The rows from ``n`` on are the
absent group, which the grouped matmuls never compute; neither kernel
reads or writes them.

* ``dispatch_rows(x, order, pos, n)`` -> (m_pad, d): row ``q`` is
  ``x[order[q] // k]`` for ``q < n``.
* ``combine_rows(y, order, pos, wts, n)`` -> (T, d) float32: ``out[t] =
  sum_j wts[t, j] * y[pos[t, j]]`` over the picks with ``pos[t, j] <
  n``. A pick on an absent expert is never read.

Rows at or past ``n`` of a dispatched array, and of the cotangent that
``combine_rows`` hands back for ``y``, are unspecified: the grouped
matmuls visit only their groups' tiles and mask what they store.

Each kernel's VJP is the other kernel (``jax.custom_vjp``): the
cotangent of ``x`` is ``combine_rows`` of the dispatched cotangent with
unit weights; that of ``y`` is the dispatch of ``wts[t, j] * g[t]`` into
row ``pos[t, j]``, and that of ``wts[t, j]``, ``<g[t], y[pos[t, j]]>``,
comes out of the same pass. Absent picks get zero cotangents.

How the rows move. An async copy moves whole (8, 128) tiles of an array
in HBM, so single rows cannot be copied there; in fast memory (VMEM) a
float32 row can be read and written at any index. So the token-indexed
side is held whole in VMEM as float32 (the dispatch's source rows,
copied in once; the combine's sums, zeroed, then copied out once), and
the sorted side streams through in blocks of 128 rows (``BlockSpec``
tiles, pipelined), one row at a time between the two. A grid step wholly
at or past ``n`` keeps the block index of the last step with rows, so its
blocks are neither read nor written back: with 8 of 64 experts held, 7
of every 8 steps do nothing. Each token's sum adds its picks in sorted
(expert) order. A token array longer than ``_RESIDENT_BYTES`` is walked
a chunk of tokens a call, the calls chained through an aliased output.
The sort and the count ``n`` are scalar prefetch (SMEM), as are the
weights. Each kernel's wrapper is under a ``jax.jit`` with its shapes
static, so a program traces and lowers it once a signature, not once a
layer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["dispatch_rows", "combine_rows"]

# Bytes of the token-indexed array a kernel keeps whole in fast memory (the
# dispatch's source rows, the combine's sums), float32: a longer array is
# walked in chunks of tokens, one call a chunk.
_RESIDENT_BYTES = 64 << 20
_ALIGN = 8  # an async copy moves whole (8, 128) tiles of rows


def _steps(m_pad: int) -> int:
    """Sorted rows a grid step takes: 128, or all of a smaller ``m_pad``
    (a multiple of 16)."""
    return min(128, m_pad)


def _chunks(T: int, d: int):
    """``(t0, t1)`` token ranges whose float32 rows fit in
    ``_RESIDENT_BYTES``; ``T`` a multiple of ``_ALIGN``."""
    size = max(_ALIGN, _RESIDENT_BYTES // (4 * d) // _ALIGN * _ALIGN)
    return [(t, min(T, t + size)) for t in range(0, T, size)]


def _vmem_limit(resident: int, blocks: int) -> int:
    return max(16 << 20, resident + blocks + (8 << 20))


def _last_step(n, rows):
    """The last grid step with rows below ``n``: a later step keeps its
    block index, so its blocks are neither read nor written back."""
    return jnp.maximum((n + rows - 1) // rows - 1, 0)


def _dispatch_kernel(*refs, rows, k, t0, t1, whole, scaled):
    """Grid step ``i``: sorted rows ``i * rows ..`` below ``n``, row ``q``
    the row ``order[q] // k`` of the source rows ``t0 .. t1`` held in fast
    memory (times ``wts[order[q]]`` where ``scaled``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    refs = list(refs)
    take = lambda count=1: [refs.pop(0) for _ in range(count)]
    order_ref, = take()
    wts_ref, = take() if scaled else [None]
    n_ref, x_hbm = take(2)
    prev = take(2 if scaled else 1) if not whole else None
    y_ref, = take() if scaled else [None]
    out_ref, = take()
    dw_ref, = take() if scaled else [None]
    res, acc, col, sem = refs
    i, n = pl.program_id(0), n_ref[0]

    @pl.when(i == 0)
    def _():
        c = pltpu.make_async_copy(x_hbm.at[pl.ds(t0, t1 - t0)], res, sem)
        c.start()
        c.wait()

    count = jnp.clip(n - i * rows, 0, rows)

    @pl.when(count > 0)
    def _():
        def one(r, carry):
            f = order_ref[i * rows + r]
            s = f // k - t0

            def copy():
                acc[pl.ds(r, 1), :] = res[pl.ds(s, 1), :]
            if whole:
                copy()
            else:  # col: 1 where the row's source is in this chunk
                mine = (s >= 0) & (s < t1 - t0)
                pl.when(mine)(copy)
                col[pl.ds(r, 1), 1:2] = jnp.full((1, 1), mine, jnp.float32)
            if scaled:
                col[pl.ds(r, 1), 0:1] = jnp.full((1, 1), wts_ref[f])
            return carry
        jax.lax.fori_loop(0, count, one, 0)
        got = acc[...]
        if scaled:
            dw = jnp.sum(got * y_ref[...].astype(jnp.float32), axis=1,
                         keepdims=True)
            got = got * col[:, 0:1]
        if not whole:  # a row whose source is in another chunk: as it was
            mine = col[:, 1:2] > 0
            got = jnp.where(mine, got, prev[0][...].astype(jnp.float32))
            if scaled:
                dw = jnp.where(mine, dw, prev[1][...])
        out_ref[...] = got.astype(out_ref.dtype)
        if scaled:
            dw_ref[...] = dw


@functools.partial(jax.jit, static_argnames=("k", "out_dtype", "interpret"))
def _gather(x, order, n, wts=None, y=None, *, k, out_dtype, interpret):
    """(m_pad, d) ``out_dtype``: row ``q < n`` is ``x[order[q] // k]``,
    times ``wts[order[q]]`` where ``wts`` (T * k,) is given; with ``y``
    (m_pad, d) also ``dw`` (m_pad,), ``dw[q] = <x[order[q] // k],
    y[q]>``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (m_pad,), (T, d) = order.shape, x.shape
    rows, scaled = _steps(m_pad), wts is not None
    x = jnp.pad(x.astype(jnp.float32), ((0, -T % _ALIGN), (0, 0)))
    scalars = [order.astype(jnp.int32)]
    if scaled:
        scalars.append(wts.astype(jnp.float32))
    scalars.append(jnp.reshape(n, (1,)).astype(jnp.int32))

    def tile(i, *scalars):
        return jnp.minimum(i, _last_step(scalars[-1][0], rows)), 0

    row_spec, col_spec = (pl.BlockSpec((rows, d), tile),
                          pl.BlockSpec((rows, 1), tile))
    shapes = [jax.ShapeDtypeStruct((m_pad, d), out_dtype)]
    if scaled:
        shapes.append(jax.ShapeDtypeStruct((m_pad, 1), jnp.float32))
    chunks = _chunks(x.shape[0], d)
    out = None
    for t0, t1 in chunks:
        whole = len(chunks) == 1
        in_specs, args, alias = [pl.BlockSpec(memory_space=pl.ANY)], [x], {}
        if not whole:
            prev = out or [jnp.zeros(sh.shape, sh.dtype) for sh in shapes]
            in_specs += [row_spec, col_spec][:len(shapes)]
            args += list(prev)
            alias = {len(scalars) + 1 + j: j for j in range(len(shapes))}
        if scaled:
            in_specs.append(row_spec)
            args.append(y)
        out = pl.pallas_call(
            functools.partial(_dispatch_kernel, rows=rows, k=k, t0=t0,
                              t1=t1, whole=whole, scaled=scaled),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(scalars), grid=(m_pad // rows,),
                in_specs=in_specs,
                out_specs=[row_spec, col_spec][:len(shapes)],
                scratch_shapes=[pltpu.VMEM((t1 - t0, d), jnp.float32),
                                pltpu.VMEM((rows, d), jnp.float32),
                                pltpu.VMEM((rows, 2), jnp.float32),
                                pltpu.SemaphoreType.DMA(())]),
            out_shape=shapes,
            input_output_aliases=alias,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_vmem_limit((t1 - t0) * d * 4,
                                             10 * rows * d * 4)),
            interpret=interpret,
            name="moe_dispatch_rows",
        )(*scalars, *args)
    return out[0] if not scaled else (out[0], out[1].reshape(m_pad))


def _combine_kernel(*refs, rows, k, t0, t1, whole, weighted):
    """Grid step ``i``: sorted rows ``i * rows ..`` below ``n``, row ``q``
    times ``wts[order[q]]`` added to the sum of token ``order[q] // k``
    among tokens ``t0 .. t1``, held in fast memory; the last step writes
    the sums out."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    refs = list(refs)
    order_ref = refs.pop(0)
    wts_ref = refs.pop(0) if weighted else None
    n_ref, y_ref = refs.pop(0), refs.pop(0)
    if not whole:
        refs.pop(0)  # the chunks before, aliased to the output
    out_hbm, res, ybuf, sem = refs
    i, n = pl.program_id(0), n_ref[0]

    @pl.when(i == 0)
    def _():
        res[...] = jnp.zeros(res.shape, jnp.float32)

    count = jnp.clip(n - i * rows, 0, rows)

    @pl.when(count > 0)
    def _():
        ybuf[...] = y_ref[...].astype(jnp.float32)

        def one(r, carry):
            f = order_ref[i * rows + r]
            t = f // k - t0

            def add():
                row = ybuf[pl.ds(r, 1), :]
                res[pl.ds(t, 1), :] += row * wts_ref[f] if weighted else row
            if whole:
                add()
            else:
                pl.when((t >= 0) & (t < t1 - t0))(add)
            return carry
        jax.lax.fori_loop(0, count, one, 0)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        c = pltpu.make_async_copy(res, out_hbm.at[pl.ds(t0, t1 - t0)], sem)
        c.start()
        c.wait()


@functools.partial(jax.jit, static_argnames=("T", "k", "interpret"))
def _scatter(y, order, n, wts=None, *, T, k, interpret):
    """(T, d) float32: ``sum_q wts[order[q]] * y[q]`` (``wts`` None: unit
    weights) over the sorted rows ``q < n`` with ``order[q] // k == t``,
    in sorted order."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m_pad, d = y.shape
    rows, weighted = _steps(m_pad), wts is not None
    T_pad = T + -T % _ALIGN
    scalars = [order.astype(jnp.int32)]
    if weighted:
        scalars.append(wts.astype(jnp.float32))
    scalars.append(jnp.reshape(n, (1,)).astype(jnp.int32))

    def tile(i, *scalars):
        return jnp.minimum(i, _last_step(scalars[-1][0], rows)), 0

    chunks = _chunks(T_pad, d)
    out = None
    for t0, t1 in chunks:
        whole = len(chunks) == 1
        in_specs, args = [pl.BlockSpec((rows, d), tile)], [y]
        if not whole:
            in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
            args.append(out if out is not None else
                        jnp.zeros((T_pad, d), jnp.float32))
        out = pl.pallas_call(
            functools.partial(_combine_kernel, rows=rows, k=k, t0=t0, t1=t1,
                              whole=whole, weighted=weighted),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(scalars), grid=(m_pad // rows,),
                in_specs=in_specs,
                out_specs=pl.BlockSpec(memory_space=pl.ANY),
                scratch_shapes=[pltpu.VMEM((t1 - t0, d), jnp.float32),
                                pltpu.VMEM((rows, d), jnp.float32),
                                pltpu.SemaphoreType.DMA(())]),
            out_shape=jax.ShapeDtypeStruct((T_pad, d), jnp.float32),
            input_output_aliases={} if whole else {len(scalars) + 1: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_vmem_limit((t1 - t0) * d * 4,
                                             6 * rows * d * 4)),
            interpret=interpret,
            name="moe_combine_rows",
        )(*scalars, *args)
    return out[:T] if T_pad != T else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def dispatch_rows(x, order, pos, n, interpret=False):
    """``x`` (T, d) to the sorted rows (m_pad, d): row ``q < n`` is
    ``x[order[q] // k]``, ``k = pos.shape[1]``."""
    return _gather(x, order, n, k=pos.shape[1], out_dtype=x.dtype,
                   interpret=interpret)


def _dispatch_fwd(x, order, pos, n, interpret):
    return dispatch_rows(x, order, pos, n, interpret), (order, pos, n)


def _dispatch_bwd(interpret, res, g):
    order, pos, n = res
    (T, k) = pos.shape
    dx = _scatter(g, order, n, T=T, k=k, interpret=interpret)
    return dx.astype(g.dtype), None, None, None


dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def combine_rows(y, order, pos, wts, n, interpret=False):
    """The sorted rows ``y`` (m_pad, d) back to the tokens, (T, d)
    float32: ``sum_j wts[t, j] * y[pos[t, j]]`` over the held picks
    (``pos[t, j] < n``)."""
    (T, k) = pos.shape
    return _scatter(y, order, n, wts.reshape(T * k), T=T, k=k,
                    interpret=interpret)


def _combine_fwd(y, order, pos, wts, n, interpret):
    return (combine_rows(y, order, pos, wts, n, interpret),
            (y, order, pos, wts, n))


def _combine_bwd(interpret, res, g):
    y, order, pos, wts, n = res
    (T, k) = pos.shape
    dy, dw = _gather(g, order, n, wts.reshape(T * k), y, k=k,
                     out_dtype=y.dtype, interpret=interpret)
    dwts = jnp.where(pos < n, jnp.take(dw, pos), 0.0)
    return dy, None, None, dwts.astype(wts.dtype), None


combine_rows.defvjp(_combine_fwd, _combine_bwd)

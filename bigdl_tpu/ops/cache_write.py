"""The decode step's K/V row write, with a batching rule of its own.

A model writes a token's K and V rows into its cache with
``write_rows(cache, rows, at)``: ``cache`` ``(b, kh, T, d)``, ``rows``
``(b, kh, m, d)``, ``at`` a scalar row index; alone it is
``dynamic_update_slice(cache, rows, (0, 0, at, 0))``. ``DecodeEngine``
runs the model under ``jax.vmap`` with a position per slot, and ``vmap``
of a ``dynamic_update_slice`` whose start is batched is a scatter, which
XLA on the TPU runs as a serial ``while`` over the slots: four tiny ops
an iteration, 2,880 iterations a StarCoder2 step, a third of the step to
write 1.5 MB. So the write is a ``custom_vmap`` whose rule looks at what
it is given:

* positions batched, one row a slot (``m == 1``), ``T`` a multiple of the
  dtype's row tile: one Pallas call (``cache_write_rows``) whose grid is
  the slots. The positions are prefetched scalars; grid step ``s`` reads
  the one aligned row tile of slot ``s`` that holds position ``at[s]``,
  replaces that row and writes the tile back, in place on the aliased
  cache. No two grid steps touch the same block.
* positions not batched: one ``dynamic_update_slice`` on the batched
  array (``vmap`` alone would make a scatter of that too).
* ``m > 1``, a ragged ``T``, or a step traced for a mesh (Pallas calls
  have no partitioning rule): ``vmap`` of the plain write, the scatter.
"""

from __future__ import annotations

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp

from bigdl_tpu.ops.attention_kernel import _interpret

__all__ = ["write_rows", "step_trace", "row_tile"]


def row_tile(dtype) -> int:
    """Rows of one (sublane, lane) tile: 8 for 4-byte elements, 16 for
    bf16, 32 for one-byte caches."""
    return 32 // jnp.dtype(dtype).itemsize


class _StepTrace(threading.local):
    kernel = True    # False: a mesh, where the kernel cannot be partitioned
    bounded = True   # False: a paged view, which ops.cache_attention reads whole
    chosen = None    # the engine's list of the forms the rules chose


_trace = _StepTrace()


@contextlib.contextmanager
def step_trace(chosen=None, kernel: bool = True, bounded: bool = True):
    """Around the tracing of a vmapped step. The rule appends the form it
    chose for each write (``"batched"`` or ``"scatter"``) to ``chosen``,
    and ``ops.cache_attention``'s rule the form of each read
    (``"bounded"`` or ``"whole"``); ``kernel=False`` (an engine with a
    mesh) keeps them to the scatter and the whole read,
    ``bounded=False`` (a step over a gathered copy of paged rows) the
    read alone."""
    old = _trace.kernel, _trace.bounded, _trace.chosen
    _trace.kernel, _trace.bounded, _trace.chosen = kernel, bounded, chosen
    try:
        yield
    finally:
        _trace.kernel, _trace.bounded, _trace.chosen = old


def _plain(cache, rows, at):
    return jax.lax.dynamic_update_slice(
        cache, rows.astype(cache.dtype), (0, 0, at, 0))


def _kernel(at_ref, rows_ref, cache_ref, out_ref, *, tile: int):
    from jax.experimental import pallas as pl

    row = at_ref[pl.program_id(0)] % tile
    iota = jax.lax.broadcasted_iota(jnp.int32, cache_ref.shape, 2)
    out_ref[...] = jnp.where(iota == row, rows_ref[...], cache_ref[...])


@jax.jit  # traced and lowered once a shape, not once a layer
def _write_batched(cache, rows, at):
    """cache (S, kh, T, d), rows (S, kh, 1, d) of cache's dtype, at (S,)
    int32: row ``at[s]`` of slot ``s`` replaced, in place."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, kh, T, d = cache.shape
    tile = row_tile(cache.dtype)
    # where dynamic_update_slice puts an out-of-range start: a negative
    # one counts from the end, then both ends clamp
    at = at.astype(jnp.int32)
    at = jnp.clip(jnp.where(at < 0, at + T, at), 0, T - 1)
    block = pl.BlockSpec((1, kh, tile, d),
                         lambda s, at: (s, 0, at[s] // tile, 0))
    return pl.pallas_call(
        functools.partial(_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S,),
            in_specs=[pl.BlockSpec((1, kh, 1, d),
                                   lambda s, at: (s, 0, 0, 0)),
                      block],
            out_specs=block),
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        input_output_aliases={2: 0},
        interpret=_interpret(),
        name="cache_write_rows",
    )(at, rows, cache)


@jax.custom_batching.custom_vmap
def write_rows(cache, rows, at):
    """``cache`` with ``rows`` (b, kh, m, d) written at rows at..at+m-1."""
    return _plain(cache, rows, at)


@write_rows.def_vmap
def _write_rows_vmap(axis_size, in_batched, cache, rows, at):
    cache_b, rows_b, at_b = in_batched
    if not at_b:  # one position for all: still one dynamic_update_slice
        if not cache_b:
            cache = jnp.broadcast_to(cache, (axis_size,) + cache.shape)
        if not rows_b:
            rows = jnp.broadcast_to(rows, (axis_size,) + rows.shape)
        return jax.lax.dynamic_update_slice(
            cache, rows.astype(cache.dtype), (0, 0, 0, at, 0)), True
    T, m = cache.shape[-2], rows.shape[-2]
    batched = (_trace.kernel and cache_b and rows_b and m == 1
               and T % row_tile(cache.dtype) == 0)
    if _trace.chosen is not None:
        _trace.chosen.append("batched" if batched else "scatter")
    if not batched:
        return jax.vmap(_plain, in_axes=(0 if cache_b else None,
                                         0 if rows_b else None, 0))(
            cache, rows, at), True
    b = cache.shape[1]
    out = _write_batched(
        cache.reshape((axis_size * b,) + cache.shape[2:]),
        rows.astype(cache.dtype).reshape((axis_size * b,) + rows.shape[2:]),
        jnp.repeat(at, b))
    return out.reshape(cache.shape), True

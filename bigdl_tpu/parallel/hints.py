"""Trace-time activation-sharding hints.

GSPMD propagates shardings through a program on its own, but reshapes that
collapse several dims into one (the conv→linear flatten) leave it free to
pick a spatial layout for the *cotangent* in the backward pass; it then has
to go e.g. ``{devices=[1,4,2,1]} → {devices=[8,1,1,1]}`` via full
replication ("Involuntary full rematerialization", spmd_partitioner.cc) —
correct, but a cliff at pod scale.

The fix is one well-placed :func:`jax.lax.with_sharding_constraint` on the
activation at the ambiguous boundary: the constraint's transpose rule
applies the same sharding to the cotangent, so the backward reshape keeps
the batch layout too. Modules can't see the mesh, and the strategy can't
see module internals, so the hand-off is a context variable: the strategy
sets the hint around the *trace* of the train step
(:meth:`DataParallel.compile_step` wraps ``train_step``), and
shape-changing modules (:class:`bigdl_tpu.nn.Reshape`) ask
:func:`constrain_batch` to pin dim 0 to the data axis.

The hint is only set by pure batch-sharding strategies (``batch_spec is
None``): under dp×sp or tensor-parallel layouts a dim-0-only constraint
would clobber the seq/model sharding of the activations it touches.
"""

from __future__ import annotations

from contextlib import contextmanager
from contextvars import ContextVar
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["batch_sharding_hint", "constrain_batch", "shard_over_batch"]

_BATCH_HINT: ContextVar[Optional[Tuple[Mesh, str]]] = ContextVar(
    "bigdl_tpu_batch_hint", default=None)


@contextmanager
def batch_sharding_hint(mesh: Mesh, axis: str):
    """Within this context (i.e. during the trace of a train step),
    :func:`constrain_batch` pins activations to ``P(axis, None, ...)``."""
    token = _BATCH_HINT.set((mesh, axis))
    try:
        yield
    finally:
        _BATCH_HINT.reset(token)


def constrain_batch(x):
    """Constrain dim 0 of ``x`` to the hinted data axis (no-op when no hint
    is active, outside a trace, or when dim 0 doesn't divide evenly —
    padding collectives would cost more than the reshard being avoided)."""
    hint = _BATCH_HINT.get()
    if hint is None or not hasattr(x, "ndim") or x.ndim < 1:
        return x
    mesh, axis = hint
    if x.shape[0] % mesh.shape[axis]:
        return x
    spec = P(axis, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shard_over_batch(fn):
    """``fn`` run per data-axis shard when a hint is active: every array
    argument and result carries the batch on dim 0, and each device gets
    its own slice through ``jax.shard_map``. For Pallas kernels — a
    Mosaic call has no GSPMD partitioning rule, and jax refuses to lower
    one inside a multi-device jit ("Mosaic kernels cannot be
    automatically partitioned"), so the data-parallel step hands each
    chip its batch slice explicitly. No hint (single device, or a
    composed dp x sp / tp layout): ``fn`` unchanged."""
    hint = _BATCH_HINT.get()
    if hint is None or hint[0].shape[hint[1]] == 1:
        return fn
    mesh, axis = hint
    return jax.shard_map(fn, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
                         check_vma=False)

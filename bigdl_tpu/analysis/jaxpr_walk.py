"""Jaxpr traversal for the tpulint pass — provenance-preserving iteration
over a ClosedJaxpr including every nested sub-jaxpr (``jit`` bodies,
``custom_vjp``/``custom_jvp`` rules, scan/while/cond branches, and
``pallas_call`` kernel bodies).

Unlike ``utils/flops.py`` (which only needs a FLOP sum), rules need to
know *where* an equation lives — so each visited jaxpr level carries a
path string like ``jit:train_step/custom_vjp_call_jaxpr/pallas_call:
_fba_fwd_kernel`` — and *who consumes* each value, so the dtype rules can
tell a stats-reduction upcast from an fp32-softmax one. Everything here
is read-only over trace-time metadata: no compilation, no execution, no
device needed.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np
from jax.extend import core as jex_core

__all__ = ["JaxprLevel", "iter_levels", "eqn_label", "consumers_map",
           "pallas_block_views", "pallas_scratch_avals",
           "pallas_kernel_name", "aval_bytes",
           "ShardedLevel", "sharded_levels", "named_sharding",
           "spec_axes", "observed_mesh_axes", "collect_constraints",
           "collect_collectives", "COLLECTIVE_PRIMS"]


def aval_bytes(aval) -> int:
    """Abstract byte size of one value (0 when shape/dtype are absent,
    e.g. tokens of an opaque effect)."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        try:
            n *= int(d)
        except TypeError:  # symbolic/polymorphic dim
            return 0
    return n * np.dtype(dtype).itemsize


def eqn_label(eqn) -> str:
    """Short label for one equation: primitive plus its best name hint
    (jit ``name``, pallas kernel name) when one exists."""
    name = eqn.params.get("name") if eqn.params else None
    if name is None and eqn.primitive.name == "pallas_call":
        name = pallas_kernel_name(eqn)
    return (f"{eqn.primitive.name}:{name}" if name
            else eqn.primitive.name)


def pallas_kernel_name(eqn) -> Optional[str]:
    """Kernel FUNCTION name of a ``pallas_call`` eqn (the kernel jaxpr's
    debug info — ``functools.partial`` wrappers resolve to the wrapped
    function), or None."""
    info = getattr(eqn.params.get("jaxpr"), "debug_info", None)
    return getattr(info, "func_name", None) or None


def _sub_jaxprs(eqn) -> Iterator[Tuple[object, str]]:
    """(jaxpr, label) pairs for every sub-jaxpr carried in one eqn's
    params — the recursion edge of the walk."""
    label = eqn_label(eqn)
    for v in eqn.params.values():
        vs = v if isinstance(v, (tuple, list)) else [v]
        for w in vs:
            if isinstance(w, jex_core.ClosedJaxpr):
                yield w.jaxpr, label
            elif isinstance(w, jex_core.Jaxpr):
                yield w, label


@dataclass
class JaxprLevel:
    """One jaxpr in the nesting tree: the jaxpr itself, the ``/``-joined
    path of enclosing eqn labels (empty for the top level), and depth."""
    jaxpr: object
    path: str
    depth: int

    def where(self, i: int, eqn) -> str:
        """Provenance string for eqn ``i`` of this level."""
        base = f"{self.path}/" if self.path else ""
        return f"{base}{eqn_label(eqn)}#{i}"


def iter_levels(jaxpr, path: str = "", depth: int = 0,
                max_depth: int = 24) -> Iterator[JaxprLevel]:
    """Yield every jaxpr level (pre-order), starting at ``jaxpr`` itself.
    Accepts a ClosedJaxpr or Jaxpr. ``max_depth`` guards against
    pathological nesting."""
    if isinstance(jaxpr, jex_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    yield JaxprLevel(jaxpr, path, depth)
    if depth >= max_depth:
        return
    for i, eqn in enumerate(jaxpr.eqns):
        for sub, label in _sub_jaxprs(eqn):
            sub_path = f"{path}/{label}#{i}" if path else f"{label}#{i}"
            yield from iter_levels(sub, sub_path, depth + 1, max_depth)


def consumers_map(jaxpr) -> Dict[object, List[object]]:
    """var -> [consumer eqns] within ONE jaxpr level (no recursion —
    cross-level dataflow goes through sub-jaxpr invars, which the nested
    level's own map sees)."""
    out: Dict[object, List[object]] = {}
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if isinstance(v, jex_core.Literal):
                continue
            out.setdefault(v, []).append(eqn)
    return out


# --------------------------------------------------------------- sharding
# The shardlint walk (ISSUE 19). jit-SPMD traces carry no collective
# eqns — the partitioner inserts them after tracing — so everything a
# static pass can know about the multichip plan lives in ANNOTATIONS:
# ``jit`` eqn params (``in_shardings``/``out_shardings`` zip with the
# body's invars/outvars), ``sharding_constraint`` eqns (the
# ``with_sharding_constraint`` steering points, e.g. grad_comm's
# compressed buckets), and — in shard_map/pmap graphs only — explicit
# collective primitives. ``sharded_levels`` threads those annotations
# through every nesting level so the sharding_rules module reads a
# var -> NamedSharding environment instead of re-deriving placement.

# explicit collective primitives (shard_map/pmap graphs only; jit-SPMD
# traces never contain these — mirrored by rules._COLLECTIVE_PRIMS).
# psum_invariant is what psum binds to under shard_map's check_vma.
COLLECTIVE_PRIMS = ("psum", "psum_invariant", "ppermute", "all_gather",
                    "all_to_all", "reduce_scatter", "psum_scatter",
                    "pmax", "pmin")

# single-input primitives that neither reshape nor re-lay-out their
# operand: a sharding known for the input holds for the output (the
# edge the wire-dtype and churn rules follow through casts)
_SHARDING_TRANSPARENT = ("convert_element_type", "copy", "device_put",
                         "stop_gradient", "neg", "exp", "log", "tanh",
                         "integer_pow", "sqrt", "rsqrt", "abs")


def named_sharding(s) -> Optional[object]:
    """``s`` if it is a usable NamedSharding-like annotation (has a spec
    and a mesh), else None — filters jit's UnspecifiedValue entries."""
    if s is None:
        return None
    if getattr(s, "spec", None) is None or getattr(s, "mesh", None) is None:
        return None
    return s


def spec_axes(spec) -> List[str]:
    """Mesh axis names referenced by one PartitionSpec, in dim order
    (entries may be axis tuples — flattened here)."""
    out: List[str] = []
    for entry in tuple(spec or ()):
        if entry is None:
            continue
        for ax in (entry if isinstance(entry, (tuple, list)) else (entry,)):
            if ax is not None:
                out.append(str(ax))
    return out


@dataclass
class ShardedLevel:
    """One jaxpr level plus its sharding environment: ``shardings`` maps
    this level's vars to the NamedSharding annotations that reach them
    (jit boundary zips, constraint eqns, transparent-op propagation)."""
    jaxpr: object
    path: str
    depth: int
    shardings: Dict[object, object]

    def where(self, i: int, eqn) -> str:
        base = f"{self.path}/" if self.path else ""
        return f"{base}{eqn_label(eqn)}#{i}"


def _bind(env: Dict[object, object], var, sharding) -> None:
    if sharding is not None and not isinstance(var, jex_core.Literal):
        env[var] = sharding


def _lookup(env: Dict[object, object], var):
    if isinstance(var, jex_core.Literal):
        return None
    return env.get(var)


def _walk_sharded(jaxpr, path: str, depth: int,
                  env: Dict[object, object], out: List[ShardedLevel],
                  max_depth: int = 24) -> None:
    level = ShardedLevel(jaxpr, path, depth, env)
    out.append(level)
    for i, eqn in enumerate(jaxpr.eqns):
        name = eqn.primitive.name
        sub_path = (f"{path}/{eqn_label(eqn)}#{i}" if path
                    else f"{eqn_label(eqn)}#{i}")
        if name == "sharding_constraint":
            _bind(env, eqn.outvars[0],
                  named_sharding(eqn.params.get("sharding")))
        elif name == "jit" and depth < max_depth:
            closed = eqn.params.get("jaxpr")
            sub = closed.jaxpr if isinstance(
                closed, jex_core.ClosedJaxpr) else closed
            sub_env: Dict[object, object] = {}
            in_sh = eqn.params.get("in_shardings") or ()
            for v, s in zip(sub.invars, in_sh):
                _bind(sub_env, v, named_sharding(s))
            # caller knowledge flows in where the boundary left the
            # sharding unspecified (nested jit under an annotated one)
            for v_sub, v_call in zip(sub.invars, eqn.invars):
                if v_sub not in sub_env:
                    _bind(sub_env, v_sub, _lookup(env, v_call))
            _walk_sharded(sub, sub_path, depth + 1, sub_env, out,
                          max_depth)
            out_sh = eqn.params.get("out_shardings") or ()
            for v, s in zip(eqn.outvars, out_sh):
                _bind(env, v, named_sharding(s))
            # body-constrained outputs bubble up through unspecified
            # out_shardings (e.g. a constrained bucket returned as-is)
            for v_call, v_body in zip(eqn.outvars, sub.outvars):
                if v_call not in env:
                    _bind(env, v_call, _lookup(sub_env, v_body))
        elif name in _SHARDING_TRANSPARENT and len(eqn.outvars) == 1 \
                and eqn.invars:
            _bind(env, eqn.outvars[0], _lookup(env, eqn.invars[0]))
        elif depth < max_depth:
            # custom_vjp/scan/while/pallas etc.: recurse with positional
            # invar propagation when the sub signature lines up
            for sub, _label in _sub_jaxprs(eqn):
                sub_env = {}
                if len(getattr(sub, "invars", ())) == len(eqn.invars):
                    for v_sub, v_call in zip(sub.invars, eqn.invars):
                        _bind(sub_env, v_sub, _lookup(env, v_call))
                _walk_sharded(sub, sub_path, depth + 1, sub_env, out,
                              max_depth)


def sharded_levels(jaxpr, max_depth: int = 24) -> List[ShardedLevel]:
    """Every jaxpr level (pre-order) with its sharding environment fully
    populated — the shardlint analogue of :func:`iter_levels`. Accepts a
    ClosedJaxpr or Jaxpr; read-only trace-time metadata, no devices."""
    if isinstance(jaxpr, jex_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    out: List[ShardedLevel] = []
    _walk_sharded(jaxpr, "", 0, {}, out, max_depth)
    return out


def observed_mesh_axes(levels: List[ShardedLevel]) -> Dict[str, int]:
    """Merged axis -> size of every mesh named by any annotation in the
    walk (constraint shardings, jit boundary shardings)."""
    axes: Dict[str, int] = {}
    for lv in levels:
        for s in lv.shardings.values():
            mesh = getattr(s, "mesh", None)
            shape = getattr(mesh, "shape", None)
            if shape:
                for k, v in dict(shape).items():
                    axes[str(k)] = int(v)
        for eqn in lv.jaxpr.eqns:
            if eqn.primitive.name != "sharding_constraint":
                continue
            s = named_sharding(eqn.params.get("sharding"))
            shape = getattr(getattr(s, "mesh", None), "shape", None)
            if shape:
                for k, v in dict(shape).items():
                    axes[str(k)] = int(v)
    return axes


def collect_constraints(levels: List[ShardedLevel]) -> List[tuple]:
    """Every ``sharding_constraint`` eqn in the walk as
    ``(level, eqn_index, eqn, sharding, prev_sharding)`` — ``sharding``
    the constraint applied, ``prev_sharding`` what the walk knew about
    the operand BEFORE the constraint (None when unannotated); the raw
    material of the wire-dtype and reshard-churn rules."""
    out = []
    for lv in levels:
        for i, eqn in enumerate(lv.jaxpr.eqns):
            if eqn.primitive.name != "sharding_constraint":
                continue
            s = named_sharding(eqn.params.get("sharding"))
            if s is None:
                continue
            prev = _lookup(lv.shardings, eqn.invars[0])
            out.append((lv, i, eqn, s, prev))
    return out


def collect_collectives(levels: List[ShardedLevel]) -> List[tuple]:
    """Every explicit collective eqn (shard_map/pmap graphs only) as
    ``(level, eqn_index, eqn, axis_names)``."""
    out = []
    for lv in levels:
        for i, eqn in enumerate(lv.jaxpr.eqns):
            if eqn.primitive.name not in COLLECTIVE_PRIMS:
                continue
            axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
            if not isinstance(axes, (tuple, list)):
                axes = (axes,)
            names = tuple(str(a) for a in axes if isinstance(a, str))
            out.append((lv, i, eqn, names))
    return out


# ------------------------------------------------------------------ pallas
def pallas_block_views(eqn) -> List[Tuple[Tuple, Tuple, object, bool]]:
    """(block_shape, array_shape, dtype, is_output) for every block
    mapping of a ``pallas_call`` eqn — the raw material of the tiling,
    padding and VMEM rules (jax 0.9.0 field names: ``array_aval``, and
    block dims wrapped as ``Blocked(block_size=n)``; squeezed dims,
    which carry no ``block_size``, count as 1)."""
    gm = eqn.params["grid_mapping"]
    views = []
    for idx, bm in enumerate(gm.block_mappings):
        bs = tuple(d if isinstance(d, (int, np.integer))
                   else int(getattr(d, "block_size", 1))
                   for d in bm.block_shape)
        aval = bm.array_aval
        views.append((bs, tuple(aval.shape), np.dtype(aval.dtype),
                      idx >= gm.num_inputs))
    return views


def pallas_scratch_avals(eqn) -> List[object]:
    """Avals of the kernel's scratch operands (the VMEM accumulators) —
    the tail invars of the kernel jaxpr, per ``num_scratch_operands``."""
    gm = eqn.params.get("grid_mapping")
    n = int(getattr(gm, "num_scratch_operands", 0) or 0)
    if n <= 0:
        return []
    kj = eqn.params.get("jaxpr")
    if isinstance(kj, jex_core.ClosedJaxpr):
        kj = kj.jaxpr
    invars = getattr(kj, "invars", None)
    if not invars:
        return []
    out = []
    for v in invars[-n:]:
        aval = getattr(v, "aval", None)
        inner = getattr(aval, "inner_aval", aval)  # Ref wraps the array
        if inner is not None:
            out.append(inner)
    return out

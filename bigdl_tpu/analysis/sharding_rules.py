"""shardlint — sharding- and collective-aware rules over the real
multichip graphs (ISSUE 19 tentpole).

The single-device tpulint rules (``rules.py``) read equations; these
rules read the SPMD *plan*: the ``NamedSharding``/``PartitionSpec``
annotations that :mod:`bigdl_tpu.analysis.jaxpr_walk.sharded_levels`
threads through nested jit levels, plus the abstract param/KV spec
trees the strategies expose. jit-SPMD traces carry no collective eqns
(the partitioner inserts them after tracing), so what a static pass can
check is exactly what the annotations promise — and that is enough for
the five failure classes that dominate multichip step time:

1. **strategy/collective consistency** — the declared ``--strategy``
   mesh implies an expected signature (dp ⇒ a steered grad reduction
   per bucket, tp ⇒ a row-split layout that creates the partial-sum
   reduce, ep ⇒ expert-axis routing); a mesh axis nothing shards over,
   an annotation naming an undeclared axis, or an explicit collective
   the strategy never asked for are all errors.
2. **replicated-large-operand** — a ≥ 1 MiB operand left fully
   replicated under a model-ish mesh axis (the mesh-aware
   generalization of PR 15's serving-only ``serving-unsharded-matmul``,
   which stays as an alias).
3. **wire-dtype** — a ≥ 1 MiB replication point still crossing in f32
   while ``--gradCompress`` is active, or an 8-bit weight
   rematerialized dense right before a sharding boundary.
4. **reshard churn** — conflicting consecutive sharding constraints
   (all-gather → re-partition ping-pong) with an estimated wasted-bytes
   figure.
5. **KV-pool sharding misfit** — a paged/dense KV layout whose
   ``kv_heads`` dim the tp degree cannot split, breaking the
   ``P(None, "model", None, None)`` head split the serving engines pin.

Everything runs fully abstractly: AbstractMesh + ``eval_shape`` traces,
no devices, no compiles — seconds on CPU (PERF.md §26).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from bigdl_tpu.analysis.jaxpr_walk import (aval_bytes, collect_collectives,
                                           collect_constraints,
                                           observed_mesh_axes,
                                           sharded_levels, spec_axes)
from bigdl_tpu.analysis.report import Finding, Report

__all__ = ["SHARD_CATALOG", "SHARD_MIN_BYTES", "expected_collective_axes",
           "run_sharding_rules", "run_replicated_operand_rules",
           "run_kv_sharding_rules"]

# operands below this are latency-bound anyway — same bar as the
# serving tp rule and the comm f32 rule (1 MiB)
SHARD_MIN_BYTES = 1 * 2 ** 20

# mesh axes that replicate params BY DESIGN (dp batch axes): the
# replicated-operand rule only fires for the model-ish axes
_DATA_AXES = ("data", "batch")

# the collective signature each strategy is allowed to produce: an
# explicit collective (shard_map graphs) over any other axis is "extra"
_EXPECTED_AXES = {
    "dp": ("data",),
    "tp": ("data", "model"),
    "sp": ("data", "seq"),
    "pp": ("data", "pipe"),
    "ep": ("data", "expert"),
}

SHARD_CATALOG = {
    "shard-collective-axis": (
        "sharding", "error",
        "a sharding annotation or explicit collective references a mesh "
        "axis the declared --strategy mesh does not define — the "
        "partitioner would reject or silently replicate it"),
    "shard-collective-missing": (
        "sharding", "error",
        "the declared strategy implies a collective signature the traced "
        "step does not carry (a mesh axis nothing shards over, a "
        "gradCompress run with no 16-bit steered bucket, a tp layout "
        "with no split weight) — the strategy is a silent no-op"),
    "shard-collective-extra": (
        "sharding", "error",
        "an explicit collective over an axis the declared strategy never "
        "asked for — an unplanned reduction in the hot path"),
    "shard-replicated-operand": (
        "sharding", "error",
        "a >=1 MiB operand fully replicated under a model-ish mesh axis "
        "(mesh-aware generalization of serving-unsharded-matmul): every "
        "shard computes/stores it whole"),
    "shard-wire-dtype": (
        "sharding", "error",
        "a >=1 MiB replication point crossing the wire in f32 while "
        "--gradCompress is active — the compressed path did not engage "
        "for this value"),
    "shard-quant-remat-wire": (
        "sharding", "warning",
        "an 8-bit tensor dequantized dense immediately before a sharding "
        "boundary — the wire/HBM carries the dense value, forfeiting the "
        "quantization win"),
    "shard-reshard-churn": (
        "sharding", "warning",
        "conflicting consecutive sharding constraints on one value: "
        "all-gather then re-partition ping-pong the partitioner must "
        "materialize, with estimated wasted wire bytes"),
    "kv-shard-misfit": (
        "sharding", "error",
        "KV pool/cache layout whose kv_heads dim the tp degree cannot "
        "split — pages replicate on every chip, breaking the "
        "P(None,'model',None,None) head split the engines pin"),
}


def _shard_finding(rule: str, message: str, where: str = "",
                   hint: str = "", detail: Optional[dict] = None,
                   severity: Optional[str] = None) -> Finding:
    fam, sev, _ = SHARD_CATALOG[rule]
    return Finding(rule=rule, family=fam, severity=severity or sev,
                   message=message, where=where, hint=hint,
                   detail=detail or {})


def expected_collective_axes(strategy: Optional[str]) -> Tuple[str, ...]:
    """Mesh axes the declared strategy may legitimately reduce over
    (``None``/unknown strategy allows any declared axis)."""
    if not strategy:
        return ()
    return _EXPECTED_AXES.get(str(strategy), ())


def _spec_is_replicated(spec) -> bool:
    return not any(a is not None for a in tuple(spec or ()))


def _eqn_out_aval(eqn):
    return getattr(eqn.outvars[0], "aval", None) if eqn.outvars else None


# ================================================ group 1: consistency
def _rule_axis_membership(constraints, collectives, declared, report):
    for lv, i, eqn, s, _prev in constraints:
        bad = [a for a in spec_axes(s.spec) if a not in declared]
        if bad:
            report.add(_shard_finding(
                "shard-collective-axis",
                f"sharding constraint over undeclared mesh axis(es) "
                f"{bad} — declared mesh is "
                f"{{{', '.join(f'{k}:{v}' for k, v in declared.items())}}}",
                where=lv.where(i, eqn),
                hint="align with_sharding_constraint specs with the "
                     "--strategy mesh (bigdl_tpu.cli.common."
                     "strategy_mesh_axes)",
                detail={"axes": bad, "mesh": dict(declared)}))
    for lv, i, eqn, names in collectives:
        bad = [a for a in names if a not in declared]
        if bad:
            report.add(_shard_finding(
                "shard-collective-axis",
                f"{eqn.primitive.name} over undeclared mesh axis(es) "
                f"{bad}",
                where=lv.where(i, eqn),
                hint="the collective's axis_name must be a declared "
                     "mesh axis",
                detail={"axes": bad, "mesh": dict(declared)}))


def _rule_extra_collectives(collectives, declared, strategy, context,
                            report):
    allowed = set(expected_collective_axes(strategy)) or set(declared)
    if context == "serving":
        # the decode/verify hot path plans NO explicit collectives —
        # tp resolution is the partitioner's (annotation-driven)
        allowed = set()
    for lv, i, eqn, names in collectives:
        extra = [a for a in names if a in declared and a not in allowed]
        if extra:
            report.add(_shard_finding(
                "shard-collective-extra",
                f"{eqn.primitive.name} over axis(es) {extra} — the "
                f"declared strategy "
                f"({strategy or context}) plans no collective there",
                where=lv.where(i, eqn),
                hint="drop the collective or declare the strategy that "
                     "owns it",
                detail={"axes": extra, "strategy": strategy,
                        "context": context}))


def _rule_signature(levels, constraints, collectives, declared, strategy,
                    grad_comm, param_specs, report):
    # (a) every declared >1 axis must be referenced by SOME annotation
    referenced = set()
    for lv in levels:
        for s in lv.shardings.values():
            referenced.update(spec_axes(getattr(s, "spec", ())))
    for _lv, _i, _eqn, s, _prev in constraints:
        referenced.update(spec_axes(s.spec))
    for _lv, _i, _eqn, names in collectives:
        referenced.update(names)
    if param_specs is not None:
        import jax
        from jax.sharding import PartitionSpec as P
        for sp in jax.tree_util.tree_leaves(
                param_specs, is_leaf=lambda x: isinstance(x, P)):
            if isinstance(sp, P):
                referenced.update(spec_axes(sp))
    for axis, size in declared.items():
        if size > 1 and axis not in referenced:
            report.add(_shard_finding(
                "shard-collective-missing",
                f"mesh axis {axis!r}:{size} is declared but no "
                "annotation in the traced step shards anything over it "
                "— the strategy is a silent no-op on that axis",
                where="mesh",
                hint="check the strategy's spec builder (megatron_specs "
                     "divisibility, batch sharding) against the model "
                     "geometry",
                detail={"axis": axis, "size": int(size),
                        "referenced": sorted(referenced)}))

    # (b) gradCompress declared ⇒ ≥1 steered 16-bit bucket (the
    # apply_grad_comm replication point) must exist in the traced step
    if grad_comm is not None and getattr(grad_comm, "active", False) \
            and any(s > 1 for s in declared.values()):
        wire16 = 0
        for _lv, _i, eqn, s, _prev in constraints:
            aval = _eqn_out_aval(eqn)
            dt = getattr(aval, "dtype", None)
            if dt is None or not _spec_is_replicated(s.spec):
                continue
            if np.dtype(dt).itemsize == 2:
                wire16 += 1
        if wire16 == 0:
            report.add(_shard_finding(
                "shard-collective-missing",
                f"--gradCompress {grad_comm.compress} is active but the "
                "traced step carries no 16-bit steered bucket "
                "(with_sharding_constraint on a compressed value) — "
                "the grad all-reduce would ride f32",
                where="grad_comm",
                hint="route reduce_grads through parallel.grad_comm."
                     "apply_grad_comm (the DataParallel path does)",
                detail={"compress": grad_comm.compress}))

    # (c) tp ⇒ the layout must actually split weights (the row-split
    # partial-sum reduce is what the strategy buys)
    if strategy == "tp" and param_specs is not None \
            and int(declared.get("model", 1)) > 1:
        import jax
        from jax.sharding import PartitionSpec as P
        leaves = [sp for sp in jax.tree_util.tree_leaves(
            param_specs, is_leaf=lambda x: isinstance(x, P))
            if isinstance(sp, P)]
        n_split = sum(1 for sp in leaves if not _spec_is_replicated(sp))
        if leaves and n_split == 0:
            report.add(_shard_finding(
                "shard-collective-missing",
                f"tp mesh (model:{declared['model']}) declared but the "
                "Megatron layout split zero parameter leaves — no "
                "row-split reduce exists; every chip runs the full "
                "model",
                where="megatron_specs",
                hint="pick a tp degree that divides d_model/heads, or "
                     "drop --strategy tp for this model",
                detail={"tp": int(declared["model"]),
                        "param_leaves": len(leaves)}))


# ===================================== group 3: wire dtype / quant remat
def _rule_wire_dtype(constraints, grad_comm, report):
    if grad_comm is None or not getattr(grad_comm, "active", False):
        return
    for lv, i, eqn, s, _prev in constraints:
        aval = _eqn_out_aval(eqn)
        nbytes = aval_bytes(aval)
        dt = getattr(aval, "dtype", None)
        if dt is None or nbytes < SHARD_MIN_BYTES:
            continue
        if _spec_is_replicated(s.spec) and np.dtype(dt) == np.float32:
            report.add(_shard_finding(
                "shard-wire-dtype",
                f"{nbytes / 2**20:.1f} MiB replication point crosses "
                f"the wire in f32 while --gradCompress "
                f"{grad_comm.compress} is active — this value bypassed "
                "the compressed bucket path",
                where=lv.where(i, eqn),
                hint="compress before the constraint "
                     "(grad_comm.compress_bucket) or exclude the value "
                     "from the steered set deliberately",
                detail={"bytes": nbytes,
                        "compress": grad_comm.compress}))


_8BIT_NAMES = ("int8", "uint8", "float8_e4m3fn", "float8_e5m2")


def _rule_quant_remat(levels, report):
    """8-bit → wide convert whose (≥1 MiB) result feeds a sharding
    boundary within a few transparent hops: the dense rematerialization
    crosses the wire, not the 8-bit value (composes with the
    quant-dequant-upcast chain rule)."""
    for lv in levels:
        dense_from_8bit = {}  # id(outvar) -> src dtype name (Vars only;
        for eqn in lv.jaxpr.eqns:  # Literal operands are unhashable)
            if eqn.primitive.name != "convert_element_type":
                continue
            src = getattr(getattr(eqn.invars[0], "aval", None), "dtype",
                          None)
            if src is None or str(np.dtype(src)) not in _8BIT_NAMES:
                continue
            out = eqn.outvars[0]
            if aval_bytes(getattr(out, "aval", None)) >= SHARD_MIN_BYTES:
                dense_from_8bit[id(out)] = str(np.dtype(src))
        if not dense_from_8bit:
            continue
        # follow ≤4 pointwise hops to a sharding_constraint consumer
        hops = dict(dense_from_8bit)
        for _ in range(4):
            grew = {}
            for eqn in lv.jaxpr.eqns:
                srcs = [v for v in eqn.invars if id(v) in hops]
                if not srcs or not eqn.outvars:
                    continue
                if eqn.primitive.name == "sharding_constraint":
                    continue
                if len(eqn.outvars) == 1 and aval_bytes(getattr(
                        eqn.outvars[0], "aval", None)) >= SHARD_MIN_BYTES:
                    grew[id(eqn.outvars[0])] = hops[id(srcs[0])]
            before = len(hops)
            hops.update(grew)
            if len(hops) == before:
                break
        for i, eqn in enumerate(lv.jaxpr.eqns):
            if eqn.primitive.name != "sharding_constraint":
                continue
            v = eqn.invars[0]
            if id(v) in hops:
                nbytes = aval_bytes(getattr(v, "aval", None))
                report.add(_shard_finding(
                    "shard-quant-remat-wire",
                    f"{hops[id(v)]} tensor rematerialized dense "
                    f"({nbytes / 2**20:.1f} MiB) before a sharding "
                    "boundary — the wire carries the dense value",
                    where=lv.where(i, eqn),
                    hint="keep the 8-bit value across the boundary and "
                         "dequantize per shard (QuantizedWeight keeps "
                         "the scale alongside)",
                    detail={"bytes": nbytes, "src_dtype": hops[id(v)]}))


# ============================================== group 4: reshard churn
def _churn_factor(axes: List[str], declared: Dict[str, int]) -> float:
    n = 1
    for a in axes:
        n *= int(declared.get(a, 1))
    return (n - 1) / n if n > 1 else 0.0


def _rule_reshard_churn(constraints, declared, report):
    for lv, i, eqn, s, prev in constraints:
        if prev is None:
            continue
        s_spec, p_spec = tuple(s.spec or ()), tuple(
            getattr(prev, "spec", ()) or ())
        if _spec_is_replicated(s_spec) or _spec_is_replicated(p_spec):
            continue  # first placement / deliberate full gather
        if s_spec == p_spec:
            continue
        nbytes = aval_bytes(_eqn_out_aval(eqn))
        if nbytes < SHARD_MIN_BYTES:
            continue
        gathered = int(nbytes * _churn_factor(spec_axes(p_spec), declared))
        rescattered = int(nbytes * _churn_factor(spec_axes(s_spec),
                                                 declared))
        report.add(_shard_finding(
            "shard-reshard-churn",
            f"consecutive conflicting sharding constraints "
            f"{p_spec} -> {s_spec} on a {nbytes / 2**20:.1f} MiB value: "
            "the partitioner must all-gather then re-partition "
            f"(~{(gathered + rescattered) / 2**20:.1f} MiB wasted wire)",
            where=lv.where(i, eqn),
            hint="pick ONE layout for the value's lifetime, or reshape "
                 "under a single constraint",
            detail={"bytes": nbytes, "from": [str(a) for a in p_spec],
                    "to": [str(a) for a in s_spec],
                    "wasted_bytes": gathered + rescattered}))


# ======================================= group 2: replicated operands
def _leaf_layout(leaf, spec):
    """(shape, nbytes, replicated) for one param leaf — reads the
    committed ``.sharding`` when the leaf is a placed array, the spec
    tree entry when linting abstractly; ``None`` (unknown placement —
    abstract leaf with no spec) never fires the rule."""
    shape = tuple(getattr(leaf, "shape", ()))
    dtype = getattr(leaf, "dtype", None)
    if not shape or dtype is None:
        return shape, 0, None
    nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
    sharding = getattr(leaf, "sharding", None)
    if sharding is not None and hasattr(sharding, "is_fully_replicated"):
        return shape, nbytes, bool(sharding.is_fully_replicated)
    if spec is not None:
        return shape, nbytes, _spec_is_replicated(spec)
    return shape, nbytes, None


def run_replicated_operand_rules(params, mesh_axes: Dict[str, int], *,
                                 specs=None, split_axes=None,
                                 rule_id: str = "shard-replicated-operand",
                                 report: Optional[Report] = None) -> Report:
    """Mesh-aware replicated-large-operand rule over training AND
    serving param trees (ISSUE 19 group 2): a ≥ 1 MiB, ≥ 2-D leaf fully
    replicated while a model-ish mesh axis is declared means every
    shard computes/stores it whole. Reads committed ``.sharding`` on
    placed arrays or the abstract ``specs`` tree; ``rule_id`` keeps the
    PR 15 ``serving-unsharded-matmul`` spelling as an alias (the serve
    preflight / existing tests)."""
    report = report if report is not None else Report()
    declared = {str(k): int(v) for k, v in (mesh_axes or {}).items()}
    if split_axes is None:
        split_axes = tuple(a for a in declared
                           if a not in _DATA_AXES and declared[a] > 1)
    sizes = [declared[a] for a in split_axes if declared.get(a, 1) > 1]
    if not sizes:
        return report
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    spec_leaves = None
    if specs is not None:
        from jax.sharding import PartitionSpec as P
        spec_leaves = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        if len(spec_leaves) != len(flat):
            spec_leaves = None  # shape mismatch: fall back to .sharding
    legacy = rule_id == "serving-unsharded-matmul"
    for n, (path, leaf) in enumerate(flat):
        if legacy and getattr(leaf, "sharding", None) is None:
            continue  # PR 15 semantics: placed trees only
        spec = spec_leaves[n] if spec_leaves is not None else None
        shape, nbytes, replicated = _leaf_layout(leaf, spec)
        if len(shape) < 2 or nbytes < SHARD_MIN_BYTES \
                or replicated is not True:
            continue
        where = jax.tree_util.keystr(path)
        splittable = sorted({a for a in split_axes
                             for d in shape if d % declared[a] == 0})
        if legacy:
            tp = max(sizes)
            report.add(Finding(
                rule=rule_id, family="serving", severity="error",
                message=f"{where}: {nbytes / 2**20:.1f} MiB weight "
                        f"{shape} is fully replicated under tp={tp} — "
                        "each chip runs this matmul whole",
                where=where,
                hint="shard dims the Megatron pairing can split "
                     "(d_model / heads divisible by K), or drop "
                     "--strategy tp for this model",
                detail={"bytes": nbytes, "shape": list(shape),
                        "tp": int(tp)}))
            continue
        mesh_str = ", ".join(f"{a}:{declared[a]}" for a in split_axes)
        if splittable:
            msg = (f"{where}: {nbytes / 2**20:.1f} MiB operand {shape} "
                   f"fully replicated though mesh axis(es) "
                   f"{splittable} could split a dim — every shard "
                   "computes it whole")
            hint = ("shard it over the model axis (megatron_specs "
                    "pairing / with_sharding_constraint), or shrink "
                    "the mesh")
        else:
            msg = (f"{where}: {nbytes / 2**20:.1f} MiB operand {shape} "
                   f"fully replicated and NO dim divides the declared "
                   f"axis(es) {{{mesh_str}}} — this degree does not "
                   "fit the model geometry")
            hint = ("pick a degree that divides d_model/heads, or "
                    "accept replication explicitly with --strategy dp")
        report.add(_shard_finding(
            rule_id, msg, where=where, hint=hint,
            detail={"bytes": nbytes, "shape": list(shape),
                    "mesh": {a: declared[a] for a in split_axes},
                    "splittable_axes": splittable}))
    return report


# ============================================ group 5: KV pool misfit
def run_kv_sharding_rules(kv_tree, tp_k: int, *, axis: str = "model",
                          page_tokens: Optional[int] = None,
                          report: Optional[Report] = None) -> Report:
    """KV-pool/cache sharding misfit under tp (ISSUE 19 group 5).
    ``kv_tree`` is the pools (paged; QuantPool nodes flatten to their
    q/s planes) or the dense cache — abstract ShapeDtypeStructs or
    placed arrays. Fires when a ≥ 1 MiB 4-D leaf's kv_heads dim
    (axis 1 of ``(slots|pages, kv_heads, tokens, head_dim)``) is not
    divisible by ``tp_k`` — the ``P(None,'model',None,None)`` head
    split the engines pin falls back to full replication — or when a
    placed leaf that COULD split was committed replicated anyway."""
    report = report if report is not None else Report()
    k = int(tp_k)
    if k <= 1:
        return report
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(kv_tree)
    for path, leaf in flat:
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = getattr(leaf, "dtype", None)
        if len(shape) != 4 or dtype is None:
            continue
        nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
        if nbytes < SHARD_MIN_BYTES:
            continue
        where = jax.tree_util.keystr(path)
        kv_heads = int(shape[1])
        if kv_heads % k:
            report.add(_shard_finding(
                "kv-shard-misfit",
                f"{where}: KV leaf {shape} has kv_heads={kv_heads} not "
                f"divisible by tp={k} — the P(None,{axis!r},None,None) "
                f"head split falls back to replicating "
                f"{nbytes / 2**20:.1f} MiB of pages on every chip",
                where=where,
                hint="pick tp dividing kv_heads (GQA: raise kv_heads "
                     "or lower K), or serve this model dp-only",
                detail={"bytes": nbytes, "shape": list(shape),
                        "kv_heads": kv_heads, "tp": k,
                        "page_tokens": page_tokens}))
            continue
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None \
                and hasattr(sharding, "is_fully_replicated") \
                and sharding.is_fully_replicated:
            report.add(_shard_finding(
                "kv-shard-misfit",
                f"{where}: KV leaf {shape} could split kv_heads="
                f"{kv_heads} over tp={k} but was committed fully "
                f"replicated ({nbytes / 2**20:.1f} MiB per chip)",
                where=where,
                hint="commit the pools through ServingSharding."
                     "kv_shardings / PagedKvCache(sharding=...)",
                detail={"bytes": nbytes, "shape": list(shape),
                        "kv_heads": kv_heads, "tp": k}))
    return report


# ================================================= composed entry point
def run_sharding_rules(closed, *, mesh_axes: Optional[Dict[str, int]] = None,
                       strategy: Optional[str] = None, grad_comm=None,
                       param_specs=None, params=None,
                       context: str = "train",
                       report: Optional[Report] = None) -> Report:
    """All annotation-level shardlint rules over one traced sharded
    step (groups 1, 3, 4 — plus group 2 when ``params``/``param_specs``
    are given). ``mesh_axes`` is the declared mesh (axis -> size;
    defaults to every mesh observed in the annotations), ``strategy``
    the declared ``--strategy`` name, ``grad_comm`` the
    :class:`~bigdl_tpu.parallel.grad_comm.GradCommConfig` in effect,
    ``context`` ``"train"`` or ``"serving"`` (serving plans no explicit
    collectives)."""
    report = report if report is not None else Report()
    levels = sharded_levels(closed)
    declared = ({str(k): int(v) for k, v in mesh_axes.items()}
                if mesh_axes else observed_mesh_axes(levels))
    constraints = collect_constraints(levels)
    collectives = collect_collectives(levels)

    _rule_axis_membership(constraints, collectives, declared, report)
    _rule_extra_collectives(collectives, declared, strategy, context,
                            report)
    _rule_signature(levels, constraints, collectives, declared, strategy,
                    grad_comm, param_specs, report)
    _rule_wire_dtype(constraints, grad_comm, report)
    _rule_quant_remat(levels, report)
    _rule_reshard_churn(constraints, declared, report)
    if params is not None and declared:
        run_replicated_operand_rules(params, declared, specs=param_specs,
                                     report=report)
    return report

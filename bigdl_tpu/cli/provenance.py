"""Shared program-configuration provenance (ISSUE 18 satellite; first
bite of ROADMAP item 5).

Several surfaces used to hand-assemble the same "which program config
produced this number" fields — the perf JSON line
(``cli/perf.py`` annotators), the ``/metrics`` ``_info`` gauge
(``serving/engine.provenance`` + ``cli/serve``), the capture records of
``scripts/serving_bench.py``, ``bigdl-tpu batch-predict``. This module
is the single assembly point:

* :func:`provenance_dict` builds the shared core — BN fusion mode,
  autotune decisions, conv layout policy, per-geometry conv decisions —
  in either of the two shapes the callers historically used:
  ``flat=False`` keeps structured dicts and omits defaults (the perf
  JSON idiom: absent key == default config), ``flat=True`` renders
  scrape-safe scalars and always emits every key (the ``/metrics``
  ``_info`` idiom: a stable label set).
* :data:`PROVENANCE_COMPANION_KEYS` is the canonical key list record
  assemblies copy from a result dict (``scripts/serving_bench.py``'s
  capture records) — one list to extend when a new provenance column
  lands.

Every field is read from the live process state at call time, exactly
as the hand-rolled copies did, so routing through here changes no
output — it only removes the copies that could drift.
"""

from __future__ import annotations

from typing import Optional

__all__ = ["provenance_dict", "PROVENANCE_COMPANION_KEYS"]

# provenance columns a record assembly copies verbatim from a result
# dict (serving_bench capture records): the config core plus the
# feed-attribution columns that make perf rows self-describing
PROVENANCE_COMPANION_KEYS = ("conv_layouts", "conv_geom", "autotune",
                             "bn_fused", "pipeline", "stall_frac",
                             "data_wait_s")


def provenance_dict(model=None, flat: bool = False) -> dict:
    """The shared provenance core, assembled from live process state.

    ``model`` supplies the BN-fusion verdict (``bn_fused`` is omitted
    when None and ``flat=False``; reported as ``"none"`` when None and
    ``flat=True`` so the scrape label set stays stable).

    ``flat=False`` (perf-JSON shape): structured values, defaults
    omitted —

    * ``conv_layouts``: the non-default layout triple dict, absent when
      default;
    * ``conv_geom``: installed per-geometry decisions dict, absent when
      none;
    * ``autotune``: the tuning annotation (mode + per-key decisions),
      absent when tuning is off with no ledger;
    * ``bn_fused``: ``off``/``stats``/``apply``.

    ``flat=True`` (``_info``-gauge shape): every key present, scalar
    values —

    * ``conv_layouts``: ``"k=v/..."`` joined string or ``"default"``;
    * ``conv_geom_decisions``: decision count (0 when none);
    * ``autotune``: the tuning MODE string;
    * ``bn_fused``: as above.
    """
    from bigdl_tpu import tuning
    from bigdl_tpu.nn.norm import bn_fused_mode
    from bigdl_tpu.ops.conv2d import (conv_layouts_if_nondefault,
                                      geom_policy_if_any)

    out: dict = {}
    cl = conv_layouts_if_nondefault()
    gp = geom_policy_if_any()
    if flat:
        out["bn_fused"] = (bn_fused_mode(model) if model is not None
                           else "none")
        out["autotune"] = tuning.get_mode()
        out["conv_layouts"] = ("/".join(f"{k}={v}" for k, v in
                                        sorted(cl.items()))
                               if cl else "default")
        out["conv_geom_decisions"] = len(gp) if gp else 0
        return out
    if model is not None:
        out["bn_fused"] = bn_fused_mode(model)
    ann = tuning.annotation()
    if ann is not None:
        out["autotune"] = ann
    if cl:
        out["conv_layouts"] = cl
    if gp:
        out["conv_geom"] = gp
    return out

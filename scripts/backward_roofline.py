"""Per-op backward roofline: achieved-vs-ceiling TF/s for the conv
passes, joined against an xplane profile's top fusions (PERF.md §11).

Two inputs:

* ``--probe FILE`` — conv_bwd_probe.py JSONL (geometry fields + per-pass
  isolated ms/TF/s under each layout). Required. From it alone the
  script emits the **isolated roofline**: for every (geometry, pass),
  the default-NHWC time, the best layout's time, and the ceiling ratio —
  i.e. how much of each pass's attainable rate the shipped default
  reaches, and what the per-geometry policy should buy.
* ``--profile DIR`` — a ``jax.profiler.trace`` directory (e.g. from
  ``perf ... --profile DIR``). Optional. The script parses the xplane
  protobuf with ``bigdl_tpu.utils.xplane`` (no tensorboard dep), takes
  the top ``--top`` device ops by total time, scales to per-step ms via
  ``--steps``, and joins each against the same-shape isolated
  microbenches by duration proximity: a fusion whose per-step time is
  within ``--tol`` of an isolated pass time gets that label and an
  achieved-vs-ceiling percentage. Unmatched fusions are listed honestly
  — the point of the table is to either land ≥40% b128 MFU or bound the
  model on this chip, not to flatter it.

Usage:
    python scripts/conv_bwd_probe.py 30 | tee /tmp/probe.jsonl
    python -m bigdl_tpu.cli.perf -m resnet50 -b 128 -i 5 --profile /tmp/xp
    python scripts/backward_roofline.py --probe /tmp/probe.jsonl \
        --profile /tmp/xp --steps 5 --out ROOFLINE_r08.md
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from bigdl_tpu.ops.conv2d import _PASSES, _row_geom  # noqa: E402

_BWD = ("dgrad", "wgrad")


def load_probe(path: str):
    """Probe JSONL -> {(geom, pass): {layout: {"ms", "tfs"}}} plus a
    display name per geometry."""
    cells, names = {}, {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                row = json.loads(line)
            except json.JSONDecodeError:
                continue
            g = _row_geom(row)
            lay = row.get("layout")
            if g is None or lay is None:
                continue
            names.setdefault(g, row.get("shape", "conv"))
            gf = float(row.get("gflops") or 0.0)
            for p in _PASSES:
                ms = row.get(f"{p}_ms")
                if ms is None:
                    continue
                ms = float(ms)
                tfs = row.get(f"{p}_tfs")
                tfs = (float(tfs) if tfs is not None
                       else (gf / ms if ms else 0.0))
                cells.setdefault((g, p), {})[lay] = {"ms": ms, "tfs": tfs,
                                                     "gflops": gf}
    if not cells:
        raise SystemExit(f"no usable probe rows in {path}")
    return cells, names


def isolated_table(cells, names):
    """Rows: per (geometry, backward pass) — NHWC vs best layout vs
    ceiling fraction. The 'ceiling' of a pass is its best measured
    layout; achieved-under-default is the NHWC cell."""
    rows = []
    for (g, p), per in sorted(cells.items(),
                              key=lambda kv: (names[kv[0][0]], kv[0][1])):
        if p not in _BWD:
            continue
        best_lay = min(per, key=lambda l: per[l]["ms"])
        best = per[best_lay]
        nhwc = per.get("NHWC", best)
        rows.append({
            "shape": names[g], "pass": p,
            "nhwc_ms": round(nhwc["ms"], 3),
            "nhwc_tfs": round(nhwc["tfs"], 1),
            "best_layout": best_lay,
            "best_ms": round(best["ms"], 3),
            "best_tfs": round(best["tfs"], 1),
            "pct_of_ceiling_default": round(
                100.0 * best["ms"] / nhwc["ms"], 1) if nhwc["ms"] else None,
        })
    return rows


def join_profile(profile_dir, cells, names, top, steps, tol):
    """Top device fusions by total time, each matched (by per-step
    duration proximity) against the isolated microbench cells."""
    from bigdl_tpu.utils.xplane import (device_planes, find_xplane_pb,
                                        op_totals, parse_xspace)

    pb = find_xplane_pb(profile_dir)
    if pb is None:
        raise SystemExit(f"no *.xplane.pb under {profile_dir}")
    planes = parse_xspace(pb)
    totals = op_totals(device_planes(planes))
    ranked = sorted(totals.items(), key=lambda kv: -kv[1]["total_ps"])
    rows = []
    for name, ent in ranked[:top]:
        ms_step = ent["total_ps"] / 1e9 / max(1, steps)
        row = {"op": name, "ms_per_step": round(ms_step, 3),
               "count": ent["count"], "match": None}
        # nearest isolated cell by relative duration distance
        best_key, best_d = None, tol
        for (g, p), per in cells.items():
            for lay, cell in per.items():
                if not cell["ms"]:
                    continue
                d = abs(ms_step - cell["ms"]) / cell["ms"]
                if d < best_d:
                    best_key, best_d = (g, p, lay), d
        if best_key is not None:
            g, p, lay = best_key
            per = cells[(g, p)]
            cell = per[lay]
            ceil = max(c["tfs"] for c in per.values())
            ach = cell["gflops"] / ms_step / 1e3 if ms_step else 0.0
            row["match"] = {
                "shape": names[g], "pass": p, "layout": lay,
                "isolated_ms": round(cell["ms"], 3),
                "rel_duration_gap": round(best_d, 3),
                "achieved_tfs": round(ach, 1),
                "ceiling_tfs": round(ceil, 1),
                "pct_of_ceiling": round(100.0 * ach / ceil, 1)
                if ceil else None,
            }
        rows.append(row)
    return pb, rows


def load_perf_mem(path):
    """Last perf JSON line of ``path`` that carries the ISSUE 12 memory
    columns -> (hbm_peak_bytes, hbm_headroom_frac, mem-dict) or None."""
    found = None
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                row = json.loads(line)
            except json.JSONDecodeError:
                continue
            if "hbm_peak_bytes" in row or isinstance(row.get("mem"), dict):
                found = row
    if found is None:
        return None
    return {"hbm_peak_bytes": found.get("hbm_peak_bytes"),
            "hbm_headroom_frac": found.get("hbm_headroom_frac"),
            "mem": found.get("mem"),
            "model": found.get("model"),
            "batch": found.get("batch")}


def markdown(iso_rows, prof_rows, pb, mem=None):
    out = ["### Isolated backward roofline (probe microbenches)", "",
           "| shape | pass | NHWC ms | NHWC TF/s | best | best ms | "
           "best TF/s | best/NHWC time |",
           "|---|---|---|---|---|---|---|---|"]
    for r in iso_rows:
        out.append(
            f"| {r['shape']} | {r['pass']} | {r['nhwc_ms']} | "
            f"{r['nhwc_tfs']} | {r['best_layout']} | {r['best_ms']} | "
            f"{r['best_tfs']} | {r['pct_of_ceiling_default']}% |")
    if prof_rows is not None:
        out += ["", f"### Profile join (top fusions, {pb})", "",
                "| op | ms/step | matched bench | "
                "achieved TF/s | ceiling TF/s | % of ceiling |",
                "|---|---|---|---|---|---|"]
        for r in prof_rows:
            m = r["match"]
            if m:
                out.append(
                    f"| {r['op']} | {r['ms_per_step']} | "
                    f"{m['shape']}/{m['pass']}/{m['layout']} "
                    f"(±{m['rel_duration_gap']}) | {m['achieved_tfs']} | "
                    f"{m['ceiling_tfs']} | {m['pct_of_ceiling']}% |")
            else:
                out.append(f"| {r['op']} | {r['ms_per_step']} | "
                           "unmatched | — | — | — |")
    if mem is not None:
        pk, hr = mem.get("hbm_peak_bytes"), mem.get("hbm_headroom_frac")
        out += ["", "### HBM attribution (ISSUE 12, from --perfJson)", "",
                f"run: {mem.get('model')} b={mem.get('batch')} — "
                f"hbm peak "
                f"{round(pk / 2**30, 2) if pk is not None else '-'} GiB, "
                f"headroom "
                f"{round(hr * 100, 1) if hr is not None else '-'}%", "",
                "| category | MiB | frac % |", "|---|---|---|"]
        m = mem.get("mem") or {}
        total = max(1, m.get("total_bytes") or 1)
        for cat, b in (m.get("categories") or {}).items():
            out.append(f"| {cat} | {b / 2**20:.1f} "
                       f"| {100.0 * b / total:.1f} |")
    return "\n".join(out) + "\n"


def main(argv=None):
    ap = argparse.ArgumentParser("backward roofline join")
    ap.add_argument("--probe", required=True,
                    help="conv_bwd_probe.py JSONL")
    ap.add_argument("--profile", default=None,
                    help="jax.profiler.trace dir (optional)")
    ap.add_argument("--top", type=int, default=10)
    ap.add_argument("--steps", type=int, default=1,
                    help="training steps covered by the trace (per-step "
                         "scaling)")
    ap.add_argument("--tol", type=float, default=0.35,
                    help="max relative duration gap for a bench match")
    ap.add_argument("--perfJson", default=None,
                    help="perf JSON log of the same run (an --obs line "
                         "with the ISSUE 12 memory columns) — adds the "
                         "HBM peak/headroom + category section")
    ap.add_argument("--out", default=None,
                    help="write the markdown table here (stdout default)")
    ap.add_argument("--json", default=None,
                    help="also dump the raw rows as JSON here")
    args = ap.parse_args(argv)

    cells, names = load_probe(args.probe)
    iso = isolated_table(cells, names)
    pb, prof = None, None
    if args.profile:
        pb, prof = join_profile(args.profile, cells, names,
                                args.top, args.steps, args.tol)
    mem = load_perf_mem(args.perfJson) if args.perfJson else None
    md = markdown(iso, prof, pb, mem)
    if args.out:
        with open(args.out, "w") as f:
            f.write(md)
        print(f"wrote {args.out}", file=sys.stderr)
    else:
        sys.stdout.write(md)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"isolated": iso, "profile": prof, "mem": mem,
                       "xplane": pb}, f, indent=1, sort_keys=True)
            f.write("\n")


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Looks the cell up in ``BENCHMARK.json``, loads ``configs/<config>.json``
and ``traffic/<traffic>.json``, runs the cell in this one process (which
holds the chip), and prints the result as the last line of stdout. With
``--trace 1`` the metrics are the cell's per-layer metrics, each read by
``metrics/<base>.py`` (``<base>.<suffix>`` in the manifest: the suffix
names the group of cells, the base names the file). Without a TPU it exits
non-zero and prints nothing on stdout. ``--rehearse-cpu`` runs the same
control flow at the files' toy ``rehearsal`` sizes on the CPU: it proves
nothing about the chip and reports no device number.
"""

import time

T0 = time.perf_counter()  # process start, as near as Python lets us see it

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the general generators a traffic file's "kind" may name
RUNNERS = {"train_steps": "train", "open_loop": "serve",
           "closed_loop": "serve"}


def load_json(*rel):
    with open(os.path.join(ROOT, *rel)) as f:
        return json.load(f)


def merged(base, over):
    """``over`` laid over ``base``, dict by dict (the rehearsal sizes)."""
    out = dict(base)
    for k, v in over.items():
        out[k] = (merged(out[k], v) if isinstance(v, dict)
                  and isinstance(out.get(k), dict) else v)
    return out


def metrics_of(manifest, section, cell):
    return [m for m in manifest[section]
            if "workloads" not in m or cell in m["workloads"]]


def read_layer_metric(name, run):
    """Import ``metrics/<base>.py`` and call its ``read(run)``."""
    base = name.split(".")[0]
    spec = importlib.util.spec_from_file_location(
        f"benchmark_metric_{base}", os.path.join(HERE, "metrics",
                                                 base + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def info(tag, **kw):
    print(json.dumps({"info": tag, **kw}), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    manifest = load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        print(f"benchmark: no workload {args.workload!r} in BENCHMARK.json "
              f"(have {sorted(cells)})", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    config = load_json(HERE, "configs", cell["config"] + ".json")
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    if args.rehearse_cpu:
        config = merged(config, config.get("rehearsal", {}))
        traffic = merged(traffic, traffic.get("rehearsal", {}))
        # virtual CPU devices for a multi-chip cell's control flow
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}")
    seconds = args.seconds or manifest["run_seconds"]

    sys.path.insert(0, ROOT)
    import jax

    # pinned: with no chip jax raises here, before anything is printed
    jax.config.update("jax_platforms", "cpu" if args.rehearse_cpu else "tpu")
    devs = jax.devices()
    if len(devs) < cell["chips"]:
        print(f"benchmark: {args.workload} needs {cell['chips']} chips, jax "
              f"sees {len(devs)}", file=sys.stderr)
        return 3
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}

    from bigdl_tpu import tuning
    from bigdl_tpu.cli import common

    from benchmark.lib import peaks

    common.enable_compile_cache()  # <checkout>/.jax_cache, or the env's
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    tuning.set_mode("off")  # nothing the run loads comes from ~/.cache
    peak = None if args.rehearse_cpu else peaks.peaks_for(device["kind"])
    parts = {"imports_s": time.perf_counter() - T0}
    opened = {}

    def window_open(now):
        opened["setup_s"] = now - T0

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")  # under $TMPDIR
    ctx = {"config": config, "traffic": traffic, "seed": args.seed,
           "seconds": seconds, "trace": bool(args.trace),
           "trace_dir": trace_dir, "chips": cell["chips"], "device": device,
           "parts": parts, "window_open": window_open, "info": info}
    try:
        runner = importlib.import_module(
            "benchmark.lib." + RUNNERS[traffic["kind"]])
        out = runner.run(ctx)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)

    stats = [d.memory_stats() or {} for d in devs[:cell["chips"]]]
    peak_bytes = max((s.get("peak_bytes_in_use") or 0) for s in stats)
    device["memory_peak_bytes"] = peak_bytes or None
    out["e2e"]["setup_s"] = opened["setup_s"]
    info("setup_parts", **{k: round(v, 3) for k, v in parts.items()},
         setup_s=round(opened["setup_s"], 3))
    info("checks", **out["checks"])

    run = dict(out["run"], config=config, traffic=traffic, cell=cell,
               device=device, peaks=peak, e2e=out["e2e"], reduced=None)
    metrics = {}
    if args.trace:
        from benchmark.lib import trace
        if run.get("planes"):
            run["reduced"] = trace.reduce(run["planes"])
        if run["reduced"] is not None and not args.rehearse_cpu:
            device["busy_s"] = run["reduced"]["busy_s"]
            device["window_s"] = run["reduced"]["window_s"]
        for m in metrics_of(manifest, "per_layer", cell["name"]):
            value = read_layer_metric(m["name"], run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in metrics_of(manifest, "end_to_end", cell["name"]):
            metrics[m["name"]] = {"value": out["e2e"][m["name"]],
                                  "unit": m["unit"]}
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    if args.rehearse_cpu:
        line["rehearsal"] = "CPU, toy sizes: proves nothing about the chip"
        for m in metrics.values():  # no CPU number under a device metric
            m["value"] = None
    if args.trace and run["reduced"] is not None:
        line["breakdown"] = trace.breakdown(run["reduced"])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""`bigdl-tpu explain` — a model's HBM plan (ISSUE 12).

    bigdl-tpu explain resnet50 -b 32
    bigdl-tpu explain transformer_lm --quantize int8+kv8   # slot forecast

No run: the training step of a perf-zoo model is lowered+compiled at two
batch sizes, the per-category byte plan of the exact step is rendered
(totalling to ``compiled.memory_analysis()``), and the linear per-sample
fit predicts the max batch that still fits the device HBM. ``--json``
prints the same as one line.

The command explains memory and nothing else. A capture directory
(``--traceSteps``, SIGUSR2, ``perf --profile``) is a ``jax.profiler``
directory: open it in XProf or Perfetto. The one reducer of a device
trace whose numbers the ledger holds is ``benchmark/lib/trace.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv=None):
    p = argparse.ArgumentParser(
        "bigdl-tpu explain",
        description="per-category HBM plan of a model's compiled "
                    "training step, headroom against the device "
                    "capacity, and the predicted max batch from a "
                    "two-point per-sample fit — no training run")
    p.add_argument("target", help="a perf model name")
    p.add_argument("--json", action="store_true",
                   help="machine output (one JSON line, printed last)")
    p.add_argument("-b", "--batchSize", type=int, default=16,
                   help="batch the plan is made for (and twice it, for "
                        "the fit)")
    p.add_argument("--seq", type=int, default=None,
                   help="transformer_lm* sequence override")
    p.add_argument("--quantize", default=None,
                   choices=("off", "int8", "fp8", "kv8", "int8+kv8",
                            "fp8+kv8"),
                   help="on a transformer_lm target: account the "
                        "serving KV cache and weights under this "
                        "quantize mode (ISSUE 17) and re-fit the "
                        "max-slot forecast — kv8 roughly quarters the "
                        "per-slot bytes, so ~2x the slots fit")
    from bigdl_tpu.cli.common import _add_platform_arg, apply_platform
    _add_platform_arg(p)
    args = p.parse_args(argv)
    if os.path.isdir(args.target):
        print(f"bigdl-tpu explain: {args.target} is a directory; explain "
              "takes a perf model name and prints its HBM plan. A capture "
              "directory is a jax.profiler directory: open it in XProf or "
              "Perfetto (the reducer the ledger uses is "
              "benchmark/lib/trace.py)", file=sys.stderr)
        return 2
    apply_platform(args)

    from bigdl_tpu.obs import memory
    b = args.batchSize
    plan = memory.plan_for_model(args.target, b, seq_len=args.seq)
    plan2 = memory.plan_for_model(args.target, 2 * b, seq_len=args.seq)
    fc = memory.forecast(plan, plan2)
    kvp = fcs = None
    if args.target.startswith("transformer_lm"):
        # serving-side companion (ISSUE 17): per-slot KV bytes and
        # the max-slot fit, dtype-aware under --quantize
        kvp = memory.serving_kv_plan(args.target, seq_len=args.seq,
                                     quantize=args.quantize)
        fcs = memory.forecast_slots(kvp)
    if args.json:
        out = memory.compact(plan)
        out["model"] = args.target
        out["forecast"] = fc
        out["plan_2x"] = memory.compact(plan2)
        if kvp is not None:
            out["serving_kv"] = kvp
            out["forecast_slots"] = fcs
        print(json.dumps(out))
    else:
        print(f"memory plan: {args.target} b={b} "
              f"({plan.get('device')})")
        print(memory.render(plan, fc))
        if kvp is not None:
            print(f"\nserving (quantize={kvp['quantize']}): "
                  f"kv/slot {kvp['kv_bytes_per_slot']} B "
                  f"(L={kvp['max_len']}, "
                  f"dtype={kvp['cache_dtype']}"
                  + (f", page={kvp['page_tokens']}"
                     if kvp['page_tokens'] else "")
                  + f"), weights {kvp['params_bytes']} B"
                  f" -> predicted max slots "
                  f"{fcs['predicted_max_slots']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

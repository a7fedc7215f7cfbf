"""Observability smoke (ISSUE 7 CI): one CPU perf run with the full
--obs surface, asserted end to end.

What it proves (the tier1.yml ``obs-smoke`` job):

1. an obs-ON lenet5 perf run stamps the phase columns
   (``data_wait_s``/``h2d_s``/``dispatch_s``/``device_s``/``ckpt_s``/
   ``stall_frac``) into its perf JSON, and their sum is sane against
   the measured wall time;
2. the Chrome-trace span timeline json-loads and contains the step
   phases;
3. a LIVE ``/metrics`` scrape from the training listener (taken while
   the run is still stepping when the box is fast enough, from the
   still-running listener right after otherwise) carries the step-phase
   histograms in serving's exposition format;
4. an obs-OFF run of the same config emits exactly the null phase
   columns and leaves the span API as compiled no-ops.

Usage:  python scripts/obs_smoke.py [--model lenet5 -b 16 -i 40]
Exit 0 = all assertions held.
"""

import argparse
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _fail(msg):
    print(f"obs_smoke: FAIL: {msg}", flush=True)
    sys.exit(1)


def main():
    ap = argparse.ArgumentParser("obs_smoke")
    ap.add_argument("--model", default="lenet5")
    ap.add_argument("-b", "--batch", type=int, default=16)
    ap.add_argument("-i", "--iters", type=int, default=40)
    args = ap.parse_args()

    from bigdl_tpu import obs
    from bigdl_tpu.cli import common, perf

    td = tempfile.mkdtemp(prefix="obs_smoke_")
    obs.enable()
    srv = obs.start_metrics_server(obs.get_registry(), port=0)
    if srv is None:
        _fail("metrics listener failed to bind")
    capture = obs.CaptureController(td, install_signal=False)
    st = common.ObsState(True, td, capture, srv)

    result = {}

    def _run():
        result["out"] = perf.run(args.model, args.batch, args.iters,
                                 "constant", use_bf16=False, obs_state=st)

    t = threading.Thread(target=_run, daemon=True)
    t.start()

    # (3) live scrape: poll while the run steps; the histograms appear
    # in the registry at the first timed iteration. If the run outraces
    # the poll (tiny model, fast box) the listener is still up — the
    # final scrape below is equally live.
    page, live = "", False
    deadline = time.time() + 300
    while t.is_alive() and time.time() < deadline:
        try:
            with urllib.request.urlopen(srv.url, timeout=5) as r:
                page = r.read().decode()
            if "train_phase_dispatch_ms_bucket" in page:
                live = True
                break
        except Exception:
            pass
        time.sleep(0.2)
    t.join(300)
    if t.is_alive():
        _fail("perf run did not finish in time")
    if "out" not in result:
        _fail("perf run raised (see traceback above)")
    if not live:
        with urllib.request.urlopen(srv.url, timeout=10) as r:
            page = r.read().decode()
    if "train_phase_dispatch_ms_bucket" not in page:
        _fail("/metrics scrape has no step-phase histograms")
    if "train_phase_device_ms_count" not in page:
        _fail("/metrics scrape has no device-phase histogram")
    print(f"obs_smoke: /metrics scrape ok (live={live}, "
          f"{len(page.splitlines())} lines)", flush=True)

    # (1) phase columns present and coherent
    out = result["out"]
    cols = ("data_wait_s", "h2d_s", "dispatch_s", "device_s", "ckpt_s",
            "stall_frac")
    for c in cols:
        if out.get(c) is None:
            _fail(f"obs-on perf JSON missing phase column {c}")
    phase_sum = (out["data_wait_s"] + out["h2d_s"] + out["dispatch_s"]
                 + out["device_s"] + out["ckpt_s"])
    ratio = phase_sum / max(out["seconds"], 1e-9)
    if not 0.5 <= ratio <= 1.05:  # CI boxes are noisy; tests pin 10%
        _fail(f"phase sum {phase_sum:.4f}s vs wall {out['seconds']}s "
              f"(ratio {ratio:.3f}) is incoherent")
    print(f"obs_smoke: phase columns ok (sum/wall = {ratio:.3f})",
          flush=True)

    # (2) the span timeline json-loads and carries the step phases
    trace_path = out.get("obs", {}).get("trace_json")
    if not trace_path:
        _fail("no trace_json in the obs annotation")
    with open(trace_path) as f:
        doc = json.load(f)
    names = {e["name"] for e in doc["traceEvents"]}
    if not {"dispatch", "device"} <= names:
        _fail(f"trace is missing step-phase spans (has {sorted(names)})")
    print(f"obs_smoke: chrome trace ok ({len(doc['traceEvents'])} "
          f"events)", flush=True)

    # (4) obs-off leg: null columns, no tracer, no obs annotation
    srv.close()
    obs.disable()
    off = perf.run(args.model, args.batch, max(4, args.iters // 10),
                   "constant", use_bf16=False)
    for c in cols:
        if c not in off or off[c] is not None:
            _fail(f"obs-off perf JSON column {c} should be null, got "
                  f"{off.get(c)!r}")
    if "obs" in off:
        _fail("obs-off perf JSON must not carry an obs annotation")
    if obs.enabled():
        _fail("the obs-off leg ran with a tracer installed")
    print("obs_smoke: obs-off null columns ok", flush=True)
    print("obs_smoke: PASS", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the
chip: the LM train-and-serve path, end to end, through the entry points a
user calls, at the full width of ``transformer_lm_1k_hd128`` (d_model
1024, 12 layers, 8 heads of 128, vocab 32,000, 1,024 tokens).

    python chip_smoke.py                 # the real thing: needs a TPU
    python chip_smoke.py --rehearse-cpu  # toy dims on the CPU; PROVES NOTHING

Two legs, one child process at a time (a chip belongs to one process; this
parent never imports jax or bigdl_tpu, so it never holds the chip):

* train — ``Optimizer.optimize()`` takes a few steps on a repeated batch
  of 16 x 1,024 seeded synthetic tokens in bf16, then ``perf.run`` prints
  its JSON line for the same configuration. With more than one device
  visible both run again under data parallelism across all of them.
* serve — ``python -m bigdl_tpu.cli.main serve transformer_lm_1k_hd128
  --randomInit --bf16 --port 0`` answers a few /generate requests (two
  prefill buckets, two concurrent, one streamed) and one /predict, then
  exits on SIGTERM. With more than one device: ``--strategy dp``.

Both children pin the TPU platform, so with no chip jax itself fails in
seconds and nothing is printed on stdout. Each leg prints one JSON object;
the last stdout line is ``{"ok": true, "device": {...}}`` only when every
check of every leg passed. Everything (plus the server log) is also
written under ``chiprun_out/chip_smoke/``.
"""

import json
import math
import os
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
REHEARSE = "--rehearse-cpu"
MODEL = "transformer_lm_1k_hd128"
VOCAB = 32000
BATCH = 16
STEPS = 6           # Optimizer steps on the repeated batch
PERF_ITERS = 5
# the driver allows 1200 s, compilation included; leave room to shut down
BUDGET_S = 1150
FLASH_KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")

_children = []      # every process this script started and has not reaped


# ===================================================================== train
def leg_train(rehearse: bool) -> int:
    """The train leg, run in a child process (``--leg train``)."""
    t0 = time.time()
    import jax

    # pinned: with no chip jax raises here, within seconds
    jax.config.update("jax_platforms", "cpu" if rehearse else "tpu")
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print("SMOKE_DEVICE " + json.dumps(device), flush=True)

    import gc

    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu import nn, tuning
    from bigdl_tpu.cli import common, perf
    from bigdl_tpu.dataset import BatchDataSet
    from bigdl_tpu.optim import SGD, Optimizer, Trigger

    common.setup_logging()
    common.enable_compile_cache()
    tuning.set_mode("off")  # nothing the run loads comes from ~/.cache

    name, seq, batch = MODEL, None, BATCH
    if rehearse:
        name, seq, batch = "transformer_lm", 32, 4
    checks, rep = {}, {
        "leg": "train", "rehearsal": rehearse, "device": device,
        "jax": jax.__version__, "jaxlib": _dist_version("jaxlib"),
        "libtpu": _dist_version("libtpu"), "model": name, "batch": batch}

    def build():
        model, in_shape = perf.build_model(name, seq_len=seq)
        model.compute_dtype = jnp.bfloat16
        return model, in_shape

    model, in_shape = build()
    rep["seq_len"] = int(in_shape[0])
    rs = np.random.RandomState(0)
    x = rs.randint(0, VOCAB, (batch, *in_shape)).astype(np.int32)
    y = rs.randint(0, VOCAB, (batch, *in_shape)).astype(np.int32)
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion())

    def optimize(strategy, tag):
        """A few Optimizer steps on the one repeated batch; the per-step
        losses come back through the Optimizer's own summary file."""
        sdir = os.path.join(OUT_DIR, f"summary_{tag}")
        if os.path.exists(os.path.join(sdir, "train.jsonl")):
            os.unlink(os.path.join(sdir, "train.jsonl"))
        opt = Optimizer(build()[0], BatchDataSet(x, y, batch), crit,
                        optim_method=SGD(learning_rate=0.01, momentum=0.9),
                        end_when=Trigger.max_iteration(STEPS),
                        strategy=strategy, seed=7)
        opt.set_summary(sdir)
        t = time.time()
        trained = opt.optimize()
        jax.block_until_ready(trained.params)
        secs = time.time() - t
        with open(os.path.join(sdir, "train.jsonl")) as f:
            losses = [json.loads(line)["loss"] for line in f]
        return opt, trained, losses, secs

    # ---- one device: Optimizer
    opt, trained, losses, secs = optimize(None, "one")
    rep.update(steps=len(losses), losses=[round(v, 4) for v in losses],
               optimize_s=round(secs, 1))
    checks["steps_done"] = len(losses) == STEPS
    checks["loss_finite_every_step"] = all(math.isfinite(v) for v in losses)
    checks["loss_fell_on_repeated_batch"] = (len(losses) > 1
                                             and losses[-1] < losses[0])
    # the Optimizer's own compiled step (same builder, persistent-cache
    # hit) must carry the three Mosaic flash kernels
    step, _ = opt._build_step()
    ab = lambda t: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
    params_abs = ab(trained.params)
    compiled = step.lower(
        params_abs, opt.model.init_state(),
        jax.eval_shape(opt.optim_method.init, params_abs),
        jax.ShapeDtypeStruct(x.shape, x.dtype),
        jax.ShapeDtypeStruct(y.shape, y.dtype),
        jax.random.PRNGKey(0)).compile()
    rep["optimizer_step_mosaic_kernels"] = perf.mosaic_kernels(compiled)
    # agreement with the repo's reference on a small input: the same
    # weights through the dense XLA attention path
    from bigdl_tpu.nn.attention import dot_product_attention
    dense, _ = perf.build_model(name, seq_len=seq,
                                lm_attn_impl=dot_product_attention)
    dense.compute_dtype = jnp.bfloat16
    fwd = lambda m: np.asarray(jax.jit(
        lambda p, t: m.apply(p, m.init_state(), t, training=False)[0]
    )(trained.params, jnp.asarray(x[:2])), np.float32)
    got, want = fwd(model), fwd(dense)
    # tied N(0,1) embeddings make the log-probs O(100s), and both paths
    # round to bf16: compare relative to the reference's own scale
    rep["logprob_shape"] = list(got.shape)
    rep["flash_vs_dense_rel_err"] = round(float(
        np.abs(got - want).max() / np.abs(want).max()), 5)
    checks["logprobs_finite_expected_shape"] = (
        got.shape == (2, int(in_shape[0]), VOCAB)
        and bool(np.isfinite(got).all()))
    checks["agrees_with_dense_reference"] = (
        rep["flash_vs_dense_rel_err"] < 0.05)
    del opt, trained, compiled, step, got, want
    gc.collect()

    # ---- one device: the perf harness (prints its own JSON line)
    out = perf.run(name, batch, PERF_ITERS, "random", use_bf16=True,
                   autotune="off", seq_len=seq)
    rep["perf"] = {k: out.get(k) for k in (
        "dtype", "device", "peak_flops_device_match", "final_loss",
        "mosaic_kernels", "strategy", "n_devices")}
    checks["perf_loss_finite"] = math.isfinite(out["final_loss"])
    if not rehearse:
        checks["backend_is_tpu"] = device["platform"] == "tpu"
        checks["device_kind_in_peak_table"] = (
            out["peak_flops_device_match"] != "cpu"
            and out["peak_flops_assumed"] is not None)
        checks["dtype_bfloat16"] = out["dtype"] == "bfloat16"
        checks["flash_kernels_in_optimizer_step"] = set(
            FLASH_KERNELS) <= set(rep["optimizer_step_mosaic_kernels"])
        checks["flash_kernels_in_perf_step"] = set(FLASH_KERNELS) <= set(
            out["mosaic_kernels"])

    # ---- every device: the same two runs under data parallelism
    if len(devs) > 1:
        from bigdl_tpu.parallel import DataParallel, make_mesh

        n = len(devs)
        strat = DataParallel(make_mesh({"data": n}))
        _, trained, dp_losses, secs = optimize(strat, "dp")
        xs, _ = strat.shard_batch(x, y)
        p0 = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        o_sh = strat._opt_sharding_tree(
            jax.eval_shape(SGD(momentum=0.9).init, p0))
        sharded = [s for s in jax.tree_util.tree_leaves(o_sh)
                   if any(a is not None for a in s.spec)]
        mem = {str(d.id): int((d.memory_stats() or {}).get(
            "bytes_in_use", 0)) for d in devs}
        pdev = sorted({d.id for leaf in jax.tree_util.tree_leaves(
            trained.params) for d in leaf.devices()})
        rep["dp"] = {
            "n_devices": n, "losses": [round(v, 4) for v in dp_losses],
            "optimize_s": round(secs, 1),
            "batch_shard_devices": sorted(
                s.device.id for s in xs.addressable_shards),
            "batch_shard_shape": list(xs.addressable_shards[0].data.shape),
            "zero1_sharded_opt_leaves": len(sharded),
            "opt_leaves": len(jax.tree_util.tree_leaves(o_sh)),
            "param_devices": pdev, "bytes_in_use": mem,
            "max_rel_loss_diff_vs_one_device": round(max(
                abs(a - b) / abs(a) for a, b in zip(losses, dp_losses)),
                6)}
        checks["dp_batch_on_distinct_devices"] = (
            len(set(rep["dp"]["batch_shard_devices"])) == n)
        checks["dp_zero1_opt_state_sharded"] = len(sharded) > 0
        checks["dp_params_on_every_device"] = len(pdev) == n
        if not rehearse:  # the CPU backend reports no memory stats
            checks["dp_bytes_in_use_on_every_device"] = all(
                v > 2 ** 20 for v in mem.values())
        checks["dp_loss_matches_one_device"] = (
            len(dp_losses) == len(losses)
            and rep["dp"]["max_rel_loss_diff_vs_one_device"] < 2e-3)
        del trained, xs
        gc.collect()
        out_dp = perf.run(name, batch, PERF_ITERS, "random", use_bf16=True,
                          autotune="off", seq_len=seq, strategy="dp")
        rep["dp"]["perf"] = {k: out_dp.get(k) for k in (
            "strategy", "n_devices", "mesh", "final_loss",
            "mosaic_kernels")}
        checks["dp_perf_loss_matches_one_device"] = (
            abs(out_dp["final_loss"] - out["final_loss"])
            < 2e-3 * abs(out["final_loss"]))
        if not rehearse:
            checks["flash_kernels_in_dp_perf_step"] = set(
                FLASH_KERNELS) <= set(out_dp["mosaic_kernels"])

    rep["seconds"] = round(time.time() - t0, 1)
    rep["checks"] = checks
    rep["ok"] = all(checks.values())
    print("SMOKE_LEG " + json.dumps(rep), flush=True)
    return 0 if rep["ok"] else 1


def _dist_version(dist: str):
    from importlib import metadata
    try:
        return metadata.version(dist)
    except metadata.PackageNotFoundError:
        return None


def run_train_leg(rehearse: bool, deadline: float) -> dict:
    """Parent side of the train leg: spawn the child, relay its output,
    return its report (``{"ok": False, "error": ...}`` when it died)."""
    cmd = [sys.executable, os.path.abspath(__file__), "--leg", "train"]
    if rehearse:
        cmd.append(REHEARSE)
    log_path = os.path.join(OUT_DIR, "train.log")
    rep, device = None, None
    with open(log_path, "w") as log:
        proc = subprocess.Popen(cmd, cwd=REPO, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)
        _children.append(proc)
        killer = threading.Timer(max(1.0, deadline - time.time()),
                                 proc.kill)
        killer.start()
        tail = []
        try:
            for line in proc.stdout:
                log.write(line)
                if line.startswith("SMOKE_LEG "):
                    rep = json.loads(line[len("SMOKE_LEG "):])
                elif line.startswith("SMOKE_DEVICE "):
                    device = json.loads(line[len("SMOKE_DEVICE "):])
                else:
                    tail = (tail + [line.rstrip()])[-12:]
            proc.wait()
        finally:
            killer.cancel()
            _children.remove(proc)
    if rep is None:
        rep = {"leg": "train", "ok": False, "device": device,
               "error": f"train child rc={proc.returncode}",
               "log_tail": tail}
    return rep


# ===================================================================== serve
def _http(url, payload=None, timeout=300.0):
    """(status, body bytes); POSTs JSON when ``payload`` is given."""
    req = urllib.request.Request(url)
    data = None
    if payload is not None:
        data = json.dumps(payload).encode()
        req.add_header("Content-Type", "application/json")
    try:
        with urllib.request.urlopen(req, data, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _metric_values(text: str, name: str) -> dict:
    """``{label-string: value}`` for every series of one metric name."""
    out = {}
    for m in re.finditer(
            rf"^bigdl_serving_{name}(\{{[^}}]*\}})? ([-0-9.e+]+)$", text,
            re.M):
        out[m.group(1) or ""] = float(m.group(2))
    return out


def run_serve_leg(rehearse: bool, n_devices: int, deadline: float) -> dict:
    t0 = time.time()
    if rehearse:
        vocab, seq, new, prompts = 64, 64, 8, (10, 40)
        cmd = ["transformer_lm", "--vocabSize", "64", "--dModel", "32",
               "--numLayers", "2", "--numHeads", "2", "--seq", "64",
               "--platform", "cpu"]
    else:
        vocab, seq, new, prompts = VOCAB, 1024, 16, (20, 300)
        cmd = [MODEL, "--platform", "tpu"]
    cmd = [sys.executable, "-m", "bigdl_tpu.cli.main", "serve", *cmd,
           "--randomInit", "--bf16", "--port", "0", "--autotune", "off"]
    if n_devices > 1:
        cmd += ["--strategy", "dp"]
    rep = {"leg": "serve", "rehearsal": rehearse, "cmd": " ".join(cmd[1:])}
    checks = {}
    log_path = os.path.join(OUT_DIR, "serve.log")
    lines, port = [], []
    ready = threading.Event()
    proc = subprocess.Popen(cmd, cwd=REPO, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    _children.append(proc)

    def reader():
        with open(log_path, "w") as log:
            for line in proc.stdout:
                log.write(line)
                log.flush()
                lines.append(line.rstrip())
                m = re.search(r"serving .+ on http://[^:]+:(\d+)", line)
                if m:
                    port.append(int(m.group(1)))
                    ready.set()
        ready.set()  # EOF: unblock the waiter on a start-up failure

    th = threading.Thread(target=reader, daemon=True)
    th.start()
    try:
        # the port is printed after every bucket is compiled (warm-up)
        if not ready.wait(max(1.0, deadline - time.time())) or not port:
            raise RuntimeError("server never reported its port")
        url = f"http://127.0.0.1:{port[0]}"
        while True:
            try:
                if _http(url + "/healthz", timeout=5)[0] == 200:
                    break
            except OSError:
                pass
            if time.time() > deadline:
                raise RuntimeError("/healthz never answered 200")
            time.sleep(0.2)
        rep["setup_s"] = round(time.time() - t0, 1)

        import random
        rnd = random.Random(0)
        toks = lambda n: [rnd.randrange(1, vocab) for _ in range(n)]
        left = lambda: max(5.0, deadline - time.time())
        results = []

        def generate(n_prompt, stream=False):
            body = {"tokens": toks(n_prompt), "max_new_tokens": new}
            if stream:
                body["stream"] = True
            st, raw = _http(url + "/generate", body, timeout=left())
            out = []
            if st == 200 and stream:  # SSE frames: data: {json}\n\n
                frames = [json.loads(f[len(b"data: "):])
                          for f in raw.split(b"\n\n")
                          if f.startswith(b"data: ")]
                out = [t for f in frames for t in f.get("tokens", [])]
                st = st if frames and frames[-1].get("done") else 599
            elif st == 200:
                out = json.loads(raw)["tokens"]
            results.append({"prompt": n_prompt, "stream": stream,
                            "status": st, "n_tokens": len(out),
                            "in_vocab": all(0 <= t < vocab for t in out)})

        generate(prompts[0])
        # concurrent: two on one chip, two per replica under dp (so the
        # least-loaded router has to reach every replica)
        ths = [threading.Thread(target=generate, args=(prompts[i % 2],))
               for i in range(2 * n_devices)]
        for t in ths:  # overlapping, a beat apart: each prefill takes
            t.start()  # far longer than that, compile included
            time.sleep(0.1)
        for t in ths:
            t.join()
        generate(prompts[0], stream=True)
        rep["generate"] = results
        checks["generate_all_200"] = all(r["status"] == 200
                                         for r in results)
        checks["generate_token_counts"] = all(
            r["n_tokens"] == new and r["in_vocab"] for r in results)

        st, raw = _http(url + "/predict",
                        {"inputs": [toks(seq)]}, timeout=left())
        preds = json.loads(raw).get("predictions") if st == 200 else None
        rep["predict"] = {"status": st, "shape": (
            [len(preds), len(preds[0])] if preds else None)}
        checks["predict_200_expected_shape"] = (
            rep["predict"]["shape"] == [1, seq])

        st, raw = _http(url + "/metrics", timeout=60)
        text = raw.decode()
        prov = json.loads(text.split("\n", 1)[0][len("# provenance "):])
        rep["provenance"] = {k: prov.get(k) for k in (
            "backend", "device_kind", "device_count", "jax",
            "compute_dtype", "prompt_buckets", "prefill_kernels",
            "param_devices", "strategy", "serving_replicas")}
        gen = _metric_values(text, "generated_tokens_total")
        rep["generated_tokens_total"] = gen
        rep["prefills_total"] = _metric_values(text, "prefills_total")
        rep["requests_done"] = len(results) + 1
        checks["metrics_200"] = st == 200
        # under dp every replica has its own labelled series
        per = {k: v for k, v in gen.items() if "replica" in k}
        checks["metrics_token_count"] = (
            sum((per or gen).values()) >= new * len(results))
        if n_devices > 1:
            checks["every_replica_generated"] = (
                len(per) == n_devices and all(v > 0 for v in per.values()))
            groups = prov.get("param_devices", "").split("|")
            checks["replica_params_on_own_device"] = (
                len(groups) == n_devices
                and len({g.split(":")[1] for g in groups}) == n_devices)
        if not rehearse:
            checks["provenance_names_tpu"] = (
                prov.get("backend") == "tpu"
                and "tpu" in str(prov.get("device_kind", "")).lower())
            checks["serving_dtype_bfloat16"] = (
                prov.get("compute_dtype") == "bfloat16")
            # both prompts' buckets attend through the compiled kernel
            pk = str(prov.get("prefill_kernels", ""))
            buckets = [int(b) for b in prov["prompt_buckets"].split(",")]
            used = {min(b for b in buckets if b >= p) for p in prompts}
            on_kernel = {int(b) for part in pk.split(";")
                         if part.startswith("flash_fwd@")
                         for b in part.split("@")[1].split(",")}
            rep["prefill_buckets_used"] = sorted(used)
            checks["two_prefill_buckets"] = len(used) >= 2
            checks["flash_kernel_in_prefill"] = used <= on_kernel
    except Exception as e:  # a failed leg must still stop its server
        rep["error"] = f"{type(e).__name__}: {e}"
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=90)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        th.join(timeout=10)
        _children.remove(proc)
    rep["exit_code"] = proc.returncode
    rep["log_tail"] = lines[-15:]
    checks["clean_exit_on_sigterm"] = (
        proc.returncode == 0 and "serving shutdown clean" in lines)
    checks["no_traceback_in_server_log"] = not any(
        "Traceback" in ln for ln in lines)
    rep["seconds"] = round(time.time() - t0, 1)
    rep["checks"] = checks
    rep["ok"] = "error" not in rep and all(checks.values())
    return rep


# ==================================================================== parent
def _kill_children(signum=None, frame=None):
    for p in list(_children):
        if p.poll() is None:
            p.kill()
    if signum is not None:
        os._exit(128 + signum)


def main(argv) -> int:
    rehearse = REHEARSE in argv
    if "--leg" in argv:
        os.makedirs(OUT_DIR, exist_ok=True)
        return leg_train(rehearse)
    unknown = [a for a in argv if a != REHEARSE]
    if unknown:
        print(f"chip_smoke: unknown argument(s) {unknown}; the only option "
              f"is {REHEARSE}", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(REPO, "bigdl_tpu")):
        print("chip_smoke: no bigdl_tpu/ package next to chip_smoke.py — "
              "run it from a checkout of the repository", file=sys.stderr)
        return 2
    os.makedirs(OUT_DIR, exist_ok=True)
    signal.signal(signal.SIGTERM, _kill_children)
    signal.signal(signal.SIGINT, _kill_children)
    t0 = time.time()
    deadline = t0 + BUDGET_S
    if rehearse:
        print("REHEARSAL on the CPU at toy dimensions: this run proves "
              "nothing about the chip.", file=sys.stderr)
    try:
        train = run_train_leg(rehearse, deadline)
        device = train.get("device")
        if device is None:
            # jax found no accelerator (or the package is broken): name
            # it, print nothing on stdout, do not try the second leg
            print("chip_smoke: the train child never reached a device — "
                  + str(train.get("error")) + "\n  "
                  + "\n  ".join(train.get("log_tail", [])),
                  file=sys.stderr)
            return 1
        print(json.dumps(train), flush=True)
        serve = run_serve_leg(rehearse, device["count"], deadline)
        print(json.dumps(serve), flush=True)
    finally:
        _kill_children()
    result = {"ok": bool(train["ok"] and serve["ok"]), "device": device,
              "seconds": round(time.time() - t0, 1),
              "legs": {"train": train, "serve": serve}}
    with open(os.path.join(OUT_DIR, "result.json"), "w") as f:
        json.dump(result, f, indent=1)
    failed = [f"{leg['leg']}:{k}" for leg in (train, serve)
              for k, v in leg.get("checks", {}).items() if not v]
    failed += [f"{leg['leg']}: {leg['error']}" for leg in (train, serve)
               if "error" in leg]
    if failed:
        print("chip_smoke: FAILED — " + "; ".join(failed), file=sys.stderr)
        return 1
    if rehearse:
        print(json.dumps({"rehearsal_passed": True, "device": device,
                          "note": "CPU toy dims; proves nothing"}))
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

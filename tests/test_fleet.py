"""Serving-fleet tests (ISSUE 20): control-plane schema, router argv
surgery and scoring, cross-process metrics aggregation, the
ResolvedConfig serve spine, worker control surface, rolling-swap
atomicity (in-flight decodes finish on the OLD weights — pinned with a
version-stamped checkpoint pair), and a router e2e against fake stdlib
worker processes (spawn, kill, supervised restart, rid echo on the
router's own 503)."""

import argparse
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from bigdl_tpu.cli import common
from bigdl_tpu.obs.aggregate import aggregate_pages, parse_samples
from bigdl_tpu.serving.fleet import control, swap
from bigdl_tpu.serving.fleet.router import (FleetRouter, NoLiveWorker,
                                            WorkerHandle,
                                            worker_base_argv)
from bigdl_tpu.serving.fleet.worker import WorkerControl


# ------------------------------------------------------- control plane
def test_worker_status_roundtrip():
    st = control.WorkerStatus(index=3, pid=42, port=8001, state="ready",
                              queue_depth=5, decode_active=2,
                              slo_burn=0.25, goodput=0.9,
                              model_version="v7", restarts=1,
                              uptime_s=12.5)
    back = control.WorkerStatus.from_dict(st.to_dict())
    assert back == st


def test_worker_status_from_dict_tolerates_unknown_keys():
    st = control.WorkerStatus.from_dict(
        {"index": 0, "state": "draining", "next_proto_field": "x"})
    assert st.index == 0 and st.state == "draining"


def test_worker_status_from_dict_rejects_bad_schema():
    with pytest.raises(ValueError):
        control.WorkerStatus.from_dict({"state": "ready"})  # no index
    with pytest.raises(ValueError):
        control.WorkerStatus.from_dict({"index": 0, "state": "zombie"})


# ------------------------------------------------------- argv surgery
def test_worker_base_argv_strips_router_owned_flags():
    argv = ["transformer_lm", "--model", "ck", "--fleet", "2",
            "--port=9000", "-p", "9001", "--host", "h", "--randomInit",
            "--modelVersion", "v1", "--fleetHeartbeatS", "0.1",
            "--fleetRestartBudget", "3", "--slots", "4",
            "--quantize", "int8"]
    out = worker_base_argv(argv)
    assert out == ["transformer_lm", "--slots", "4",
                   "--quantize", "int8"]


def test_router_worker_argv_reattaches_current_weights():
    r = FleetRouter("m", 2, base_argv=["m", "--slots", "2"],
                    checkpoint="ck_v1", version="v1")
    av = r.worker_argv(1)
    assert av[:3] == [sys.executable, "-m",
                      "bigdl_tpu.serving.fleet.worker"]
    assert ["--model", "ck_v1"] == av[av.index("--model"):
                                      av.index("--model") + 2]
    assert "--workerIndex" in av and av[av.index("--port") + 1] == "0"
    # after a rolling swap, restarts must boot with the NEW checkpoint
    r.note_reloaded("ck_v2", "v2")
    av2 = r.worker_argv(1)
    assert av2[av2.index("--model") + 1] == "ck_v2"
    assert av2[av2.index("--modelVersion") + 1] == "v2"
    assert r.random_init is False


# ------------------------------------------------------------- scoring
class _FakeProc:
    def __init__(self, rc=None):
        self.rc = rc
        self.pid = 12345

    def poll(self):
        return self.rc


def _handle(i, depth=0, burn=0.0, state="ready", alive=True,
            draining=False):
    h = WorkerHandle(i)
    h.proc = _FakeProc(None if alive else 1)
    h.port = 9000 + i
    h.state = state
    h.draining = draining
    h.status = control.WorkerStatus(index=i, queue_depth=depth,
                                    slo_burn=burn)
    return h


def test_pick_prefers_lowest_depth():
    r = FleetRouter("m", 2, base_argv=[], random_init=True)
    r._handles = [_handle(0, depth=4), _handle(1, depth=1)]
    assert r.pick().index == 1


def test_pick_burn_breaks_depth_ties():
    # equal queue depth: traffic drifts away from the replica already
    # burning its SLO budget
    r = FleetRouter("m", 2, base_argv=[], random_init=True)
    r._handles = [_handle(0, depth=2, burn=2.0),
                  _handle(1, depth=2, burn=0.0)]
    assert r.pick().index == 1


def test_pick_skips_dead_draining_and_excluded():
    r = FleetRouter("m", 4, base_argv=[], random_init=True)
    r._handles = [_handle(0, alive=False), _handle(1, draining=True),
                  _handle(2, depth=9), _handle(3, depth=0)]
    assert r.pick().index == 3
    assert r.pick(exclude={3}).index == 2
    with pytest.raises(NoLiveWorker):
        r.pick(exclude={2, 3})


def test_readyz_tracks_routable_workers():
    r = FleetRouter("m", 2, base_argv=[], random_init=True)
    r._handles = [_handle(0), _handle(1, alive=False)]
    status, detail = r.handle_readyz()
    assert status == 200 and detail["workers_routable"] == 1
    r._handles = [_handle(0, alive=False), _handle(1, alive=False)]
    status, detail = r.handle_readyz()
    assert status == 503 and detail["status"] == "unready"


# --------------------------------------------------------- aggregation
def test_parse_samples_skips_comments_and_garbage():
    page = ("# HELP a b\n# TYPE a counter\nns_a_total 3\n"
            'ns_b{x="1"} 2.5\nnot a sample\nns_c nan\n')
    got = parse_samples(page)
    assert ("ns_a_total", "", 3.0) in got
    assert ("ns_b", 'x="1"', 2.5) in got
    assert all(n != "not" for n, _, _ in got)


def test_aggregate_pages_sums_and_relabels():
    pages = {"0": "ns_req_total 3\nns_up 1\n",
             "1": "ns_req_total 4\nns_up 1\n"}
    out = aggregate_pages(pages)
    assert "ns_req_total 7" in out
    assert 'ns_req_total{worker="0"} 3' in out
    assert 'ns_req_total{worker="1"} 4' in out
    assert "ns_up 2" in out


def test_aggregate_pages_skips_quantiles_info_and_nonfinite():
    pages = {"0": ('ns_lat{quantile="0.5"} 7\nns_info{cfg="a"} 1\n'
                   "ns_bad nan\nns_ok 1\n"),
             "1": "ns_ok 2\n"}
    out = aggregate_pages(pages)
    assert "ns_ok 3" in out
    # per-worker relabels are kept, but no quantile/info/nan sums
    assert 'ns_lat{worker="0",quantile="0.5"} 7' in out
    assert "\nns_lat " not in out and "\nns_info " not in out \
        and "\nns_bad " not in out
    # existing worker labels never double-count
    pages2 = {"9": 'ns_ok{worker="0"} 5\n'}
    assert "ns_ok 5" not in aggregate_pages(pages2)


# ----------------------------------------------- ResolvedConfig spine
def _serve_ns(**kw):
    base = dict(strategy=None, quantize="off", speculate=0, fleet=0,
                model="transformer_lm")
    base.update(kw)
    return argparse.Namespace(**base)


def test_resolve_serve_config_topology_and_fleet():
    cfg = common.resolve_serve_config(
        _serve_ns(strategy="dp:2+tp:2", fleet=3))
    assert (cfg.serving_replicas, cfg.serving_tp) == (2, 2)
    assert cfg.fleet_workers == 3
    assert cfg.mesh == {"model": 2}
    d = cfg.describe()
    assert d["serving_replicas"] == 2 and d["fleet_workers"] == 3


def test_resolve_serve_config_abstract_devices_fit_explicit_shape():
    # dp:8+tp:4 needs 32 virtual devices — abstract resolution (the
    # router process, no jax call) must size them, not reject the spec
    cfg = common.resolve_serve_config(_serve_ns(strategy="dp:8+tp:4"))
    assert (cfg.serving_replicas, cfg.serving_tp) == (8, 4)


def test_resolve_serve_config_respects_real_device_count():
    with pytest.raises(SystemExit, match="devices"):
        common.resolve_serve_config(_serve_ns(strategy="tp:4"),
                                    n_devices=2)


def test_resolve_serve_config_normalizes_quantize_off():
    assert common.resolve_serve_config(_serve_ns()).quantize is None
    cfg = common.resolve_serve_config(_serve_ns(quantize="int8+kv8"))
    assert cfg.quantize == "int8+kv8"
    with pytest.raises(SystemExit, match="quantize"):
        common.resolve_serve_config(_serve_ns(quantize="int4"))


def test_resolve_serve_config_rejects_negative_fleet():
    with pytest.raises(SystemExit, match="fleet"):
        common.resolve_serve_config(_serve_ns(fleet=-1))


# ------------------------------------------- one worker process per chip
def test_fleet_pins_each_worker_to_its_own_chip(monkeypatch):
    """ISSUE 21: measured on the four-chip host — two unpinned processes
    cannot share it (the first claims every chip; the second dies with
    "TPU is already in use"), two pinned ones each see one chip."""
    from bigdl_tpu.cli.serve import build_parser
    from bigdl_tpu.serving.fleet import router as fr

    e0, e1 = fr.chip_pin_env(0), fr.chip_pin_env(1)
    assert e0["TPU_VISIBLE_CHIPS"] == "0" and e1["TPU_VISIBLE_CHIPS"] == "1"
    assert e0["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert (e0["TPU_MESH_CONTROLLER_PORT"]
            != e1["TPU_MESH_CONTROLLER_PORT"])

    monkeypatch.setattr(fr, "host_tpu_chips", lambda: 4)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    argv = ["transformer_lm", "--randomInit", "--fleet", "5"]
    with pytest.raises(SystemExit, match="4 TPU chip"):
        fr.run_fleet(build_parser().parse_args(argv), argv)
    argv = ["transformer_lm", "--randomInit", "--fleet", "2",
            "--strategy", "tp:2"]
    with pytest.raises(SystemExit, match="pinned to ONE chip"):
        fr.run_fleet(build_parser().parse_args(argv), argv)


# ------------------------------------------------ worker control plane
class _FakeBatcher:
    def __init__(self, depth=0):
        self.queue_depth = depth


class _FakeApp:
    def __init__(self, depth=0):
        self.replicas = None
        self.engine = object()
        self.batcher = _FakeBatcher(depth)
        self.decoder = None
        self.model_version = "v0"
        self.extra_routes = {}


def test_worker_control_registers_routes_and_heartbeats():
    app = _FakeApp(depth=3)
    wc = WorkerControl(app, index=2, version="v5", port=8123)
    assert ("GET", control.CONTROL_PATH) in app.extra_routes
    assert ("POST", control.RELOAD_PATH) in app.extra_routes
    assert app.model_version == "v5"
    status, body = wc.handle_state()
    assert status == 200
    st = control.WorkerStatus.from_dict(body)
    assert (st.index, st.queue_depth, st.model_version) == (2, 3, "v5")
    assert st.state == "ready" and st.pid == os.getpid()


def test_worker_reload_validates_payload():
    wc = WorkerControl(_FakeApp(), index=0)
    status, body = wc.handle_reload({"checkpoint": "ck"})  # no version
    assert status == 400 and "version" in body["error"]
    status, body = wc.handle_reload(
        {"checkpoint": "ck", "version": "v1", "drain_timeout_s": "x"})
    assert status == 400


def test_worker_reload_maps_swap_errors(monkeypatch):
    app = _FakeApp()
    wc = WorkerControl(app, index=0, version="v1")

    def _boom(*a, **k):
        raise swap.WeightSwapError("drain timeout")

    monkeypatch.setattr(swap, "swap_app_weights", _boom)
    status, body = wc.handle_reload({"checkpoint": "ck",
                                     "version": "v2"})
    assert status == 503 and "drain" in body["error"]
    assert wc.status().state == "ready"  # back in rotation on failure


def test_swap_drain_timeout_raises_without_touching_weights():
    app = _FakeApp(depth=1)  # never drains
    clock_t = [0.0]

    def clock():
        clock_t[0] += 10.0
        return clock_t[0]

    with pytest.raises(swap.WeightSwapError, match="NOT swapped"):
        swap.swap_app_weights(app, "ck", "v2", drain_timeout_s=5.0,
                              clock=clock)
    assert app.model_version == "v0"


# -------------------------------------- rolling-swap atomicity (jax)
def _offline_greedy(model, params, prompt, n):
    import numpy as np
    seq = [int(t) for t in prompt]
    toks = []
    for _ in range(n):
        logp, _ = model.apply(params, model.init_state(),
                              np.asarray([seq], np.int32))
        tok = int(np.argmax(np.asarray(logp)[0, -1]))
        toks.append(tok)
        seq.append(tok)
    return toks


@pytest.fixture(scope="module")
def swap_ckpts(tmp_path_factory):
    """A version-stamped checkpoint pair of the same tiny LM whose
    greedy decodes provably DIFFER on a chosen prompt — which weights
    answered a request is then observable from the tokens alone.
    Random inits can collapse to the same argmax, so candidate trees
    and prompts are searched until a differing pair is found."""
    import jax

    from bigdl_tpu import models
    from bigdl_tpu.utils.file import save_pytree
    root = tmp_path_factory.mktemp("fleet_swap")
    m = models.transformer_lm(50, d_model=32, num_layers=2,
                              num_heads=2, max_len=64)
    params1 = m.init(jax.random.PRNGKey(1))
    candidates = [m.init(jax.random.PRNGKey(s)) for s in (2, 3)]
    candidates.append(jax.tree_util.tree_map(lambda a: -a, params1))
    prompts = ([7, 3, 9], [2, 11, 5], [1, 2, 3, 4], [13, 7],
               [21, 34, 8, 2])
    found = None
    for params2 in candidates:
        for prompt in prompts:
            ref1 = _offline_greedy(m, params1, prompt, 8)
            ref2 = _offline_greedy(m, params2, prompt, 8)
            if ref1 != ref2:
                found = (params2, list(prompt), ref1, ref2)
                break
        if found:
            break
    assert found, "no weight pair with distinguishable greedy output"
    params2, prompt, ref1, ref2 = found
    out = {}
    for ver, params in (("v1", params1), ("v2", params2)):
        d = root / f"ck_{ver}"
        save_pytree({"params": params, "mod_state": m.init_state()},
                    str(d / "model.1"))
        out[ver] = str(d)
    return m, out, prompt, ref1, ref2


def _build_worker_app(ckpt, version):
    from bigdl_tpu.cli import serve as serve_cli
    args = serve_cli.build_parser().parse_args(
        ["transformer_lm", "--model", ckpt, "--vocabSize", "50",
         "--dModel", "32", "--numLayers", "2", "--numHeads", "2",
         "--seq", "64", "--slots", "2", "--buckets", "1,2",
         "--maxWaitMs", "2", "--modelVersion", version])
    common.apply_platform(args)
    app, engine, in_shape, in_dtype = serve_cli.build_app(args)
    return app


def _post_versioned(url, body, timeout=120.0):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return (json.loads(r.read()),
                r.headers.get("x-model-version"))


def test_rolling_swap_atomicity_in_flight_finishes_on_old_weights(
        swap_ckpts):
    """The satellite-3 pin: a /generate admitted BEFORE the swap
    completes on the v1 weights (its tokens match the v1 offline
    reference bit-for-bit and it reports x-model-version v1), while the
    swap — issued mid-decode — drains first, then lands v2; the next
    request matches the v2 reference. No response mixes versions."""
    from bigdl_tpu.serving import make_server
    model, cks, prompt, ref1, ref2 = swap_ckpts
    ck1, ck2 = cks["v1"], cks["v2"]

    app = _build_worker_app(ck1, "v1")
    WorkerControl(app, index=0, version="v1")
    srv = make_server(app, "127.0.0.1", 0)
    port = srv.server_address[1]
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    url = f"http://127.0.0.1:{port}"
    try:
        results = {}

        def _gen():
            results["body"], results["ver"] = _post_versioned(
                url + "/generate",
                {"tokens": prompt, "max_new_tokens": 8})

        g = threading.Thread(target=_gen)
        g.start()
        # wait until the request is genuinely in flight, then reload:
        # the swap MUST block on the drain, not yank the tree mid-batch
        deadline = time.monotonic() + 30
        while swap._in_flight(app) == 0:
            assert time.monotonic() < deadline, "request never admitted"
            time.sleep(0.002)
        code, body = control.request_json(
            "POST", "127.0.0.1", port, control.RELOAD_PATH,
            {"checkpoint": ck2, "version": "v2"}, timeout=120.0)
        assert code == 200, body
        g.join(120)
        assert results["body"]["tokens"] == ref1, \
            "in-flight decode leaked post-swap weights"
        assert results["ver"] == "v1"
        # after the swap: v2 weights, v2 header, provenance renamed
        body, ver = _post_versioned(
            url + "/generate", {"tokens": prompt, "max_new_tokens": 8})
        assert body["tokens"] == ref2 and ver == "v2"
        assert app.model_version == "v2"
        page = app.handle_metrics()
        assert '"model_version": "v2"' in page
    finally:
        srv.shutdown()
        srv.server_close()
        app.close()


def test_swap_failure_keeps_old_weights_serving(swap_ckpts):
    model, cks, prompt, ref1, _ = swap_ckpts
    ck1 = cks["v1"]
    app = _build_worker_app(ck1, "v1")
    wc = WorkerControl(app, index=0, version="v1")
    try:
        status, body = wc.handle_reload(
            {"checkpoint": os.path.join(ck1, "no_such_dir"),
             "version": "v9"})
        assert status in (500, 503), body
        assert app.model_version == "v1"
        # still serving, still on the old tree
        got = app.handle_generate({"tokens": prompt,
                                   "max_new_tokens": 8})
        assert got[0] == 200 and got[1]["tokens"] == ref1
    finally:
        app.close()


# ------------------------------------------- router e2e (fake workers)
_FAKE_WORKER = r"""
import json, sys
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
idx = int(sys.argv[1])
class H(BaseHTTPRequestHandler):
    def _j(self, code, obj):
        d = json.dumps(obj).encode()
        self.send_response(code)
        self.send_header("x-request-id",
                         self.headers.get("x-request-id", ""))
        self.send_header("x-model-version", "vF")
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(d)))
        self.end_headers()
        self.wfile.write(d)
    def do_GET(self):
        if self.path == "/control/state":
            self._j(200, {"index": idx, "state": "ready",
                          "queue_depth": 0, "decode_active": 0,
                          "model_version": "vF"})
        else:
            self._j(200, {"ok": True, "worker": idx})
    def do_POST(self):
        n = int(self.headers.get("Content-Length", 0))
        self.rfile.read(n)
        self._j(200, {"scores": [idx]})
    def log_message(self, *a):
        pass
srv = ThreadingHTTPServer(("127.0.0.1", 0), H)
print("serving fake on http://127.0.0.1:%d" % srv.server_address[1],
      flush=True)
srv.serve_forever()
"""


@pytest.fixture
def fake_fleet(tmp_path):
    script = tmp_path / "fake_worker.py"
    script.write_text(_FAKE_WORKER)
    from bigdl_tpu.resilience.supervisor import RetryPolicy
    router = FleetRouter(
        "fake", 2,
        make_argv=lambda i: [sys.executable, str(script), str(i)],
        heartbeat_s=0.1, start_timeout_s=30.0,
        restart_policy=RetryPolicy(budget=3, base_s=0.05,
                                   multiplier=1.0, max_s=0.1,
                                   jitter=0.0, seed=0))
    srv = None
    try:
        router.start()
        from http.server import ThreadingHTTPServer

        from bigdl_tpu.serving.fleet.router import _RouterHandler
        srv = ThreadingHTTPServer(("127.0.0.1", 0), _RouterHandler)
        srv.daemon_threads = True
        srv.router = router
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        yield router, f"http://127.0.0.1:{srv.server_address[1]}"
    finally:
        if srv is not None:
            srv.shutdown()
            srv.server_close()
        router.close()


def _get_json(url, timeout=10.0):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _post_json(url, body, headers=None, timeout=10.0):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def test_router_spawns_and_proxies(fake_fleet):
    router, url = fake_fleet
    status, body, _ = _get_json(url + "/readyz")
    assert status == 200 and body["workers_routable"] == 2
    status, body, hdr = _post_json(url + "/predict", {"inputs": [1]},
                                   headers={"x-request-id": "rt-1"})
    assert status == 200 and body["scores"][0] in (0, 1)
    assert hdr.get("x-request-id") == "rt-1"
    assert hdr.get("x-model-version") == "vF"
    status, body, _ = _get_json(url + "/debug/fleet")
    assert status == 200
    assert [w["model_version"] for w in body["workers"]] == ["vF", "vF"]


def test_router_restarts_killed_worker(fake_fleet):
    router, url = fake_fleet
    h = router.worker_handles()[0]
    pid0 = h.proc.pid
    h.proc.kill()
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        status, body, _ = _get_json(url + "/readyz")
        assert status == 200, "readyz flipped with a live survivor"
        if h.routable() and h.proc.pid != pid0:
            break
        time.sleep(0.1)
    assert h.routable() and h.restarts == 1 and h.proc.pid != pid0


def test_router_503_with_rid_when_all_workers_gone(fake_fleet):
    router, url = fake_fleet
    router._stop.set()  # freeze the monitor so nothing restarts
    if router._monitor is not None:
        router._monitor.join(5.0)
    for h in router.worker_handles():
        h.proc.kill()
        h.proc.wait(5.0)
    status, body, hdr = _post_json(url + "/predict", {"inputs": [1]},
                                   headers={"x-request-id": "rt-dead"})
    assert status == 503 and "no live fleet worker" in body["error"]
    assert hdr.get("x-request-id") == "rt-dead"
    status, body, _ = _get_json(url + "/readyz")
    assert status == 503 and body["workers_routable"] == 0


def test_router_metrics_aggregate_fake_workers(fake_fleet):
    router, url = fake_fleet
    page = router.handle_metrics()
    assert "bigdl_fleet_workers 2" in page
    assert "# fleet aggregate" in page
    prov = json.loads(next(
        l for l in page.splitlines()
        if l.startswith("# provenance ")).split(" ", 2)[2])
    assert prov["fleet_workers"] == 2 and prov["model"] == "fake"

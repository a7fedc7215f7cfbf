"""The decode loop keeps one step in flight (ISSUE 31): it dispatches step
n+1 before it reads step n. The tokens must be those of the one-step-deep
engine, bit for bit, whatever ends a request while a step is in flight.

Every case runs the same seeded requests twice: through caller-driven
``step()`` (dispatch, then retire the same step) and through the loop's own
round, ``_round(ahead=True)``, either on the real thread (``loop``) or
called by hand (``hand``), which lets a test land a cancel or an expiry
exactly between two rounds. Toy sizes, float32, CPU."""

import jax
import numpy as np
import pytest

from bigdl_tpu import models
from bigdl_tpu.models import SambaYLM
from bigdl_tpu.serving import DecodeEngine, MetricsRegistry

MAX_LEN = 64
RNG = np.random.RandomState(31)
PROMPTS = [RNG.randint(1, 50, size=n).tolist() for n in (5, 9, 12, 7, 3, 10)]


def lm():
    m = models.transformer_lm(50, d_model=32, num_layers=2, num_heads=2,
                              max_len=MAX_LEN)
    # at these sizes the class's own draw repeats its last token whatever
    # the temperature (a tied head under an embedding of unit scale); a
    # smaller embedding makes the sampled tokens differ from step to step,
    # so a wrong position or key shows
    params = m.init(jax.random.PRNGKey(1))
    return m, dict(params, emb={"weight": params["emb"]["weight"] * 0.15})


def sambay():
    # a window of 8: the ring wraps within a dozen tokens
    m = SambaYLM(init_std=0.125, vocab=96, d_model=64, num_layers=8,
                 num_heads=4, num_kv_heads=2, d_ff=128, window=8,
                 mb_per_layer=2, max_len=MAX_LEN)
    return m, m.init(jax.random.PRNGKey(1))


KINDS = {"lm_dense": (lm, {}),
         "lm_paged": (lm, {"kv_page_tokens": 8}),
         "sambay_dense": (sambay, {})}


class Clock:
    now = 0.0

    def __call__(self):
        return self.now


class Pair:
    """Three engines of one kind with their own registries: ``sync`` is
    driven by ``step()``, ``hand`` by ``_round(ahead=True)`` from the
    test, ``loop`` by its own thread."""

    def __init__(self, kind):
        build, kw = KINDS[kind]
        model, params = build()
        self.clock = Clock()
        for name in ("sync", "hand", "loop"):
            eng = DecodeEngine(model, params, slots=2, max_waiting=16,
                               prompt_buckets=(16,), clock=self.clock,
                               metrics=MetricsRegistry(), **kw)
            setattr(self, name, eng)
        self.loop.start()

    def close(self):
        for name in ("sync", "hand", "loop"):
            getattr(self, name).close()


@pytest.fixture(scope="module", params=sorted(KINDS))
def pair(request):
    p = Pair(request.param)
    yield p
    p.close()


@pytest.fixture(scope="module")
def toy_lm():
    return lm()


def count(eng, name):
    return eng.metrics.counter(name).value


def submit_all(eng, reqs):
    """Futures and streamed sinks of ``reqs`` (dicts of ``submit``'s
    arguments); the lock keeps a running loop from starting between two
    submits, so both engines see the same waiting queue."""
    sinks = [[] for _ in reqs]
    with eng._lock:
        futs = [eng.submit(emit=lambda toks, done, s=s: s.append(
                    (list(toks), done)), **r)
                for r, s in zip(reqs, sinks)]
    return futs, sinks


def drain(eng, futs, how):
    if how == "loop":
        for f in futs:
            assert f._event.wait(60)
        with eng._lock:  # the round that resolved the last one has ended
            pass
    else:
        rounds = 0
        while eng.busy():
            eng.step() if how == "sync" else eng._round(ahead=True)
            rounds += 1
            assert rounds < 500
    assert eng._flight is None and not eng.busy()


def results(futs):
    out = []
    for f in futs:
        try:
            out.append(f.result(0))
        except Exception as e:  # cancelled or expired
            out.append(type(e).__name__)
    return out


def streamed(sink):
    return [t for toks, _ in sink for t in toks]


def both_ways(pair, reqs, how):
    """``reqs`` through the one-step-deep engine and through the engine
    that runs ahead: token lists and streams of both."""
    fs, ss = submit_all(pair.sync, reqs)
    drain(pair.sync, fs, "sync")
    eng = getattr(pair, how)
    fa, sa = submit_all(eng, reqs)
    drain(eng, fa, how)
    return results(fs), results(fa), ss, sa


SAMPLING = {
    "greedy": {},
    "temperature": {"temperature": 0.9, "seed": 7},
    "top_k_top_p": {"temperature": 1.1, "top_k": 8, "top_p": 0.9,
                    "seed": 2 ** 31 + 5},
}


@pytest.mark.parametrize("how", ["hand", "loop"])
@pytest.mark.parametrize("sampling", sorted(SAMPLING))
def test_same_tokens_as_the_one_step_deep_engine(pair, sampling, how):
    # five requests on two slots: three are handed off from `_waiting`
    # into a slot a step in flight still writes to
    reqs = [dict(tokens=PROMPTS[i], max_new_tokens=n, **SAMPLING[sampling])
            for i, n in enumerate((9, 4, 13, 1, 6))]
    want, got, sink_want, sink_got = both_ways(pair, reqs, how)
    assert got == want
    assert [len(o) for o in got] == [9, 4, 13, 1, 6]
    for out, s_want, s_got in zip(got, sink_want, sink_got):
        assert streamed(s_got) == streamed(s_want) == out
        assert [done for _, done in s_got][-1] is True
    if sampling != "greedy":
        greedy = [dict(r, temperature=0.0) for r in reqs]
        assert both_ways(pair, greedy, "hand")[1] != got


@pytest.mark.parametrize("how", ["hand", "loop"])
def test_stop_token_drops_the_token_computed_after_it(pair, how):
    probe = dict(tokens=PROMPTS[0], max_new_tokens=12, temperature=1.5,
                 seed=3)
    (free,), _, _, _ = both_ways(pair, [probe], "hand")
    # the first token that had not come before: the request stops there
    k = next(i for i in range(2, 11) if free[i] not in free[:i])
    eng = getattr(pair, how)
    dropped = count(eng, "decode_dropped_tokens_total")
    want, got, _, sink = both_ways(pair, [dict(probe, stop_token=free[k])],
                                   how)
    assert got == want == [free[:k + 1]]
    assert streamed(sink[0]) == free[:k + 1]  # nothing after the stop
    assert count(eng, "decode_dropped_tokens_total") == dropped + 1
    assert count(pair.sync, "decode_dropped_tokens_total") == 0


@pytest.mark.parametrize("how", ["hand", "loop"])
def test_a_request_that_fills_the_cache_to_its_last_row(pair, how):
    """``prompt + max_new == max_len``: the last token is computed at
    position ``max_len - 1``, and no slot is ever run past its last."""
    eng = getattr(pair, how)
    seen, dispatch = [], eng._dispatch

    def spy(advance, prog, pos, *rest):
        seen.append(np.asarray(pos).copy())
        return dispatch(advance, prog, pos, *rest)

    eng._dispatch = spy
    try:
        reqs = [dict(tokens=PROMPTS[5], max_new_tokens=MAX_LEN - 10),
                dict(tokens=PROMPTS[1], max_new_tokens=3)]
        want, got, _, _ = both_ways(pair, reqs, how)
    finally:
        del eng._dispatch
    assert got == want and len(got[0]) == MAX_LEN - 10
    assert max(p.max() for p in seen) == MAX_LEN - 1
    assert min(p.min() for p in seen) >= 0
    if eng.paged:
        assert eng.kv_pages_in_use() == 0  # no reservation left behind


def end_between_rounds(pair, end):
    """A request ended by ``end(engine)`` between two rounds, while the
    step that advanced it is in flight; a waiting request takes its slot.
    Returns what the sync engine and the run-ahead engine gave."""
    reqs = [dict(tokens=PROMPTS[0], max_new_tokens=20, rid="ends",
                 deadline=100.0),
            dict(tokens=PROMPTS[1], max_new_tokens=11, temperature=0.7,
                 seed=11),
            dict(tokens=PROMPTS[2], max_new_tokens=8, temperature=0.7,
                 seed=12)]  # waits for the slot of "ends"
    out = {}
    for name, turn in (("sync", lambda e: e.step()),
                       ("hand", lambda e: e._round(ahead=True))):
        eng = getattr(pair, name)
        pair.clock.now = 0.0
        futs, sinks = submit_all(eng, reqs)
        # three tokens emitted either way: the first round ahead only
        # dispatches, so it takes one more
        for _ in range(3 if name == "sync" else 4):
            turn(eng)
        assert len(sinks[0]) == 3
        assert (eng._flight is not None) == (name == "hand")
        end(eng)
        assert eng._reqs[0].tokens == PROMPTS[2]  # handed off at once
        while eng.busy():
            turn(eng)
        pair.clock.now = 0.0
        out[name] = (results(futs), [streamed(s) for s in sinks])
    return out


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_a_request_ended_while_its_step_is_in_flight(pair, how):
    def cancel(eng):
        assert eng.cancel("ends")

    def expire(eng):
        pair.clock.now = 200.0
        eng._expire(pair.clock.now)

    dropped = count(pair.hand, "decode_dropped_tokens_total")
    out = end_between_rounds(pair, cancel if how == "cancel" else expire)
    (res_s, str_s), (res_h, str_h) = out["sync"], out["hand"]
    assert res_h == res_s
    assert res_h[0] in ("RuntimeError", "DeadlineExceeded")
    assert len(res_h[1]) == 11 and len(res_h[2]) == 8
    # the ended request streamed its three tokens and no more, and the
    # slot's next owner read none of them
    assert str_h == str_s and len(str_h[0]) == 3
    assert str_h[2] == res_h[2]
    assert count(pair.hand, "decode_dropped_tokens_total") == dropped + 1


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_three_slots_at_different_positions_equal_each_run_alone(kind):
    """Eight steps with three slots live at positions 5, 9 and 12: every
    slot's row goes to its own position in one batched write (ISSUE 33),
    and each answer is the one its request gets with the engine to
    itself."""
    build, kw = KINDS[kind]
    model, params = build()
    reqs = [dict(tokens=PROMPTS[i], max_new_tokens=8, temperature=0.9,
                 seed=40 + i) for i in (0, 1, 2)]

    def run(batch):
        eng = DecodeEngine(model, params, slots=3, prompt_buckets=(16,),
                           **kw)
        try:
            futs, _ = submit_all(eng, batch)
            assert sorted(int(p) for p in eng._pos)[3 - len(batch):] == \
                sorted(len(r["tokens"]) for r in batch)
            drain(eng, futs, "sync")
            return results(futs)
        finally:
            eng.close()

    together = run(reqs)
    assert [len(o) for o in together] == [8, 8, 8]
    assert together == [run([r])[0] for r in reqs]
    assert len({tuple(o) for o in together}) == 3


def test_step_keeps_its_contract(pair):
    """What ``benchmark/lib/serve.py``'s check relies on: after k calls
    of ``step()`` the slot's logits are those of position ``s - 1 + k``,
    the future of a ``max_new = n + 1`` request resolves on call n + 1,
    and nothing is left in flight."""
    eng, n, prompt = pair.sync, 3, PROMPTS[2]
    steps0 = count(eng, "decode_steps_total")
    fut = eng.submit(prompt, n + 1)
    slot = next(i for i, r in enumerate(eng._reqs) if r is not None)
    got = [np.asarray(eng._logits)[slot]]
    for k in range(1, n + 1):
        assert eng.step() == 1 and eng._flight is None
        assert int(eng._pos[slot]) == len(prompt) + k
        got.append(np.asarray(eng._logits)[slot])
        assert not fut.done()
    eng.step()
    out = fut.result(0)
    assert len(out) == n + 1 and eng.step() == 0
    # greedy: the token of step k+1 is the argmax of the logits after k
    assert [int(np.argmax(g)) for g in got] == out
    if not isinstance(eng.model, SambaYLM):  # test_sambay_lm.py has its own
        logp, _ = eng.model.apply(eng.params, eng.model.init_state(),
                                  np.asarray([prompt + out[:n]], np.int32))
        want = np.asarray(logp)[0, len(prompt) - 1:]
        got = np.stack(got)
        got = got - np.log(np.exp(got).sum(-1, keepdims=True))
        np.testing.assert_allclose(got, want, atol=2e-4)
    assert count(eng, "decode_steps_total") == steps0 + n + 1


def test_counters_say_how_often_the_loop_runs_ahead(pair):
    sync, loop = pair.sync, pair.loop
    req = [dict(tokens=PROMPTS[3], max_new_tokens=40)]
    before = {n: (count(sync, n), count(loop, n))
              for n in ("decode_steps_total", "decode_runahead_steps_total")}
    want, got, _, _ = both_ways(pair, req, "loop")
    assert got == want
    moved = {n: (count(sync, n) - b[0], count(loop, n) - b[1])
             for n, b in before.items()}
    # 40 steps either way; the loop dispatched all but the first of them
    # with the step before still unread
    assert moved["decode_steps_total"] == (40, 40)
    assert moved["decode_runahead_steps_total"] == (0, 39)
    assert count(sync, "decode_runahead_steps_total") == 0
    for reg in (sync.metrics, loop.metrics):
        page = reg.render()
        assert "decode_runahead_steps_total" in page
        assert "decode_dropped_tokens_total" in page


def test_metrics_endpoint_shows_both_counters(pair):
    from bigdl_tpu.serving import ServingApp
    app = ServingApp(name="toy", metrics=pair.loop.metrics,
                     decoder=pair.loop)
    page = app.handle_metrics()
    for name in ("decode_runahead_steps_total",
                 "decode_dropped_tokens_total"):
        assert f"bigdl_serving_{name}" in page


def test_the_speculative_round_stays_one_step_deep(toy_lm):
    """The loop observes ``speculate``: a speculative engine's thread
    never leaves a step in flight and never counts one run ahead."""
    model, params = toy_lm
    plain = DecodeEngine(model, params, slots=2, prompt_buckets=(16,)
                         ).generate(PROMPTS[4], 10)
    eng = DecodeEngine(model, params, slots=2, prompt_buckets=(16,),
                       speculate=2, metrics=MetricsRegistry())
    eng.start()
    try:
        assert eng.generate(PROMPTS[4], 10) == plain
        assert eng._flight is None
        assert count(eng, "decode_runahead_steps_total") == 0
    finally:
        eng.close()


@pytest.mark.parametrize("how", ["close", "declare_dead"])
def test_close_and_declare_dead_drop_the_step_in_flight(toy_lm, how):
    model, params = toy_lm
    eng = DecodeEngine(model, params, slots=2, prompt_buckets=(16,),
                       metrics=MetricsRegistry())
    fut = eng.submit(PROMPTS[0], 10)
    eng._round(ahead=True)
    eng._round(ahead=True)
    assert eng._flight is not None
    if how == "close":
        eng.close()
    else:
        eng.declare_dead(RuntimeError("wedged"))
    assert eng._flight is None
    assert count(eng, "decode_dropped_tokens_total") == 1
    with pytest.raises(Exception):
        fut.result(0)


def test_submitters_and_cancels_race_the_loop(pair):
    """Twelve threads submit and four of them cancel at a shortened
    switch interval while the loop runs ahead: every answer that was not
    cancelled is the one-step-deep engine's, and nothing is left in a
    slot, in flight or reserved."""
    import sys
    import threading

    reqs = [dict(tokens=PROMPTS[i % len(PROMPTS)], max_new_tokens=5 + i,
                 temperature=0.9, seed=100 + i, rid=f"r{i}")
            for i in range(12)]
    want, _, _, _ = both_ways(pair, reqs, "hand")
    eng, got, cancelled = pair.loop, {}, set()

    def client(i):
        fut = eng.submit(**reqs[i])
        if i % 3 == 0 and eng.cancel(f"r{i}"):
            cancelled.add(i)
        try:
            got[i] = fut.result(60)
        except RuntimeError:
            got[i] = None

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(90)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(before)
    for i in range(12):
        assert got[i] == (None if i in cancelled else want[i]), i
    assert len(cancelled) < 12 and eng.alive()
    with eng._lock:
        assert eng._flight is None and not eng.busy()
        assert eng.kv_pages_in_use() == 0

"""Training cells (traffic kind ``train_steps``): whole ``Optimizer``
steps on a fixed pool of seeded rows through ``BatchDataSet``. The window
is taken from inside ``Optimizer.optimize()`` by the end trigger, which the
Optimizer calls after every step, once the loss has been fetched to the
host (``log_every=1``, its default): a step ends in a scalar fetch."""

import math
import time

import numpy as np

from . import reference, trace
from .model import build_model, seeded_params


class Window:
    """The Optimizer's ``end_when``: records the host clock at the end of
    every step, opens the window after the warm-up steps, traces a slice
    after the scored steps of a traced run, and ends the run."""

    def __init__(self, *, warmup_steps, seconds, trace_dir, trace_steps,
                 on_open):
        self.warmup, self.seconds = warmup_steps, seconds
        self.trace_dir, self.trace_steps = trace_dir, trace_steps
        self.on_open = on_open
        self.times, self.losses = {}, {}
        self.t_open = self.t_close = self.close_it = None
        self.trace_from = None
        self._span = None
        self.done = False

    def __call__(self, driver):
        """True once the run is over; the loop asks twice at an epoch
        boundary, so a repeated iteration gets the last answer."""
        now = time.perf_counter()
        it = driver["iteration"]
        if it in self.times or it == 0:
            return self.done
        self.done = self._step_ended(it, now, float(driver["loss"]))
        return self.done

    def _step_ended(self, it, now, loss):
        self.times[it] = now
        self.losses[it] = loss
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        if it < self.warmup:
            return False
        if it == self.warmup:
            self.t_open = now
            self.on_open(now)
        if self.t_close is None and now - self.t_open >= self.seconds:
            self.t_close, self.close_it = now, it
            if self.trace_dir is None:
                return True
            trace.start(self.trace_dir)
            self.trace_from = it
        if self.trace_from is not None:
            if it - self.trace_from >= self.trace_steps:
                return True
            self._span = trace.annotate("optimizer_step")
            self._span.__enter__()
        return False


def make_rows(seed, n_rows, seq_len, vocab):
    """Seeded synthetic rows; the target of a position is the next token
    of the same row (the last position's is drawn)."""
    rs = np.random.RandomState(seed % (2 ** 32))
    toks = rs.randint(1, vocab, (n_rows, seq_len + 1)).astype(np.int32)
    return toks[:, :-1].copy(), toks[:, 1:].copy()


def run(ctx):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import nn
    from bigdl_tpu.dataset import BatchDataSet
    from bigdl_tpu.optim import AdamW, Optimizer

    cfg, traffic, parts = ctx["config"], ctx["traffic"], ctx["parts"]
    margs, tr = cfg["model"], cfg["train"]
    n_chips = ctx["chips"]
    seq = traffic["seq_len"]
    batch = tr["per_chip_batch"] * n_chips
    on_tpu = ctx["device"]["platform"] == "tpu"

    t = time.perf_counter()
    model = build_model(cfg, attn_impl="flash" if on_tpu else None,
                        compute_dtype=jnp.dtype(tr["compute_dtype"]),
                        remat=tr.get("remat", False))
    params = seeded_params(model, ctx["seed"], jnp.float32)
    x, y = make_rows(ctx["seed"], batch * traffic["pool_batches"], seq,
                     margs["vocab"])
    jax.block_until_ready(params)
    parts["weights_and_rows_s"] = time.perf_counter() - t

    # correctness, outside the window: the plain f32 reference's loss on
    # the first batch, row by row, against the Optimizer's first loss
    t = time.perf_counter()
    ref_loss = float(np.mean([reference.mean_nll(params, margs, x[i], y[i])
                              for i in range(batch)]))
    parts["reference_s"] = time.perf_counter() - t

    strategy = None
    if traffic.get("strategy") == "dp":
        from bigdl_tpu.parallel import DataParallel, make_mesh
        strategy = DataParallel(make_mesh({"data": n_chips}))
    seconds = ctx["seconds"]
    trace_steps = traffic["trace_steps"] if ctx["trace"] else 0
    if ctx["trace"]:  # the traced slice follows a shortened scored window
        seconds = max(seconds * 0.5, seconds - traffic["trace_reserve_s"])
    t_opt = time.perf_counter()
    win = Window(warmup_steps=traffic["warmup_steps"], seconds=seconds,
                 trace_dir=ctx["trace_dir"] if ctx["trace"] else None,
                 trace_steps=trace_steps, on_open=ctx["window_open"])
    opt = Optimizer(model, BatchDataSet(x, y, batch),
                    nn.TimeDistributedCriterion(nn.ClassNLLCriterion()),
                    optim_method=AdamW(learning_rate=tr["learning_rate"],
                                       weight_decay=tr["weight_decay"]),
                    end_when=win, strategy=strategy, seed=ctx["seed"] % 2**31)
    opt.set_state(params=params)
    del params
    trained = opt.optimize()
    jax.block_until_ready(trained.params)
    planes = trace.stop_and_load(ctx["trace_dir"]) if ctx["trace"] else None
    parts["first_step_s"] = win.times[1] - t_opt  # compile or cache load
    parts["warmup_steps_s"] = win.t_open - win.times[1]

    its = sorted(i for i in win.times if win.warmup <= i <= win.close_it)
    n_steps = len(its) - 1
    elapsed = win.times[its[-1]] - win.times[its[0]]
    step_ms = [(win.times[b] - win.times[a]) * 1e3
               for a, b in zip(its, its[1:])]
    tok_s_chip = n_steps * batch * seq / elapsed / n_chips
    # tolerance: bf16 activations round each logit by ~2^-9 of its size;
    # over a batch's tokens the mean NLL moved by < 2e-4 relative on the
    # chip (PERF.md), f32 compute by < 1e-6; 1e-3 fails a wrong mask, a
    # dropped bias or scale (>= 1e-2) and bf16 log-probs (~4e-3)
    rel = abs(win.losses[1] - ref_loss) / abs(ref_loss)
    finite = all(math.isfinite(v) for v in win.losses.values())
    return {
        "correct": bool(finite and rel < traffic["loss_rel_tol"]),
        "attempted": n_steps, "failed": 0,
        "e2e": {"train_tok_s": tok_s_chip},
        "checks": {"first_loss": win.losses[1], "reference_loss": ref_loss,
                   "rel_err": rel, "losses_finite": finite,
                   "last_loss": win.losses[max(win.losses)]},
        "run": {"kind": "train", "step_ms": step_ms, "steps": n_steps,
                "batch": batch, "seq_len": seq, "chips": n_chips,
                "tok_s_chip": tok_s_chip, "planes": planes,
                "traced_steps": trace_steps},
    }

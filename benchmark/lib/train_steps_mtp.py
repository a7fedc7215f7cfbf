"""Training cells of a model with a multi-token prediction module (traffic
kind ``train_steps_mtp``): ``lib/train.py``'s whole ``Optimizer`` steps on
a fixed pool of seeded rows through ``BatchDataSet``, its ``Window`` and
its rows, with three differences.

* The loss is the model's own criterion (``model.criterion()``:
  ``CE_main + weight * CE_mtp``), not a next-token NLL built here.
* ``correct`` holds four checks, all made after the window on the weights
  drawn again from the seed, so none of them counts in ``setup_s``:

  - the Optimizer's first loss against the plain reference's loss on the
    whole first batch (relative error under ``loss_rel_tol``);
  - the timed step's own first gradient, read from its AdamW state after
    step 1 (the first moment is ``(1 - beta1) g``), against the
    reference's ``batch_grads`` (``jax.vjp`` of its loss) on the same
    batch: the worst leaf's ``|g - g_ref| / |g_ref|`` under
    ``grad_rel_tol``;
  - the parameters' change over the warm-up steps, copied from the timed
    step's state, against as many steps of a plain AdamW (numpy, float32,
    the configuration's numbers) on the reference's gradients of the same
    batches: the worst leaf's ``|dp - dp_ref| / |dp_ref|`` under
    ``step_rel_tol``, where a step that leaves its state unchanged reads
    exactly 1;
  - on the batch's first row both heads' logits of ``model.train_logits``
    (the forward ``apply(..., training=True)`` runs in the step) against
    the reference's ``routes``: the largest error over the reference's
    largest |logit| (the MTP head over the rows it has a target for),
    under ``logits_rel_tol``, at the rows where the program chose the
    experts this chip holds as the reference chose them in every routed
    layer before that row's head; the share of rows where it did not is
    held under ``flip_share_tol``. A row whose held experts flipped under
    bf16 moves its logits as much as a planted fault does (the traffic
    file's ``logits_rel_tol_why``), so the rows are told apart by the
    routing, which both sides report.

  The state is copied to the host where the Optimizer hands each step's
  state to its checkpoint writer, before the next step is dispatched:
  during the warm-up, so it counts in ``setup_s`` (two copies of the
  float32 tree, a second or two on the chip's host).
* ``run`` carries the model's training counters (``train_counters``: the
  Optimizer fetches them with each loss, so ``counted_steps`` is every
  step), read over the whole process."""

import math
import time

import numpy as np

from . import trace
from .model import build_model, load_reference, seeded_params
from .train import Window, make_rows


def _row_errs(got, want):
    """Each row's largest error over the reference's largest |logit|."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return np.abs(got - want).max(-1) / np.abs(want).max()


def _held_sets(chosen, held, share):
    """(rows, held) bool: the held experts each row chose, of the global
    expert ids ``chosen`` (rows, top_k)."""
    local = np.asarray(chosen) - share * held
    return np.stack([(local == e).any(-1) for e in range(held)], -1)


def logits_checks(margs, got, want):
    """The logits check of one row: ``got`` the program's ``train_logits``
    of it (main (1, s, V), MTP (1, s, V), bit sets (1, s, layers,
    words)), ``want`` the reference's ``routes``."""
    (g_main, g_mtp, picked), (w_main, w_mtp, chosen) = got, want
    held, share = margs["experts_held"], margs.get("share", 0)
    e = np.arange(held)
    bits = np.asarray(picked)[0]
    ours = ((bits[..., e // 32] >> (e % 32).astype(np.uint32)) & 1) > 0
    same = [np.all(ours[:len(c), l] == _held_sets(c, held, share), -1)
            for l, c in enumerate(chosen)]
    rows = np.all(same[:-1], axis=0)         # the main model's layers
    mtp_rows = rows[:-1] & same[-1]          # and the MTP block's
    main, mtp = _row_errs(g_main[0], w_main), _row_errs(g_mtp[0, :-1], w_mtp)
    worst = lambda errs, keep: float(errs[keep].max()) if keep.any() \
        else float("inf")
    return {"logits_rel_err": worst(main, rows),
            "mtp_logits_rel_err": worst(mtp, mtp_rows),
            "flipped_share": float(1 - rows.mean()),
            "mtp_flipped_share": float(1 - mtp_rows.mean()),
            "logits_rel_err_all_rows": float(main.max()),
            "mtp_logits_rel_err_all_rows": float(mtp.max())}


def _leaves(tree):
    import jax
    return [(jax.tree_util.keystr(path), np.asarray(leaf, np.float32))
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)]


def worst_leaf(got, want):
    """(the largest ``|got - want| / |want|`` over the leaves of two
    trees, that leaf's name). A leaf that is zero in ``want`` (the
    selection bias's gradient) reads 0 if it is zero in ``got`` too, else
    infinity."""
    worst, name = 0.0, None
    for (key, g), (_, w) in zip(_leaves(got), _leaves(want)):
        scale = float(np.linalg.norm(w))
        diff = float(np.linalg.norm(g - w))
        err = diff / scale if scale else (0.0 if diff == 0 else math.inf)
        if name is None or err > worst:
            worst, name = err, key
    return worst, name


def keep_state(opt, warm, beta1):
    """Host copies of the timed step's own state, taken where the
    Optimizer hands each step's state to its checkpoint writer (called
    after every step, before the next step is dispatched and donates it):
    the first gradient (AdamW's first moment after step 1 over ``1 -
    beta1``) and the parameters after step ``warm``."""
    import jax
    kept, write = {}, opt._maybe_checkpoint

    def checkpoint(params, mod_state, opt_state, driver):
        it = driver["iteration"]
        if it == 1:
            kept["grad"] = jax.tree_util.tree_map(
                lambda m: np.asarray(m) / np.float32(1 - beta1),
                jax.device_get(opt_state["m"]))
        if it == warm:
            kept["params"] = jax.device_get(params)
        return write(params, mod_state, opt_state, driver)

    opt._maybe_checkpoint = checkpoint
    return kept


def adamw_change(grad_at, p0, steps, tr):
    """The change of every leaf of ``p0`` (a host tree) over ``steps``
    steps of the plain AdamW: decoupled decay, bias-corrected moments,
    float32 on the host, the parameters kept in float32 as the program
    keeps them (a leaf that moves by its decay alone, 1e-5 of itself a
    step, moves by what float32 can hold); ``grad_at(t, params)`` is step
    t's gradient at ``params()``, a device copy made leaf by leaf."""
    import jax
    lr, wd, eps = tr["learning_rate"], tr["weight_decay"], tr["eps"]
    b1, b2 = tr["beta1"], tr["beta2"]
    flat, treedef = jax.tree_util.tree_flatten(p0)
    p = [a.copy() for a in flat]
    m = [np.zeros_like(a) for a in flat]
    v = [np.zeros_like(a) for a in flat]
    params = lambda: jax.tree_util.tree_unflatten(
        treedef, [jax.device_put(a) for a in p])
    for t in range(1, steps + 1):
        g = jax.tree_util.tree_leaves(grad_at(t, params))
        c1, c2 = np.float32(1 - b1 ** t), np.float32(1 - b2 ** t)
        for pa, ma, va, ga in zip(p, m, v, g):
            ma *= np.float32(b1)
            ma += np.float32(1 - b1) * ga
            va *= np.float32(b2)
            va += np.float32(1 - b2) * (ga * ga)
            upd = (ma / c1) / (np.sqrt(va / c2) + np.float32(eps))
            upd += np.float32(wd) * pa
            pa -= np.float32(lr) * upd
    return jax.tree_util.tree_unflatten(
        treedef, [pa - a for pa, a in zip(p, flat)])


def reference_checks(model, reference, margs, tr, params, x, y, batch,
                     pool, kept, warm):
    """The gradient, parameter-change and logits checks on the seeded
    weights ``params`` (device; released here): see the module's
    docstring."""
    import jax

    rows = lambda t: slice((t - 1) % pool * batch, (t - 1) % pool * batch
                           + batch)
    first_loss, g1 = reference.batch_grads(params, margs, x[rows(1)],
                                           y[rows(1)])
    g1 = jax.device_get(g1)
    grad_err, grad_leaf = worst_leaf(kept.pop("grad"), g1)
    logits = logits_checks(
        margs, jax.device_get(jax.jit(model.train_logits)(params, x[:1])),
        jax.device_get(reference.routes(params, margs, x[0])))
    p0 = jax.device_get(params)
    del params
    first = [g1]
    del g1

    def grad_at(t, params):
        if t == 1:
            return first.pop()
        return jax.device_get(reference.batch_grads(
            params(), margs, x[rows(t)], y[rows(t)])[1])

    change = adamw_change(grad_at, p0, warm, tr)
    ours = jax.tree_util.tree_map(np.subtract, kept.pop("params"), p0)
    step_err, step_leaf = worst_leaf(ours, change)
    return first_loss, {"grad_rel_err": grad_err, "grad_worst_leaf": grad_leaf,
                        "step_rel_err": step_err, "step_worst_leaf": step_leaf,
                        **logits}


def run(ctx):
    import gc

    import jax
    import jax.numpy as jnp

    from bigdl_tpu.dataset import BatchDataSet
    from bigdl_tpu.obs.metrics import get_registry
    from bigdl_tpu.optim import AdamW, Optimizer

    cfg, traffic, parts = ctx["config"], ctx["traffic"], ctx["parts"]
    margs, tr = cfg["model"], cfg["train"]
    n_chips = ctx["chips"]
    seq = traffic["seq_len"]
    batch = tr["per_chip_batch"] * n_chips
    warm = traffic["warmup_steps"]
    on_tpu = ctx["device"]["platform"] == "tpu"
    reference = load_reference(cfg)  # a file without the key fails here

    t = time.perf_counter()
    model = build_model(cfg, attn_impl="flash" if on_tpu else None,
                        compute_dtype=jnp.dtype(tr["compute_dtype"]),
                        remat=tr.get("remat", False))
    params = seeded_params(model, ctx["seed"], jnp.float32)
    x, y = make_rows(ctx["seed"], batch * traffic["pool_batches"], seq,
                     margs["vocab"])
    jax.block_until_ready(params)
    parts["weights_and_rows_s"] = time.perf_counter() - t

    seconds = ctx["seconds"]
    trace_steps = traffic["trace_steps"] if ctx["trace"] else 0
    if ctx["trace"]:  # the traced slice follows a shortened scored window
        seconds = max(seconds * 0.5, seconds - traffic["trace_reserve_s"])
    t_opt = time.perf_counter()
    win = Window(warmup_steps=warm, seconds=seconds,
                 trace_dir=ctx["trace_dir"] if ctx["trace"] else None,
                 trace_steps=trace_steps, on_open=ctx["window_open"])
    opt = Optimizer(model, BatchDataSet(x, y, batch), model.criterion(),
                    optim_method=AdamW(learning_rate=tr["learning_rate"],
                                       beta1=tr["beta1"], beta2=tr["beta2"],
                                       eps=tr["eps"],
                                       weight_decay=tr["weight_decay"]),
                    end_when=win, seed=ctx["seed"] % 2**31)
    opt.set_state(params=params)
    kept = keep_state(opt, warm, tr["beta1"])
    del params
    trained = opt.optimize()
    jax.block_until_ready(trained.params)
    planes = trace.stop_and_load(ctx["trace_dir"]) if ctx["trace"] else None
    parts["first_step_s"] = win.times[1] - t_opt  # compile or cache load
    parts["warmup_steps_s"] = win.t_open - win.times[1]
    del trained, opt  # the checks need the device's memory
    gc.collect()

    its = sorted(i for i in win.times if win.warmup <= i <= win.close_it)
    n_steps = len(its) - 1
    elapsed = win.times[its[-1]] - win.times[its[0]]
    step_ms = [(win.times[b] - win.times[a]) * 1e3
               for a, b in zip(its, its[1:])]
    tok_s_chip = n_steps * batch * seq / elapsed / n_chips
    registry = get_registry()
    counters = {name: registry.counter(name).value
                for name in getattr(model, "train_counters", {})}

    # correctness, after the window: the same seeded weights drawn again
    t = time.perf_counter()
    ref_loss, checks = reference_checks(
        model, reference, margs, tr,
        seeded_params(model, ctx["seed"], jnp.float32), x, y, batch,
        traffic["pool_batches"], kept, warm)
    rel = abs(win.losses[1] - ref_loss) / abs(ref_loss)
    finite = all(math.isfinite(v) for v in win.losses.values())
    tol, flips = traffic["logits_rel_tol"], traffic["flip_share_tol"]
    return {
        "correct": bool(
            finite and rel < traffic["loss_rel_tol"]
            and checks["grad_rel_err"] < traffic["grad_rel_tol"]
            and checks["step_rel_err"] < traffic["step_rel_tol"]
            and checks["logits_rel_err"] < tol
            and checks["mtp_logits_rel_err"] < tol
            and checks["flipped_share"] < flips
            and checks["mtp_flipped_share"] < flips),
        "attempted": n_steps, "failed": 0,
        "e2e": {"train_tok_s": tok_s_chip},
        "checks": {"first_loss": win.losses[1], "reference_loss": ref_loss,
                   "rel_err": rel, **checks, "losses_finite": finite,
                   "last_loss": win.losses[max(win.losses)],
                   "checks_s": time.perf_counter() - t},
        "run": {"kind": "train", "step_ms": step_ms, "steps": n_steps,
                "batch": batch, "seq_len": seq, "chips": n_chips,
                "tok_s_chip": tok_s_chip, "planes": planes,
                "traced_steps": trace_steps, "train_counters": counters,
                "counted_steps": max(win.times)},
    }

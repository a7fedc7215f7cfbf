#!/usr/bin/env python3
"""The controls of ``serve_rag_batch``: the cell's run with one function of
the plain reference replaced, judged by the harness's own comparison
(``lib/closed_loop_agreed_rows.py compare`` and ``judged``); each has to
come out ``"correct": false``. The program is what the cell times.

    python3 benchmark/controls/serve_rag_batch.py <control>[,<control>...] \
        --seed <n> --seconds 5 --trace 0 [--rehearse-cpu]

``window_off_reference``      the window layers attend the whole prefix:
                              what a ring that never wrapped, or a kernel
                              that ignored its window, computes;
``halfsplit_rope_reference``  q and k turn in NeoX's pairs (i, i + 64), not
                              GPT-J's (2i, 2i + 1);
``serial_block_reference``    a sequential pre-norm block, ``h += A(LN(h));
                              h += FFN(LN(h))`` (the layer's one LayerNorm
                              twice), not the parallel one;
``shared_sum_reference``      the four shared experts summed, not averaged;
``fp8_reference``             the nearest precision below the
                              configuration's: the reference reads every
                              weight rounded to float8_e4m3, the program
                              the bfloat16 ones;
``none``                      no change (the cell itself).

One name runs the cell with that reference in place of the plain one and
prints ``benchmark/run.py``'s result line. Several names, comma-separated,
run the cell once: its own reference decides the result line, and each
planted reference is put through the same comparison against the same
program rows, one ``{"info": "control", "control": <name>, "correct":
...}`` line each, before the result line. ``tests/benchmark_tests/
test_benchmark_command_a.py`` runs them at the rehearsal sizes; PERF.md
section 6 has the chip's readings.
"""

import os
import sys
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "serve_rag_batch"


def _half_split(x, theta):
    """x (s, heads, d) at positions 0..s-1, dims [0, d/2) paired with
    [d/2, d)."""
    import jax.numpy as jnp
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _serial(h, norm, attend, feed):
    x = h + attend(norm(h))
    return x + feed(norm(x))


def _fp8(w):
    import jax.numpy as jnp
    return w.astype(jnp.float8_e4m3fn).astype(jnp.float32)


# control -> the reference's functions it replaces
PLANTED = {
    "window_off_reference": {"_visible": lambda i, j, window: j <= i},
    "halfsplit_rope_reference": {"_rotate": _half_split},
    "serial_block_reference": {"_block": _serial},
    "shared_sum_reference": {"_combine_shared": sum},
    "fp8_reference": {"_weight": _fp8},
    "none": {},
}


def planted(load, cfg, name):
    """The reference ``load(cfg)`` with control ``name``'s functions
    replaced (before its first call traces them)."""
    ref = load(cfg)
    for fn_name, fn in PLANTED[name].items():
        getattr(ref, fn_name)  # a name the reference does not have fails
        setattr(ref, fn_name, fn)
    return ref


def one(name):
    """The cell with control ``name``'s reference in place of its own."""
    from benchmark.lib import serve
    load = serve.load_reference
    return mock.patch.object(serve, "load_reference",
                             lambda cfg: planted(load, cfg, name))


def several(names):
    """The cell, and each control's reference through the same comparison
    against the same program rows."""
    from benchmark.lib import closed_loop_agreed_rows as agreed, serve
    load = serve.load_reference

    def check_and_warm(ctx, decoder, params, reference):
        margs, chk = ctx["config"]["model"], ctx["traffic"]["check"]
        rows = agreed.program_rows(ctx, decoder, params)
        checks = agreed.compare(rows, reference, params, margs)
        for name in names:
            got = agreed.compare(rows, planted(load, ctx["config"], name),
                                 params, margs)
            ctx["info"]("control", control=name,
                        correct=agreed.judged(got, chk), **got)
        checks["prefill_buckets"] = agreed.warm_buckets(ctx, decoder)
        return checks

    return mock.patch.object(agreed, "check_and_warm", check_and_warm)


def main(argv):
    names = argv[0].split(",") if argv else []
    if not names or not all(n in PLANTED for n in names):
        print(f"usage: serve_rag_batch.py {'|'.join(PLANTED)}[,...] "
              "[benchmark/run.py's arguments]", file=sys.stderr)
        return 2
    from benchmark import run
    with one(names[0]) if len(names) == 1 else several(names):
        return run.main(["--workload", CELL, *argv[1:]])


if __name__ == "__main__":
    rc = main(sys.argv[1:])
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)

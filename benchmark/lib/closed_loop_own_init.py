"""Traffic kind ``closed_loop_own_init``: ``lib/serve.py``'s closed loop,
letter for letter, on weights drawn by the model class's own ``init``.

``lib/model.py seeded_params`` draws every leaf N(0, 0.02). A state-space
layer is not made to start there: with conv taps, skip and poles of 0.02
the Mamba-1 and gated-memory mixers put out 3e-4 of their layer's MLP
output, and the comparison that decides ``correct`` cannot see a broken
scan or a stale state (PERF.md section 7). A configuration whose cell
names this kind states its draw, ``"weights": {"draw": "own_init"}``: the
class's own ``init(key)`` from the seed (for Mamba the published one: conv
U(+-1/2), ``A_log = log(1..N)``, ``D = 1``, ``b_dt`` the inverse softplus
of a log-uniform step), and N(0, 0.02) added to every vector, so that no
bias sits at 0 and no norm scale at exactly 1 and a dropped one still
shows, as under ``seeded_params``. Made on the device in one
jitted call, in the dtype the weights are held in.

Everything else is ``serve.run``: the one name it looks the draw up by is
replaced for the length of the call, in this process, which runs one cell.
A later ``benchmark`` PR that lets ``lib/model.py`` read the ``"weights"``
key makes this file and the kind unnecessary."""

from unittest import mock

import jax
import jax.numpy as jnp

from . import serve
from .model import INIT_STD, named


def own_init_params(model, seed, dtype):
    """``seeded_params``'s signature, the class's own draw."""
    def make(key):
        k_init, k_noise = jax.random.split(key)
        leaves, treedef = jax.tree_util.tree_flatten(model.init(k_init))
        out = []
        for i, v in enumerate(leaves):
            if v.ndim == 1:
                v = v + INIT_STD * jax.random.normal(
                    jax.random.fold_in(k_noise, i), v.shape, jnp.float32)
            out.append(v.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


def run(ctx):
    draw = named(ctx["config"], "weights", 'how its weights are drawn: '
                 '{"draw": "own_init"}')["draw"]
    if draw != "own_init":
        raise ValueError('traffic kind "closed_loop_own_init" runs the draw '
                         f'"own_init"; the configuration states {draw!r}')
    ctx = dict(ctx, traffic=dict(ctx["traffic"], kind="closed_loop"))
    with mock.patch.object(serve, "seeded_params", own_init_params):
        return serve.run(ctx)

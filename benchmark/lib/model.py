"""Build the program's ``TransformerLM`` from a configuration file and
make its weights on the device from the seed, in one jitted call and in
the dtype they are held in (f32 master weights for training, bf16 for
serving)."""

import jax
import jax.numpy as jnp

INIT_STD = 0.02  # GPT-2/StarCoder2 initializer_range (assumed; see configs)


def build_model(cfg, *, attn_impl, compute_dtype=jnp.bfloat16, remat=False):
    from bigdl_tpu.models.transformer_lm import TransformerLM

    return TransformerLM(attn_impl=attn_impl, remat=remat,
                         compute_dtype=compute_dtype, **cfg["model"])


def seeded_params(model, seed, dtype):
    """Weights with the model's own tree structure and the benchmark's
    values: matrices N(0, 0.02), biases N(0, 0.02) (so a dropped bias
    shows against the reference), norm scales 1 + N(0, 0.02)."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            v = INIT_STD * jax.random.normal(jax.random.fold_in(key, i),
                                             leaf.shape, jnp.float32)
            names = [getattr(p, "key", "") for p in path]
            if names[-1] == "weight" and names[-2].startswith("ln"):
                v = 1.0 + v
            out.append(v.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))

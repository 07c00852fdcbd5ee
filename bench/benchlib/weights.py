"""Weights from the seed: one jitted call on the device, in the type they
are held in. Both the program and the plain reference are handed this tree;
neither makes a weight of its own."""

import math


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63 (the driver's seeds pass
    2**31)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2**31)), seed // (2**31))


def leaf_rule(path, shape):
    """(kind, std) for a leaf, by the last name of its flax path: kernels
    1/sqrt(fan_in), embeddings 0.02 (GPT-2's published init), norm scales
    1 + 0.05 n, every bias 0.02 n so that a dropped bias shows."""
    name = path[-1]
    if name == "kernel":
        return "normal", 1.0 / math.sqrt(shape[0])
    if name == "embedding":
        return "normal", 0.02
    if name == "scale":
        return "one_plus", 0.05
    if name == "bias":
        return "normal", 0.02
    raise ValueError(f"bench/benchlib/weights.py has no rule for leaf {'/'.join(path)}")


def make_params(shape_tree, seed: int, dtype):
    """A param tree with the structure and shapes of `shape_tree` (nested
    dicts of ShapeDtypeStruct, as `jax.eval_shape(model.init, ...)` gives),
    every leaf drawn from the seed, made in one jitted call in `dtype`."""
    import jax
    import jax.numpy as jnp
    from flax.traverse_util import flatten_dict, unflatten_dict

    flat = flatten_dict(shape_tree)
    paths = sorted(flat)
    rules = [leaf_rule(p, flat[p].shape) for p in paths]

    @jax.jit
    def build(key):
        out = {}
        for i, (path, (kind, std)) in enumerate(zip(paths, rules)):
            x = jax.random.normal(jax.random.fold_in(key, i), flat[path].shape, jnp.float32) * std
            if kind == "one_plus":
                x = x + 1.0
            out[path] = x.astype(dtype)
        return out

    return unflatten_dict(build(seed_key(seed)))


def param_shapes(model, *init_args):
    import jax

    return jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), *init_args)["params"])

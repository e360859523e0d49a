"""Weights from a seed, made by the benchmark (never by the program): one
jitted call on the device, in the type they are served or trained in. Names
are slash-joined paths of the public checkpoint layout; every reference
declares the shapes it needs in its ``weight_shapes``."""

import jax
import jax.numpy as jnp


def seed_key(seed):
    """A PRNG key for any whole-number seed (more than 32 signed bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def make_weights(shapes, seed, dtype, std=0.02):
    """``{name: array}`` for ``{name: shape}``: normal(0, std) everywhere
    (biases too, so that no gradient is zero by symmetry), and 1 + normal for
    layer-norm scales. The same seed gives the same weights."""
    names = sorted(shapes)

    def make(key):
        out = {}
        for i, name in enumerate(names):
            x = std * jax.random.normal(jax.random.fold_in(key, i),
                                        shapes[name], jnp.float32)
            if name.endswith("/scale"):
                x = 1.0 + x
            out[name] = x.astype(dtype)
        return out

    return jax.jit(make)(seed_key(seed))


def nest(flat):
    """``{"a/b": x}`` -> ``{"a": {"b": x}}``."""
    tree = {}
    for name, x in flat.items():
        node = tree
        parts = name.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = x
    return tree


def flatten(tree, prefix=""):
    """Inverse of ``nest`` (dict order = sorted names, which is also the
    order ``jax.tree_util.tree_leaves`` visits a dict in)."""
    out = {}
    for k in sorted(tree):
        name = f"{prefix}/{k}" if prefix else k
        if isinstance(tree[k], dict):
            out.update(flatten(tree[k], name))
        else:
            out[name] = tree[k]
    return out

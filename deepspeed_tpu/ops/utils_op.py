"""Flatten/unflatten over pytrees.

Capability parity with the reference's ``utils`` op (``csrc/utils/
flatten_unflatten.cpp``: torch's flatten_dense_tensors exposed as a fast op,
used by the engine and ZeRO). Under XLA these are pure data movement that the
compiler fuses/elides, so no native kernel is needed; the API matches so ZeRO
and fp16 code reads like the reference design.
"""

import numpy as np

import jax
import jax.numpy as jnp


def tree_spec(tree):
    """(treedef, shapes, dtypes, sizes) describing a pytree of arrays."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = [l.shape for l in leaves]
    dtypes = [l.dtype for l in leaves]
    sizes = [int(np.prod(s)) if len(s) else 1 for s in shapes]
    return treedef, shapes, dtypes, sizes


def flatten_dense_tensors(tree, dtype=jnp.float32):
    """Concatenate all leaves into one flat 1-D array (jit-safe)."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.zeros((0,), dtype)
    return jnp.concatenate([l.reshape(-1).astype(dtype) for l in leaves])


def unflatten_dense_tensors(flat, treedef, shapes, dtypes):
    """Inverse of flatten: split + reshape back into the pytree (jit-safe).

    Each piece passes an optimization barrier between its 1-D slice and its
    reshape. Without it XLA:TPU commutes the two, and a leaf with a tiny
    minor dimension (BERT's [1024, 2] next-sentence head) re-views the WHOLE
    flat vector as [n/2, 2], whose tiled layout pads the 2 to 128 lanes: a
    64x copy of the vector (43 GB for BERT-large) that fails buffer
    assignment."""
    sizes = [int(np.prod(s)) if len(s) else 1 for s in shapes]
    offsets = np.cumsum([0] + sizes)
    leaves = [
        jax.lax.optimization_barrier(
            jax.lax.slice(flat, (int(offsets[i]),), (int(offsets[i + 1]),))
        ).reshape(shapes[i]).astype(dtypes[i])
        for i in range(len(shapes))
    ]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def tree_map_multi(fn, n_out, tree, *rest):
    """Map ``fn`` (returning an ``n_out``-tuple) over aligned pytrees and
    un-zip the results into ``n_out`` pytrees. Unlike
    ``tree_map(..., is_leaf=lambda x: isinstance(x, tuple))`` picking, this is
    robust to tuples appearing INSIDE the input pytrees (e.g. the compiled
    pipeline's ``(stacked_params, aux_params)``)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    rest_leaves = [jax.tree_util.tree_leaves(r) for r in rest]
    outs = [fn(l, *(rl[i] for rl in rest_leaves)) for i, l in enumerate(leaves)]
    return tuple(
        jax.tree_util.tree_unflatten(treedef, [o[k] for o in outs])
        for k in range(n_out)
    )


def pad_to_multiple(flat, multiple):
    """Zero-pad a flat array so its length divides ``multiple``; returns (padded, orig_len)."""
    n = flat.shape[0]
    padded = int(np.ceil(n / multiple)) * multiple if n else multiple
    if padded != n:
        flat = jnp.concatenate([flat, jnp.zeros((padded - n,), flat.dtype)])
    return flat, n

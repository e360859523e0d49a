"""Sequence/context parallelism: ring attention over the mesh.

The reference's long-sequence story is block-sparse attention + activation
checkpointing (SURVEY §2.2: SP/CP absent in v0.3.10) — but long-context is
first-class here: ring attention shards the SEQUENCE across devices and
rotates key/value chunks around the ring with ``ppermute``, overlapping each
hop with the local attention partial. Memory per device is O(S/W * D) and the
full S x S score matrix never exists anywhere — sequences scale linearly with
the ring size.

``ring_attention`` composes with the fused kernel design: each hop's partial
uses the same online-softmax merge the Pallas kernel uses per block, so the
math is exactly flash attention, distributed.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from deepspeed_tpu.parallel.mesh import DATA_AXIS
from deepspeed_tpu.utils.shard_map_compat import shard_map


def _local_attention_partial(q, k, v, bias, q_offset, k_offset, causal):
    """Partial attention of local q against one k/v chunk: returns
    (m, l, acc) for the online-softmax merge. Shapes: q [B,H,Sq,D],
    k/v [B,H,Sk,D], bias [B, Sk]."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    s = s + bias[:, None, None, :].astype(jnp.float32)
    if causal:
        Sq, Sk = q.shape[2], k.shape[2]
        q_pos = q_offset + jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)
        k_pos = k_offset + jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
        s = jnp.where((q_pos >= k_pos)[None, None], s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)                      # [B,H,Sq,1]
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bhst,bhtd->bhsd", p, v.astype(jnp.float32))
    return m, l, acc


def _merge(carry, part):
    m0, l0, a0 = carry
    m1, l1, a1 = part
    m = jnp.maximum(m0, m1)
    c0 = jnp.exp(m0 - m)
    c1 = jnp.exp(m1 - m)
    return m, l0 * c0 + l1 * c1, a0 * c0 + a1 * c1


def ring_attention_local(q, k, v, bias, axis_name, causal=False):
    """Runs INSIDE shard_map: q,k,v are the local [B,H,S/W,D] sequence shards,
    ``bias`` the local [B, S/W] key bias. Rotates k/v around ``axis_name``.
    """
    W = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    Sc = q.shape[2]
    perm = [(i, (i + 1) % W) for i in range(W)]  # chunks move to the next rank

    m = jnp.full(q.shape[:3] + (1,), -1e30, jnp.float32)
    l = jnp.zeros(q.shape[:3] + (1,), jnp.float32)
    acc = jnp.zeros(q.shape, jnp.float32)
    # carry entries must be device-varying over the ring axis from the
    # start (shard_map vma typing): constants start unvarying.
    m, l, acc = (jax.lax.pcast(t, (axis_name,), to="varying") for t in (m, l, acc))

    def body(step, carry):
        m, l, acc, k_cur, v_cur, b_cur = carry
        # chunk currently held arrived from rank (idx - step) mod W
        src = jax.lax.rem(idx - step + W, W)
        part = _local_attention_partial(
            q, k_cur, v_cur, b_cur, q_offset=idx * Sc, k_offset=src * Sc, causal=causal
        )
        m, l, acc = _merge((m, l, acc), part)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        b_nxt = jax.lax.ppermute(b_cur, axis_name, perm)
        return m, l, acc, k_nxt, v_nxt, b_nxt

    m, l, acc, _, _, _ = jax.lax.fori_loop(0, W, body, (m, l, acc, k, v, bias))
    out = jnp.where(l > 0, acc / jnp.where(l > 0, l, 1.0), 0.0)
    return out.astype(q.dtype)


def ring_attention(q, k, v, mask=None, mesh=None, axis_name=DATA_AXIS, causal=False):
    """Driver: shards [B,H,S,D] inputs along ``axis_name`` over ``mesh`` and
    runs the ring. ``mask``: additive [B,S] (or [B,1,1,S]) key bias."""
    B, H, S, D = q.shape
    if mesh is None:
        import deepspeed_tpu.parallel.mesh as mesh_lib

        mesh = mesh_lib.create_mesh()
    W = mesh.shape[axis_name]
    assert S % W == 0, f"seq len {S} must divide ring size {W}"
    if mask is None:
        bias = jnp.zeros((B, S), jnp.float32)
    elif mask.ndim == 4:
        bias = mask[:, 0, 0, :].astype(jnp.float32)
    else:
        bias = mask.astype(jnp.float32)

    seq = PartitionSpec(None, None, axis_name, None)
    bseq = PartitionSpec(None, axis_name)
    fn = shard_map(
        functools.partial(ring_attention_local, axis_name=axis_name, causal=causal),
        mesh=mesh,
        in_specs=(seq, seq, seq, bseq),
        out_specs=seq,
    )
    return fn(q, k, v, bias)

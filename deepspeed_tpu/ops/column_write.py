"""One new column in each lane's block of a paged array, in place.

A serving pool keeps tokens last (``[..., width, T]``: a page or a ring's
block holds ``T`` tokens, each a column across ``width`` rows), so one
token's key or value is a value in every tile of its block and nothing
finer than the whole block can be written in place (a one-column update made
XLA re-lay a whole pool around it: ``PERF.md`` section 7). A decode step
therefore owes, a lane, one read of its block and one write of it.
``write_columns`` is that and no more:

- on a TPU a Pallas kernel whose grid walks the lanes: a lane's block is
  fetched at the indices given as scalars ahead of the grid, the column is
  replaced in VMEM and the block goes back where it came from
  (``input_output_aliases``: the pool is updated in place and every block no
  lane names is never touched), fetches and write-backs overlapping from
  lane to lane. The new values enter as they lie, ``[B, width]``, whole and
  once a call, and a lane's row becomes its column inside the kernel, on
  the transpose unit (laid out ``[B, width, 1]`` for the kernel they are
  one value a 128-lane row, and that relayout took longer than the write:
  ``PERF.md``, PR 48);
- elsewhere (the CPU suite) the same in plain operations: the lanes' blocks
  gathered, the column chosen by ``where``, one scatter of whole blocks.

Lanes may name one block (inactive lanes all write the spare page 0): which
of their columns it then holds is not said; it never fails.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _on_tpu():
    return jax.default_backend() == "tpu"


def write_columns(pool, at, new, col):
    """``pool [L, ..., width, T]`` with lane ``b``'s block at ``pool[at[0],
    at[1][b], ...]``: ``at`` is a row ``n`` of the leading axis and then one
    index array ``[B]`` for every axis up to the block. ``n`` is a Python
    int (a decoder whose layers are unrolled: the row is part of the
    program's text) or a traced int32 scalar (a decoder walked by a loop
    inside the program, ``models/ouro.py``: the kernel reads the row as one
    more scalar ahead of the grid). ``new [B, width]`` becomes column ``col
    [B]`` of the lane's block; a lane whose ``col`` is -1 leaves its block
    as it was. Returns the pool."""
    new, col = new.astype(pool.dtype), col.astype(jnp.int32)
    if _on_tpu():
        return _write_columns_pallas(pool, at, new, col)
    column = jnp.arange(pool.shape[-1])[None, :] == col[:, None]
    blocks = jnp.where(column[:, None, :], new[:, :, None], pool[at])
    return pool.at[at].set(blocks)


def _write_columns_pallas(pool, at, new, col, interpret=False):
    n, *index = at
    width, T = pool.shape[-2:]
    k = len(index)
    assert pool.ndim == k + 3 and new.shape[1:] == (width,), (
        pool.shape, k, new.shape)
    # a traced row is the first scalar ahead of the grid; a Python int stays
    # in the index map, and the call is what it was without the former
    row = () if isinstance(n, (int, np.integer)) else (
        jnp.asarray(n, jnp.int32).reshape(1),)
    r = len(row)

    def kernel(*refs):
        col_ref, new_ref, old_ref, out_ref = refs[r + k:]
        b = pl.program_id(0)
        # the lane's row, alike on T sublanes, turned: every column of the
        # result is the row, and no other lane's value (a NaN among them)
        # is ever in a register with it
        mine = jnp.broadcast_to(new_ref[pl.ds(b, 1), :], (T, width)).T
        here = jax.lax.broadcasted_iota(jnp.int32, (width, T), 1)
        # float32 in VMEM: a select of 16-bit values is no vector operation
        # on a v5e and a row of a packed 16-bit array cannot be read at a
        # traced index; the round trip is exact
        out_ref[...] = jnp.where(
            here == col_ref[b], mine,
            old_ref[...].astype(jnp.float32)).astype(out_ref.dtype)

    def block_of(b, *scalars):
        return (scalars[0][0] if r else n,
                *(s[b] for s in scalars[r:r + k]), 0, 0)

    block = pl.BlockSpec((None,) * (k + 1) + (width, T), block_of)
    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=r + k + 1, grid=(new.shape[0],),
            in_specs=[pl.BlockSpec(new.shape, lambda b, *_: (0, 0)), block],
            out_specs=block),
        input_output_aliases={r + k + 2: 0}, interpret=interpret,
    )(*row, *(i.astype(jnp.int32) for i in index), col,
      new.astype(jnp.float32), pool)

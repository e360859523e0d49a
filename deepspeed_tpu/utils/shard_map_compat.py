"""The repo's one ``shard_map`` entry point.

A thin adapter over ``jax.shard_map`` that keeps the keyword surface the
call sites were written against: ``check_rep`` (upstream's ``check_vma``) and
an optional ``axis_names`` given as any iterable.
"""

from jax import shard_map as _shard_map


def shard_map(f, mesh, in_specs, out_specs, check_rep=True, axis_names=None):
    # check_rep defaults True like upstream — callers that need it off
    # (pallas_call bodies whose ShapeDtypeStructs carry no vma annotations,
    # custom-vjp pipelines) must say so explicitly.
    # axis_names = the MANUAL axes; any other mesh axis (e.g. a TP ``model``
    # axis) stays automatic and GSPMD handles its collectives.
    return _shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_rep, axis_names=frozenset(axis_names or ()),
    )

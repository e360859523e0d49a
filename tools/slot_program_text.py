"""The slot-state serving programs (two a family: twelve of the six families
whose layers are unrolled, two of the looped one) as lowered for a TPU at
their cells' own shapes: one sha256 a program, of the StableHLO text with
the Mosaic kernels' bodies stripped of source locations. A refactor that
traces the same operations in the same order keeps them all; the control a
move of
shared model code is held to where the CPU's pins
(``tests/unit/test_mimo_v2.py::PARENT_TEXT``) cannot see, because on a TPU
``write_columns``, ``attend_pages``, ``attend_tiles`` and ``attend_pairs``
take their Pallas branches.

    python tools/slot_program_text.py [--out DIR]

Nothing is compiled or run and no array is made (shapes only). On a TPU the
kernels' branches are taken as the cells take them; anywhere else the
``_on_tpu`` switches are forced, which the last line says (``forced``).
Prints one JSON line: ``{"device": ..., "forced": bool, "sha256": {program:
digest}}``; ``--out`` also keeps the texts.
"""

import argparse
import glob
import hashlib
import importlib
import json
import os
import sys
import types

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# a Mosaic kernel's serialized body embeds source lines: one way to strip them
from tests.unit.test_kernels_tpu_lowering import (  # noqa: E402
    _text_without_locations,
)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def cell_programs(cfg):
    """``{name: lowered}`` of a serving configuration's two programs, as
    its cell builds them: the family ``family_for`` picks, its pool as
    ``build`` describes it (under ``eval_shape``: no array is made), the
    weights' shapes of the configuration's reference."""
    from benchmarks.refs import weights as weights_mod
    from deepspeed_tpu.inference.serving import ServingConfig
    from deepspeed_tpu.inference.serving.families.slot_state import (
        RowPrefillFamily)
    from deepspeed_tpu.inference.serving.family import family_for
    from deepspeed_tpu.inference.serving.metrics import ServingMetrics

    adapter = importlib.import_module("benchmarks.models." + cfg["adapter"])
    ref = importlib.import_module("benchmarks.refs." + cfg["reference"])
    serving = cfg["serving"]
    dtype = jnp.dtype(serving["param_dtype"])
    params = weights_mod.nest({k: _sds(v, dtype)
                               for k, v in ref.weight_shapes(cfg).items()})
    model_cfg = adapter.model_config(cfg)
    family = family_for(model_cfg)
    loop = types.SimpleNamespace(
        config=ServingConfig(**{k: serving[k] for k in (
            "max_slots", "max_seq_len", "kv_cache_dtype", "kv_page_tokens",
            "kv_pool_tokens", "prefill_chunk_tokens") if k in serving}),
        max_seq_len=serving["max_seq_len"], metrics=ServingMetrics())
    pools = []

    def build(params):
        built, pool = family.build(loop, params)
        pools.append(pool)
        return built, pool.state

    # the parameters as the programs take them (a family may restack them)
    params, state = jax.eval_shape(build, params)
    pool = pools[0]
    B, mp = pool.max_slots, pool.pages_per_lane
    R, T = ((family.rows, family.row_tokens)
            if isinstance(family, RowPrefillFamily) else (1, family.chunk))
    i32 = jnp.int32
    calls = {
        family.decode_program: (_sds((B,), i32), _sds((B,), i32),
                                _sds((B,), jnp.bool_), _sds((B, mp), i32)),
        family.prefill_program: (_sds((R, T), i32), _sds((R,), i32),
                                 _sds((R,), i32), _sds((R,), i32),
                                 _sds((R, mp), i32)),
    }
    return {program.__name__: program.trace(
                params, state, *args, cfg=model_cfg,
                page_tokens=pool.page_tokens, keep_logits=False,
            ).lower(lowering_platforms=("tpu",))
            for program, args in calls.items()}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", help="directory to keep the texts in")
    args = ap.parse_args()
    forced = jax.default_backend() != "tpu"
    if forced:
        from deepspeed_tpu.ops import (column_write, paged_decode,
                                       paged_prefill)
        for kernels in (column_write, paged_decode, paged_prefill):
            kernels._on_tpu = lambda: True
    digests = {}
    for path in sorted(glob.glob(os.path.join(
            REPO_ROOT, "benchmarks", "configs", "*.json"))):
        with open(path) as f:
            cfg = json.load(f)
        if cfg.get("kind") != "serve" or "model_type" not in cfg:
            continue
        for name, lowered in cell_programs(cfg).items():
            text = _text_without_locations(lowered)
            digests[name] = hashlib.sha256(text.encode()).hexdigest()
            if args.out:
                os.makedirs(args.out, exist_ok=True)
                with open(os.path.join(args.out, name + ".txt"), "w") as f:
                    f.write(text)
    dev = jax.devices()[0]
    print(json.dumps({"device": f"{dev.platform}:{dev.device_kind}",
                      "forced": forced, "sha256": digests}))


if __name__ == "__main__":
    main()

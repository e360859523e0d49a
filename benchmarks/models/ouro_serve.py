"""The system under test for Ouro serving cells, reached through the entry
points a server calls: ``ServingEngine.start()``, ``submit(...,
stream_cb=...)`` and ``stop()``, the same engine, scheduler, page allocator,
metrics and tracer as the other families' cells. This file is the only place
the cell touches the program."""

from benchmarks.models import kimi_linear_serve
from benchmarks.refs import weights as weights_mod
# at import, so that a checkout without the model fails before it makes
# 5.3 GB of weights (importing the package touches no JAX backend)
from deepspeed_tpu.models.ouro import OuroConfig


def model_config(cfg):
    """The program's ``OuroConfig`` for a configuration file: the published
    keys, nothing reduced and no share."""
    return OuroConfig.from_dict(cfg)


class Program(kimi_linear_serve.Program):
    """``start``, ``submit``, ``counters``, ``stop`` and ``close`` are the
    Kimi-Linear adapter's: the engine behind them is the same."""

    def __init__(self, cfg, flat_weights):
        from deepspeed_tpu.inference.serving import (ServingConfig,
                                                     ServingEngine)

        kimi_linear_serve._quiet()
        serving = cfg["serving"]
        self.model_cfg = model_config(cfg)
        params = weights_mod.nest(flat_weights)
        # the family stacks the layers and lets the per-layer leaves go as
        # it does; the flat names must not hold them on the chip meanwhile
        # (the layers twice and the pool are over the chip's memory)
        flat_weights.clear()
        self.engine = ServingEngine(
            params, self.model_cfg, ServingConfig(
                max_slots=serving["max_slots"],
                max_queue=serving["max_queue"],
                max_seq_len=serving["max_seq_len"],
                prompt_buckets=tuple(serving["prompt_buckets"]),
                kv_cache_dtype=serving["kv_cache_dtype"],
                kv_page_tokens=serving["kv_page_tokens"],
                kv_pool_tokens=serving["kv_pool_tokens"],
                prefill_chunk_tokens=serving["prefill_chunk_tokens"]))
        self.max_slots = int(serving["max_slots"])

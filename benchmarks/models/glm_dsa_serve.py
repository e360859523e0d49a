"""The system under test for GLM-5.2 serving cells, reached through the entry
points a server calls: ``ServingEngine.start()``, ``submit(...,
stream_cb=...)`` and ``stop()``, the same engine, scheduler, page allocator,
metrics and tracer as the other families' cells. This file is the only place
the cell touches the program."""

from benchmarks.models import kimi_linear_serve
from benchmarks.refs import weights as weights_mod
# at import, so that a checkout without the model fails before it makes
# 9.4 GB of weights (importing the package touches no JAX backend)
from deepspeed_tpu.models.glm_dsa import GlmDsaConfig


def model_config(cfg):
    """The program's ``GlmDsaConfig`` for a configuration file: the
    published keys, with the share this chip holds (the file's
    ``num_hidden_layers``, ``n_routed_experts`` and ``vocab_size`` count
    what is held; ``share`` gives the published counts and where the share
    starts)."""
    share = cfg.get("share", {})
    held = cfg["n_routed_experts"]
    published = dict(cfg, n_routed_experts=share.get(
        "n_routed_experts_published", held))
    return GlmDsaConfig.from_dict(
        published, experts_held=(share.get("experts_first", 0), held),
        vocab_first=share.get("vocab_first", 0),
        first_layer=share.get("first_layer", 0))


class Program(kimi_linear_serve.Program):
    """``start``, ``submit``, ``counters``, ``stop`` and ``close`` are the
    Kimi-Linear adapter's: the engine behind them is the same."""

    def __init__(self, cfg, flat_weights):
        from deepspeed_tpu.inference.serving import (ServingConfig,
                                                     ServingEngine)

        kimi_linear_serve._quiet()
        serving = cfg["serving"]
        self.model_cfg = model_config(cfg)
        self.engine = ServingEngine(
            weights_mod.nest(flat_weights), self.model_cfg, ServingConfig(
                max_slots=serving["max_slots"],
                max_queue=serving["max_queue"],
                max_seq_len=serving["max_seq_len"],
                prompt_buckets=tuple(serving["prompt_buckets"]),
                kv_cache_dtype=serving["kv_cache_dtype"],
                kv_page_tokens=serving["kv_page_tokens"],
                kv_pool_tokens=serving["kv_pool_tokens"],
                prefill_chunk_tokens=serving["prefill_chunk_tokens"]))
        self.max_slots = int(serving["max_slots"])

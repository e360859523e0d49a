"""The system under test for Kimi-Linear serving cells, reached through the
entry points a server calls: ``ServingEngine.start()``, ``submit(...,
stream_cb=...)`` and ``stop()``, the same engine, scheduler, page allocator,
metrics and tracer as GPT-2's cells. This file is the only place the cell
touches the program."""

from benchmarks.refs import weights as weights_mod
# at import, so that a checkout without the model fails before it makes
# 8.6 GB of weights (importing the package touches no JAX backend)
from deepspeed_tpu.models.kimi_linear import KimiLinearConfig


def model_config(cfg):
    """The program's ``KimiLinearConfig`` for a configuration file: the
    published keys, with the share this chip holds (the file's
    ``num_experts`` and ``vocab_size`` count what is held; ``share`` gives
    the published counts and where the share starts)."""
    share = cfg.get("share", {})
    published = dict(cfg, num_experts=share.get("num_experts_published",
                                                cfg["num_experts"]))
    return KimiLinearConfig.from_dict(
        published,
        experts_held=(share.get("experts_first", 0), cfg["num_experts"]),
        vocab_first=share.get("vocab_first", 0))


class Program:
    def __init__(self, cfg, flat_weights):
        from deepspeed_tpu.inference.serving import (ServingConfig,
                                                     ServingEngine)

        _quiet()
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
                prefill_chunk_tokens=serving["prefill_chunk_tokens"]))
        self.max_slots = int(serving["max_slots"])

    def start(self):
        self.engine.start()

    def submit(self, prompt_ids, max_new_tokens, stream_cb):
        return self.engine.submit(prompt_ids, max_new_tokens=max_new_tokens,
                                  stream_cb=stream_cb)

    def counters(self):
        """The program's own counters (``ServingMetrics.snapshot()``)."""
        return self.engine.metrics.snapshot()

    def stop(self):
        self.engine.stop(timeout_s=30.0)

    def close(self):
        """Free the weights, the latent pool and the state slots on the
        device (the reference runs after this, in the memory they leave)."""
        import jax

        engine, self.engine = self.engine, None
        engine.pool.delete()
        for leaf in jax.tree_util.tree_leaves(engine.params):
            if hasattr(leaf, "delete"):
                leaf.delete()


def _quiet():
    """The program logs at INFO to standard output; keep warnings only."""
    import logging

    logging.getLogger("DeepSpeedTPU").setLevel(logging.WARNING)

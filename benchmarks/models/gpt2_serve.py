"""The system under test for GPT-2 serving cells, reached through the entry
points a server calls: ``ServingEngine.start()``, ``submit(...,
stream_cb=...)`` and ``stop()``. This file is the only place the serving
cells touch the program."""

from benchmarks.refs import weights as weights_mod


class Program:
    def __init__(self, cfg, flat_weights):
        from deepspeed_tpu.inference.serving import (ServingConfig,
                                                     ServingEngine)
        from deepspeed_tpu.models.gpt2 import GPT2Config

        _quiet()
        serving = cfg["serving"]
        self.model_cfg = GPT2Config(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            num_hidden_layers=cfg["num_hidden_layers"],
            num_attention_heads=cfg["num_attention_heads"],
            max_position_embeddings=cfg["max_position_embeddings"])
        self.engine = ServingEngine(
            weights_mod.nest(flat_weights), self.model_cfg, ServingConfig(
                max_slots=serving["max_slots"],
                max_queue=serving["max_queue"],
                max_seq_len=serving["max_seq_len"],
                prompt_buckets=tuple(serving["prompt_buckets"]),
                kv_cache_dtype=serving["kv_cache_dtype"],
                kv_page_tokens=serving["kv_page_tokens"],
                attention_impl=serving["attention_impl"],
                prefix_cache_mb=serving["prefix_cache_mb"],
                speculative_k=serving["speculative_k"],
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
        """Free the weights and the KV pool on the device (the reference
        runs after this, in the memory they leave)."""
        import jax

        engine, self.engine = self.engine, None
        for leaf in jax.tree_util.tree_leaves(
                (engine.params, engine.pool.k, engine.pool.v)):
            if hasattr(leaf, "delete"):
                leaf.delete()


def _quiet():
    """The program logs at INFO to standard output; keep warnings only."""
    import logging

    logging.getLogger("DeepSpeedTPU").setLevel(logging.WARNING)

"""Nemotron-H behind the serving loop (``models/nemotron_h.py``): one chunked
prefill program that takes several prompts a call and one decode program,
over a ``HybridStatePool`` (key and value pages of the attention blocks
beside Mamba-2 state slots). The contract it is called through is
``serving/family.py``; what it shares with the other family over state slots
is ``families/slot_state.py``."""

from functools import partial

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.serving.families.slot_state import (
    RowPrefillFamily,
)
from deepspeed_tpu.inference.serving.kv_pool import HybridStatePool
from deepspeed_tpu.models import nemotron_h as nh


@partial(jax.jit, static_argnames=("cfg", "page_tokens", "keep_logits"),
         donate_argnums=(1,))  # jaxlint: hot
def _nemotron_prefill_chunk_jit(params, state, ids, slots, starts, lens,
                                page_tables, *, cfg, page_tokens,
                                keep_logits):
    state, first, logits = nh.prefill_chunk(
        params, cfg, state, ids, slots, starts, lens, page_tables,
        page_tokens=page_tokens)
    return state, first, logits if keep_logits else None


@partial(jax.jit, static_argnames=("cfg", "page_tokens", "keep_logits"),
         donate_argnums=(1,))  # jaxlint: hot
def _nemotron_decode_step_jit(params, state, tokens, positions, active,
                              page_tables, *, cfg, page_tokens, keep_logits):
    state, tokens, positions, logits, moe = nh.decode_step(
        params, cfg, state, tokens, positions, active, page_tables,
        page_tokens=page_tokens)
    return state, tokens, positions, logits if keep_logits else None, moe


class NemotronHFamily(RowPrefillFamily):
    """Nemotron-H through the shared loop: pages of keys and values (the
    key-value heads side by side in a paged row) and Mamba-2 state slots in
    one ``HybridStatePool``; admission, lane churn and the decode step kept
    in flight as ``SlotStateFamily`` has them, and the prefill call of
    several prompts in rows as ``RowPrefillFamily`` lays it out: a row is
    ``chunk_size`` tokens (the SSD chunk, a whole number of pages)."""

    name = "nemotron_h"
    cached = "keys and values"
    decode_program = staticmethod(_nemotron_decode_step_jit)
    prefill_program = staticmethod(_nemotron_prefill_chunk_jit)

    def row_length(self, page):
        return self.cfg.chunk_size

    def check_options(self, cfg, params):
        page = super().check_options(cfg, params)
        if self.cfg.chunk_size % page:
            self.refuse(f"kv_page_tokens={page}",
                        f"writes a row's keys and values as whole pages: a "
                        f"divisor of chunk_size={self.cfg.chunk_size}")

    def build(self, loop, params):
        self.loop = loop
        m, cfg = self.cfg, loop.config
        dtype = jnp.dtype(params["embed_tokens"]["embedding"].dtype)
        n_mamba, n_attn = len(m.mamba_index), len(m.attn_index)
        pool = HybridStatePool(
            cfg.max_slots, loop.max_seq_len,
            paged={"k": (n_attn, m.kv_width, dtype),
                   "v": (n_attn, m.kv_width, dtype)},
            slotted={"ssm": (n_mamba, (m.mamba_num_heads, m.mamba_head_dim,
                                       m.ssm_state_size), jnp.float32),
                     "conv": (n_mamba, (m.conv_kernel - 1, m.conv_dim),
                              dtype)},
            page_tokens=cfg.kv_page_tokens, pool_tokens=cfg.kv_pool_tokens)
        self.row_tokens = m.chunk_size
        self.rows = int(cfg.prefill_chunk_tokens) // m.chunk_size
        self.paged_attn_layers = n_attn
        loop.metrics.record_state_pool(0, 0, pool.slot_bytes(),
                                       pool.paged_bytes())
        return params, pool

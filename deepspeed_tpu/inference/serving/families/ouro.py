"""Ouro behind the serving loop (``models/ouro.py``): one chunked prefill
program that takes several prompts a call and one decode program, over a
``HybridStatePool`` that holds pages and nothing else. The first family
whose cache rows are not its weight layers: the decoder runs its
``num_hidden_layers`` layers ``total_ut_steps`` times a token with one set
of weights, and a token caches a row of keys and a row of values for every
(pass, layer), ``cache_rows`` of each. No state is a slot's, so admission
resets nothing and the pool has no slot array. The contract it is called
through is ``serving/family.py``; what it shares with the other families
over state slots is ``families/slot_state.py``."""

from functools import partial

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.serving.families.slot_state import (
    RowPrefillFamily,
)
from deepspeed_tpu.inference.serving.kv_pool import HybridStatePool
from deepspeed_tpu.models import ouro as ou


@partial(jax.jit, static_argnames=("cfg", "page_tokens", "keep_logits"),
         donate_argnums=(1,))  # jaxlint: hot
def _ouro_prefill_chunk_jit(params, state, ids, slots, starts, lens,
                            page_tables, *, cfg, page_tokens, keep_logits):
    state, first, logits = ou.prefill_chunk(
        params, cfg, state, ids, slots, starts, lens, page_tables,
        page_tokens=page_tokens)
    return state, first, logits if keep_logits else None


@partial(jax.jit, static_argnames=("cfg", "page_tokens", "keep_logits"),
         donate_argnums=(1,))  # jaxlint: hot
def _ouro_decode_step_jit(params, state, tokens, positions, active,
                          page_tables, *, cfg, page_tokens, keep_logits):
    state, tokens, positions, logits, moe = ou.decode_step(
        params, cfg, state, tokens, positions, active, page_tables,
        page_tokens=page_tokens)
    return state, tokens, positions, logits if keep_logits else None, moe


class OuroFamily(RowPrefillFamily):
    """Ouro through the shared loop. The pool is described from the
    configuration: ``k`` and ``v`` pages of ``cache_rows`` rows (passes x
    layers), ``cache_widths`` wide, claimed from the ``kv_pool_tokens``
    budget, and no slot array (``reset=()``: a page is read behind its
    lane's position). Admission, lane churn and the decode step kept in
    flight are ``SlotStateFamily``'s, the prefill call of several prompts
    in rows ``RowPrefillFamily``'s: a row is one page of tokens.

    The programs take the layers' weights stacked on a leading axis
    (``models/ouro.py::stack_layers``). ``build`` stacks them BEFORE the
    pool exists and consumes the per-layer leaves of the tree it was given
    as it goes: the layers are a third of a chip and cannot be on it twice
    beside the pool, so a caller that wants the chip's memory keeps no
    reference of its own to them."""

    name = "ouro"
    cached = "keys and values"
    decode_program = staticmethod(_ouro_decode_step_jit)
    prefill_program = staticmethod(_ouro_prefill_chunk_jit)

    def build(self, loop, params):
        self.loop = loop
        m, cfg = self.cfg, loop.config
        dtype = jnp.dtype(params["embed_tokens"]["embedding"].dtype)
        params = ou.stack_layers(params)
        pool = HybridStatePool(
            cfg.max_slots, loop.max_seq_len,
            paged={name: (m.cache_rows, width, dtype)
                   for name, width in m.cache_widths.items()},
            slotted={}, page_tokens=cfg.kv_page_tokens,
            pool_tokens=cfg.kv_pool_tokens, reset=())
        self.row_tokens = pool.page_tokens
        self.rows = int(cfg.prefill_chunk_tokens) // pool.page_tokens
        self.paged_attn_layers = m.cache_rows
        loop.metrics.record_state_pool(0, 0, pool.slot_bytes(),
                                       pool.paged_bytes())
        loop.metrics.record_loop_cache(
            m.cache_rows, m.cache_values_per_token * dtype.itemsize)
        return params, pool

    def count_prefill(self, starts, lens):
        self.loop.metrics.record_loop(0, self.cfg.cache_rows)

    def count_attended(self, held):
        """Also what the step attends to and holds, as
        ``PagesAndRingsFamily`` counts them (every one of the ``cache_rows``
        rows reads every position its active lanes hold), and the passes
        the step runs: all of them, the number an exit that depended on the
        gate would lower."""
        super().count_attended(held)
        metrics = self.loop.metrics
        metrics.record_attended(held.sum(), self.loop.pool.pages_in_use)
        metrics.record_loop(self.cfg.total_ut_steps, self.cfg.cache_rows)

"""Keye-VL behind the serving loop (``models/keye.py``): one chunked prefill
program that takes several prompts a call and one decode program, over a
``HybridStatePool`` that holds pages and nothing else: a layer's keys and
values (a token a tile, so that the decode step can fetch the positions its
indexer selected and no others) and the indexer's keys, claimed together
through the one page table from the ``kv_pool_tokens`` budget. No state is a
slot's, so admission resets nothing and the pool has no slot array. The
contract it is called through is ``serving/family.py``; what it shares with
the other families over state slots is ``families/slot_state.py``."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.serving.families.slot_state import (
    RowPrefillFamily,
    count_prefill_blocks,
)
from deepspeed_tpu.inference.serving.kv_pool import HybridStatePool
from deepspeed_tpu.models import keye as ky


@partial(jax.jit, static_argnames=("cfg", "page_tokens", "keep_logits"),
         donate_argnums=(1,))  # jaxlint: hot
def _keye_prefill_chunk_jit(params, state, ids, slots, starts, lens,
                            page_tables, *, cfg, page_tokens, keep_logits):
    state, first, logits = ky.prefill_chunk(
        params, cfg, state, ids, slots, starts, lens, page_tables,
        page_tokens=page_tokens)
    return state, first, logits if keep_logits else None


@partial(jax.jit, static_argnames=("cfg", "page_tokens", "keep_logits"),
         donate_argnums=(1,))  # jaxlint: hot
def _keye_decode_step_jit(params, state, tokens, positions, active,
                          page_tables, *, cfg, page_tokens, keep_logits):
    state, tokens, positions, logits, moe = ky.decode_step(
        params, cfg, state, tokens, positions, active, page_tables,
        page_tokens=page_tokens)
    return state, tokens, positions, logits if keep_logits else None, moe


def count_decode_blocks(metrics, context, *, topk, lanes, table_positions,
                        layers):
    """What a decode step's attention walks of the tiles its selection
    fetched, from the positions the active lanes hold (``context
    [active]``, the new one among them): the blocks each lane's selection
    fills, and the ``K = min(topk, table_positions)`` slots of every one of
    the ``lanes`` the step has, each summed over ``layers``
    (``ServingMetrics.record_decode_blocks``)."""
    K = min(topk, table_positions)
    metrics.record_decode_blocks(
        layers * ky.decode_blocks(np.minimum(context, K)).sum(),
        layers * lanes * ky.decode_blocks(K))


class KeyeFamily(RowPrefillFamily):
    """Keye-VL through the shared loop. The pool is described from the
    configuration's ``cache_widths``: ``kv`` a token's tile of key and
    value heads, ``ik`` the indexer's key, both paged, and no slot array
    (``reset=()``: there is nothing a new occupant could inherit, a page is
    read behind its lane's position). Admission, lane churn and the decode
    step kept in flight are ``SlotStateFamily``'s, the prefill call of
    several prompts in rows ``RowPrefillFamily``'s: a row is one page of
    tokens."""

    name = "keye"
    cached = "keys, values and the indexer's keys"
    decode_program = staticmethod(_keye_decode_step_jit)
    prefill_program = staticmethod(_keye_prefill_chunk_jit)

    def build(self, loop, params):
        self.loop = loop
        m, cfg = self.cfg, loop.config
        dtype = jnp.dtype(params["embed_tokens"]["embedding"].dtype)
        pool = HybridStatePool(
            cfg.max_slots, loop.max_seq_len,
            paged={name: (m.num_hidden_layers, what, dtype)
                   for name, what in m.cache_widths.items()},
            slotted={}, page_tokens=cfg.kv_page_tokens,
            pool_tokens=cfg.kv_pool_tokens, reset=())
        self.row_tokens = pool.page_tokens
        self.rows = int(cfg.prefill_chunk_tokens) // pool.page_tokens
        loop.metrics.record_state_pool(0, 0, pool.slot_bytes(),
                                       pool.paged_bytes())
        return params, pool

    def count_prefill(self, starts, lens):
        count_prefill_blocks(self.loop.metrics, starts, lens,
                             page_tokens=self.row_tokens,
                             layers=self.cfg.num_hidden_layers)

    def count_attended(self, held):
        """What the step's indexers score and what its attention then
        reads, for the roofline's readers: a layer scores every position an
        active lane holds (its own new one too) and attends to ``topk`` of
        them at most. The work-list counters of ``RowPrefillFamily`` are
        not counted: no layer here walks a lane's key blocks."""
        metrics, m = self.loop.metrics, self.cfg
        context = np.asarray(held, np.int64) + 1
        metrics.record_attended(held.sum(), self.loop.pool.pages_in_use)
        metrics.record_selected(
            m.num_hidden_layers * context.sum(),
            m.num_hidden_layers * np.minimum(context, m.topk).sum())
        pool = self.loop.pool
        count_decode_blocks(metrics, context, topk=m.topk,
                            lanes=pool.max_slots, table_positions=(
                                pool.pages_per_lane * pool.page_tokens),
                            layers=m.num_hidden_layers)

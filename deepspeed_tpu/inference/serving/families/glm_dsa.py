"""GLM-5.2 behind the serving loop (``models/glm_dsa.py``): one chunked
prefill program that takes several prompts a call and one decode program,
over a ``HybridStatePool`` that holds pages and nothing else: every layer's
latent rows (a token a row of its own, so that the decode step can fetch the
positions a selection names and no others) and, for the layers that run the
indexer and for no others, the indexer's keys; both claimed together through
the one page table from the ``kv_pool_tokens`` budget. No state is a slot's,
so admission resets nothing and the pool has no slot array. The contract it
is called through is ``serving/family.py``; what it shares with the other
families over state slots is ``families/slot_state.py``."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.serving.families.slot_state import (
    RowPrefillFamily,
    count_prefill_blocks,
)
from deepspeed_tpu.inference.serving.kv_pool import HybridStatePool
from deepspeed_tpu.models import glm_dsa as gd


@partial(jax.jit, static_argnames=("cfg", "page_tokens", "keep_logits"),
         donate_argnums=(1,))  # jaxlint: hot
def _glm_prefill_chunk_jit(params, state, ids, slots, starts, lens,
                           page_tables, *, cfg, page_tokens, keep_logits):
    state, first, logits = gd.prefill_chunk(
        params, cfg, state, ids, slots, starts, lens, page_tables,
        page_tokens=page_tokens)
    return state, first, logits if keep_logits else None


@partial(jax.jit, static_argnames=("cfg", "page_tokens", "keep_logits"),
         donate_argnums=(1,))  # jaxlint: hot
def _glm_decode_step_jit(params, state, tokens, positions, active,
                         page_tables, *, cfg, page_tokens, keep_logits):
    state, tokens, positions, logits, moe = gd.decode_step(
        params, cfg, state, tokens, positions, active, page_tables,
        page_tokens=page_tokens)
    return state, tokens, positions, logits if keep_logits else None, moe


class GlmDsaFamily(RowPrefillFamily):
    """GLM-5.2 through the shared loop. The pool is described from the
    configuration's ``cache_arrays``: ``latent``, a row a layer, a token's
    576 values as a row of its own; ``ik``, the indexer's key, a row for
    each layer that selects and none for a layer that attends under another
    layer's selection; both paged, and no slot array (``reset=()``: there is
    nothing a new occupant could inherit, a page is read behind its lane's
    position). Admission, lane churn and the decode step kept in flight are
    ``SlotStateFamily``'s, the prefill call of several prompts in rows
    ``RowPrefillFamily``'s: a row is one page of tokens."""

    name = "glm_dsa"
    cached = "latent rows and the indexer's keys"
    decode_program = staticmethod(_glm_decode_step_jit)
    prefill_program = staticmethod(_glm_prefill_chunk_jit)

    def check_options(self, cfg, params):
        page = super().check_options(cfg, params)
        gd.check_params(params, self.cfg)
        return page

    def build(self, loop, params):
        self.loop = loop
        m, cfg = self.cfg, loop.config
        dtype = jnp.dtype(params["embed_tokens"]["embedding"].dtype)
        pool = HybridStatePool(
            cfg.max_slots, loop.max_seq_len,
            paged={name: (rows, what, dtype)
                   for name, (rows, what) in m.cache_arrays.items()},
            slotted={}, page_tokens=cfg.kv_page_tokens,
            pool_tokens=cfg.kv_pool_tokens, reset=())
        self.row_tokens = pool.page_tokens
        self.rows = int(cfg.prefill_chunk_tokens) // pool.page_tokens
        self.selecting = len(m.indexer_index)   # layers that run an indexer
        loop.metrics.record_state_pool(0, 0, pool.slot_bytes(),
                                       pool.paged_bytes())
        return params, pool

    def count_prefill(self, starts, lens):
        """The key blocks the call's walks of latent pages reach, every
        layer's: the layers that select and those that attend under another
        layer's selection walk alike."""
        count_prefill_blocks(self.loop.metrics, starts, lens,
                             page_tokens=self.row_tokens,
                             layers=self.cfg.num_hidden_layers)

    def count_attended(self, held):
        """What the step's indexers score and what its attention then
        reads, for the roofline's readers: a layer that selects scores every
        position an active lane holds (its own new one too); every layer
        attends ``index_topk`` of them at most, the layers without an
        indexer under a selection that another layer computed. The
        work-list counters of ``RowPrefillFamily`` are not counted: no layer
        here walks a lane's key blocks."""
        metrics, m = self.loop.metrics, self.cfg
        context = np.asarray(held, np.int64) + 1
        metrics.record_attended(held.sum(), self.loop.pool.pages_in_use)
        attended = np.minimum(context, m.index_topk).sum()
        metrics.record_selected(
            self.selecting * context.sum(), m.num_hidden_layers * attended,
            (m.num_hidden_layers - self.selecting) * attended)

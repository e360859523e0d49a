"""Laguna behind the serving loop (``models/laguna.py``): one chunked prefill
program that takes several prompts a call and one decode program, over a
``HybridStatePool`` that holds state of two lifetimes: the full-attention
layers' keys and values in pages, which a request claims for its own span
from the ``kv_pool_tokens`` budget, and the window layers' keys and values
in a ring a lane, which is the lane's whatever its occupant. The contract it
is called through is ``serving/family.py``; what it shares with the other
families over state slots is ``families/slot_state.py``."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.generation import (
    DEFAULT_PAGE_TOKENS,
    resolve_page_tokens,
)
from deepspeed_tpu.inference.serving.families.slot_state import (
    RowPrefillFamily,
)
from deepspeed_tpu.inference.serving.kv_pool import HybridStatePool
from deepspeed_tpu.models import laguna as lg


@partial(jax.jit, static_argnames=("cfg", "page_tokens", "keep_logits"),
         donate_argnums=(1,))  # jaxlint: hot
def _laguna_prefill_chunk_jit(params, state, ids, slots, starts, lens,
                              page_tables, *, cfg, page_tokens, keep_logits):
    state, first, logits = lg.prefill_chunk(
        params, cfg, state, ids, slots, starts, lens, page_tables,
        page_tokens=page_tokens)
    return state, first, logits if keep_logits else None


@partial(jax.jit, static_argnames=("cfg", "page_tokens", "keep_logits"),
         donate_argnums=(1,))  # jaxlint: hot
def _laguna_decode_step_jit(params, state, tokens, positions, active,
                            page_tables, *, cfg, page_tokens, keep_logits):
    state, tokens, positions, logits, moe = lg.decode_step(
        params, cfg, state, tokens, positions, active, page_tables,
        page_tokens=page_tokens)
    return state, tokens, positions, logits if keep_logits else None, moe


class LagunaFamily(RowPrefillFamily):
    """Laguna through the shared loop. A full-attention layer's keys and
    values live in pages (the key-value heads side by side in a paged row);
    a window layer's in a ring of ``sliding_window`` positions a lane, among
    the pool's slot arrays. The pool is described from the configuration's
    ``cache_widths``, a width a name (``k``, ``v``, ``wk``, ``wv``: Laguna's
    four are one number; ``families/mimo_v2.py`` runs this class over four
    that differ). A ring is read behind a position mask, which
    hides whatever a previous occupant left, so admission zeroes nothing
    (``reset=()``). Admission, lane churn and the decode step kept in flight
    are ``SlotStateFamily``'s, the prefill call of several prompts in rows
    ``RowPrefillFamily``'s: a row is one page of tokens."""

    name = "laguna"
    cached = "keys and values"
    decode_program = staticmethod(_laguna_decode_step_jit)
    prefill_program = staticmethod(_laguna_prefill_chunk_jit)

    def check_options(self, cfg, params):
        page = super().check_options(cfg, params)
        if cfg.prefill_chunk_tokens < page or cfg.prefill_chunk_tokens % page:
            self.refuse(f"prefill_chunk_tokens={cfg.prefill_chunk_tokens}",
                        f"prefills in rows of one page: a positive multiple "
                        f"of kv_page_tokens={page}")
        if self.cfg.sliding_window % page:
            self.refuse(f"kv_page_tokens={page}",
                        f"writes a row into a window layer's ring with one "
                        f"update: a divisor of "
                        f"sliding_window={self.cfg.sliding_window}")

    def build(self, loop, params):
        self.loop = loop
        m, cfg = self.cfg, loop.config
        dtype = jnp.dtype(params["embed_tokens"]["embedding"].dtype)
        n_full, n_window = len(m.full_index), len(m.window_index)
        # a ring in blocks of one page, laid out as pages are (tokens last)
        page = resolve_page_tokens(cfg.kv_page_tokens or DEFAULT_PAGE_TOKENS,
                                   loop.max_seq_len)
        widths = m.cache_widths
        pool = HybridStatePool(
            cfg.max_slots, loop.max_seq_len,
            paged={name: (n_full, widths[name], dtype)
                   for name in ("k", "v")},
            slotted={name: (n_window, (m.sliding_window // page,
                                       widths[name], page), dtype)
                     for name in ("wk", "wv")},
            page_tokens=cfg.kv_page_tokens, pool_tokens=cfg.kv_pool_tokens,
            reset=())
        assert pool.page_tokens == page, (pool.page_tokens, page)
        self.row_tokens = page
        self.rows = int(cfg.prefill_chunk_tokens) // page
        self.paged_attn_layers = n_full
        self.ring_layers = n_window
        loop.metrics.record_state_pool(0, 0, pool.slot_bytes(),
                                       pool.paged_bytes())
        return params, pool

    def count_attended(self, held):
        """Also what the step attends to and holds, for the roofline's and
        the pool's readers: a full layer reads every position its active
        lanes hold, a window layer what of its ring is behind the mask."""
        super().count_attended(held)
        metrics = self.loop.metrics
        metrics.record_attended(held.sum(), self.loop.pool.pages_in_use)
        metrics.record_ring_positions(
            self.ring_layers
            * np.minimum(held + 1, self.cfg.sliding_window).sum())

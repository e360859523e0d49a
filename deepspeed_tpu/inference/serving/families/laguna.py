"""Laguna behind the serving loop (``models/laguna.py``): one chunked prefill
program that takes several prompts a call and one decode program, over a
``HybridStatePool`` of pages for the full-attention layers and a ring a lane
for the window layers. The contract it is called through is
``serving/family.py``; everything but its name and its two programs it
shares with the other families over state slots, in
``families/slot_state.py`` (``PagesAndRingsFamily``)."""

from functools import partial

import jax

from deepspeed_tpu.inference.serving.families.slot_state import (
    PagesAndRingsFamily,
)
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


class LagunaFamily(PagesAndRingsFamily):
    """Laguna through the shared loop: the pool of pages and rings, the
    prefill call in rows of one page, admission, lane churn and the decode
    step kept in flight are ``families/slot_state.py``'s; the four arrays of
    the pool are one width here (``LagunaConfig.cache_widths``)."""

    name = "laguna"
    decode_program = staticmethod(_laguna_decode_step_jit)
    prefill_program = staticmethod(_laguna_prefill_chunk_jit)

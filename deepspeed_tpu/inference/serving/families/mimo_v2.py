"""MiMo-V2 behind the serving loop (``models/mimo_v2.py``): one chunked
prefill program that takes several prompts a call and one decode program,
over a ``HybridStatePool`` of pages for the full-attention layers and a ring
a lane for the window layers (``families/slot_state.py``'s
``PagesAndRingsFamily``, which Laguna's family is too). What is this
model's is in the configuration, which the pool is described from
(``cache_widths``: keys of 192 and values of 128 a head, 4 key-value heads
in a full layer and 8 in a window layer give ``k``, ``v``, ``wk`` and ``wv``
four widths), and in the programs (a sink in the window layers' softmax, a
value scale, a third of a head rotated, a sixteenth of the experts held)."""

from functools import partial

import jax

from deepspeed_tpu.inference.serving.families.slot_state import (
    PagesAndRingsFamily,
)
from deepspeed_tpu.models import mimo_v2 as mm


@partial(jax.jit, static_argnames=("cfg", "page_tokens", "keep_logits"),
         donate_argnums=(1,))  # jaxlint: hot
def _mimo_prefill_chunk_jit(params, state, ids, slots, starts, lens,
                            page_tables, *, cfg, page_tokens, keep_logits):
    state, first, logits = mm.prefill_chunk(
        params, cfg, state, ids, slots, starts, lens, page_tables,
        page_tokens=page_tokens)
    return state, first, logits if keep_logits else None


@partial(jax.jit, static_argnames=("cfg", "page_tokens", "keep_logits"),
         donate_argnums=(1,))  # jaxlint: hot
def _mimo_decode_step_jit(params, state, tokens, positions, active,
                          page_tables, *, cfg, page_tokens, keep_logits):
    state, tokens, positions, logits, moe = mm.decode_step(
        params, cfg, state, tokens, positions, active, page_tables,
        page_tokens=page_tokens)
    return state, tokens, positions, logits if keep_logits else None, moe


class MiMoV2Family(PagesAndRingsFamily):
    """MiMo-V2 through the shared loop: the pool of pages and rings, the
    prefill rows and admission of ``families/slot_state.py``, with a window
    of one page (a ring of one block) as published."""

    name = "mimo_v2"
    decode_program = staticmethod(_mimo_decode_step_jit)
    prefill_program = staticmethod(_mimo_prefill_chunk_jit)

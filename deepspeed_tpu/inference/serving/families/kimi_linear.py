"""Kimi-Linear behind the serving loop (``models/kimi_linear.py``): one
chunked prefill program for every prompt length and one decode program, over
a ``HybridStatePool`` (latent pages beside recurrent state slots). The
contract it is called through is ``serving/family.py``; what it shares with
the other family over state slots is ``families/slot_state.py``."""

import time
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.serving.families.slot_state import (
    SlotStateFamily,
)
from deepspeed_tpu.inference.serving.kv_pool import HybridStatePool
from deepspeed_tpu.models import kimi_linear as kl


@partial(jax.jit, static_argnames=("cfg", "page_tokens", "keep_logits"),
         donate_argnums=(1,))  # jaxlint: hot
def _kimi_prefill_chunk_jit(params, state, ids, slots, starts, lens,
                            page_tables, *, cfg, page_tokens, keep_logits):
    state, first, logits = kl.prefill_chunk(
        params, cfg, state, ids, slots, starts, lens, page_tables,
        page_tokens=page_tokens)
    return state, first, logits if keep_logits else None


@partial(jax.jit, static_argnames=("cfg", "page_tokens", "keep_logits"),
         donate_argnums=(1,))  # jaxlint: hot
def _kimi_decode_step_jit(params, state, tokens, positions, active,
                          page_tables, *, cfg, page_tokens, keep_logits):
    state, tokens, positions, logits, moe = kl.decode_step(
        params, cfg, state, tokens, positions, active, page_tables,
        page_tokens=page_tokens)
    return state, tokens, positions, logits if keep_logits else None, moe


class KimiLinearFamily(SlotStateFamily):
    """Kimi-Linear through the shared loop: latent pages and KDA state
    slots in one ``HybridStatePool``; one prefill program of one prompt's
    next ``prefill_chunk_tokens`` tokens, which carries state from chunk to
    chunk (so every prompt length runs it and a prompt is padded by less
    than one chunk); admission, lane churn and the decode step kept in
    flight as ``SlotStateFamily`` has them."""

    name = "kimi_linear"
    cached = "latent rows"
    decode_program = staticmethod(_kimi_decode_step_jit)
    prefill_program = staticmethod(_kimi_prefill_chunk_jit)

    def check_options(self, cfg, params):
        page = super().check_options(cfg, params)
        if (cfg.prefill_chunk_tokens < kl.KDA_CHUNK
                or cfg.prefill_chunk_tokens % kl.KDA_CHUNK):
            self.refuse(f"prefill_chunk_tokens={cfg.prefill_chunk_tokens}",
                        f"prefills in chunks only: a positive multiple of "
                        f"{kl.KDA_CHUNK} tokens")
        if cfg.prefill_chunk_tokens % page:
            self.refuse(f"prefill_chunk_tokens={cfg.prefill_chunk_tokens}",
                        f"writes a chunk's latent rows as whole pages: a "
                        f"multiple of kv_page_tokens={page}")

    def build(self, loop, params):
        self.loop = loop
        m, cfg = self.cfg, loop.config
        dtype = jnp.dtype(params["embed_tokens"]["embedding"].dtype)
        n_kda, n_mla = len(m.kda_index), len(m.mla_index)
        H, D = m.linear_num_heads, m.linear_head_dim
        pool = HybridStatePool(
            cfg.max_slots, loop.max_seq_len,
            paged={"latent": (n_mla, m.latent_width, dtype)},
            slotted={"kda": (n_kda, (H, D, D), jnp.float32),
                     "conv": (n_kda, (m.short_conv_kernel_size - 1,
                                      3 * m.kda_width), dtype)},
            page_tokens=cfg.kv_page_tokens, pool_tokens=cfg.kv_pool_tokens)
        self.chunk = int(cfg.prefill_chunk_tokens)
        loop.metrics.record_state_pool(0, 0, pool.slot_bytes(),
                                       pool.paged_bytes())
        return params, pool

    def advance_prefill(self, stats, now):
        """One call of the chunked prefill program: the next chunk of the
        request that has waited longest. A request whose prompt ends in
        this chunk takes its first token and joins the decode lanes."""
        if not self._prefilling:
            return now
        top = now
        loop = self.loop
        pool = loop.pool
        self.expire_prefilling(stats, now)
        if not self._prefilling:
            return now
        st = self._prefilling[0]
        req, Tc = st.req, self.chunk
        part = req.prompt[st.pos:st.pos + Tc]
        ids = np.zeros((1, Tc), np.int32)
        ids[0, :len(part)] = part
        ends = st.pos + Tc >= len(req.prompt)
        cspan = (loop.tracer.span(
                     "serving/prefill_chunk", cat="serving",
                     args={"request_ids": [req.id], "tokens": len(part)})
                 if loop.tracer.enabled else telemetry.NULL_SPAN)
        t0 = time.monotonic()
        if st.pos == 0:
            loop.metrics.record_queue_wait(t0 - req.submit_time)
        with cspan:
            loop.launched("prefill")
            pool.state, first, self.last_prefill_logits = (
                self.prefill_program(
                    loop.params, pool.state, *jax.device_put(
                        (ids, np.array([st.slot], np.int32),
                         np.array([st.pos], np.int32),
                         np.array([len(part)], np.int32),
                         pool.page_tables[st.slot][None])),
                    cfg=self.cfg, page_tokens=pool.page_tokens,
                    keep_logits=self.keep_logits))
            if self.prefill_sentinel is not None:
                self.prefill_sentinel.check()
            # the one read-back of a chunk, and only of a chunk that ends
            # a prompt: the first token is the TTFT endpoint
            first_host = (int(loop.read_back(first, "prefill", newest=True)[0])
                          if ends else None)
        now = time.monotonic()
        loop.prefill_ran()
        stats["prefill_chunks"] += 1
        loop.metrics.record_prefill_chunk()
        st.pos += len(part)
        st.prefill_s += now - t0
        st.positions_run += Tc
        if ends:
            self._prefilling.remove(st)
            loop.metrics.record_prefill(
                tokens=len(req.prompt), reused_tokens=0, requests=1,
                prefill_s=st.prefill_s, positions_run=st.positions_run)
            pool.positions[st.slot] = len(req.prompt)
            stats["retired"] += loop.first_token(req, st.slot, first_host,
                                                 now)
        loop.metrics.admit_time_s += now - top
        return now

"""Nemotron-H behind the serving loop (``models/nemotron_h.py``): one chunked
prefill program that takes several prompts a call and one decode program,
over a ``HybridStatePool`` (key and value pages of the attention blocks
beside Mamba-2 state slots). The contract it is called through is
``serving/family.py``; what it shares with the other family over state slots
is ``families/slot_state.py``."""

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
from deepspeed_tpu.models import nemotron_h as nh

# Decode steps a prompt may wait for a prefill call's rows to fill. The
# program's shape is fixed, so a call costs the same with one row in use or
# all of them (it reads every expert either way), and every lane waits for
# it; a held prompt costs its own lane a token a step.
PREFILL_HOLD_STEPS = 16


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


class NemotronHFamily(SlotStateFamily):
    """Nemotron-H through the shared loop: pages of keys and values (the
    key-value heads side by side in a paged row) and Mamba-2 state slots in
    one ``HybridStatePool``; admission, lane churn and the decode step kept
    in flight as ``SlotStateFamily`` has them.

    A prefill call runs ``prefill_chunk_tokens`` positions as rows of
    ``chunk_size`` tokens (the SSD chunk, a whole number of pages). The
    prompts being read take rows in the order they were admitted, each as
    many as its remaining tokens need while rows are left, so a call holds
    several prompts, a long prompt advances by several rows in one call and
    no prompt is padded by more than a row. A call is held back, for at most
    ``PREFILL_HOLD_STEPS`` steps and only while lanes decode, until the
    prompts waiting fill its rows."""

    name = "nemotron_h"
    cached = "keys and values"
    decode_program = staticmethod(_nemotron_decode_step_jit)
    prefill_program = staticmethod(_nemotron_prefill_chunk_jit)

    def __init__(self, model_config):
        super().__init__(model_config)
        self._held = 0              # steps the waiting prompts were held

    def check_options(self, cfg, params):
        page = super().check_options(cfg, params)
        row = self.cfg.chunk_size
        if cfg.prefill_chunk_tokens < row or cfg.prefill_chunk_tokens % row:
            self.refuse(f"prefill_chunk_tokens={cfg.prefill_chunk_tokens}",
                        f"prefills in rows of chunk_size tokens only: a "
                        f"positive multiple of {row}")
        if row % page:
            self.refuse(f"kv_page_tokens={page}",
                        f"writes a row's keys and values as whole pages: a "
                        f"divisor of chunk_size={row}")

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
        self.rows = int(cfg.prefill_chunk_tokens) // m.chunk_size
        loop.metrics.record_state_pool(0, 0, pool.slot_bytes(),
                                       pool.paged_bytes())
        return params, pool

    # -- prefill ---------------------------------------------------------
    def _rows_waiting(self):
        T = self.cfg.chunk_size
        return sum(-(-(len(st.req.prompt) - st.pos) // T)
                   for st in self._prefilling)

    def advance_prefill(self, stats, now):
        """One call of the chunked prefill program over the next rows of
        the prompts being read, longest-waiting first. A request whose
        prompt ends in this call takes its first token and joins the decode
        lanes."""
        if not self._prefilling:
            return now
        top = now
        loop = self.loop
        pool = loop.pool
        self.expire_prefilling(stats, now)
        if not self._prefilling:
            return now
        R, T = self.rows, self.cfg.chunk_size
        if (self._rows_waiting() < R and loop.lanes.requests
                and self._held < PREFILL_HOLD_STEPS):
            self._held += 1
            return now
        self._held = 0
        ids = np.zeros((R, T), np.int32)
        slots = np.full(R, pool.max_slots, np.int32)    # no slot: no write
        starts = np.zeros(R, np.int32)
        lens = np.zeros(R, np.int32)
        tables = np.zeros((R, pool.page_tables.shape[1]), np.int32)
        riders = []                 # (request's state, tokens, last row)
        r = 0
        for st in self._prefilling:
            if r == R:
                break
            part = st.req.prompt[st.pos:st.pos + (R - r) * T]
            n = -(-len(part) // T)
            flat = np.zeros(n * T, np.int32)
            flat[:len(part)] = part
            ids[r:r + n] = flat.reshape(n, T)
            slots[r:r + n] = st.slot
            starts[r:r + n] = st.pos + T * np.arange(n)
            lens[r:r + n] = np.minimum(T, len(part) - T * np.arange(n))
            tables[r:r + n] = pool.page_tables[st.slot]
            r += n
            riders.append((st, len(part), r - 1))
        ends = [st.pos + took >= len(st.req.prompt) for st, took, _ in riders]
        cspan = (loop.tracer.span(
                     "serving/prefill_chunk", cat="serving",
                     args={"request_ids": [st.req.id for st, _, _ in riders],
                           "rows": r,
                           "tokens": sum(took for _, took, _ in riders)})
                 if loop.tracer.enabled else telemetry.NULL_SPAN)
        t0 = time.monotonic()
        for st, _, _ in riders:
            if st.pos == 0:
                loop.metrics.record_queue_wait(t0 - st.req.submit_time)
        with cspan:
            pool.state, first, self.last_prefill_logits = (
                self.prefill_program(
                    loop.params, pool.state,
                    *jax.device_put((ids, slots, starts, lens, tables)),
                    cfg=self.cfg, page_tokens=pool.page_tokens,
                    keep_logits=self.keep_logits))
            if self.prefill_sentinel is not None:
                self.prefill_sentinel.check()
            # the one read-back of a call, and only of a call that ends a
            # prompt: the first tokens are the TTFT endpoints
            first_host = np.asarray(first) if any(ends) else None
        now = time.monotonic()
        loop.prefill_ran()
        stats["prefill_chunks"] += 1
        loop.metrics.record_prefill_chunk(rows=r, empty_positions=(R - r) * T)
        for (st, took, last_row), ended in zip(riders, ends):
            st.positions_run += -(-took // T) * T
            st.pos += took
            st.prefill_s += now - t0
            if not ended:
                continue
            self._prefilling.remove(st)
            loop.metrics.record_prefill(
                tokens=len(st.req.prompt), reused_tokens=0, requests=1,
                prefill_s=st.prefill_s, positions_run=st.positions_run)
            pool.positions[st.slot] = len(st.req.prompt)
            stats["retired"] += loop.first_token(
                st.req, st.slot, int(first_host[last_row]), now)
        loop.metrics.admit_time_s += now - top
        return now

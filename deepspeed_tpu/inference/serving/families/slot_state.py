"""What the families whose lanes hold a slot's state have letter for letter
in common (``families/kimi_linear.py``, ``families/nemotron_h.py``,
``families/laguna.py``, ``families/mimo_v2.py``, ``families/keye.py``,
``families/ouro.py``, ``families/glm_dsa.py``): a request holds a lane while
its prompt is read, admission claims the lane's slot and pages of a
``HybridStatePool`` (and zeroes what the pool says a new occupant must not
inherit), lane churn patches the device's lane vectors, one decode step is kept
in flight, and the options none of them can honour. ``RowPrefillFamily`` adds
the prefill call that six of them lay out alike: several prompts a call, in
rows.
``PagesAndRingsFamily`` adds the pool of two of them: pages for the full
layers, a ring a lane for the window layers. What differs stays with the
family: the state's description and the jitted programs. A family's file
imports this one and none of its siblings."""

import time

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.generation import (
    DEFAULT_PAGE_TOKENS,
    resolve_page_tokens,
)
from deepspeed_tpu.inference.serving.family import (
    ServingFamily,
    UnsupportedOptionError,
)
from deepspeed_tpu.inference.serving.kv_pool import (
    HybridStatePool,
    PoolExhaustedError,
)
from deepspeed_tpu.models.paged_layers import (
    decode_key_span,
    prefill_key_span,
)


@jax.jit  # jaxlint: hot
def _patch_lanes_jit(tokens, positions, joined, new_tokens, new_positions):
    """Lane churn: the lanes that ``joined`` take the host's token and
    position; every other lane keeps what the device has, which is a step
    ahead of the host while a decode step is in flight."""
    return (jnp.where(joined, new_tokens, tokens),
            jnp.where(joined, new_positions, positions))


def count_prefill_blocks(metrics, starts, lens, *, page_tokens, layers):
    """What a prefill call's attention walks under a selection, from the
    rows the host laid out (``starts [R]``, ``lens [R]``, 0 an empty row):
    the key blocks each row's own prompt reaches, and every row to the
    longest one's end, each summed over the ``layers`` that attend
    (``ServingMetrics.record_prefill_blocks``)."""
    span = prefill_key_span(page_tokens)
    blocks = np.where(lens > 0, -(-(starts.astype(np.int64) + lens) // span),
                      0)
    metrics.record_prefill_blocks(layers * blocks.sum(),
                                  layers * len(blocks) * blocks.max())


class Prefilling:
    """A request that holds a lane while its prompt is read in chunks."""

    __slots__ = ("req", "slot", "pos", "prefill_s", "positions_run")

    def __init__(self, req, slot):
        self.req = req
        self.slot = slot
        self.pos = 0
        self.prefill_s = 0.0
        self.positions_run = 0


class SlotStateFamily(ServingFamily):
    """A family over a ``HybridStatePool``: one chunked prefill program for
    every prompt length and one decode program that returns, beside the
    tokens, three integers of the expert layers' load, read in the same
    transfer. A subclass names its two programs (``decode_program``,
    ``prefill_program``: jitted, with ``cfg``, ``page_tokens`` and
    ``keep_logits`` static), what it caches a token (``cached``, for the
    refusals' wording), builds the pool and lays out its prefill calls.

    One decode step is kept in flight: a call dispatches step N and then
    reads back step N - 1, which finished while the host emitted N - 2, so
    the device does not wait for the host between steps. (Read back at
    once, a twelfth of every step is dispatch and read-back latency on a
    shared host, and six runs of the Kimi-Linear cell spread by 0.65% of
    their rate where 0.5% admits a cell: PERF.md, PR 27.) The device's
    lane vectors are therefore the truth for lanes that go on; lane churn
    patches only the lanes that joined. A lane that retires on token N - 1
    has already been given step N: its row of N is never emitted, its
    writes go to pages and a slot that are its own until a later program (a
    reset, a prefill: the device runs them in order) makes them someone
    else's.

    ``keep_logits`` (tests set it before the first step) makes both programs
    hand back the logits their token was taken from, in ``last_logits`` and
    ``last_prefill_logits``; otherwise they are never materialised."""

    decode_program = None
    prefill_program = None
    cached = None

    def __init__(self, model_config):
        self.cfg = model_config
        self.keep_logits = False
        self.last_logits = None
        self.last_prefill_logits = None
        self._prefilling = []       # requests that hold a lane, in order
        self._in_flight = None      # (tokens, moe counts, request ids) of N
        self._on_device = {}        # slot -> request id the device decodes

    def refuse(self, option, why):
        raise UnsupportedOptionError(
            f"serving.{option}: the {self.name} family {why}")

    def check_options(self, cfg, params):
        """The refusals every such family shares; returns the page size the
        pool will have, for the family's own checks of its chunk."""
        no = self.refuse
        if cfg.prefix_cache_mb > 0:
            no(f"prefix_cache_mb={cfg.prefix_cache_mb}",
               "has no snapshot of a slot's state to seed a prefix from")
        if cfg.prefix_spill_mb > 0 or cfg.prefix_spill_dir is not None:
            no("prefix_spill_mb/prefix_spill_dir",
               "has no spill codec (the codecs frame keys and values)")
        if cfg.speculative_k:
            no(f"speculative_k={cfg.speculative_k}",
               "cannot roll a slot's state back over rejected drafts")
        if cfg.attention_impl not in (None, "dense"):
            no(f"attention_impl={cfg.attention_impl!r}",
               "has one attention path a program")
        if cfg.attention_kernel is not None or cfg.kernel_interpret is not None:
            no("attention_kernel/kernel_interpret",
               "has no kernel-tier backend")
        if cfg.mesh_shape is not None:
            no(f"mesh_shape={cfg.mesh_shape}",
               "has no tensor-parallel sharding rules")
        if cfg.partition_rules:
            no("partition_rules", "has no tensor-parallel sharding rules")
        dtype = jnp.dtype(params["embed_tokens"]["embedding"].dtype)
        stored = {"bfloat16": "bf16", "float32": "fp32"}.get(dtype.name)
        if cfg.kv_cache_dtype != stored:
            no(f"kv_cache_dtype={cfg.kv_cache_dtype!r}",
               f"stores {self.cached} in the compute type only "
               f"({stored!r} for {dtype.name} parameters)")
        if cfg.fault_injection:
            no("fault_injection", "has no fault-injection points")
        return resolve_page_tokens(cfg.kv_page_tokens or DEFAULT_PAGE_TOKENS,
                                   cfg.max_seq_len or 2 ** 20)

    def refuse_handoff(self):
        raise UnsupportedOptionError(
            f"handoff: the {self.name} family has no handoff codec (the "
            f"codec frames pages of keys and values, not a slot's state)")

    def sentinel_programs(self):
        return self.decode_program, self.prefill_program

    def prefilling(self):
        return len(self._prefilling)

    # -- admission -------------------------------------------------------
    def admit(self, stats):
        """Give each queued request a free slot and its pages, zero what of
        the slot's state a new occupant must not inherit (recurrent state;
        nothing where a position mask hides the previous occupant), and let
        ``advance_prefill`` read its prompt a chunk a step. A request that
        finds no pages waits at the head of the queue, and is counted."""
        loop = self.loop
        pool = loop.pool
        while pool.free_slots > 0:
            req = loop.scheduler.pop_next()
            if req is None:
                return
            try:
                slot = pool.allocate(loop.alloc_tokens(req))
            except PoolExhaustedError:
                # a slot is free (the loop's condition): pages are not
                loop.scheduler.requeue_front(req)
                loop.metrics.record_page_wait()
                return
            if pool.reset_names:
                with (loop.tracer.span("serving/state_reset", cat="serving",
                                       args={"slot": slot})
                      if loop.tracer.enabled else telemetry.NULL_SPAN):
                    pool.reset_slot(slot)
            loop.metrics.record_admission(loop.scheduler.buckets[-1],
                                          len(req.prompt))
            req.slot = slot
            self._prefilling.append(Prefilling(req, slot))
            stats["admitted"] += 1

    def expire_prefilling(self, stats, now):
        """Time out the requests whose deadline passed while their prompt
        was being read."""
        for st in [s for s in self._prefilling
                   if s.req.deadline_exceeded(now)]:
            self._prefilling.remove(st)
            self.loop.finish_timeout(st.req, phase="prefill")
            stats["retired"] += 1

    # -- decode ----------------------------------------------------------
    def upload_lanes(self):
        """Lane churn. The active mask and the page tables are the host's
        to say; tokens and positions are patched for the lanes that joined
        since the last upload and left alone for the rest."""
        pool, lanes = self.loop.pool, self.loop.lanes
        joined = np.zeros(pool.max_slots, bool)
        for slot, req in lanes.requests.items():
            joined[slot] = self._on_device.get(slot) != req.id
        self._on_device = {s: r.id for s, r in lanes.requests.items()}
        host = jax.device_put(
            (joined, lanes.tokens,
             np.ascontiguousarray(pool.positions, dtype=np.int32),
             lanes.active.copy(),
             np.ascontiguousarray(pool.page_tables)))
        if lanes.dev_tokens is None:
            lanes.dev_tokens, lanes.dev_positions = host[1], host[2]
        else:
            lanes.dev_tokens, lanes.dev_positions = _patch_lanes_jit(
                lanes.dev_tokens, lanes.dev_positions, *host[:3])
        lanes.dev_active, lanes.dev_page_tables = host[3], host[4]
        lanes.dirty = False

    def decode_step(self, guard):  # jaxlint: hot
        loop = self.loop
        pool, lanes = loop.pool, loop.lanes
        # whose step this is: a slot may change hands before it is read
        riders = {slot: req.id for slot, req in lanes.requests.items()}
        loop.launched("decode")
        with guard:
            (pool.state, lanes.dev_tokens, lanes.dev_positions,
             self.last_logits, moe) = self.decode_program(
                loop.params, pool.state, lanes.dev_tokens,
                lanes.dev_positions, lanes.dev_active,
                lanes.dev_page_tables, cfg=self.cfg,
                page_tokens=pool.page_tokens, keep_logits=self.keep_logits)
        if self.decode_sentinel is not None:
            self.decode_sentinel.check()
        before, self._in_flight = self._in_flight, (lanes.dev_tokens, moe,
                                                    riders)
        if before is None:
            return (), (), 0, 0
        # the step's single deliberate sync, on the step BEFORE the one just
        # dispatched: its tokens and, in the same transfer, the three
        # integers of its expert layers
        host_tokens, moe = loop.read_back(before[:2], "decode", newest=False)
        loop.metrics.record_moe(self.cfg.n_moe_layers, *moe.tolist())
        loop.metrics.record_state_pool(
            pool.state_slots_in_use, pool.pages_in_use, pool.slot_bytes(),
            pool.paged_bytes())
        lanes.tokens = host_tokens.copy()
        return ([slot for slot, req in lanes.requests.items()
                 if before[2].get(slot) == req.id],
                host_tokens[:, None].tolist(), 0, 0)


# Decode steps a prompt may wait for a prefill call's rows to fill. The
# program's shape is fixed, so a call costs the same with one row in use or
# all of them (it reads every expert either way), and every lane waits for
# it; a held prompt costs its own lane a token a step.
PREFILL_HOLD_STEPS = 16


class RowPrefillFamily(SlotStateFamily):
    """A ``SlotStateFamily`` whose attention layers over pages are
    ``models/paged_layers.py``'s (``paged_attn_layers`` of them, which
    ``build`` sets; a family whose paged attention walks no work list,
    ``families/keye.py`` or ``families/glm_dsa.py``, counts what it reads in a
    ``count_attended`` of its own) and whose prefill call runs
    ``prefill_chunk_tokens`` positions as ``rows`` rows of ``row_tokens``
    tokens (``build`` sets both; a row is ``row_length`` tokens: one page,
    unless the family's mixer has a chunk of its own). The prompts being read
    take rows in the order they were admitted, each as many as its remaining
    tokens need while rows are left, so a call holds several prompts, a long
    prompt advances by several rows in one call and no prompt is padded by more
    than a row. A call is held back, for at most ``PREFILL_HOLD_STEPS``
    steps and only while lanes decode, until the prompts waiting fill its
    rows. The program takes ``(params, state, ids [R, T], slots [R], starts
    [R], lens [R], page_tables [R, mp])``; an empty row carries
    ``max_slots`` for its slot, which no write reaches."""

    row_tokens = None
    rows = None
    paged_attn_layers = None

    def __init__(self, model_config):
        super().__init__(model_config)
        self._held = 0              # steps the waiting prompts were held

    def row_length(self, page):
        """Tokens of a prefill row where a page holds ``page``."""
        return page

    def check_options(self, cfg, params):
        page = super().check_options(cfg, params)
        row = self.row_length(page)
        if cfg.prefill_chunk_tokens < row or cfg.prefill_chunk_tokens % row:
            self.refuse(f"prefill_chunk_tokens={cfg.prefill_chunk_tokens}",
                        f"prefills in rows of {row} tokens: a positive "
                        f"multiple of it")
        return page

    def decode_step(self, guard):  # jaxlint: hot
        """The step of ``SlotStateFamily``, and what it attends to, counted
        from the allocator's host mirror of the lanes' positions (a step
        behind the device's, which costs no read-back)."""
        self.count_attended(
            self.loop.pool.positions[list(self.loop.lanes.requests)])
        return super().decode_step(guard)

    def count_attended(self, held):
        """``held [lanes]`` positions of the active lanes: how much of the
        lanes x blocks rectangle the step's paged attention walks, and how
        many of the walked blocks' pages hold a key it attends."""
        page = self.loop.pool.page_tokens
        span = decode_key_span(page)
        self.loop.metrics.record_attn_blocks(
            held // span + 1, self.paged_attn_layers, held // page + 1,
            span // page)

    def count_prefill(self, starts, lens):
        """``starts [R]``, ``lens [R]`` of the prefill call about to run
        (0: an empty row): a family that counts what the call walks does it
        here, on the host."""

    def _rows_waiting(self):
        T = self.row_tokens
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
        R, T = self.rows, self.row_tokens
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
        self.count_prefill(starts, lens)
        with cspan:
            loop.launched("prefill")
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
            first_host = (loop.read_back(first, "prefill", newest=True)
                          if any(ends) else None)
        now = time.monotonic()
        loop.prefill_ran()
        stats["prefill_chunks"] += 1
        loop.metrics.record_prefill_chunk(rows=r, empty_positions=(R - r) * T)
        # the call's time is counted once, shared by the tokens it read
        per_token = (now - t0) / sum(took for _, took, _ in riders)
        for (st, took, last_row), ended in zip(riders, ends):
            st.positions_run += -(-took // T) * T
            st.pos += took
            st.prefill_s += per_token * took
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


class PagesAndRingsFamily(RowPrefillFamily):
    """A ``RowPrefillFamily`` over a decoder of full and window layers
    (``models/paged_layers.py``'s walk: ``families/laguna.py``,
    ``families/mimo_v2.py``). The pool holds state of two lifetimes: a
    full-attention layer's keys and values in pages (the key-value heads
    side by side in a paged row), which a request claims for its own span
    from the ``kv_pool_tokens`` budget, and a window layer's in a ring of
    ``sliding_window`` positions a lane, among the pool's slot arrays, which
    is the lane's whatever its occupant. It is described from the
    configuration: ``cache_widths``, a width a name (``k``, ``v``, ``wk``,
    ``wv``: one number in Laguna, four in MiMo-V2), ``full_index``,
    ``window_index`` and ``sliding_window``. A ring is read behind a
    position mask, which hides whatever a previous occupant left, so
    admission zeroes nothing (``reset=()``). A row is one page of tokens."""

    cached = "keys and values"

    def check_options(self, cfg, params):
        page = super().check_options(cfg, params)
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

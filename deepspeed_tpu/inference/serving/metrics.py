"""Serving metrics: tokens/s, TTFT percentiles, queue depth, occupancy,
prefill-vs-decode split, prefix-cache hit rate.

Recorded through the SAME ``monitor_from_config`` backends the training
engines use (tensorboard/csv/both), so a serving deployment's dashboards
come from the one construction path — a new monitor backend lights up
here for free. All aggregation is host-side and O(1) per scheduler
iteration (TTFT percentiles sort a bounded sample window at
``snapshot()`` time, not on the serving loop); with no monitor
configured the recorder is still useful as a cheap in-process stats
object (``snapshot()``).
"""

from bisect import bisect_left
from collections import deque

# Upper edges, in microseconds, of the buckets the plain decode reads are
# counted in (``record_read``): a quarter of a millisecond doubling to 512
# ms, and one bucket above. A fixed list: nothing grows with the reads.
READ_EDGES_US = tuple(250 * 2 ** i for i in range(12))
_READ_EDGES_S = tuple(us * 1e-6 for us in READ_EDGES_US)
READ_BUCKETS = tuple(str(us) for us in READ_EDGES_US) + ("inf",)
_READ_KEYS = tuple((f"decode_reads_le_us_{b}", f"decode_read_s_le_us_{b}")
                   for b in READ_BUCKETS)

# TTFT percentile window: newest samples win once full (a long-running
# server's p95 should describe current traffic, not hour-old compiles).
_TTFT_WINDOW = 8192


def _percentile(sorted_samples, q):
    """Nearest-rank percentile over an ascending list (deterministic, no
    interpolation — matches how SLOs are usually stated)."""
    if not sorted_samples:
        return None
    n = len(sorted_samples)
    rank = max(1, -(-q * n // 100))              # ceil(q/100 * n)
    return sorted_samples[min(int(rank), n) - 1]


class ServingMetrics:
    """Aggregates serving counters and forwards gauges to a monitor."""

    def __init__(self, monitor=None):
        self.monitor = monitor
        self.decode_steps = 0
        self.tokens_emitted = 0
        self.requests_completed = 0
        self.requests_timed_out = 0
        self.decode_time_s = 0.0
        # prefill: whole-prompt forwards (batched / chunked); ``tokens``
        # counts positions actually computed, so prefix-cache reuse shows
        # up as the gap between prompt tokens and prefill tokens
        self.prefill_calls = 0
        self.prefill_tokens = 0
        self.prefill_reused_tokens = 0
        self.prefill_time_s = 0.0
        # positions the prefill programs RAN (rows x bucket, dummy rows
        # and padding included), beside the positions the prompts needed
        self.prefill_positions_run = 0
        # the loop's own account of its time: every one a monotone sum
        # over clock stamps the loop takes anyway (no container, nothing
        # per token but float adds). loop_busy_s: wall time of iterations
        # that did anything; decode_host_s: token read-back to the end of
        # the iteration; admit_time_s: whole admissions (prefill_time_s is
        # the part inside them spent from prefill dispatch to read-back)
        self.loop_busy_s = 0.0
        self.decode_host_s = 0.0
        self.admit_time_s = 0.0
        # the loop's waits for the device, on the stamps of the one helper
        # every blocking read goes through (``ServingEngine.launched`` /
        # ``read_back``): decode_dispatch_s from ``launched("decode")`` to
        # the read's start (or to the call's end where the call reads
        # nothing), <kind>_reads and <kind>_read_wait_s the blocking reads
        # themselves, by the kind of program, "decode" or "prefill". A
        # decode read that had a prefill program's device time inside its
        # wait is in the totals only; the others, the plain reads, fall
        # each into one bucket of ``READ_EDGES_US`` by their length
        # (``_plain_reads`` counts, ``_plain_read_s`` seconds), so the
        # reads behind a prefill are the totals less the buckets' sums.
        # dry_after_<kind>_s: from the return of a read after which nothing
        # dispatched is left to run to the next ``launched``, by the kind
        # of the read that began the spell
        self.decode_dispatch_s = 0.0
        self.decode_reads = 0
        self.decode_read_wait_s = 0.0
        self.prefill_reads = 0
        self.prefill_read_wait_s = 0.0
        self._plain_reads = [0] * len(READ_BUCKETS)
        self._plain_read_s = [0.0] * len(READ_BUCKETS)
        self.dry_after_decode_s = 0.0
        self.dry_after_prefill_s = 0.0
        self.dry_spells_after_prefill = 0
        # submit() to prefill dispatch, per admitted request
        self.queue_wait_s = 0.0
        self.queue_waits = 0
        # gaps between consecutive tokens of one request; a gap is
        # stalled when a prefill (or a chunk) ran between its two tokens
        self.token_gaps = 0
        self.token_gap_s = 0.0
        self.stalled_gaps = 0
        self.stalled_gap_s = 0.0
        # prefix cache lookups (mirrors the cache's own counters so a
        # snapshot works without reaching into the engine)
        self.prefix_hits = 0
        self.prefix_misses = 0
        # spill tier: promotion hit rate, dropped-corrupt counter, and
        # pull sources for live byte/entry/RSS gauges (engine wires
        # set_spill_sources; snapshot degrades gracefully unwired)
        self.spill_hits = 0
        self.spill_misses = 0
        self.spill_corrupt_total = 0
        self._spill_stats_fn = None
        self._host_rss_mb_fn = None
        # speculative decoding: drafts proposed/accepted across steps and
        # the pool's storage footprint (recorded once, at engine build)
        self.draft_proposed = 0
        self.draft_accepted = 0
        self.kv_pool_bytes = 0
        # paged KV pool: last-seen page occupancy/fragmentation gauges
        # and a per-bucket histogram of admitted prompt lengths
        # {bucket: [count, token_sum, min_len, max_len]}
        self.pages_in_use = 0
        self.page_fragmentation = 0.0
        self._admitted_by_bucket = {}
        # disaggregated prefill/decode handoff (engine calls
        # record_handoff; events beyond these four still count as a
        # dict entry so a new event kind never raises)
        self.handoff_exports = 0
        self.handoff_installs = 0
        self.handoff_dup_installs = 0
        self.handoff_resumes = 0
        self.handoff_reaped = 0
        # routed experts and state of a second kind (families that have
        # them; zero otherwise). Monotone sums over what the decode
        # program hands back beside its tokens: expert layers run (layers x
        # decode steps), token-expert picks that fell on held experts, held
        # experts with at least one token, the busiest held expert's tokens
        # (the last two summed over layers and steps); calls of a chunked
        # prefill program and the rows of them that carried a prompt;
        # what a family whose step's bytes go with its contexts says its
        # decode steps attended to (the active lanes' positions, summed a
        # step) and held (pages in use, summed a step); the (lane, key
        # block) pairs the paged decode attention of a family's step walked,
        # beside the lanes x blocks to the longest lane's end that a walk
        # of every lane alike would have read; admission passes
        # that ended for want of pages under a ``kv_pool_tokens`` budget;
        # and last-value gauges of the hybrid state pool
        self.moe_layer_steps = 0
        self.moe_picks_here = 0
        self.moe_experts_touched = 0
        self.moe_expert_load_max = 0
        self.prefill_chunks = 0
        self.prefill_chunk_rows = 0
        self.decode_context_tokens = 0
        self.pool_pages_in_use_steps = 0
        self.decode_ring_positions = 0
        self.dsa_keys_scored = 0
        self.dsa_keys_attended = 0
        self.dsa_layers_shared_attended = 0
        self.dsa_prefill_blocks_walked = 0
        self.dsa_prefill_blocks_dense = 0
        self.dsa_decode_blocks_walked = 0
        self.dsa_decode_blocks_dense = 0
        self.decode_attn_blocks_walked = 0
        self.decode_attn_blocks_dense = 0
        self.decode_attn_pages_fetched = 0
        self.decode_attn_pages_in_blocks = 0
        # a family whose decoder runs its stack several times a token: the
        # passes its decode steps ran, the layer applications (passes x
        # layers) of its decode steps and prefill calls, and what a token
        # caches (a row a (pass, layer))
        self.loop_passes = 0
        self.loop_layer_calls = 0
        self.loop_cache_rows = 0
        self.loop_cache_bytes_per_token = 0
        self.page_waits = 0
        self.state_slots_in_use = 0
        self.latent_pages_in_use = 0
        self.state_pool_bytes = 0
        self.latent_pool_bytes = 0
        # TTFT: time from submit() to the request's first token
        self._ttft_sum = 0.0
        self._ttft_count = 0
        self._ttft_max = 0.0
        # deque(maxlen=...) evicts the oldest sample in O(1); the old list
        # did an O(n) pop(0) memmove per TTFT once full
        self._ttft_window = deque(maxlen=_TTFT_WINDOW)

    # -- recording hooks (engine calls these) ---------------------------
    def record_first_token(self, ttft_s):
        self._ttft_sum += ttft_s
        self._ttft_count += 1
        self._ttft_max = max(self._ttft_max, ttft_s)
        self._ttft_window.append(ttft_s)
        self._record("Serving/ttft_s", ttft_s, self._ttft_count)

    def record_prefill(self, tokens, reused_tokens, requests, prefill_s,
                       positions_run=0):
        """One prefill call: ``tokens`` computed this call (suffix only
        on a prefix hit), ``reused_tokens`` seeded from the prefix cache,
        over ``requests`` prompts in ``prefill_s`` seconds;
        ``positions_run`` is what the program computed for them (rows x
        bucket, summed over the chunks of a chunked prefill)."""
        self.prefill_calls += 1
        self.prefill_tokens += tokens
        self.prefill_positions_run += positions_run
        self.prefill_reused_tokens += reused_tokens
        self.prefill_time_s += prefill_s
        if prefill_s > 0:
            self._record("Serving/prefill_tokens_per_sec",
                         tokens / prefill_s, self.prefill_calls)
        self._record("Serving/prefill_batch", requests, self.prefill_calls)

    def record_prefill_chunk(self, rows=1, empty_positions=0):
        """One call of a chunked prefill program: ``rows`` of it carried a
        prompt's tokens; ``empty_positions`` are the positions of the rows
        that carried none, which the program computed all the same (a
        prompt's own rows are counted when it ends, ``record_prefill``)."""
        self.prefill_chunks += 1
        self.prefill_chunk_rows += rows
        self.prefill_positions_run += empty_positions

    def record_moe(self, layer_steps, picks_here, experts_touched,
                   expert_load_max):
        """One decode step's expert layers, from the integers the decode
        program returns in the same transfer as its tokens."""
        self.moe_layer_steps += layer_steps
        self.moe_picks_here += picks_here
        self.moe_experts_touched += experts_touched
        self.moe_expert_load_max += expert_load_max

    def record_attended(self, context_tokens, pages_in_use):
        """One decode step of a family whose attention reads a lane's whole
        context: the positions its active lanes hold, and the pages in use
        (``decode_context_tokens``, ``pool_pages_in_use_steps``)."""
        self.decode_context_tokens += int(context_tokens)
        self.pool_pages_in_use_steps += int(pages_in_use)

    def record_ring_positions(self, positions):
        """One decode step of a family with window layers: the positions
        its active lanes' rings hold behind their mask, summed over the
        window layers (``decode_ring_positions``)."""
        self.decode_ring_positions += int(positions)

    def record_selected(self, keys_scored, keys_attended, shared_attended=0):
        """One decode step of a family whose attention reads what a learned
        indexer selects: the keys its indexers scored, summed over the
        layers that score and active lanes (every position a lane holds),
        and the keys its attention then read, summed over the layers that
        attend (``min(context, topk)`` a lane a layer): ``dsa_keys_scored``,
        ``dsa_keys_attended``. Where some layers attend under a selection
        that another layer computed, ``shared_attended`` is their part of
        the second sum: ``dsa_layers_shared_attended``."""
        self.dsa_keys_scored += int(keys_scored)
        self.dsa_keys_attended += int(keys_attended)
        self.dsa_layers_shared_attended += int(shared_attended)

    def record_prefill_blocks(self, walked, dense):
        """One prefill call of such a family: the key blocks its rows' own
        prompts reach, summed over rows and layers (what
        ``ops/paged_prefill.py``'s kernel walks), and rows x the longest
        row's blocks (what the walk in plain operations runs):
        ``dsa_prefill_blocks_walked``, ``dsa_prefill_blocks_dense``."""
        self.dsa_prefill_blocks_walked += int(walked)
        self.dsa_prefill_blocks_dense += int(dense)

    def record_decode_blocks(self, walked, dense):
        """One decode step of such a family: the blocks of selected
        positions its active lanes' selections fill, summed over lanes and
        layers (what ``ops/paged_prefill.py::attend_tiles`` walks), and
        every lane's every block (what the two products in plain operations
        read): ``dsa_decode_blocks_walked``, ``dsa_decode_blocks_dense``."""
        self.dsa_decode_blocks_walked += int(walked)
        self.dsa_decode_blocks_dense += int(dense)

    def record_attn_blocks(self, blocks, layers, pages, block_pages):
        """One decode step of a family whose paged attention walks a work
        list (``models/paged_layers.py::gqa_decode``): ``blocks`` key blocks
        each active lane owns, in each of ``layers`` layers. Beside the
        pairs walked, the rectangle they are cut from: every lane to the
        longest one's end (``decode_attn_blocks_walked``,
        ``decode_attn_blocks_dense``). And the walked blocks by page:
        ``pages`` each active lane holds a key in, which is what
        ``ops/paged_decode.py``'s kernel fetches, beside every page of every
        walked block, ``block_pages`` a block, which is what the walk in
        plain operations gathers (``decode_attn_pages_fetched``,
        ``decode_attn_pages_in_blocks``)."""
        if len(blocks):
            self.decode_attn_blocks_walked += layers * int(blocks.sum())
            self.decode_attn_blocks_dense += (layers * len(blocks)
                                              * int(blocks.max()))
            self.decode_attn_pages_fetched += layers * int(pages.sum())
            self.decode_attn_pages_in_blocks += (layers * block_pages
                                                 * int(blocks.sum()))

    def record_loop(self, passes, layer_calls):
        """One program call of a family whose decoder is a loop over its
        stack (``families/ouro.py``): the ``passes`` a decode step ran (0
        for a prefill call, which is not a step) and the call's layer
        applications, passes x layers (``loop_passes``,
        ``loop_layer_calls``)."""
        self.loop_passes += int(passes)
        self.loop_layer_calls += int(layer_calls)

    def record_loop_cache(self, rows, bytes_per_token):
        """Gauges of such a family's cache, constants of its construction:
        the rows a token caches (one a (pass, layer)) and their bytes, keys
        and values."""
        self.loop_cache_rows = int(rows)
        self.loop_cache_bytes_per_token = int(bytes_per_token)
        self._record("Loop/cache_rows", self.loop_cache_rows, 1)
        self._record("Loop/cache_bytes_per_token",
                     self.loop_cache_bytes_per_token, 1)

    def record_page_wait(self):
        """An admission pass ended with a slot free and the head of the
        queue waiting for pages."""
        self.page_waits += 1

    def record_state_pool(self, slots_in_use, pages_in_use, slot_bytes,
                          paged_bytes):
        """Gauges of a hybrid state pool (slot state beside paged rows)."""
        self.state_slots_in_use = int(slots_in_use)
        self.latent_pages_in_use = int(pages_in_use)
        self.state_pool_bytes = int(slot_bytes)
        self.latent_pool_bytes = int(paged_bytes)

    def record_queue_wait(self, wait_s, requests=1):
        """``requests`` admitted prompts waited ``wait_s`` seconds in all
        between submit() and the dispatch of their prefill."""
        self.queue_wait_s += wait_s
        self.queue_waits += requests

    def record_token_gap(self, gap_s, stalled):
        """One gap between consecutive tokens of one request."""
        self.token_gaps += 1
        self.token_gap_s += gap_s
        if stalled:
            self.stalled_gaps += 1
            self.stalled_gap_s += gap_s

    def record_iteration(self, busy_s, decode_host_s):
        """One loop iteration that did anything: its wall time, and the
        part of it after the decode step's token read-back (0.0 when no
        decode step ran)."""
        self.loop_busy_s += busy_s
        self.decode_host_s += decode_host_s

    def record_read(self, kind, wait_s, behind_prefill=False):
        """One blocking read of the loop thread: ``wait_s`` from its start
        to its return. A decode read whose wait held a prefill program
        (``behind_prefill``) stays out of the buckets. A read of another
        kind than a program's output (the prefix cache's copy of a
        prompt's K/V) is counted nowhere."""
        if kind == "prefill":
            self.prefill_reads += 1
            self.prefill_read_wait_s += wait_s
        elif kind == "decode":
            self.decode_reads += 1
            self.decode_read_wait_s += wait_s
            if not behind_prefill:
                b = bisect_left(_READ_EDGES_S, wait_s)
                self._plain_reads[b] += 1
                self._plain_read_s[b] += wait_s

    def record_dry_spell(self, kind, dry_s):
        """The loop knew the device had no model program queued for
        ``dry_s`` seconds after a read of ``kind``."""
        if kind == "decode":
            self.dry_after_decode_s += dry_s
        else:
            self.dry_spells_after_prefill += 1
            self.dry_after_prefill_s += dry_s

    def record_prefix_lookup(self, hit):
        if hit:
            self.prefix_hits += 1
        else:
            self.prefix_misses += 1
        lookups = self.prefix_hits + self.prefix_misses
        self._record("Serving/PrefixHitRate",
                     self.prefix_hits / lookups, lookups)

    def record_spill_lookup(self, hit):
        """One spill-tier consult on the counted (acquire) path: ``hit``
        when the returned entry was just promoted out of the spill
        tier — ``Serving/SpillHitRate`` is the fraction of prefix
        lookups the demotion tier saved from a cold re-prefill."""
        if hit:
            self.spill_hits += 1
        else:
            self.spill_misses += 1
        lookups = self.spill_hits + self.spill_misses
        self._record("Serving/SpillHitRate",
                     self.spill_hits / lookups, lookups)

    def record_spill_corrupt(self):
        """A spilled entry failed its checksum/framing on promotion and
        was dropped (the request fell through to a normal prefill)."""
        self.spill_corrupt_total += 1
        self._record("Serving/spill_corrupt_total",
                     self.spill_corrupt_total, self.spill_corrupt_total)

    def set_spill_sources(self, spill_stats_fn=None, host_rss_mb_fn=None):
        """Wire pull sources for the live gauges: ``spill_stats_fn`` ->
        the SpillStore ``stats()`` dict (bytes/entries), and
        ``host_rss_mb_fn`` -> current host RSS in MiB (the guard's
        reader). Both surface in ``snapshot()`` and therefore in the
        ``Serving/Snapshot`` Prometheus exposition."""
        self._spill_stats_fn = spill_stats_fn
        self._host_rss_mb_fn = host_rss_mb_fn

    def record_admission(self, bucket, prompt_len):
        """One admitted prompt: tally its TRUE length (not the padded
        bucket width) under the bucket it was admitted to, building the
        per-bucket admitted-prompt-length histogram."""
        h = self._admitted_by_bucket.get(bucket)
        if h is None:
            self._admitted_by_bucket[bucket] = [
                1, prompt_len, prompt_len, prompt_len]
        else:
            h[0] += 1
            h[1] += prompt_len
            h[2] = min(h[2], prompt_len)
            h[3] = max(h[3], prompt_len)
        self._record(f"Serving/admitted_prompt_len_bucket_{bucket}",
                     prompt_len, self._admitted_by_bucket[bucket][0])

    def record_completion(self):
        self.requests_completed += 1

    def record_timeout(self):
        self.requests_timed_out += 1

    def record_step(self, queue_depth, active_slots, max_slots,
                    tokens_this_step, step_s, accepted_tokens=0,
                    proposed_tokens=0, pages_in_use=0,
                    page_fragmentation=0.0):
        """One decode step. With speculation armed, ``proposed_tokens``
        is k * active lanes and ``accepted_tokens`` how many drafts the
        oracle confirmed — tokens_this_step then exceeds the lane count
        by exactly the accepted drafts (minus early retirements).
        ``pages_in_use``/``page_fragmentation`` come from the paged
        pool's ``occupancy()`` — last-value gauges, not counters."""
        self.decode_steps += 1
        self.tokens_emitted += tokens_this_step
        self.decode_time_s += step_s
        self.pages_in_use = pages_in_use
        self.page_fragmentation = page_fragmentation
        step = self.decode_steps
        self._record("Serving/queue_depth", queue_depth, step)
        self._record("Serving/pages_in_use", pages_in_use, step)
        self._record("Serving/page_fragmentation", page_fragmentation, step)
        self._record("Serving/batch_occupancy",
                     active_slots / max_slots if max_slots else 0.0, step)
        if step_s > 0:
            self._record("Serving/tokens_per_sec",
                         tokens_this_step / step_s, step)
        self._record("Serving/tokens_per_step", tokens_this_step, step)
        if proposed_tokens > 0:
            self.draft_proposed += proposed_tokens
            self.draft_accepted += accepted_tokens
            self._record("Serving/accept_rate",
                         accepted_tokens / proposed_tokens, step)

    def record_handoff(self, event):
        """One KV-handoff lifecycle event: 'export' (prefill side,
        pages snapshotted at retire), 'install' / 'dup_install' (decode
        side, pages landed / idempotent re-send dropped), 'resume'
        (lane activated from installed pages), 'reaped' (orphaned
        claim freed by the TTL reaper)."""
        attr = f"handoff_{event}s" if not event.endswith("ed") \
            else f"handoff_{event}"
        setattr(self, attr, getattr(self, attr, 0) + 1)
        self._record(f"Serving/{attr}", getattr(self, attr), 1)

    def record_kv_pool_bytes(self, nbytes):
        """Pool storage footprint (KV + scales) — a construction-time
        constant, re-recordable if a pool is ever rebuilt."""
        self.kv_pool_bytes = int(nbytes)
        self._record("Serving/kv_pool_bytes", int(nbytes), 1)

    def _record(self, tag, value, step):
        if self.monitor is not None:
            self.monitor.record(tag, value, step)

    # -- reading --------------------------------------------------------
    def avg_ttft_s(self):
        return self._ttft_sum / self._ttft_count if self._ttft_count else None

    def ttft_percentiles(self):
        """(p50, p95) over the recent TTFT window, (None, None) empty."""
        window = sorted(self._ttft_window)
        return _percentile(window, 50), _percentile(window, 95)

    def tokens_per_sec(self):
        """Decode-loop throughput (excludes idle wall time between
        requests — the number a capacity planner wants)."""
        if self.decode_time_s <= 0:
            return None
        return self.tokens_emitted / self.decode_time_s

    def prefill_tokens_per_sec(self):
        if self.prefill_time_s <= 0:
            return None
        return self.prefill_tokens / self.prefill_time_s

    def prefix_hit_rate(self):
        lookups = self.prefix_hits + self.prefix_misses
        return self.prefix_hits / lookups if lookups else None

    def spill_hit_rate(self):
        lookups = self.spill_hits + self.spill_misses
        return self.spill_hits / lookups if lookups else None

    def accept_rate(self):
        """Cumulative draft acceptance rate, None before any
        speculative step (or with speculation disabled)."""
        if self.draft_proposed <= 0:
            return None
        return self.draft_accepted / self.draft_proposed

    def tokens_per_step(self):
        """Mean emitted tokens per decode step — the speculative
        multiplier a capacity planner multiplies lane count by."""
        if self.decode_steps <= 0:
            return None
        return self.tokens_emitted / self.decode_steps

    def snapshot(self):
        p50, p95 = self.ttft_percentiles()
        snap = {
            "decode_steps": self.decode_steps,
            "tokens_emitted": self.tokens_emitted,
            "requests_completed": self.requests_completed,
            "requests_timed_out": self.requests_timed_out,
            "tokens_per_sec": self.tokens_per_sec(),
            "avg_ttft_s": self.avg_ttft_s(),
            "max_ttft_s": self._ttft_max if self._ttft_count else None,
            "ttft_p50_s": p50,
            "ttft_p95_s": p95,
            # prefill-vs-decode token split: prompt positions computed by
            # prefill forwards vs tokens emitted by the decode loop
            "prefill_tokens": self.prefill_tokens,
            "decode_tokens": self.tokens_emitted,
            "prefill_calls": self.prefill_calls,
            "prefill_tokens_per_sec": self.prefill_tokens_per_sec(),
            "prefill_positions_run": self.prefill_positions_run,
            # where the loop's time went (monotone sums: a reader takes
            # after-minus-before over its window)
            "decode_time_s": self.decode_time_s,
            "prefill_time_s": self.prefill_time_s,
            "loop_busy_s": self.loop_busy_s,
            "decode_host_s": self.decode_host_s,
            "admit_time_s": self.admit_time_s,
            # the loop's waits for the device (docs/observability.md)
            "decode_dispatch_s": self.decode_dispatch_s,
            "decode_reads": self.decode_reads,
            "decode_read_wait_s": self.decode_read_wait_s,
            "prefill_reads": self.prefill_reads,
            "prefill_read_wait_s": self.prefill_read_wait_s,
            "dry_after_decode_s": self.dry_after_decode_s,
            "dry_after_prefill_s": self.dry_after_prefill_s,
            "dry_spells_after_prefill": self.dry_spells_after_prefill,
            "queue_wait_s": self.queue_wait_s,
            "queue_waits": self.queue_waits,
            "token_gaps": self.token_gaps,
            "token_gap_s": self.token_gap_s,
            "stalled_gaps": self.stalled_gaps,
            "stalled_gap_s": self.stalled_gap_s,
            "prefix_reused_tokens": self.prefill_reused_tokens,
            "prefix_hit_rate": self.prefix_hit_rate(),
            # speculative decoding + pool storage
            "accept_rate": self.accept_rate(),
            "tokens_per_step": self.tokens_per_step(),
            "draft_proposed": self.draft_proposed,
            "draft_accepted": self.draft_accepted,
            "kv_pool_bytes": self.kv_pool_bytes,
            "pages_in_use": self.pages_in_use,
            "page_fragmentation": self.page_fragmentation,
            # routed experts, chunked prefill, hybrid state pool
            "moe_layer_steps": self.moe_layer_steps,
            "moe_picks_here": self.moe_picks_here,
            "moe_experts_touched": self.moe_experts_touched,
            "moe_expert_load_max": self.moe_expert_load_max,
            "prefill_chunks": self.prefill_chunks,
            "prefill_chunk_rows": self.prefill_chunk_rows,
            "decode_context_tokens": self.decode_context_tokens,
            "pool_pages_in_use_steps": self.pool_pages_in_use_steps,
            "decode_ring_positions": self.decode_ring_positions,
            "dsa_keys_scored": self.dsa_keys_scored,
            "dsa_keys_attended": self.dsa_keys_attended,
            "dsa_layers_shared_attended": self.dsa_layers_shared_attended,
            "dsa_prefill_blocks_walked": self.dsa_prefill_blocks_walked,
            "dsa_prefill_blocks_dense": self.dsa_prefill_blocks_dense,
            "dsa_decode_blocks_walked": self.dsa_decode_blocks_walked,
            "dsa_decode_blocks_dense": self.dsa_decode_blocks_dense,
            "decode_attn_blocks_walked": self.decode_attn_blocks_walked,
            "decode_attn_blocks_dense": self.decode_attn_blocks_dense,
            "decode_attn_pages_fetched": self.decode_attn_pages_fetched,
            "decode_attn_pages_in_blocks": self.decode_attn_pages_in_blocks,
            "loop_passes": self.loop_passes,
            "loop_layer_calls": self.loop_layer_calls,
            "loop_cache_rows": self.loop_cache_rows,
            "loop_cache_bytes_per_token": self.loop_cache_bytes_per_token,
            "page_waits": self.page_waits,
            "state_slots_in_use": self.state_slots_in_use,
            "latent_pages_in_use": self.latent_pages_in_use,
            "state_pool_bytes": self.state_pool_bytes,
            "latent_pool_bytes": self.latent_pool_bytes,
            # disaggregated prefill/decode handoff lifecycle
            "handoff_exports": self.handoff_exports,
            "handoff_installs": self.handoff_installs,
            "handoff_dup_installs": self.handoff_dup_installs,
            "handoff_resumes": self.handoff_resumes,
            "handoff_reaped": self.handoff_reaped,
            # spill tier + memory pressure (pull gauges: live bytes and
            # host RSS are read at snapshot time, not last-recorded)
            "spill_hit_rate": self.spill_hit_rate(),
            "spill_corrupt_total": self.spill_corrupt_total,
        }
        if self._spill_stats_fn is not None:
            try:
                sstats = self._spill_stats_fn() or {}
            except Exception:
                sstats = {}
            snap["spill_bytes"] = sstats.get("bytes", 0)
            snap["spill_disk_bytes"] = sstats.get("disk_bytes", 0)
            snap["spill_entries"] = sstats.get("entries", 0)
        if self._host_rss_mb_fn is not None:
            rss = self._host_rss_mb_fn()
            if rss is not None:
                snap["host_rss_mb"] = rss
        # the plain decode reads by length, a key an upper edge
        for (count_key, seconds_key), n, secs in zip(
                _READ_KEYS, self._plain_reads, self._plain_read_s):
            snap[count_key] = n
            snap[seconds_key] = secs
        # flattened per-bucket admitted-prompt-length histogram: numeric
        # keys so export_to's gauge filter picks them up unchanged
        for bucket in sorted(self._admitted_by_bucket):
            count, total, lo, hi = self._admitted_by_bucket[bucket]
            snap[f"admitted_prompts_bucket_{bucket}"] = count
            snap[f"admitted_prompt_len_mean_bucket_{bucket}"] = total / count
            snap[f"admitted_prompt_len_min_bucket_{bucket}"] = lo
            snap[f"admitted_prompt_len_max_bucket_{bucket}"] = hi
        return snap

    def export_to(self, registry, name="Serving/Snapshot"):
        """Expose the numeric ``snapshot()`` fields as pull gauges on a
        telemetry registry — rendered live at every ``/metrics`` scrape
        (pushed gauges would be stale between monitor flushes)."""
        registry.gauge_fn(
            name,
            lambda: {k: v for k, v in self.snapshot().items()
                     if isinstance(v, (int, float)) and not isinstance(v, bool)},
            help="live ServingMetrics.snapshot()")
        return registry

    def close(self):
        if self.monitor is not None:
            self.monitor.flush()


# rollout phases in escalation order; the phase gauge exports the index
ROLLOUT_PHASES = ("idle", "staging", "canary", "promoting", "rolling_back",
                  "committed")


class RolloutMetrics:
    """Counters and gauges for the weight-rollout state machine.

    Two lifetimes on purpose: *per-rollout* counters (shadow compares,
    shadow diffs, canary crashes) reset when ``begin_rollout`` starts the
    next attempt — a diff rate must describe THIS canary, not a previous
    one — while *fleet-lifetime* counters (rollouts/rollbacks/commits)
    only ever grow. Exported under ``Rollout/*`` (``Rollout/phase``,
    ``Rollout/shadow_diff_total``, ``Rollout/rollbacks_total``, ...)."""

    def __init__(self, monitor=None):
        self.monitor = monitor
        self.phase = "idle"
        self.target_tag = None
        # lifetime
        self.rollouts_total = 0
        self.rollbacks_total = 0
        self.commits_total = 0
        # per-rollout (reset by begin_rollout)
        self.shadow_compared_total = 0
        self.shadow_diff_total = 0
        self.canary_crashes = 0
        self.last_rollback_reason = None
        self.last_recovery_s = None

    def begin_rollout(self, tag):
        self.rollouts_total += 1
        self.target_tag = str(tag)
        self.shadow_compared_total = 0
        self.shadow_diff_total = 0
        self.canary_crashes = 0
        self.last_rollback_reason = None
        self.last_recovery_s = None
        self.set_phase("staging")

    def set_phase(self, phase):
        if phase not in ROLLOUT_PHASES:
            raise ValueError(f"unknown rollout phase {phase!r}")
        self.phase = phase
        self._record("Rollout/phase", float(ROLLOUT_PHASES.index(phase)),
                     self.rollouts_total)

    def record_shadow(self, matched):
        self.shadow_compared_total += 1
        if not matched:
            self.shadow_diff_total += 1
        self._record("Rollout/shadow_diff_total",
                     float(self.shadow_diff_total),
                     self.shadow_compared_total)

    def record_canary_crash(self):
        self.canary_crashes += 1

    def record_rollback(self, reason):
        self.rollbacks_total += 1
        self.last_rollback_reason = str(reason)
        self._record("Rollout/rollbacks_total",
                     float(self.rollbacks_total), self.rollouts_total)

    def record_commit(self):
        self.commits_total += 1

    def shadow_diff_rate(self):
        if self.shadow_compared_total <= 0:
            return 0.0
        return self.shadow_diff_total / self.shadow_compared_total

    def _record(self, tag, value, step):
        if self.monitor is not None:
            self.monitor.record(tag, value, step)

    def snapshot(self):
        return {
            "phase": float(ROLLOUT_PHASES.index(self.phase)),
            "rollouts_total": float(self.rollouts_total),
            "rollbacks_total": float(self.rollbacks_total),
            "commits_total": float(self.commits_total),
            "shadow_compared_total": float(self.shadow_compared_total),
            "shadow_diff_total": float(self.shadow_diff_total),
            "shadow_diff_rate": float(self.shadow_diff_rate()),
            "canary_crashes": float(self.canary_crashes),
            "last_recovery_s": float(self.last_recovery_s or 0.0),
        }

    def export_to(self, registry, name="Rollout"):
        """Pull gauges under ``Rollout/*`` so the SLO engine and the
        fleet collector can alert on a stuck or flapping rollout."""
        registry.gauge_fn(name, self.snapshot,
                          help="weight-rollout state machine counters")
        return registry

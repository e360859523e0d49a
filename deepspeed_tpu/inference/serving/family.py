"""The contract between ``ServingEngine`` (the loop) and a model family.

The loop (``engine.py``) knows no model: it owns ``submit`` and the futures,
the scheduler, expiry, which request rides which slot, stamp/emit/retire,
``ServingMetrics``, spans, the degrade ladder, the SLO engine, the memory
guard, the injector hooks and the threads. What a lane's state is and which
jitted programs fill and advance it is a ``ServingFamily``
(``families/gpt2.py``, ``families/kimi_linear.py``,
``families/nemotron_h.py``, ``families/laguna.py``,
``families/mimo_v2.py``, ``families/keye.py``, ``families/ouro.py``). The
arrows point one
way: the loop calls the family through the methods below, and a family calls
back only this short public list of the loop it was built for:

- ``first_token(req, slot, token, now)``: a prompt's prefill is done, its
  state is in ``slot``; ``finish_timeout(req, phase)``; ``alloc_tokens(req)``
  (the page budget a request claims); ``prefill_ran()`` (a prefill program
  ran, so the token gaps it sits in count as stalled);
- read access to ``params``, ``pool``, ``lanes`` (the ``LaneState``),
  ``metrics``, ``tracer``, ``scheduler``, ``config``, ``max_seq_len``;
- what only a family with a prefix cache, speculation or fault arms needs:
  ``prefix_cache``, ``prefix_inserts_paused()``,
  ``relieve_memory_pressure()``, ``injector``, ``step_count``.

No family reads a name of the loop that starts with ``_``
(``tests/unit/test_serving_layers.py`` holds both files to that).

Adding a family: its own file under ``families/``, one line in
``family_for``, and a pool class in ``kv_pool.py`` only if its state is of a
new kind (``HybridStatePool`` takes paged rows and slot arrays by
description, and which of the slot arrays a new occupant must find zeroed;
``families/slot_state.py`` holds what the families over it share, and a
family's file imports no other family's). Its model file under ``models/``
takes the layers it shares with the others from ``models/paged_layers.py``
and imports no sibling either.
"""

import numpy as np

from deepspeed_tpu.models.glm_dsa import GlmDsaConfig
from deepspeed_tpu.models.keye import KeyeConfig
from deepspeed_tpu.models.kimi_linear import KimiLinearConfig
from deepspeed_tpu.models.laguna import LagunaConfig
from deepspeed_tpu.models.mimo_v2 import MiMoV2Config
from deepspeed_tpu.models.nemotron_h import NemotronHConfig
from deepspeed_tpu.models.ouro import OuroConfig
from deepspeed_tpu.profiling.sentinels import CompileSentinel


class UnsupportedOptionError(NotImplementedError):
    """A ``ServingConfig`` option (named in the message) that the model's
    family cannot honour yet. Raised at construction: no silent fallback."""


class LaneState:
    """Which request rides which slot, and every lane's decode operands: the
    host's mirrors and their device copies. The loop owns it and sets
    ``dirty`` on lane churn; the family uploads (``upload_lanes``) and
    advances the device copies in its programs. Positions and page tables
    have their host mirrors in the pool (``pool.positions``,
    ``pool.page_tables``), next to the allocator that writes them."""

    def __init__(self, max_slots):
        self.requests = {}                              # slot -> Request
        self.tokens = np.zeros(max_slots, np.int32)     # pending token a lane
        self.active = np.zeros(max_slots, bool)
        # device-resident decode operands: uploaded ONLY on lane churn
        # (``dirty``), advanced in-jit otherwise, so steady-state decode
        # performs exactly one explicit transfer per step (the token read)
        self.dirty = True
        self.dev_tokens = None
        self.dev_positions = None
        self.dev_active = None
        self.dev_page_tables = None


class ServingFamily:
    """What the loop asks of a family, in the order it asks."""

    name = None
    decode_sentinel = None
    prefill_sentinel = None

    def check_options(self, cfg, params):
        """Raise ``UnsupportedOptionError``, by the option's name, for what
        this family cannot honour."""
        raise NotImplementedError

    def build(self, loop, params):
        """Bind to ``loop`` and build the state behind its allocator.
        Returns ``(params as the programs take them, pool)``."""
        raise NotImplementedError

    def sentinel_programs(self):
        """(decode program, prefill program) for the compile sentinels."""
        raise NotImplementedError

    def arm_sentinels(self, budget):
        decode_prog, prefill_prog = self.sentinel_programs()
        self.decode_sentinel = CompileSentinel(
            decode_prog, budget, name="serving decode step")
        self.prefill_sentinel = CompileSentinel(
            prefill_prog, budget, name="serving batched prefill")

    def export_telemetry(self, registry, server):
        """Gauges and ``/snapshot`` providers of the family's own (``server``
        is None when no port is configured)."""

    def refuse_handoff(self):
        """Raise if the family has no handoff codec for its state."""

    def prefilling(self):
        """Requests that hold a lane but are not decoding yet."""
        return 0

    def advance_prefill(self, stats, now):
        """Run what prefill work is in flight (at most one program call a
        step); returns the last clock stamp taken."""
        return now

    def admit(self, stats):
        """Pop queued requests into free slots and start their prefill."""
        raise NotImplementedError

    def lane_joined(self, req, slot, first_tok):
        """``req`` starts decoding in ``slot`` (the loop has set the shared
        lane state)."""

    def lane_left(self, slot):
        """``slot``'s request retired or timed out."""

    def set_speculation(self, on):
        """The degrade ladder's rung 1 switches speculation off and back
        on; a family without it has nothing to switch."""

    def upload_lanes(self):
        """Lane churn: the one upload of the lane operands."""
        raise NotImplementedError

    def decode_step(self, guard):
        """Dispatch the decode program(s) under ``guard`` and make the
        step's one host read. Returns ``(slots, rows, accepted,
        proposed)``: the slots whose tokens these are (every active lane,
        but for a family that reads the step before the one it just
        dispatched), ``rows[slot]`` the tokens to emit for it in order
        (one for plain decode, accepted + 1 for a speculative step), and
        the step's accepted and proposed draft counts."""
        raise NotImplementedError


def family_for(model_config):
    """The family of a model configuration: the one place under
    ``serving/`` that names a configuration's type."""
    if isinstance(model_config, KimiLinearConfig):
        from deepspeed_tpu.inference.serving.families.kimi_linear import (
            KimiLinearFamily)
        return KimiLinearFamily(model_config)
    if isinstance(model_config, NemotronHConfig):
        from deepspeed_tpu.inference.serving.families.nemotron_h import (
            NemotronHFamily)
        return NemotronHFamily(model_config)
    if isinstance(model_config, LagunaConfig):
        from deepspeed_tpu.inference.serving.families.laguna import (
            LagunaFamily)
        return LagunaFamily(model_config)
    if isinstance(model_config, MiMoV2Config):
        from deepspeed_tpu.inference.serving.families.mimo_v2 import (
            MiMoV2Family)
        return MiMoV2Family(model_config)
    if isinstance(model_config, KeyeConfig):
        from deepspeed_tpu.inference.serving.families.keye import KeyeFamily
        return KeyeFamily(model_config)
    if isinstance(model_config, OuroConfig):
        from deepspeed_tpu.inference.serving.families.ouro import OuroFamily
        return OuroFamily(model_config)
    if isinstance(model_config, GlmDsaConfig):
        from deepspeed_tpu.inference.serving.families.glm_dsa import (
            GlmDsaFamily)
        return GlmDsaFamily(model_config)
    from deepspeed_tpu.inference.serving.families.gpt2 import GPT2Family
    return GPT2Family(model_config)

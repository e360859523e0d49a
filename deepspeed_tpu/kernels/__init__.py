"""Hand-fused Pallas kernel tier behind an op_builder-style registry.

The reference ships its native layer as ``csrc/`` CUDA kernels loaded
through ``op_builder``'s "install native, fall back to compatible"
pattern. This package is that layer's TPU port: each kernel declares a
Pallas implementation AND the repo's existing composed-XLA
implementation as its fallback/parity oracle, and a ``KernelRegistry``
probes availability by *executing* a tiny instance at first use:

- TPU backend        -> native Pallas (real custom calls), never the
                        interpreter. A failed probe RAISES
                        ``KernelProbeError`` with the compiler's
                        message unless ``attention_kernel="xla"``
                        asked for the twin on purpose.
- CPU / CI           -> Pallas interpret mode (same kernel body,
                        executed eagerly — what the parity suite pins
                        bitwise against the XLA fallback)
- probe failure off  -> the XLA fallback, plus ONE edge-triggered
  the TPU               ``jax/kernel_fallback`` telemetry instant and a
                        ``Kernels/fallbacks_total`` counter.

Kernels registered here:

- ``decode_attention`` — fused paged decode attention: one kernel per
  lane doing QK, mask, online softmax and V-gather ACROSS THE LANE'S
  PAGE TABLE (scalar-prefetch indexed DMA), consuming int8 KV pages
  directly so dequantization fuses into the matmul.
- ``sparse_attention`` — the banded sink+window block-sparse attention
  behind the ``sparse_xla`` seam (``_attend_window_one``'s exact math).

Selection is resolved ONCE per call site and threaded into the jitted
programs as a static argument (``kernel_impl``), so a selection change
can never serve a stale compiled program. See ``docs/kernels.md``.
"""

from deepspeed_tpu.kernels.registry import (
    KernelProbeError,
    KernelRegistry,
    get_registry,
    record_call,
    registry_snapshot,
    reset_registry,
)
from deepspeed_tpu.kernels.decode_attention import (
    chunk_attend,
    decode_attend,
)
from deepspeed_tpu.kernels.sparse_attention import (
    band_attend,
    chunk_band_attend,
)

# Public backend names the attention_impl seam dispatches through this
# tier (generation.ATTENTION_IMPLS includes both).
KERNEL_IMPLS = ("pallas", "xla")
KERNEL_BACKENDS = {"pallas_decode": "decode_attention",
                   "pallas_sparse": "sparse_attention"}


def kernel_for_backend(attn_impl):
    """Registry kernel name behind an ``attention_impl`` backend name,
    or None for backends that do not route through the tier."""
    return KERNEL_BACKENDS.get(attn_impl)


def resolve(attn_impl, requested=None, interpret=None):
    """Resolve the (kernel_impl, kernel_interpret) static pair for a
    kernel-tier backend name: ``requested`` forces "pallas"/"xla"
    (None = the probe result), ``interpret`` forces interpret mode
    (None = auto: interpret everywhere but on a real TPU backend, where
    it is refused). An unavailable "pallas" raises ``KernelProbeError``
    on a TPU backend and degrades to "xla" with the edge-triggered
    fallback instant elsewhere."""
    name = kernel_for_backend(attn_impl)
    if name is None:
        return None, False
    return get_registry().resolve(name, requested=requested,
                                  interpret=interpret)


__all__ = [
    "KERNEL_BACKENDS",
    "KERNEL_IMPLS",
    "KernelProbeError",
    "KernelRegistry",
    "band_attend",
    "chunk_attend",
    "chunk_band_attend",
    "decode_attend",
    "get_registry",
    "kernel_for_backend",
    "record_call",
    "registry_snapshot",
    "reset_registry",
    "resolve",
]

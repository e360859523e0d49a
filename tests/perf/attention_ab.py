"""Pallas flash kernel vs XLA-fused attention, fwd+bwd, on the real chip.

Decides where the kernel pays off (long sequences, sparsity, dropout) and
where XLA's own fusion is already optimal (short seq) — the measurement
SURVEY §7 calls for before hand-writing more Pallas.

Run on a TPU host (one process, it holds the chip):
    python tests/perf/attention_ab.py

Timing contract: each measurement is ONE jitted program that chains N
data-dependent forward+backward passes in a ``fori_loop`` and ends in one
scalar fetch, so a pass of a millisecond is not timed by the host's dispatch
(which costs about two on a v5e host). The ``rows`` column is the traced
``Kernels/flash_attention/rows_per_step``: how many (batch, head) rows a
grid step of the kernels took at that shape.
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.attention import (
    flash_attention,
    traced_rows_per_step,
)
from deepspeed_tpu.ops.transformer.transformer import _attention_core


ITERS = 20


def timeit(chain, args):
    float(chain(*args))  # compile + settle
    t0 = time.perf_counter()
    float(chain(*args))  # the fetch waits for the whole chain
    return (time.perf_counter() - t0) / ITERS * 1e3


def make_fb(attn):
    """``ITERS`` forward+backward passes of ``attn`` in one program, each
    fed by the one before so that none can overlap or be folded away."""
    def fb(q, k, v):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)

        _, g = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        return g[0] + g[1] + g[2]

    @jax.jit
    def chain(q, k, v):
        q = jax.lax.fori_loop(
            0, ITERS, lambda _, q: q + 0 * fb(q, k, v)[:1, :1, :1, :1], q)
        return jnp.sum(q.astype(jnp.float32))

    return chain


def xla_attn(q, k, v):
    """The model's own XLA-fused einsum chain: the layer's attention core
    with the kernel switched off by argument."""
    return _attention_core(q, k, v, None, 0.0, True, None, use_pallas=False)


def main():
    dev = jax.devices()[0]
    print(f"device: {dev.device_kind} ({dev.platform})")
    rng = np.random.RandomState(0)
    drop_rng = jax.random.PRNGKey(7)
    print(f"{'B':>4} {'H':>3} {'S':>5} {'pallas ms':>10} {'rows':>4} "
          f"{'+drop ms':>9} {'xla ms':>8} {'ratio':>6}")
    for B, H, S in ((64, 16, 128), (16, 16, 512), (4, 16, 2048), (1, 16, 8192)):
        D = 64
        mk = lambda: jnp.asarray(rng.randn(B, H, S, D).astype(np.float32) * 0.1,
                                 jnp.bfloat16)
        q, k, v = mk(), mk(), mk()
        tp = timeit(make_fb(flash_attention), (q, k, v))
        print(f"{B:>4} {H:>3} {S:>5} {tp:>10.3f} {traced_rows_per_step():>4} ",
              end="", flush=True)
        # deterministic in-kernel dropout: the reference's stochastic_mode
        # trades determinism for speed — this column shows the deterministic
        # TPU PRNG's actual cost, closing that question with data. Guarded:
        # a dropout-leg failure must not lose the printed pallas number.
        try:
            td = timeit(make_fb(lambda q, k, v: flash_attention(
                q, k, v, dropout_rate=0.1, dropout_rng=drop_rng)), (q, k, v))
            print(f"{td:>9.3f} ", end="", flush=True)
        except Exception:  # noqa: BLE001
            print(f"{'err':>9} ", end="", flush=True)
        try:
            # the naive XLA leg materializes O(S^2) buffers and can OOM HBM
            # at long S — never lose the already-measured pallas number
            tx = timeit(make_fb(xla_attn), (q, k, v))
            print(f"{tx:>8.3f} {tx / tp:>6.2f}x")
        except Exception as e:  # noqa: BLE001
            print(f"{'oom/err':>8} ({type(e).__name__})")


if __name__ == "__main__":
    main()

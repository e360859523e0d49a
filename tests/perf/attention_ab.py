"""Pallas flash kernel vs XLA-fused attention, fwd+bwd, on the real chip.

Decides where the kernel pays off (long sequences, sparsity, dropout) and
where XLA's own fusion is already optimal (short seq) — the measurement
SURVEY §7 calls for before hand-writing more Pallas.

Run on a TPU host (one process, it holds the chip):
    python tests/perf/attention_ab.py

Timing contract (see bench.py ``_timed_chain``): each measurement chains N
data-dependent iterations and ends in one scalar fetch, which waits for the
whole chain.
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.attention import flash_attention
from deepspeed_tpu.ops.transformer.transformer import _attention_core


def timeit(f, args, iters=20):
    q, k, v = args
    float(jnp.sum(f(q, k, v).astype(jnp.float32)))  # compile + settle
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = f(q, k, v)
        # thread a data dependency so iteration i+1 cannot start before i
        # finishes — independent dispatches could overlap on the device
        # queue and the single final fetch would understate ms/iter
        q = q + 0 * out[:1, :1, :1, :1]
    float(jnp.sum(out.astype(jnp.float32)))  # fetch waits for the chain
    return (time.perf_counter() - t0) / iters * 1e3


def make_fb(attn):
    @jax.jit
    def fb(q, k, v):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)

        _, g = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        return g[0] + g[1] + g[2]

    return fb


def xla_attn(q, k, v):
    """The model's own XLA-fused einsum chain: the layer's attention core
    with the kernel switched off by argument."""
    return _attention_core(q, k, v, None, 0.0, True, None, use_pallas=False)


def main():
    dev = jax.devices()[0]
    print(f"device: {dev.device_kind} ({dev.platform})")
    rng = np.random.RandomState(0)
    drop_rng = jax.random.PRNGKey(7)
    print(f"{'B':>4} {'H':>3} {'S':>5} {'pallas ms':>10} {'+drop ms':>9} "
          f"{'xla ms':>8} {'ratio':>6}")
    for B, H, S in ((64, 16, 128), (16, 16, 512), (4, 16, 2048), (1, 16, 8192)):
        D = 64
        mk = lambda: jnp.asarray(rng.randn(B, H, S, D).astype(np.float32) * 0.1,
                                 jnp.bfloat16)
        q, k, v = mk(), mk(), mk()
        tp = timeit(make_fb(flash_attention), (q, k, v))
        print(f"{B:>4} {H:>3} {S:>5} {tp:>10.2f} ", end="", flush=True)
        # deterministic in-kernel dropout: the reference's stochastic_mode
        # trades determinism for speed — this column shows the deterministic
        # TPU PRNG's actual cost, closing that question with data. Guarded:
        # a dropout-leg failure must not lose the printed pallas number.
        try:
            td = timeit(make_fb(lambda q, k, v: flash_attention(
                q, k, v, dropout_rate=0.1, dropout_rng=drop_rng)), (q, k, v))
            print(f"{td:>9.2f} ", end="", flush=True)
        except Exception:  # noqa: BLE001
            print(f"{'err':>9} ", end="", flush=True)
        try:
            # the naive XLA leg materializes O(S^2) buffers and can OOM HBM
            # at long S — never lose the already-measured pallas number
            tx = timeit(make_fb(xla_attn), (q, k, v))
            print(f"{tx:>8.2f} {tx / tp:>6.2f}x")
        except Exception as e:  # noqa: BLE001
            print(f"{'oom/err':>8} ({type(e).__name__})")


if __name__ == "__main__":
    main()

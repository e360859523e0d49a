"""The flash kernels vs XLA-fused attention, fwd+bwd, on the real chip.

The table ``ops/transformer/attention.py::materialises_scores`` rests on:
where the kernels pay off (long sequences, sparsity, O(S) memory) and where
XLA's own fusion of the materialised scores is faster. Both implementations
are called below the public entry's rule, so every leg runs at every shape
whatever the rule says there.

Run on a TPU host (one process, it holds the chip):
    python tests/perf/attention_ab.py

Timing contract: each measurement is ONE jitted program that chains N
data-dependent forward+backward passes in a ``fori_loop`` and ends in one
scalar fetch, so a pass of a millisecond is not timed by the host's dispatch
(which costs about two on a v5e host). ``rows`` is the traced
``Kernels/flash_attention/rows_per_step``: how many (batch, head) rows a
grid step of the kernels took at that shape. ``temp MB`` is the compiled
program's ``temp_size_in_bytes``: what one pass holds beside its arguments
(q, k, v, bias: the same for every leg), so the S x S tensors show there.

Legs, for the kernels (``kern``) and the materialised path (``dense``):
plain; with a key bias as BERT passes it; with dropout 0.1 (the kernels'
own generator; for ``dense`` a ``jax.random`` mask, and ``dense+drop gen``
one from the chip's generator through XLA); causal as GPT-2 training passes
it; ``model``: with the key bias under
``jax.checkpoint(dots_with_no_batch_dims_saveable)``, as the scanned blocks
run it (forward, recomputed forward, backward). ``xla16`` is
``_attention_core(use_pallas=False)``, which rounds the scores to the input
dtype before the softmax: the leg PR 30's table read, kept to compare.
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer import attention as attn
from deepspeed_tpu.ops.transformer.transformer import _attention_core


ITERS = 20
# the cells' shape and two more whose float32 scores are the same 64 MiB (the
# most XLA keeps in on-chip memory: the rule's budget), then 4x the scores a
# step up to 4 GiB
SHAPES = ((64, 16, 128), (16, 16, 256), (4, 16, 512),
          (16, 16, 512), (4, 16, 2048), (1, 16, 8192))
DROP_RNG = jax.random.PRNGKey(7)
RATE = 0.1


def kern(causal=False, rate=0.0):
    """The streaming kernels, below the rule."""
    def run(q, k, v, bias):
        seed = (jax.random.randint(DROP_RNG, (1,), 0, 2**31 - 1, dtype=jnp.int32)
                if rate else None)
        return attn._attention(q, k, v, bias, seed, None, attn.DEFAULT_BLOCK,
                               causal, False, rate)
    return run


def dense(causal=False):
    """The materialised path, below the rule."""
    def run(q, k, v, bias):
        return attn._attention_dense(q, k, v, bias, causal=causal)
    return run


def dense_drop(draw):
    """The materialised path's mathematics with dropout on the
    probabilities, the mask from ``draw(shape) -> bool``: the form that LOST
    to the kernels with ``jax.random`` masks (PERF.md, PR 32), so the
    library does not have it and the rule keeps dropout on the kernels."""
    def run(q, k, v, bias):
        scale = 1.0 / float(np.sqrt(q.shape[-1]))
        s = jnp.einsum("bhsd,bhtd->bhst", q, k,
                       preferred_element_type=jnp.float32) * scale
        probs = jax.nn.softmax(s + bias[:, None, None, :].astype(jnp.float32))
        probs = jnp.where(draw(probs.shape), probs / (1.0 - RATE), 0.0)
        return jnp.einsum("bhst,bhtd->bhsd", probs.astype(q.dtype), v,
                          preferred_element_type=jnp.float32).astype(q.dtype)
    return run


def threefry_mask(shape):
    return jax.random.bernoulli(DROP_RNG, 1.0 - RATE, shape)


def generator_mask(shape):
    """The same keep rate from the chip's own generator
    (``lax.rng_bit_generator``): what a materialised dropout would have to
    draw from to be worth a rule of its own."""
    state = jnp.tile(jax.random.key_data(DROP_RNG).astype(jnp.uint32), 2)
    _, bits = jax.lax.rng_bit_generator(state, shape, dtype=jnp.uint32)
    return bits >= jnp.uint32(int(RATE * 2**32))


def xla16(q, k, v, bias):
    return _attention_core(q, k, v, bias[:, None, None, :], 0.0, True, None,
                           use_pallas=False)


def as_model(run):
    return jax.checkpoint(
        run, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)


def measure(run, q, k, v, bias):
    """(ms a pass, temp bytes) of ``ITERS`` forward+backward passes of
    ``run`` in one program, each fed by the one before so that none can
    overlap or be folded away."""
    def fb(q, k, v, bias):
        def loss(q, k, v, bias):
            return jnp.sum(run(q, k, v, bias).astype(jnp.float32) ** 2)

        _, g = jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(q, k, v, bias)
        return g[0] + g[1] + g[2] + jnp.sum(g[3]).astype(q.dtype)

    def chain(q, k, v, bias):
        q = jax.lax.fori_loop(
            0, ITERS, lambda _, q: q + 0 * fb(q, k, v, bias)[:1, :1, :1, :1], q)
        return jnp.sum(q.astype(jnp.float32))

    compiled = jax.jit(chain).lower(q, k, v, bias).compile()
    float(compiled(q, k, v, bias))  # settle
    t0 = time.perf_counter()
    float(compiled(q, k, v, bias))  # the fetch waits for the whole chain
    ms = (time.perf_counter() - t0) / ITERS * 1e3
    return ms, compiled.memory_analysis().temp_size_in_bytes


def main():
    dev = jax.devices()[0]
    lines = [f"device: {dev.device_kind} ({dev.platform}); ms a forward+backward "
             f"pass / temp MB; bf16, head size 64"]
    print(lines[0], flush=True)
    rng = np.random.RandomState(0)
    for B, H, S in SHAPES:
        D = 64
        mk = lambda: jnp.asarray(rng.randn(B, H, S, D).astype(np.float32) * 0.1,
                                 jnp.bfloat16)
        q, k, v = mk(), mk(), mk()
        none = jnp.zeros((B, S), jnp.bfloat16)
        # BERT's additive key bias: a tenth of the keys padded out
        pads = jnp.asarray(np.where(rng.rand(B, S) < 0.1, -10000.0, 0.0),
                           jnp.bfloat16)
        legs = [
            ("kern", kern(), none), ("kern+bias", kern(), pads),
            ("kern+drop", kern(rate=RATE), none),
            ("kern+causal", kern(causal=True), none),
            ("kern model", as_model(kern()), pads),
            ("xla16", xla16, none),
            ("dense", dense(), none), ("dense+bias", dense(), pads),
            ("dense+drop", dense_drop(threefry_mask), none),
            ("dense+drop gen", dense_drop(generator_mask), none),
            ("dense+causal", dense(causal=True), none),
            ("dense model", as_model(dense()), pads),
        ]
        scores_mb = B * H * S * S * 4 / 2**20
        row = [f"({B},{H},{S}) fp32 scores {scores_mb:.0f} MB:"]
        for name, run, bias in legs:
            try:
                # a materialised leg can run out of HBM at a long sequence:
                # never lose the legs already measured
                ms, temp = measure(run, q, k, v, bias)
                cell = f"{name} {ms:.3f} / {temp / 2**20:.0f}"
            except Exception as e:  # noqa: BLE001
                cell = f"{name} failed ({type(e).__name__})"
            if name == "kern":
                cell += f" rows {attn.traced_rows_per_step()}"
            row.append(cell)
            print(f"  {cell}", file=sys.stderr, flush=True)
        lines.append("  ".join(row))
        print(lines[-1], flush=True)
    out = os.path.join(os.path.dirname(__file__), "..", "..", "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "attention_ab.txt"), "w") as f:
        f.write("\n".join(lines) + "\n")


if __name__ == "__main__":
    main()

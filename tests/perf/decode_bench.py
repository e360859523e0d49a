"""Decode-path scaling evidence: KV-cache generate() vs full-recompute.

The KV cache makes each new token O(1) in past length while the naive
loop (re-running the full forward on the growing sequence, the only
option without inference/generation.py) is O(S) per token — so total
generation cost is O(S) vs O(S^2). This harness measures both at a few
continuation lengths and writes DECODE_BENCH[_CPU].json with the
tokens/sec ratio. Run anywhere; the artifact records the platform.

    JAX_PLATFORMS=cpu python tests/perf/decode_bench.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference import generate
from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _timed_forward(model, params, ids, reps=3):
    """Mean seconds for one JITTED full forward at ``ids``' length (the
    fair baseline: a real naive loop would jit per length too)."""
    fwd = jax.jit(lambda p, i: model.apply(p, i, deterministic=True))
    jax.block_until_ready(fwd(params, ids))  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fwd(params, ids)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def main():
    platform = jax.devices()[0].platform
    cfg = GPT2Config(
        vocab_size=512, hidden_size=128, num_hidden_layers=4,
        num_attention_heads=4, max_position_embeddings=1024,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    model, params = init_gpt2(cfg, batch_size=1, seq_len=8, seed=0)
    prompt = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (1, 8)), jnp.int32)

    # correctness anchor: cache and naive paths emit identical greedy
    # tokens (small n so the naive per-length compiles stay cheap)
    ids = prompt
    for _ in range(8):
        logits = model.apply(params, ids, deterministic=True)
        ids = jnp.concatenate(
            [ids, jnp.argmax(logits[:, -1], axis=-1)[:, None]], axis=1)
    assert np.array_equal(
        np.asarray(generate(params, cfg, prompt, 8)),
        np.asarray(ids[:, prompt.shape[1]:])), "paths disagree"

    S = prompt.shape[1]
    f_lo = _timed_forward(model, params, jnp.zeros((1, S + 1), jnp.int32))
    fwd = jax.jit(lambda p, i: model.apply(p, i, deterministic=True))

    rows = []
    for n_new in (32, 128, 512):
        out_c = generate(params, cfg, prompt, n_new)  # compile
        jax.block_until_ready(out_c)
        t0 = time.perf_counter()
        out_c = generate(params, cfg, prompt, n_new)
        jax.block_until_ready(out_c)
        t_cache = time.perf_counter() - t0

        # deep-length parity anchor: the cache's LAST token at this full
        # length must equal the full forward's argmax on the sequence so
        # far (one compile, catches cache/position bugs past any boundary)
        ids_full = jnp.concatenate([prompt, out_c[:, :-1]], axis=1)
        last_ref = jnp.argmax(fwd(params, ids_full)[:, -1], axis=-1)
        assert np.array_equal(np.asarray(out_c[:, -1]), np.asarray(last_ref)), (
            f"cache diverges from full forward at length {S + n_new}")

        # Naive baseline cost ESTIMATED, not looped: the no-cache loop runs
        # one full forward per token on the growing sequence (plus one XLA
        # compile per distinct length, not counted here). Its execution
        # cost is n_new x the mean of the compiled forward at the start
        # and end lengths (the forward is ~linear in S at these sizes).
        f_hi = _timed_forward(model, params,
                              jnp.zeros((1, S + n_new), jnp.int32))
        t_naive = n_new * (f_lo + f_hi) / 2.0

        rows.append({
            "new_tokens": n_new,
            "kv_cache_tok_per_s": round(n_new / t_cache, 1),
            "naive_tok_per_s_est": round(n_new / t_naive, 1),
            "speedup_vs_naive_est": round(t_naive / t_cache, 2),
        })
        print(rows[-1], flush=True)

    out = {"platform": platform, "model": "gpt2-tiny(L4,H128)",
           "rows": rows, "complete": True}
    name = "DECODE_BENCH.json" if platform == "tpu" else "DECODE_BENCH_CPU.json"
    with open(os.path.join(REPO, name), "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Long-sequence demonstration: dense vs block-sparse attention scaling.

The reference's sparse-attention headline (docs/_posts/2020-09-09-sparse-
attention.md:28) is (a) sequences ~10x longer than the dense path can
handle and (b) up to 6.3x faster training at comparable lengths. This leg
produces the equivalent artifact for the TPU kernels: per sequence length,
fwd+bwd step time for

  - ``xla_dense``  : naive attention materializing the [B,H,S,S] scores —
                     the memory wall the reference's dense baseline hits;
  - ``flash``      : the Pallas flash kernel (O(S*D) memory, dense compute);
  - ``sparse``     : the same kernel with a banded block layout (+ one
                     global block), compute ∝ S instead of S^2 (TPU only:
                     off-TPU the fused kernel falls back to the dense
                     reference, so this row shows ~1x there);
  - ``sparse_xla`` : the UNFUSED block-sparse pipeline (MatMul sdd ->
                     sparse Softmax -> MatMul dsd, ops/sparse_attention/) —
                     packed [B,nnz,blk,blk] compute on every backend, so the
                     compute-propto-S ratio shows even on CPU.

Each measurement runs in its OWN subprocess so an OOM at long S is a row in
the artifact ("oom": true), not a crash — the dense path's failure point IS
the demonstration. Writes LONGSEQ_BENCH.json at the repo root.

Run: ``python tests/perf/longseq_bench.py`` on a TPU host (each child is the
one process holding the chip while it runs; the parent never touches JAX);
``JAX_PLATFORMS=cpu ...`` gives the CPU ratio shape, which is not a device
number.
"""

import json
import os
import subprocess
import sys
import time

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, REPO)

OUT = os.path.join(REPO, "LONGSEQ_BENCH.json")
BLOCK = 128
BAND = 1  # +/- one block around the diagonal
B, H, D = 1, 4, 64
CHILD_TIMEOUT = int(os.environ.get("LONGSEQ_CHILD_TIMEOUT", "900"))


def _measure(impl, S, iters):
    """Child-side: one fwd+bwd timing. Printed as a JSON line."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.transformer.attention import flash_attention

    dev = jax.devices()[0]
    rng = np.random.RandomState(0)
    dtype = jnp.bfloat16 if dev.platform == "tpu" else jnp.float32
    mk = lambda: jnp.asarray(rng.randn(B, H, S, D).astype(np.float32) * 0.1, dtype)
    q, k, v = mk(), mk(), mk()

    nb = S // BLOCK
    layout = np.zeros((H, nb, nb), np.int64)
    for i in range(nb):
        layout[:, i, 0] = 1  # global first block (BigBird-style anchor)
        for j in range(max(0, i - BAND), min(nb, i + BAND + 1)):
            layout[:, i, j] = 1

    if impl == "xla_dense":
        def attn(q, k, v):
            s = jnp.einsum("bhsd,bhtd->bhst", q, k) / np.sqrt(D)
            p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
            return jnp.einsum("bhst,bhtd->bhsd", p, v)
    elif impl == "flash":
        attn = flash_attention
    elif impl == "sparse":
        attn = lambda q, k, v: flash_attention(q, k, v, layout=layout)
    elif impl == "sparse_xla":
        from deepspeed_tpu.ops.sparse_attention.matmul import MatMul, Softmax

        sdd = MatMul(layout, BLOCK, "sdd", trans_b=True)   # q @ k^T, sparse out
        sm = Softmax(layout, BLOCK)
        dsd = MatMul(layout, BLOCK, "dsd")                 # probs @ v

        def attn(q, k, v):
            scores = sdd(q, k)
            p = sm(scores, scale=1.0 / np.sqrt(D))
            return dsd(p.astype(v.dtype), v)
    else:
        raise ValueError(impl)

    @jax.jit
    def fb(q, k, v):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)

        _, g = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        return g[0] + g[1] + g[2]

    float(jnp.sum(fb(q, k, v).astype(jnp.float32)))  # compile + settle
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fb(q, k, v)
        # data dependency: iteration i+1 waits for i, and the one final
        # fetch waits for the chain (see attention_ab.py)
        q = q + 0 * out[:1, :1, :1, :1]
    float(jnp.sum(out.astype(jnp.float32)))
    ms = (time.perf_counter() - t0) / iters * 1e3
    print("ROW " + json.dumps({
        "impl": impl, "seq": S, "ms": round(ms, 2),
        "device_kind": dev.device_kind, "platform": dev.platform,
    }), flush=True)


def _spawn(impl, S, iters):
    r = None
    try:
        r = subprocess.run(
            [sys.executable, __file__, "--child", impl, str(S), str(iters)],
            capture_output=True, text=True, timeout=CHILD_TIMEOUT, cwd=REPO,
        )
        for line in reversed(r.stdout.strip().splitlines()):
            if line.startswith("ROW "):
                return json.loads(line[4:])
    except subprocess.TimeoutExpired:
        return {"impl": impl, "seq": S, "timeout": True}
    err = (r.stderr or r.stdout).strip()[-400:] if r is not None else ""
    oom = "RESOURCE_EXHAUSTED" in err or "out of memory" in err.lower() or (
        r is not None and r.returncode in (-9, 137))  # OOM-killed
    return {"impl": impl, "seq": S, "oom": oom, "error": err[-200:]}


def main():
    seqs = [int(s) for s in os.environ.get(
        "LONGSEQ_SEQS", "1024,2048,4096,8192,16384").split(",")]
    iters = int(os.environ.get("LONGSEQ_ITERS", "5"))
    rows = []
    for S in seqs:
        for impl in ("xla_dense", "flash", "sparse", "sparse_xla"):
            row = _spawn(impl, S, iters)
            rows.append(row)
            print(json.dumps(row), flush=True)
            # persist after EVERY row: if the parent is killed mid-sweep
            # the completed measurements survive instead of being discarded
            # with the process
            _write_summary(rows, seqs)
    _write_summary(rows, seqs)


def _write_summary(rows, seqs):
    by = {(r["impl"], r["seq"]): r for r in rows}
    summary = {"rows": rows, "block": BLOCK, "band": BAND,
               "shape": {"B": B, "H": H, "D": D},
               "complete": len(rows) == len(seqs) * 4}
    ok = [r for r in rows if "ms" in r]
    if ok:
        platforms = {r["platform"] for r in ok}
        summary["device_kind"] = ok[0]["device_kind"]
        # children on different platforms must never be stamped (or
        # ratio'd) as one TPU measurement
        summary["platform"] = platforms.pop() if len(platforms) == 1 else "mixed"
        dense_ok = [r["seq"] for r in ok if r["impl"] == "xla_dense"]
        sparse_ok = [r["seq"] for r in ok if r["impl"] in ("sparse", "sparse_xla")]
        summary["max_seq_dense"] = max(dense_ok) if dense_ok else 0
        summary["max_seq_sparse"] = max(sparse_ok) if sparse_ok else 0
        ratios = {}
        for S in seqs:
            dense = [by.get(("xla_dense", S), {}).get("ms"),
                     by.get(("flash", S), {}).get("ms")]
            sparse = [by.get(("sparse", S), {}).get("ms"),
                      by.get(("sparse_xla", S), {}).get("ms")]
            d = min((x for x in dense if x), default=None)   # best dense
            s = min((x for x in sparse if x), default=None)  # best sparse
            if d and s:
                ratios[str(S)] = round(d / s, 2)
        summary["sparse_speedup_vs_dense"] = ratios
        if ratios:
            best_seq = max(ratios, key=lambda k: ratios[k])
            summary["headline"] = (
                f"block-sparse attention is {ratios[best_seq]}x faster than the "
                f"best dense path at seq {best_seq}"
                + (f"; dense tops out at {summary['max_seq_dense']}, sparse reaches "
                   f"{summary['max_seq_sparse']}"
                   if summary["max_seq_sparse"] > summary["max_seq_dense"] else "")
            )
    # TPU runs own LONGSEQ_BENCH.json; anything else (CPU ratio shape, mixed
    # platforms) goes to the _CPU file so a landed TPU artifact is never
    # clobbered by the docstring's CPU invocation.
    out = OUT if summary.get("platform") == "tpu" else OUT.replace(
        ".json", "_CPU.json")
    with open(out, "w") as f:
        json.dump(summary, f, indent=1)
    print("wrote", out)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        _measure(sys.argv[2], int(sys.argv[3]), int(sys.argv[4]))
    else:
        main()

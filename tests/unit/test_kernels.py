"""The kernels/ tier: Pallas fused decode + banded sparse attention
behind the op_builder-style registry.

Three layers of coverage, mirroring the tier's contract
(docs/kernels.md):

1. **Kernel parity** — the Pallas bodies (interpret mode on CPU) must
   match the composed-XLA fallback bitwise on the registry's probe case
   and to ULP-level across a shape grid (both run the literal shared
   math helpers; XLA fusion may still reassociate a last bit), plus a
   dense numpy oracle to fp32 tolerance, including odd query positions,
   partially-filled pages,
   the null-sink (base == 0) band case, and int8 pages with the
   quantization thresholds test_quantization.py established.
2. **Registry semantics** — probe caching, config-forced selection,
   ValueError on bad requests, call counters in the snapshot, and what
   a failed probe means: off-TPU a degrade to the XLA fallback with ONE
   edge-triggered ``jax/kernel_fallback`` instant; on a (patched) TPU
   backend a ``KernelProbeError`` carrying the compiler's message, with
   interpret mode never selected there.
3. **Integration** — ``generate()`` per kernel backend bitwise vs the
   dense greedy oracle, the serving continuous-vs-``generate()`` oracle
   per backend (mixed classes, speculation, int8 pool), CompileSentinel
   recompile pins for the new jitted programs, and ``transfer_free()``
   steady-state decode with the Pallas-interpret kernels armed.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu import kernels, telemetry
from deepspeed_tpu.inference.generation import generate
from deepspeed_tpu.inference.serving.families import gpt2 as serving_engine_mod
from deepspeed_tpu.inference.serving.config import ServingConfig
from deepspeed_tpu.inference.serving.engine import ServingEngine
from deepspeed_tpu.ops import column_write
from deepspeed_tpu.kernels.registry import KernelProbeError, KernelRegistry
from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2
from deepspeed_tpu.profiling import CompileSentinel, transfer_free
from deepspeed_tpu.runtime.config import get_serving_config


@pytest.fixture(autouse=True, scope="module")
def _drop_jit_caches():
    yield
    jax.clear_caches()


@pytest.fixture()
def clean_registry():
    """Tests that pin probe outcomes must not leak them into the
    process-global registry other tests (and the serving engine) read."""
    kernels.reset_registry()
    yield kernels.get_registry()
    kernels.reset_registry()


def _tiny_config():
    return GPT2Config(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=32,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)


@pytest.fixture(scope="module")
def model():
    cfg = _tiny_config()
    _, params = init_gpt2(cfg, batch_size=2, seq_len=4, seed=0)
    return cfg, params


# -- dense numpy oracles ----------------------------------------------------

def _dense_decode_oracle(q, pages_k, pages_v, tables, qpos):
    """Brute-force paged attention in float64: gather each lane's pages
    into a contiguous cache, causal-mask on global key position, dense
    softmax."""
    B, C, nh, hd = q.shape
    P, _, pt, _ = pages_k.shape
    mp = tables.shape[1]
    out = np.zeros((B, C, nh, hd))
    for b in range(B):
        k = np.concatenate([pages_k[tables[b, j]] for j in range(mp)], 1)
        v = np.concatenate([pages_v[tables[b, j]] for j in range(mp)], 1)
        kpos = np.arange(mp * pt)
        for c in range(C):
            s = np.einsum("nd,ntd->nt", q[b, c].astype(np.float64),
                          k.astype(np.float64)) / np.sqrt(hd)
            s = np.where(kpos[None] <= qpos[b, c], s, -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[b, c] = np.einsum("nt,ntd->nd", p, v.astype(np.float64))
    return out


def _dense_band_oracle(q, k_win, v_win, k_sink, v_sink, pos, base):
    """Brute-force sink+window band attention: window key i at global
    position base+i is valid iff <= pos; sink key j iff j < base."""
    N, nh, hd = q.shape
    W, pt = k_win.shape[2], k_sink.shape[2]
    out = np.zeros((N, nh, hd))
    for n in range(N):
        k = np.concatenate([k_sink[n], k_win[n]], 1).astype(np.float64)
        v = np.concatenate([v_sink[n], v_win[n]], 1).astype(np.float64)
        valid = np.concatenate([np.arange(pt) < base[n],
                                base[n] + np.arange(W) <= pos[n]])
        s = np.einsum("nd,ntd->nt", q[n].astype(np.float64), k) / np.sqrt(hd)
        s = np.where(valid[None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[n] = np.einsum("nt,ntd->nd", p, v)
    return out


def _paged_case(seed, B, C, nh, pt, hd, mp, P):
    rng = np.random.RandomState(seed)
    q = rng.randn(B, C, nh, hd).astype(np.float32)
    pk = rng.randn(P, nh, pt, hd).astype(np.float32)
    pv = rng.randn(P, nh, pt, hd).astype(np.float32)
    tables = np.stack([rng.permutation(P)[:mp] for _ in range(B)]).astype(
        np.int32)
    qpos = np.sort(rng.randint(0, mp * pt, (B, C)), axis=1).astype(np.int32)
    return q, pk, pv, tables, qpos


# -- 1. kernel parity -------------------------------------------------------

@pytest.mark.parametrize("shape", [
    (2, 1, 2, 4, 8, 2, 5),     # single-query decode step
    (1, 3, 4, 8, 16, 3, 7),    # multi-query chunk, odd C
    (3, 2, 2, 4, 8, 4, 9),     # more lanes than pages-per-lane
])
def test_decode_attend_parity_grid(shape):
    """Pallas-interpret == XLA fallback bitwise (same literal math, same
    op sequence) and both match the dense float64 oracle."""
    B, C, nh, pt, hd, mp, P = shape
    q, pk, pv, tables, qpos = _paged_case(3, B, C, nh, pt, hd, mp, P)
    got_p = np.asarray(kernels.decode_attend(
        jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv),
        jnp.asarray(tables), jnp.asarray(qpos), page_tokens=pt,
        dtype=jnp.float32, impl="pallas", interpret=True))
    got_x = np.asarray(kernels.decode_attend(
        jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv),
        jnp.asarray(tables), jnp.asarray(qpos), page_tokens=pt,
        dtype=jnp.float32, impl="xla"))
    # the shared math helper keeps the op SEQUENCE identical; XLA is
    # still free to fuse/reassociate differently around lax.map vs the
    # interpreted grid, so the general grid pins ULP-level agreement
    # (the probe case below stays exactly bitwise)
    np.testing.assert_allclose(got_p, got_x, rtol=3e-7, atol=1e-7)
    want = _dense_decode_oracle(q, pk, pv, tables, qpos)
    np.testing.assert_allclose(got_p, want, rtol=1e-4, atol=1e-4)


def test_decode_attend_probe_case_is_bitwise():
    """The registry's own probe instance: Pallas-interpret == XLA
    fallback bit-for-bit (the parity oracle the availability probe
    enforces at load)."""
    from deepspeed_tpu.kernels.decode_attention import _probe_case
    q, pk, pv, tables, qpos, pt = _probe_case()
    got_p = np.asarray(kernels.decode_attend(
        q, pk, pv, tables, qpos, page_tokens=pt, dtype=jnp.float32,
        impl="pallas", interpret=True))
    got_x = np.asarray(kernels.decode_attend(
        q, pk, pv, tables, qpos, page_tokens=pt, dtype=jnp.float32,
        impl="xla"))
    assert np.array_equal(got_p, got_x)


def test_decode_attend_odd_positions_mid_page():
    """Odd query positions that land mid-page: only the occupied prefix
    of the last page may contribute (the causal mask, not page padding,
    draws the boundary)."""
    B, C, nh, pt, hd, mp, P = 2, 2, 2, 8, 8, 3, 7
    q, pk, pv, tables, _ = _paged_case(11, B, C, nh, pt, hd, mp, P)
    qpos = np.asarray([[0, 5], [9, 17]], np.int32)      # incl. position 0
    got = np.asarray(kernels.decode_attend(
        jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv),
        jnp.asarray(tables), jnp.asarray(qpos), page_tokens=pt,
        dtype=jnp.float32, impl="pallas", interpret=True))
    want = _dense_decode_oracle(q, pk, pv, tables, qpos)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_decode_attend_int8_pages_fused_dequant():
    """int8 pages with per-(page, head) scales consumed directly: the
    dequant fuses into the QK/PV matmuls. Pallas-interpret == XLA
    fallback bitwise; both within the int8 quantization thresholds of
    the dense oracle over dequantized pages."""
    B, C, nh, pt, hd, mp, P = 2, 1, 2, 8, 16, 2, 5
    q, pk, pv, tables, qpos = _paged_case(5, B, C, nh, pt, hd, mp, P)
    sk = (np.abs(pk).max(axis=(2, 3)) / 127.0 + 1e-8).astype(np.float32)
    sv = (np.abs(pv).max(axis=(2, 3)) / 127.0 + 1e-8).astype(np.float32)
    qk = np.clip(np.rint(pk / sk[:, :, None, None]), -127, 127)
    qv = np.clip(np.rint(pv / sv[:, :, None, None]), -127, 127)
    args = (jnp.asarray(q), jnp.asarray(qk, jnp.int8),
            jnp.asarray(qv, jnp.int8), jnp.asarray(tables),
            jnp.asarray(qpos))
    kw = dict(page_tokens=pt, dtype=jnp.float32,
              k_scale=jnp.asarray(sk), v_scale=jnp.asarray(sv))
    got_p = np.asarray(kernels.decode_attend(
        *args, impl="pallas", interpret=True, **kw))
    got_x = np.asarray(kernels.decode_attend(*args, impl="xla", **kw))
    np.testing.assert_allclose(got_p, got_x, rtol=3e-7, atol=1e-7)
    want = _dense_decode_oracle(q, qk * sk[:, :, None, None],
                                qv * sv[:, :, None, None], tables, qpos)
    # established int8 KV tolerance (test_quantization.py): the scores
    # see exact dequantized values, so only fp accumulation order drifts
    np.testing.assert_allclose(got_p, want, rtol=1e-3, atol=1e-3)


def test_chunk_attend_matches_paged_route():
    """The contiguous adapter views [B, nh, S, hd] caches as identity-
    table page runs — bitwise the same kernel as the pool path, and the
    reason the continuous-vs-generate() oracle holds by construction."""
    B, C, nh, pt, hd = 2, 2, 2, 4, 8
    S = 3 * pt
    rng = np.random.RandomState(7)
    q = rng.randn(B, C, nh, hd).astype(np.float32)
    ck = rng.randn(B, nh, S, hd).astype(np.float32)
    cv = rng.randn(B, nh, S, hd).astype(np.float32)
    qpos = np.asarray([[3, 6], [7, 11]], np.int32)
    got = np.asarray(kernels.chunk_attend(
        jnp.asarray(q), jnp.asarray(ck), jnp.asarray(cv),
        jnp.asarray(qpos), pt, jnp.float32, impl="pallas", interpret=True))
    # oracle: the adapter's identity tables over a row-major [B*mp]
    # paging of the contiguous cache
    mp = S // pt
    pages_k = np.stack([ck[b, :, j * pt:(j + 1) * pt]
                        for b in range(B) for j in range(mp)])
    pages_v = np.stack([cv[b, :, j * pt:(j + 1) * pt]
                        for b in range(B) for j in range(mp)])
    tables = np.arange(B * mp, dtype=np.int32).reshape(B, mp)
    want = _dense_decode_oracle(q, pages_k, pages_v, tables, qpos)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("null_sink", [False, True])
def test_band_attend_parity(null_sink):
    """Banded sink+window kernel vs fallback bitwise and vs the dense
    band oracle; ``null_sink`` pins base == 0 where every sink key is
    masked (the window already covers the anchor page)."""
    N, nh, W, pt, hd = 5, 2, 12, 4, 8
    rng = np.random.RandomState(13)
    q = rng.randn(N, nh, hd).astype(np.float32)
    kw = rng.randn(N, nh, W, hd).astype(np.float32)
    vw = rng.randn(N, nh, W, hd).astype(np.float32)
    ks = rng.randn(N, nh, pt, hd).astype(np.float32)
    vs = rng.randn(N, nh, pt, hd).astype(np.float32)
    if null_sink:
        base = np.zeros(N, np.int32)
        pos = np.asarray([0, 3, 5, 8, 11], np.int32)
    else:
        base = np.asarray([4, 4, 8, 8, 12], np.int32)
        pos = base + np.asarray([0, 5, 3, 11, 7], np.int32)
    args = tuple(jnp.asarray(a) for a in (q, kw, vw, ks, vs, pos, base))
    got_p = np.asarray(kernels.band_attend(
        *args, dtype=jnp.float32, impl="pallas", interpret=True))
    got_x = np.asarray(kernels.band_attend(
        *args, dtype=jnp.float32, impl="xla"))
    np.testing.assert_allclose(got_p, got_x, rtol=3e-7, atol=1e-7)
    want = _dense_band_oracle(q, kw, vw, ks, vs, pos, base)
    np.testing.assert_allclose(got_p, want, rtol=1e-4, atol=1e-4)


def test_chunk_band_attend_pallas_matches_xla():
    """The generate()-side band adapter: window slicing is shared XLA,
    so Pallas vs fallback stays bitwise through the full entry point,
    on both the direct path (C <= pt) and the pt-blocked scan path."""
    B, nh, pt, hd = 2, 2, 4, 8
    S = 6 * pt
    rng = np.random.RandomState(17)
    ck = rng.randn(B, nh, S, hd).astype(np.float32)
    cv = rng.randn(B, nh, S, hd).astype(np.float32)
    for C, qp in ((2, [[9, 10], [17, 18]]),
                  (8, [list(range(8, 16)), list(range(12, 20))])):
        q = rng.randn(B, C, nh, hd).astype(np.float32)
        qpos = np.asarray(qp, np.int32)
        outs = [np.asarray(kernels.chunk_band_attend(
            jnp.asarray(q), jnp.asarray(ck), jnp.asarray(cv),
            jnp.asarray(qpos), pt, jnp.float32, impl=impl, interpret=True))
            for impl in ("pallas", "xla")]
        np.testing.assert_allclose(outs[0], outs[1], rtol=3e-7, atol=1e-7)


# -- 2. registry semantics --------------------------------------------------

def test_registry_probe_caches_and_resolves():
    reg = KernelRegistry()
    calls = []

    def probe_fn(interpret):
        calls.append(interpret)

    reg.register("toy", probe_fn)
    assert reg.names() == ("toy",)
    assert reg.probe("toy") == (True, None)
    assert reg.probe("toy") == (True, None)
    assert len(calls) == 1                       # cached after first run
    impl, interp = reg.resolve("toy")
    assert impl == "pallas"
    assert interp == (jax.default_backend() != "tpu")
    assert reg.resolve("toy", requested="xla") == ("xla", interp)
    assert reg.resolve("toy", interpret=False) == ("pallas", False)
    with pytest.raises(ValueError, match="kernel impl"):
        reg.resolve("toy", requested="cuda")


def test_registry_unknown_kernel_is_unavailable_not_fatal():
    reg = KernelRegistry()
    ok, err = reg.probe("nope")
    assert not ok and "unknown kernel" in err
    assert reg.resolve("nope") == ("xla", reg.interpret_default())


def test_registry_probe_failure_degrades_with_one_instant():
    """A failed probe must degrade to the XLA fallback (never crash) and
    emit the ``jax/kernel_fallback`` instant exactly once — the
    edge-trigger keeps a hot resolve loop from flooding the trace."""
    reg = KernelRegistry()

    def broken(interpret):
        raise RuntimeError("no pallas lowering on this backend")

    reg.register("broken", broken)
    tracer, _ = telemetry.configure(True)
    try:
        tracer.events(drain=True)
        for _ in range(3):
            assert reg.resolve("broken", requested="pallas")[0] == "xla"
        falls = [e for e in tracer.events()
                 if e["name"] == "jax/kernel_fallback"]
        assert len(falls) == 1
        assert falls[0]["args"]["kernel"] == "broken"
        assert "no pallas lowering" in falls[0]["args"]["error"]
    finally:
        telemetry.configure(False)
    snap = reg.snapshot()["broken"]
    assert snap["available"] is False and snap["selected"] == "xla"
    assert "no pallas lowering" in snap["probe_error"]


@pytest.fixture()
def tpu_backend(monkeypatch):
    monkeypatch.setattr("deepspeed_tpu.kernels.registry._on_tpu", lambda: True)


def _broken_probe(interpret):
    raise RuntimeError("Mosaic failed to compile TPU kernel")


def test_failed_probe_raises_on_tpu(tpu_backend):
    """On the chip a kernel that does not compile must never be reported
    as running: asking for Pallas raises with the compiler's message,
    and "xla" stays the explicit way to get the twin."""
    reg = KernelRegistry().register("broken", _broken_probe)
    for requested in (None, "pallas"):
        with pytest.raises(KernelProbeError, match="Mosaic failed to compile"):
            reg.resolve("broken", requested=requested)
    assert reg.resolve("broken", requested="xla") == ("xla", False)
    with pytest.raises(KernelProbeError, match="unknown kernel"):
        reg.resolve("nope")


def test_no_interpret_mode_on_tpu(tpu_backend):
    reg = KernelRegistry().register("fine", lambda interpret: None)
    assert reg.resolve("fine") == ("pallas", False)
    with pytest.raises(ValueError, match="kernel_interpret=True on a TPU"):
        reg.resolve("fine", interpret=True)
    with pytest.raises(ValueError, match="kernel_interpret=True on a TPU"):
        kernels.resolve("pallas_decode", interpret=True)


def test_serving_probe_failure_raises_on_tpu(model, clean_registry,
                                             tpu_backend):
    """The engine resolves its kernels at construction, so a broken
    kernel fails the build instead of serving from the twin."""
    cfg, params = model
    clean_registry.force_probe_result("decode_attention", False,
                                      error="simulated lowering failure")
    with pytest.raises(KernelProbeError, match="simulated lowering failure"):
        ServingEngine(params, cfg, ServingConfig(
            max_slots=2, max_seq_len=32, prompt_buckets=(4, 8),
            kv_page_tokens=4, attention_impl="pallas_decode"))


def test_registry_snapshot_counts_calls(clean_registry):
    reg = clean_registry
    reg.record_call("decode_attention", "pallas")
    reg.record_call("decode_attention", "pallas")
    reg.record_call("sparse_attention", "xla")
    snap = reg.snapshot()
    assert snap["decode_attention"]["calls"]["pallas"] == 2
    assert snap["sparse_attention"]["calls"]["xla"] == 1
    # builtin kernels probe clean on CPU (interpret mode)
    assert reg.resolve("decode_attention") == ("pallas", True)
    assert snap["decode_attention"]["probed"] in (True, False)


def test_resolve_is_identity_for_non_kernel_backends():
    assert kernels.kernel_for_backend("dense") is None
    assert kernels.kernel_for_backend("pallas_decode") == "decode_attention"
    assert kernels.kernel_for_backend("pallas_sparse") == "sparse_attention"
    assert kernels.resolve("flash") == (None, False)
    assert kernels.resolve("sparse_xla") == (None, False)


def test_force_probe_result_hook(clean_registry):
    reg = clean_registry
    reg.force_probe_result("decode_attention", False, error="pinned")
    assert reg.resolve("decode_attention") == ("xla", True)
    assert reg.snapshot()["decode_attention"]["probe_error"] == "pinned"
    reg.force_probe_result("decode_attention", True)
    assert reg.resolve("decode_attention")[0] == "pallas"


# -- 3a. generate() integration ---------------------------------------------

def _gen(params, cfg, prompt, n_new, **kw):
    out = generate(params, cfg, jnp.asarray([prompt], jnp.int32), n_new, **kw)
    return np.asarray(out)[0].tolist()


def test_generate_kernel_backends_match_dense_oracle(model):
    """Greedy tokens through both kernel backends — Pallas AND the
    forced-XLA fallback — must equal the dense path bitwise (band
    invariance: the tiny model's whole context fits inside sink +
    window, so the sparse band is dense here)."""
    cfg, params = model
    prompts = [[5, 9, 3], [7, 1, 2, 2, 4]]
    for prompt in prompts:
        want = _gen(params, cfg, prompt, 6)
        for be in ("pallas_decode", "pallas_sparse"):
            for kern in (None, "pallas", "xla"):
                got = _gen(params, cfg, prompt, 6, attn_impl=be,
                           kv_page_tokens=4, attention_kernel=kern)
                assert got == want, (be, kern, got, want)


def test_generate_rejects_kernel_knobs_on_non_kernel_backends(model):
    cfg, params = model
    with pytest.raises(ValueError, match="attention_kernel"):
        _gen(params, cfg, [1, 2], 2, attn_impl="dense",
             attention_kernel="pallas")


# -- 3b. config validation --------------------------------------------------

def test_serving_config_kernel_keys_parse_and_default():
    cfg = get_serving_config({"serving": {
        "attention_impl": "pallas_decode", "attention_kernel": "xla",
        "kernel_interpret": True}})
    assert cfg.attention_kernel == "xla" and cfg.kernel_interpret is True
    cfg = get_serving_config({"serving": {}})
    assert cfg.attention_kernel is None and cfg.kernel_interpret is None


def test_serving_config_kernel_keys_validate():
    with pytest.raises(ValueError, match="attention_kernel"):
        get_serving_config({"serving": {"attention_kernel": "cuda"}})
    with pytest.raises(ValueError, match="kernel_interpret"):
        get_serving_config({"serving": {"kernel_interpret": "yes"}})


def test_serving_config_accepts_kernel_backend_names():
    for be in ("pallas_decode", "pallas_sparse"):
        assert get_serving_config(
            {"serving": {"attention_impl": be}}).attention_impl == be


# -- 3c. serving integration ------------------------------------------------

def _engine(cfg, params, **overrides):
    kw = dict(max_slots=3, max_queue=8, max_seq_len=32,
              prompt_buckets=(4, 8), kv_page_tokens=4)
    kw.update(overrides)
    return ServingEngine(params, cfg, ServingConfig(**kw))


def _serve(eng, prompts, n_new=6):
    futs = [eng.submit(p, max_new_tokens=n_new) for p in prompts]
    eng.drain(max_steps=300)
    return [list(f.result(timeout=1)) for f in futs]


@pytest.mark.parametrize("backend", ["pallas_decode", "pallas_sparse"])
def test_serving_oracle_kernel_backends(model, backend):
    """The continuous-vs-generate() oracle per kernel backend: slot
    churn, mixed lengths, and the paged pool must not perturb a single
    bit vs the one-shot path through the SAME kernel."""
    cfg, params = model
    prompts = [[5, 9, 3], [7, 1], [2, 2, 4, 6, 1], [9, 8, 7, 6, 5, 4, 3]]
    eng = _engine(cfg, params, attention_impl=backend)
    got = _serve(eng, prompts)
    for p, g in zip(prompts, got):
        assert g == _gen(params, cfg, p, 6, attn_impl=backend,
                         kv_page_tokens=4), (backend, p)


def test_serving_mixed_kernel_and_seam_classes(model):
    """A bucket ladder mixing all four lane classes (dense, kernel-full,
    kernel-window) in ONE engine: each lane follows its own backend's
    oracle while sharing the pool and the step loop."""
    cfg, params = model
    eng = _engine(cfg, params, max_slots=4, prompt_buckets=(2, 4, 8),
                     attention_impl={"default": "dense",
                                     4: "pallas_decode",
                                     8: "pallas_sparse"})
    prompts = [[5, 9], [7, 1, 2], [2, 2, 4, 6, 1, 3]]
    impls = ["dense", "pallas_decode", "pallas_sparse"]
    got = _serve(eng, prompts)
    for p, g, imp in zip(prompts, got, impls):
        assert g == _gen(params, cfg, p, 6, attn_impl=imp,
                         kv_page_tokens=4), imp


@pytest.mark.parametrize("backend", ["pallas_decode", "pallas_sparse"])
def test_serving_speculative_kernel_backends(model, backend):
    """speculative_k > 0 routes the verify program through
    ``_spec_step_kernel_jit`` — output-identical to k=0 per backend."""
    cfg, params = model
    prompts = [[5, 9, 3], [2, 2, 4, 6, 1]]
    eng = _engine(cfg, params, attention_impl=backend, speculative_k=2)
    got = _serve(eng, prompts)
    for p, g in zip(prompts, got):
        assert g == _gen(params, cfg, p, 6, attn_impl=backend,
                         kv_page_tokens=4), backend


def test_serving_int8_pool_kernel_matches_seam(model):
    """int8 pages consumed directly by the fused kernel must emit the
    same tokens as the established dequant-at-use seam backends over
    the same quantized storage."""
    cfg, params = model
    prompts = [[5, 9, 3], [7, 1]]
    for kern_be, seam_be in (("pallas_decode", "flash"),
                             ("pallas_sparse", "sparse_xla")):
        a = _engine(cfg, params, attention_impl=kern_be,
                       kv_cache_dtype="int8")
        b = _engine(cfg, params, attention_impl=seam_be,
                       kv_cache_dtype="int8")
        assert _serve(a, prompts) == _serve(b, prompts), kern_be


def test_serving_probe_failure_degrades_to_xla(model, clean_registry):
    """The degrade contract end-to-end: a broken Pallas install (pinned
    probe failure) must leave serving fully functional on the XLA
    fallback — same tokens, fallback recorded in the snapshot."""
    cfg, params = model
    clean_registry.force_probe_result("decode_attention", False,
                                      error="simulated lowering failure")
    eng = _engine(cfg, params, attention_impl="pallas_decode")
    assert eng.family._kernel_impl["pallas_decode"] == "xla"
    prompts = [[5, 9, 3], [7, 1]]
    got = _serve(eng, prompts)
    for p, g in zip(prompts, got):
        assert g == _gen(params, cfg, p, 6, attn_impl="pallas_decode",
                         kv_page_tokens=4, attention_kernel="xla")
    snap = kernels.registry_snapshot()["decode_attention"]
    assert snap["selected"] == "xla"
    assert snap["calls"]["xla"] > 0


def test_engine_rejects_kernel_knob_without_kernel_backend(model):
    cfg, params = model
    with pytest.raises(ValueError, match="attention_kernel"):
        _engine(cfg, params, attention_impl="dense",
                attention_kernel="pallas")
    with pytest.raises(ValueError, match="kernel_interpret"):
        _engine(cfg, params, attention_impl="pallas_decode",
                kernel_interpret="yes")


def test_kernel_program_compile_pins(model):
    """Recompile pins for the new jitted programs: steady-state decode
    with a kernel backend must reuse ONE compiled decode program, and
    each prefill bucket compiles at most once."""
    cfg, params = model
    decode_sent = CompileSentinel(
        serving_engine_mod._decode_step_kernel_jit, 1,
        name="kernel decode step")
    prefill_sent = CompileSentinel(
        serving_engine_mod._prefill_batch_kernel_jit, 2,
        name="kernel prefill")
    eng = _engine(cfg, params, attention_impl="pallas_decode")
    prompts = [[5, 9, 3], [7, 1], [2, 2, 4, 6, 1]]   # buckets 4, 4, 8
    got = _serve(eng, prompts)
    assert all(got)
    assert decode_sent.check() <= 1
    assert prefill_sent.check() <= 2


def test_spec_kernel_program_compile_pin(model):
    cfg, params = model
    spec_sent = CompileSentinel(
        serving_engine_mod._spec_step_kernel_jit, 1,
        name="kernel spec step")
    eng = _engine(cfg, params, attention_impl="pallas_sparse",
                     speculative_k=2)
    _serve(eng, [[5, 9, 3], [7, 1, 2]])
    assert spec_sent.check() <= 1


@pytest.mark.parametrize("backend", ["pallas_decode", "pallas_sparse"])
def test_steady_state_transfer_free_kernel(model, backend):
    """transfer_free() holds with the Pallas-interpret kernels armed:
    the kernel programs take only device operands + static selection, so
    steady-state decode stays at ONE explicit host read per step."""
    cfg, params = model
    eng = _engine(cfg, params, attention_impl=backend)
    prompts = [[5, 9, 3], [7, 1, 2, 4]]
    wants = [_gen(params, cfg, p, 8, attn_impl=backend, kv_page_tokens=4)
             for p in prompts]
    futs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.step()
    eng.step()
    assert eng.lanes.dirty is False and len(eng.lanes.requests) == 2
    with transfer_free():
        for _ in range(4):
            stats = eng.step()
            assert stats["decoded"] == 2
    eng.drain(max_steps=100)
    for f, want in zip(futs, wants):
        assert list(f.result(timeout=1)) == want


def test_snapshot_exposes_kernel_registry(model):
    """The serving /snapshot contract: a ``kernels`` section mirrors the
    registry (selection + call counters) so fleet scrapes can SLO on
    silent fallback."""
    cfg, params = model
    eng = _engine(cfg, params, attention_impl="pallas_decode")
    _serve(eng, [[5, 9, 3]])
    snap = kernels.registry_snapshot()
    assert snap["decode_attention"]["calls"]["pallas"] > 0
    assert snap["decode_attention"]["selected"] == "pallas"


# ---------------------------------------------------------------------------
# column_write: one new column in each lane's block, in place
# ---------------------------------------------------------------------------

def _pages_of(lanes, pages, rng):
    """Distinct pages for the lanes, but lanes 1 and 3 on the spare page 0."""
    index = 1 + rng.permutation(pages - 1)[:lanes]
    index[[1, 3]] = 0
    return index.tolist()


# pool shape, (row, an index array an axis up to the block), a traced row,
# values no arithmetic may touch in lanes 5 and 6 (pages of their own)
_COLUMN_CASES = {
    "pages": ((2, 9, 24, 16), (1, [3, 0, 5, 0, 8]), False, False),
    "rings": ((3, 5, 2, 24, 16), (2, [0, 1, 2, 3, 4], [1, 0, 1, 1, 0]),
              False, False),
    "rings_of_one_block": ((3, 5, 1, 40, 8),
                           (0, [0, 1, 2, 3, 4], [0, 0, 0, 0, 0]),
                           False, False),
    # Ouro's block: 8 lanes of [2048, 128], the cache row traced
    "ouro_block_traced_row": (
        (3, 9, 2048, 128), (2, [3, 0, 5, 0, 8, 1, 7, 2]), True, False),
    # lanes no multiple of a tile's 8 or 16 rows
    "lanes_127": ((2, 130, 24, 16),
                  (1, _pages_of(127, 130, np.random.default_rng(7))),
                  False, False),
    # a lane beside others whose rows hold inf, NaN and -0.0
    "neighbours_inf_nan": ((2, 12, 24, 16), (1, [3, 0, 5, 0, 8, 1, 7, 2, 9]),
                           True, True),
}


@pytest.mark.parametrize("case", list(_COLUMN_CASES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_column_write_kernel_is_its_plain_twin(case, dtype):
    """The Pallas kernel (interpreted here) and the gather, ``where`` and
    scatter the CPU runs write the same pool, bit for bit: column ``col``
    of each lane's block and nothing else, a lane with ``col`` -1 nothing
    at all. Lanes 1 and 3 of the paged cases both name the spare page 0,
    which may hold either's column: left out of the comparison, and neither
    path fails on it. A lane's ``inf``, ``NaN`` and ``-0.0`` reach that
    lane's column as they are and no other lane's (the kernel turns a row
    into a column itself: a sum or a product over the lanes of a tile would
    spread the first two and lose the sign of the third)."""
    shape, at, traced, poisoned = _COLUMN_CASES[case]
    rng = np.random.default_rng(3)
    n, *index = at
    lanes, T = len(index[0]), shape[-1]
    pool = jnp.asarray(rng.normal(size=shape), dtype)
    new = rng.normal(size=(lanes, shape[-2])).astype(np.float32)
    if poisoned:
        new[5, ::3], new[5, 1::3], new[5, 2::3] = np.inf, np.nan, -0.0
        new[6, ::2], new[6, 1::2] = -np.inf, np.nan
        new[4, 5], new[lanes - 1, 0] = -0.0, np.nan
    new = jnp.asarray(new, dtype)
    col = rng.integers(0, T, lanes)
    col[:5] = [2, 7, -1, T - 1, 0]
    col = jnp.asarray(col, jnp.int32)
    index = [jnp.asarray(i, jnp.int32) for i in index]
    plain, fused = (
        np.asarray(jax.jit(lambda pool, n, index, new, col: write(
            pool, (n, *index), new, col), static_argnums=() if traced else 1)(
                pool, jnp.int32(n) if traced else n, index, new, col).astype(
                    jnp.float32))
        for write in (column_write.write_columns, functools.partial(
            column_write._write_columns_pallas, interpret=True)))
    want = np.array(pool.astype(jnp.float32))
    for b, c in enumerate(np.asarray(col)):
        if c >= 0:
            want[(n, *(i[b] for i in index))][:, c] = np.asarray(
                new.astype(jnp.float32))[b]
    rows = slice(1, None) if len(index) == 1 else slice(None)  # spare page
    for got in (plain, fused):
        np.testing.assert_array_equal(got[:, rows].view(np.uint32),
                                      want[:, rows].view(np.uint32))

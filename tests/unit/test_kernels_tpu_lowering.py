"""Every Pallas kernel the repo ships must LOWER for a TPU.

The CPU suite runs the kernels with ``interpret=True``, which never
touches the Pallas-to-Mosaic lowering — so a kernel Mosaic cannot take
(a vector load from SMEM, an illegal block tiling, a batched contraction
without a leading batch dimension) stays green here and only fails on
the chip. ``jit(f).trace(...).lower(lowering_platforms=("tpu",))`` runs
that lowering on the CPU host: no chip, no libtpu call, about a second a
kernel. Shapes are the ones ``chip_smoke.py`` runs: BERT-large training
attention and the GPT-2-large serving tier (20 heads of 64, 128-token
pages).

The ``slow`` tests go one step further and run the real Mosaic/XLA:TPU
compiler from the installed libtpu against a v5e topology description,
which also catches what only the TPU compiler decides: an accumulator dtype
Mosaic rejects, VMEM overflow, a layout that blows a buffer up past HBM.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu import kernels
from deepspeed_tpu.models import glm_dsa as glm_mod
from deepspeed_tpu.models import keye as keye_mod
from deepspeed_tpu.models import paged_layers
from deepspeed_tpu.ops import column_write, paged_decode, paged_prefill
from deepspeed_tpu.ops.transformer import attention as attn_mod
from deepspeed_tpu.ops.transformer.attention import flash_attention

SDS = jax.ShapeDtypeStruct
NH, HD, PT = 20, 64, 128          # GPT-2 large heads, default KV page


def _lower_tpu(fn, *args):
    return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))


def _text_without_locations(lowered):
    """The lowered module's text with each Mosaic kernel's serialized body
    (base64 MLIR bytecode, which embeds source lines and differs from one
    trace to the next) replaced by its MLIR without debug locations."""
    import base64
    import re

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def body(match):
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            return module.operation.get_asm(enable_debug_info=False)

    return re.sub(r'\\22body\\22: \\22([^\\]*)\\22', body,
                  lowered.as_text())


def _assert_mosaic(lowered, n_kernels):
    text = lowered.as_text()
    assert text.count("tpu_custom_call") >= n_kernels, (
        f"expected >= {n_kernels} Mosaic custom call(s) in the lowered "
        f"module, found {text.count('tpu_custom_call')}")


@pytest.fixture()
def pallas_path(monkeypatch):
    """flash_attention picks its implementation from the default backend,
    which is the CPU here: force the TPU branch so the trace holds the
    kernels being lowered."""
    monkeypatch.setattr(attn_mod, "_on_tpu", lambda: True)


def _banded_layout(heads, nb):
    layout = np.zeros((heads, nb, nb), np.int64)
    for i in range(nb):
        layout[:, i, max(0, i - 1):i + 1] = 1
    return layout


def _bert_attention_grad(dropout, **kw):
    rng = jax.random.PRNGKey(0)

    def loss(q, k, v):
        out = flash_attention(q, k, v, dropout_rate=dropout,
                              dropout_rng=rng if dropout else None, **kw)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    return jax.grad(loss, argnums=(0, 1, 2))


# Calls the rule (``materialises_scores``) leaves to the kernels, each by
# what it shows of itself: dropout at BERT-large's seq 128 / micro-batch 64;
# twice that batch (128 MiB of float32 scores on the device); seq 512.
_KERNEL_CALLS = {
    "s128_dropout": ((64, 16, 128, 64), 0.1),
    "s128_batch128": ((128, 16, 128, 64), 0.0),
    "s512": ((16, 16, 512, 64), 0.0),
    "s512_dropout": ((16, 16, 512, 64), 0.1),
}


@pytest.mark.parametrize("call", sorted(_KERNEL_CALLS))
def test_training_flash_fwd_bwd_lowers(pallas_path, monkeypatch, call):
    """Forward kernel plus both backward kernels, with and without
    in-kernel dropout, several (batch, head) rows a grid step; and the rule
    costs such a call nothing: the lowered text, source locations
    aside, is what it is with the rule switched off, which is the entry as
    it was before the rule (PERF.md PR 32 compares it with the parent
    commit's own the same way)."""
    shape, dropout = _KERNEL_CALLS[call]
    qkv = SDS(shape, jnp.bfloat16)
    lowered = _lower_tpu(_bert_attention_grad(dropout), qkv, qkv, qkv)
    _assert_mosaic(lowered, 3)
    assert attn_mod.traced_rows_per_step() > 1
    monkeypatch.setattr(attn_mod, "materialises_scores", lambda *a, **k: False)
    without = _lower_tpu(_bert_attention_grad(dropout), qkv, qkv, qkv)
    text = _text_without_locations(lowered)
    assert "stable_mosaic" in text and text == _text_without_locations(without)


@pytest.mark.parametrize("causal", [False, True])
def test_training_attention_at_bert_large_s128_lowers_to_no_kernel(
        pallas_path, causal):
    """BERT-large seq128 / micro-batch 64 without dropout: 64 MiB of float32
    scores, the rule's edge. The materialised path: no Mosaic call, float32
    scores out of bf16 operands."""
    qkv = SDS((64, 16, 128, 64), jnp.bfloat16)
    before = attn_mod.trace_counts()
    text = _lower_tpu(_bert_attention_grad(0.0, causal=causal),
                      qkv, qkv, qkv).as_text()
    assert attn_mod.traced_implementation(since=before) == "dense"
    assert "tpu_custom_call" not in text
    assert ("(tensor<64x16x128x64xbf16>, tensor<64x16x128x64xbf16>) -> "
            "tensor<64x16x128x128xf32>") in text


def test_training_flash_unaligned_seq_pads_into_kernel(pallas_path):
    """S=200 with scores over the budget is padded to a block multiple and
    runs the kernel — it must not switch to the jnp reference."""
    qkv = SDS((64, 16, 200, 64), jnp.bfloat16)
    lowered = _lower_tpu(lambda q, k, v: flash_attention(q, k, v, causal=True),
                         qkv, qkv, qkv)
    _assert_mosaic(lowered, 1)
    assert lowered.out_info.shape == (64, 16, 200, 64)
    with pytest.raises(ValueError, match="block-sparse"):
        flash_attention(*(jnp.zeros((1, 4, 100, 64), jnp.bfloat16),) * 3,
                        layout=_banded_layout(4, 1))


def test_training_block_sparse_lowers(pallas_path):
    """One block-sparse layout (banded causal, the scalar-prefetch LUT
    path) at seq512, forward and backward: one row a grid step, because a
    sparse LUT differs by head."""
    qkv = SDS((16, 16, 512, 64), jnp.bfloat16)
    layout = _banded_layout(16, 4)

    def loss(q, k, v):
        out = flash_attention(q, k, v, layout=layout, causal=True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    _assert_mosaic(_lower_tpu(jax.grad(loss, argnums=(0, 1, 2)),
                              qkv, qkv, qkv), 3)
    assert attn_mod.traced_rows_per_step() == 1


def test_training_flash_lowers_under_a_mesh(pallas_path):
    """Batch-sharded q/k/v under jit on several devices: the kernels must
    shard_map themselves over the mesh in context, because Mosaic refuses
    automatic partitioning (this failed the first four-chip run, and the
    CPU suite could not see it: it runs the jnp reference)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(1, 4, 1),
                ("pipe", "data", "model"))
    qkv = SDS((64, 16, 128, 64), jnp.bfloat16,
              sharding=NamedSharding(mesh, P("data")))
    rng = jax.random.PRNGKey(0)

    def loss(q, k, v):
        out = flash_attention(q, k, v, dropout_rate=0.1, dropout_rng=rng)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def meshed(q, k, v):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return loss(q, k, v)

    _assert_mosaic(_lower_tpu(jax.grad(meshed, argnums=(0, 1, 2)),
                              qkv, qkv, qkv), 3)
    assert attn_mod.traced_rows_per_step() > 1      # grouped by the 256 rows a device holds
    with pytest.raises(NotImplementedError, match="automatically partitioned"):
        _lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)


def _decode_args(chunk, page_dtype, lanes=8, pages_per_lane=8):
    n_pages = lanes * pages_per_lane + 1
    pages = SDS((n_pages, NH, PT, HD), page_dtype)
    args = [SDS((lanes, chunk, NH, HD), jnp.float32), pages, pages,
            SDS((lanes, pages_per_lane), jnp.int32),
            SDS((lanes, chunk), jnp.int32)]
    if page_dtype == jnp.int8:
        args += [SDS((n_pages, NH), jnp.float32)] * 2
    return args


def _decode_fn(q, pk, pv, tables, qpos, k_scale=None, v_scale=None):
    return kernels.decode_attend(
        q, pk, pv, tables, qpos, page_tokens=PT, dtype=jnp.float32,
        impl="pallas", interpret=False, k_scale=k_scale, v_scale=v_scale)


@pytest.mark.parametrize("page_dtype", [jnp.float32, jnp.int8])
@pytest.mark.parametrize("chunk", [1, 256])
def test_decode_attention_lowers(page_dtype, chunk):
    """Paged decode at GPT-2-large width: the one-token decode step and
    a prefill-bucket-wide chunk (tiled over the query-block grid axis),
    fp32 and int8 pages."""
    _assert_mosaic(_lower_tpu(_decode_fn, *_decode_args(chunk, page_dtype)), 1)


def _band_args(dtype, n=8):
    win = SDS((n, NH, 2 * PT, HD), dtype)
    sink = SDS((n, NH, PT, HD), dtype)
    return [SDS((n, NH, HD), dtype), win, win, sink, sink,
            SDS((n,), jnp.int32), SDS((n,), jnp.int32)]


def _band_fn(dtype):
    return lambda *a: kernels.band_attend(*a, dtype=dtype, impl="pallas",
                                          interpret=False)


def test_sparse_attention_lowers():
    _assert_mosaic(_lower_tpu(_band_fn(jnp.float32),
                              *_band_args(jnp.float32)), 1)


# the five arrays a decode step writes a column into: MiMo-V2.5's pages and
# ring of one block, Laguna's ring of four blocks, Nemotron-H's pages, Ouro's
# pages (a cache row a (pass, layer), the row traced in its programs)
_COLUMN_POOLS = [((2, 4097, 768, 128), 128), ((5, 128, 1, 1536, 128), 128),
                 ((3, 64, 4, 1024, 128), 64), ((1, 4097, 256, 128), 128),
                 ((192, 41, 2048, 128), 8)]


def _column_write_args(shape, lanes, dtype=jnp.bfloat16):
    i32 = jnp.int32
    return ([SDS(shape, dtype)] + [SDS((lanes,), i32)] * (len(shape) - 3)
            + [SDS((lanes, shape[-2]), dtype), SDS((lanes,), i32)])


def _column_write_fn(pool, *rest):
    *index, new, col = rest
    return column_write._write_columns_pallas(pool, (0, *index), new, col)


@pytest.mark.parametrize("shape, lanes", _COLUMN_POOLS)
def test_column_write_lowers(shape, lanes):
    _assert_mosaic(_lower_tpu(_column_write_fn,
                              *_column_write_args(shape, lanes)), 1)


@pytest.mark.parametrize("shape, lanes", _COLUMN_POOLS)
def test_column_write_takes_the_new_values_as_they_lie(shape, lanes):
    """No array ``[lanes, width, 1]`` in the text around the kernel's call:
    the kernel's operand is ``new [lanes, width]`` itself. (Laid out
    ``[8, 2048, 1]`` for a ``(width, 1)`` block, a token's 32 KB of keys
    became 4 MB, one value a 128-lane row, and the relayout took Ouro's
    layer call longer than the page write: ``PERF.md``, PR 48.)"""
    text = _lower_tpu(_column_write_fn,
                      *_column_write_args(shape, lanes)).as_text()
    width = shape[-2]
    assert f"tensor<{lanes}x{width}x1x" not in text
    call, = (l for l in text.splitlines() if "tpu_custom_call" in l)
    assert f"tensor<{lanes}x{width}xf32>, tensor<" in call.rsplit(" : ", 1)[1]


def _prefill_walk_args(rows=16, pages=5121, table=128):
    """Keye-VL's cell: 16 rows of 128 queries, 32 heads of 128 on 4
    key-value heads, a pool of 6 layers, tables of 128 pages."""
    i32, bf = jnp.int32, jnp.bfloat16
    return [SDS((rows, 128, 4, 8, 128), bf), SDS((6, pages, 128, 8, 128), bf),
            SDS((rows, table), i32), SDS((rows,), i32), SDS((rows,), i32),
            SDS((rows, 128, table * 128), i32)]


def _prefill_walk_fn(q, pool, tables, starts, lens, u):
    return keye_mod.attend_selected(q, pool, 3, tables, 4, starts, lens, u,
                                    2048)


def test_paged_prefill_lowers_under_the_selection(monkeypatch):
    """``ops/paged_prefill.py`` with Keye-VL's mask traced inside it, at the
    cell's shapes: one Mosaic call, and the lowered walk holds no array
    with a query axis, a key axis and the heads."""
    monkeypatch.setattr(paged_prefill, "_on_tpu", lambda: True)
    lowered = _lower_tpu(_prefill_walk_fn, *_prefill_walk_args())
    _assert_mosaic(lowered, 1)
    assert "x4x8x128x512xf32" not in lowered.as_text()
    monkeypatch.setattr(paged_prefill, "_on_tpu", lambda: False)
    # (a function of its own: the first one's trace is cached)
    plain = _lower_tpu(lambda *args: _prefill_walk_fn(*args),
                       *_prefill_walk_args()).as_text()
    assert "tpu_custom_call" not in plain and "x4x8x128x512xf32" in plain


def _latent_walk_args(rows=16, pages=5121, table=128):
    """GLM-5.2's cell: 16 rows of 128 queries, 64 absorbed heads on a latent
    row of 640, a pool of 6 layers, tables of 128 pages."""
    i32, bf = jnp.int32, jnp.bfloat16
    return [SDS((rows, 128, 64, 640), bf), SDS((6, pages, 128, 640), bf),
            SDS((rows, table), i32), SDS((rows,), i32), SDS((rows,), i32),
            SDS((rows, 128, table * 128), i32)]


def _latent_walk_fn(q, latent, tables, starts, lens, u):
    pos = starts[:, None] + jnp.arange(128)[None, :]
    selection = paged_layers.row_selection(u, pos, 2048, 512, q.dtype)
    n_blocks = (jnp.max(starts + lens) + 511) // 512
    return glm_mod.attend_selected(glm_mod.GlmDsaConfig(), q, latent, 3,
                                   tables, 4, n_blocks, starts, lens,
                                   selection)


def test_latent_prefill_lowers_under_the_selection(monkeypatch):
    """``paged_prefill.attend_latent`` with GLM-5.2's mask traced inside it,
    at the cell's shapes: one Mosaic call, and the lowered walk holds no
    float32 array with the rows, the heads, the queries and a block of keys
    (the scores) or the rank (the accumulator)."""
    monkeypatch.setattr(paged_prefill, "_on_tpu", lambda: True)
    lowered = _lower_tpu(_latent_walk_fn, *_latent_walk_args())
    _assert_mosaic(lowered, 1)
    assert "16x64x128x512xf32" not in lowered.as_text()
    monkeypatch.setattr(paged_prefill, "_on_tpu", lambda: False)
    # (a function of its own: the first one's trace is cached)
    plain = _lower_tpu(lambda *args: _latent_walk_fn(*args),
                       *_latent_walk_args()).as_text()
    assert "tpu_custom_call" not in plain and "16x64x128x512xf32" in plain


def _chosen_tiles_args(lanes=64, K=2048):
    """Keye-VL's cell: a decode step's 64 lanes, 2,048 selected tiles of 4
    key heads and 4 value heads each, 32 query heads."""
    i32, bf = jnp.int32, jnp.bfloat16
    return [SDS((lanes, 4, 8, 128), bf), SDS((lanes, K, 8, 128), bf),
            SDS((lanes, K), jnp.bool_), SDS((lanes,), i32),
            SDS((lanes,), jnp.bool_)]


def test_selected_tiles_attention_lowers_as_the_tiles_lie(monkeypatch):
    """``paged_prefill.attend_tiles`` through ``keye.attend_chosen`` at the
    cell's shapes: one Mosaic call that takes the gathered tiles whole, and
    no product over a half of them; off the TPU the two products, and no
    kernel."""
    monkeypatch.setattr(paged_prefill, "_on_tpu", lambda: True)
    lowered = _lower_tpu(keye_mod.attend_chosen, *_chosen_tiles_args())
    _assert_mosaic(lowered, 1)
    text = lowered.as_text()
    assert "64x2048x4x128xbf16" not in text and "dot_general" not in text
    monkeypatch.setattr(paged_prefill, "_on_tpu", lambda: False)
    plain = _lower_tpu(lambda *args: keye_mod.attend_chosen(*args),
                       *_chosen_tiles_args()).as_text()
    assert "tpu_custom_call" not in plain
    assert plain.count("dot_general") == 2 and "64x2048x4x128xbf16" in plain


# the cells whose decode attention is ``gqa_decode``: (lanes, KV heads, query
# heads a KV head, hd, vd, the pools' rows and pages, pairs in the work list)
_PAGED_DECODE_CELLS = {
    "ouro": (8, 16, 1, 128, 128, (192, 41), 16),
    "mimo_v2": (128, 4, 16, 192, 128, (2, 4097), 1296),
    "laguna_48_heads": (64, 8, 6, 128, 128, (2, 3585), 1040),
    "laguna_64_heads": (64, 8, 8, 128, 128, (2, 3585), 1040),
    "nemotron_h": (128, 2, 16, 128, 128, (1, 3073), 768),
}


def _paged_decode_args(cell):
    B, kvh, J, hd, vd, rows_pages, pairs = _PAGED_DECODE_CELLS[cell]
    i32, bf = jnp.int32, jnp.bfloat16
    return [SDS((B, kvh, J, hd), bf), SDS(rows_pages + (kvh * hd, 128), bf),
            SDS(rows_pages + (kvh * vd, 128), bf), SDS((), i32),
            SDS((pairs, 4), i32), SDS((pairs,), i32), SDS((pairs,), i32),
            SDS((), i32)]


@pytest.mark.parametrize("cell", sorted(_PAGED_DECODE_CELLS))
def test_paged_decode_attention_lowers_at_the_cells_shapes(cell, monkeypatch):
    """``ops/paged_decode.py::attend_pairs`` at each cell's own shapes, the
    pools' row a traced scalar (Ouro's loop; a Python int is the same call
    with a constant): one Mosaic call that is handed both pools whole, and
    ``usable`` takes the shape on a TPU (but Nemotron-H's pages of 64 KB,
    which lower and measured slower than the plain walk)."""
    q, k_pool, v_pool, *_ = args = _paged_decode_args(cell)
    assert not paged_decode.usable(q, k_pool, v_pool)
    monkeypatch.setattr(paged_decode, "_on_tpu", lambda: True)
    assert paged_decode.usable(q, k_pool, v_pool) == (cell != "nemotron_h")
    lowered = _lower_tpu(paged_decode.attend_pairs, *args)
    _assert_mosaic(lowered, 1)
    assert lowered.as_text().count("tpu_custom_call") == 1


@pytest.mark.slow
def test_serving_kernels_compile_for_v5e(monkeypatch):
    """The real compiler: Mosaic + XLA:TPU from the installed libtpu,
    against a v5e topology description, for both serving kernels in
    every storage dtype. Catches what lowering cannot (an accumulator
    dtype Mosaic rejects, an op it cannot legalize, VMEM overflow)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    place = lambda args: [SDS(a.shape, a.dtype, sharding=dev) for a in args]
    for page_dtype in (jnp.float32, jnp.bfloat16, jnp.int8):
        for chunk in (1, 256):
            _lower_tpu(_decode_fn,
                       *place(_decode_args(chunk, page_dtype))).compile()
    for dtype in (jnp.float32, jnp.bfloat16):
        _lower_tpu(_band_fn(dtype), *place(_band_args(dtype))).compile()
    for shape, lanes in _COLUMN_POOLS:
        _lower_tpu(_column_write_fn,
                   *place(_column_write_args(shape, lanes))).compile()
    monkeypatch.setattr(paged_prefill, "_on_tpu", lambda: True)
    _lower_tpu(lambda *args: _prefill_walk_fn(*args),
               *place(_prefill_walk_args())).compile()
    assert "latent_prefill_attention" in _lower_tpu(
        lambda *args: _latent_walk_fn(*args),
        *place(_latent_walk_args())).compile().as_text()
    text = _lower_tpu(lambda *args: keye_mod.attend_chosen(*args),
                      *place(_chosen_tiles_args())).compile().as_text()
    # the compiled program holds the kernel and no copy of a half of the
    # tiles, heads first or positions first
    assert "selected_tiles_attention" in text
    assert "bf16[64,4,2048,128]" not in text
    assert "bf16[64,2048,4,128]" not in text


@pytest.mark.slow
def test_training_flash_kernels_compile_for_v5e(pallas_path):
    """The three training kernels through the real compiler at the BERT
    cells' sequence (with dropout, and without it at twice the batch: calls
    ``materialises_scores`` leaves to them) and at longer sequences, at the
    rows a grid step the rule gives each: a group that overflows VMEM is
    refused here, before the chip."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    rows = {}
    for shape, dropout in (((128, 16, 128, 64), 0.0), ((64, 16, 128, 64), 0.1),
                           ((16, 16, 512, 64), 0.1), ((4, 16, 2048, 64), 0.0),
                           ((4, 16, 2048, 128), 0.1)):
        qkv = SDS(shape, jnp.bfloat16, sharding=dev)
        before = attn_mod.trace_counts()
        _lower_tpu(_bert_attention_grad(dropout), qkv, qkv, qkv).compile()
        assert attn_mod.traced_implementation(since=before) == "pallas"
        rows[shape] = attn_mod.traced_rows_per_step()
    assert rows[(64, 16, 128, 64)] > rows[(4, 16, 2048, 64)] > 1


@pytest.mark.slow
def test_zero2_update_compiles_for_v5e_2x2():
    """ZeRO-2's flat-master update for a four-chip host. Rebuilding a
    [1024, 2] leaf (BERT's next-sentence head) out of the gathered flat
    vector once made XLA:TPU re-view the whole vector as [n/2, 2] and pad
    the 2 to 128 lanes: 64x the vector, 43 GB for BERT-large, refused at
    buffer assignment on the first four-chip run. Same structure here,
    sized so that the 64x copy cannot fit 16 GB."""
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deepspeed_tpu.ops.adam.fused_adam import FusedAdam
    from deepspeed_tpu.ops.utils_op import tree_spec
    from deepspeed_tpu.runtime.zero.sharded_optimizer import (
        ZeroShardedOptimizer,
        ZeroState,
    )

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4, 1),
                ("pipe", "data", "model"))
    rep, shard = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    tree = lambda dtype: {
        "encoder": SDS((140, 1000, 1000), dtype, sharding=rep),
        "nsp_head": SDS((1024, 2), dtype, sharding=rep)}
    opt = ZeroShardedOptimizer(FusedAdam(lr=1e-4), stage=2, mesh=mesh)
    opt._spec = tree_spec(tree(jnp.bfloat16))
    opt._numel = sum(opt._spec[3])
    flat = SDS((-(-opt._numel // 4) * 4,), jnp.float32, sharding=shard)
    inner = jax.tree_util.tree_map(
        lambda x: SDS(x.shape, x.dtype,
                      sharding=shard if x.shape == flat.shape else rep),
        jax.eval_shape(opt.inner.init, flat))
    compiled = jax.jit(
        lambda g, st, p, lr: opt.update(g, st, p, lr=lr)).trace(
        tree(jnp.float32), ZeroState(flat_master=flat, inner_state=inner),
        tree(jnp.bfloat16), SDS((), jnp.float32, sharding=rep)).lower(
        lowering_platforms=("tpu",)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2 ** 30


@pytest.mark.slow
def test_nemotron_h_programs_compile_for_v5e_at_the_cells_size():
    """The two programs of the Nemotron-H serving family at the benchmark
    cell's own size (128 lanes of 3,072 positions, 16 rows of 128 a prefill
    call, bfloat16 parameters), for one described v5e chip: they fit, and
    neither copies the 1.07 GB SSM pool or the key and value pools (a
    scatter or a one-column update made XLA re-lay Kimi-Linear's pool twice
    a step, PERF.md PR 27). About 25 s; nothing runs."""
    import json
    import os
    import re

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.models import nemotron_h_serve
    from benchmarks.refs import nemotron_h_ref as ref
    from benchmarks.refs import weights as weights_mod
    from deepspeed_tpu.inference.serving.families import nemotron_h as fam

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmarks", "configs",
                           "nemotron3_nano_30b_serve_ep2.json")) as f:
        cfg = json.load(f)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dtype: SDS(shape, dtype, sharding=dev)
    params = weights_mod.nest({k: sds(v, jnp.bfloat16)
                               for k, v in ref.weight_shapes(cfg).items()})
    m = nemotron_h_serve.model_config(cfg)
    serving = cfg["serving"]
    B, pt = serving["max_slots"], serving["kv_page_tokens"]
    mp = serving["max_seq_len"] // pt
    R = serving["prefill_chunk_tokens"] // m.chunk_size
    pages = B * mp + 1
    state = {"ssm": sds((4, B, 64, 64, 128), jnp.float32),
             "conv": sds((4, B, 3, m.conv_dim), jnp.bfloat16),
             "k": sds((1, pages, m.kv_width, pt), jnp.bfloat16),
             "v": sds((1, pages, m.kv_width, pt), jnp.bfloat16)}
    i32 = lambda *shape: sds(shape, jnp.int32)
    static = dict(cfg=m, page_tokens=pt, keep_logits=False)
    programs = {
        "decode": fam._nemotron_decode_step_jit.lower(
            params, state, i32(B), i32(B), sds((B,), jnp.bool_), i32(B, mp),
            **static),
        "prefill": fam._nemotron_prefill_chunk_jit.lower(
            params, state, i32(R, m.chunk_size), i32(R), i32(R), i32(R),
            i32(R, mp), **static)}
    pool_copy = re.compile(
        rf"= (f32\[4,{B},64,64,128\]|bf16\[1,{pages},{m.kv_width},{pt}\])"
        rf"\S* copy\(")
    for name, lowered in programs.items():
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes < 8.0e9, name
        assert mem.temp_size_in_bytes < 1.0e9, name
        assert mem.alias_size_in_bytes > 1.4e9, name     # the state, donated
        assert not pool_copy.search(compiled.as_text()), name


def test_column_write_lowers_with_a_traced_row():
    """The row of the leading axis as a traced scalar (a decoder walked by
    a loop inside the program): one Mosaic call still, with the row one
    more scalar ahead of the grid."""
    shape, lanes = _COLUMN_POOLS[-1]

    def fn(pool, n, *rest):
        *index, new, col = rest
        return column_write._write_columns_pallas(pool, (n, *index), new, col)

    pool, *rest = _column_write_args(shape, lanes)
    _assert_mosaic(_lower_tpu(fn, pool, SDS((), jnp.int32), *rest), 1)


@pytest.mark.slow
def test_ouro_programs_compile_for_v5e_at_the_cells_size(monkeypatch):
    """The two programs of the Ouro serving family at the benchmark cell's
    own size (8 lanes of 640 positions, 4 rows of 128 a prefill call, 48
    layers x 4 passes, bfloat16), for one described v5e chip: arguments of
    13.6 GB fit beside temporaries of a few MB, the two pools are carried
    through both loops in place (aliased to the results, no copy of pool
    shape), and the text holds one layer, not 192. About 10 s; nothing
    runs."""
    import json
    import os
    import re

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.models import ouro_serve
    from benchmarks.refs import ouro_ref as ref
    from benchmarks.refs import weights as weights_mod
    from deepspeed_tpu.inference.serving.families import ouro as fam
    from deepspeed_tpu.models import ouro as ou

    monkeypatch.setattr(column_write, "_on_tpu", lambda: True)
    monkeypatch.setattr(paged_decode, "_on_tpu", lambda: True)
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmarks", "configs",
                           "ouro_2p6b_serve.json")) as f:
        cfg = json.load(f)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dtype: SDS(shape, dtype, sharding=dev)
    m = ouro_serve.model_config(cfg)
    stacked = jax.eval_shape(ou.stack_layers, weights_mod.nest(
        {k: SDS(v, jnp.bfloat16) for k, v in ref.weight_shapes(cfg).items()}))
    params = jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), stacked)
    serving = cfg["serving"]
    B, pt = serving["max_slots"], serving["kv_page_tokens"]
    mp = serving["max_seq_len"] // pt
    R = serving["prefill_chunk_tokens"] // pt
    pages = serving["kv_pool_tokens"] // pt + 1
    pool = (m.cache_rows, pages, m.cache_widths["k"], pt)
    assert pool == (192, 41, 2048, 128)
    state = {n: sds(pool, jnp.bfloat16) for n in ("k", "v")}
    i32 = lambda *shape: sds(shape, jnp.int32)
    static = dict(cfg=m, page_tokens=pt, keep_logits=False)
    programs = {
        "decode": fam._ouro_decode_step_jit.lower(
            params, state, i32(B), i32(B), sds((B,), jnp.bool_), i32(B, mp),
            **static),
        "prefill": fam._ouro_prefill_chunk_jit.lower(
            params, state, i32(R, pt), i32(R), i32(R), i32(R), i32(R, mp),
            **static)}
    pool_copy = re.compile(r"= bf16\[192,41,2048,128\]\S* copy(-start)?\(")
    for name, lowered in programs.items():
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        assert 13.5e9 < mem.argument_size_in_bytes < 13.7e9, name
        assert mem.temp_size_in_bytes < 64e6, name
        assert mem.alias_size_in_bytes == 2 * 192 * 41 * 2048 * 128 * 2, name
        text = compiled.as_text()
        assert not pool_copy.search(text), name
        assert len(text.splitlines()) < 4000, name
    # page_write of k and of v, and the attention over the pages
    assert programs["decode"].compile().as_text().count(
        "tpu_custom_call") == 3

"""Keye-VL-2.0 decoder (Kwai-Keye/Keye-VL-2.0-30B-A3B, ``model_type:
KeyeVL2``): pure functions of a parameter tree, for serving. The language
model alone, on text tokens: the vision tower of the published model has no
key in the ``config.json`` this is written from and is not served.

Every layer is alike (``decoder_sparse_step`` 1, ``mlp_only_layers`` []):
``h += Attn(RMSNorm(h)); h += Experts(RMSNorm(h))``; final RMSNorm; untied
``lm_head``; no bias in any projection. ``Attn`` is grouped-query attention
(32 query heads on 4 key-value heads of 128, an RMSNorm over each query and
key head, the whole head rotated) over the keys a **learned indexer**
selects, and over no others: the indexer scores every cached position ``s
<= t`` for the query at ``t``, ``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
kI[s])`` with 16 indexer heads of 64 on one key head (``qI = x W_qI``, ``kI
= LayerNorm(x W_kI)``, both rotated, ``w = x W_w / sqrt(16 x 64)``), in
float32; the ``min(topk, t + 1)`` positions with the largest scores are
attended, one set a query token for all its heads (DeepSeek-V3.2's sparse
attention on this model's sizes; ``topk`` 2,048). ``Experts`` is the
softmax-routed expert layer of ``parallel/expert.py``: scores over all
``num_experts`` in float32, the 8 largest renormalised, SwiGLU experts, no
shared expert; this program holds ``experts_held`` of them and adds up what
those give.

A position is a triple under ``mrope_section`` (temporal, height, width);
a text token's three components are its index in the sequence, and the
sections then rotate as plain rotary positions do, which is what is here:
the programs take one position a token.

Two entry points, both functions of ``(params, cfg, state, ...)`` that the
serving engine jits, with the arguments of every slot-state family's:

- ``prefill_chunk``: ``R`` rows of one page of tokens, each the next tokens
  of some prompt; rows of one prompt are consecutive and in order. A layer
  writes each row as one page, scores the rows' queries against their
  prompts' indexer keys a block of pages at a time, finds each query's
  ``topk``-th largest score exactly (bisection over the scores' bits), and
  walks the key blocks with the selection as its mask: on a TPU in one
  kernel a layer that keeps a block's scores in VMEM
  (``ops/paged_prefill.py``), elsewhere as ``paged_layers.gqa_prefill``
  does.
- ``decode_step``: one token for every active lane. A layer scores the
  lane's indexer keys, takes the exact top ``topk`` (``lax.top_k``: the
  lower position first among equal scores) and **fetches those positions'
  keys and values and no others**: what a step reads of its cache is the
  indexer's keys of every position and ``min(context, topk)`` positions'
  keys and values, not the context's. The fetched tiles ``[B, K, 2 KV,
  hd]`` are attended as they lie (``attend_chosen``): on a TPU by one
  kernel a layer that reads a block of them once for all heads
  (``ops/paged_prefill.py::attend_tiles``), elsewhere, and at any shape
  the kernel was not built for, by two products that XLA lays each half
  of the tiles out again for.

``state`` is ``{"kv": [L, pages, page_tokens, 2 x KV, head_dim], "ik": [L,
pages, indexer_head_dim, page_tokens]}``: pages only, no slot array. The
indexer's keys lie as the other families' pages do, a page's tokens along
the last axis, because every one of them is read (a page is one matrix
operand). Keys and values lie the other way round, a token's key heads and
value heads together as one ``(8, 128)`` tile: the chip's memory moves
whole tiles, a token that is a column across tiles cannot be fetched
without the 127 beside it, and a token that is a tile can (``PERF.md``, PR
42: the same 2,048 positions a lane are fetched five times faster).
Page 0 is the sink for positions that are not live. The router and the
index scores are float32 whatever the parameters' type; keys, of both
kinds, are cached normed and rotated.
"""

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import paged_layers as pl
from deepspeed_tpu.ops import paged_prefill
from deepspeed_tpu.ops.column_write import write_columns
from deepspeed_tpu.parallel import expert as expert_mod


@dataclass(frozen=True)
class KeyeConfig:
    """The published keys of ``config.json`` (``sa_config``'s and
    ``rope_scaling.mrope_section`` by their own names), plus the share of a
    deployment this program holds: ``experts_held`` (first, count) of the
    ``num_experts`` the router scores, and ``vocab_size`` rows of the
    vocabulary starting at ``vocab_first`` (traffic ids, logits and
    sampling are over the slice)."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000000.0
    mrope_section: tuple = (16, 24, 24)
    attention_bias: bool = False
    sliding_window: int = None
    use_sliding_window: bool = False
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: tuple = ()
    tie_word_embeddings: bool = False
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    indexer_num_kv_heads: int = 1
    topk: int = 2048
    q_chunk_size: int = 512         # the published kernel's tiles: read by
    kv_chunk_size: int = 512        # nothing, the result is the same
    max_position_embeddings: int = 262144
    experts_held: tuple = None      # (first, count); None = all
    vocab_first: int = 0

    def __post_init__(self):
        for name in ("mrope_section", "mlp_only_layers"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        object.__setattr__(self, "experts_held", expert_mod.held_share(
            self.experts_held, self.num_experts))
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must divide into their key-value "
                             "heads")
        if sum(self.mrope_section) != self.head_dim // 2:
            raise ValueError(
                f"mrope_section {self.mrope_section} does not cover the "
                f"{self.head_dim // 2} frequencies of a head")
        # what the published model does not do and this program does not
        # compute: refused by the key's name
        if self.sliding_window is not None or self.use_sliding_window:
            raise ValueError("sliding_window/use_sliding_window: every "
                             "layer attends to what its indexer selects, "
                             "none to a window")
        if self.mlp_only_layers or self.decoder_sparse_step != 1:
            raise ValueError("mlp_only_layers/decoder_sparse_step: every "
                             "layer is an expert layer, none has a dense "
                             "MLP")
        if self.attention_bias:
            raise ValueError("attention_bias: the projections have no bias")
        if self.tie_word_embeddings:
            raise ValueError("tie_word_embeddings: the head is its own "
                             "matrix")
        if self.indexer_num_kv_heads != 1:
            raise ValueError("indexer_num_kv_heads: the indexer's heads "
                             "share one key head")
        if self.topk < 1:
            raise ValueError(f"topk={self.topk}")

    @classmethod
    def from_dict(cls, cfg, **share):
        """From the keys of the published ``config.json``. A key for the
        vision tower (whose embeddings and position triples this program
        has no argument for) is refused by its name."""
        for key in ("vision_config", "visual", "vision_tower"):
            if cfg.get(key):
                raise ValueError(
                    f"{key}: the decoder is served on text tokens alone; "
                    f"a tower's embeddings, and positions whose three "
                    f"M-RoPE components differ, have no way in")
        rope = cfg.get("rope_scaling") or {}
        kinds = {rope.get(k, "default") for k in ("rope_type", "type")}
        if kinds != {"default"}:
            raise ValueError(f"rope_scaling {rope}: plain frequencies only")
        own = ("experts_held", "vocab_first", "mrope_section")
        kw = {k: cfg[k] for k in cls.__dataclass_fields__
              if k in cfg and k not in own}
        kw.update({k: v for k, v in (cfg.get("sa_config") or {}).items()
                   if k in cls.__dataclass_fields__})
        if "mrope_section" in rope:
            kw["mrope_section"] = tuple(rope["mrope_section"])
        kw.update(share)
        return cls(**kw)

    # -- derived ---------------------------------------------------------
    @property
    def attention(self):
        """The heads and head sizes, as the grouped-query functions read
        them."""
        return pl.AttentionShape(self.num_attention_heads,
                                 self.num_key_value_heads, self.head_dim,
                                 self.head_dim)

    @property
    def rope(self):
        """Plain frequencies over the whole head: what the three sections
        of ``mrope_section`` are for a text token."""
        return pl.RopeSpec(rope_theta=self.rope_theta)

    @property
    def n_moe_layers(self):
        return self.num_hidden_layers

    @property
    def cache_widths(self):
        """{name of a pool array: what a token caches there}, as
        ``HybridStatePool`` takes a paged array's description: ``kv`` a
        token's shape, its key heads and then its value heads as one tile
        of ``2 x KV`` rows (pages with their tokens first); ``ik`` a width,
        the indexer's one key head (pages with their tokens last). Two
        paged arrays and no slot array."""
        return {"kv": (2 * self.num_key_value_heads, self.head_dim),
                "ik": self.indexer_head_dim}


# -- what a layer's attention is given --------------------------------------

def _rotate(p, cfg):
    """``rotate(q, k, positions)`` of the grouped-query projections: the
    RMSNorm over each head, then the rotation of the whole head."""
    shape = cfg.attention
    turn = pl.rotary(cfg.rope, shape, "rope")

    def rotate(q, k, positions):
        with jax.named_scope("qk_norm"):
            heads = k.shape[:-1] + (shape.num_key_value_heads, shape.head_dim)
            q = pl.rms_norm(q, p["q_norm"]["scale"], cfg.rms_norm_eps)
            k = pl.rms_norm(k.reshape(heads), p["k_norm"]["scale"],
                            cfg.rms_norm_eps).reshape(k.shape)
        return turn(q, k, positions)
    return rotate


def indexer_project(p, cfg, x, positions):
    """The indexer's inputs for ``x [..., d]`` at ``positions [...]``: ``qI
    [..., 16, 64]`` and ``kI [..., 64]`` in ``x``'s type (as the cache
    holds the keys), rotated; ``w [..., 16]`` float32, scaled."""
    ni, hi = cfg.indexer_num_heads, cfg.indexer_head_dim
    with jax.named_scope("dsa_project"):
        qI = pl.dot(x, p["wq"]["kernel"]).reshape(x.shape[:-1] + (ni, hi))
        kI = pl.layer_norm(pl.dot(x, p["wk"]["kernel"]), p["k_norm"],
                           cfg.rms_norm_eps)
        qI = pl.apply_rope(cfg.rope, qI, positions).astype(x.dtype)
        kI = pl.apply_rope(cfg.rope, kI[..., None, :], positions)[..., 0, :]
        w = pl.dot(x, p["weights_proj"]["kernel"]) * (ni * hi) ** -0.5
    return qI, kI.astype(x.dtype), w


# The indexer's arithmetic and the exact top-k two ways are
# ``paged_layers``'s (shared with ``models/glm_dsa.py``), under the names
# they had here.
_LOWEST, _sortable, _selected = pl.LOWEST, pl.sortable, pl.selected
kth_largest, index_scores = pl.kth_largest, pl.index_scores
select_topk, prefill_key_span = pl.select_topk, pl.prefill_key_span


# -- attention under a selection ---------------------------------------------

def _tiles(cfg, k, v, dtype):
    """A token's tile ``[..., 2 KV, hd]``: its key heads, then its value
    heads."""
    kvh, hd = cfg.num_key_value_heads, cfg.head_dim
    lead = k.shape[:-1]
    return jnp.concatenate([k.reshape(lead + (kvh, hd)),
                            v.reshape(lead + (kvh, hd))], axis=-2).astype(dtype)


def _attend_blocks(q, kv_pool, n, tables, bp, n_blocks, selection):
    """The walk in plain operations, the twin of ``paged_prefill``'s kernel
    for every backend but a TPU (and every shape but its own): ``n_blocks``
    key blocks of ``bp`` pages of row ``n`` of ``kv_pool`` under the
    selection (``_selected``'s operands, a row each), every row to the
    call's longest, as ``paged_layers.gqa_prefill`` walks its own. A block's
    float32 scores ``[R, KV, J, T, span]`` are an array in memory here.
    Returns the context ``[R, KV, J, T, hd]`` float32."""
    R, T, kvh, J, hd = q.shape
    u, least, ties_left, ties_before, below = selection
    span = below.shape[0]
    scale = hd ** -0.5

    def block(j):
        pages = jax.lax.dynamic_slice_in_dim(tables, j * bp, bp, axis=1)
        kvb = kv_pool[n, pages].astype(q.dtype)        # [R, bp, pt, 2KV, hd]
        s = jnp.einsum("rtgjd,rnpgd->rgjtnp", q, kvb[..., :kvh, :],
                       preferred_element_type=jnp.float32).reshape(
                           R, kvh, J, T, span) * scale
        uj = jax.lax.dynamic_slice_in_dim(u, j * span, span, axis=1)
        ok = jax.vmap(_selected, in_axes=(None, 0, 0, 0, 0, None))(
            j, uj, least, ties_left, ties_before, below)     # [R, span, T]
        s = jnp.where(jnp.swapaxes(ok, 1, 2)[:, None, None], s, -1e30)

        def weigh(pr):
            return jnp.einsum(
                "rgjtnp,rnpgd->rgjtd",
                pr.astype(q.dtype).reshape(R, kvh, J, T, -1, kvb.shape[2]),
                kvb[..., kvh:, :], preferred_element_type=jnp.float32)
        return s, weigh

    return pl.online_softmax_loop(n_blocks, block, (R, kvh, J, T), hd)


def attend_selected(q, kv_pool, n, tables, bp, starts, lens, u, topk):
    """The second half of ``dsa_prefill``: ``q [R, T, KV, J, hd]`` attends,
    of row ``n`` of ``kv_pool`` under ``tables [R, blocks * bp]``, the
    ``min(topk, position + 1)`` keys with the largest ``u [R, T, S]``
    (sortable scores; a key after its query holds ``-inf``'s image or
    less), the lower position first among equals. The threshold and the
    tie counts in plain operations (``dsa_select``), then the walk
    (``dsa_attend``): on a TPU at the shapes it takes, one kernel a layer
    that keeps a block's scores in VMEM and walks each row's own blocks
    (``ops/paged_prefill.py``); anywhere else ``_attend_blocks``. Returns
    the context ``[R, KV, J, T, hd]``."""
    T = q.shape[1]
    span = bp * T
    pos = starts[:, None] + jnp.arange(T)[None, :]                   # [R, T]
    with jax.named_scope("dsa_select"):
        selection = pl.row_selection(u, pos, topk, span, q.dtype)
    with jax.named_scope("dsa_attend"):
        if paged_prefill.usable(q, kv_pool):
            # a row walks its own prompt's blocks; none, where it is empty
            counts = jnp.where(lens > 0, (starts + lens + span - 1) // span, 0)
            return paged_prefill.attend_pages(
                q, kv_pool, n, tables, counts, _selected,
                keyed=selection[:1], rowed=selection[1:4],
                shared=selection[4:], block_pages=bp)
        end = jnp.max(jnp.where(lens > 0, starts + lens, 0))
        return _attend_blocks(q, kv_pool, n, tables, bp,
                              (end + span - 1) // span, selection)


def dsa_prefill(p, cfg, x, kv_pool, ik_pool, n, page_tables, starts, lens,
                page_tokens):
    """Attention under the indexer's selection over ``R`` rows of one page
    of tokens. ``x [R, T, d]``; ``kv_pool`` the whole ``[L, pages, T, 2 KV,
    hd]`` array, ``ik_pool`` the whole ``[L, pages, 64, T]`` one and ``n``
    this layer's row of them. A row's tiles and indexer keys are written to
    its prompt's page first (one in-place update a row and array); then
    every query scores its prompt's indexer keys up to its own position, a
    block of pages at a time (``dsa_index``), its ``min(topk, position +
    1)``-th largest score is found (``dsa_select``), and the keys and values
    are walked block by block, attending where the score is above that
    threshold and, of the keys that score exactly the threshold, to the
    lowest positions that fill the count: the set ``lax.top_k`` gives the
    decode step (``dsa_attend``; both in ``attend_selected``). Returns
    ``(y, kv_pool, ik_pool)``."""
    R, T, _ = x.shape
    shape = cfg.attention
    kvh, hd = shape.num_key_value_heads, shape.head_dim
    J = shape.num_attention_heads // kvh
    pt = page_tokens
    mp = page_tables.shape[1]
    assert T == pt, (T, pt)
    pos = starts[:, None] + jnp.arange(T)[None, :]                   # [R, T]
    q, k, v = pl.gqa_project(p, shape, x)
    q, k = _rotate(p, cfg)(q, k, pos)
    qI, kI, w = indexer_project(p["indexer"], cfg, x, pos)
    logical = starts // pt
    dest = jnp.where((lens > 0) & (logical < mp),
                     jnp.take_along_axis(
                         page_tables, jnp.clip(logical, 0, mp - 1)[:, None],
                         1)[:, 0], 0)
    tiles = _tiles(cfg, k, v, kv_pool.dtype)                 # [R, T, 2KV, hd]
    ik_new = jnp.swapaxes(kI.astype(ik_pool.dtype), 1, 2)    # [R, 64, T]

    def put(r, pools):
        return (jax.lax.dynamic_update_slice(
                    pools[0], tiles[r][None, None], (n, dest[r], 0, 0, 0)),
                jax.lax.dynamic_update_slice(
                    pools[1], ik_new[r][None, None], (n, dest[r], 0, 0)))

    kv_pool, ik_pool = jax.lax.fori_loop(0, R, put, (kv_pool, ik_pool))
    tables, bp = pl.blocks_of_pages(page_tables, pl.PREFILL_KEY_BLOCK, pt)
    span = bp * pt
    end = jnp.max(jnp.where(lens > 0, starts + lens, 0))
    n_blocks = (end + span - 1) // span

    with jax.named_scope("dsa_index"):
        u = pl.index_rows(qI, w, ik_pool, n, tables, bp, pos, n_blocks)
    ctx = attend_selected(q, kv_pool, n, tables, bp, starts, lens, u,
                          cfg.topk)
    ctx = jnp.moveaxis(ctx, 3, 1).reshape(R, T, kvh * J * hd)
    return (pl.dot(ctx.astype(x.dtype), p["o_proj"]["kernel"]).astype(x.dtype),
            kv_pool, ik_pool)


def decode_blocks(selected):
    """Blocks of ``paged_prefill.TILE_SPAN`` positions that hold the
    ``selected`` positions (``min(context, topk)`` a lane) at the head of a
    lane's row, where ``lax.top_k`` puts them: what a decode step's
    attention walks. Plain arithmetic: the program's arrays and the host's
    counts alike."""
    return (selected + paged_prefill.TILE_SPAN - 1) // paged_prefill.TILE_SPAN


def attend_chosen(q, tiles, chosen, positions, active):
    """The second half of ``dsa_decode``: ``q [B, KV, J, hd]`` attends,
    of ``tiles [B, K, 2 KV, hd]`` (the tiles of the positions the lanes
    selected, in ``lax.top_k``'s order), those that are ``chosen [B, K]``.
    On a TPU at the shapes it takes, one kernel a layer that reads a block
    of tiles once for all heads and takes each head out of it in VMEM
    (``ops/paged_prefill.py::attend_tiles``), a lane walking the blocks its
    selection fills and an inactive lane none (its context is zero there);
    anywhere else the two products over all ``K``, for which the tiles'
    halves are laid out again head-major. Returns the context ``[B, KV, J,
    hd]``."""
    kvh, hd = q.shape[1], q.shape[-1]
    if paged_prefill.tiles_usable(q, tiles):
        counts = jnp.where(active, decode_blocks(
            jnp.minimum(positions + 1, tiles.shape[1])), 0)
        return paged_prefill.attend_tiles(q, tiles, chosen, counts)
    a = jnp.einsum("bgjd,bkgd->bgjk", q, tiles[:, :, :kvh],
                   preferred_element_type=jnp.float32) * hd ** -0.5
    a = jax.nn.softmax(jnp.where(chosen[:, None, None], a, -1e30), axis=-1)
    return jnp.einsum("bgjk,bkgd->bgjd", a.astype(q.dtype), tiles[:, :, kvh:],
                      preferred_element_type=jnp.float32)


def dsa_decode(p, cfg, x, kv_pool, ik_pool, n, page_tables, positions, active,
               page_tokens):
    """Attention under the indexer's selection for one token of every lane.
    ``x [B, d]``; the new tile and indexer key are written at ``positions``
    before they are scored (a tile in place; a column of the lane's indexer
    page through ``write_columns``; inactive lanes all name the spare page
    0). Then, a lane: the scores of all its indexer keys (``dsa_index``:
    the lane's pages of ``ik``, every one of them), the exact top ``topk``
    of the positions up to its own (``dsa_select``) and attention over
    those positions' tiles, which are fetched one by one and are all the
    step reads of ``kv`` (``dsa_attend``: the gather, then
    ``attend_chosen``)."""
    Bn = x.shape[0]
    shape = cfg.attention
    pt = page_tokens
    mp = page_tables.shape[1]
    logical = jnp.clip(positions // pt, 0, mp - 1)
    phys = jnp.where(active & (positions < mp * pt),
                     page_tables[jnp.arange(Bn), logical], 0)
    col = positions % pt
    q, k, v = pl.gqa_project(p, shape, x)
    q, k = _rotate(p, cfg)(q, k, positions)
    qI, kI, w = indexer_project(p["indexer"], cfg, x, positions)
    with jax.named_scope("page_write"):
        kv_pool = kv_pool.at[n, phys, col].set(
            _tiles(cfg, k, v, kv_pool.dtype))
        ik_pool = write_columns(ik_pool, (n, phys), kI, col)
    with jax.named_scope("dsa_index"):
        keys = ik_pool[n, page_tables].astype(x.dtype)       # [B, mp, 64, pt]
        s = index_scores(qI, w, keys)                        # [B, mp * pt]
    with jax.named_scope("dsa_select"):
        at, chosen = select_topk(s, positions, cfg.topk)             # [B, K]
    with jax.named_scope("dsa_attend"):
        page = pl.pages_of(at, page_tables, pt)
        tiles = kv_pool[n, page, at % pt].astype(x.dtype)    # [B, K, 2KV, hd]
        ctx = attend_chosen(q, tiles, chosen, positions, active)
    ctx = ctx.reshape(Bn, -1)
    return (pl.dot(ctx.astype(x.dtype), p["o_proj"]["kernel"]).astype(x.dtype),
            kv_pool, ik_pool)


# -- the two programs -------------------------------------------------------

def _experts(lp, cfg, x, live, tile, every_expert=False):
    """A layer's experts over flat tokens ``x [N, d]``; ``live [N]`` says
    which tokens are real. Returns ``(y, counts [3] int32)`` as
    ``expert.routed_moe_ffn`` gives them."""
    return expert_mod.routed_moe_ffn(
        lp["mlp"], x, live, k=cfg.num_experts_per_tok, scaling=1.0,
        renormalize=cfg.norm_topk_prob, held=cfg.experts_held, tile=tile,
        every_expert=every_expert, scoring="softmax")


def prefill_chunk(params, cfg, state, ids, slots, starts, lens, page_tables,
                  *, page_tokens, moe_tile=128):
    """``R`` rows of the prompts being read, as every family of rows of one
    page takes them: ``ids [R, T]`` with ``T = page_tokens``, ``slots [R]``
    (read by nothing: no state is a slot's), ``starts [R]`` (a multiple of
    ``T``), ``lens [R]`` (0: an empty row, which writes the spare page),
    ``page_tables [R, mp]``. Returns ``(state, first [R], logits [R,
    V])``."""
    del slots
    R, T = ids.shape
    assert T == page_tokens, (T, page_tokens)
    eps = cfg.rms_norm_eps
    h = params["embed_tokens"]["embedding"][ids]
    live = (jnp.arange(T)[None, :] < lens[:, None]).reshape(R * T)
    kv_pool, ik_pool = state["kv"], state["ik"]
    for l in range(cfg.num_hidden_layers):
        lp = params["layers"][str(l)]
        x = pl.rms_norm(h, lp["input_layernorm"]["scale"], eps)
        y, kv_pool, ik_pool = dsa_prefill(
            lp["self_attn"], cfg, x, kv_pool, ik_pool, l, page_tables,
            starts, lens, page_tokens)
        h = h + y
        x = pl.rms_norm(h, lp["post_attention_layernorm"]["scale"], eps)
        y, _ = _experts(lp, cfg, x.reshape(R * T, -1), live, moe_tile)
        h = h + y.reshape(h.shape)
    at = jnp.clip(lens - 1, 0, T - 1)
    h_last = jnp.take_along_axis(h, at[:, None, None], axis=1)[:, 0]
    logits = pl.lm_head(h_last, params["norm"]["scale"], eps,
                        params["lm_head"]["kernel"])
    first = jnp.argmax(logits, -1).astype(jnp.int32)
    return {"kv": kv_pool, "ik": ik_pool}, first, logits


def decode_step(params, cfg, state, tokens, positions, active, page_tables,
                *, page_tokens, moe_tile=16):
    """One token for every active lane (lane ``b`` is slot ``b``). Returns
    ``(state, tokens, positions, logits [B, V], moe [3] int32)``; ``moe``
    sums, over this step's expert layers, the picks that fell on held
    experts, the held experts touched and the busiest one's tokens (active
    lanes only). An expert layer reads every held expert, picked or not: an
    eighth of the experts under a full batch's picks leaves few idle in a
    step, which ones follows the weights, and a step that reads them all
    takes the same time whatever they are (``moe`` still counts the experts
    that were picked)."""
    eps = cfg.rms_norm_eps
    h = params["embed_tokens"]["embedding"][tokens]
    kv_pool, ik_pool = state["kv"], state["ik"]
    moe = jnp.zeros(3, jnp.int32)
    for l in range(cfg.num_hidden_layers):
        lp = params["layers"][str(l)]
        x = pl.rms_norm(h, lp["input_layernorm"]["scale"], eps)
        y, kv_pool, ik_pool = dsa_decode(
            lp["self_attn"], cfg, x, kv_pool, ik_pool, l, page_tables,
            positions, active, page_tokens)
        h = h + y
        x = pl.rms_norm(h, lp["post_attention_layernorm"]["scale"], eps)
        y, counts = _experts(lp, cfg, x, active, moe_tile, every_expert=True)
        moe = moe + counts
        h = h + y
    logits = pl.lm_head(h, params["norm"]["scale"], eps,
                        params["lm_head"]["kernel"])
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    tokens = jnp.where(active, nxt, tokens)
    positions = jnp.where(active, positions + 1, positions)
    return {"kv": kv_pool, "ik": ik_pool}, tokens, positions, logits, moe

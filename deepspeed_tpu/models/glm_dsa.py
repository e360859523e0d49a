"""GLM-5.2 decoder (zai-org/GLM-5.2, ``model_type: glm_moe_dsa``): pure
functions of a parameter tree, for serving. The multi-token-prediction layer
of the published model (``num_nextn_predict_layers`` 1) drafts for its own
model and is not served: greedy verification serves the same tokens with and
without it.

Block ``l``: ``h += Attn_l(RMSNorm(h)); h += FFN_l(RMSNorm(h))``; final
RMSNorm; untied ``lm_head``; no bias in any projection.

``Attn`` is latent attention (MLA) with a query latent and a decoupled,
rotated key: ``cq = RMSNorm(x W_qa)``, ``q = cq W_qb`` as 64 heads of (192
nope | 64 rope), the rope part rotated; ``[c | kr] = x W_kva``, ``c =
RMSNorm(c)``, ``kr`` rotated (one rotary key for all heads). **A token caches
``[c, kr]``, 576 values**, and ``W_kvb`` gives head ``h`` its ``k_nope = c
W_kvb^K_h`` and ``v = c W_kvb^V_h``. Both programs attend in the absorbed
form: ``W_kvb^K`` goes into the query and ``W_kvb^V`` onto the context, so
the latent rows are read as cached and a query head is 576 wide against ONE
shared key of 576 and value of 512 (a prefill row is one page of 128 queries:
expanding a block of 512 keys for it costs 15 GFLOP where the absorbed
products cost 9, and on the chip a layer's walk took 46 ms absorbed against
86 expanded: ``PERF.md``, PR 50). Rotation is over **interleaved pairs**
(channels ``(2i, 2i + 1)`` turn together, ``rope_interleave``); the rotated
channels are kept even ones first, then odd ones, in queries and keys alike,
which changes no product.

Attention reads only the positions a **learned indexer** selects (DeepSeek-
V3.2's, on this model's sizes): ``qI = cq W_qI`` as 32 heads of 128 (from
the QUERY LATENT), ``kI = LayerNorm(x W_kI)`` (one key head), ``w = x W_w``;
the first 64 channels of every ``qI`` head and of ``kI`` rotated; ``I[t, s]
= sum_j w[t, j] relu(qI[t, j] . kI[s]) / sqrt(32 x 128)`` in float32; the
``min(index_topk, t + 1)`` positions with the largest score are attended,
the lower position first among equals, one set a token for all heads. **The
indexer runs in the layers ``indexer_types`` calls ``full``** (21 of the
published 78) and caches ``kI``, 128 values a token, there alone; **a
``shared`` layer has no indexer weights and no indexer cache and attends
under the selection of the nearest ``full`` layer below it**: the walk hands
the selection on from layer to layer, in prefill each row's per-query
threshold and tie counts, in decode each lane's chosen positions.

``FFN`` is a dense SwiGLU where ``mlp_layer_types`` says ``dense`` and the
sigmoid-routed expert layer of ``parallel/expert.py`` elsewhere (a
correction bias that chooses and does not weigh, the 8 picked scores
renormalised and scaled by ``routed_scaling_factor``, one shared expert);
this program holds ``experts_held`` of the ``n_routed_experts`` and adds up
what those give.

This program holds ``num_hidden_layers`` layers of the published lists
starting at ``first_layer``; a layer's parameters are under its PUBLISHED
number (``layers/<l>``).

Two entry points, both functions of ``(params, cfg, state, ...)`` that the
serving engine jits, with the arguments of every slot-state family's:

- ``prefill_chunk``: ``R`` rows of one page of tokens, each the next tokens
  of some prompt. A layer writes each row's latent rows as one page; a
  ``full`` layer also writes the row's indexer keys, scores the rows' queries
  against their prompts' indexer keys (``dsa_index``) and finds each query's
  threshold (``dsa_select``); every layer walks the latent pages a block at
  a time under the selection as its mask (``mla_attend``: on a TPU one
  kernel a layer, ``ops/paged_prefill.py::attend_latent``, in which a row
  walks its own blocks; elsewhere ``_attend_blocks``).
- ``decode_step``: one token for every active lane. A ``full`` layer scores
  the lane's indexer keys and takes the exact top ``index_topk``; every layer
  **fetches those positions' latent rows and no others** (``dsa_fetch``: a
  gather of ``[B, 512, 640]`` a block of the selection) and attends them as
  they lie.

``state`` is ``{"latent": [L, pages, page_tokens, 640], "ik": [n_full,
pages, 128, page_tokens]}``: pages only, no slot array. A latent page holds
its tokens FIRST, each a row of its own padded to whole 128-lane tiles
(``latent_row``), so that single rows can be fetched (``models/
kimi_linear.py`` keeps the same 576 values token-last because it reads
every row; ``PERF.md``, PR 42, measured what fetching single columns of
such a page costs); the indexer's keys lie token-last because every one of
them is read, and exist only for the ``full`` layers. Page 0 is the sink for
positions that are not live. The router and the index scores are float32
whatever the parameters' type.
"""

import contextlib
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import paged_layers as pl
from deepspeed_tpu.ops import paged_prefill
from deepspeed_tpu.ops.column_write import write_columns
from deepspeed_tpu.parallel import expert as expert_mod


@dataclass(frozen=True)
class GlmDsaConfig:
    """The published keys of ``config.json`` (``rope_parameters.rope_theta``
    by its own name), plus the share of a deployment this program holds:
    ``num_hidden_layers`` layers from ``first_layer`` of the published
    per-layer lists, ``experts_held`` (first, count) of the
    ``n_routed_experts`` the router scores, and ``vocab_size`` rows of the
    vocabulary starting at ``vocab_first``."""

    vocab_size: int = 154880
    hidden_size: int = 6144
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 78
    num_attention_heads: int = 64
    num_key_value_heads: int = 64
    head_dim: int = 192             # as published; read by nothing
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    qk_head_dim: int = 256
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    index_topk_freq: int = 4
    index_skip_topk_offset: int = 3
    index_topk_pattern: tuple = None
    indexer_types: tuple = None     # None: from the two numbers above
    mlp_layer_types: tuple = None   # None: from first_k_dense_replace
    first_k_dense_replace: int = 3
    moe_layer_freq: int = 1
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-5
    rope_theta: float = 8000000.0
    rope_interleave: bool = True
    indexer_rope_interleave: bool = True
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 1048576
    num_nextn_predict_layers: int = 1   # not served; read by nothing
    experts_held: tuple = None      # (first, count); None = all
    vocab_first: int = 0
    first_layer: int = 0

    def __post_init__(self):
        last = self.first_layer + self.num_hidden_layers
        if self.indexer_types is None:
            off, freq = self.index_skip_topk_offset, self.index_topk_freq
            kinds = tuple("full" if l < off or (l - off) % freq == freq - 1
                          else "shared" for l in range(last))
        else:
            kinds = tuple(self.indexer_types)
        if self.mlp_layer_types is None:
            mlps = tuple("dense" if l < self.first_k_dense_replace
                         else "sparse" for l in range(last))
        else:
            mlps = tuple(self.mlp_layer_types)
        object.__setattr__(self, "indexer_types", kinds)
        object.__setattr__(self, "mlp_layer_types", mlps)
        object.__setattr__(self, "experts_held", expert_mod.held_share(
            self.experts_held, self.n_routed_experts))
        for name, kinds_, known in (
                ("indexer_types", kinds, ("full", "shared")),
                ("mlp_layer_types", mlps, ("dense", "sparse"))):
            if len(kinds_) < last:
                raise ValueError(f"{name} names {len(kinds_)} layers, the "
                                 f"program holds layers up to {last - 1}")
            if set(kinds_) - set(known):
                raise ValueError(f"{name}: {known[0]} or {known[1]}, not "
                                 f"{sorted(set(kinds_) - set(known))}")
        if kinds[self.first_layer] != "full":
            raise ValueError(
                f"first_layer={self.first_layer}: a shared layer attends "
                f"under the selection of a full layer below it, which this "
                f"program would not hold")
        if self.qk_head_dim != self.qk_nope_head_dim + self.qk_rope_head_dim:
            raise ValueError("qk_head_dim is qk_nope_head_dim + "
                             "qk_rope_head_dim")
        if self.index_topk < 1:
            raise ValueError(f"index_topk={self.index_topk}")
        # what the published model does not do and this program does not
        # compute: refused by the key's name
        if self.index_topk_pattern is not None:
            raise ValueError("index_topk_pattern: indexer_types says which "
                             "layers select")
        if not (self.rope_interleave and self.indexer_rope_interleave):
            raise ValueError("rope_interleave/indexer_rope_interleave: "
                             "rotation is over interleaved pairs")
        if self.scoring_func != "sigmoid" or self.topk_method != "noaux_tc":
            raise ValueError("scoring_func/topk_method: a sigmoid router "
                             "whose correction bias chooses")
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError("n_group/topk_group: the router picks among "
                             "all experts, in no groups")
        if self.n_shared_experts not in (0, 1):
            raise ValueError("n_shared_experts must be 0 or 1")
        if self.moe_layer_freq != 1:
            raise ValueError("moe_layer_freq: mlp_layer_types says which "
                             "layers have experts")
        if self.hidden_act != "silu":
            raise ValueError("hidden_act: the FFNs are SwiGLUs")
        if self.attention_bias:
            raise ValueError("attention_bias: the projections have no bias")
        if self.tie_word_embeddings:
            raise ValueError("tie_word_embeddings: the head is its own "
                             "matrix")

    @classmethod
    def from_dict(cls, cfg, **share):
        """From the keys of the published ``config.json``."""
        rope = cfg.get("rope_parameters") or {}
        if rope.get("rope_type", "default") != "default":
            raise ValueError(f"rope_parameters {rope}: plain frequencies "
                             f"only")
        own = ("experts_held", "vocab_first", "first_layer")
        kw = {k: cfg[k] for k in cls.__dataclass_fields__
              if k in cfg and k not in own}
        if "rope_theta" in rope:
            kw["rope_theta"] = rope["rope_theta"]
        for name in ("indexer_types", "mlp_layer_types"):
            if kw.get(name) is not None:
                kw[name] = tuple(kw[name])
        kw.update(share)
        return cls(**kw)

    # -- derived ---------------------------------------------------------
    @property
    def layers(self):
        """The published numbers of the layers held, in order."""
        return tuple(range(self.first_layer,
                           self.first_layer + self.num_hidden_layers))

    def selects(self, l):
        """Whether layer ``l`` (published number) runs the indexer."""
        return self.indexer_types[l] == "full"

    def layer_is_moe(self, l):
        return self.mlp_layer_types[l] == "sparse"

    @property
    def latent_index(self):
        """{layer: row of the latent pool}."""
        return {l: n for n, l in enumerate(self.layers)}

    @property
    def indexer_index(self):
        """{layer: row of the indexer's key pool}, the ``full`` layers."""
        return {l: n for n, l in enumerate(
            l for l in self.layers if self.selects(l))}

    @property
    def n_moe_layers(self):
        return sum(self.layer_is_moe(l) for l in self.layers)

    @property
    def latent_width(self):
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self):
        """The width of a token's row in a latent page: ``latent_width``
        padded with zeros to whole 128-lane tiles (576 -> 640). The chip
        lays an array's last axis along its 128 lanes; a last axis that is
        not whole tiles makes XLA put the page's 128 tokens there instead
        and the array is token-last in memory whatever its shape says: every
        program that indexed a token then copied the whole pool into a
        padded layout and back (compiled for a v5e: two temporaries of the
        pool's size, ``PERF.md``, PR 50)."""
        return -(-self.latent_width // 128) * 128

    @property
    def rope(self):
        return pl.RopeSpec(rope_theta=self.rope_theta)

    @property
    def cache_arrays(self):
        """{name of a pool array: (its rows, what a token caches there)}, as
        ``HybridStatePool`` takes a paged array's description: ``latent``,
        a row every layer, a token's shape (pages with their tokens first,
        each a row that can be fetched alone); ``ik``, a row a ``full`` layer
        only, a width (pages with their tokens last)."""
        return {"latent": (self.num_hidden_layers, (self.latent_row,)),
                "ik": (len(self.indexer_index), self.index_head_dim)}


def check_params(params, cfg):
    """A ``full`` layer has the indexer's weights and a ``shared`` layer has
    none: refused by the layer's number otherwise (a ``shared`` layer with an
    indexer of its own is another model)."""
    for l in cfg.layers:
        has = "indexer" in params["layers"][str(l)]["self_attn"]
        if has != cfg.selects(l):
            raise ValueError(
                f"layers/{l}/self_attn/indexer: layer {l} is "
                f"{cfg.indexer_types[l]!r} in indexer_types and "
                + ("has indexer weights: it attends under the selection of "
                   "the full layer below it" if has
                   else "has no indexer weights"))


# -- rotation -----------------------------------------------------------------

def rope_pairs(spec, x, positions, r):
    """The first ``r`` channels of each head of ``x [..., hd]`` rotated to
    ``positions`` as interleaved pairs: channels ``(2i, 2i + 1)`` turn
    together by ``position x theta^(-2i / r)``. The rotated channels come
    out even ones first, then odd ones (``[x_2i', ..., x_2i+1', ..., rest]``):
    queries and keys are laid out alike, so no product changes. Float32
    inside, ``x``'s type out."""
    inv, _ = pl.rope_inv_freq(spec, r)
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        inv, jnp.float32)
    over_heads = positions.shape + (1,) * (x.ndim - 1 - positions.ndim) + (
        r // 2,)
    cos, sin = jnp.cos(ang).reshape(over_heads), jnp.sin(ang).reshape(
        over_heads)
    pairs = x[..., :r].astype(jnp.float32).reshape(x.shape[:-1] + (r // 2, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin,
         x[..., r:].astype(jnp.float32)], axis=-1).astype(x.dtype)


# -- what a layer's attention is given ---------------------------------------

def _kv_b(p, cfg):
    """``kv_b_proj`` as ``[rank, heads, nope + v]``."""
    return p["kv_b_proj"]["kernel"].reshape(
        cfg.kv_lora_rank, cfg.num_attention_heads,
        cfg.qk_nope_head_dim + cfg.v_head_dim)


def mla_project(p, cfg, x, positions):
    """Of ``x [..., d]`` at ``positions [...]``: the query latent ``cq [...,
    q_lora_rank]``, the absorbed queries ``[..., heads, latent_row]``
    (``W_kvb^K`` taken into the nope part, the rope part rotated) and the row
    a token caches, ``[RMSNorm(c), kr rotated]``, both with zeros up to
    ``latent_row``, all in ``x``'s type."""
    nh, dn, dr = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                  cfg.qk_rope_head_dim)
    rank = cfg.kv_lora_rank
    with jax.named_scope("mla_project"):
        cq = pl.rms_norm(pl.dot(x, p["q_a_proj"]["kernel"]).astype(x.dtype),
                         p["q_a_layernorm"]["scale"], cfg.rms_norm_eps)
        q = pl.dot(cq, p["q_b_proj"]["kernel"]).astype(x.dtype).reshape(
            x.shape[:-1] + (nh, dn + dr))
        ckr = pl.dot(x, p["kv_a_proj_with_mqa"]["kernel"]).astype(x.dtype)
        c = pl.rms_norm(ckr[..., :rank], p["kv_a_layernorm"]["scale"],
                        cfg.rms_norm_eps)
    with jax.named_scope("rope"):
        q_rope = rope_pairs(cfg.rope, q[..., dn:], positions, dr)
        kr = rope_pairs(cfg.rope, ckr[..., None, rank:], positions,
                        dr)[..., 0, :]
    with jax.named_scope("mla_absorb"):
        q_lat = jnp.einsum("...hd,chd->...hc", q[..., :dn],
                           _kv_b(p, cfg)[..., :dn],
                           preferred_element_type=jnp.float32).astype(x.dtype)
    pad = cfg.latent_row - cfg.latent_width
    return (cq, jnp.concatenate(
                [q_lat, q_rope, jnp.zeros(q_rope.shape[:-1] + (pad,),
                                          x.dtype)], -1),
            jnp.concatenate([c, kr, jnp.zeros(kr.shape[:-1] + (pad,),
                                              x.dtype)], -1))


def mla_output(p, cfg, ctx_lat, dtype):
    """``ctx_lat [..., heads, rank]``, the attention's weighted latent rows,
    through ``W_kvb^V`` and ``o_proj``: ``[..., d]``."""
    ctx = jnp.einsum("...hc,chd->...hd", ctx_lat.astype(dtype),
                     _kv_b(p, cfg)[..., cfg.qk_nope_head_dim:],
                     preferred_element_type=jnp.float32)
    ctx = ctx.reshape(ctx.shape[:-2] + (-1,)).astype(dtype)
    return pl.dot(ctx, p["o_proj"]["kernel"]).astype(dtype)


def indexer_project(p, cfg, x, cq, positions):
    """The indexer's inputs for ``x [..., d]`` with query latent ``cq`` at
    ``positions [...]``: ``qI [..., 32, 128]`` (from ``cq``) and ``kI [...,
    128]`` (from ``x``) in ``x``'s type (as the cache holds the keys), their
    first ``qk_rope_head_dim`` channels rotated; ``w [..., 32]`` float32 with
    both of the score's scales in it (``1 / sqrt(heads x head)``)."""
    ni, hi, dr = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    with jax.named_scope("dsa_project"):
        qI = pl.dot(cq, p["wq_b"]["kernel"]).reshape(x.shape[:-1] + (ni, hi))
        kI = pl.layer_norm(pl.dot(x, p["wk"]["kernel"]), p["k_norm"],
                           cfg.rms_norm_eps)
        qI = rope_pairs(cfg.rope, qI, positions, dr).astype(x.dtype)
        kI = rope_pairs(cfg.rope, kI[..., None, :], positions, dr)[..., 0, :]
        w = pl.dot(x, p["weights_proj"]["kernel"]) * (ni * hi) ** -0.5
    return qI, kI.astype(x.dtype), w


def _softmax_scale(cfg):
    return cfg.qk_head_dim ** -0.5


def _carried(shared):
    """The scope a ``shared`` layer's attention is traced under: what it
    reads and attends, it reads and attends under a selection that another
    layer computed."""
    return jax.named_scope("dsa_carry") if shared else contextlib.nullcontext()


# -- prefill ------------------------------------------------------------------

def _attend_blocks(cfg, q, latent, n, tables, bp, n_blocks, selection):
    """``q [R, T, heads, latent_row]`` (absorbed) over ``n_blocks`` key
    blocks of ``bp`` pages of row ``n`` of ``latent [L, pages, pt,
    latent_row]`` under the selection (``paged_layers.selected``'s operands,
    a row each), every row to the call's longest: a block's latent rows are the
    keys as they lie and, their first ``rank`` values, the values. The walk
    in plain operations, the twin of ``paged_prefill.attend_latent`` for
    every backend but a TPU (and every shape but its own): a block's float32
    scores and the accumulator ``[R, heads, T, span]`` are arrays in memory
    here. Returns the weighted latent rows ``[R, heads, T, rank]`` float32."""
    R, T, nh, _ = q.shape
    rank = cfg.kv_lora_rank
    u, least, ties_left, ties_before, below = selection
    span = below.shape[0]
    scale = _softmax_scale(cfg)

    def block(j):
        pages = jax.lax.dynamic_slice_in_dim(tables, j * bp, bp, axis=1)
        lat = latent[n, pages].astype(q.dtype).reshape(R, span, -1)
        s = jnp.einsum("rthc,rsc->rhts", q, lat,
                       preferred_element_type=jnp.float32) * scale
        uj = jax.lax.dynamic_slice_in_dim(u, j * span, span, axis=1)
        ok = jax.vmap(pl.selected, in_axes=(None, 0, 0, 0, 0, None))(
            j, uj, least, ties_left, ties_before, below)     # [R, span, T]
        s = jnp.where(jnp.swapaxes(ok, 1, 2)[:, None], s, -1e30)

        def weigh(pr):
            return jnp.einsum("rhts,rsc->rhtc", pr.astype(q.dtype),
                              lat[..., :rank],
                              preferred_element_type=jnp.float32)
        return s, weigh

    return pl.online_softmax_loop(n_blocks, block, (R, nh, T), rank)


def attend_selected(cfg, q, latent, n, tables, bp, n_blocks, starts, lens,
                    selection):
    """The walk of ``mla_prefill``: ``q [R, T, heads, latent_row]``
    (absorbed) attends, of row ``n`` of ``latent`` under ``tables [R, blocks
    * bp]``, the keys ``selection`` names (``paged_layers.row_selection``'s
    tuple for these queries). On a TPU at the shapes it takes, one kernel a
    layer that keeps a block's scores and the accumulator in VMEM and walks
    each row's own blocks (``ops/paged_prefill.py::attend_latent``); anywhere
    else ``_attend_blocks``, every row to the ``n_blocks`` of the call's
    longest. Returns the weighted latent rows ``[R, heads, T, rank]``."""
    if paged_prefill.latent_usable(q, latent, cfg.kv_lora_rank):
        span = bp * q.shape[1]
        # a row walks its own prompt's blocks; none, where it is empty
        counts = jnp.where(lens > 0, (starts + lens + span - 1) // span, 0)
        return paged_prefill.attend_latent(
            q, latent, n, tables, counts, pl.selected, keyed=selection[:1],
            rowed=selection[1:4], shared=selection[4:], block_pages=bp,
            rank=cfg.kv_lora_rank, scale=_softmax_scale(cfg))
    return _attend_blocks(cfg, q, latent, n, tables, bp, n_blocks, selection)


def mla_prefill(p, cfg, x, latent, ik, rows, page_tables, starts, lens,
                page_tokens, selection):
    """A layer's attention over ``R`` rows of one page of tokens. ``x [R,
    T, d]``; ``latent`` and ``ik`` the whole pool arrays, ``rows = (n, m)``
    this layer's row of each (``m`` None in a ``shared`` layer, which then
    attends under ``selection``, the one the nearest ``full`` layer below
    found for these same queries). A row's latent rows are written to its
    prompt's page first (one in-place update a row); a ``full`` layer also
    writes the row's indexer keys, scores every query against its prompt's
    indexer keys up to its own position (``dsa_index``) and finds each
    query's ``min(index_topk, position + 1)``-th largest score and the tie
    counts (``dsa_select``); then the latent pages are walked block by block,
    attending where the selection says (``mla_attend``; a ``shared`` layer
    under the scope ``dsa_carry``). Returns ``(y, latent, ik,
    selection)``."""
    R, T, _ = x.shape
    n, m = rows
    pt = page_tokens
    mp = page_tables.shape[1]
    assert T == pt, (T, pt)
    pos = starts[:, None] + jnp.arange(T)[None, :]                   # [R, T]
    cq, q, new = mla_project(p, cfg, x, pos)
    logical = starts // pt
    dest = jnp.where((lens > 0) & (logical < mp),
                     jnp.take_along_axis(
                         page_tables, jnp.clip(logical, 0, mp - 1)[:, None],
                         1)[:, 0], 0)
    new = new.astype(latent.dtype)                           # [R, T, 640]
    with jax.named_scope("mla_latent_write"):
        latent = jax.lax.fori_loop(
            0, R, lambda r, pool: jax.lax.dynamic_update_slice(
                pool, new[r][None, None], (n, dest[r], 0, 0)), latent)
    tables, bp = pl.blocks_of_pages(page_tables, pl.PREFILL_KEY_BLOCK, pt)
    span = bp * pt
    end = jnp.max(jnp.where(lens > 0, starts + lens, 0))
    n_blocks = (end + span - 1) // span
    if m is not None:
        qI, kI, w = indexer_project(p["indexer"], cfg, x, cq, pos)
        ik_new = jnp.swapaxes(kI.astype(ik.dtype), 1, 2)     # [R, 128, T]
        with jax.named_scope("dsa_key_write"):
            ik = jax.lax.fori_loop(
                0, R, lambda r, pool: jax.lax.dynamic_update_slice(
                    pool, ik_new[r][None, None], (m, dest[r], 0, 0)), ik)
        with jax.named_scope("dsa_index"):
            u = pl.index_rows(qI, w, ik, m, tables, bp, pos, n_blocks)
        with jax.named_scope("dsa_select"):
            selection = pl.row_selection(u, pos, cfg.index_topk, span,
                                         x.dtype)
    with _carried(m is None), jax.named_scope("mla_attend"):
        ctx_lat = attend_selected(cfg, q, latent, n, tables, bp, n_blocks,
                                  starts, lens, selection)
        y = mla_output(p, cfg, jnp.swapaxes(ctx_lat, 1, 2), x.dtype)
    return y, latent, ik, selection


# -- decode -------------------------------------------------------------------

FETCH_BLOCK = 512       # selected positions a decode step fetches at a time


def attend_rows(cfg, q, rows, chosen):
    """``q [B, heads, latent_row]`` (absorbed) against the latent rows
    ``rows [B, k, latent_row]`` its lane fetched, of which those ``chosen
    [B, k]`` count: a row is the key as it lies and, its first ``rank``
    values, the value. Returns the masked float32 scores ``[B, heads, k]``
    and the function from probabilities to weighted latent rows ``[B, heads,
    rank]``, as ``paged_layers.online_softmax_loop`` takes a block."""
    s = jnp.einsum("bhc,bkc->bhk", q, rows,
                   preferred_element_type=jnp.float32) * _softmax_scale(cfg)

    def weigh(pr):
        return jnp.einsum("bhk,bkc->bhc", pr.astype(q.dtype),
                          rows[..., :cfg.kv_lora_rank],
                          preferred_element_type=jnp.float32)
    return jnp.where(chosen[:, None], s, -1e30), weigh


def attend_fetched(cfg, q, latent, n, page, col, chosen):
    """The second half of ``mla_decode``: ``q [B, heads, latent_row]``
    attends the positions ``(page, col) [B, K]`` of row ``n`` of ``latent``
    that are ``chosen [B, K]``, ``FETCH_BLOCK`` of them at a time: a block's
    rows are fetched (``dsa_fetch``: one gather of ``[B, block, latent_row]``,
    which with the other blocks' is all the step reads of ``latent``),
    scored and weighed while they are there, and the running softmax goes on
    to the next block. (All ``K`` fetched at once and used by the two
    products in turn, the compiler for this chip fetched them twice in four
    layers of six rather than hold 168 MB between the products beside 14.75
    GB of weights and pages: ``PERF.md``, PR 50.) Returns the weighted latent
    rows ``[B, heads, rank]`` float32."""
    B, K = page.shape
    span = FETCH_BLOCK if K % FETCH_BLOCK == 0 else K

    def block(j):
        pj, cj, okj = (jax.lax.dynamic_slice_in_dim(a, j * span, span, axis=1)
                       for a in (page, col, chosen))
        with jax.named_scope("dsa_fetch"):
            rows = latent[n, pj, cj].astype(q.dtype)   # [B, span, 640]
        return attend_rows(cfg, q, rows, okj)

    return pl.online_softmax_loop(K // span, block, q.shape[:2],
                                  cfg.kv_lora_rank)


def mla_decode(p, cfg, x, latent, ik, rows, page_tables, positions, active,
               page_tokens, selection):
    """A layer's attention for one token of every lane. ``x [B, d]``; the
    new latent row (and, in a ``full`` layer, the new indexer key: a column
    of the lane's page through ``write_columns``) is written at
    ``positions`` before anything is scored; inactive lanes all name the
    spare page 0. A ``full`` layer (``rows = (n, m)`` with ``m`` its row of
    ``ik``) scores all of the lane's indexer keys (``dsa_index``) and takes
    the exact top ``index_topk`` of the positions up to its own
    (``dsa_select``); a ``shared`` layer (``m`` None) takes ``selection``,
    the ``(page, column, chosen)`` of each lane's positions as the nearest
    ``full`` layer below chose them, over. Either then fetches those
    positions' latent rows, which are all the step reads of ``latent``, and
    attends them (``mla_attend``, the fetch ``dsa_fetch`` inside it), a
    ``shared`` layer under the scope ``dsa_carry``. Returns
    ``(y, latent, ik, selection)``."""
    Bn = x.shape[0]
    n, m = rows
    pt = page_tokens
    mp = page_tables.shape[1]
    logical = jnp.clip(positions // pt, 0, mp - 1)
    phys = jnp.where(active & (positions < mp * pt),
                     page_tables[jnp.arange(Bn), logical], 0)
    col = positions % pt
    cq, q, new = mla_project(p, cfg, x, positions)
    with jax.named_scope("mla_latent_write"):
        latent = latent.at[n, phys, col].set(new.astype(latent.dtype))
    if m is None:
        page, at_col, chosen = selection
    else:
        qI, kI, w = indexer_project(p["indexer"], cfg, x, cq, positions)
        with jax.named_scope("dsa_key_write"):
            ik = write_columns(ik, (m, phys), kI, col)
        with jax.named_scope("dsa_index"):
            keys = ik[m, page_tables].astype(x.dtype)    # [B, mp, 128, pt]
            s = pl.index_scores(qI, w, keys)             # [B, mp * pt]
        with jax.named_scope("dsa_select"):
            # the two zeros are one score (``lax.top_k`` orders -0 below
            # +0; the prefill's threshold does not tell them apart)
            at, chosen = pl.select_topk(jnp.where(s == 0, 0.0, s), positions,
                                        cfg.index_topk)
            page, at_col = pl.pages_of(at, page_tables, pt), at % pt
    with _carried(m is None), jax.named_scope("mla_attend"):
        ctx_lat = attend_fetched(cfg, q, latent, n, page, at_col, chosen)
        y = mla_output(p, cfg, ctx_lat, x.dtype)
    return y, latent, ik, (page, at_col, chosen)


# -- the two programs -------------------------------------------------------

def _ffn(lp, cfg, l, x, live, tile, every_expert=False):
    """Layer ``l``'s FFN over flat tokens ``x [N, d]``; ``live [N]`` says
    which tokens are real. Returns ``(y, counts [3] int32)`` as
    ``expert.routed_moe_ffn`` gives them (zeros for a dense layer)."""
    if not cfg.layer_is_moe(l):
        return pl.swiglu(x, lp["mlp"]), jnp.zeros(3, jnp.int32)
    return expert_mod.routed_moe_ffn(
        lp["mlp"], x, live, k=cfg.num_experts_per_tok,
        scaling=cfg.routed_scaling_factor, renormalize=cfg.norm_topk_prob,
        held=cfg.experts_held, tile=tile, every_expert=every_expert)


def _walk(params, cfg, state, h, attend, ffn):
    """The decoder's layers over ``h``: ``attend(lp, x, latent, ik, rows,
    selection)`` and ``ffn(lp, l, x)``, the selection handed from a ``full``
    layer to the ``shared`` layers above it. Returns ``(h, state, moe)``."""
    eps = cfg.rms_norm_eps
    latent, ik = state["latent"], state["ik"]
    moe = jnp.zeros(3, jnp.int32)
    selection = None
    for l in cfg.layers:
        lp = params["layers"][str(l)]
        rows = (cfg.latent_index[l], cfg.indexer_index.get(l))
        x = pl.rms_norm(h, lp["input_layernorm"]["scale"], eps)
        y, latent, ik, selection = attend(lp["self_attn"], x, latent, ik,
                                          rows, selection)
        h = h + y
        x = pl.rms_norm(h, lp["post_attention_layernorm"]["scale"], eps)
        y, counts = ffn(lp, l, x)
        moe = moe + counts
        h = h + y
    return h, {"latent": latent, "ik": ik}, moe


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
    live = (jnp.arange(T)[None, :] < lens[:, None]).reshape(R * T)

    def attend(p, x, latent, ik, rows, selection):
        return mla_prefill(p, cfg, x, latent, ik, rows, page_tables, starts,
                           lens, page_tokens, selection)

    def ffn(lp, l, x):
        y, counts = _ffn(lp, cfg, l, x.reshape(R * T, -1), live, moe_tile)
        return y.reshape(x.shape), counts

    h, state, _ = _walk(params, cfg, state,
                        params["embed_tokens"]["embedding"][ids], attend, ffn)
    at = jnp.clip(lens - 1, 0, T - 1)
    h_last = jnp.take_along_axis(h, at[:, None, None], axis=1)[:, 0]
    logits = pl.lm_head(h_last, params["norm"]["scale"], cfg.rms_norm_eps,
                        params["lm_head"]["kernel"])
    return state, jnp.argmax(logits, -1).astype(jnp.int32), logits


def decode_step(params, cfg, state, tokens, positions, active, page_tables,
                *, page_tokens, moe_tile=16):
    """One token for every active lane (lane ``b`` is slot ``b``). Returns
    ``(state, tokens, positions, logits [B, V], moe [3] int32)``; ``moe``
    sums, over this step's expert layers, the picks that fell on held
    experts, the held experts touched and the busiest one's tokens (active
    lanes only). An expert layer reads every held expert, picked or not
    (``every_expert``): a sixteenth of the experts under a full batch's picks
    leaves two in sixteen idle a step, which ones follows the weights, and a
    step that reads them all takes the same time whatever they are."""
    def attend(p, x, latent, ik, rows, selection):
        return mla_decode(p, cfg, x, latent, ik, rows, page_tables,
                          positions, active, page_tokens, selection)

    def ffn(lp, l, x):
        return _ffn(lp, cfg, l, x, active, moe_tile, every_expert=True)

    h, state, moe = _walk(params, cfg, state,
                          params["embed_tokens"]["embedding"][tokens],
                          attend, ffn)
    logits = pl.lm_head(h, params["norm"]["scale"], cfg.rms_norm_eps,
                        params["lm_head"]["kernel"])
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    tokens = jnp.where(active, nxt, tokens)
    positions = jnp.where(active, positions + 1, positions)
    return state, tokens, positions, logits, moe

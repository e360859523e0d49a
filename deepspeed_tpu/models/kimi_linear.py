"""Kimi-Linear decoder (moonshotai/Kimi-Linear-48B-A3B): pure functions of a
parameter tree, for serving.

Block: ``h += Mixer(RMSNorm(h)); h += FFN(RMSNorm(h))``; no positions
anywhere; final RMSNorm; untied ``lm_head``. The mixer of a layer is KDA
(Kimi Delta Attention: a gated delta rule with a per-channel decay, behind
a short causal convolution) or MLA without rotation (a 576-wide latent row
cached per token), by the configuration's layer lists (numbered from 1).
The FFN is a dense SwiGLU in the leading layers and the sigmoid-routed
expert layer of ``parallel/expert.py`` after them.

Two entry points, both functions of ``(params, cfg, state, ...)`` that the
serving engine jits:

- ``prefill_chunk``: the next ``Tc`` tokens of ``R`` prompts, reading
  and writing each prompt's lane of the state (KDA state and convolution
  tails carried from the previous chunk, latent rows appended to the
  lane's pages). Chunkwise KDA inside the chunk, expanded MLA over the
  lane's pages. One program serves every prompt length.
- ``decode_step``: one token for every active lane, KDA by the recurrence
  and MLA in the absorbed form over the lane's pages.

``state`` is ``{"kda": [Lk, slots, H, dk, dv] float32, "conv": [Lk,
slots, K-1, 3*H*dk], "latent": [Lm, pages, rank+rope, page_tokens]}``
(a page holds its tokens along the LAST axis: 128 tokens fill the chip's
128-wide tiles exactly where 576 values would be padded to 640, and given
pages the other way round XLA re-laid the whole pool on the way in and out
of every step); page 0 is the sink for positions that are not live. The
KDA state and the router are float32 whatever the parameters' type.
"""

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import paged_layers as pl
from deepspeed_tpu.parallel import expert as expert_mod

HIGHEST = jax.lax.Precision.HIGHEST
KDA_CHUNK = 64          # tokens per chunk of the chunkwise KDA form
PREFILL_KEY_BLOCK = 1024   # latent rows expanded at a time in prefill
DECODE_KEY_BLOCK = 512     # latent rows attended at a time in decode


@dataclass(frozen=True)
class KimiLinearConfig:
    """The published keys of ``config.json`` (flattened), plus the share of
    a deployment this program holds: ``experts_held`` (first, count) of the
    ``num_experts`` the router scores, and ``vocab_size`` rows of the
    vocabulary starting at ``vocab_first`` (traffic ids, logits and
    sampling are over the slice)."""

    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 27
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    linear_num_heads: int = 32
    linear_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_layers: tuple = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                         21, 22, 23, 25, 26)
    full_attn_layers: tuple = (4, 8, 12, 16, 20, 24, 27)
    num_experts: int = 256
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 2.446
    moe_renormalize: bool = True
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 1048576
    experts_held: tuple = None          # (first, count); None = all
    vocab_first: int = 0

    def __post_init__(self):
        object.__setattr__(self, "experts_held", expert_mod.held_share(
            self.experts_held, self.num_experts))
        for i in range(1, self.num_hidden_layers + 1):
            if (i in self.kda_layers) == (i in self.full_attn_layers):
                raise ValueError(
                    f"layer {i} must be in exactly one of kda_layers and "
                    f"full_attn_layers")
        if self.num_shared_experts not in (0, 1):
            raise ValueError("num_shared_experts must be 0 or 1")

    @classmethod
    def from_dict(cls, cfg, **share):
        """From the keys of the published ``config.json`` (the nested
        ``linear_attn_config`` group included)."""
        lin = cfg["linear_attn_config"]
        keys = ("vocab_size", "hidden_size", "intermediate_size",
                "moe_intermediate_size", "num_hidden_layers",
                "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "num_experts",
                "num_experts_per_token", "num_shared_experts",
                "first_k_dense_replace", "routed_scaling_factor",
                "moe_renormalize", "rms_norm_eps")
        kw = {k: cfg[k] for k in keys if k in cfg}
        kw.update(
            linear_num_heads=lin["num_heads"],
            linear_head_dim=lin["head_dim"],
            short_conv_kernel_size=lin["short_conv_kernel_size"],
            kda_layers=tuple(lin["kda_layers"]),
            full_attn_layers=tuple(lin["full_attn_layers"]),
            max_position_embeddings=cfg.get("model_max_length", 1048576))
        kw.update(share)
        return cls(**kw)

    # -- derived ---------------------------------------------------------
    def layer_kind(self, i):
        """``"kda"`` or ``"mla"`` for layer ``i`` (from 1)."""
        return "kda" if i in self.kda_layers else "mla"

    def layer_is_moe(self, i):
        return i > self.first_k_dense_replace

    @property
    def kda_index(self):
        """{layer: row of the KDA state} for this depth's KDA layers."""
        ls = [i for i in range(1, self.num_hidden_layers + 1)
              if self.layer_kind(i) == "kda"]
        return {layer: n for n, layer in enumerate(ls)}

    @property
    def mla_index(self):
        ls = [i for i in range(1, self.num_hidden_layers + 1)
              if self.layer_kind(i) == "mla"]
        return {layer: n for n, layer in enumerate(ls)}

    @property
    def n_moe_layers(self):
        return sum(self.layer_is_moe(i)
                   for i in range(1, self.num_hidden_layers + 1))

    @property
    def latent_width(self):
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def kda_width(self):
        return self.linear_num_heads * self.linear_head_dim


# -- small pieces ---------------------------------------------------------

def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


# -- KDA --------------------------------------------------------------------

def kda_recurrent_step(S, q, k, v, g, beta):
    """One token of the recurrence as published, per lane and head.
    S [..., dk, dv] float32; q, k, g [..., dk]; v [..., dv]; beta [...]."""
    S = jnp.exp(g)[..., :, None] * S
    u = beta[..., None] * (v - jnp.sum(S * k[..., :, None], axis=-2))
    S = S + k[..., :, None] * u[..., None, :]
    o = jnp.sum(S * q[..., :, None], axis=-2)
    return S, o


def kda_chunkwise(S0, q, k, v, g, beta, chunk=KDA_CHUNK):
    """The same recurrence over ``T`` tokens in chunks of ``chunk``:
    ``S0 [H, dk, dv]``, ``q, k, g [T, H, dk]``, ``v [T, H, dv]``,
    ``beta [T, H]``, all float32; returns ``(S_T, o [T, H, dv])``.

    Inside a chunk, with ``G_t`` the running sum of ``g`` from the chunk's
    start: ``u_i = beta_i (v_i - S0^T (e^{G_i} k_i) - sum_{j<i} A_ij u_j)``
    with ``A_ij = (k_i e^{G_i}) . (k_j e^{-G_j})``, a unit lower-triangular
    system solved once per chunk; then ``o_t = S0^T (q_t e^{G_t}) +
    sum_{i<=t} ((q_t e^{G_t}) . (k_i e^{-G_i})) u_i`` and ``S_C = e^{G_C}
    (S0 + sum_i (k_i e^{-G_i}) u_i^T)``. The two exponentials of a product
    are taken about the chunk's middle so that neither leaves float32's
    range while ``|g| * chunk / 2`` stays under about 80. A token with
    ``g = 0`` and ``beta = 0`` leaves the state as it was (padding)."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    n = T // chunk
    assert n * chunk == T, (T, chunk)

    def split(x):   # [T, H, ...] -> [n, H, chunk, ...]
        return jnp.moveaxis(x.reshape((n, chunk) + x.shape[1:]), 2, 1)

    q, k, v, g, beta = (split(x) for x in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=2)                        # [n, H, C, dk]
    mid = G[:, :, chunk // 2 - 1:chunk // 2]         # about the middle
    up, down = jnp.exp(G - mid), jnp.exp(mid - G)
    q_in, k_in = q * up, k * up                      # e^{G} sides
    k_out = k * down                                 # e^{-G} side
    mm = dict(precision=HIGHEST, preferred_element_type=jnp.float32)
    A = jnp.einsum("nhik,nhjk->nhij", k_in, k_out, **mm)
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    lower = jnp.eye(chunk) + jnp.where(strict, A, 0.0) * beta[..., None]
    rhs = jnp.concatenate([v, k * jnp.exp(G)], -1) * beta[..., None]
    sol = jax.scipy.linalg.solve_triangular(lower, rhs, lower=True,
                                            unit_diagonal=True)
    u_free, w = sol[..., :dv], sol[..., dv:]         # U = u_free - w S0
    QK = jnp.where(jnp.tril(jnp.ones((chunk, chunk), bool)),
                   jnp.einsum("nhik,nhjk->nhij", q_in, k_out, **mm), 0.0)
    q_abs = q * jnp.exp(G)                           # against S0 itself
    k_end = k * jnp.exp(G[:, :, -1:] - G)            # decayed to chunk end
    decay_end = jnp.exp(G[:, :, -1])                 # [n, H, dk]

    def body(S, xs):
        u_free, w, QK, q_abs, k_end, decay_end = xs
        u = u_free - jnp.einsum("hck,hkv->hcv", w, S, **mm)
        o = (jnp.einsum("hck,hkv->hcv", q_abs, S, **mm)
             + jnp.einsum("hij,hjv->hiv", QK, u, **mm))
        S = (decay_end[..., None] * S
             + jnp.einsum("hck,hcv->hkv", k_end, u, **mm))
        return S, o

    S, o = jax.lax.scan(body, S0, (u_free, w, QK, q_abs, k_end, decay_end))
    return S, jnp.moveaxis(o, 1, 2).reshape(T, H, dv)


def _kda_inputs(p, cfg, x, conv_ext):
    """Everything of the KDA mixer that does not touch the state.
    ``x [..., T, d]`` normed input, ``conv_ext [..., K-1+T, 3W]`` the
    pre-convolution projections with the carried tail in front. Returns
    float32 ``q, k, v, g [..., T, H, D]``, ``beta [..., T, H]`` and the
    output gate ``[..., T, H, D]``."""
    H, D, W = cfg.linear_num_heads, cfg.linear_head_dim, cfg.kda_width
    K = cfg.short_conv_kernel_size
    T = x.shape[-2]
    wconv = jnp.concatenate([p[n]["kernel"] for n in
                             ("q_conv", "k_conv", "v_conv")], -1)
    wconv = wconv.astype(jnp.float32)                # [K, 3W]
    ext = conv_ext.astype(jnp.float32)
    y = sum(wconv[j] * jax.lax.slice_in_dim(ext, j, j + T, axis=-2)
            for j in range(K))
    y = jax.nn.silu(y)
    lead = x.shape[:-1]
    q, k, v = (y[..., i * W:(i + 1) * W].reshape(lead + (H, D))
               for i in range(3))
    q = _l2norm(q) * (D ** -0.5)
    k = _l2norm(k)
    f = pl.dot(pl.dot(x, p["f_a_proj"]["kernel"]).astype(x.dtype),
               p["f_b_proj"]["kernel"]) + p["dt_bias"].astype(jnp.float32)
    g = -jnp.exp(p["A_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(
        f.reshape(lead + (H, D)))
    beta = jax.nn.sigmoid(pl.dot(x, p["b_proj"]["kernel"]))
    gate = pl.dot(pl.dot(x, p["g_a_proj"]["kernel"]).astype(x.dtype),
                  p["g_b_proj"]["kernel"]) + p["g_b_proj"]["bias"].astype(
                      jnp.float32)
    return q, k, v, g, beta, jax.nn.sigmoid(gate).reshape(lead + (H, D))


def _kda_project(p, x):
    """Pre-convolution projections ``[..., 3W]`` in ``x``'s type."""
    return jnp.concatenate(
        [pl.dot(x, p[n]["kernel"]) for n in ("q_proj", "k_proj", "v_proj")],
        -1).astype(x.dtype)


def _kda_output(p, cfg, o, gate, dtype):
    o = pl.rms_norm(o, p["o_norm"]["scale"], cfg.rms_norm_eps) * gate
    lead = o.shape[:-2]
    return pl.dot(o.reshape(lead + (cfg.kda_width,)).astype(dtype),
                  p["o_proj"]["kernel"]).astype(dtype)


def kda_prefill(p, cfg, x, S0, tail, lens):
    """KDA mixer over a chunk. ``x [R, Tc, d]``, ``S0 [R, H, D, D]``,
    ``tail [R, K-1, 3W]``, ``lens [R]`` valid tokens of each row (the rest
    is padding and leaves state and tail alone). Returns
    ``(y [R, Tc, d], S [R, H, D, D], tail)``."""
    K1 = cfg.short_conv_kernel_size - 1
    Tc = x.shape[1]
    ext = jnp.concatenate([tail, _kda_project(p, x)], axis=1)
    q, k, v, g, beta, gate = _kda_inputs(p, cfg, x, ext)
    live = (jnp.arange(Tc)[None, :] < lens[:, None])
    g = jnp.where(live[..., None, None], g, 0.0)
    beta = jnp.where(live[..., None], beta, 0.0)
    S, o = jax.vmap(kda_chunkwise)(S0, q, k, v, g, beta)
    new_tail = jax.vmap(
        lambda e, n: jax.lax.dynamic_slice_in_dim(e, n, K1, axis=0))(ext, lens)
    return _kda_output(p, cfg, o, gate, x.dtype), S, new_tail


def kda_decode(p, cfg, x, S0, tail, active):
    """KDA mixer for one token of every lane. ``x [B, d]``; inactive
    lanes keep state and tail."""
    u = _kda_project(p, x)                           # [B, 3W]
    ext = jnp.concatenate([tail, u[:, None]], axis=1)
    q, k, v, g, beta, gate = _kda_inputs(p, cfg, x[:, None], ext)
    q, k, v, g, beta, gate = (t[:, 0] for t in (q, k, v, g, beta, gate))
    g = jnp.where(active[:, None, None], g, 0.0)
    beta = jnp.where(active[:, None], beta, 0.0)
    S, o = kda_recurrent_step(S0, q, k, v, g, beta)
    new_tail = jnp.where(active[:, None, None], ext[:, 1:], tail)
    return _kda_output(p, cfg, o, gate, x.dtype), S, new_tail


# -- MLA (no rotation) ------------------------------------------------------

def _mla_q(p, cfg, x):
    nh = cfg.num_attention_heads
    q = pl.dot(x, p["q_proj"]["kernel"]).astype(x.dtype)
    q = q.reshape(x.shape[:-1] + (nh, cfg.qk_nope_head_dim
                                  + cfg.qk_rope_head_dim))
    return q[..., :cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]


def mla_latent(p, cfg, x):
    """The row cached per token: ``[RMSNorm(c), kr]``, rank + rope wide."""
    ckr = pl.dot(x, p["kv_a_proj_with_mqa"]["kernel"]).astype(x.dtype)
    c = pl.rms_norm(ckr[..., :cfg.kv_lora_rank], p["kv_a_layernorm"]["scale"],
                    cfg.rms_norm_eps)
    return jnp.concatenate([c, ckr[..., cfg.kv_lora_rank:]], -1)


def _kv_b(p, cfg):
    """``kv_b_proj`` as ``[rank, nh, nope + v]``."""
    nh = cfg.num_attention_heads
    return p["kv_b_proj"]["kernel"].reshape(
        cfg.kv_lora_rank, nh, cfg.qk_nope_head_dim + cfg.v_head_dim)


def mla_prefill(p, cfg, x, latent_pool, n, page_tables, starts, lens,
                page_tokens):
    """MLA over a chunk, expanded: the chunk's latent rows are written to
    the lanes' pages first, then every query attends the lane's rows up to
    its own position, a block of pages at a time (keys and values expanded
    from the latent by ``kv_b_proj``). ``x [R, Tc, d]``; ``latent_pool``
    is the whole ``[Lm, pages, width, page_tokens]`` array and ``n`` this
    layer's row of it (indexed in place: a slice taken out and set back
    would copy the pool). Returns ``(y, latent_pool)``."""
    R, Tc, _ = x.shape
    nh, dn, dr, dvh = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                       cfg.qk_rope_head_dim, cfg.v_head_dim)
    pt = page_tokens
    mp = page_tables.shape[1]
    pos = starts[:, None] + jnp.arange(Tc)[None, :]              # [R, Tc]
    # the chunk starts on a page and covers whole pages: each is written
    # with one in-place update (a scatter into the pool made XLA re-lay the
    # whole pool around it). What a page holds beyond the prompt's end is
    # overwritten by decode before it can be attended.
    assert Tc % pt == 0, (Tc, pt)
    per_row = Tc // pt
    rows = mla_latent(p, cfg, x).astype(latent_pool.dtype)
    blocks = jnp.swapaxes(rows.reshape(R, per_row, pt, -1), 2, 3)
    logical = starts[:, None] // pt + jnp.arange(per_row)[None, :]
    dest = jnp.where((lens[:, None] > 0) & (logical < mp),
                     jnp.take_along_axis(
                         page_tables, jnp.clip(logical, 0, mp - 1), 1), 0)

    def put(i, pool):
        r, j = i // per_row, i % per_row
        return jax.lax.dynamic_update_slice(
            pool, blocks[r, j][None, None], (n, dest[r, j], 0, 0))

    latent_pool = jax.lax.fori_loop(0, R * per_row, put, latent_pool)
    q_nope, q_rope = _mla_q(p, cfg, x)                           # [R,Tc,nh,*]
    wkv = _kv_b(p, cfg)
    scale = (dn + dr) ** -0.5
    bp = max(1, PREFILL_KEY_BLOCK // pt)                         # pages a block
    nblk = -(-mp // bp)
    tables = jnp.pad(page_tables, ((0, 0), (0, nblk * bp - mp)))
    end = jnp.max(jnp.where(lens > 0, starts + lens, 0))
    n_blocks = (end + bp * pt - 1) // (bp * pt)

    def block(j):
        pages = jax.lax.dynamic_slice_in_dim(tables, j * bp, bp, axis=1)
        lat = latent_pool[n, pages].astype(x.dtype)      # [R, bp, width, pt]
        kv = jnp.einsum("rncp,chd->rnphd", lat[:, :, :cfg.kv_lora_rank], wkv,
                        preferred_element_type=jnp.float32).astype(
                            x.dtype).reshape(R, bp * pt, nh, dn + dvh)
        kpos = j * bp * pt + jnp.arange(bp * pt)
        s = (jnp.einsum("rqhd,rshd->rhqs", q_nope, kv[..., :dn],
                        preferred_element_type=jnp.float32)
             + jnp.einsum("rqhd,rndp->rhqnp", q_rope,
                          lat[:, :, cfg.kv_lora_rank:],
                          preferred_element_type=jnp.float32).reshape(
                              R, nh, Tc, bp * pt)) * scale
        ok = kpos[None, None, None, :] <= pos[:, None, :, None]
        s = jnp.where(ok, s, -1e30)

        def weigh(pr):
            return jnp.einsum("rhqs,rshd->rhqd", pr.astype(x.dtype),
                              kv[..., dn:],
                              preferred_element_type=jnp.float32)
        return s, weigh

    ctx = pl.online_softmax_loop(n_blocks, block, (R, nh, Tc), dvh)
    ctx = jnp.moveaxis(ctx, 1, 2).reshape(R, Tc, nh * dvh)
    return pl.dot(ctx.astype(x.dtype), p["o_proj"]["kernel"]).astype(
        x.dtype), latent_pool


def mla_decode(p, cfg, x, latent_pool, n, page_tables, positions, active,
               page_tokens):
    """MLA for one token of every lane, absorbed: ``kv_b_proj``'s key half
    goes into the query and its value half onto the output, so a lane's
    latent rows are read as they are cached. ``x [B, d]``; the new row is
    written at ``positions`` (into row ``n`` of the whole pool, in place)
    before it is attended."""
    B = x.shape[0]
    nh, dn, dvh = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    rank = cfg.kv_lora_rank
    pt = page_tokens
    mp = page_tables.shape[1]
    logical = jnp.clip(positions // pt, 0, mp - 1)
    phys = jnp.where(active & (positions < mp * pt),
                     page_tables[jnp.arange(B), logical], 0)
    rows = mla_latent(p, cfg, x).astype(latent_pool.dtype)

    # each lane's page is read, given its new column and written back whole,
    # in place: a scatter, or an update one column wide, made XLA re-lay the
    # whole pool on the way into and out of every step
    column = jnp.arange(pt)[None, None, :] == (positions % pt)[:, None, None]
    pages = jnp.where(column, rows[:, :, None], latent_pool[n, phys])

    def put(b, pool):
        return jax.lax.dynamic_update_slice(
            pool, pages[b][None, None], (n, phys[b], 0, 0))

    latent_pool = jax.lax.fori_loop(0, B, put, latent_pool)
    q_nope, q_rope = _mla_q(p, cfg, x)                           # [B, nh, *]
    wkv = _kv_b(p, cfg)
    q_lat = jnp.einsum("bhd,chd->bhc", q_nope, wkv[..., :dn],
                       preferred_element_type=jnp.float32).astype(x.dtype)
    scale = (dn + cfg.qk_rope_head_dim) ** -0.5
    bp = max(1, DECODE_KEY_BLOCK // pt)
    nblk = -(-mp // bp)
    tables = jnp.pad(page_tables, ((0, 0), (0, nblk * bp - mp)))
    end = jnp.max(jnp.where(active, positions + 1, 0))
    n_blocks = (end + bp * pt - 1) // (bp * pt)

    def block(j):
        pages = jax.lax.dynamic_slice_in_dim(tables, j * bp, bp, axis=1)
        lat = latent_pool[n, pages].astype(x.dtype)      # [B, bp, width, pt]
        kpos = j * bp * pt + jnp.arange(bp * pt)
        s = (jnp.einsum("bhc,bncp->bhnp", q_lat, lat[:, :, :rank],
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhd,bndp->bhnp", q_rope, lat[:, :, rank:],
                          preferred_element_type=jnp.float32)
             ).reshape(B, nh, bp * pt) * scale
        s = jnp.where(kpos[None, None, :] <= positions[:, None, None], s,
                      -1e30)

        def weigh(pr):
            return jnp.einsum("bhnp,bncp->bhc",
                              pr.astype(x.dtype).reshape(B, nh, bp, pt),
                              lat[:, :, :rank],
                              preferred_element_type=jnp.float32)
        return s, weigh

    ctx_lat = pl.online_softmax_loop(n_blocks, block, (B, nh), rank)
    ctx = jnp.einsum("bhc,chd->bhd", ctx_lat.astype(x.dtype), wkv[..., dn:],
                     preferred_element_type=jnp.float32)
    return pl.dot(ctx.reshape(B, nh * dvh).astype(x.dtype),
                  p["o_proj"]["kernel"]).astype(x.dtype), latent_pool


# -- the two programs -------------------------------------------------------

def _ffn(lp, cfg, i, x, live, tile):
    """Layer ``i``'s FFN over flat tokens ``x [N, d]``; ``live [N]`` says
    which tokens are real. Returns ``(y, counts [3] int32)``: picks that
    fell on held experts, held experts touched, the busiest one's tokens."""
    if not cfg.layer_is_moe(i):
        return pl.swiglu(x, lp["mlp"]), jnp.zeros(3, jnp.int32)
    return expert_mod.routed_moe_ffn(
        lp["mlp"], x, live, k=cfg.num_experts_per_token,
        scaling=cfg.routed_scaling_factor,
        renormalize=cfg.moe_renormalize, held=cfg.experts_held, tile=tile)


def prefill_chunk(params, cfg, state, ids, slots, starts, lens, page_tables,
                  *, page_tokens, moe_tile=128):
    """The next ``Tc`` tokens of ``R`` prompts. ``ids [R, Tc]``, ``slots
    [R]`` (distinct), ``starts [R]`` tokens already prefilled, ``lens [R]``
    valid tokens of this chunk, ``page_tables [R, mp]``. Returns ``(state, first [R], logits [R, V])``:
    the greedy token after each row's last valid position (meaningful for a
    row whose prompt ends in this chunk), and the logits it was taken
    from."""
    R, Tc = ids.shape
    eps = cfg.rms_norm_eps
    h = params["embed_tokens"]["embedding"][ids]
    live = (jnp.arange(Tc)[None, :] < lens[:, None]).reshape(R * Tc)
    kda, conv, latent = state["kda"], state["conv"], state["latent"]
    for i in range(1, cfg.num_hidden_layers + 1):
        lp = params["layers"][str(i)]
        x = pl.rms_norm(h, lp["input_layernorm"]["scale"], eps)
        if cfg.layer_kind(i) == "kda":
            n = cfg.kda_index[i]
            with jax.named_scope("kda_mix"):
                y, S, tail = kda_prefill(lp["self_attn"], cfg, x,
                                         kda[n, slots], conv[n, slots], lens)
                kda = kda.at[n, slots].set(S)
                conv = conv.at[n, slots].set(tail.astype(conv.dtype))
        else:
            n = cfg.mla_index[i]
            with jax.named_scope("mla_attend"):
                y, latent = mla_prefill(lp["self_attn"], cfg, x, latent, n,
                                        page_tables, starts, lens,
                                        page_tokens)
        h = h + y
        x = pl.rms_norm(h, lp["post_attention_layernorm"]["scale"], eps)
        y, _ = _ffn(lp, cfg, i, x.reshape(R * Tc, -1), live, moe_tile)
        h = h + y.reshape(h.shape)
    last = jnp.clip(lens - 1, 0, Tc - 1)
    h_last = jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]
    logits = pl.lm_head(h_last, params["norm"]["scale"], eps,
                        params["lm_head"]["kernel"])
    first = jnp.argmax(logits, -1).astype(jnp.int32)
    return {"kda": kda, "conv": conv, "latent": latent}, first, logits


def decode_step(params, cfg, state, tokens, positions, active, page_tables,
                *, page_tokens, moe_tile=8):
    """One token for every active lane (lane ``b`` is slot ``b``).
    Returns ``(state, tokens, positions, logits [B, V], moe [3] int32)``;
    ``moe`` sums, over this step's expert layers, the picks that fell on
    held experts, the held experts touched and the busiest one's tokens
    (active lanes only)."""
    B = tokens.shape[0]
    eps = cfg.rms_norm_eps
    h = params["embed_tokens"]["embedding"][tokens]
    kda, conv, latent = state["kda"], state["conv"], state["latent"]
    moe = jnp.zeros(3, jnp.int32)
    for i in range(1, cfg.num_hidden_layers + 1):
        lp = params["layers"][str(i)]
        x = pl.rms_norm(h, lp["input_layernorm"]["scale"], eps)
        if cfg.layer_kind(i) == "kda":
            n = cfg.kda_index[i]
            with jax.named_scope("kda_mix"):
                y, S, tail = kda_decode(lp["self_attn"], cfg, x, kda[n, :B],
                                        conv[n, :B], active)
                kda = kda.at[n, :B].set(S)
                conv = conv.at[n, :B].set(tail.astype(conv.dtype))
        else:
            n = cfg.mla_index[i]
            with jax.named_scope("mla_attend"):
                y, latent = mla_decode(lp["self_attn"], cfg, x, latent, n,
                                       page_tables, positions, active,
                                       page_tokens)
        h = h + y
        x = pl.rms_norm(h, lp["post_attention_layernorm"]["scale"], eps)
        y, counts = _ffn(lp, cfg, i, x, active, moe_tile)
        moe = moe + counts
        h = h + y
    logits = pl.lm_head(h, params["norm"]["scale"], eps,
                        params["lm_head"]["kernel"])
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    tokens = jnp.where(active, nxt, tokens)
    positions = jnp.where(active, positions + 1, positions)
    return ({"kda": kda, "conv": conv, "latent": latent}, tokens, positions,
            logits, moe)

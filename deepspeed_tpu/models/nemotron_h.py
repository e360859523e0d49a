"""Nemotron-H decoder (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B, ``model_type:
nemotron_h``): pure functions of a parameter tree, for serving.

Block ``i``: ``h += Mixer_i(RMSNorm(h))``, one mixer a block by the letter
``hybrid_override_pattern[i - 1]``: ``M`` a Mamba-2 mixer, ``E`` the
sigmoid-routed expert layer of ``parallel/expert.py`` with two-matrix
squared-ReLU experts and a wider shared one, ``*`` grouped-query attention
with no position encoding of any kind. Final RMSNorm; untied ``lm_head``.

Two entry points, both functions of ``(params, cfg, state, ...)`` that the
serving engine jits:

- ``prefill_chunk``: ``R`` rows of ``chunk_size`` tokens, each the next
  tokens of some prompt. A row reads the recurrent state and the
  convolution tail it starts from out of its prompt's slot, or, where it
  follows the row before it in the same prompt, from that row: a long
  prompt takes several rows of one call and a short one a single row, so a
  call holds several prompts and a prompt is padded by less than one row.
  The SSD form inside a row (one chunk of the published ``chunk_size``);
  keys and values written as whole pages, attention over the row's own
  pages. One program serves every prompt length.
- ``decode_step``: one token for every active lane, Mamba-2 by the
  recurrence and attention over the lane's pages (a work list of the
  lanes' blocks of keys: ``paged_layers.gqa_decode``).

``state`` is ``{"ssm": [Lm, slots, H, P, N] float32, "conv": [Lm, slots,
K-1, conv_dim], "k", "v": [La, pages, kv_heads * head_dim, page_tokens]}``
(a page holds its tokens along the LAST axis and the key-value heads side
by side along the one before: the layout of ``HybridStatePool``'s paged
arrays, and why it is so is in ``models/kimi_linear.py``); page 0 is the
sink for positions that are not live. SSM state and the router are float32
whatever the parameters' type.
"""

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import paged_layers as pl
from deepspeed_tpu.parallel import expert as expert_mod


@dataclass(frozen=True)
class NemotronHConfig:
    """The published keys of ``config.json``, plus the share of a deployment
    this program holds: ``experts_held`` (first, count) of the
    ``n_routed_experts`` the router scores, and ``vocab_size`` rows of the
    vocabulary starting at ``vocab_first`` (traffic ids, logits and sampling
    are over the slice). Of ``hybrid_override_pattern`` the first
    ``num_hidden_layers`` letters are run."""

    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = (
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    layer_norm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    experts_held: tuple = None          # (first, count); None = all
    vocab_first: int = 0

    def __post_init__(self):
        object.__setattr__(self, "experts_held", expert_mod.held_share(
            self.experts_held, self.n_routed_experts))
        if len(self.hybrid_override_pattern) < self.num_hidden_layers:
            raise ValueError(
                f"hybrid_override_pattern names "
                f"{len(self.hybrid_override_pattern)} blocks, "
                f"num_hidden_layers={self.num_hidden_layers}")
        if set(self.pattern) - set("ME*"):
            raise ValueError(
                f"hybrid_override_pattern {self.pattern!r}: a block is M "
                f"(Mamba-2), E (experts) or * (attention)")
        if self.n_shared_experts not in (0, 1):
            raise ValueError("n_shared_experts must be 0 or 1")
        if (self.mamba_num_heads % self.n_groups
                or self.num_attention_heads % self.num_key_value_heads):
            raise ValueError("heads must divide into their groups")

    @classmethod
    def from_dict(cls, cfg, **share):
        """From the keys of the published ``config.json``."""
        keys = ("vocab_size", "hidden_size", "num_hidden_layers",
                "hybrid_override_pattern", "mamba_num_heads",
                "mamba_head_dim", "ssm_state_size", "n_groups",
                "conv_kernel", "chunk_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "n_routed_experts",
                "num_experts_per_tok", "moe_intermediate_size",
                "moe_shared_expert_intermediate_size", "n_shared_experts",
                "routed_scaling_factor", "norm_topk_prob",
                "layer_norm_epsilon", "max_position_embeddings")
        kw = {k: cfg[k] for k in keys if k in cfg}
        kw.update(share)
        return cls(**kw)

    # -- derived ---------------------------------------------------------
    @property
    def pattern(self):
        return self.hybrid_override_pattern[:self.num_hidden_layers]

    def layer_kind(self, i):
        """``"mamba"``, ``"moe"`` or ``"attn"`` for block ``i`` (from 1)."""
        return {"M": "mamba", "E": "moe", "*": "attn"}[self.pattern[i - 1]]

    def _index(self, kind):
        ls = [i for i in range(1, self.num_hidden_layers + 1)
              if self.layer_kind(i) == kind]
        return {layer: n for n, layer in enumerate(ls)}

    @property
    def mamba_index(self):
        """{block: row of the SSM state} for this depth's Mamba-2 blocks."""
        return self._index("mamba")

    @property
    def attn_index(self):
        return self._index("attn")

    @property
    def n_moe_layers(self):
        return len(self._index("moe"))

    @property
    def d_inner(self):
        """What the mixer uses, not ``expand`` x ``hidden_size``."""
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def v_head_dim(self):
        """A value head is as wide as a key head here; the grouped-query
        functions (``models/paged_layers.py``) read the two apart."""
        return self.head_dim

    @property
    def kv_width(self):
        """Values a token of one attention block caches for keys, and as
        many for values (one head size serves both): the key-value heads
        side by side."""
        return self.num_key_value_heads * self.head_dim


# -- Mamba-2 ----------------------------------------------------------------

def ssm_recurrent_step(S, x, B, C, dt, A, D):
    """One token of the recurrence as published, per lane: ``S' = exp(dt A)
    S + dt x B^T``, ``y = S' C + D x``. ``S [..., G, J, P, N]`` float32 (the
    heads as groups of ``J``), ``x [..., G, J, P]``, ``B, C [..., G, N]``
    (a group's heads share them), ``dt [..., G, J]``, ``A, D [G, J]``. A
    token with ``dt = 0`` leaves the state as it was."""
    decay = jnp.exp(dt * A)
    S = (decay[..., None, None] * S
         + (dt[..., None] * x)[..., None] * B[..., None, None, :])
    y = jnp.sum(S * C[..., None, None, :], axis=-1) + D[..., None] * x
    return S, y


def ssd_chunk(x, B, C, dt, A):
    """The same recurrence over one chunk of ``T`` tokens, without its
    initial state (SSD, the quadratic form inside a chunk). ``x [T, G, J,
    P]``, ``B, C [T, G, N]``, ``dt [T, G, J]``, ``A [G, J]``, float32.
    Returns ``(y [T, G, J, P]`` what the chunk's own tokens give each
    other, ``grow [T, G, J]`` the decay from the chunk's start to each
    token, ``local [G, J, P, N]`` the state the chunk's tokens leave at its
    end)``: with ``S0`` the state before the chunk, token ``t`` reads ``y_t
    + grow_t (S0 C_t)`` and the chunk leaves ``grow_T S0 + local``. Every
    exponent is a sum of ``dt A <= 0`` over a span of tokens, so none
    overflows; a token with ``dt = 0`` (padding) changes nothing."""
    T = x.shape[0]
    # the heads lead and the tokens trail, so that the [T, T] faces are the
    # tiles the chip multiplies
    dts = jnp.moveaxis(dt, 0, -1)                            # [G, J, T]
    cum = jnp.cumsum(dts * A[..., None], axis=-1)
    span = cum[..., :, None] - cum[..., None, :]             # [G, J, t, s]
    L = jnp.exp(jnp.where(jnp.tril(jnp.ones((T, T), bool)), span, -jnp.inf))
    CB = jnp.einsum("tgn,sgn->gts", C, B, **pl.EXACT)
    M = CB[:, None] * L * dts[..., None, :]
    y = jnp.einsum("gjts,sgjp->tgjp", M, x, **pl.EXACT)
    to_end = jnp.exp(cum[..., -1:] - cum) * dts              # [G, J, T]
    local = jnp.einsum("sgjp,sgn->gjpn",
                       x * jnp.moveaxis(to_end, -1, 0)[..., None], B,
                       **pl.EXACT)
    return y, jnp.moveaxis(jnp.exp(cum), -1, 0), local


def _mamba_project(p, cfg, x):
    """``[z, xBC, dt] = x W_in`` in ``x``'s type (``dt`` float32)."""
    with jax.named_scope("mamba_in_proj"):
        zxbcdt = pl.dot(x, p["in_proj"]["kernel"])
        di, cd = cfg.d_inner, cfg.conv_dim
        return (zxbcdt[..., :di].astype(x.dtype),
                zxbcdt[..., di:di + cd].astype(x.dtype),
                zxbcdt[..., di + cd:])


def _mamba_conv(p, cfg, ext, T):
    """``SiLU(conv(xBC) + b)``, depthwise and causal: ``ext [..., K-1+T,
    conv_dim]`` holds the carried tail in front. Returns float32 ``x [...,
    T, G, J, P]``, ``B, C [..., T, G, N]``."""
    with jax.named_scope("mamba_conv"):
        w = p["conv1d"]["kernel"].astype(jnp.float32)        # [K, conv_dim]
        ext = ext.astype(jnp.float32)
        y = sum(w[j] * jax.lax.slice_in_dim(ext, j, j + T, axis=-2)
                for j in range(cfg.conv_kernel))
        y = jax.nn.silu(y + p["conv1d"]["bias"].astype(jnp.float32))
        G, N, di = cfg.n_groups, cfg.ssm_state_size, cfg.d_inner
        lead = y.shape[:-1]
        x = y[..., :di].reshape(lead + (G, cfg.mamba_num_heads // G,
                                        cfg.mamba_head_dim))
        B = y[..., di:di + G * N].reshape(lead + (G, N))
        C = y[..., di + G * N:].reshape(lead + (G, N))
        return x, B, C


def _per_head(p, cfg):
    """``A = -exp(A_log)``, ``D`` and ``dt_bias`` as ``[G, J]`` float32."""
    shape = (cfg.n_groups, cfg.mamba_num_heads // cfg.n_groups)
    return (-jnp.exp(p["A_log"].astype(jnp.float32)).reshape(shape),
            p["D"].astype(jnp.float32).reshape(shape),
            p["dt_bias"].astype(jnp.float32).reshape(shape))


def _mamba_gate_out(p, cfg, y, z, dtype):
    """Gate first, then the norm in ``n_groups`` groups of channels, then
    ``W_out``. ``y [..., G, J, P]`` float32, ``z [..., d_inner]``."""
    with jax.named_scope("mamba_gate_out"):
        lead = z.shape[:-1]
        G = cfg.n_groups
        y = y.reshape(lead + (cfg.d_inner,)) * jax.nn.silu(
            z.astype(jnp.float32))
        y = y.reshape(lead + (G, cfg.d_inner // G))
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                              + cfg.layer_norm_epsilon)
        y = y.reshape(lead + (cfg.d_inner,)) * p["norm"]["scale"].astype(
            jnp.float32)
        return pl.dot(y.astype(dtype), p["out_proj"]["kernel"]).astype(dtype)


def mamba_prefill(p, cfg, x, S_slot, tail_slot, lens, follows):
    """Mamba-2 mixer over ``R`` rows of one chunk each. ``x [R, T, d]``,
    ``S_slot [R, H, P, N]`` and ``tail_slot [R, K-1, conv_dim]`` what each
    row's slot holds, ``lens [R]`` valid tokens of each row (the rest is
    padding and leaves state and tail alone), ``follows [R]`` rows that
    take state and tail from the row before them instead. Returns ``(y [R,
    T, d], S [R, H, P, N], tail)`` as each row leaves them."""
    R, T, _ = x.shape
    K1 = cfg.conv_kernel - 1
    G, J = cfg.n_groups, cfg.mamba_num_heads // cfg.n_groups
    z, xBC, dt = _mamba_project(p, cfg, x)
    # a row that follows is preceded by a full row: its tail is that row's
    # last K-1 inputs
    before = jnp.concatenate([tail_slot[:1], xBC[:-1, T - K1:]], axis=0)
    tail_in = jnp.where(follows[:, None, None], before, tail_slot)
    ext = jnp.concatenate([tail_in.astype(xBC.dtype), xBC], axis=1)
    xs, B, C = _mamba_conv(p, cfg, ext, T)
    A, D, dt_bias = _per_head(p, cfg)
    live = jnp.arange(T)[None, :] < lens[:, None]
    dt = jnp.where(live[..., None, None],
                   jax.nn.softplus(dt.reshape(R, T, G, J) + dt_bias), 0.0)
    with jax.named_scope("ssd_scan"):
        y, grow, local = jax.vmap(ssd_chunk, in_axes=(0, 0, 0, 0, None))(
            xs, B, C, dt, A)
        S_slot = S_slot.reshape((R, G, J) + S_slot.shape[2:])

        def chain(S_prev, row):
            from_slot, follows_r, grow_end, local_r = row
            S0 = jnp.where(follows_r, S_prev, from_slot)
            S_end = grow_end[..., None, None] * S0 + local_r
            return S_end, (S0, S_end)

        _, (S0, S_end) = jax.lax.scan(
            chain, jnp.zeros_like(S_slot[0]),
            (S_slot, follows, grow[:, -1], local))
        y = (y + grow[..., None] * jnp.einsum("rgjpn,rtgn->rtgjp", S0, C,
                                              **pl.EXACT)
             + D[..., None] * xs)
    new_tail = jax.vmap(
        lambda e, n: jax.lax.dynamic_slice_in_dim(e, n, K1, axis=0))(ext, lens)
    return (_mamba_gate_out(p, cfg, y, z, x.dtype),
            S_end.reshape((R, G * J) + S_end.shape[3:]), new_tail)


def mamba_decode(p, cfg, x, S0, tail, active):
    """Mamba-2 mixer for one token of every lane. ``x [B, d]``; inactive
    lanes keep state and tail."""
    Bn = x.shape[0]
    G, J = cfg.n_groups, cfg.mamba_num_heads // cfg.n_groups
    z, xBC, dt = _mamba_project(p, cfg, x)
    ext = jnp.concatenate([tail.astype(xBC.dtype), xBC[:, None]], axis=1)
    xs, B, C = _mamba_conv(p, cfg, ext, 1)
    A, D, dt_bias = _per_head(p, cfg)
    dt = jnp.where(active[:, None, None],
                   jax.nn.softplus(dt.reshape(Bn, G, J) + dt_bias), 0.0)
    with jax.named_scope("ssm_step"):
        S, y = ssm_recurrent_step(
            S0.reshape((Bn, G, J) + S0.shape[2:]), xs[:, 0], B[:, 0], C[:, 0],
            dt, A, D)
    new_tail = jnp.where(active[:, None, None], ext[:, 1:], tail)
    return _mamba_gate_out(p, cfg, y, z, x.dtype), S.reshape(S0.shape), new_tail


# -- the two programs -------------------------------------------------------

def _experts(lp, cfg, x, live, tile):
    """An expert block over flat tokens ``x [N, d]``; ``live [N]`` says
    which tokens are real. Returns ``(y, counts [3] int32)`` as
    ``expert.routed_moe_ffn`` gives them."""
    return expert_mod.routed_moe_ffn(
        lp["mixer"], x, live, k=cfg.num_experts_per_tok,
        scaling=cfg.routed_scaling_factor, renormalize=cfg.norm_topk_prob,
        held=cfg.experts_held, tile=tile)


def prefill_chunk(params, cfg, state, ids, slots, starts, lens, page_tables,
                  *, page_tokens, moe_tile=128):
    """``R`` rows of the prompts being read. ``ids [R, T]`` with ``T =
    chunk_size``, ``slots [R]`` the slot of each row's prompt, ``starts
    [R]`` tokens of it already read, ``lens [R]`` valid tokens of the row
    (0: an empty row, which reads and writes no state), ``page_tables [R,
    mp]``. Rows of one prompt are consecutive and in order (``row_links``).
    Returns ``(state, first [R], logits [R, V])``: the greedy token after
    each row's last valid position (meaningful for the row in which a
    prompt ends), and the logits it was taken from."""
    R, T = ids.shape
    assert T == cfg.chunk_size, (T, cfg.chunk_size)
    eps = cfg.layer_norm_epsilon
    h = params["embed_tokens"]["embedding"][ids]
    live = (jnp.arange(T)[None, :] < lens[:, None]).reshape(R * T)
    ssm, conv, k_pool, v_pool = (state[n] for n in ("ssm", "conv", "k", "v"))
    follows, last = pl.row_links(slots, starts, lens, T)
    n_slots = ssm.shape[1]
    read = jnp.minimum(slots, n_slots - 1)
    write = jnp.where(last, slots, n_slots)          # out of range: no write
    for i in range(1, cfg.num_hidden_layers + 1):
        lp = params["layers"][str(i)]
        x = pl.rms_norm(h, lp["norm"]["scale"], eps)
        kind = cfg.layer_kind(i)
        if kind == "mamba":
            n = cfg.mamba_index[i]
            y, S, tail = mamba_prefill(lp["mixer"], cfg, x, ssm[n, read],
                                       conv[n, read], lens, follows)
            ssm = ssm.at[n, write].set(S, mode="drop")
            conv = conv.at[n, write].set(tail.astype(conv.dtype),
                                         mode="drop")
        elif kind == "attn":
            n = cfg.attn_index[i]
            with jax.named_scope("gqa_attend"):
                y, k_pool, v_pool = pl.gqa_prefill(
                    lp["mixer"], cfg, x, k_pool, v_pool, n, page_tables,
                    starts, lens, page_tokens)
        else:
            y, _ = _experts(lp, cfg, x.reshape(R * T, -1), live, moe_tile)
            y = y.reshape(h.shape)
        h = h + y
    at = jnp.clip(lens - 1, 0, T - 1)
    h_last = jnp.take_along_axis(h, at[:, None, None], axis=1)[:, 0]
    logits = pl.lm_head(h_last, params["norm_f"]["scale"], eps,
                        params["lm_head"]["kernel"])
    first = jnp.argmax(logits, -1).astype(jnp.int32)
    return ({"ssm": ssm, "conv": conv, "k": k_pool, "v": v_pool}, first,
            logits)


def decode_step(params, cfg, state, tokens, positions, active, page_tables,
                *, page_tokens, moe_tile=16):
    """One token for every active lane (lane ``b`` is slot ``b``). Returns
    ``(state, tokens, positions, logits [B, V], moe [3] int32)``; ``moe``
    sums, over this step's expert blocks, the picks that fell on held
    experts, the held experts touched and the busiest one's tokens (active
    lanes only)."""
    Bn = tokens.shape[0]
    eps = cfg.layer_norm_epsilon
    h = params["embed_tokens"]["embedding"][tokens]
    ssm, conv, k_pool, v_pool = (state[n] for n in ("ssm", "conv", "k", "v"))
    moe = jnp.zeros(3, jnp.int32)
    for i in range(1, cfg.num_hidden_layers + 1):
        lp = params["layers"][str(i)]
        x = pl.rms_norm(h, lp["norm"]["scale"], eps)
        kind = cfg.layer_kind(i)
        if kind == "mamba":
            n = cfg.mamba_index[i]
            y, S, tail = mamba_decode(lp["mixer"], cfg, x, ssm[n, :Bn],
                                      conv[n, :Bn], active)
            ssm = ssm.at[n, :Bn].set(S)
            conv = conv.at[n, :Bn].set(tail.astype(conv.dtype))
        elif kind == "attn":
            n = cfg.attn_index[i]
            with jax.named_scope("gqa_attend"):
                y, k_pool, v_pool = pl.gqa_decode(
                    lp["mixer"], cfg, x, k_pool, v_pool, n, page_tables,
                    positions, active, page_tokens)
        else:
            y, counts = _experts(lp, cfg, x, active, moe_tile)
            moe = moe + counts
        h = h + y
    logits = pl.lm_head(h, params["norm_f"]["scale"], eps,
                        params["lm_head"]["kernel"])
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    tokens = jnp.where(active, nxt, tokens)
    positions = jnp.where(active, positions + 1, positions)
    return ({"ssm": ssm, "conv": conv, "k": k_pool, "v": v_pool}, tokens,
            positions, logits, moe)

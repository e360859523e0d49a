"""Laguna decoder (poolside/Laguna-XS.2, ``model_type: laguna``): pure
functions of a parameter tree, for serving.

Layer ``l`` (from 0): ``h += Attn_l(RMSNorm(h)); h += FFN_l(RMSNorm(h))``;
final RMSNorm; untied ``lm_head``; no bias anywhere. ``Attn_l`` is
grouped-query attention whose query heads (``num_attention_heads_per_layer``),
rotary positions and mask go by ``layer_types[l]``: a *full* layer rotates
the first half of each head with YaRN-scaled frequencies and attends to
every earlier position; a *window* layer rotates the whole head with plain
frequencies and attends to the ``sliding_window`` positions that end at its
own. A sigmoid gate, one scalar a head from the layer's input, scales the
attention output ahead of ``o_proj``. ``FFN_l`` is a dense SwiGLU where
``mlp_layer_types[l]`` says so and the sigmoid-routed expert layer of
``parallel/expert.py`` (SwiGLU experts, a shared one, no bias on the router)
elsewhere.

Two entry points, both functions of ``(params, cfg, state, ...)`` that the
serving engine jits, laid out as ``models/nemotron_h.py``'s:

- ``prefill_chunk``: ``R`` rows of one page of tokens, each the next tokens
  of some prompt; rows of one prompt are consecutive and in order. A full
  layer writes a row as a whole page and attends over the row's own pages
  (``nemotron_h.gqa_prefill``). A window layer attends over what its lane's
  ring held before the call and the rows of the same prompt before it in
  the call, and leaves the ring holding the last ``sliding_window``
  positions the call read of each prompt.
- ``decode_step``: one token for every active lane. A window layer writes
  position ``p`` at ``p mod sliding_window`` of the lane's ring and reads
  the ring once; a full layer walks the (lane, block of 512 keys) pairs
  its active lanes own, a tile of pairs' pages gathered at a time
  (``nemotron_h.gqa_decode``), so its bytes follow the sum of the lanes'
  contexts and not the longest one's.

``state`` is ``{"k", "v": [Lf, pages, kv_heads * head_dim, page_tokens]``
(the full layers' pages, as Nemotron-H's), ``"wk", "wv": [Lw, slots, W /
page_tokens, kv_heads * head_dim, page_tokens]}`` (a ring of ``W =
sliding_window`` positions a lane for each window layer, in blocks laid out
as pages are, tokens last: given the tokens first, XLA re-laid the whole
array on the way into every decode step's scores). The window functions
below take a layer's shape, its window and what the model does to queries,
keys and the context as arguments, so a model whose values are narrower
than its keys, whose window layers have key-value heads of their own or
whose softmax has a learned sink (``models/mimo_v2.py``) runs them too:
each of the four arrays is then as wide as its own projection makes it.
Ring slot ``j`` (block ``j / page_tokens``, column ``j % page_tokens``) of a
lane at position ``p`` holds position ``p - (p - j) mod W``; the mask hides
it where that is negative, which is all that a previous occupant of the
lane can have left there. So a lane needs no reset. The router is
float32 whatever the parameters' type; keys are cached rotated.
"""

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.kimi_linear import _dot, rms_norm, swiglu
from deepspeed_tpu.models.nemotron_h import (
    _gqa_project,
    gqa_decode,
    gqa_prefill,
    row_links,
)
from deepspeed_tpu.ops.column_write import write_columns
from deepspeed_tpu.parallel import expert as expert_mod

FULL, WINDOW = "full_attention", "sliding_attention"
_PERIOD = (FULL, WINDOW, WINDOW, WINDOW)


@dataclass(frozen=True)
class RopeSpec:
    """One entry of the published ``rope_parameters``."""

    rope_theta: float = 10000.0
    rope_type: str = "default"
    partial_rotary_factor: float = 1.0
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def __post_init__(self):
        if self.rope_type not in ("default", "yarn"):
            raise ValueError(f"rope_type {self.rope_type!r}: default or yarn")

    @classmethod
    def from_dict(cls, d):
        return cls(**{k: d[k] for k in cls.__dataclass_fields__ if k in d})


@dataclass(frozen=True)
class AttentionShape:
    """What ``nemotron_h``'s grouped-query functions and the window
    functions below read of a configuration, for one layer: its query
    heads, its key-value heads, the size of a query or key head and the
    size of a value head."""

    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    v_head_dim: int


@dataclass(frozen=True)
class LagunaConfig:
    """The published keys of ``config.json`` (``rope_parameters`` as two
    ``RopeSpec``). Of the per-layer lists the first ``num_hidden_layers``
    entries are run; every expert of a layer is held."""

    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    moe_routed_scaling_factor: float = 2.5
    moe_apply_router_weight_on_input: bool = False
    gating: bool = True
    sliding_window: int = 512
    layer_types: tuple = _PERIOD * 10
    mlp_layer_types: tuple = ("dense",) + ("sparse",) * 39
    num_attention_heads_per_layer: tuple = (48, 64, 64, 64) * 10
    rope_full: RopeSpec = RopeSpec(
        rope_theta=500000.0, rope_type="yarn", partial_rotary_factor=0.5,
        factor=64.0, original_max_position_embeddings=4096, beta_fast=64.0,
        beta_slow=1.0, attention_factor=1.4158883083359672)
    rope_window: RopeSpec = RopeSpec()

    def __post_init__(self):
        for name in ("layer_types", "mlp_layer_types",
                     "num_attention_heads_per_layer"):
            value = tuple(getattr(self, name))
            object.__setattr__(self, name, value)
            if len(value) < self.num_hidden_layers:
                raise ValueError(
                    f"{name} names {len(value)} layers, "
                    f"num_hidden_layers={self.num_hidden_layers}")
        L = self.num_hidden_layers
        if set(self.layer_types[:L]) - {FULL, WINDOW}:
            raise ValueError(f"layer_types: {FULL} or {WINDOW}")
        if set(self.mlp_layer_types[:L]) - {"dense", "sparse"}:
            raise ValueError("mlp_layer_types: dense or sparse")
        if any(n % self.num_key_value_heads
               for n in self.num_attention_heads_per_layer[:L]):
            raise ValueError("query heads must divide into their key-value "
                             "heads in every layer")
        if self.moe_apply_router_weight_on_input:
            raise ValueError("moe_apply_router_weight_on_input: the expert "
                             "layer weighs outputs only")

    @classmethod
    def from_dict(cls, cfg):
        """From the keys of the published ``config.json``."""
        skip = ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer", "rope_full", "rope_window")
        kw = {k: cfg[k] for k in cls.__dataclass_fields__
              if k in cfg and k not in skip}
        for name in skip[:3]:
            if name in cfg:
                kw[name] = tuple(cfg[name])
        rope = cfg.get("rope_parameters", {})
        if FULL in rope:
            kw["rope_full"] = RopeSpec.from_dict(rope[FULL])
        if WINDOW in rope:
            kw["rope_window"] = RopeSpec.from_dict(rope[WINDOW])
        return cls(**kw)

    # -- derived ---------------------------------------------------------
    def is_window(self, l):
        return self.layer_types[l] == WINDOW

    def is_moe(self, l):
        return self.mlp_layer_types[l] == "sparse"

    def attention(self, l):
        """Layer ``l``'s head counts, as the grouped-query functions read
        them."""
        return AttentionShape(self.num_attention_heads_per_layer[l],
                              self.num_key_value_heads, self.head_dim,
                              self.head_dim)

    def rope(self, l):
        return self.rope_window if self.is_window(l) else self.rope_full

    def _index(self, window):
        ls = [l for l in range(self.num_hidden_layers)
              if self.is_window(l) == window]
        return {layer: n for n, layer in enumerate(ls)}

    @property
    def full_index(self):
        """{layer: row of the paged keys and values}."""
        return self._index(False)

    @property
    def window_index(self):
        """{layer: row of the rings}."""
        return self._index(True)

    @property
    def n_moe_layers(self):
        return sum(self.is_moe(l) for l in range(self.num_hidden_layers))

    @property
    def kv_width(self):
        """Values a token of one layer caches for keys, and as many for
        values (one head size and one key-value head count serve every
        layer): the key-value heads side by side."""
        return self.num_key_value_heads * self.head_dim

    @property
    def cache_widths(self):
        """{name of a pool array: values a token caches there}, as the
        family describes the pool: the full layers' ``k`` and ``v`` pages
        and the window layers' ``wk`` and ``wv`` rings."""
        return dict.fromkeys(("k", "v", "wk", "wv"), self.kv_width)


# -- rotary positions -------------------------------------------------------

def rope_inv_freq(spec, head_dim):
    """``(inv_freq [r / 2] float64, r)`` for the ``r = head_dim x
    partial_rotary_factor`` rotated dimensions. ``default``: ``theta^(-2i /
    r)``. ``yarn`` (as the family's published code computes it): the
    extrapolated frequency where a dimension turns more than ``beta_fast``
    times over the original length, the same over ``factor`` where it turns
    less than ``beta_slow`` times, and a linear ramp between the two."""
    r = int(head_dim * spec.partial_rotary_factor)
    extrap = spec.rope_theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    if spec.rope_type == "default":
        return extrap, r
    interp = extrap / spec.factor

    def turns_at(n):        # the dimension that turns n times over the length
        return (r * math.log(spec.original_max_position_embeddings
                             / (2 * math.pi * n))
                / (2 * math.log(spec.rope_theta)))

    low = max(math.floor(turns_at(spec.beta_fast)), 0)
    high = min(math.ceil(turns_at(spec.beta_slow)), r - 1)
    ramp = np.clip((np.arange(r // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return interp * ramp + extrap * (1.0 - ramp), r


def apply_rope(spec, x, positions):
    """Rotate the first ``r`` dimensions of each head of ``x [..., hd]`` to
    ``positions`` (one for each leading index of ``x`` up to the heads'
    axes), rotate-half convention: the rotated dimensions split in two
    halves, ``[x1 cos - x2 sin, x2 cos + x1 sin]``; ``cos`` and ``sin``
    times ``attention_factor``. Float32 inside, ``x``'s type out."""
    inv, r = rope_inv_freq(spec, x.shape[-1])
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        inv, jnp.float32)
    # the tables first and their broadcast shape after, so that queries and
    # keys of one layer share them (one cos and one sin a position)
    over_heads = positions.shape + (1,) * (x.ndim - 1 - positions.ndim) + (
        r // 2,)
    cos = (jnp.cos(ang) * spec.attention_factor).reshape(over_heads)
    sin = (jnp.sin(ang) * spec.attention_factor).reshape(over_heads)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :r // 2], x32[..., r // 2:r]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x32[..., r:]],
        axis=-1).astype(x.dtype)


def rotary(spec, shape, scope):
    """``rotate(q, k, positions)`` by ``spec`` for a layer of ``shape``:
    ``q [..., KV, J, hd]``, ``k [..., KV * hd]`` as ``_gqa_project`` gives
    them; traced under the name ``scope``."""
    def rotate(q, k, positions):
        with jax.named_scope(scope):
            heads = k.shape[:-1] + (shape.num_key_value_heads, shape.head_dim)
            return (apply_rope(spec, q, positions),
                    apply_rope(spec, k.reshape(heads), positions).reshape(
                        k.shape))
    return rotate


def _rotate(cfg, l):
    """Layer ``l``'s ``rotate``: YaRN over half a head in a full layer,
    plain frequencies over the whole head in a window layer."""
    return rotary(cfg.rope(l), cfg.attention(l),
                  "rope_window" if cfg.is_window(l) else "rope_full")


def _gate(p, cfg, l, x):
    """``gate(ctx [..., Q * hd])``: every head's output times
    ``sigmoid(x W_g)``, one scalar a head, float32."""
    if not cfg.gating:
        return None

    def gate(ctx):
        with jax.named_scope("attn_gate"):
            g = jax.nn.sigmoid(_dot(x, p["g_proj"]["kernel"]))
            heads = ctx.reshape(g.shape + (cfg.head_dim,))
            return (heads * g[..., None]).reshape(ctx.shape)
    return gate


# -- window layers: a ring a lane -------------------------------------------

def _sink_softmax(s, sink):
    """Softmax of masked scores ``s [B, KV, J, ..., keys]`` over the keys.
    With ``sink [KV, J]``, one learned logit a query head, the sink joins
    the maximum and the denominator and weighs nothing: the extra column of
    a softmax that is dropped afterwards. (A row that is all mask gives
    zeros then, and not the uniform weights a plain softmax gives; both are
    rows no caller reads.)"""
    if sink is None:
        return jax.nn.softmax(s, axis=-1)
    with jax.named_scope("attend_window_sink"):
        sink = sink.astype(jnp.float32).reshape(
            sink.shape + (1,) * (s.ndim - 2 - sink.ndim))
        m = jnp.maximum(jnp.max(s, axis=-1), sink)
        e = jnp.exp(s - m[..., None])
        return e / (jnp.sum(e, axis=-1) + jnp.exp(sink - m))[..., None]


def window_prefill(p, shape, x, wk, wv, n, slots, starts, lens, *, window,
                   rotate, gate=None, sink=None):
    """A window layer of ``shape`` (an ``AttentionShape``: keys of ``hd``,
    values of ``vd``) and ``W = window`` over ``R`` rows of ``T`` tokens.
    ``wk`` the whole ``[Lw, slots, W / T, KV * hd, T]`` rings, ``wv`` the
    whole ``[Lw, slots, W / T, KV * vd, T]`` ones and ``n`` this layer's
    row of them; ``W % T == 0`` (``W == T`` is a ring of one block) and
    every ``starts`` a multiple of ``T``, so a row is one block of its
    ring. ``rotate`` and ``gate`` as in ``nemotron_h.gqa_prefill``; ``sink
    [KV, J]`` one learned logit a query head that takes part of the
    softmax's mass and adds no value (None: a plain softmax, and nothing
    traced for it). A query at position ``s`` attends to
    positions ``(s - W, s]``: the row's own tokens up to its own, and the
    ``W`` positions before the row, which are the rows of the same prompt
    before it in the call where those reach, and what the lane's ring held
    before the call for the rest (a prompt read in earlier calls; positions
    before the prompt's start are hidden). Afterwards each prompt's last
    ``W`` positions of the call are written to its ring, each row with one
    in-place update. Returns ``(y, wk, wv)``."""
    R, T, _ = x.shape
    W = window
    kvh, hd, vd = (shape.num_key_value_heads, shape.head_dim,
                   shape.v_head_dim)
    J = shape.num_attention_heads // kvh
    assert W % T == 0 and wk.shape[2:] == (W // T, kvh * hd, T) and (
        wv.shape[2:] == (W // T, kvh * vd, T)), (W, T, wk.shape, wv.shape)
    back = W // T                       # rows that reach into a row's window
    pos = starts[:, None] + jnp.arange(T)[None, :]                   # [R, T]
    q, k, v = _gqa_project(p, shape, x)
    q, k = rotate(q, k, pos)
    follows, _ = row_links(slots, starts, lens, T)
    # the first row of each row's prompt in this call, and where the call
    # stops reading that prompt
    rows = jnp.arange(R)
    first = jax.lax.cummax(jnp.where(follows, 0, rows))
    stop = jax.ops.segment_max(starts + lens, first, num_segments=R)[first]
    lane = jnp.minimum(slots, wk.shape[1] - 1)
    # block b of the window before row r is row r - back + b of the call
    # where that row is of the same prompt, and else block ((start / T) + b)
    # % back of the ring: position start - W + b T on
    reach = rows[:, None] - back + jnp.arange(back)[None, :]       # [R, back]
    from_call = reach >= first[:, None]
    held_at = (starts[:, None] // T + jnp.arange(back)[None, :]) % back

    def blocks(new, ring, width):
        """``[R, back + 1, KV, width, T]``: the window before each row,
        then the row itself, a block's tokens last as the ring holds
        them."""
        own = jnp.swapaxes(new, 1, 2)                     # [R, KV*width, T]
        in_call = own[jnp.clip(reach, 0, R - 1)]
        held = jnp.take_along_axis(
            ring[n, lane], held_at[:, :, None, None], axis=1).astype(new.dtype)
        before = jnp.where(from_call[:, :, None, None], in_call, held)
        return jnp.concatenate([before, own[:, None]], axis=1).reshape(
            R, back + 1, kvh, width, T), own

    with jax.named_scope("attend_window"):
        keys, k_own = blocks(k, wk, hd)
        vals, v_own = blocks(v, wv, vd)
        s = jnp.einsum("rtgjd,rngdp->rgjtnp", q, keys,
                       preferred_element_type=jnp.float32).reshape(
                           R, kvh, J, T, (back + 1) * T) * hd ** -0.5
        # a key before the row, at index i of W: inside the window of query
        # t where i > t, and a position at all where start - W + i >= 0; a
        # key of the row: causal
        t = jnp.arange(T)[:, None]
        ok = jnp.concatenate([
            jnp.broadcast_to((jnp.arange(W)[None, :] > t)[None], (R, T, W))
            & (starts[:, None, None] - W + jnp.arange(W)[None, None, :] >= 0),
            jnp.broadcast_to((jnp.arange(T)[None, :] <= t)[None], (R, T, T)),
        ], axis=2)
        pr = _sink_softmax(jnp.where(ok[:, None, None], s, -1e30), sink)
        ctx = jnp.einsum(
            "rgjtnp,rngdp->rtgjd",
            pr.astype(x.dtype).reshape(R, kvh, J, T, back + 1, T), vals,
            preferred_element_type=jnp.float32)
    ctx = ctx.reshape(R, T, kvh * J * vd)
    if gate is not None:
        ctx = gate(ctx)
    y = _dot(ctx.astype(x.dtype), p["o_proj"]["kernel"]).astype(x.dtype)

    # the ring keeps each prompt's last W positions of the call: a row is
    # block (start / T) % back, its tokens that are real and not overwritten
    # by a later row of the same prompt
    keep = (jnp.arange(T)[None, :] < lens[:, None]) & (
        pos >= stop[:, None] - W)
    block_of = (starts // T) % back

    def put(r, rings):
        out = []
        for ring, new in zip(rings, (k_own, v_own)):
            at = (n, lane[r], block_of[r], 0, 0)
            old = jax.lax.dynamic_slice(ring, at,
                                        (1, 1, 1, new.shape[1], T))
            block = jnp.where(keep[r][None, :], new[r].astype(ring.dtype),
                              old[0, 0, 0])
            out.append(jax.lax.dynamic_update_slice(
                ring, block[None, None, None], at))
        return tuple(out)

    wk, wv = jax.lax.fori_loop(0, R, put, (wk, wv))
    return y, wk, wv


def _own_softmax(s, own, sink):
    """Softmax over masked scores ``s [B, KV, J, keys]`` and one more
    column ``own [B, KV, J]``, the score of a key that is not among
    ``s``'s; ``sink [KV, J]`` as in ``_sink_softmax``. Returns the weights
    of ``s``'s keys and the weight of the own column."""
    m = jnp.maximum(jnp.max(s, axis=-1), own)
    rest = 0.0
    if sink is not None:
        with jax.named_scope("attend_window_sink"):
            sink = sink.astype(jnp.float32)
            m = jnp.maximum(m, sink)
            rest = jnp.exp(sink - m)
    e, e_own = jnp.exp(s - m[..., None]), jnp.exp(own - m)
    total = jnp.sum(e, axis=-1) + e_own + rest
    return e / total[..., None], e_own / total


def _ring_write(ring, n, at, new, active):
    """``new [B, width]`` into slot ``at [B]`` of each active lane's ring,
    row ``n`` of ``ring [Lw, slots, back, width, T]``: column ``at % T`` of
    block ``at // T``; an inactive lane's ring stays as it was (its prompt
    may be half read). A ring of one block IS its lane's block, so the
    whole layer's rings take their columns in one pass, read, ``where`` and
    written back at a static index, which XLA does in place: the layer's
    rings move once each way. Of a ring of several blocks only the block
    that holds the slot moves (``write_columns``)."""
    Bn, (back, T) = new.shape[0], (ring.shape[2], ring.shape[4])
    col = jnp.where(active, at % T, -1)
    if back > 1:
        return write_columns(ring, (n, jnp.arange(Bn), at // T), new, col)
    column = jnp.arange(T)[None, :] == col[:, None]
    blocks = jnp.where(column[:, None, None, :], new[:, None, :, None],
                       ring[n, :Bn])
    return jax.lax.dynamic_update_slice(ring, blocks[None], (n, 0, 0, 0, 0))


def window_decode(p, shape, x, wk, wv, n, positions, active, *, window,
                  rotate, gate=None, sink=None):
    """A window layer for one token of every lane (lane ``b`` is slot
    ``b``); ``shape``, ``window``, ``rotate``, ``gate`` and ``sink`` as in
    ``window_prefill``. ``x [B, d]``. The ring is read once, as the step
    found it: slot ``j`` holds position ``p - (p - j) % W``, hidden where
    that is negative and at ``j = p % W``, which still holds ``p - W``; the
    new key and value are one more column of the softmax beside it, so the
    read does not wait for the write. They go to slot ``p % W`` of an
    active lane's ring in one pass a layer and array (``_ring_write``)."""
    Bn = x.shape[0]
    W = window
    kvh, hd, vd = (shape.num_key_value_heads, shape.head_dim,
                   shape.v_head_dim)
    J = shape.num_attention_heads // kvh
    back, T = wk.shape[2], wk.shape[4]
    q, k, v = _gqa_project(p, shape, x)
    q, k = rotate(q, k, positions)
    at = positions % W
    k, v = k.astype(wk.dtype), v.astype(wv.dtype)     # as the ring holds them
    with jax.named_scope("attend_window"):
        kb = wk[n, :Bn].astype(x.dtype).reshape(Bn, back, kvh, hd, T)
        vb = wv[n, :Bn].astype(x.dtype).reshape(Bn, back, kvh, vd, T)
        k_own = k.astype(x.dtype).reshape(Bn, kvh, hd)
        v_own = v.astype(x.dtype).reshape(Bn, kvh, vd)
        s = jnp.einsum("bgjd,bngdp->bgjnp", q, kb,
                       preferred_element_type=jnp.float32).reshape(
                           Bn, kvh, J, W) * hd ** -0.5
        s_own = jnp.einsum("bgjd,bgd->bgj", q, k_own,
                           preferred_element_type=jnp.float32) * hd ** -0.5
        held = positions[:, None] - (positions[:, None]
                                     - jnp.arange(W)[None, :]) % W
        ok = (held >= 0) & (jnp.arange(W)[None, :] != at[:, None])
        pr, pr_own = _own_softmax(jnp.where(ok[:, None, None], s, -1e30),
                                  s_own, sink)
        ctx = jnp.einsum("bgjnp,bngdp->bgjd",
                         pr.astype(x.dtype).reshape(Bn, kvh, J, back, T), vb,
                         preferred_element_type=jnp.float32)
        ctx = ctx + (pr_own.astype(x.dtype).astype(jnp.float32)[..., None]
                     * v_own.astype(jnp.float32)[:, :, None, :])
    with jax.named_scope("ring_write"):
        wk = _ring_write(wk, n, at, k, active)
        wv = _ring_write(wv, n, at, v, active)
    ctx = ctx.reshape(Bn, kvh * J * vd)
    if gate is not None:
        ctx = gate(ctx)
    return (_dot(ctx.astype(x.dtype), p["o_proj"]["kernel"]).astype(x.dtype),
            wk, wv)


# -- the two programs -------------------------------------------------------

def _ffn(lp, cfg, l, x, live, tile):
    """Layer ``l``'s FFN over flat tokens ``x [N, d]``; ``live [N]`` says
    which tokens are real. Returns ``(y, counts [3] int32)`` as
    ``expert.routed_moe_ffn`` gives them (zeros for a dense layer)."""
    if not cfg.is_moe(l):
        return swiglu(x, lp["mlp"]), jnp.zeros(3, jnp.int32)
    return expert_mod.routed_moe_ffn(
        lp["mlp"], x, live, k=cfg.num_experts_per_tok,
        scaling=cfg.moe_routed_scaling_factor, renormalize=True,
        held=(0, cfg.num_experts), tile=tile)


def _head(params, cfg, h):
    with jax.named_scope("lm_head"):
        h = rms_norm(h, params["norm"]["scale"], cfg.rms_norm_eps)
        return _dot(h, params["lm_head"]["kernel"])


def prefill_chunk(params, cfg, state, ids, slots, starts, lens, page_tables,
                  *, page_tokens, moe_tile=128):
    """``R`` rows of the prompts being read. ``ids [R, T]`` with ``T =
    page_tokens``, ``slots [R]`` the slot of each row's prompt, ``starts
    [R]`` tokens of it already read (a multiple of ``T``), ``lens [R]``
    valid tokens of the row (0: an empty row, which writes nothing),
    ``page_tables [R, mp]``. Rows of one prompt are consecutive and in
    order (``nemotron_h.row_links``). Returns ``(state, first [R], logits
    [R, V])``: the greedy token after each row's last valid position
    (meaningful for the row in which a prompt ends), and the logits it was
    taken from."""
    R, T = ids.shape
    assert T == page_tokens, (T, page_tokens)
    eps = cfg.rms_norm_eps
    h = params["embed_tokens"]["embedding"][ids]
    live = (jnp.arange(T)[None, :] < lens[:, None]).reshape(R * T)
    k_pool, v_pool, wk, wv = (state[n] for n in ("k", "v", "wk", "wv"))
    for l in range(cfg.num_hidden_layers):
        lp = params["layers"][str(l)]
        x = rms_norm(h, lp["input_layernorm"]["scale"], eps)
        p = lp["self_attn"]
        if cfg.is_window(l):
            y, wk, wv = window_prefill(
                p, cfg.attention(l), x, wk, wv, cfg.window_index[l], slots,
                starts, lens, window=cfg.sliding_window,
                rotate=_rotate(cfg, l), gate=_gate(p, cfg, l, x))
        else:
            with jax.named_scope("attend_full"):
                y, k_pool, v_pool = gqa_prefill(
                    p, cfg.attention(l), x, k_pool, v_pool,
                    cfg.full_index[l], page_tables, starts, lens, page_tokens,
                    rotate=_rotate(cfg, l), gate=_gate(p, cfg, l, x))
        h = h + y
        x = rms_norm(h, lp["post_attention_layernorm"]["scale"], eps)
        y, _ = _ffn(lp, cfg, l, x.reshape(R * T, -1), live, moe_tile)
        h = h + y.reshape(h.shape)
    at = jnp.clip(lens - 1, 0, T - 1)
    h_last = jnp.take_along_axis(h, at[:, None, None], axis=1)[:, 0]
    logits = _head(params, cfg, h_last)
    first = jnp.argmax(logits, -1).astype(jnp.int32)
    return {"k": k_pool, "v": v_pool, "wk": wk, "wv": wv}, first, logits


def decode_step(params, cfg, state, tokens, positions, active, page_tables,
                *, page_tokens, moe_tile=16):
    """One token for every active lane (lane ``b`` is slot ``b``). Returns
    ``(state, tokens, positions, logits [B, V], moe [3] int32)``; ``moe``
    sums, over this step's expert layers, the picks that fell on held
    experts, the held experts touched and the busiest one's tokens (active
    lanes only)."""
    eps = cfg.rms_norm_eps
    h = params["embed_tokens"]["embedding"][tokens]
    k_pool, v_pool, wk, wv = (state[n] for n in ("k", "v", "wk", "wv"))
    moe = jnp.zeros(3, jnp.int32)
    for l in range(cfg.num_hidden_layers):
        lp = params["layers"][str(l)]
        x = rms_norm(h, lp["input_layernorm"]["scale"], eps)
        p = lp["self_attn"]
        if cfg.is_window(l):
            y, wk, wv = window_decode(
                p, cfg.attention(l), x, wk, wv, cfg.window_index[l],
                positions, active, window=cfg.sliding_window,
                rotate=_rotate(cfg, l), gate=_gate(p, cfg, l, x))
        else:
            with jax.named_scope("attend_full"):
                y, k_pool, v_pool = gqa_decode(
                    p, cfg.attention(l), x, k_pool, v_pool,
                    cfg.full_index[l], page_tables, positions, active,
                    page_tokens, rotate=_rotate(cfg, l),
                    gate=_gate(p, cfg, l, x))
        h = h + y
        x = rms_norm(h, lp["post_attention_layernorm"]["scale"], eps)
        y, counts = _ffn(lp, cfg, l, x, active, moe_tile)
        moe = moe + counts
        h = h + y
    logits = _head(params, cfg, h)
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    tokens = jnp.where(active, nxt, tokens)
    positions = jnp.where(active, positions + 1, positions)
    return ({"k": k_pool, "v": v_pool, "wk": wk, "wv": wv}, tokens,
            positions, logits, moe)

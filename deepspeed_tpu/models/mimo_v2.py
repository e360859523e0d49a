"""MiMo-V2 decoder (XiaomiMiMo/MiMo-V2.5, ``model_type: mimo_v2``): pure
functions of a parameter tree, for serving. The language model alone: the
vision and audio towers and the multi-token-prediction layers of the
published model have no key in its ``config.json`` and are not served.

Layer ``l`` (from 0): ``h += Attn_l(RMSNorm(h)); h += FFN_l(RMSNorm(h))``;
final RMSNorm; untied ``lm_head``; no bias anywhere. ``Attn_l`` is
grouped-query attention whose keys and values differ in width (heads of
``head_dim`` 192 for queries and keys, of ``v_head_dim`` 128 for values:
scores over 192, the context over 128, ``o_proj`` from 64 x 128) and whose
key-value heads, rotary base and mask go by ``hybrid_layer_pattern[l]``: a
*full* layer (0) has ``num_key_value_heads`` and attends to every earlier
position with a plain softmax; a *window* layer (1) has
``swa_num_key_value_heads``, attends to the ``sliding_window`` positions
that end at its own, and its softmax has a learned sink, one logit a query
head that takes part of the mass and adds no value. Both rotate the first
``int(head_dim x partial_rotary_factor)`` dimensions of every query and key
head with plain frequencies (``rope_theta`` in a full layer,
``swa_rope_theta`` in a window layer) and pass the rest through. The values
are scaled by ``attention_value_scale``: here the context is, once, ahead of
``o_proj`` (the sum over keys is linear in the values, and the context is
float32 where a cached value is not), so the cache holds values as
``v_proj`` gives them. ``FFN_l`` is a dense SwiGLU where ``moe_layer_freq[l]``
is 0 and the sigmoid-routed expert layer of ``parallel/expert.py`` elsewhere:
a correction bias that chooses and does not weigh, the picked scores
renormalised, no scaling factor, no shared expert; this program holds
``experts_held`` of the ``n_routed_experts`` the router scores and adds up
what those give.

Two entry points, both functions of ``(params, cfg, state, ...)`` that the
serving engine jits: ``prefill_chunk`` (``R`` rows of one page of tokens, each
the next tokens of some prompt) and ``decode_step`` (one token for every
active lane). Both are the walk of a decoder of full and window layers in
``models/paged_layers.py`` over this model's hooks, ``_attention`` and
``_ffn``.

``state`` is ``{"k": [Lf, pages, KV x 192, page_tokens], "v": [Lf, pages, KV
x 128, page_tokens], "wk": [Lw, slots, W / page_tokens, KVw x 192,
page_tokens], "wv": [Lw, slots, W / page_tokens, KVw x 128, page_tokens]}``:
four arrays of four widths. With the published window of 128 and pages of
128 a ring is one block. A ring is read behind its position mask, so a lane
needs no reset. The router is float32 whatever the parameters' type; keys
are cached rotated.
"""

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import paged_layers as pl
from deepspeed_tpu.parallel import expert as expert_mod

_PATTERN = (0, 1, 1, 1, 1) + (0, 1, 1, 1, 1, 1) * 7 + (0,)


@dataclass(frozen=True)
class MiMoV2Config:
    """The published keys of ``config.json``, plus the share of a deployment
    this program holds: ``experts_held`` (first, count) of the
    ``n_routed_experts`` the router scores, and ``vocab_size`` rows of the
    vocabulary starting at ``vocab_first`` (traffic ids, logits and sampling
    are over the slice). Of ``hybrid_layer_pattern`` and ``moe_layer_freq``
    the first ``num_hidden_layers`` entries are run."""

    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 8
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    sliding_window: int = 128
    hybrid_layer_pattern: tuple = _PATTERN
    moe_layer_freq: tuple = (0,) + (1,) * 47
    partial_rotary_factor: float = 0.334
    rope_theta: float = 10000000.0
    swa_rope_theta: float = 10000.0
    attention_value_scale: float = 0.707
    add_full_attention_sink_bias: bool = False
    add_swa_attention_sink_bias: bool = True
    layernorm_epsilon: float = 1e-5
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 2048
    n_shared_experts: int = None
    norm_topk_prob: bool = True
    routed_scaling_factor: float = None
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    max_position_embeddings: int = 1048576
    experts_held: tuple = None          # (first, count); None = all
    vocab_first: int = 0

    def __post_init__(self):
        L = self.num_hidden_layers
        for name in ("hybrid_layer_pattern", "moe_layer_freq"):
            value = tuple(getattr(self, name))
            object.__setattr__(self, name, value)
            if len(value) < L:
                raise ValueError(f"{name} names {len(value)} layers, "
                                 f"num_hidden_layers={L}")
            if set(value[:L]) - {0, 1}:
                raise ValueError(f"{name}: 0 or 1 a layer")
        object.__setattr__(self, "experts_held", expert_mod.held_share(
            self.experts_held, self.n_routed_experts))
        for heads, kv in ((self.num_attention_heads,
                           self.num_key_value_heads),
                          (self.swa_num_attention_heads,
                           self.swa_num_key_value_heads)):
            if heads % kv:
                raise ValueError("query heads must divide into their "
                                 "key-value heads in both kinds of layer")
        # what the published model does not do and this program does not
        # compute: refused by the key's name
        if self.add_full_attention_sink_bias:
            raise ValueError("add_full_attention_sink_bias: the paged "
                             "attention of a full layer has no sink")
        if self.scoring_func != "sigmoid" or self.topk_method != "noaux_tc":
            raise ValueError("scoring_func/topk_method: the expert layer is "
                             "sigmoid-routed with a correction bias "
                             "(noaux_tc)")
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError("n_group/topk_group: the router picks among "
                             "all experts, not among groups of them")
        if self.n_shared_experts:
            raise ValueError("n_shared_experts: the expert layer has no "
                             "shared expert")

    @classmethod
    def from_dict(cls, cfg, **share):
        """From the keys of the published ``config.json``."""
        rope = cfg.get("rope_scaling") or {}
        kinds = {rope.get(k, "default") for k in ("rope_type", "type")}
        if kinds != {"default"}:
            raise ValueError(f"rope_scaling {rope}: plain frequencies only")
        kw = {k: cfg[k] for k in cls.__dataclass_fields__
              if k in cfg and k not in ("experts_held", "vocab_first")}
        kw.update(share)
        return cls(**kw)

    # -- derived ---------------------------------------------------------
    def is_window(self, l):
        return self.hybrid_layer_pattern[l] == 1

    def is_moe(self, l):
        return self.moe_layer_freq[l] == 1

    def _shape(self, window):
        if window:
            return pl.AttentionShape(
                self.swa_num_attention_heads, self.swa_num_key_value_heads,
                self.swa_head_dim, self.swa_v_head_dim)
        return pl.AttentionShape(self.num_attention_heads,
                                 self.num_key_value_heads, self.head_dim,
                                 self.v_head_dim)

    def attention(self, l):
        """Layer ``l``'s heads and head sizes, as the grouped-query and the
        window functions read them: its kind's own."""
        return self._shape(self.is_window(l))

    def rope(self, l):
        return pl.RopeSpec(
            rope_theta=(self.swa_rope_theta if self.is_window(l)
                        else self.rope_theta),
            partial_rotary_factor=self.partial_rotary_factor)

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
    def cache_widths(self):
        """{name of a pool array: values a token caches there}: a full
        layer's keys and values in ``k`` and ``v`` pages, a window layer's
        in ``wk`` and ``wv`` rings, the key-value heads side by side; four
        widths (768, 512, 1,536 and 1,024 as published)."""
        full, ring = self._shape(False), self._shape(True)
        return {"k": full.num_key_value_heads * full.head_dim,
                "v": full.num_key_value_heads * full.v_head_dim,
                "wk": ring.num_key_value_heads * ring.head_dim,
                "wv": ring.num_key_value_heads * ring.v_head_dim}


# -- what a layer's attention is given --------------------------------------

def _value_scale(cfg):
    """``gate(ctx)`` of the attention functions: the context times
    ``attention_value_scale``, which is what scaling every value gives."""
    def scale(ctx):
        with jax.named_scope("attn_value_scale"):
            return ctx * cfg.attention_value_scale
    return scale


def _attention(cfg, l, p, x):
    """``(shape, keyword arguments)`` of layer ``l``'s attention call, as
    ``paged_layers``'s walk asks for them (``x``, the layer's normed input,
    is read by nothing here)."""
    del x
    shape = cfg.attention(l)
    window = cfg.is_window(l)
    how = dict(rotate=pl.rotary(cfg.rope(l), shape,
                                "rope_window" if window else "rope_full"),
               gate=_value_scale(cfg))
    if window:
        how["window"] = cfg.sliding_window
        if cfg.add_swa_attention_sink_bias:
            kvh = shape.num_key_value_heads
            how["sink"] = p["attention_sink_bias"].reshape(
                kvh, shape.num_attention_heads // kvh)
    return shape, how


# -- the two programs -------------------------------------------------------

def _ffn(lp, cfg, l, x, live, tile, decode):
    """Layer ``l``'s FFN over flat tokens ``x [N, d]``; ``live [N]`` says
    which tokens are real. Returns ``(y, counts [3] int32)`` as
    ``expert.routed_moe_ffn`` gives them (zeros for a dense layer). In a
    ``decode`` step an expert layer reads every held expert, picked or not:
    a sixteenth of the experts under a full batch's picks leaves one in
    twenty idle in a step, which one it is follows the weights, and a step
    that reads them all takes the same time whatever they are (the counts
    are still of the experts that were picked)."""
    if not cfg.is_moe(l):
        return pl.swiglu(x, lp["mlp"]), jnp.zeros(3, jnp.int32)
    return expert_mod.routed_moe_ffn(
        lp["mlp"], x, live, k=cfg.num_experts_per_tok,
        scaling=cfg.routed_scaling_factor or 1.0,
        renormalize=cfg.norm_topk_prob, held=cfg.experts_held, tile=tile,
        every_expert=decode)


def prefill_chunk(params, cfg, state, ids, slots, starts, lens, page_tables,
                  *, page_tokens, moe_tile=128):
    """``paged_layers.full_window_prefill_chunk`` over this model's layers."""
    return pl.full_window_prefill_chunk(
        params, cfg, state, ids, slots, starts, lens, page_tables,
        page_tokens=page_tokens, moe_tile=moe_tile, attention=_attention,
        ffn=_ffn, eps=cfg.layernorm_epsilon)


def decode_step(params, cfg, state, tokens, positions, active, page_tables,
                *, page_tokens, moe_tile=16):
    """``paged_layers.full_window_decode_step`` over this model's layers."""
    return pl.full_window_decode_step(
        params, cfg, state, tokens, positions, active, page_tables,
        page_tokens=page_tokens, moe_tile=moe_tile, attention=_attention,
        ffn=_ffn, eps=cfg.layernorm_epsilon)

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
serving engine jits: ``prefill_chunk`` (``R`` rows of one page of tokens, each
the next tokens of some prompt) and ``decode_step`` (one token for every
active lane). Both are the walk of a decoder of full and window layers in
``models/paged_layers.py``, which says what a full layer does with its pages
and a window layer with its ring, over this model's hooks: ``_attention``
(the layer's head counts, its rotary kind and the gate) and ``_ffn``.

``state`` is ``{"k", "v": [Lf, pages, kv_heads * head_dim, page_tokens]``
(the full layers' pages), ``"wk", "wv": [Lw, slots, W / page_tokens,
kv_heads * head_dim, page_tokens]}`` (a ring of ``W = sliding_window``
positions a lane for each window layer): the layouts, and why a ring needs
no reset, are in ``models/paged_layers.py``. The router is float32 whatever
the parameters' type; keys are cached rotated.
"""

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import paged_layers as pl
from deepspeed_tpu.parallel import expert as expert_mod

FULL, WINDOW = "full_attention", "sliding_attention"
_PERIOD = (FULL, WINDOW, WINDOW, WINDOW)


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
    rope_full: pl.RopeSpec = pl.RopeSpec(
        rope_theta=500000.0, rope_type="yarn", partial_rotary_factor=0.5,
        factor=64.0, original_max_position_embeddings=4096, beta_fast=64.0,
        beta_slow=1.0, attention_factor=1.4158883083359672)
    rope_window: pl.RopeSpec = pl.RopeSpec()

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
            kw["rope_full"] = pl.RopeSpec.from_dict(rope[FULL])
        if WINDOW in rope:
            kw["rope_window"] = pl.RopeSpec.from_dict(rope[WINDOW])
        return cls(**kw)

    # -- derived ---------------------------------------------------------
    def is_window(self, l):
        return self.layer_types[l] == WINDOW

    def is_moe(self, l):
        return self.mlp_layer_types[l] == "sparse"

    def attention(self, l):
        """Layer ``l``'s head counts, as the grouped-query functions read
        them."""
        return pl.AttentionShape(self.num_attention_heads_per_layer[l],
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


def _rotate(cfg, l):
    """Layer ``l``'s ``rotate``: YaRN over half a head in a full layer,
    plain frequencies over the whole head in a window layer."""
    return pl.rotary(cfg.rope(l), cfg.attention(l),
                     "rope_window" if cfg.is_window(l) else "rope_full")


def _gate(p, cfg, l, x):
    """``gate(ctx [..., Q * hd])``: every head's output times
    ``sigmoid(x W_g)``, one scalar a head, float32."""
    if not cfg.gating:
        return None

    def gate(ctx):
        with jax.named_scope("attn_gate"):
            g = jax.nn.sigmoid(pl.dot(x, p["g_proj"]["kernel"]))
            heads = ctx.reshape(g.shape + (cfg.head_dim,))
            return (heads * g[..., None]).reshape(ctx.shape)
    return gate


def _attention(cfg, l, p, x):
    """``(shape, keyword arguments)`` of layer ``l``'s attention call, as
    ``paged_layers``'s walk asks for them; ``x`` is the layer's normed
    input, which the gate reads."""
    how = dict(rotate=_rotate(cfg, l), gate=_gate(p, cfg, l, x))
    if cfg.is_window(l):
        how["window"] = cfg.sliding_window
    return cfg.attention(l), how


# -- the two programs -------------------------------------------------------

def _ffn(lp, cfg, l, x, live, tile, decode):
    """Layer ``l``'s FFN over flat tokens ``x [N, d]``; ``live [N]`` says
    which tokens are real; the same in a ``decode`` step as in a prefill
    call. Returns ``(y, counts [3] int32)`` as ``expert.routed_moe_ffn``
    gives them (zeros for a dense layer)."""
    del decode
    if not cfg.is_moe(l):
        return pl.swiglu(x, lp["mlp"]), jnp.zeros(3, jnp.int32)
    return expert_mod.routed_moe_ffn(
        lp["mlp"], x, live, k=cfg.num_experts_per_tok,
        scaling=cfg.moe_routed_scaling_factor, renormalize=True,
        held=(0, cfg.num_experts), tile=tile)


def prefill_chunk(params, cfg, state, ids, slots, starts, lens, page_tables,
                  *, page_tokens, moe_tile=128):
    """``paged_layers.full_window_prefill_chunk`` over this model's layers."""
    return pl.full_window_prefill_chunk(
        params, cfg, state, ids, slots, starts, lens, page_tables,
        page_tokens=page_tokens, moe_tile=moe_tile, attention=_attention,
        ffn=_ffn, eps=cfg.rms_norm_eps)


def decode_step(params, cfg, state, tokens, positions, active, page_tables,
                *, page_tokens, moe_tile=16):
    """``paged_layers.full_window_decode_step`` over this model's layers."""
    return pl.full_window_decode_step(
        params, cfg, state, tokens, positions, active, page_tables,
        page_tokens=page_tokens, moe_tile=moe_tile, attention=_attention,
        ffn=_ffn, eps=cfg.rms_norm_eps)

"""Ouro decoder (ByteDance/Ouro-2.6B, ``model_type: ouro``, a looped language
model): pure functions of a parameter tree, for serving.

A dense multi-head decoder whose whole stack of ``num_hidden_layers`` layers
is run ``total_ut_steps`` times a token with one set of weights. ``h`` the
residual stream, ``t`` the pass, ``l`` the layer (both from 0):

    h = embed[id]
    for t:  for l:  h += RMSNorm_l2(Attn_l(RMSNorm_l1(h)))
                    h += RMSNorm_l4(SwiGLU_l(RMSNorm_l3(h)))
            h = RMSNorm_f(h)
    logits = h W_head

four norms a layer (one ahead of each sub-layer, one on its output ahead of
the residual add: ``input_layernorm``, ``input_layernorm_2``,
``post_attention_layernorm``, ``post_attention_layernorm_2``), the final
norm at the end of EVERY pass, the untied head after the last; no bias
anywhere. ``Attn_l`` is multi-head attention (as many key-value heads as
query heads) with the whole head rotated, over every earlier position. Pass
``t`` of layer ``l`` caches its keys and values in a row of its own, ``c = t
x num_hidden_layers + l``, and attends to that row only: what pass ``t`` of
layer ``l`` made for the earlier positions, never another pass's. A token
therefore caches ``cache_rows = total_ut_steps x num_hidden_layers`` rows
though the weights have ``num_hidden_layers`` layers.

The exit gate (``early_exit_gate``: ``g_t = h_t . w_g + b_g`` on the normed
``h_t`` of pass ``t``; ``benchmarks/refs/ouro_ref.py::exit_distribution``)
gives a distribution over the passes; a token leaves at the first pass whose
cumulated mass reaches ``early_exit_threshold``. At the published threshold
of 1 that is the last pass, so every token runs every pass and the gate
changes no served number: the programs do not read it. A threshold under 1
(lanes of one step that leave at different passes) is refused by name.

Two entry points, both functions of ``(params, cfg, state, ...)`` that the
serving engine jits: ``prefill_chunk`` and ``decode_step``, with the
signatures the sibling families' programs have. The walk is ROLLED: the
layers' weights are stacked on a leading axis (``stack_layers``:
``params["stack"]``) and scanned, inside a loop over the passes, the cache
row a traced value and the two pools the loops' carries; a program's text
holds one layer whatever the two counts are. Attention is
``models/paged_layers.py``'s (``gqa_prefill``, ``gqa_decode``, ``rotary``).

``state`` is ``{"k", "v": [cache_rows, pages, heads * head_dim,
page_tokens]}``, laid out as ``models/paged_layers.py`` says; keys are
cached rotated.
"""

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import paged_layers as pl

@dataclass(frozen=True)
class OuroConfig:
    """The published keys of ``config.json``."""

    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    rope_scaling: dict = None
    use_sliding_window: bool = False
    tie_word_embeddings: bool = False
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0

    def __post_init__(self):
        # what this program does not compute: refused by the key's name
        if self.total_ut_steps < 1:
            raise ValueError(f"total_ut_steps={self.total_ut_steps}: the "
                             f"stack is run at least once")
        if self.early_exit_threshold < 1:
            raise ValueError(
                f"early_exit_threshold={self.early_exit_threshold}: under 1 "
                f"the lanes of a step leave the stack at different passes; "
                f"a step here runs every pass for every lane")
        if self.rope_scaling is not None:
            raise ValueError(f"rope_scaling={self.rope_scaling!r}: plain "
                             f"frequencies only (null as published)")
        if self.use_sliding_window:
            raise ValueError("use_sliding_window: every layer attends to "
                             "every earlier position")
        if self.tie_word_embeddings:
            raise ValueError("tie_word_embeddings: the head is its own "
                             "matrix")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must divide into their key-value "
                             "heads")

    @classmethod
    def from_dict(cls, cfg):
        """From the keys of the published ``config.json``."""
        return cls(**{k: cfg[k] for k in cls.__dataclass_fields__
                      if k in cfg})

    # -- derived ---------------------------------------------------------
    @property
    def cache_rows(self):
        """Rows a token caches: one a (pass, layer)."""
        return self.total_ut_steps * self.num_hidden_layers

    @property
    def attention(self):
        """The head counts, as the grouped-query functions read them."""
        return pl.AttentionShape(self.num_attention_heads,
                                 self.num_key_value_heads, self.head_dim,
                                 self.head_dim)

    @property
    def cache_widths(self):
        """{name of a pool array: values a token caches in one row of
        it}."""
        return dict.fromkeys(("k", "v"),
                             self.num_key_value_heads * self.head_dim)

    @property
    def cache_values_per_token(self):
        """Values a token caches over all its rows, keys and values."""
        return self.cache_rows * sum(self.cache_widths.values())

    n_moe_layers = 0            # what the loop records a decode step's
                                # three expert integers under


@jax.jit
def _stack(*leaves):
    return jnp.stack(leaves)


def stack_layers(params):
    """``params`` as the programs take them: the per-layer trees of the
    public layout (``layers/<l>/...``) stacked leaf by leaf on a leading
    axis under ``stack``. ``params["layers"]`` is CONSUMED: a layer's leaf
    is let go as soon as it is stacked, so that a caller who keeps no other
    reference holds the layers once and one stacked leaf more at the peak (a
    third of a chip of weights cannot be held twice beside a pool)."""
    layers = params.pop("layers")
    rows, trees = zip(*(jax.tree_util.tree_flatten(layers.pop(str(l)))
                        for l in range(len(layers))))
    stacked = []
    for i in range(len(rows[0])):
        stacked.append(_stack(*(row[i] for row in rows)))
        for row in rows:
            row[i] = None
    return dict(params, stack=jax.tree_util.tree_unflatten(trees[0], stacked))


# -- the two programs -------------------------------------------------------

def _cache_row(cfg, t, l):
    """The row of the paged arrays that pass ``t`` of layer ``l`` writes and
    attends to (both may be traced)."""
    return t * cfg.num_hidden_layers + l


def _walk(params, cfg, h, k_pool, v_pool, attend):
    """The rolled walk: ``total_ut_steps`` passes of a scan over the stacked
    layers, the final norm at the end of each. ``attend(p, x, k_pool,
    v_pool, row) -> (y, k_pool, v_pool)`` is the program's attention over
    cache row ``row`` (traced). Returns the last pass's normed ``h`` and
    the pools."""
    L, eps = cfg.num_hidden_layers, cfg.rms_norm_eps

    def one_pass(t, carry):
        def layer(carry, xs):
            h, k_pool, v_pool = carry
            lp, l = xs
            x = pl.rms_norm(h, lp["input_layernorm"]["scale"], eps)
            with jax.named_scope("attend_full"):
                y, k_pool, v_pool = attend(lp["self_attn"], x, k_pool,
                                           v_pool, _cache_row(cfg, t, l))
            h = h + pl.rms_norm(y, lp["input_layernorm_2"]["scale"], eps)
            x = pl.rms_norm(h, lp["post_attention_layernorm"]["scale"], eps)
            h = h + pl.rms_norm(pl.swiglu(x, lp["mlp"]),
                                lp["post_attention_layernorm_2"]["scale"], eps)
            return (h, k_pool, v_pool), None

        with jax.named_scope("loop_pass"):
            layers = (params["stack"], jnp.arange(L, dtype=jnp.int32))
            (h, k_pool, v_pool), _ = jax.lax.scan(layer, carry, layers)
        with jax.named_scope("loop_final_norm"):
            h = pl.rms_norm(h, params["norm"]["scale"], eps)
        return h, k_pool, v_pool

    return jax.lax.fori_loop(0, cfg.total_ut_steps, one_pass,
                             (h, k_pool, v_pool))


def _head(h, params):
    """Float32 logits of a normed ``h`` (the final norm is each pass's
    last operation)."""
    with jax.named_scope("lm_head"):
        return pl.dot(h, params["lm_head"]["kernel"])


def _rotate(cfg):
    return pl.rotary(pl.RopeSpec(rope_theta=float(cfg.rope_theta)),
                     cfg.attention, "rope_full")


def prefill_chunk(params, cfg, state, ids, slots, starts, lens, page_tables,
                  *, page_tokens):
    """``R`` rows of the prompts being read: ``ids [R, T]`` with ``T =
    page_tokens``, ``slots [R]`` the slot of each row's prompt (unread: no
    state is a slot's), ``starts [R]`` tokens of it already read (a
    multiple of ``T``), ``lens [R]`` valid tokens of the row (0: an empty
    row, which writes nothing), ``page_tables [R, mp]``. Rows of one prompt
    are consecutive and in order. Returns ``(state, first [R], logits [R,
    V])``: the greedy token after each row's last valid position, and the
    logits it was taken from."""
    del slots
    R, T = ids.shape
    assert T == page_tokens, (T, page_tokens)
    shape, rotate = cfg.attention, _rotate(cfg)

    def attend(p, x, k_pool, v_pool, row):
        return pl.gqa_prefill(p, shape, x, k_pool, v_pool, row, page_tables,
                              starts, lens, page_tokens, rotate=rotate)

    h = params["embed_tokens"]["embedding"][ids]
    h, k_pool, v_pool = _walk(params, cfg, h, state["k"], state["v"], attend)
    at = jnp.clip(lens - 1, 0, T - 1)
    h_last = jnp.take_along_axis(h, at[:, None, None], axis=1)[:, 0]
    logits = _head(h_last, params)
    first = jnp.argmax(logits, -1).astype(jnp.int32)
    return {"k": k_pool, "v": v_pool}, first, logits


def decode_step(params, cfg, state, tokens, positions, active, page_tables,
                *, page_tokens):
    """One token for every active lane (lane ``b`` is slot ``b``). Returns
    ``(state, tokens, positions, logits [B, V], moe [3] int32)``; ``moe``
    is zeros (no expert layer), there because the families' decode programs
    hand back three integers beside their tokens."""
    shape, rotate = cfg.attention, _rotate(cfg)

    def attend(p, x, k_pool, v_pool, row):
        return pl.gqa_decode(p, shape, x, k_pool, v_pool, row, page_tables,
                             positions, active, page_tokens, rotate=rotate)

    h = params["embed_tokens"]["embedding"][tokens]
    h, k_pool, v_pool = _walk(params, cfg, h, state["k"], state["v"], attend)
    logits = _head(h, params)
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    tokens = jnp.where(active, nxt, tokens)
    positions = jnp.where(active, positions + 1, positions)
    return ({"k": k_pool, "v": v_pool}, tokens, positions, logits,
            jnp.zeros(3, jnp.int32))

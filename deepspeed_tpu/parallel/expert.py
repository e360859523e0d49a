"""Mixture-of-Experts with expert parallelism.

Beyond the v0.3.10 reference (which predates DeepSpeed-MoE), but a
reference-family capability users expect: later DeepSpeed made MoE +
expert parallelism a headline feature. Built TPU-first rather than as a
port of that CUDA/torch design:

- **Static-capacity one-hot dispatch** (Switch/GShard): routing becomes
  three einsums (dispatch, expert FFN, combine) over a [tokens, experts,
  capacity] one-hot tensor — all MXU work, no scatter/gather, shapes
  static under jit. Tokens past an expert's capacity are dropped (their
  combine weight is zero), exactly the Switch training recipe.
- **Expert parallelism** = shard the expert dimension of the stacked
  expert params over an existing mesh axis (default ``data`` — the same
  expert-parallel-within-DP layout DeepSpeed-MoE uses) and exchange
  tokens with ONE ``lax.all_to_all`` each way inside ``shard_map``.
  Comm volume per device per direction is O(tokens/W * d_model),
  independent of the expert count.

Two entry points:
- ``MoELayer`` — flax module for the single-program pjit path; pair with
  ``expert_shardings`` to lay its stacked expert params over the mesh and
  let GSPMD partition the dispatch einsums.
- ``expert_parallel_ffn`` — the explicit shard_map + all_to_all program
  (runs INSIDE shard_map), for when the schedule must be pinned rather
  than left to the partitioner.
"""

from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu.parallel.mesh import DATA_AXIS, replicated_sharding


# ---------------------------------------------------------------------------
# Routing (Switch-style top-1, static capacity)
# ---------------------------------------------------------------------------

def top1_gating(logits, capacity):
    """Switch top-1 router.

    logits: [T, E] raw router scores. capacity: max tokens per expert.
    Returns (dispatch [T, E, C] one-hot, combine [T, E, C] gate-weighted,
    aux_loss scalar). ``aux_loss`` is the Switch load-balancing loss
    E * sum_e(frac_tokens_e * mean_prob_e); 1.0 at perfect balance.
    """
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)                      # [T]
    mask = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)      # [T, E]
    gate = jnp.sum(probs * mask, axis=-1)                        # [T]

    # position of each token within its expert's queue (0-based)
    pos = jnp.cumsum(mask, axis=0) * mask - mask                 # [T, E]
    keep = mask * (pos < capacity)                               # [T, E]
    pos_clamped = jnp.minimum(pos, capacity - 1).astype(jnp.int32)
    slot = jax.nn.one_hot(pos_clamped, capacity, dtype=jnp.float32)  # [T, E, C]

    dispatch = keep[:, :, None] * slot                           # [T, E, C]
    combine = dispatch * gate[:, None, None]                     # [T, E, C]

    frac_tokens = jnp.mean(mask, axis=0)                         # [E]
    mean_prob = jnp.mean(probs, axis=0)                          # [E]
    aux_loss = E * jnp.sum(frac_tokens * mean_prob)
    return dispatch, combine, aux_loss


def _expert_ffn(params, x):
    """Stacked-expert FFN: x [E, C, d] -> [E, C, d] through per-expert
    (w1 [E, d, f], b1 [E, f], w2 [E, f, d], b2 [E, d]). Weights cast to the
    activation dtype so bf16 activations get bf16 MXU operands (f32 master
    params stay f32 in the optimizer — same recipe as the fused layer)."""
    w1 = params["w1"].astype(x.dtype)
    w2 = params["w2"].astype(x.dtype)
    h = jnp.einsum("ecd,edf->ecf", x, w1) + params["b1"].astype(x.dtype)[:, None, :]
    h = jax.nn.gelu(h)
    return jnp.einsum("ecf,efd->ecd", h, w2) + params["b2"].astype(x.dtype)[:, None, :]


def moe_ffn(params, x, capacity):
    """Single-program MoE FFN over flat tokens x [T, d].

    params: {"router": [d, E], "w1": [E, d, f], "b1": [E, f],
             "w2": [E, f, d], "b2": [E, d]}.
    Returns (out [T, d], aux_loss).
    """
    # router math in f32 (softmax numerics); dispatch/FFN/combine stay in
    # x.dtype so bf16 activations keep bf16-MXU throughput on the three
    # big einsums — only the [T,E] gating tensors are ever f32
    logits = x.astype(jnp.float32) @ params["router"]
    dispatch, combine, aux = top1_gating(logits, capacity)
    expert_in = jnp.einsum("td,tec->ecd", x, dispatch.astype(x.dtype))
    expert_out = _expert_ffn(params, expert_in)                  # [E, C, d]
    out = jnp.einsum("ecd,tec->td", expert_out, combine.astype(x.dtype))
    return out.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Top-k routing without capacity (sigmoid or softmax scores); a chip's share
# of the experts
# ---------------------------------------------------------------------------

def sigmoid_topk_routing(x, router, bias, k, scaling=1.0, renormalize=True):
    """DeepSeek-V3-style router over ALL experts: ``s = sigmoid(x W_r)`` in
    float32 (``highest`` matmul precision: a TPU would otherwise round the
    operands), the ``k`` largest of ``s + bias`` are picked, and a pick
    weighs ``s / sum of the picked s * scaling`` (``bias`` chooses, it does
    not weigh; ``None`` for a router that has none). x [T, d]; returns
    (idx [T, k] int32, weights [T, k] f32)."""
    s = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(
        s if bias is None else s + bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if renormalize:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), w * scaling


def softmax_topk_routing(x, router, k, renormalize=True):
    """Softmax router over ALL experts (the Qwen3-MoE family's, which Keye-VL
    shares): ``p = softmax(x W_r)`` in float32 at ``highest`` matmul
    precision, the ``k`` largest are picked and a pick weighs ``p / sum of
    the picked p`` (``renormalize``) or ``p`` itself. No bias, no scaling.
    x [T, d]; returns (idx [T, k] int32, weights [T, k] f32)."""
    p = jax.nn.softmax(jnp.matmul(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    w, idx = jax.lax.top_k(p, k)
    if renormalize:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), w


def expert_function(weights):
    """An expert's function, chosen by the matrices it is given:
    ``apply(rows, matrix)`` is the float32 result, ``matrix(name)`` handing
    it one of the expert's matrices. ``{gate_proj, up_proj, down_proj}`` is
    a SwiGLU, ``down(silu(gate x) * up x)``; ``{up_proj, down_proj}`` alone
    a squared ReLU, ``down(relu(up x)^2)``. The routed experts and a shared
    expert go through the same function; their widths may differ."""
    def mm(a, w):
        return jnp.matmul(a, w, preferred_element_type=jnp.float32)

    def swiglu(r, matrix):
        a = mm(r, matrix("gate_proj"))
        b = mm(r, matrix("up_proj"))
        return mm((jax.nn.silu(a) * b).astype(r.dtype), matrix("down_proj"))

    def relu2(r, matrix):
        a = jax.nn.relu(mm(r, matrix("up_proj")))
        return mm(jnp.square(a).astype(r.dtype), matrix("down_proj"))

    return swiglu if "gate_proj" in weights else relu2


def held_experts_ffn(x, experts, idx, weights, held, tile, live=None,
                     every_expert=False):
    """The part of a routed expert layer that the experts held here give:
    ``sum over picks p of token t with idx[t, p] held of weights[t, p] *
    Expert_e(x_t)``, the expert's function being what ``expert_function``
    reads off ``experts``. No capacity, no dropped token: the picks are
    grouped by expert, each group padded up to a multiple of ``tile`` rows,
    and a loop runs over the tiles in use (its trip count is data, the
    shapes are not), reading one expert's weights a tile. An expert no token
    picked is never read, unless ``every_expert`` gives each held expert at
    least one tile (of empty rows where nothing picked it): the call then
    reads the same weights whatever the router chose, for a caller whose
    few rows touch nearly every held expert anyway and whose step should
    take the same time from one routing to the next. A pick of an expert
    held elsewhere costs nothing here.

    x [T, d]; experts {gate_proj (SwiGLU only), up_proj [E_h, d, f],
    down_proj [E_h, f, d]}; idx, weights [T, k]; held (first, count) among
    the router's experts; live [T] bool marks real tokens (None = all).
    Returns (y [T, d] float32, counts [3] int32: picks that fell on held
    experts, held experts with at least one token, the busiest one's
    tokens)."""
    T, d = x.shape
    k = idx.shape[1]
    first, n_held = held
    apply = expert_function(experts)
    assert experts["up_proj"].shape[0] == n_held, (experts["up_proj"].shape,
                                                   held)
    M = T * k
    e = idx.reshape(M) - first
    here = (e >= 0) & (e < n_held)
    if live is not None:
        here = here & jnp.repeat(live, k)
    e = jnp.where(here, e, n_held)                   # n_held = "not here"
    counts = jnp.zeros(n_held + 1, jnp.int32).at[e].add(1)[:n_held]
    tiled = jnp.maximum(counts, 1) if every_expert else counts
    padded = -(-tiled // tile) * tile
    group_end = jnp.cumsum(padded)
    group_start = group_end - padded
    # a pick's row: its group's start plus its rank among the group's picks
    order = jnp.argsort(e, stable=True)
    sorted_e = e[order]
    first_of = jnp.cumsum(counts) - counts
    rank = jnp.arange(M) - first_of[jnp.minimum(sorted_e, n_held - 1)]
    m_pad = -(-M // tile) * tile + n_held * tile     # static bound
    dest_sorted = jnp.where(
        sorted_e < n_held,
        group_start[jnp.minimum(sorted_e, n_held - 1)] + rank, m_pad)
    dest = jnp.zeros(M, jnp.int32).at[order].set(dest_sorted.astype(jnp.int32))
    token_of_row = jnp.full(m_pad, T, jnp.int32).at[dest].set(
        jnp.arange(M, dtype=jnp.int32) // k, mode="drop")
    rows = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])[token_of_row]
    n_tiles = group_end[-1] // tile
    tile_expert = jnp.minimum(jnp.searchsorted(
        group_end, jnp.arange(m_pad // tile) * tile, side="right"),
        n_held - 1)

    def one_tile(i, out):
        ex = tile_expert[i]
        r = jax.lax.dynamic_slice_in_dim(rows, i * tile, tile, axis=0)
        y = apply(r, lambda name: jax.lax.dynamic_index_in_dim(
            experts[name], ex, 0, False))
        return jax.lax.dynamic_update_slice_in_dim(out, y, i * tile, axis=0)

    out = jax.lax.fori_loop(0, n_tiles, one_tile,
                            jnp.zeros((m_pad, d), jnp.float32))
    picked = out.at[dest].get(mode="fill", fill_value=0.0)       # [M, d]
    y = jnp.einsum("tkd,tk->td", picked.reshape(T, k, d),
                   weights.astype(jnp.float32))
    stats = jnp.stack([jnp.sum(counts), jnp.sum(counts > 0),
                       jnp.max(counts)]).astype(jnp.int32)
    return y, stats


def held_share(held, scored):
    """``(first, count)`` of the ``scored`` experts a router scores that one
    chip holds, as a configuration states it: None is all of them, and a
    share that reaches outside them is refused."""
    first, count = (0, scored) if held is None else held
    if not (0 <= first and count >= 1 and first + count <= scored):
        raise ValueError(f"experts_held={held} outside the {scored} experts "
                         f"the router scores")
    return first, count


def routed_moe_ffn(params, x, live=None, *, k, scaling, renormalize, held,
                   tile, every_expert=False, scoring="sigmoid"):
    """One chip's share of a routed expert layer, with a shared expert
    where the parameters have one: the router scores every expert of the
    published count and picks ``k`` a token, by ``scoring``: ``"sigmoid"``
    (``sigmoid_topk_routing``: a correction bias that chooses where the
    parameters hold one, ``scaling`` on the weights) or ``"softmax"``
    (``softmax_topk_routing``: no bias, and ``scaling`` must be 1). This
    chip adds up what the experts it holds (``held = (first, count)``)
    give, plus the shared expert, which every chip of the deployment
    computes alike. What the other chips' experts would add is their part
    of the sum: on one chip the layer runs without its exchange, and
    nothing stands in for it.

    params: {gate: {kernel [d, E], e_score_correction_bias [E] (optional,
    sigmoid only)}, experts: {gate_proj, up_proj, down_proj} or {up_proj,
    down_proj}, shared_experts (optional): the same names with ``/kernel``,
    of a width of its own}; ``expert_function`` reads each one's function
    off its matrices. x [T, d]; ``every_expert`` as ``held_experts_ffn``
    takes it. Returns (y [T, d] in x's type, counts [3] int32 as
    ``held_experts_ffn`` gives them)."""
    gate = params["gate"]
    with jax.named_scope("moe_route"):
        if scoring == "sigmoid":
            idx, w = sigmoid_topk_routing(
                x, gate["kernel"], gate.get("e_score_correction_bias"), k,
                scaling, renormalize)
        elif scoring == "softmax":
            assert scaling == 1.0 and "e_score_correction_bias" not in gate
            idx, w = softmax_topk_routing(x, gate["kernel"], k, renormalize)
        else:
            raise ValueError(f"scoring {scoring!r}: sigmoid or softmax")
    with jax.named_scope("moe_experts"):
        y, stats = held_experts_ffn(x, params["experts"], idx, w, held, tile,
                                    live, every_expert)
    if "shared_experts" in params:
        with jax.named_scope("moe_shared"):
            sp = params["shared_experts"]
            y = y + expert_function(sp)(x, lambda name: sp[name]["kernel"])
    return y.astype(x.dtype), stats


# ---------------------------------------------------------------------------
# Expert parallelism (runs inside shard_map)
# ---------------------------------------------------------------------------

def expert_parallel_ffn(params, x, capacity, axis_name=DATA_AXIS):
    """MoE FFN with the expert dim sharded over ``axis_name``; call INSIDE
    shard_map. Local views: x [T/W, d] (token-sharded), expert params
    [E/W, ...] (expert-sharded), router [d, E] replicated.

    One all_to_all ships each device's [E, C_local, d] dispatch tensor so
    every device holds ALL tokens bound for its local experts; the inverse
    all_to_all ships results back. aux_loss is psum-averaged so every
    device returns the same scalar (routing is computed on local tokens —
    the data-parallel recipe; capacity is per device per expert).
    """
    W = jax.lax.psum(1, axis_name)
    E = params["w1"].shape[0] * W
    assert params["router"].shape[1] == E, (
        f"router scores {params['router'].shape[1]} experts but "
        f"{params['w1'].shape[0]} local x {W} devices = {E}")

    logits = x.astype(jnp.float32) @ params["router"]            # [Tl, E]
    dispatch, combine, aux = top1_gating(logits, capacity)
    aux = jax.lax.pmean(aux, axis_name)

    expert_in = jnp.einsum("td,tec->ecd", x, dispatch.astype(x.dtype))
    # [E, C, d] -> [El, W*C, d]: keep local experts, gather their tokens
    # from every device (tiled all_to_all: split dim 0 W ways, concat the
    # received slices along dim 1)
    expert_in = jax.lax.all_to_all(
        expert_in, axis_name, split_axis=0, concat_axis=1, tiled=True)
    expert_out = _expert_ffn(params, expert_in)                  # [El, W*C, d]
    # inverse: [El, W*C, d] -> [E, C, d]
    expert_out = jax.lax.all_to_all(
        expert_out, axis_name, split_axis=1, concat_axis=0, tiled=True)
    out = jnp.einsum("ecd,tec->td", expert_out, combine.astype(x.dtype))
    return out.astype(x.dtype), aux


def expert_shardings(mesh, params, axis=DATA_AXIS):
    """NamedShardings laying MoE params over ``mesh``: stacked expert
    tensors (leading expert dim) split on ``axis``, everything else (the
    router, and any non-MoE leaves in a larger tree) replicated.

    A leaf shards only when it is one of ``w1/b1/w2/b2`` inside a COMPLETE
    MoE param group — a mapping that also holds ``router`` and all four
    expert tensors as siblings (the tree ``MoELayer``/``moe_ffn`` produce).
    Name alone is not enough: plain dense blocks commonly call their
    weights ``w1``/``w2`` too, and sharding those would split d_model."""
    expert_names = {"w1", "b1", "w2", "b2"}
    moe_group = expert_names | {"router"}

    def is_moe_group(node):
        try:
            keys = set(node.keys())
        except AttributeError:
            return False
        return moe_group <= keys

    def walk(node, inside_group):
        if isinstance(node, dict) or hasattr(node, "keys"):
            grouped = is_moe_group(node)
            return type(node)(
                (k, walk(
                    v,
                    grouped and k in expert_names,
                )) for k, v in node.items())
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v, False) for v in node)
        if inside_group:
            return NamedSharding(
                mesh, PartitionSpec(axis, *([None] * (node.ndim - 1))))
        return replicated_sharding(mesh)

    return walk(params, False)


# ---------------------------------------------------------------------------
# Flax module (single-program pjit path)
# ---------------------------------------------------------------------------

@dataclass
class MoEConfig:
    num_experts: int = 8
    d_model: int = 512
    d_ff: int = 2048
    # capacity = capacity_factor * T / E (Switch's recipe), min 4
    capacity_factor: float = 1.25


class MoELayer(nn.Module):
    """Switch-style MoE FFN block over [B, S, d] activations.

    Returns (out [B, S, d], aux_loss); add ``aux_loss`` (scaled, Switch
    uses 1e-2) to the training loss. Param tree: router [d, E] and stacked
    expert tensors w1/b1/w2/b2 with leading expert dim — shard the expert
    dim over the mesh with ``expert_shardings`` for expert parallelism.
    """
    config: MoEConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, S, d = x.shape
        assert d == cfg.d_model, (d, cfg.d_model)
        init = nn.initializers.normal(stddev=0.02)
        params = {
            "router": self.param("router", init, (d, cfg.num_experts), jnp.float32),
            "w1": self.param("w1", init, (cfg.num_experts, d, cfg.d_ff), jnp.float32),
            "b1": self.param("b1", nn.initializers.zeros, (cfg.num_experts, cfg.d_ff), jnp.float32),
            "w2": self.param("w2", init, (cfg.num_experts, cfg.d_ff, d), jnp.float32),
            "b2": self.param("b2", nn.initializers.zeros, (cfg.num_experts, d), jnp.float32),
        }
        T = B * S
        capacity = max(4, int(np.ceil(cfg.capacity_factor * T / cfg.num_experts)))
        out, aux = moe_ffn(params, x.reshape(T, d), capacity)
        return out.reshape(B, S, d), aux

"""Shared sharding-spec registry: ONE ordered regex -> PartitionSpec rule
table over param-tree paths, consumed by BOTH engines.

The reference scatters distribution decisions across process groups and
per-engine heuristics; DeepCompile's argument (PAPERS.md) is that they
belong in one compiler-visible layer. This module is that layer for the
TPU port: an ordered ``match_partition_rules``-style rule table (first
match wins, exactly like the EasyLM/levanter exemplars in SNIPPETS.md)
resolves every placement the repo makes —

- the serving engine's tensor-parallel params, its paged KV pool
  ``[L, n_pages+1, nh, page_tokens, hd]`` (sharded over heads on the
  ``model`` axis), and its replicated host-uploaded lane state;
- the ZeRO train engine's flat-shard/overlap-pin placements
  (``runtime/zero/sharded_optimizer.py`` resolves through
  ``train_sharding`` instead of ad-hoc spec literals).

jaxlint JL011 treats the ``*_PARTITION_RULES`` dict literals below as
the canonical table: a PartitionSpec literal elsewhere that disagrees
with the registry rule for the same tree path is a finding, so spec
truth cannot fork per engine.

Named failure modes are real exceptions, not silent resharding:
``UnmatchedPathError`` (a leaf no rule matches, unless the registry was
built with ``replicate_unmatched=True``) and ``UnknownAxisError`` (a
rule names an axis the mesh does not define).
"""

import re

import numpy as np

import jax
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    create_mesh,
)


class ShardingRegistryError(ValueError):
    """Base class for registry failures (a ValueError: bad rule tables
    are configuration errors)."""


class UnmatchedPathError(ShardingRegistryError):
    """A param-tree path matched no rule and ``replicate_unmatched`` is
    off — the registry refuses to guess a placement."""


class UnknownAxisError(ShardingRegistryError):
    """A rule's PartitionSpec names a mesh axis the target mesh (or
    configured ``mesh_shape``) does not define."""


# ---------------------------------------------------------------------------
# Canonical rule tables.
#
# Keys are ordered regexes searched against '/'-joined tree paths; first
# match wins. jaxlint harvests these dict literals as the canonical
# spec registry (names ending in _PARTITION_RULES), so keep every
# project-wide placement here rather than inline at the use site.
# ---------------------------------------------------------------------------

# Serving/tensor-parallel rules for the GPT-2 scanned-layer tree
# (stacked leaves: kernels [L, in, out], biases [L, dim]).  Megatron
# split: column-parallel qkv/ff1 (output dim over `model`), row-parallel
# attn_out/ff2 (input dim over `model`), everything else replicated.
# The non-param `serving/*` paths are the engine's device buffers:
# the paged KV pool and its quant scales shard the heads dim, lane
# state uploads replicate. The buffer specs carry no trailing ``None``s:
# that is the form jit hands back on its outputs, and the jit fast-path
# cache compares specs literally — a pool created as (None, None, model,
# None, None) and returned as (None, None, model) would cost every decode
# program a second cache entry.
SERVING_PARTITION_RULES = {
    r"(qkv|ff1)/(kernel|kernel_q)$": PartitionSpec(None, None, MODEL_AXIS),
    r"(qkv|ff1)/(bias|scale)$": PartitionSpec(None, MODEL_AXIS),
    r"(attn_out|ff2)/(kernel|kernel_q)$": PartitionSpec(None, MODEL_AXIS, None),
    r"^serving/kv_pool$": PartitionSpec(None, None, MODEL_AXIS),
    r"^serving/kv_scale$": PartitionSpec(None, None, MODEL_AXIS),
    r"^serving/prefill_kv$": PartitionSpec(None, None, MODEL_AXIS),
    r"^serving/lane_state$": PartitionSpec(),
    r".*": PartitionSpec(),
}

# ZeRO train-engine placements: the 1/world flat master+grad shards
# split over `data`, the overlap-tap grad buckets and gathered params
# pin replicated, ZeRO-3 stacked leaves split their leading dim.
TRAIN_PARTITION_RULES = {
    r"^zero/flat_shard$": PartitionSpec(DATA_AXIS),
    r"^zero/grad_bucket$": PartitionSpec(),
    r"^zero/gathered$": PartitionSpec(),
    r"^zero3/stacked_leading$": PartitionSpec(DATA_AXIS),
}


def tree_path_str(path):
    """'/'-joined key path for a ``tree_map_with_path`` entry."""
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _spec_axes(spec):
    """Flat tuple of axis names a PartitionSpec mentions."""
    axes = []
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            axes.extend(entry)
        else:
            axes.append(entry)
    return tuple(axes)


class ShardingRegistry:
    """Ordered first-match-wins regex -> PartitionSpec rule table.

    ``rules`` is a dict (insertion-ordered) or iterable of
    ``(pattern, PartitionSpec)`` pairs. Patterns are ``re.search``-ed
    against '/'-joined tree paths. Scalar leaves are always replicated
    regardless of the matching rule (a 0-d array admits no partitioned
    dim). Unmatched paths raise :class:`UnmatchedPathError` unless
    ``replicate_unmatched`` is set.
    """

    def __init__(self, rules, replicate_unmatched=False, name="registry"):
        if isinstance(rules, dict):
            rules = rules.items()
        self.rules = []
        for pattern, spec in rules:
            if not isinstance(spec, PartitionSpec):
                spec = PartitionSpec(*spec)
            self.rules.append((pattern, re.compile(pattern), spec))
        self.replicate_unmatched = bool(replicate_unmatched)
        self.name = name

    # -- validation ---------------------------------------------------

    def axes(self):
        """All axis names any rule mentions."""
        out = []
        for _, _, spec in self.rules:
            for ax in _spec_axes(spec):
                if ax not in out:
                    out.append(ax)
        return tuple(out)

    def validate_axes(self, mesh_axes):
        """Raise :class:`UnknownAxisError` if any rule names an axis
        outside ``mesh_axes`` (an iterable of axis names or a Mesh)."""
        if hasattr(mesh_axes, "axis_names"):
            mesh_axes = mesh_axes.axis_names
        known = tuple(mesh_axes)
        for pattern, _, spec in self.rules:
            for ax in _spec_axes(spec):
                if ax not in known:
                    raise UnknownAxisError(
                        f"{self.name}: rule {pattern!r} names axis "
                        f"{ax!r} but the mesh defines only {known}"
                    )
        return self

    # -- resolution ---------------------------------------------------

    def spec_for(self, path, ndim=None):
        """First-match PartitionSpec for a '/'-joined tree path.

        ``ndim=0`` (scalar leaf) always resolves replicated. A spec
        longer than ``ndim`` is a rule/leaf rank mismatch and raises
        :class:`ShardingRegistryError`.
        """
        if ndim == 0:
            return PartitionSpec()
        for pattern, rx, spec in self.rules:
            if rx.search(path):
                if ndim is not None and len(spec) > ndim:
                    raise ShardingRegistryError(
                        f"{self.name}: rule {pattern!r} spec {spec} has "
                        f"{len(spec)} entries but leaf '{path}' has only "
                        f"{ndim} dims"
                    )
                return spec
        if self.replicate_unmatched:
            return PartitionSpec()
        raise UnmatchedPathError(
            f"{self.name}: no rule matches param-tree path '{path}' "
            f"(set replicate_unmatched=True to default to replication)"
        )

    def specs(self, tree):
        """Pytree of PartitionSpecs mirroring ``tree``."""
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: self.spec_for(
                tree_path_str(path), ndim=np.ndim(leaf)),
            tree,
        )

    def shardings(self, mesh, tree):
        """Pytree of NamedShardings for ``tree`` over ``mesh``."""
        self.validate_axes(mesh)
        return jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, spec), self.specs(tree),
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        )

    # -- placement ----------------------------------------------------

    def make_shard_fns(self, mesh, tree):
        """Pytree of per-leaf callables placing a leaf on the mesh per
        its matched rule (the EasyLM ``make_shard_fns`` shape)."""
        return jax.tree_util.tree_map(
            lambda sh: (lambda leaf, _sh=sh: jax.device_put(leaf, _sh)),
            self.shardings(mesh, tree),
            is_leaf=lambda x: isinstance(x, NamedSharding),
        )

    def make_gather_fns(self, mesh, tree):
        """Pytree of per-leaf callables gathering a leaf back to a
        fully-replicated array on the mesh (bitwise round-trip partner
        of :meth:`make_shard_fns`)."""
        replicated = NamedSharding(mesh, PartitionSpec())
        return jax.tree_util.tree_map(
            lambda _sh: (lambda leaf: jax.device_put(leaf, replicated)),
            self.shardings(mesh, tree),
            is_leaf=lambda x: isinstance(x, NamedSharding),
        )

    def shard(self, mesh, tree):
        """Place every leaf of ``tree`` per its matched rule."""
        return jax.tree_util.tree_map(
            lambda fn, leaf: fn(leaf), self.make_shard_fns(mesh, tree),
            tree)

    def gather(self, mesh, tree):
        """Gather every leaf of ``tree`` back to replicated."""
        return jax.tree_util.tree_map(
            lambda fn, leaf: fn(leaf), self.make_gather_fns(mesh, tree),
            tree)

    def table(self):
        """Aggregated ordered {pattern: PartitionSpec} view (what the
        jaxlint JL011 cross-check and the docs render)."""
        return {pattern: spec for pattern, _, spec in self.rules}


def match_partition_rules(rules, tree, replicate_unmatched=False):
    """Functional one-shot: pytree of PartitionSpecs for ``tree`` from
    ordered ``rules`` (the SNIPPETS ``match_partition_rules`` shape)."""
    return ShardingRegistry(
        rules, replicate_unmatched=replicate_unmatched).specs(tree)


# ---------------------------------------------------------------------------
# Mesh factory + the two canonical registries.
# ---------------------------------------------------------------------------

def normalize_mesh_shape(mesh_shape):
    """(data, model) ints from a 2-sequence or {axis: size} dict."""
    if mesh_shape is None:
        return 1, 1
    if isinstance(mesh_shape, dict):
        unknown = [k for k in mesh_shape if k not in (DATA_AXIS, MODEL_AXIS)]
        if unknown:
            raise UnknownAxisError(
                f"mesh_shape names unknown axes {unknown!r}; serving "
                f"meshes define ({DATA_AXIS!r}, {MODEL_AXIS!r})"
            )
        data = int(mesh_shape.get(DATA_AXIS, 1))
        model = int(mesh_shape.get(MODEL_AXIS, 1))
    else:
        shape = tuple(int(v) for v in mesh_shape)
        if len(shape) != 2:
            raise ShardingRegistryError(
                f"mesh_shape must be (data, model), got {mesh_shape!r}")
        data, model = shape
    if data < 1 or model < 1:
        raise ShardingRegistryError(
            f"mesh_shape sizes must be >= 1, got ({data}, {model})")
    return data, model


def create_serving_mesh(mesh_shape, devices=None):
    """('pipe','data','model') Mesh for a (data, model) shape over the
    first data*model devices, reusing ``parallel/mesh.py``'s factory so
    axis names/order stay the project-wide constants."""
    data, model = normalize_mesh_shape(mesh_shape)
    devices = list(devices if devices is not None else jax.devices())
    need = data * model
    if len(devices) < need:
        raise ShardingRegistryError(
            f"mesh_shape ({data}, {model}) needs {need} devices, "
            f"have {len(devices)}"
        )
    return create_mesh(data_parallel_size=data, model_parallel_size=model,
                       devices=devices[:need])


def serving_registry(extra_rules=None, replicate_unmatched=True):
    """The canonical serving-side registry. ``extra_rules`` (ordered
    (pattern, spec-elements) pairs, e.g. from ds_config
    ``parallel.partition_rules``) take precedence over the built-ins."""
    rules = list(extra_rules or [])
    rules += list(SERVING_PARTITION_RULES.items())
    return ShardingRegistry(rules, replicate_unmatched=replicate_unmatched,
                            name="serving_registry")


def train_registry():
    """The canonical train/ZeRO-side registry."""
    return ShardingRegistry(TRAIN_PARTITION_RULES, name="train_registry")


_TRAIN = None


def train_spec(path):
    """Registry-resolved PartitionSpec for a named train placement
    (e.g. 'zero/flat_shard')."""
    global _TRAIN
    if _TRAIN is None:
        _TRAIN = train_registry()
    return _TRAIN.spec_for(path)


def train_sharding(mesh, path):
    """NamedSharding for a named train placement over ``mesh``."""
    return NamedSharding(mesh, train_spec(path))


def serving_spec(path, registry=None):
    """Registry-resolved PartitionSpec for a named serving placement
    (e.g. 'serving/kv_pool')."""
    return (registry or serving_registry()).spec_for(path)


def serving_sharding(mesh, path, registry=None):
    """NamedSharding for a named serving placement over ``mesh``."""
    return NamedSharding(mesh, serving_spec(path, registry=registry))

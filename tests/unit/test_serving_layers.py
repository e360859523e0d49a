"""The layering of ``deepspeed_tpu/inference/serving/``, read off the source
(pure ``ast``: nothing here imports jax or the package).

The picture (``serving/family.py``): the loop (``engine.py``) knows no model;
it calls a ``ServingFamily`` through the contract; a family calls back only
public names of the loop; the jitted programs live with their family, under
the names the benchmark's trace readers look for."""

import ast
import glob
import os
import re

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SERVING = os.path.join(REPO_ROOT, "deepspeed_tpu", "inference", "serving")
FAMILY_FILES = sorted(glob.glob(os.path.join(SERVING, "families", "*.py")))


def _tree(path):
    with open(path, encoding="utf-8") as fh:
        return ast.parse(fh.read(), filename=path)


def _imported_modules(tree):
    """Every module a file imports, absolute or relative, as dotted text
    (``from ..generation import x`` gives ``..generation`` and
    ``..generation.x``)."""
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            out.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = "." * node.level + (node.module or "")
            out.add(base)
            out.update(f"{base}.{a.name}" for a in node.names)
    return out


def _imports_of(path, package, names):
    """Which modules among ``names`` of ``package`` (dotted) the file
    imports, absolutely (``import a.b.x``, ``from a.b import x``, ``from
    a.b.x import y``) or relative to its own package."""
    found = set()
    for module in _imported_modules(_tree(path)):
        if module.startswith("."):
            parts = module.lstrip(".").split(".")
        elif module.startswith(package + "."):
            parts = module[len(package) + 1:].split(".")
        else:
            continue
        found.update(parts[:1])
    return found & set(names)


def _dotted(node):
    """``a.b.c`` for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    return ".".join([node.id] + parts[::-1])


def test_the_loop_imports_no_model_code():
    """``engine.py`` reaches models, ``generation.py``, the kernel tier and
    quantization only through a family, and names no family."""
    tree = _tree(os.path.join(SERVING, "engine.py"))
    banned = ("models", "generation", "kernels", "quantization", "families")
    bad = sorted(m for m in _imported_modules(tree)
                 if set(m.strip(".").split(".")) & set(banned))
    assert bad == []


MODELS = os.path.join(REPO_ROOT, "deepspeed_tpu", "models")
SLOT_STATE_MODELS = ("kimi_linear", "nemotron_h", "laguna", "mimo_v2", "keye",
                     "ouro", "glm_dsa")


@pytest.mark.parametrize("module", SLOT_STATE_MODELS + ("paged_layers",))
def test_a_slot_state_model_imports_no_sibling(module):
    """The layers the seven decoders share are ``models/paged_layers.py``'s:
    a model's file imports it and no other model's, and it imports none of
    them, so a new family adds files under ``models/`` and edits none."""
    path = os.path.join(MODELS, module + ".py")
    others = set(SLOT_STATE_MODELS) - {module}
    assert _imports_of(path, "deepspeed_tpu.models", others) == set()
    if module != "paged_layers":
        assert _imports_of(path, "deepspeed_tpu.models",
                           ["paged_layers"]) == {"paged_layers"}


def test_a_familys_file_imports_no_other_familys():
    """What families share is ``families/slot_state.py``'s (and the
    contract's, ``family.py``): no family subclasses a sibling."""
    names = {os.path.basename(p)[:-3] for p in FAMILY_FILES}
    bad = {}
    for path in FAMILY_FILES:
        own = os.path.basename(path)[:-3]
        others = names - {own, "slot_state", "__init__"}
        got = _imports_of(
            path, "deepspeed_tpu.inference.serving.families", others)
        if got:
            bad[own] = got
    assert bad == {}


def test_there_are_families_to_hold_to_the_contract():
    names = {os.path.basename(p) for p in FAMILY_FILES}
    assert {"gpt2.py", "kimi_linear.py", "nemotron_h.py", "laguna.py",
            "mimo_v2.py", "keye.py", "ouro.py", "glm_dsa.py",
            "slot_state.py"} <= names, names


@pytest.mark.parametrize(
    "path", FAMILY_FILES + [os.path.join(SERVING, "family.py")],
    ids=os.path.basename)
def test_a_family_touches_no_private_name_of_the_loop(path):
    """The loop object a family is built for is ``loop`` (``self.loop``):
    no ``loop._x``, and no other object of the loop's reached through it
    by a private name (``loop.pool._x`` is the pool's own business and is
    not flagged; ``engine._x`` is, whatever the object is called)."""
    bad = []
    for node in ast.walk(_tree(path)):
        if not (isinstance(node, ast.Attribute) and node.attr.startswith("_")
                and not node.attr.startswith("__")):
            continue
        owner = _dotted(node.value) or ""
        if owner.split(".")[-1] in ("loop", "engine", "eng"):
            bad.append(f"{os.path.basename(path)}:{node.lineno} "
                       f"{owner}.{node.attr}")
    assert bad == []


def _model_config_types(tree):
    """(name, line, enclosing function) of each use of a ``*Config`` type
    that comes from ``deepspeed_tpu.models``."""
    aliases, types = set(), set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module and (
                node.module.startswith("deepspeed_tpu.models")):
            for a in node.names:
                (types if a.name.endswith("Config") else aliases).add(
                    a.asname or a.name)
    uses = []

    def visit(node, func):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            func = node.name
        if isinstance(node, ast.Name) and node.id in types:
            uses.append((node.id, node.lineno, func))
        if (isinstance(node, ast.Attribute) and node.attr.endswith("Config")
                and isinstance(node.value, ast.Name)
                and node.value.id in aliases):
            uses.append((node.attr, node.lineno, func))
        for child in ast.iter_child_nodes(node):
            visit(child, func)

    visit(tree, None)
    return uses


def test_a_model_configuration_is_known_by_type_to_family_for_alone():
    """And by field to its family: of the configuration it is handed, the
    loop reads only what every model has. ``replica.py`` is left out: its
    worker entry point builds the model it then hands to the engine, as any
    caller does."""
    reads = {node.attr
             for node in ast.walk(_tree(os.path.join(SERVING, "engine.py")))
             if isinstance(node, ast.Attribute)
             and (_dotted(node.value) or "").endswith("model_config")}
    assert reads <= {"max_position_embeddings", "vocab_size"}, reads
    found = {}
    for path in sorted(glob.glob(os.path.join(SERVING, "**", "*.py"),
                                 recursive=True)):
        rel = os.path.relpath(path, SERVING)
        if rel == "replica.py":
            continue
        uses = _model_config_types(_tree(path))
        if uses:
            found[rel] = {func for _, _, func in uses}
    assert found == {"family.py": {"family_for"}}, found


def _benchmark_program_names():
    """The jitted functions the benchmark's trace readers look for
    (``PROGRAM``/``PROGRAMS`` in ``benchmarks/metrics/*.py``, ``jit_<name>``
    in the trace)."""
    names = set()
    for path in glob.glob(os.path.join(REPO_ROOT, "benchmarks", "metrics",
                                       "*.py")):
        for node in _tree(path).body:
            if isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id in ("PROGRAM", "PROGRAMS")
                    for t in node.targets):
                for const in ast.walk(node.value):
                    if isinstance(const, ast.Constant):
                        names.add(re.sub(r"^jit_", "", const.value))
    return names


# where each serving program lives: a model's with its family, the page
# and slot programs with the pool
HOMES = {
    "_decode_step_jit": "families/gpt2.py",
    "_prefill_batch_jit": "families/gpt2.py",
    "_kimi_decode_step_jit": "families/kimi_linear.py",
    "_kimi_prefill_chunk_jit": "families/kimi_linear.py",
    "_nemotron_decode_step_jit": "families/nemotron_h.py",
    "_nemotron_prefill_chunk_jit": "families/nemotron_h.py",
    "_laguna_decode_step_jit": "families/laguna.py",
    "_laguna_prefill_chunk_jit": "families/laguna.py",
    "_mimo_decode_step_jit": "families/mimo_v2.py",
    "_mimo_prefill_chunk_jit": "families/mimo_v2.py",
    "_keye_decode_step_jit": "families/keye.py",
    "_keye_prefill_chunk_jit": "families/keye.py",
    "_ouro_decode_step_jit": "families/ouro.py",
    "_ouro_prefill_chunk_jit": "families/ouro.py",
    "_glm_decode_step_jit": "families/glm_dsa.py",
    "_glm_prefill_chunk_jit": "families/glm_dsa.py",
    "_install_pages": "kv_pool.py",
    "_zero_slot": "kv_pool.py",
}


def test_each_program_the_benchmark_reads_is_defined_once_in_its_home():
    wanted = _benchmark_program_names()
    assert wanted == set(HOMES), wanted ^ set(HOMES)
    defined = {}
    for path in glob.glob(os.path.join(SERVING, "**", "*.py"),
                          recursive=True):
        for node in ast.walk(_tree(path)):
            if isinstance(node, ast.FunctionDef) and node.name in wanted:
                defined.setdefault(node.name, []).append(
                    os.path.relpath(path, SERVING).replace(os.sep, "/"))
    assert defined == {name: [home] for name, home in HOMES.items()}


def test_every_family_program_and_decode_step_is_a_marked_hot_loop():
    """``tools/jaxlint`` finds the serving hot path by the
    ``# jaxlint: hot`` marker at the ``def`` (or the line above), not by a
    table of file names: a jitted program or a ``decode_step`` without it
    would go unlinted."""
    unmarked, seen = [], 0
    for path in FAMILY_FILES + [os.path.join(SERVING, "engine.py")]:
        with open(path, encoding="utf-8") as fh:
            lines = fh.read().split("\n")
        for node in ast.walk(_tree(path)):
            if not isinstance(node, ast.FunctionDef):
                continue
            jitted = any("jit" in ast.unparse(d) for d in node.decorator_list)
            if not (jitted or node.name in ("decode_step", "step")):
                continue
            seen += 1
            if not any("jaxlint: hot" in lines[at - 1]
                       for at in (node.lineno, node.lineno - 1)):
                unmarked.append(f"{os.path.basename(path)}:{node.name}")
    assert unmarked == [] and seen >= 13 + 1 + 2 + 2 + 2 + 3 + 2 + 1, (
        unmarked, seen)

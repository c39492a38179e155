"""The package's import graph, as a ratchet (ROADMAP D12).

One case a sub-package: an AST scan of every ``import`` in it, lazy ones
inside functions included. Two rules. Nothing under ``gaussiank_sgd_tpu/``
imports what measures it (``analysis``, or any module whose name begins
with ``bench``: the benchmark, a bench script, a bench library): the
program does not depend on its benchmark.
And a sub-package imports only along the arrows of ``ARROWS``; the three
back-edges that exist today are listed in ``KNOWN_BACK_EDGES`` by file, so
a fourth fails here and a repaired one has to be struck from the list.
"""

import ast
import os

import pytest

PKG = "gaussiank_sgd_tpu"
PKG_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), PKG)


def _measures(name):
    """What measures the program; the program imports none of it."""
    return name == "analysis" or name.startswith("bench")


# sub-package -> what it may import from the package (lower layers first;
# ``compile_cache`` and ``virtual_cpu`` are the root's leaf modules)
ARROWS = {
    "compressors": set(),
    "ops": {"compressors"},
    "parallel": {"compressors", "ops"},
    # the experts' grouped products are kernels of `ops/grouped_matmul.py`
    # (PR 41); `ops` imports nothing of `models`
    "models": {"ops"},
    "data": set(),
    "telemetry": set(),
    "policy": {"compressors", "parallel"},
    "training": {"compressors", "data", "models", "parallel", "policy",
                 "telemetry"},
    "service": {"telemetry", "training"},
    "lint": {"compressors", "parallel", "virtual_cpu"},
}

# debts, not design: (importing file, imported sub-package)
KNOWN_BACK_EDGES = {
    # the registry builds the Pallas selectors, and ops imports
    # compressors.base: a cycle
    ("compressors/registry.py", "ops"),
    # the fused kernel's wrapper asks the wire module for its layout
    ("ops/pallas_pack.py", "parallel"),
    # the decoder-only LM calls ring attention
    ("models/transformer_lm.py", "parallel"),
}


def _imports(path, rel):
    """(absolute dotted module, line, the names taken from it) of every
    import in ``path``."""
    with open(path, encoding="utf-8") as fh:
        tree = ast.parse(fh.read(), filename=path)
    # the package a relative import starts from
    here = [PKG] + rel.split("/")[:-1]
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield a.name, node.lineno, []
        elif isinstance(node, ast.ImportFrom):
            names = [a.name for a in node.names]
            if not node.level:
                yield node.module, node.lineno, names
                continue
            base = here[:len(here) - (node.level - 1)]
            if node.module:
                yield ".".join(base + [node.module]), node.lineno, names
            else:
                for name in names:
                    yield ".".join(base + [name]), node.lineno, []


def _scan(sub):
    for root, dirs, files in os.walk(os.path.join(PKG_DIR, sub)):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(root, name)
                rel = os.path.relpath(path, PKG_DIR).replace(os.sep, "/")
                for module, line, _ in _imports(path, rel):
                    yield rel, line, module.split(".")


@pytest.mark.parametrize("sub", sorted(ARROWS))
def test_a_subpackage_imports_only_along_the_arrows(sub):
    assert os.path.isdir(os.path.join(PKG_DIR, sub))
    measuring, off_the_arrows, back_edges = [], [], set()
    for rel, line, parts in _scan(sub):
        where = f"{rel}:{line} imports {'.'.join(parts)}"
        if any(_measures(name) for name in parts):
            measuring.append(where)
        if parts[0] != PKG or len(parts) < 2 or parts[1] == sub:
            continue
        if (rel, parts[1]) in KNOWN_BACK_EDGES:
            back_edges.add((rel, parts[1]))
        elif parts[1] not in ARROWS[sub]:
            off_the_arrows.append(where)
    assert not measuring, measuring
    assert not off_the_arrows, off_the_arrows
    # a known debt that was repaired leaves the list
    assert back_edges == {e for e in KNOWN_BACK_EDGES
                          if e[0].startswith(sub + "/")}


# ---- inside `models/`: blocks below, models above, and no private names
MODELS_DIR = os.path.join(PKG_DIR, "models")
MODEL_FILES = sorted(n for n in os.listdir(MODELS_DIR)
                     if n.endswith(".py") and n != "__init__.py")
BLOCK_FILES = sorted("blocks/" + n
                     for n in os.listdir(os.path.join(MODELS_DIR, "blocks"))
                     if n.endswith(".py"))
# a debt, not design: the seed's decoder-only LM takes `MLP` and
# `sinusoidal_positions` from the seed's transformer
KNOWN_MODEL_IMPORTS = {("transformer_lm.py", "transformer")}


@pytest.mark.parametrize("rel", MODEL_FILES + BLOCK_FILES)
def test_a_model_file_imports_blocks_and_never_another_model(rel):
    """Under `models/` a block imports blocks, a model file imports blocks
    (`__init__.py` alone imports the models), and a name that crosses a
    file is public: no `from ... import _name`."""
    models = {n[:-len(".py")] for n in MODEL_FILES}
    assert "mellum2" in models and "blocks/attention.py" in BLOCK_FILES
    imports_a_model, private, known = [], [], set()
    for module, line, names in _imports(os.path.join(MODELS_DIR, rel),
                                        "models/" + rel):
        parts = module.split(".")
        if parts[:2] != [PKG, "models"] or len(parts) < 3:
            continue
        target = "/".join(parts[2:])
        if (rel, target) in KNOWN_MODEL_IMPORTS:
            known.add((rel, target))
        elif parts[2] in models:
            imports_a_model.append(f"models/{rel}:{line} imports {target}")
        private += [f"models/{rel}:{line} takes {n} from {target}"
                    for n in names if n.startswith("_")]
    assert not imports_a_model, imports_a_model
    assert not private, private
    assert known == {e for e in KNOWN_MODEL_IMPORTS if e[0] == rel}

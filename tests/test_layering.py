"""The package's layering: every ``repro`` import points down.

One case per module under ``src/repro`` (package ``__init__`` files
included).  Each case imports the module, which catches an import left
dangling by a deletion, then parses its source and checks every
``repro.*`` import in it -- module level and inside functions alike --
against ``LAYERS``: an import may name a module of the importer's own
layer or of a lower one, never of a higher one.

An import names the module it names: ``from repro.data.sparse import x``
is an edge to ``data.sparse``, not to the ``repro.data`` package whose
``__init__`` Python also runs on the way.  A package ``__init__`` sits
in the layer of what it re-exports.
"""

import ast
import importlib
import pathlib

import pytest

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"

# Lowest first.  Names are relative to ``repro``; ``pkg.*`` covers the
# package's ``__init__`` and every module under it, and an exact name
# wins over a wildcard.  docs/architecture.md draws the same table.
LAYERS = (
    # L0: math, layouts, the mesh and the observability primitives
    ("obs.*", "core.*", "data.sparse", "data.lockfile", "sharding.rules",
     "roofline.*", "launch.compile_cache", "launch.mesh"),
    # L1: the Pallas kernels and the signature engine
    ("kernels.*",),
    # L2: the data pipeline over the kernels, the linear models, optimizers
    ("data", "data.pipeline", "data.preprocess", "data.sigshard",
     "data.synthetic", "models.linear", "optim.*"),
    # L3: the index and training
    ("index.*", "train.*", "core.lsh"),
    # L4: serving
    ("launch.serve", "launch.server"),
)

# Upward edges that are known debts, as (importer, imported) pairs.
# Every entry is a named debt in ROADMAP.md; the list starts empty.
ALLOWED_UPWARD = frozenset()


def _modules():
    out = []
    for path in sorted((SRC / "repro").rglob("*.py")):
        parts = path.relative_to(SRC).with_suffix("").parts
        if parts[-1] == "__init__":
            parts = parts[:-1]
        out.append((".".join(parts), path))
    return out


MODULES = _modules()
MODULE_NAMES = {name for name, _ in MODULES}


_EXACT = {name: level for level, names in enumerate(LAYERS)
          for name in names if not name.endswith(".*")}
_TREES = {name[:-2]: level for level, names in enumerate(LAYERS)
          for name in names if name.endswith(".*")}


def layer_of(module: str) -> int:
    rel = module.removeprefix("repro.")
    if rel in _EXACT:
        return _EXACT[rel]
    parts = rel.split(".")
    for n in range(len(parts), 0, -1):      # the deepest "pkg.*" first
        stem = ".".join(parts[:n])
        if stem in _TREES:
            return _TREES[stem]
    raise LookupError(f"{module} has no layer: place it in LAYERS")


def repro_imports(module: str, path: pathlib.Path):
    """Every ``repro`` module that ``module``'s source names."""
    is_pkg = path.name == "__init__.py"
    package = module if is_pkg else module.rpartition(".")[0]
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names
                        if a.name.split(".")[0] == "repro")
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                up = package.rsplit(".", node.level - 1)[0]
                base = f"{up}.{base}" if base else up
            if base.split(".")[0] != "repro":
                continue
            for alias in node.names:
                sub = f"{base}.{alias.name}"
                yield sub if sub in MODULE_NAMES else base


def test_layer_table_names_existing_modules():
    for name in [*_EXACT, *_TREES]:
        target = "repro." + name
        assert target in MODULE_NAMES or any(
            m.startswith(target + ".") for m in MODULE_NAMES), name


@pytest.mark.parametrize("module,path", MODULES,
                         ids=[name for name, _ in MODULES])
def test_module_imports_point_down(module, path):
    importlib.import_module(module)
    level = layer_of(module)
    upward = sorted(
        (target, layer_of(target))
        for target in set(repro_imports(module, path))
        if layer_of(target) > level
        and (module, target) not in ALLOWED_UPWARD)
    assert not upward, (
        f"{module} (L{level}) imports a higher layer: "
        + ", ".join(f"{t} (L{lv})" for t, lv in upward))

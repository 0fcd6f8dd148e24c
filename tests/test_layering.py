"""The import graph below ``serve/`` points down.

An AST walk over the checker-path packages, function bodies included:
nothing there imports ``jepsen_tpu.serve`` or ``jepsen_tpu.lint``, and
``obs/`` — the leaf the engine imports — imports none of the others.  The
shape ladder lives in ``engine/ladder.py`` so that none of them has to.
"""

import ast
import pathlib

import pytest

PKG = pathlib.Path(__file__).resolve().parents[1] / "jepsen_tpu"

#: the checker path: ``core.analyze`` down to the kernels
LAYERS = ["engine", "ops", "parallel", "checker", "obs", "elle", "elle_tpu",
          "models", "independent.py"]

UPWARD = ("jepsen_tpu.serve", "jepsen_tpu.lint", "jepsen_tpu.monitor")

#: the only upward imports left, each a named debt in ROADMAP.md
#: ("engine -> monitor.epochs", with D5)
EXCEPTIONS = {
    ("engine/stream.py", "jepsen_tpu.monitor.epochs"),
    ("elle_tpu/incremental.py", "jepsen_tpu.monitor.epochs"),
}


def _files(layer):
    root = PKG / layer
    return [root] if root.is_file() else sorted(root.rglob("*.py"))


def _imports(path, top=PKG.parent):
    """Every module a file imports, absolute, wherever the statement sits;
    ``from jepsen_tpu import serve`` counts as ``jepsen_tpu.serve``."""
    rel = path.relative_to(top).with_suffix("").parts
    package = rel[:-1]
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name
        elif isinstance(node, ast.ImportFrom):
            base = list(package[:len(package) - node.level + 1]) \
                if node.level else []
            if node.module:
                base.append(node.module)
            mod = ".".join(base)
            yield mod
            for alias in node.names:
                yield f"{mod}.{alias.name}"


def _under(mod, prefix):
    return mod == prefix or mod.startswith(prefix + ".")


def _violations(layer, banned):
    out = set()
    for path in _files(layer):
        rel = path.relative_to(PKG).as_posix()
        for mod in _imports(path):
            if any(_under(mod, b) for b in banned) \
                    and not any(rel == f and _under(mod, m)
                                for f, m in EXCEPTIONS):
                out.add((rel, mod))
    return sorted(out)


@pytest.mark.parametrize("layer", LAYERS)
def test_checker_path_imports_nothing_above_it(layer):
    assert _files(layer), layer
    assert _violations(layer, UPWARD) == []


def test_obs_is_a_leaf():
    others = [f"jepsen_tpu.{name.removesuffix('.py')}"
              for name in LAYERS if name != "obs"]
    assert _violations("obs", others) == []


def test_the_exceptions_still_exist():
    # an exception nobody needs any more is a debt paid: take it off the list
    for rel, mod in EXCEPTIONS:
        assert any(_under(m, mod) for m in _imports(PKG / rel)), (rel, mod)


def test_the_walk_sees_function_bodies_and_relative_imports(tmp_path):
    f = tmp_path / "jepsen_tpu" / "engine" / "x.py"
    f.parent.mkdir(parents=True)
    f.write_text("def f():\n    from jepsen_tpu.serve import buckets\n"
                 "    from .. import lint\n"
                 "    from ..monitor.epochs import a\n")
    assert {"jepsen_tpu.serve", "jepsen_tpu.lint",
            "jepsen_tpu.monitor.epochs"} <= set(_imports(f, tmp_path))

"""Which package may import which: the edges that must not exist.

Read from the sources with ``ast`` — nothing is imported or executed, so a
lazy import inside a function counts like one at the top of a module.  The
layers, lowest first: ``config`` / ``kernel_plans`` (leaves), ``ops``,
``models``, ``serving``, ``fleet``; ``telemetry`` beside them, below
``serving``; ``lint`` on top, reading all of them (its ``contracts`` and
``concurrency`` annotations are the one thing imported from it).
"""

from __future__ import annotations

import ast
from pathlib import Path

import pytest

PKG = Path(__file__).resolve().parent.parent / "raft_tpu"


def _imports(path: Path):
    """Absolute dotted names of everything ``path`` imports, with the line
    of each: ``from ..lint.budget import x`` in ``raft_tpu/ops/a.py`` gives
    ``raft_tpu.lint.budget.x``."""
    here = ("raft_tpu",) + path.relative_to(PKG).parts[:-1]
    for node in ast.walk(ast.parse(path.read_text(encoding="utf-8"))):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, node.lineno
        elif isinstance(node, ast.ImportFrom):
            base = list(here[:len(here) - node.level + 1]) if node.level \
                else []
            if node.module:
                base.append(node.module)
            for alias in node.names:
                yield ".".join(base + [alias.name]), node.lineno


def _sources(where: str):
    target = PKG / where
    return [target] if target.is_file() else sorted(target.rglob("*.py"))


@pytest.mark.parametrize("where,forbidden", [
    # the kernels, the model and the server decide for themselves; the
    # static analyzer reads what they decided (these three fail wherever a
    # kernel or the engine asks lint/budget.py for its plan or its grid)
    ("ops", "raft_tpu.lint.budget"),
    ("models", "raft_tpu.lint.budget"),
    ("serving", "raft_tpu.lint.budget"),
    # lower layers know nothing of the ones above them
    ("ops", "raft_tpu.models"),
    ("ops", "raft_tpu.serving"),
    ("models", "raft_tpu.serving"),
    ("telemetry", "raft_tpu.serving"),
    ("serving", "raft_tpu.fleet"),
    # the leaf every layer reads imports nothing of the package
    ("kernel_plans.py", "raft_tpu"),
], ids=lambda v: v.replace("raft_tpu.", ""))
def test_no_import_edge(where, forbidden):
    files = _sources(where)
    assert files, where
    found = [f"{path.relative_to(PKG.parent)}:{line} imports {name}"
             for path in files for name, line in _imports(path)
             if name == forbidden or name.startswith(forbidden + ".")]
    assert not found, "\n".join(found)


MOVED = ("LANE", "SUBLANE", "round_up", "VMEM_BYTES", "VMEM_CEILING_BYTES",
         "GRU_HALO", "GRU_TAPS", "CorrLevelPlan", "corr_level_plan",
         "corr_band", "GruRowPlan", "gru_row_plan",
         "gru_scoped_bytes", "gru_vmem_limit", "enumerate_warmup_grid",
         "resolved_policy", "Key")


def test_the_analyzer_defines_no_plan_and_no_grid():
    """One name, one place: ``lint/budget.py`` imports the kernels' plans
    and the server's grid to read them, and neither defines nor rebinds
    any of those names."""
    tree = ast.parse((PKG / "lint" / "budget.py").read_text(encoding="utf-8"))
    bound = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            bound.add(node.name)
        elif isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            bound |= {n.id for t in targets for n in ast.walk(t)
                      if isinstance(n, ast.Name)}
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            bound |= {a.asname for a in node.names if a.asname}
    assert not bound & set(MOVED), sorted(bound & set(MOVED))

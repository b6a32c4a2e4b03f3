"""Import direction: the lower packages never import the upper ones.

``utils``, ``ops``, ``data``, ``models``, ``parallel`` and ``obs`` are what
the trainer, the serving stack, the CLIs' tools and ``eval`` are built
from; an import the other way makes a model know the engine that runs it.
Read from the source with ``ast`` (no module is executed), every import
form, function-local ones included.
"""

import ast
import pathlib

import pytest

PACKAGE = pathlib.Path(__file__).resolve().parent.parent / "tpu_trainer"
LOWER = ("utils", "ops", "data", "models", "parallel", "obs")
UPPER = ("serving", "training", "tools", "eval")
# The one arrow still pointing up, by (file, imported module). It is a
# debt, not a licence: ROADMAP D12 (`utils/checkpoint.py` is the trainer's
# checkpoint manager and takes its `TrainingConfig`).
KNOWN_DEBT = {("utils/checkpoint.py", "tpu_trainer.training.config")}


def imported_modules(path):
    """Absolute dotted names of everything ``path`` imports."""
    tree = ast.parse(path.read_text(), filename=str(path))
    package = ("tpu_trainer",) + path.relative_to(PACKAGE).parts[:-1]
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name
        elif isinstance(node, ast.ImportFrom):
            base = ".".join(package[:len(package) - node.level + 1]
                            if node.level else ())
            module = ".".join(p for p in (base, node.module) if p)
            yield module
            # `from tpu_trainer import serving` names a package too.
            for alias in node.names:
                yield f"{module}.{alias.name}"


@pytest.mark.fast
@pytest.mark.parametrize("package", LOWER)
def test_lower_package_imports_no_upper_one(package):
    files = sorted((PACKAGE / package).rglob("*.py"))
    assert files, f"no sources under tpu_trainer/{package}"
    upward = set()
    for path in files:
        rel = path.relative_to(PACKAGE).as_posix()
        for module in imported_modules(path):
            parts = module.split(".")
            if (parts[0] == "tpu_trainer" and len(parts) > 1
                    and parts[1] in UPPER
                    and (rel, ".".join(parts[:3])) not in KNOWN_DEBT):
                upward.add(f"{rel} imports {module}")
    assert not upward, "\n".join(sorted(upward))

"""The commands the documents quote exist.

Every session starts from ``README.md`` and the verify skill; a command
there that names a script or module no longer in the tree sends it after
code that is gone (for six PRs the quick start opened with a benchmark
script that the driver no longer ran).
"""

import importlib.util
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
# `python[3] <path>.py` or `python[3] -m <module>`; `-c` and other options
# name nothing to resolve.
COMMAND = re.compile(r"\bpython3?\s+(?:(-m)\s+)?([\w./-]+)")


def _module_found(name):
    try:
        return importlib.util.find_spec(name) is not None
    except ModuleNotFoundError:  # a parent package is missing
        return False


@pytest.mark.fast
@pytest.mark.parametrize("document", [
    "README.md", ".claude/skills/verify/SKILL.md", "perf/README.md"])
def test_quoted_commands_resolve(document):
    text = (REPO / document).read_text()
    quoted = {(m.group(1), m.group(2).rstrip("."))
              for m in COMMAND.finditer(text)}
    modules = {name for flag, name in quoted if flag}
    scripts = {name for flag, name in quoted
               if not flag and name.endswith(".py")}
    assert modules or scripts, f"{document} quotes no command"
    missing = sorted(
        [f"python -m {m}" for m in modules if not _module_found(m)]
        + [f"python {s}" for s in scripts if not (REPO / s).is_file()])
    assert not missing, f"{document} quotes what is not there: {missing}"

"""A plain reference lives once: benchmarks/reference/<name>.py, which
decides a cell's `correct` on the chip and which the tier-1 tests load
through conftest.load_reference.  It is independent of the program it
checks: it imports nothing of it."""

import ast
import functools
import glob
import json
import os

import pytest

from conftest import load_reference, reference_path

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# another commit's tree unpacked for a comparison, not this one's
NOT_THE_TREE = {".git", "_parent", "_scratch", "_archive_check",
                "chiprun_out"}

CONFIGS = sorted(
    os.path.basename(path)[:-len(".json")] for path in glob.glob(
        os.path.join(ROOT, "benchmarks", "configs", "*.json")))
REFERENCES = sorted(
    os.path.basename(path)[:-len(".py")] for path in glob.glob(
        os.path.join(ROOT, "benchmarks", "reference", "*.py")))


@functools.lru_cache(maxsize=None)
def _python_files():
    found = []
    for top, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in NOT_THE_TREE
                   and d != "__pycache__"]
        found += [os.path.join(top, name) for name in files
                  if name.endswith(".py")]
    return tuple(found)


def test_every_declared_configuration_is_a_case():
    """The cases below are globbed: the benchmark's declared
    configurations are all among them."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = json.load(f)["configs"]
    assert declared and REFERENCES
    assert {os.path.basename(config["file"]) for config in declared} \
        <= {name + ".json" for name in CONFIGS}


@pytest.mark.parametrize("config", CONFIGS)
def test_config_reference_is_kept_once(config):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           config + ".json")) as f:
        name = json.load(f)["reference"]
    path = reference_path(name)
    ref = load_reference(name)
    assert callable(ref.read_params) and callable(ref.loss)
    with open(path, "rb") as f:
        text = f.read()
    copies = [other for other in _python_files()
              if not os.path.samefile(other, path)
              and os.path.getsize(other) == len(text)
              and open(other, "rb").read() == text]
    assert not copies, copies


@pytest.mark.parametrize("reference", REFERENCES)
def test_reference_imports_nothing_of_the_program(reference):
    path = reference_path(reference)
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            # a relative import reaches into benchmarks/ whatever it names
            imported.add("." * node.level + (node.module or ""))
    ours = sorted(name for name in imported if name.startswith(".")
                  or name.split(".")[0] in ("paddle_tpu", "benchmarks",
                                            "tools"))
    assert not ours, ours

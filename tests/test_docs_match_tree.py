"""The documents a new owner reads first say what the tree holds: the
README names every cell of ``BENCHMARK.json``, and a path that a document
puts in backticks exists."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]


def read(name):
    with open(os.path.join(ROOT, name)) as f:
        return f.read()


@pytest.mark.parametrize("cell", CELLS)
def test_the_readme_names_the_cell(cell):
    assert f"`{cell}`" in read("README.md")


@pytest.mark.parametrize("doc", ["README.md", "docs/DESIGN.md", "PARITY.md"])
def test_a_backticked_path_exists(doc):
    """Every backticked ``top/level/path.py|.json|.md`` whose first part
    is a name at the root of the repository (a path inside a package,
    such as ``ops/kda.py``, is not judged; nor is run-time output)."""
    top = {
        n for n in os.listdir(ROOT)
        if not n.startswith(".") and n != "chiprun_out"
    }
    paths = {
        p for p in re.findall(r"`([\w./-]+\.(?:py|json|md))`", read(doc))
        if p.split("/")[0] in top
    }
    assert len(paths) > 5
    missing = sorted(p for p in paths if not os.path.exists(
        os.path.join(ROOT, p)
    ))
    assert not missing, missing

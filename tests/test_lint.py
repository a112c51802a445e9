"""Static-hygiene tier — the testing/test_flake8.py analogue (SURVEY.md
§4 tier 3). No flake8 in the image, so the checks are stdlib: every
module compiles, no debugger hooks or conflict markers ship, public
modules carry docstrings. tools/ and examples/ ride the same gates
(syntax/debugger/marker only — round tooling may be terse), so a torn
watcher script or manifest can't silently rot between rounds."""

import ast
import os
import pathlib

import pytest

pytestmark = pytest.mark.lint

REPO = pathlib.Path(__file__).resolve().parent.parent
PACKAGE = REPO / "kubeflow_tpu"

PY_FILES = sorted(
    p for p in PACKAGE.rglob("*.py")
    if "__pycache__" not in p.parts
) + [REPO / "bench.py", REPO / "__graft_entry__.py", REPO / "chip_smoke.py"]

# the test corpus and round tooling are lint-gated for the
# syntax/marker/debugger checks (not the docstring rule: helpers and
# one-off sweep scripts may be terse)
TEST_FILES = sorted(
    p for p in (REPO / "tests").rglob("*.py")
    if "__pycache__" not in p.parts
)
TOOL_FILES = sorted(
    p for p in (REPO / "tools").rglob("*.py")
    if "__pycache__" not in p.parts
)
EXAMPLE_FILES = sorted(
    p for pat in ("*.yaml", "*.yml")
    for p in (REPO / "examples").rglob(pat)
)


@pytest.mark.parametrize("path", PY_FILES + TEST_FILES + TOOL_FILES,
                         ids=lambda p: str(p.relative_to(REPO)))
def test_module_is_clean(path):
    """Syntax / debugger-hook / conflict-marker gates, delegated to the
    hygiene pass (kubeflow_tpu/analysis/hygiene.py) so pytest and
    tools/lint_all.sh enforce one implementation, not two drifting ones."""
    from kubeflow_tpu.analysis import hygiene

    findings = hygiene.check_py(str(path), path.read_text())
    assert not findings, "\n".join(f.render() for f in findings)


@pytest.mark.parametrize(
    "path",
    [p for p in PY_FILES if p.name != "__main__.py"],
    ids=lambda p: str(p.relative_to(REPO)),
)
def test_module_has_docstring(path):
    tree = ast.parse(path.read_text())
    assert ast.get_docstring(tree), f"{path}: missing module docstring"


@pytest.mark.parametrize("path", EXAMPLE_FILES,
                         ids=lambda p: str(p.relative_to(REPO)))
def test_example_manifest_is_clean(path):
    """examples/ manifests: parse as YAML, ship no conflict markers
    (the hygiene pass's yaml gate, enforced from pytest too)."""
    from kubeflow_tpu.analysis import hygiene

    src = path.read_text()
    findings = hygiene.check_yaml(str(path), src)
    assert not findings, "\n".join(f.render() for f in findings)
    assert src.strip(), f"{path}: empty manifest"


def test_no_reference_tree_imports():
    """The build must be standalone: nothing may import from or open
    /root/reference (the read-only upstream)."""
    for p in PY_FILES:
        assert "/root/reference" not in p.read_text(), p

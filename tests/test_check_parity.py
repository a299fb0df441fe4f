"""tools/check_parity.py's reference resolver: every file, test and
module a document names is in the tree, whatever else this checkout
holds. (The surface checks run whole, once, in the slow
tests/test_examples.py::test_parity_doc_references_resolve.)"""

import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

sys.path.insert(0, str(REPO))

from tools import check_parity  # noqa: E402


@pytest.mark.parametrize(
    "doc", check_parity.documents(),
    ids=lambda doc: str(doc.relative_to(REPO)))
def test_document_references_resolve(doc):
    """One case a document, so a failure names the document."""
    assert check_parity.dangling_references(doc) == []


@pytest.fixture()
def tree(tmp_path, monkeypatch):
    """A small tree of its own with one run output in it."""
    for rel in ("tools/kept.py", "tests/test_kept.py", "docs/a.md",
                "horovod_tpu/__init__.py", "horovod_tpu/pkg/__init__.py",
                "horovod_tpu/bare/mod.py", "results/boxes/box.json",
                "tools/__pycache__/stale.py"):
        (tmp_path / rel).parent.mkdir(parents=True, exist_ok=True)
        (tmp_path / rel).write_text("")
    (tmp_path / "tests/test_kept.py").write_text("def test_one(): pass\n")
    (tmp_path / "horovod_tpu/pkg/__init__.py").write_text(
        "# run is only a word of this comment\n"
        "from .x import (made,\n                aliased as other)\n"
        "def defined(): pass\n")
    (tmp_path / ".gitignore").write_text(
        "__pycache__/\n# a run's boxes\nresults/boxes/\n")
    monkeypatch.setattr(check_parity, "REPO", tmp_path)
    check_parity._tree_names.cache_clear()
    check_parity._ignored_patterns.cache_clear()
    yield tmp_path
    check_parity._tree_names.cache_clear()
    check_parity._ignored_patterns.cache_clear()


def _dangling(tree, text):
    doc = tree / "docs" / "a.md"
    doc.write_text(text)
    return [m.split(": ", 1)[1] for m in
            check_parity.dangling_references(doc)]


def test_resolver_finds_what_is_there_and_names_what_is_not(tree):
    assert _dangling(tree, "`tools/kept.py:12`, `kept.py`, test_kept, "
                     "test_one, `bare/mod.py`, run tools/kept.py\n") == []
    assert _dangling(tree, "`tools/gone.py`, `gone.md`, test_gone and "
                     "python tools/gone_too.py --flag\n") == [
        "path: gone.md", "path: tools/gone.py", "path: tools/gone_too.py",
        "test: test_gone"]


def test_a_run_output_neither_resolves_nor_dangles(tree):
    """The answer is the same with and without the ignored directory,
    and a file that exists only under one backs no bare name."""
    text = "default `results/boxes`, `stale.py`, `box.json`\n"
    with_boxes = _dangling(tree, text)
    (tree / "results/boxes/box.json").unlink()
    (tree / "results/boxes").rmdir()
    check_parity._tree_names.cache_clear()
    assert with_boxes == _dangling(tree, text) == [
        "path: box.json", "path: stale.py"]


def test_dotted_name_is_a_module_or_a_name_the_package_binds(tree):
    assert _dangling(tree, "`horovod_tpu.pkg.made` `horovod_tpu.pkg.other` "
                     "`horovod_tpu.pkg.defined` `horovod_tpu.bare.mod`") == []
    # `run` is a word of a comment, `aliased` was renamed on import, and
    # `bare` has no __init__.py to bind anything.
    assert _dangling(tree, "`horovod_tpu.pkg.run` `horovod_tpu.pkg.aliased` "
                     "`horovod_tpu.bare.nothing`") == [
        "module: horovod_tpu.bare.nothing", "module: horovod_tpu.pkg.aliased",
        "module: horovod_tpu.pkg.run"]

"""Docs stay true: every relative markdown link under docs/ resolves to a
real file, and the worked examples' code blocks execute as doctests
(the worked example cannot rot). CI runs this file as the docs job."""
import doctest
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
DOCS = REPO / "docs"

# [text](target) — inline markdown links
_LINK_RE = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
_CODE_BLOCK_RE = re.compile(r"```python\n(.*?)```", re.S)


def _markdown_files():
    return sorted(DOCS.glob("*.md"))


def test_docs_directory_has_the_site():
    names = {p.name for p in _markdown_files()}
    assert {"index.md", "scheduling.md", "cluster.md", "perfmodel.md",
            "serving.md", "autoscaling.md", "offloading.md",
            "hardware.md"} <= names


@pytest.mark.parametrize("md", _markdown_files(), ids=lambda p: p.name)
def test_relative_links_resolve(md):
    text = md.read_text(encoding="utf-8")
    # don't treat links inside fenced code blocks as navigation
    text = re.sub(r"```.*?```", "", text, flags=re.S)
    broken = []
    for target in _LINK_RE.findall(text):
        if target.startswith(("http://", "https://", "mailto:", "#")):
            continue
        path = target.split("#", 1)[0]
        if not path:
            continue
        if not (md.parent / path).exists():
            broken.append(target)
    assert not broken, f"{md.name}: broken relative link(s) {broken}"


@pytest.mark.parametrize("name", ["scheduling.md", "cluster.md",
                                  "autoscaling.md", "offloading.md",
                                  "hardware.md", "serving.md"])
def test_worked_examples_execute(name, monkeypatch):
    monkeypatch.chdir(REPO)   # examples use repo-relative fixture paths
    text = (DOCS / name).read_text(encoding="utf-8")
    blocks = [b for b in _CODE_BLOCK_RE.findall(text) if ">>>" in b]
    assert blocks, f"{name} must carry runnable >>> examples"
    parser = doctest.DocTestParser()
    runner = doctest.DocTestRunner(
        optionflags=doctest.NORMALIZE_WHITESPACE | doctest.ELLIPSIS)
    globs = {}   # blocks share state, like one top-to-bottom session
    for i, block in enumerate(blocks):
        test = parser.get_doctest(block, globs, f"{name}[{i}]",
                                  f"docs/{name}", 0)
        runner.run(test, clear_globs=False)
        globs = test.globs
    assert runner.failures == 0, (
        f"{runner.failures} doctest failure(s) in docs/{name}")

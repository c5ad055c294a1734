"""README.md and docs/*.md name no repository path that does not exist.

Checked, inside back-quotes and fenced blocks: every path under one of
the repository's directories, every bare name that starts with a
capital and ends ``.md`` / ``.json`` / ``.jsonl`` (the root's records),
and every bare ``bench*.py`` / ``bench*.jsonl``.  Other bare names
(``pages.py``, ``meta.json``) are files of some directory the sentence
names and are left alone."""
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
DOCS = ["README.md"] + sorted(p.relative_to(ROOT).as_posix() for p in (ROOT / "docs").glob("*.md"))
DIRS = ("deepspeed_tpu", "tools", "tests", "benchmark", "docs", "bin", "examples", "csrc")

_CODE = re.compile(r"```.*?```|`[^`\n]+`", re.S)
_TOKEN = re.compile(r"[\w./{},<>*\[\]-]+")
_BARE = re.compile(r"(?:[A-Z][\w.-]*\.(?:md|jsonl?)|bench[\w-]*\.(?:py|jsonl))$")
_BRACES = re.compile(r"\{([^{}]*)\}")


def _expand(token):
    """``tools/{a,b}_chaos.py`` -> both paths."""
    group = _BRACES.search(token)
    if group is None:
        return [token]
    return [path for pick in group.group(1).split(",")
            for path in _expand(token[:group.start()] + pick + token[group.end():])]


def named_paths(text):
    for code in _CODE.findall(text):
        for token in _TOKEN.findall(code):
            token = token.split("::")[0].rstrip(".,")
            if any(c in token for c in "<>*[]") or "..." in token:
                continue  # a pattern or a placeholder, not one path
            for path in _expand(token):
                path = path.strip("{},")
                if "/" in path.rstrip("/"):
                    if path.split("/")[0] in DIRS:
                        yield path
                elif _BARE.match(path):
                    yield path


@pytest.mark.parametrize("doc", DOCS)
def test_named_paths_exist(doc):
    text = (ROOT / doc).read_text()
    # `path:12` and `path:12-40` name lines of the path
    text = re.sub(r"(\.\w+):\d+(?:-\d+)?", r"\1", text)
    missing = sorted({p for p in named_paths(text) if not (ROOT / p).exists()})
    assert not missing, f"{doc} names paths that do not exist: {missing}"

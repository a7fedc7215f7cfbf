"""README truth (ISSUE 32): what the README names exists.

Three tests, none parametrised by what it finds: every repo path in
backticks exists, every ``bigdl-tpu <command>`` it spells is a command
of the launcher, and every "PERF.md §N" is a section PERF.md has."""

import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DIRS = ("bigdl_tpu/", "benchmark/", "scripts/", "tests/")
_ROOT_FILE = re.compile(r"^\w[\w.-]*\.(py|json|jsonl|md)$")
_FENCE = re.compile(r"^```[^\n]*\n(.*?)^```", re.S | re.M)


def _readme() -> str:
    with open(os.path.join(REPO, "README.md")) as f:
        return f.read()


def _code(text: str):
    """(fenced blocks, inline code spans) of a markdown text."""
    return _FENCE.findall(text), re.findall(r"`([^`\n]+)`",
                                            _FENCE.sub("", text))


def _repo_path(span: str) -> "str | None":
    """The repo-relative path a code span names, or None: one word that
    starts with a source directory, or a bare root ``*.py`` / ``*.json``
    / ``*.jsonl`` / ``*.md`` name."""
    word = span.strip()
    if word.startswith("./"):
        word = word[2:]
    if not re.fullmatch(r"[\w./-]+", word):
        return None  # a command line, a placeholder, a glob
    if word.startswith(_DIRS) or _ROOT_FILE.match(word):
        return word
    return None


def test_readme_paths_exist():
    blocks, spans = _code(_readme())
    words = list(spans)
    for block in blocks:  # in a command, the words that are repo paths
        words += [w for w in block.split()
                  if w.lstrip("./").startswith(_DIRS)]
    paths = sorted({p for p in map(_repo_path, words) if p})
    assert len(paths) > 20, paths  # the pattern still finds the README's
    missing = [p for p in paths
               if not os.path.exists(os.path.join(REPO, p))]
    assert not missing, f"README.md names what is not there: {missing}"


def test_readme_commands_are_the_launchers():
    from bigdl_tpu.cli.main import _COMMANDS

    blocks, spans = _code(_readme())
    named = set()
    for code in blocks + spans:
        named.update(re.findall(r"\bbigdl-tpu[ \t]+([a-z][\w-]*)", code))
    assert {"explain", "serve", "lint"} <= named, named
    unknown = sorted(named - set(_COMMANDS))
    assert not unknown, f"README.md spells unknown commands: {unknown}"


def test_readme_perf_sections_exist():
    with open(os.path.join(REPO, "PERF.md")) as f:
        have = set(re.findall(r"^## (\d+)\. ", f.read(), re.M))
    cited = re.findall(r"PERF\.md\s+§\s*(\d+(?:\.\d+)*)", _readme())
    assert cited and have, (cited, have)
    dead = sorted({c for c in cited if c not in have})
    assert not dead, (f"README.md cites PERF.md sections {dead}; "
                      f"it has {sorted(have)}")

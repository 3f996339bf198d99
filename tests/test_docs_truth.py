"""The documents a newcomer reads first say what is so: the README's paths
exist, it names the benchmark that the ledger reads, the console scripts
that `pyproject.toml` promises resolve, and no tool lies about unnamed."""

import glob
import importlib
import json
import os
import re
import tomllib

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return f.read()


with open(os.path.join(REPO, "pyproject.toml"), "rb") as _f:
    SCRIPTS = tomllib.load(_f)["project"]["scripts"]


def _readme_paths():
    """Backticked tokens of the README's prose that read as paths of this
    repository.  Fenced blocks are commands, not paths; in the layout table
    only the last column is this repository's (the first is the
    reference's); `a/b.py::name` and `a/b.name` name something in `a/b.py`."""
    text = re.sub(r"```.*?```", "", _read("README.md"), flags=re.S)
    lines = [line.rstrip("|").rsplit("|", 1)[-1] if line.startswith("|")
             else line for line in text.splitlines()]
    for token in re.findall(r"`([^`\n]+)`", "\n".join(lines)):
        token = token.split("::")[0].split(" ")[0]
        if not re.fullmatch(r"[\w./-]+", token) or "..." in token:
            continue
        if token.endswith("/") or re.search(
                r"\.(py|md|json|jsonl|toml|sh|cc)$", token):
            yield token
        elif "/" in token:
            yield re.sub(r"\.\w+$", ".py", token)


def test_readme_paths_exist():
    paths = sorted(set(_readme_paths()))
    assert len(paths) > 50
    # the layout table writes the package's modules without `bigdl_tpu/`
    missing = [p for p in paths if not any(
        os.path.exists(os.path.join(REPO, base, p))
        for base in ("", "bigdl_tpu"))]
    assert missing == []


def test_readme_names_the_benchmark():
    readme = _read("README.md")
    declared = json.loads(_read("BENCHMARK.json"))
    assert " ".join(declared["command"]) in readme
    for cell in declared["workloads"]:
        assert f"`{cell['name']}`" in readme
    for name in ("BENCHMARK.json", "PERF.md", "PERF_LEDGER.jsonl"):
        assert f"`{name}`" in readme
    assert not re.search(r"(?<![\w/])bench\.py", readme)


@pytest.mark.parametrize("script", sorted(SCRIPTS))
def test_console_scripts_resolve(script):
    module, _, attr = SCRIPTS[script].partition(":")
    assert callable(getattr(importlib.import_module(module), attr))


def test_every_tool_is_named_by_a_test_or_a_document():
    """A tool that no test runs and no document mentions is a tool nobody
    can know to run, or to keep working."""
    readers = (glob.glob(os.path.join(REPO, "tests", "**", "*.py"),
                         recursive=True)
               + glob.glob(os.path.join(REPO, "docs", "*.md"))
               + [os.path.join(REPO, *p) for p in (
                   ("README.md",), ("PERF.md",), ("benchmark", "README.md"),
                   (".claude", "skills", "verify", "SKILL.md"))])
    said = "\n".join(open(p).read() for p in readers
                     if os.path.abspath(p) != os.path.abspath(__file__))
    tools = [p for d in ("tools", os.path.join("bigdl_tpu", "tools"))
             for p in sorted(os.listdir(os.path.join(REPO, d)))
             if p.endswith((".py", ".sh")) and p != "__init__.py"]
    assert len(tools) > 25
    unnamed = [t for t in tools
               if not re.search(rf"\b{re.escape(t.rsplit('.', 1)[0])}\b", said)]
    assert unnamed == []

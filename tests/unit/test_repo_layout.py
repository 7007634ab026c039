"""The operator surface names files that exist (PR 28).

The Makefile's recipes and the operator documents are what a reader runs
and follows; PR 28 deleted a second measuring apparatus (a script at the
repo root and its records) that both still pointed at.  These tests hold
the surface to the tree: a recipe or a document that names a file the
tree no longer has fails here, and so does a committed record at the repo
root other than the three the benchmark owns.  Text only; nothing is run
or timed.
"""

import fnmatch
import importlib.util
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PACKAGE = "batchai_retinanet_horovod_coco_tpu"

# Where a run writes: a path under one of these is an output, not a file
# of the tree.
OUTPUT_DIRS = ("/tmp/", "/mnt/", "/data/", "artifacts/obs/", "chiprun_out/", ".bench_out/", "export/")
# Files a run writes into its own output directory, which the documents
# name without one.  Each is a string literal of the package (checked
# below), so a name the code stops writing leaves this set too.
RUN_OUTPUTS = {"trace.json", "PERF_REPORT.json", "NUMERICS_DUMP.json", "FLEET_METRICS.json"}


def _makefile_recipes() -> list[str]:
    """The Makefile's recipe lines, continuations joined and ``$(NAME)``
    expanded from the file's own ``NAME = value`` / ``NAME ?= value``."""
    with open(os.path.join(ROOT, "Makefile")) as f:
        text = f.read().replace("\\\n", " ")
    variables = dict(re.findall(r"^([A-Z_]+)\s*\??=\s*(.*)$", text, re.M))
    recipes = [line[1:] for line in text.splitlines() if line.startswith("\t")]
    return [re.sub(r"\$\((\w+)\)", lambda m: variables.get(m.group(1), m.group(0)), r) for r in recipes]


def test_makefile_recipes_name_files_that_exist():
    recipes = _makefile_recipes()
    assert len(recipes) >= 20
    scripts = {p for r in recipes for p in re.findall(r"(?<![\w/.])((?:[\w.-]+/)*[\w-]+\.py)\b", r)}
    modules = {m for r in recipes for m in re.findall(r"python3? -m ([\w.]+)", r)}
    assert "train.py" in scripts and f"{PACKAGE}.analysis" in modules  # the patterns still bite
    missing = sorted(p for p in scripts if not os.path.isfile(os.path.join(ROOT, p)))
    assert not missing, f"Makefile recipes run files the tree does not have: {missing}"
    unresolved = sorted(m for m in modules if importlib.util.find_spec(m) is None)
    assert not unresolved, f"Makefile recipes run modules that do not resolve: {unresolved}"


def _tree_basenames() -> set[str]:
    names = set()
    for top in (PACKAGE, "benchmark", "scripts", "tests", "artifacts"):
        for _, _, files in os.walk(os.path.join(ROOT, top)):
            names.update(files)
    return names | set(os.listdir(ROOT))


def _named_files(markdown: str) -> set[str]:
    """Backticked tokens that name a ``.py``, ``.json`` or ``.md`` file:
    ``path/file.py``, ``file.py::function``, ``file.py:120`` all count as
    the file; a token with a wildcard or a placeholder does not."""
    out = set()
    for span in re.findall(r"`([^`\n]+)`", markdown):
        for word in span.split():
            word = re.sub(r":\d+(-\d+)?$", "", word.strip("(),;:").split("::")[0])
            if re.search(r"\.(py|json|md)$", word) and not re.search(r"[*<>{}$…=]|\.\.\.", word):
                out.add(word)
    return out


@pytest.mark.parametrize("document", ["README.md", "RUNBOOK.md"])
def test_operator_docs_name_files_that_exist(document):
    with open(os.path.join(ROOT, document)) as f:
        named = _named_files(f.read())
    assert len(named) >= 20
    basenames = _tree_basenames()
    missing = []
    for word in sorted(named):
        if word in RUN_OUTPUTS or any(d in word for d in OUTPUT_DIRS):
            continue
        if "/" in word:
            found = any(os.path.exists(os.path.join(ROOT, base, word)) for base in ("", PACKAGE, "tests"))
        else:
            found = word in basenames
        if not found:
            missing.append(word)
    assert not missing, f"{document} names files the tree does not have: {missing}"


def test_run_outputs_are_names_the_package_writes():
    literals = set()
    for folder, _, files in os.walk(os.path.join(ROOT, PACKAGE)):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as f:
                    literals.update(re.findall(r'"([\w.]+\.json)"', f.read()))
    assert RUN_OUTPUTS <= literals, sorted(RUN_OUTPUTS - literals)


def test_no_committed_record_outside_the_ledger():
    """Numbers live in ``PERF_LEDGER.jsonl`` (the driver's) and ``PERF.md``;
    ``BENCHMARK.json`` and ``BASELINE.json`` declare, they do not record."""
    records = [
        name
        for name in os.listdir(ROOT)
        if name not in ("BENCHMARK.json", "BASELINE.json")
        and any(fnmatch.fnmatch(name, pat) for pat in ("*BENCH*.json", "MULTICHIP_*.json", "PERF_REPORT*.json"))
    ]
    assert not records, f"committed records at the repo root: {sorted(records)}"

"""Docs cannot rot silently (ISSUE 2 satellite).

Two contracts:

  1. every script in ``examples/`` runs to completion (reduced args where
     the example is a long-running driver);
  2. every repo path and every fully-qualified ``repro...`` symbol named
     in ``docs/*.md`` / ``README.md`` exists — docs referring to renamed
     or deleted code fail the tier-1 suite.
"""
import importlib
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

# ---------------------------------------------------------------- examples
# Every file in examples/ must be registered here (enforced below) with
# the arguments that make it a CI-sized run.
EXAMPLE_ARGS = {
    "quickstart.py": [],
    "fifo_sizing_dse.py": [],
    "pipeline_perfsim.py": [],
}


def test_every_example_is_registered():
    on_disk = sorted(f for f in os.listdir(os.path.join(REPO, "examples"))
                     if f.endswith(".py"))
    assert on_disk == sorted(EXAMPLE_ARGS), (
        "examples/ and EXAMPLE_ARGS disagree — register new examples here "
        "so they are executed by the docs suite")


@pytest.mark.parametrize("name", sorted(EXAMPLE_ARGS))
def test_example_runs(name, tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep * bool(env.get("PYTHONPATH")) \
        + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", name)]
        + EXAMPLE_ARGS[name],
        cwd=tmp_path,                      # artifacts go here, not the repo
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, (
        f"examples/{name} failed:\n--- stdout ---\n{proc.stdout[-2000:]}\n"
        f"--- stderr ---\n{proc.stderr[-2000:]}")


# ------------------------------------------------------------- doc symbols
DOC_FILES = ["README.md", "docs/architecture.md", "docs/api.md",
             "docs/dse_guide.md", "docs/sweep_guide.md"]

_TOKEN = re.compile(r"`([^`\n]+)`")
_DOTTED = re.compile(r"^repro(\.[A-Za-z_]\w*)+$")


def _tokens(doc):
    with open(os.path.join(REPO, doc)) as f:
        return _TOKEN.findall(f.read())


def test_doc_files_exist():
    for doc in DOC_FILES:
        assert os.path.exists(os.path.join(REPO, doc)), doc


@pytest.mark.parametrize("doc", DOC_FILES)
def test_doc_paths_exist(doc):
    """Backticked repo paths (src/..., docs/..., *.py, *.md, *.json) must
    exist on disk — also tried relative to src/repro for `core/...` style
    references."""
    missing = []
    for tok in _tokens(doc):
        if ("/" not in tok or any(c in tok for c in " *(,=<>{")
                or tok.startswith("http")):
            continue
        rel = tok.rstrip("/")
        if not (os.path.exists(os.path.join(REPO, rel))
                or os.path.exists(os.path.join(SRC, "repro", rel))):
            missing.append(tok)
    assert not missing, f"{doc} names nonexistent paths: {missing}"


@pytest.mark.parametrize("doc", DOC_FILES)
def test_doc_symbols_resolve(doc):
    """Backticked fully-qualified names (`repro.x.y[.Z[.attr]](...)`) must
    import/resolve — the call-signature tail is ignored."""
    sys.path.insert(0, SRC)
    try:
        bad = []
        for tok in _tokens(doc):
            name = tok.split("(")[0].strip()
            if not _DOTTED.match(name):
                continue
            parts = name.split(".")
            obj, rest = None, parts
            for i in range(len(parts), 0, -1):
                try:
                    obj = importlib.import_module(".".join(parts[:i]))
                    rest = parts[i:]
                    break
                except ImportError:
                    continue
            if obj is None:
                bad.append(tok)
                continue
            try:
                for attr in rest:
                    obj = getattr(obj, attr)
            except AttributeError:
                bad.append(tok)
        assert not bad, f"{doc} names unresolvable symbols: {bad}"
    finally:
        sys.path.remove(SRC)


def test_api_doc_covers_public_exports():
    """Every name in repro.core.__all__, repro.corpus.__all__ and
    repro.delta.__all__ must be mentioned in docs/api.md — new public API
    cannot ship undocumented."""
    sys.path.insert(0, SRC)
    try:
        import repro.core as core
        import repro.corpus as corpus
        import repro.delta as delta
        with open(os.path.join(REPO, "docs", "api.md")) as f:
            text = f.read()
        missing = [n for n in (list(core.__all__) + list(corpus.__all__)
                               + list(delta.__all__))
                   if n not in text]
        assert not missing, f"docs/api.md does not mention: {missing}"
    finally:
        sys.path.remove(SRC)

"""The documents describe the tree that is there.

One case a document: every repo-relative path it names in backticks, or
runs as `python <path>`, exists. And one census: the `MMLSPARK_TPU_*`
environment switches the code reads are the ones listed here, so a new
switch is a visible act (ROADMAP D13).
"""

import fnmatch
import pathlib
import re
import subprocess

import pytest

REPO = pathlib.Path(__file__).parent.parent

# CHANGES.md and ROADMAP.md are history: they name what is gone on purpose
DOCUMENTS = sorted(
    ["README.md", "PERF.md", "benchmark/README.md", "tools/ci.sh",
     "tools/runme.sh", ".claude/skills/verify/SKILL.md"]
    + [str(p.relative_to(REPO)) for p in (REPO / "docs").glob("*.md")])

# the documents shorten paths to these roots
ROOTS = ("", "mmlspark_tpu/", "benchmark/")

ALLOWED = {
    # what a run writes
    "status.json", "manifest.json", "replica-N.jsonl", "elastic_status.json",
    "_chip/diag.py", "flight-<process>-<pid>-<n>.jsonl",
    # the reference's own
    "docs/lightgbm.md", "docs/mmlspark-serving.md", "spark.read.csv",
}

_ENDING = r"\.(?:py|sh|jsonl|json|md|csv)"
_PATH = re.compile(
    r"(?<![\w/.<>*-])((?:[\w.<>*-]+/)*[\w.<>*-]+" + _ENDING + r")"
    r"(?::\d+(?:-\d+)?)?(?![\w/<*-])")
_BACKTICKED = re.compile(r"`([^`\n]+)`")
_PYTHON_RUNS = re.compile(r"\bpython3?\s+(?:-\w+\s+)*([\w./-]+\.py)\b")


def _tracked() -> "list[str]":
    """What git would commit; without a work tree of git's, what is there."""
    try:
        out = subprocess.run(
            ["git", "ls-files", "--cached", "--others", "--exclude-standard"],
            cwd=REPO, capture_output=True, text=True, check=True).stdout
        files = out.split("\n")
    except (OSError, subprocess.CalledProcessError):
        files = [str(p.relative_to(REPO)) for p in REPO.rglob("*")
                 if ".git" not in p.parts]
    return [f for f in files if f and (REPO / f).is_file()]


@pytest.fixture(scope="module")
def tracked():
    files = _tracked()
    return files, {f.rsplit("/", 1)[-1] for f in files}


def _named_paths(text: str) -> "set[str]":
    spans = _BACKTICKED.findall(text)
    named = {m for span in spans for m in _PATH.findall(span)}
    return named | set(_PYTHON_RUNS.findall(text))


def _exists(path: str, files, names) -> bool:
    # a placeholder (`configs/<name>.json`) stands for any one file
    pattern = re.sub(r"<[^>]*>", "*", path)
    if "*" in pattern:
        if "/" not in pattern:
            return bool(fnmatch.filter(names, pattern))
        return any(fnmatch.filter(files, root + pattern) for root in ROOTS)
    if "/" not in path:
        return path in names
    return any((REPO / (root + path)).is_file() for root in ROOTS)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_path_a_document_names_exists(document, tracked):
    files, names = tracked
    named = _named_paths((REPO / document).read_text())
    missing = sorted(p for p in named - ALLOWED
                     if not _exists(p, files, names))
    assert not missing, f"{document} names what is not in the tree: {missing}"


def test_the_environment_switches_are_the_listed_twelve():
    listed = {"MMLSPARK_TPU_" + name for name in (
        "FUSED_HIST", "HIST_GROUP", "KERNELS", "RING_GATHER", "SANITIZE",
        "SWEEP_FULLFIT", "NO_NATIVE", "NATIVE_DIR", "TRACE_DIR",
        "METRICS__ENABLED", "LOG__LEVEL", "LOG__FORMAT")}
    found = set()
    for top in ("mmlspark_tpu", "tools"):
        for path in (REPO / top).rglob("*"):
            if path.suffix in (".py", ".sh", ".cpp") and path.is_file():
                found |= set(re.findall(r"MMLSPARK_TPU_[A-Z0-9_]+",
                                        path.read_text()))
    # the bare prefix is core/config.py's own (section__key overrides)
    assert found - {"MMLSPARK_TPU_"} == listed

"""The living documents name only files that exist.

A document a newcomer is sent to — the README, CLAUDE.md, the subsystem
guides under docs/, the verify skill — must not send them on to a file
that is gone: every repo-relative path such a document names in
backticks has to exist. DATED logs are exempt, because what they record
is a state the tree has left behind and naming files of that state is
their job: ``CHANGES.md`` (one entry a PR), ``docs/perf_round*.md`` (CPU
performance logs, each headed with its round), ``docs/results_*``
(experiment write-ups with their commands) and the ``*_gonogo.md``
decision notes. ``ROADMAP.md`` and ``PERF.md`` cite retired files by
commit on purpose and are kept true by hand.
"""
import functools
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LIVING_DOCS = ["README.md", "CLAUDE.md", "BASELINE.md", "docs/serving.md",
               "docs/telemetry.md", "docs/scenarios.md", "docs/lint.md",
               ".claude/skills/verify/SKILL.md", "checkpoints/README.md",
               "scripts/experiments/README.md"]

#: where a document's relative paths are rooted: the repo, the package
#: (``rl/fused.py``), and the directories whose files prose names bare
ROOTS = ("", "ddls_tpu", "scripts", "docs", "benchmarks", "tests",
         "ddls_tpu/scripts")
TOP_DIRS = ("ddls_tpu", "docs", "scripts", "tests", "benchmarks",
            "checkpoints", "notebooks", ".claude")
#: what building, testing and running leave behind (.gitignore) and the
#: run directories a recipe tells the reader to create
GENERATED = (".jax_cache", "chiprun_out", "benchmarks/out", "runs/",
             "ddls_tpu/native/_build", ".pytest_cache", "outputs/")
SOURCE_EXT = (".py", ".md", ".cpp", ".toml", ".yaml", ".yml", ".sh")
DATA_EXT = (".json", ".jsonl", ".txt", ".pbtxt", ".csv")
#: a bare data file counts as a repo path only if it is named like the
#: repo's committed records (BENCHMARK.json, PERF_LEDGER.jsonl, ...)
RECORD_NAME = re.compile(r"^[A-Z][A-Z0-9_]*(_r?\d+)?\.jsonl?$")
PLACEHOLDER = re.compile(r"[*<>{}$~…|=]|\.\.\.")


@functools.lru_cache(maxsize=None)
def _tracked_basenames():
    names = set()
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if d not in (".git", ".jax_cache", "chiprun_out",
                                "__pycache__", "_build", ".pytest_cache")]
        names.update(files)
    return frozenset(names)


def _candidate(word: str):
    """The repo-relative path a backticked word names, or None."""
    if PLACEHOLDER.search(word):
        return None
    word = word.strip("()[],;\"'").rstrip(".")
    # `rl/fused.py:54`, `tests/test_x.py::test_name`, `bench.py:46`
    word = re.split(r"::|:(?=[\dA-Za-z_])", word)[0]
    if (not word or word.startswith(("/", "-", "http", "#"))
            or word.startswith(GENERATED)):
        return None
    base = word.rstrip("/").rsplit("/", 1)[-1]
    ext = os.path.splitext(base)[1]
    if "/" in word:
        if (ext in SOURCE_EXT + DATA_EXT or word.endswith("/")
                or word.split("/", 1)[0] in TOP_DIRS):
            return word
        return None
    if ext in SOURCE_EXT or RECORD_NAME.match(base):
        return word
    return None


def named_paths(text: str):
    out = []
    for span in re.findall(r"`([^`\n]+)`", text):
        for word in span.split():
            path = _candidate(word)
            if path:
                out.append(path)
    return out


def _exists(path: str, basenames, doc_dir: str) -> bool:
    if any(os.path.exists(os.path.join(REPO, root, path))
           for root in ROOTS + (doc_dir,)):
        return True
    # a bare file name (`loops.py`, `engine.cpp`) names a file somewhere
    return "/" not in path and path in basenames


@pytest.mark.parametrize("doc", LIVING_DOCS)
def test_living_document_names_only_files_that_exist(doc):
    with open(os.path.join(REPO, doc)) as f:
        paths = named_paths(f.read())
    assert paths, f"{doc} names no path: the extraction has gone blind"
    basenames = _tracked_basenames()
    missing = sorted({p for p in paths
                      if not _exists(p, basenames, os.path.dirname(doc))})
    assert not missing, f"{doc} names files that do not exist: {missing}"


def test_readme_layout_names_every_package():
    with open(os.path.join(REPO, "README.md")) as f:
        readme = f.read()
    layout = readme.split("## Layout", 1)[1].split("\n## ", 1)[0]
    pkg_root = os.path.join(REPO, "ddls_tpu")
    packages = sorted(
        d for d in os.listdir(pkg_root)
        if os.path.isfile(os.path.join(pkg_root, d, "__init__.py")))
    assert len(packages) >= 18
    missing = [p for p in packages if f"`ddls_tpu/{p}/`" not in layout]
    assert not missing, f"README.md's layout table omits {missing}"
    assert "`benchmarks/`" in layout
    assert "`ddls_tpu/graphs/arch.py`" in layout or "`arch.py`" in layout


def test_path_extraction_sees_what_it_should():
    """The extraction itself: line and test suffixes are cut, commands
    are read word by word, placeholders, outputs and non-paths are let
    be."""
    text = ("`python bench.py --mode sim` `rl/fused.py:54` `dp/mp` "
            "`tests/test_x.py::test_y` `runs/bench1` `RECORD_r09.json` "
            "`trace.json` `benchmarks/run.py --workload <cell>` "
            "`docs/perf_round*.md` `ddls_tpu/serve/` `/tmp/x.py`")
    assert named_paths(text) == [
        "bench.py", "rl/fused.py", "tests/test_x.py", "RECORD_r09.json",
        "benchmarks/run.py", "ddls_tpu/serve/"]

"""The front page, ``docs/`` and the verify skill name only what the
checkout holds, and the root holds one measurement system's records.

A document that sends its reader to a deleted script or record is how a
second, older measurement system outlived the benchmark by thirty PRs.
``PERF.md``, ``ROADMAP.md`` and ``CHANGES.md`` are history: they name
what was deleted, and are not held to this.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = sorted(
    ["README.md", ".claude/skills/verify/SKILL.md"]
    + [
        os.path.relpath(p, REPO)
        for p in glob.glob(os.path.join(REPO, "docs", "*.md"))
    ]
)

TREES = ("dlrover_tpu/", "scripts/", "tests/", "benchmarks/", "docs/",
         "examples/")
ROOT_RECORD = re.compile(r"[A-Z][A-Z0-9_]*\.(json|jsonl|md)")
RUN_AS_SCRIPT = re.compile(r"python3?\s+(?:-\w+\s+)*([\w.-]+\.py)\b")
# Not the checkout's: a user's own script on an example command line,
# and the manifest inside a checkpoint's step directory.
NOT_OURS = {"train.py", "script.py", "MANIFEST.json"}


def _ignored():
    """Names ``.gitignore`` lists: made at run time, absent from a
    checkout by design."""
    with open(os.path.join(REPO, ".gitignore")) as f:
        lines = [ln.strip() for ln in f]
    return [ln.lstrip("/") for ln in lines if ln and not ln.startswith("#")]


def _is_ignored(token, ignored):
    for name in ignored:
        if name.endswith("/") and "/" + name in "/" + token + "/":
            return True
        if os.path.basename(token) == name:
            return True
    return False


def _code_spans(doc, text):
    """Every backticked span and every line of a fenced block."""
    fenced = False
    for line in text.splitlines():
        if line.lstrip().startswith("```"):
            fenced = not fenced
            continue
        if fenced:
            yield line
            continue
        if doc == "docs/MIGRATION.md" and line.startswith("|"):
            # The first column names the reference's tree, not ours.
            line = line.split("|", 2)[-1]
        yield from re.findall(r"`([^`\n]+)`", line)


def _named_paths(doc, text):
    for span in _code_spans(doc, text):
        for name in RUN_AS_SCRIPT.findall(span):
            if "/" not in name:
                yield name
        for word in span.split():
            word = word.strip("\"'(),;[]")
            word = word.split("::")[0]             # a test's node id
            word = re.sub(r":\d+([-–]\d+)?$", "", word)  # file.py:12-30
            word = word.rstrip(".:")
            if re.search(r"[<>{}…$]|\.\.\.", word):
                continue  # a placeholder, not a name
            if word.startswith(TREES) or ROOT_RECORD.fullmatch(word):
                yield word


def _exists(token):
    path = os.path.join(REPO, token)
    if any(ch in token for ch in "*?"):
        return bool(glob.glob(path))
    return os.path.exists(path)


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_only_paths_that_exist(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        text = f.read()
    ignored = _ignored()
    named = sorted(set(_named_paths(doc, text)) - NOT_OURS)
    assert named or doc.startswith("docs/"), "the rule found no path at all"
    gone = [
        t for t in named if not _exists(t) and not _is_ignored(t, ignored)
    ]
    assert gone == [], f"{doc} names what the checkout does not hold"


def test_root_holds_no_measurement_record_but_the_drivers():
    ignored = _ignored()
    records = sorted(
        name for name in os.listdir(REPO)
        if name.endswith((".json", ".jsonl"))
        and not _is_ignored(name, ignored)
    )
    assert records == ["BASELINE.json", "BENCHMARK.json", "PERF_LEDGER.jsonl"]

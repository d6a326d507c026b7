"""Shared infrastructure for the dlrover_tpu static invariant checkers.

Every checker encodes a bug class this project has actually paid for
(see ``docs/STATIC_ANALYSIS.md`` for the catalog with one anecdote per
code).  The framework is deliberately stdlib-only — ``ast`` for
structure, ``tokenize`` for comments/pragmas — so the analyzer runs in
any environment the control plane runs in, including jax-free agent
containers and CI images without a dev toolchain.

Vocabulary:

* **Finding** — one violation: ``(code, path, line, col, message)``.
* **SourceFile** — a parsed file plus its comment map and the set of
  ``# dlr: noqa[...]`` suppressions per line.
* **Project** — the whole analyzed corpus plus the repo root, for
  checkers that cross-reference docs/ and tests/ (fault-point drift,
  telemetry schema).
* **Checker** — either per-file (``scope = "file"``) or whole-corpus
  (``scope = "project"``).

Suppression pragma::

    risky_line()  # dlr: noqa[DLR001]
    risky_line()  # dlr: noqa[DLR001,DLR004]
    risky_line()  # dlr: noqa          (all codes — use sparingly)

A suppressed finding still shows up in the JSON report (``suppressed``
list) so ``analysis/gate.py`` can count how much is being waved through.
"""

import ast
import io
import os
import re
import tokenize
from dataclasses import dataclass, field
from typing import Dict, Iterable, Iterator, List, Optional, Set, Tuple

NOQA_RE = re.compile(
    r"#\s*dlr:\s*noqa(?:\[\s*(?P<codes>[A-Z0-9,\s]+?)\s*\])?", re.I
)


@dataclass
class Finding:
    code: str
    path: str  # repo/cwd-relative where possible
    line: int
    col: int
    message: str
    checker: str = ""
    suppressed: bool = False

    def key(self) -> Tuple:
        return (self.path, self.line, self.col, self.code, self.message)

    def to_dict(self) -> Dict:
        return {
            "code": self.code,
            "path": self.path,
            "line": self.line,
            "col": self.col,
            "message": self.message,
            "checker": self.checker,
            "suppressed": self.suppressed,
        }


class SourceFile:
    """One parsed Python file.

    ``noqa`` maps line number → set of suppressed codes (empty set means
    *all* codes suppressed on that line); ``comments`` maps line number
    → raw comment text (used for annotation pragmas like
    ``# dlr: shared-across-threads`` and ``# dlr: no-retry``).
    """

    def __init__(self, path: str, display_path: Optional[str] = None):
        self.path = os.path.abspath(path)
        self.display_path = display_path or os.path.relpath(path)
        with open(path, "rb") as f:
            raw = f.read()
        self.text = raw.decode("utf-8", errors="replace")
        self.tree: Optional[ast.AST] = None
        self.parse_error: Optional[SyntaxError] = None
        try:
            self.tree = ast.parse(self.text, filename=path)
        except SyntaxError as e:
            self.parse_error = e
        self.comments: Dict[int, str] = {}
        self.noqa: Dict[int, Optional[Set[str]]] = {}
        self._scan_comments()

    def _scan_comments(self):
        try:
            tokens = tokenize.generate_tokens(
                io.StringIO(self.text).readline
            )
            for tok in tokens:
                if tok.type != tokenize.COMMENT:
                    continue
                line = tok.start[0]
                self.comments[line] = tok.string
                m = NOQA_RE.search(tok.string)
                if m:
                    codes = m.group("codes")
                    if codes:
                        self.noqa[line] = {
                            c.strip().upper()
                            for c in codes.split(",")
                            if c.strip()
                        }
                    else:
                        self.noqa[line] = None  # bare noqa: everything
        except (tokenize.TokenError, IndentationError, SyntaxError):
            pass

    def comment_on_or_above(self, line: int, needle: str,
                            lookback: int = 2) -> bool:
        """True when a comment containing ``needle`` sits on ``line`` or
        within ``lookback`` lines above it (annotation pragmas)."""
        for ln in range(line, line - lookback - 1, -1):
            if needle in self.comments.get(ln, ""):
                return True
        return False

    def is_suppressed(self, line: int, code: str) -> bool:
        if line not in self.noqa:
            return False
        codes = self.noqa[line]
        return codes is None or code.upper() in codes


class Project:
    """The analyzed corpus plus the repo root for cross-file checkers."""

    def __init__(self, files: List[SourceFile], root: Optional[str]):
        self.files = files
        self.root = root
        # Side-channel for structured verdicts (e.g. the DLR018 wire
        # schema comparison) — copied onto the Report after the run.
        self.extras: Dict[str, object] = {}
        # Finding keys a whole-program pass has *refuted*: a project
        # checker with strictly more information (resolved callees,
        # interprocedural summaries) may retract a file-local
        # heuristic's guess.  Applied during report assembly.
        self.retractions: Set[Tuple] = set()
        self._by_suffix_cache: Dict[str, Optional[SourceFile]] = {}

    def find_file(self, *suffixes: str) -> Optional[SourceFile]:
        """First analyzed file whose normalized path ends with one of
        ``suffixes`` (e.g. ``telemetry/events.py``)."""
        key = "|".join(suffixes)
        if key in self._by_suffix_cache:
            return self._by_suffix_cache[key]
        found = None
        for sf in self.files:
            norm = sf.path.replace(os.sep, "/")
            if any(norm.endswith(s) for s in suffixes):
                found = sf
                break
        self._by_suffix_cache[key] = found
        return found

    def root_path(self, *parts: str) -> Optional[str]:
        if not self.root:
            return None
        p = os.path.join(self.root, *parts)
        return p if os.path.exists(p) else None


class Checker:
    """Base class.  Subclasses set ``code``/``name``/``description`` and
    implement :meth:`check` (scope ``"file"``) or :meth:`check_project`
    (scope ``"project"``).  One checker may emit several codes (list the
    extras in ``extra_codes``) — selection filters still apply per code.
    """

    code = "DLR000"
    extra_codes: Tuple[str, ...] = ()
    name = "base"
    description = ""
    scope = "file"

    def codes(self) -> Tuple[str, ...]:
        return (self.code,) + tuple(self.extra_codes)

    def check(self, sf: SourceFile) -> Iterator[Finding]:
        return iter(())

    def check_project(self, project: Project) -> Iterator[Finding]:
        return iter(())


_REGISTRY: List[Checker] = []


def register(cls):
    """Class decorator: instantiate and add to the global registry."""
    _REGISTRY.append(cls())
    return cls


def all_checkers() -> List[Checker]:
    # Import side effect: checker modules self-register.
    from dlrover_tpu.analysis import checkers  # noqa: F401

    return list(_REGISTRY)


def find_project_root(start: str) -> Optional[str]:
    """Walk up from ``start`` looking for the repo root (identified by a
    ``docs/FAULT_TOLERANCE.md`` or a ``.git``)."""
    cur = os.path.abspath(start)
    if os.path.isfile(cur):
        cur = os.path.dirname(cur)
    for _ in range(12):
        if (
            os.path.exists(os.path.join(cur, "docs", "FAULT_TOLERANCE.md"))
            or os.path.exists(os.path.join(cur, ".git"))
        ):
            return cur
        parent = os.path.dirname(cur)
        if parent == cur:
            return None
        cur = parent
    return None


def collect_files(paths: Iterable[str]) -> List[str]:
    out: List[str] = []
    seen: Set[str] = set()
    for path in paths:
        if os.path.isfile(path):
            candidates = [path]
        else:
            candidates = []
            for dirpath, dirnames, filenames in os.walk(path):
                dirnames[:] = [
                    d for d in dirnames
                    if d not in ("__pycache__", ".git", "_build")
                ]
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        candidates.append(os.path.join(dirpath, fn))
        for c in candidates:
            a = os.path.abspath(c)
            if a not in seen:
                seen.add(a)
                out.append(c)
    return out


@dataclass
class Report:
    findings: List[Finding] = field(default_factory=list)
    suppressed: List[Finding] = field(default_factory=list)
    checked_files: int = 0
    checkers: List[str] = field(default_factory=list)
    # Structured per-checker verdicts (``comm_schema`` etc.), surfaced
    # in the JSON report for ``analysis/gate.py`` to judge.
    extras: Dict = field(default_factory=dict)

    @property
    def exit_code(self) -> int:
        return 1 if self.findings else 0

    def to_dict(self) -> Dict:
        return {
            "checked_files": self.checked_files,
            "checkers": self.checkers,
            "findings": [f.to_dict() for f in self.findings],
            "suppressed": [f.to_dict() for f in self.suppressed],
            "counts": self.counts(),
            "extras": self.extras,
        }

    def counts(self) -> Dict[str, int]:
        counts: Dict[str, int] = {}
        for f in self.findings:
            counts[f.code] = counts.get(f.code, 0) + 1
        return counts


def _code_selected(code: str, select: Optional[Set[str]],
                   ignore: Optional[Set[str]]) -> bool:
    code = code.upper()
    if select and not any(code.startswith(s) for s in select):
        return False
    if ignore and any(code.startswith(s) for s in ignore):
        return False
    return True


def run_paths(
    paths: Iterable[str],
    select: Optional[Iterable[str]] = None,
    ignore: Optional[Iterable[str]] = None,
    project_root: Optional[str] = None,
) -> Report:
    """Analyze ``paths`` with every registered checker.

    ``select``/``ignore`` are code prefixes (``DLR001`` or just ``DLR``);
    select wins first, then ignore subtracts.  Returns a :class:`Report`
    whose ``findings`` are the *unsuppressed* violations — the CLI exits
    nonzero iff that list is non-empty.
    """
    paths = list(paths)
    select_set = {s.strip().upper() for s in select or [] if s.strip()}
    ignore_set = {s.strip().upper() for s in ignore or [] if s.strip()}
    file_paths = collect_files(paths)
    files = [SourceFile(p) for p in file_paths]
    root = project_root or (
        find_project_root(paths[0]) if paths else None
    )
    project = Project(files, root)

    raw: List[Finding] = []
    for sf in files:
        if sf.parse_error is not None:
            raw.append(
                Finding(
                    "DLR000",
                    sf.display_path,
                    sf.parse_error.lineno or 1,
                    (sf.parse_error.offset or 1) - 1,
                    f"syntax error: {sf.parse_error.msg}",
                    checker="parse",
                )
            )
    checkers = all_checkers()
    for checker in checkers:
        if not any(
            _code_selected(c, select_set, ignore_set)
            for c in checker.codes()
        ):
            continue
        if checker.scope == "project":
            raw.extend(checker.check_project(project))
        else:
            for sf in files:
                if sf.tree is None:
                    continue
                raw.extend(checker.check(sf))

    by_path = {sf.display_path: sf for sf in files}
    report = Report(
        checked_files=len(files),
        checkers=[c.name for c in checkers],
        extras=project.extras,
    )
    seen: Set[Tuple] = set()
    for f in sorted(raw, key=lambda f: (f.path, f.line, f.col, f.code)):
        if f.key() in seen or f.key() in project.retractions:
            continue
        seen.add(f.key())
        if not _code_selected(f.code, select_set, ignore_set):
            continue
        sf = by_path.get(f.path)
        if sf is not None and sf.is_suppressed(f.line, f.code):
            f.suppressed = True
            report.suppressed.append(f)
        else:
            report.findings.append(f)
    return report

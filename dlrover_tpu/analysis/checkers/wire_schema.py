"""DLR018 — wire-schema drift gate for ``@comm_message`` dataclasses.

Every RPC payload in this codebase is a ``@comm_message`` dataclass
(``common/comm.py``), encoded by field name.  During an elastic restart
old and new binaries coexist on the same sockets, so the wire schema is
a *compatibility contract*, not an implementation detail:

* a renamed or removed field silently drops data sent by older peers
  (``_decode`` filters unknown kwargs) or breaks their reads;
* a new field **without a default** makes the new binary unable to
  construct the message from an older peer's bytes at all — a
  ``TypeError`` in the middle of a rolling restart.

The checker snapshots each message's declared fields — name, annotation
text, has-default — against a golden file committed at
``tests/analysis_fixtures/comm_schema.json`` (for fixture trees, a
``comm_schema.json`` sibling of the analyzed ``comm.py`` wins) and
fails on:

* a message class present in the snapshot but gone from the code;
* a field present in the snapshot but gone from its class (rename ==
  remove + add: the add half is judged separately);
* a new field without a default.

Additive changes — new message classes, new fields *with* defaults —
pass, and are listed in the ``comm_schema`` verdict the JSON report
carries (``extras``), which ``analysis/gate.py`` copies into its
summary and tier-1 holds to ``ok``.  After a deliberate, reviewed
schema change, regenerate the snapshot with::

    python -m dlrover_tpu.analysis --update-comm-schema

Annotation *type* changes do not fail (the encoder is duck-typed) but
are listed in the verdict so a reviewer sees them.
"""

import ast
import json
import os
from typing import Dict, Iterator, Optional, Tuple

from dlrover_tpu.analysis.core import (
    Checker,
    Finding,
    Project,
    SourceFile,
    register,
)

SNAPSHOT_RELPATH = os.path.join(
    "tests", "analysis_fixtures", "comm_schema.json"
)


def _deco_name(deco: ast.AST) -> str:
    if isinstance(deco, ast.Call):
        deco = deco.func
    if isinstance(deco, ast.Attribute):
        return deco.attr
    if isinstance(deco, ast.Name):
        return deco.id
    return ""


def extract_schema(sf: SourceFile) -> Dict[str, Dict[str, Dict]]:
    """``{class: {field: {"type": str, "default": bool}}}`` for every
    ``@comm_message`` class in one parsed file, in declaration order."""
    out: Dict[str, Dict[str, Dict]] = {}
    if sf.tree is None:
        return out
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.ClassDef):
            continue
        if not any(
            _deco_name(d) == "comm_message" for d in node.decorator_list
        ):
            continue
        fields: Dict[str, Dict] = {}
        for stmt in node.body:
            if isinstance(stmt, ast.AnnAssign) and isinstance(
                stmt.target, ast.Name
            ):
                fields[stmt.target.id] = {
                    "type": ast.unparse(stmt.annotation),
                    "default": stmt.value is not None,
                }
        out[node.name] = fields
    return out


def snapshot_path_for(project: Project, sf: SourceFile) -> Optional[str]:
    """Sibling ``comm_schema.json`` first (fixture trees), then the
    repo-level golden snapshot."""
    sibling = os.path.join(os.path.dirname(sf.path), "comm_schema.json")
    if os.path.exists(sibling):
        return sibling
    if project.root:
        cand = os.path.join(project.root, SNAPSHOT_RELPATH)
        if os.path.exists(cand):
            return cand
    return None


def render_snapshot(schema: Dict[str, Dict[str, Dict]]) -> str:
    return json.dumps(
        {"version": 1, "messages": schema}, indent=2, sort_keys=True
    ) + "\n"


def _class_lines(sf: SourceFile) -> Dict[str, int]:
    out = {}
    if sf.tree is not None:
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.ClassDef):
                out[node.name] = node.lineno
    return out


@register
class WireSchemaChecker(Checker):
    code = "DLR018"
    name = "wire-schema"
    description = (
        "@comm_message wire schema must stay decode-compatible with the "
        "committed snapshot: no renamed/removed fields, no new fields "
        "without defaults"
    )
    scope = "project"

    def check_project(self, project: Project) -> Iterator[Finding]:
        sf = project.find_file("/comm.py")
        if sf is None or sf.tree is None:
            project.extras["comm_schema"] = {"status": "absent"}
            return
        schema = extract_schema(sf)
        verdict: Dict = {
            "status": "ok",
            "messages": len(schema),
            "snapshot": None,
            "breaking": [],
            "added_messages": [],
            "added_fields": [],
            "type_changes": [],
        }
        project.extras["comm_schema"] = verdict
        snap_path = snapshot_path_for(project, sf)
        if snap_path is None:
            verdict["status"] = "missing-snapshot"
            yield Finding(
                self.code, sf.display_path, 1, 0,
                (
                    "no wire-schema snapshot found (expected "
                    f"{SNAPSHOT_RELPATH} or a comm_schema.json next to "
                    "comm.py) — the drift gate is blind; generate one "
                    "with --update-comm-schema"
                ),
                checker=self.name,
            )
            return
        verdict["snapshot"] = os.path.relpath(
            snap_path, project.root or os.getcwd()
        )
        try:
            with open(snap_path, "r", encoding="utf-8") as f:
                golden = json.load(f)["messages"]
        except (OSError, ValueError, KeyError) as e:
            verdict["status"] = "bad-snapshot"
            yield Finding(
                self.code, sf.display_path, 1, 0,
                f"unreadable wire-schema snapshot {snap_path}: {e}",
                checker=self.name,
            )
            return
        lines = _class_lines(sf)
        yield from self._compare(sf, golden, schema, lines, verdict)
        if verdict["breaking"]:
            verdict["status"] = "drift"
        elif verdict["added_messages"] or verdict["added_fields"]:
            verdict["status"] = "additive"

    def _compare(
        self,
        sf: SourceFile,
        golden: Dict,
        schema: Dict,
        lines: Dict[str, int],
        verdict: Dict,
    ) -> Iterator[Finding]:
        for cls, old_fields in sorted(golden.items()):
            if cls not in schema:
                verdict["breaking"].append(f"removed message {cls}")
                yield Finding(
                    self.code, sf.display_path, 1, 0,
                    (
                        f"wire message {cls} was removed or renamed but "
                        "is still in the committed schema snapshot — "
                        "older peers still send it and _decode will "
                        "raise on their bytes; restore it, or update "
                        "the snapshot via --update-comm-schema after a "
                        "compatibility review"
                    ),
                    checker=self.name,
                )
                continue
            new_fields = schema[cls]
            line = lines.get(cls, 1)
            for fname, old_spec in sorted(old_fields.items()):
                if fname not in new_fields:
                    verdict["breaking"].append(
                        f"removed field {cls}.{fname}"
                    )
                    yield Finding(
                        self.code, sf.display_path, line, 0,
                        (
                            f"field {cls}.{fname} was removed or "
                            "renamed — a rename is invisible on the "
                            "wire: older peers keep sending the old "
                            "name (silently dropped) and expect it "
                            "back; keep the old field through one "
                            "release, then --update-comm-schema"
                        ),
                        checker=self.name,
                    )
                elif old_spec.get("type") != new_fields[fname].get(
                    "type"
                ):
                    verdict["type_changes"].append(
                        f"{cls}.{fname}: {old_spec.get('type')} -> "
                        f"{new_fields[fname].get('type')}"
                    )
            for fname, new_spec in sorted(new_fields.items()):
                if fname in old_fields:
                    continue
                if new_spec.get("default"):
                    verdict["added_fields"].append(f"{cls}.{fname}")
                else:
                    verdict["breaking"].append(
                        f"new required field {cls}.{fname}"
                    )
                    yield Finding(
                        self.code, sf.display_path, line, 0,
                        (
                            f"new field {cls}.{fname} has no default — "
                            "during a rolling restart the new binary "
                            "cannot construct this message from an "
                            "older peer's bytes (TypeError in "
                            "_decode); give it a default, then "
                            "--update-comm-schema"
                        ),
                        checker=self.name,
                    )
        for cls in sorted(set(schema) - set(golden)):
            verdict["added_messages"].append(cls)

"""The analyzer's gate over this package: pragma budget and wire
schema verdict.

``tests/test_analysis.py::TestTree`` runs the analyzer over
``dlrover_tpu/`` in tier-1 and passes the JSON payload through
:func:`analysis_summary`.  Two policies live here:

* **Pragma budget** — suppressions (``# dlr: noqa[...]``) are debt.
  The per-code tally committed at
  ``tests/analysis_fixtures/pragma_budget.json`` is the budget; a tree
  whose tally *grows* for any code fails.  A deliberate new
  suppression re-baselines that file by hand, in review, as
  ``--update-comm-schema`` re-baselines the wire schema.  Shrinking is
  always fine (paying debt needs no edit).

* **Wire schema verdict** — the ``comm_schema`` entry the DLR018
  checker leaves in the report's ``extras`` is copied into the summary,
  so the verdict says not just "analysis green" but "the wire schema is
  byte-compatible with the snapshot" (or what changed additively).
"""

from typing import Dict, List, Optional

__all__ = [
    "suppressed_counts",
    "pragma_budget",
    "analysis_summary",
]


def suppressed_counts(payload: Dict) -> Dict[str, int]:
    """Per-code tally of suppressed findings in an analyzer JSON
    payload."""
    out: Dict[str, int] = {}
    for f in payload.get("suppressed", []):
        code = f.get("code", "?")
        out[code] = out.get(code, 0) + 1
    return out


def pragma_budget(
    current: Dict[str, int],
    baseline: Optional[Dict[str, int]],
) -> Dict:
    """Compare the suppressed tally against the budget.  Returns::

        {"ok": bool, "grew": ["DLR00x: a -> b", ...],
         "baseline": {...} | None}

    ``baseline=None`` (a tree without a committed budget) always
    passes — there is nothing to diff against.
    """
    grew: List[str] = []
    if baseline is not None:
        for code in sorted(set(current) | set(baseline)):
            was, now = baseline.get(code, 0), current.get(code, 0)
            if now > was:
                grew.append(f"{code}: {was} -> {now}")
    return {"ok": not grew, "grew": grew, "baseline": baseline}


def analysis_summary(
    payload: Dict,
    rc: int,
    budget: Optional[Dict[str, int]] = None,
) -> Dict:
    """The gate's verdict on one analyzer run.

    ``budget`` is the per-code suppressed tally the tree is held to.
    ``ok`` requires a clean exit AND a respected pragma budget.
    """
    counts = suppressed_counts(payload)
    verdict = pragma_budget(counts, budget)
    summary = {
        "ok": rc == 0 and verdict["ok"],
        "rc": rc,
        "finding_count": len(payload.get("findings", [])),
        "suppressed_count": len(payload.get("suppressed", [])),
        "counts": payload.get("counts", {}),
        "suppressed_counts": counts,
        "pragma_budget": verdict,
        "checked_files": payload.get("checked_files"),
    }
    schema = payload.get("extras", {}).get("comm_schema")
    if schema is not None:
        summary["comm_schema"] = schema
    return summary

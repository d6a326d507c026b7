"""Telemetry warehouse: durable cross-job stats in the Brain store.

The live telemetry subsystem (goodput accountant, doctor verdicts,
step-phase profiler, perf ledger) dies with the job; this module is
where its output goes to outlive it.  One sqlite file — the Brain
server's in cluster mode, a job-local file under the telemetry dir in
local-master mode — holds a versioned schema of *runs* (job uuid,
run/attempt, model+mesh config fingerprint, software versions) and
durable records of five kinds:

``goodput``     interval summaries from the online accountant
``incident``    doctor verdicts (straggler, perf_regression, hang, …)
``step_phase``  per-rank step-phase distributions (data_wait/dispatch/
                device/total)
``device_mem``  device-memory high-water marks
``perf``        perf-ledger entries (tokens/s, MFU, blind flag)

Reference parity: ``dlrover/go/brain`` persists job runtime metrics to
MySQL and mines them for new-job resource estimates; AMP-style strategy
search (PAPERS.md) needs the same historical profile store.  The
read-side API here (``history``/``best_known_config``/``goodput_trend``)
is what ROADMAP item 3's warm-start consumes — ``auto/planner.py`` calls
it through :func:`dlrover_tpu.auto.planner.warehouse_warm_start`.

Like ``store.py``, everything is stdlib sqlite behind a lock with
parameterized queries only (enforced tree-wide by the DLR009 checker).
"""

import glob
import hashlib
import json
import os
import sqlite3
import threading
import time
from typing import Any, Dict, Iterable, List, Optional

from dlrover_tpu.common.log import logger

SCHEMA_VERSION = 1

# Job-local warehouse location: explicit path > telemetry dir sibling.
ENV_WAREHOUSE_DB = "DLROVER_WAREHOUSE_DB"
# "0" disables job-local warehousing entirely (tests, smoke runs).
ENV_WAREHOUSE = "DLROVER_WAREHOUSE"

RECORD_KINDS = (
    "goodput", "incident", "step_phase", "device_mem", "perf", "kv",
    "serve", "slo", "traffic", "fleet",
)

# Incident triggers whose verdict nodes name repeat offenders.
_OFFENDER_TRIGGERS = ("straggler", "perf_regression")


def config_fingerprint(config: Optional[dict]) -> str:
    """Stable short fingerprint of a model+mesh config dict.

    Canonical-JSON sha256, truncated: enough to key cross-job lookups,
    short enough to read in a report.  ``{}``/None fingerprint to the
    same value, so "no config" runs still group.
    """
    blob = json.dumps(
        config or {}, sort_keys=True, separators=(",", ":"), default=str
    )
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()[:12]


def enabled() -> bool:
    return os.environ.get(ENV_WAREHOUSE, "1") != "0"


def default_warehouse_path() -> str:
    explicit = os.environ.get(ENV_WAREHOUSE_DB, "")
    if explicit:
        return explicit
    from dlrover_tpu.telemetry import events as _tevents

    return os.path.join(_tevents.telemetry_dir(), "warehouse.sqlite")


def _coerce_ts(t) -> Optional[float]:
    """Epoch seconds from a float, numeric string, or ISO-8601 string
    (the perf ledger stamps ISO); None when absent/unparseable."""
    if t is None:
        return None
    if isinstance(t, (int, float)):
        return float(t)
    s = str(t)
    try:
        return float(s)
    except ValueError:
        pass
    try:
        import datetime

        return datetime.datetime.fromisoformat(s).timestamp()
    except ValueError:
        return None


def _repo_root() -> str:
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


class TelemetryWarehouse:
    """Thread-safe sqlite warehouse (``:memory:`` or a file path).

    May share a db file with :class:`~dlrover_tpu.brain.store.
    JobStatsStore` — the table sets are disjoint.
    """

    def __init__(self, path: str = ":memory:"):
        self.path = path
        parent = os.path.dirname(path)
        if parent and path != ":memory:":
            os.makedirs(parent, exist_ok=True)
        self._conn = sqlite3.connect(path, check_same_thread=False)
        self._lock = threading.Lock()
        with self._lock:
            self._conn.executescript(
                """
                CREATE TABLE IF NOT EXISTS warehouse_meta (
                    key TEXT PRIMARY KEY,
                    value TEXT
                );
                CREATE TABLE IF NOT EXISTS runs (
                    job_uid TEXT,
                    run TEXT DEFAULT '',
                    attempt INTEGER DEFAULT 0,
                    fingerprint TEXT DEFAULT '',
                    config TEXT DEFAULT '{}',
                    versions TEXT DEFAULT '{}',
                    started REAL,
                    updated REAL,
                    PRIMARY KEY (job_uid, run, attempt)
                );
                CREATE INDEX IF NOT EXISTS idx_wh_runs_fp
                    ON runs (fingerprint);
                CREATE TABLE IF NOT EXISTS records (
                    id INTEGER PRIMARY KEY AUTOINCREMENT,
                    job_uid TEXT,
                    run TEXT DEFAULT '',
                    attempt INTEGER DEFAULT 0,
                    kind TEXT,
                    t REAL,
                    rank TEXT DEFAULT '',
                    trigger TEXT DEFAULT '',
                    value REAL,
                    payload TEXT DEFAULT '{}'
                );
                CREATE INDEX IF NOT EXISTS idx_wh_records_job
                    ON records (job_uid, t);
                CREATE INDEX IF NOT EXISTS idx_wh_records_kind
                    ON records (kind, t);
                """
            )
            row = self._conn.execute(
                "SELECT value FROM warehouse_meta WHERE key=?",
                ("schema_version",),
            ).fetchone()
            if row is None:
                self._conn.execute(
                    "INSERT INTO warehouse_meta (key, value) VALUES (?, ?)",
                    ("schema_version", str(SCHEMA_VERSION)),
                )
            elif int(row[0]) < SCHEMA_VERSION:
                # Versioned-migration slot: CREATE/ALTER statements for
                # vN→vN+1 land here, then the stamp advances.  v1 has
                # nothing to migrate from.
                self._conn.execute(
                    "UPDATE warehouse_meta SET value=? WHERE key=?",
                    (str(SCHEMA_VERSION), "schema_version"),
                )
            self._conn.commit()

    @property
    def schema_version(self) -> int:
        with self._lock:
            row = self._conn.execute(
                "SELECT value FROM warehouse_meta WHERE key=?",
                ("schema_version",),
            ).fetchone()
        return int(row[0]) if row else 0

    # -- runs --------------------------------------------------------------
    def register_run(
        self,
        job_uid: str,
        run: str = "",
        attempt: int = 0,
        config: Optional[dict] = None,
        versions: Optional[dict] = None,
        fingerprint: Optional[str] = None,
    ) -> str:
        """Upsert one run row; returns its fingerprint."""
        config = dict(config or {})
        fp = fingerprint or config_fingerprint(config)
        now = time.time()
        with self._lock:
            self._conn.execute(
                "INSERT INTO runs (job_uid, run, attempt, fingerprint, "
                "config, versions, started, updated) "
                "VALUES (?, ?, ?, ?, ?, ?, ?, ?) "
                "ON CONFLICT(job_uid, run, attempt) DO UPDATE SET "
                "fingerprint=excluded.fingerprint, config=excluded.config, "
                "versions=excluded.versions, updated=excluded.updated",
                (job_uid, run, int(attempt), fp, json.dumps(config),
                 json.dumps(dict(versions or {})), now, now),
            )
            self._conn.commit()
        return fp

    def update_run_config(
        self, job_uid: str, patch: dict, run: str = "", attempt: int = 0
    ) -> str:
        """Merge ``patch`` into the run's config (top-level keys) and
        refresh the fingerprint.  Creates the run row if absent — config
        often trickles in after the first telemetry batch."""
        with self._lock:
            row = self._conn.execute(
                "SELECT config FROM runs WHERE job_uid=? AND run=? "
                "AND attempt=?",
                (job_uid, run, int(attempt)),
            ).fetchone()
        config = json.loads(row[0]) if row else {}
        config.update(patch or {})
        return self.register_run(
            job_uid, run=run, attempt=attempt, config=config
        )

    def get_run(
        self, job_uid: str, run: str = "", attempt: int = 0
    ) -> Optional[dict]:
        with self._lock:
            row = self._conn.execute(
                "SELECT job_uid, run, attempt, fingerprint, config, "
                "versions, started, updated FROM runs WHERE job_uid=? "
                "AND run=? AND attempt=?",
                (job_uid, run, int(attempt)),
            ).fetchone()
        return self._run_row(row) if row else None

    def runs(self, job_uid: str = "") -> List[dict]:
        q = ("SELECT job_uid, run, attempt, fingerprint, config, versions,"
             " started, updated FROM runs")
        args: list = []
        if job_uid:
            q += " WHERE job_uid=?"
            args.append(job_uid)
        q += " ORDER BY started"
        with self._lock:
            rows = self._conn.execute(q, args).fetchall()
        return [self._run_row(r) for r in rows]

    @staticmethod
    def _run_row(row) -> dict:
        return {
            "job_uid": row[0],
            "run": row[1],
            "attempt": row[2],
            "fingerprint": row[3],
            "config": json.loads(row[4]),
            "versions": json.loads(row[5]),
            "started": row[6],
            "updated": row[7],
        }

    # -- writers -----------------------------------------------------------
    def _add(
        self,
        job_uid: str,
        kind: str,
        t: Optional[float] = None,
        run: str = "",
        attempt: int = 0,
        rank: str = "",
        trigger: str = "",
        value: Optional[float] = None,
        payload: Optional[dict] = None,
    ):
        if kind not in RECORD_KINDS:
            raise ValueError(f"unknown warehouse record kind {kind!r}")
        ts = _coerce_ts(t)
        with self._lock:
            self._conn.execute(
                "INSERT INTO records (job_uid, run, attempt, kind, t, "
                "rank, trigger, value, payload) "
                "VALUES (?, ?, ?, ?, ?, ?, ?, ?, ?)",
                (job_uid, run, int(attempt), kind,
                 ts if ts is not None else time.time(), str(rank),
                 trigger, value, json.dumps(payload or {}, default=str)),
            )
            self._conn.commit()

    def add_goodput_summary(
        self,
        job_uid: str,
        summary: dict,
        run: str = "",
        attempt: int = 0,
        t: Optional[float] = None,
    ):
        """One interval summary from the online accountant
        (``GoodputAccountant.summary(detail=False)`` shape)."""
        payload = {
            "goodput_pct": summary.get("goodput_pct"),
            "window_s": summary.get("window_s"),
            "phases": summary.get("phases", {}),
            "ranks": len(summary.get("ranks", {}) or {}),
            "events_ingested": summary.get("events_ingested", 0),
        }
        self._add(
            job_uid, "goodput", t=t, run=run, attempt=attempt,
            value=summary.get("goodput_pct"), payload=payload,
        )

    def add_incident(
        self,
        job_uid: str,
        trigger: str,
        reason: str = "",
        nodes: Optional[list] = None,
        run: str = "",
        attempt: int = 0,
        t: Optional[float] = None,
        extra: Optional[dict] = None,
    ):
        """``extra`` rides in the payload — the gateway attaches each
        ``serve_scale`` decision's full input snapshot (backlog, burn
        state, forecast term, dwell/cooldown timers) through it."""
        payload = {
            "reason": reason,
            "nodes": [list(n) for n in nodes or []],
        }
        if extra:
            payload.update(extra)
        self._add(
            job_uid, "incident", t=t, run=run, attempt=attempt,
            trigger=trigger, payload=payload,
        )

    def add_step_phase(
        self,
        job_uid: str,
        phases: dict,
        rank: str = "",
        run: str = "",
        attempt: int = 0,
        t: Optional[float] = None,
    ):
        """``phases``: data_wait_s/dispatch_s/device_s/total_s seconds."""
        self._add(
            job_uid, "step_phase", t=t, run=run, attempt=attempt,
            rank=str(rank), value=phases.get("total_s"), payload=phases,
        )

    def add_memory_watermark(
        self,
        job_uid: str,
        peak_bytes: float,
        rank: str = "",
        run: str = "",
        attempt: int = 0,
        t: Optional[float] = None,
        detail: Optional[dict] = None,
    ):
        self._add(
            job_uid, "device_mem", t=t, run=run, attempt=attempt,
            rank=str(rank), value=float(peak_bytes), payload=detail or {},
        )

    def add_perf_entry(
        self, job_uid: str, entry: dict, run: str = "", attempt: int = 0
    ):
        """One perf-ledger entry (``perf_history.jsonl`` shape)."""
        self._add(
            job_uid, "perf", t=entry.get("ts"), run=run, attempt=attempt,
            trigger=str(entry.get("source", "")),
            value=entry.get("tokens_per_sec"), payload=entry,
        )

    def add_kv_summary(
        self, job_uid: str, entry: dict, run: str = "", attempt: int = 0
    ):
        """One embedding-service summary (``kind: "kv"`` line of the
        program's perf history).  Value is the headline rows/s of
        whichever source produced it, so the trend query can plot a
        single capacity line per source."""
        value = None
        for k in ("aggregate_rows_per_s", "contended_gather_rows_per_s",
                  "gather_rows_per_s", "hot_key_skew"):
            if entry.get(k) is not None:
                value = float(entry[k])
                break
        self._add(
            job_uid, "kv", t=entry.get("ts"), run=run, attempt=attempt,
            trigger=str(entry.get("source", "")), value=value,
            payload=entry,
        )

    def add_serve_summary(
        self, job_uid: str, entry: dict, run: str = "", attempt: int = 0
    ):
        """One serving summary (``kind: "serve"`` line of the program's
        perf history).  Value is the gateway's
        generated tokens/s, the headline the trend query plots; the
        legacy-engine baseline and servput numbers ride in the
        payload."""
        value = None
        for k in ("gateway_tokens_per_sec", "tokens_per_sec"):
            if entry.get(k) is not None:
                value = float(entry[k])
                break
        self._add(
            job_uid, "serve", t=entry.get("ts"), run=run, attempt=attempt,
            trigger=str(entry.get("source", "")), value=value,
            payload=entry,
        )

    def add_traffic_summary(
        self, job_uid: str, entry: dict, run: str = "", attempt: int = 0
    ):
        """One gateway traffic window (``kind: "traffic"`` — the pump's
        per-window arrival summary: requests, prompt+budget tokens and
        the derived tokens/s).  Value is the window's token arrival
        rate, the line the forecast fitter and trend query read."""
        value = entry.get("tokens_per_sec")
        if value is None:
            tokens = entry.get("tokens")
            window = entry.get("window_s")
            if (isinstance(tokens, (int, float))
                    and isinstance(window, (int, float)) and window > 0):
                value = float(tokens) / float(window)
        self._add(
            job_uid, "traffic", t=entry.get("ts"), run=run,
            attempt=attempt, trigger=str(entry.get("source", "gateway")),
            value=float(value) if value is not None else None,
            payload=entry,
        )

    def add_slo_record(
        self, job_uid: str, entry: dict, run: str = "", attempt: int = 0,
        trigger: str = "",
    ):
        """One error-budget account (``kind: "slo"`` — the SLO engine's
        :meth:`~dlrover_tpu.telemetry.slo.SloEngine.snapshot` shape,
        optionally with the burn alert that forced the write).  Value is
        the worst budget-remaining fraction across objectives, so the
        trend query plots the tightest budget as a single line."""
        value = None
        slos = entry.get("slos") or {}
        for s in slos.values():
            rem = (s.get("budget") or {}).get("remaining")
            if rem is not None:
                value = rem if value is None else min(value, float(rem))
        self._add(
            job_uid, "slo", t=entry.get("ts"), run=run, attempt=attempt,
            trigger=trigger, value=value, payload=entry,
        )

    def add_fleet_snapshot(
        self, job_uid: str, entry: dict, run: str = "", attempt: int = 0
    ):
        """One federated fleet snapshot (``kind: "fleet"`` — the
        observer daemon's ``/fleetz.json`` shape).  Value is the number
        of live (non-stale) scraped sources, so the trend query plots
        fleet coverage as a single line; canary and anomaly state ride
        in the payload."""
        sources = entry.get("sources") or []
        live = sum(1 for s in sources if not s.get("stale"))
        self._add(
            job_uid, "fleet", t=entry.get("ts"), run=run,
            attempt=attempt, trigger=str(entry.get("observer", "")),
            value=float(live), payload=entry,
        )

    def add_records(self, job_uid: str, records: List[dict]) -> int:
        """Batch-insert generic record dicts (the Brain RPC ingestion
        path: ``comm.BrainWarehouseBatch``).  Unknown kinds are dropped,
        not raised — a newer master must not wedge an older Brain."""
        rows = []
        now = time.time()
        for rec in records:
            if not isinstance(rec, dict):
                continue
            kind = rec.get("kind")
            if kind not in RECORD_KINDS:
                continue
            t = _coerce_ts(rec.get("t"))
            rows.append((
                job_uid, str(rec.get("run", "")),
                int(rec.get("attempt", 0) or 0), kind,
                t if t is not None else now,
                str(rec.get("rank", "")), str(rec.get("trigger", "")),
                rec.get("value"),
                json.dumps(rec.get("payload") or {}, default=str),
            ))
        if rows:
            with self._lock:
                self._conn.executemany(
                    "INSERT INTO records (job_uid, run, attempt, kind, t,"
                    " rank, trigger, value, payload)"
                    " VALUES (?, ?, ?, ?, ?, ?, ?, ?, ?)",
                    rows,
                )
                self._conn.commit()
        return len(rows)

    # -- batched ingestion (the master servicer's telemetry RPC path) ------
    def ingest_events(
        self,
        job_uid: str,
        events: Iterable[dict],
        run: Optional[str] = None,
        attempt: Optional[int] = None,
    ) -> Dict[str, int]:
        """Batch-ingest telemetry events; only the durable kinds land
        (step-phase distributions, their piggybacked memory watermarks,
        and verdict annotations).  Step/span/goodput-phase events stay
        in the JSONL streams — the warehouse stores *summaries*, not the
        raw feed.  Returns per-kind insert counts."""
        counts = {"step_phase": 0, "device_mem": 0, "incident": 0}
        rows = []
        for e in events:
            if not isinstance(e, dict):
                continue
            ev = e.get("ev")
            e_run = run if run is not None else str(e.get("run", "") or "")
            e_att = (
                attempt if attempt is not None
                else int(e.get("attempt", 0) or 0)
            )
            rank = f"{e.get('role', '')}{e.get('rank', '')}"
            t = e.get("t")
            if ev == "step_phase":
                phases = {
                    k: e.get(k)
                    for k in ("data_wait_s", "dispatch_s", "device_s",
                              "total_s", "step")
                    if e.get(k) is not None
                }
                rows.append((job_uid, e_run, e_att, "step_phase", t, rank,
                             "", e.get("total_s"), json.dumps(phases)))
                counts["step_phase"] += 1
                mem = e.get("mem_peak_bytes")
                if mem is not None:
                    rows.append(
                        (job_uid, e_run, e_att, "device_mem", t, rank, "",
                         float(mem),
                         json.dumps({"devices": e.get("mem_devices", 0)}))
                    )
                    counts["device_mem"] += 1
            elif ev == "verdict":
                rows.append(
                    (job_uid, e_run, e_att, "incident", t, rank,
                     str(e.get("action", "")),
                     None,
                     json.dumps({"reason": e.get("reason", ""),
                                 "nodes": e.get("nodes", [])}))
                )
                counts["incident"] += 1
        if rows:
            now = time.time()
            rows = [
                (j, r, a, k,
                 _coerce_ts(t) if _coerce_ts(t) is not None else now,
                 rk, tr, v, p)
                for (j, r, a, k, t, rk, tr, v, p) in rows
            ]
            with self._lock:
                self._conn.executemany(
                    "INSERT INTO records (job_uid, run, attempt, kind, t,"
                    " rank, trigger, value, payload)"
                    " VALUES (?, ?, ?, ?, ?, ?, ?, ?, ?)",
                    rows,
                )
                self._conn.commit()
        return counts

    # -- read-side queries (ROADMAP item 3's warm-start surface) -----------
    def records(
        self,
        job_uid: str = "",
        kind: str = "",
        limit: int = 1000,
        since: float = 0.0,
    ) -> List[dict]:
        q = ("SELECT job_uid, run, attempt, kind, t, rank, trigger, value,"
             " payload FROM records WHERE t>=?")
        args: list = [since]
        if job_uid:
            q += " AND job_uid=?"
            args.append(job_uid)
        if kind:
            q += " AND kind=?"
            args.append(kind)
        q += " ORDER BY t DESC LIMIT ?"
        args.append(limit)
        with self._lock:
            rows = self._conn.execute(q, args).fetchall()
        out = []
        for r in reversed(rows):  # chronological
            out.append({
                "job_uid": r[0], "run": r[1], "attempt": r[2], "kind": r[3],
                "t": r[4], "rank": r[5], "trigger": r[6], "value": r[7],
                "payload": json.loads(r[8]),
            })
        return out

    def history(self, fingerprint: str) -> List[dict]:
        """All runs sharing a config fingerprint, each annotated with its
        outcome aggregates — the cross-job signal a new job mines."""
        out = []
        for run in self.runs():
            if run["fingerprint"] != fingerprint:
                continue
            out.append(self._annotate_run(run))
        return out

    def _annotate_run(self, run: dict) -> dict:
        job, r, a = run["job_uid"], run["run"], run["attempt"]
        with self._lock:
            gp = self._conn.execute(
                "SELECT AVG(value), MAX(t) FROM records WHERE job_uid=? "
                "AND run=? AND attempt=? AND kind='goodput' "
                "AND value IS NOT NULL",
                (job, r, a),
            ).fetchone()
            last_gp = self._conn.execute(
                "SELECT value FROM records WHERE job_uid=? AND run=? "
                "AND attempt=? AND kind='goodput' AND value IS NOT NULL "
                "ORDER BY t DESC LIMIT 1",
                (job, r, a),
            ).fetchone()
            perf = self._conn.execute(
                "SELECT MAX(value) FROM records WHERE job_uid=? AND run=? "
                "AND attempt=? AND kind='perf' AND value IS NOT NULL",
                (job, r, a),
            ).fetchone()
            incidents = self._conn.execute(
                "SELECT COUNT(*) FROM records WHERE job_uid=? AND run=? "
                "AND attempt=? AND kind='incident'",
                (job, r, a),
            ).fetchone()
        out = dict(run)
        out["goodput_avg"] = (
            round(gp[0], 2) if gp and gp[0] is not None else None
        )
        out["goodput_last"] = (
            round(last_gp[0], 2) if last_gp and last_gp[0] is not None
            else None
        )
        out["best_tokens_per_sec"] = perf[0] if perf else None
        out["incidents"] = incidents[0] if incidents else 0
        return out

    def best_known_config(self, fingerprint: str) -> Optional[dict]:
        """The historical config (+ provenance) of the best-scoring run
        with this fingerprint: highest tokens/s where perf history
        exists, else highest average goodput.  None when no history."""
        best, best_score, best_source = None, None, ""
        for h in self.history(fingerprint):
            if h["best_tokens_per_sec"] is not None:
                score, source = h["best_tokens_per_sec"], "tokens_per_sec"
            elif h["goodput_avg"] is not None:
                # Goodput scores in [0,100]; any real tokens/s measurement
                # outranks it so mixed histories prefer perf evidence.
                score, source = h["goodput_avg"], "goodput_pct"
            else:
                continue
            key = (source == "tokens_per_sec", score)
            if best_score is None or key > best_score:
                best_score, best, best_source = key, h, source
        if best is None:
            return None
        return {
            "config": best["config"],
            "job_uid": best["job_uid"],
            "run": best["run"],
            "attempt": best["attempt"],
            "fingerprint": fingerprint,
            "score": best_score[1],
            "score_source": best_source,
            "goodput_avg": best["goodput_avg"],
            "incidents": best["incidents"],
        }

    def goodput_trend(self, job_uid: str, limit: int = 500) -> List[dict]:
        recs = self.records(job_uid=job_uid, kind="goodput", limit=limit)
        return [
            {"t": r["t"], "goodput_pct": r["value"],
             "window_s": r["payload"].get("window_s")}
            for r in recs
        ]

    def incident_frequency(self, job_uid: str = "") -> Dict[str, int]:
        q = ("SELECT trigger, COUNT(*) FROM records WHERE kind='incident'")
        args: list = []
        if job_uid:
            q += " AND job_uid=?"
            args.append(job_uid)
        q += " GROUP BY trigger ORDER BY COUNT(*) DESC"
        with self._lock:
            rows = self._conn.execute(q, args).fetchall()
        return {r[0] or "(unknown)": r[1] for r in rows}

    def straggler_offenders(self) -> Dict[str, int]:
        """Node → repeat count across straggler/perf incidents; the
        fleet's "same rank 3 jobs in a row" signal."""
        out: Dict[str, int] = {}
        for rec in self.records(kind="incident", limit=10000):
            if rec["trigger"] not in _OFFENDER_TRIGGERS:
                continue
            for node in rec["payload"].get("nodes", []):
                try:
                    name = f"{node[0]}{node[1]}"
                except (IndexError, TypeError):
                    name = str(node)
                out[name] = out.get(name, 0) + 1
        return dict(
            sorted(out.items(), key=lambda kv: kv[1], reverse=True)
        )

    def perf_trend(self, limit: int = 1000) -> List[dict]:
        out = []
        for rec in self.records(kind="perf", limit=limit):
            p = rec["payload"]
            out.append({
                "t": rec["t"],
                "job_uid": rec["job_uid"],
                "run": rec["run"],
                "round": p.get("round", rec["run"]),
                "source": p.get("source", rec["trigger"]),
                "backend": p.get("backend"),
                "tokens_per_sec": rec["value"],
                "mfu": p.get("mfu"),
                "measured": p.get("measured"),
                "blind": p.get("blind"),
            })
        return out

    def kv_trend(self, limit: int = 1000) -> List[dict]:
        """Embedding-service capacity across rounds: one row per kv
        record, keyed by bench source.  Reshard drills carry recovery
        stats instead of a rows/s value."""
        out = []
        for rec in self.records(kind="kv", limit=limit):
            p = rec["payload"]
            row = {
                "t": rec["t"],
                "job_uid": rec["job_uid"],
                "run": rec["run"],
                "source": p.get("source", rec["trigger"]),
                "rows_per_s": rec["value"],
                "shards": p.get("shards"),
                "scaling_vs_1shard": p.get("scaling_vs_1shard"),
                "measured": p.get("measured"),
            }
            if p.get("event") == "reshard_drill":
                row.update({
                    "event": "reshard_drill",
                    "recovery_s": p.get("recovery_s"),
                    "lost_rows": p.get("lost_rows"),
                })
            out.append(row)
        return out

    def kv_hot_keys(self, limit: int = 100) -> List[dict]:
        """Per-shard hot-key skew rows (``source: "hot_keys"``) — the
        input Brain-driven shard splitting reads: which owner is
        saturated by a zipfian head, and by how much."""
        out = []
        for rec in self.records(kind="kv", limit=limit):
            p = rec["payload"]
            if p.get("source") != "hot_keys":
                continue
            out.append({
                "t": rec["t"],
                "job_uid": rec["job_uid"],
                "run": rec["run"],
                "owner": p.get("owner"),
                "rows": p.get("rows"),
                "hot_key_skew": p.get("hot_key_skew"),
                "top": (p.get("top") or [])[:8],
            })
        return out

    def serve_trend(self, limit: int = 1000) -> List[dict]:
        """Serving capacity across rounds: one row per serve record,
        keyed by bench source — the gateway's tokens/s next to the
        legacy slot-pool baseline and the servput closure."""
        out = []
        for rec in self.records(kind="serve", limit=limit):
            p = rec["payload"]
            out.append({
                "t": rec["t"],
                "job_uid": rec["job_uid"],
                "run": rec["run"],
                "source": p.get("source", rec["trigger"]),
                "tokens_per_sec": rec["value"],
                "legacy_tokens_per_sec": p.get("legacy_tokens_per_sec"),
                "speedup_vs_legacy": p.get("speedup_vs_legacy"),
                "servput_pct": p.get("servput_pct"),
                "ttft_s": p.get("ttft_s"),
                "tpot_s": p.get("tpot_s"),
                "measured": p.get("measured"),
                "blind": p.get("blind"),
            })
        return out

    def traffic_trend(self, job_uid: str = "",
                      limit: int = 1000) -> List[dict]:
        """Token arrival rate over time: one row per recorded gateway
        window — the shape the forecast fitter replays and the
        "Traffic shape" report section plots."""
        out = []
        for rec in self.records(job_uid=job_uid, kind="traffic",
                                limit=limit):
            p = rec["payload"]
            out.append({
                "t": rec["t"],
                "job_uid": rec["job_uid"],
                "run": rec["run"],
                "source": p.get("source", rec["trigger"]),
                "tokens_per_sec": rec["value"],
                "requests": p.get("requests"),
                "tokens": p.get("tokens"),
                "window_s": p.get("window_s"),
            })
        return out

    def slo_trend(self, limit: int = 1000) -> List[dict]:
        """Error-budget posture across rounds: one row per slo record —
        the tightest remaining budget, which objective owns it, and
        whether a burn alert forced the write."""
        out = []
        for rec in self.records(kind="slo", limit=limit):
            p = rec["payload"]
            worst = None
            for name, s in (p.get("slos") or {}).items():
                rem = (s.get("budget") or {}).get("remaining")
                if rem is not None and (
                    worst is None or rem < worst[1]
                ):
                    worst = (name, float(rem))
            out.append({
                "t": rec["t"],
                "job_uid": rec["job_uid"],
                "run": rec["run"],
                "budget_remaining": rec["value"],
                "tightest_slo": worst[0] if worst else None,
                "alert": (p.get("alert") or {}).get("slo"),
            })
        return out

    def observer_trend(self, limit: int = 1000) -> List[dict]:
        """Fleet-observer posture across rounds: one row per fleet
        snapshot — scrape coverage, canary failure counts, and how many
        anomaly/divergence verdicts the observer has issued."""
        out = []
        for rec in self.records(kind="fleet", limit=limit):
            p = rec["payload"]
            canaries = p.get("canaries") or []
            counts = p.get("verdict_counts") or {}
            out.append({
                "t": rec["t"],
                "job_uid": rec["job_uid"],
                "run": rec["run"],
                "observer": p.get("observer", rec["trigger"]),
                "live_sources": rec["value"],
                "canary_probes": sum(
                    c.get("probes", 0) for c in canaries
                ),
                "canary_failures": sum(
                    c.get("failures", 0) for c in canaries
                ),
                "slo_burning": p.get("slo_burning") or [],
                "anomalies": counts.get("anomaly", 0),
                "correlated": counts.get("correlated_anomaly", 0),
                "divergences": counts.get("canary_divergence", 0),
            })
        return out

    def fleet_report(self) -> dict:
        """Everything the ``brain report`` CLI renders, as one dict."""
        jobs: Dict[str, Any] = {}
        for run in self.runs():
            job = jobs.setdefault(run["job_uid"], {"runs": []})
            job["runs"].append(self._annotate_run(run))
        for job_uid, job in jobs.items():
            trend = self.goodput_trend(job_uid)
            job["goodput_trend"] = trend[-20:]
            job["goodput_last"] = (
                trend[-1]["goodput_pct"] if trend else None
            )
            job["incidents"] = self.incident_frequency(job_uid)
        return {
            "schema_version": self.schema_version,
            "generated_at": time.time(),
            "db": self.path,
            "jobs": jobs,
            "incident_frequency": self.incident_frequency(),
            "straggler_offenders": self.straggler_offenders(),
            "perf_trend": self.perf_trend(),
            "kv_trend": self.kv_trend(),
            "kv_hot_keys": self.kv_hot_keys(),
            "serve_trend": self.serve_trend(),
            "slo_trend": self.slo_trend(),
            "traffic_trend": self.traffic_trend(),
            "observer_trend": self.observer_trend(),
        }

    # -- backfill (round 1–7 history from the flat files) ------------------
    def ingest_perf_ledger(
        self, path: str, job_uid: str = "perf-ledger"
    ) -> int:
        """Ingest ``perf_history.jsonl`` (torn-line tolerant); one run per
        ledger round so rounds are individually queryable."""
        if not os.path.exists(path):
            return 0
        n = 0
        seen_runs = set()
        with open(path, "r", encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    entry = json.loads(line)
                except ValueError:
                    continue  # torn tail line from a crashed appender
                rnd = str(entry.get("round", ""))
                if rnd not in seen_runs:
                    seen_runs.add(rnd)
                    self.register_run(
                        job_uid, run=rnd,
                        config=self._bench_config(entry),
                    )
                if entry.get("kind") == "kv":
                    self.add_kv_summary(job_uid, entry, run=rnd)
                elif entry.get("kind") == "serve":
                    self.add_serve_summary(job_uid, entry, run=rnd)
                else:
                    self.add_perf_entry(job_uid, entry, run=rnd)
                n += 1
        return n

    def ingest_bench_file(self, path: str, job_uid: str = "bench") -> int:
        """Ingest one ``BENCH_r0N.json`` (bench harness output with an
        optional ``parsed`` block)."""
        try:
            with open(path, "r", encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError):
            return 0
        rnd = os.path.splitext(os.path.basename(path))[0]
        rnd = rnd.replace("BENCH_", "")
        parsed = doc.get("parsed") or {}
        entry = {
            "ts": None,
            "round": rnd,
            "source": "bench",
            "backend": parsed.get("backend"),
            "tokens_per_sec": (
                parsed.get("value")
                if parsed.get("unit") in ("tokens/s", "tokens_per_sec")
                else None
            ),
            "error": parsed.get("error"),
            "vs_baseline": parsed.get("vs_baseline"),
            "mfu": parsed.get("mfu"),
            "n_params": parsed.get("n_params"),
            "measured": bool(parsed),
            "blind": False,
            "rc": doc.get("rc"),
        }
        self.register_run(job_uid, run=rnd, config=self._bench_config(parsed))
        self.add_perf_entry(job_uid, entry, run=rnd)
        return 1

    @staticmethod
    def _bench_config(entry: dict) -> dict:
        cfg = {}
        for k in ("backend", "n_params", "steps"):
            if entry.get(k) is not None:
                cfg[k] = entry[k]
        return cfg

    def backfill(self, root: Optional[str] = None) -> Dict[str, int]:
        """Ingest the repo's flat perf history (``perf_history.jsonl`` +
        ``BENCH_r0*.json``) so rounds 1..N are queryable."""
        root = root or _repo_root()
        counts = {"ledger": 0, "bench": 0}
        counts["ledger"] = self.ingest_perf_ledger(
            os.path.join(root, "perf_history.jsonl")
        )
        for path in sorted(glob.glob(os.path.join(root, "BENCH_r0*.json"))):
            counts["bench"] += self.ingest_bench_file(path)
        return counts

    # -- retention ---------------------------------------------------------
    def clean(
        self,
        max_age_s: float = 90 * 86400,
        max_records_per_job: int = 20000,
        max_traffic_records_per_job: int = 5000,
    ) -> Dict[str, int]:
        """Bounded growth: drop records older than ``max_age_s`` and cap
        each job to its newest ``max_records_per_job`` records; runs with
        no records left and no recent update are compacted away too.
        ``traffic`` windows — the pump writes one per gateway window,
        the highest-volume kind — get their own tighter per-job cap so
        forecast history never crowds out incident/perf records."""
        cutoff = time.time() - max_age_s
        with self._lock:
            records_deleted = self._conn.execute(
                "DELETE FROM records WHERE t < ?", (cutoff,)
            ).rowcount
            for (job_uid,) in self._conn.execute(
                "SELECT DISTINCT job_uid FROM records WHERE kind='traffic'"
            ).fetchall():
                records_deleted += self._conn.execute(
                    "DELETE FROM records WHERE job_uid=? AND "
                    "kind='traffic' AND id NOT IN "
                    "(SELECT id FROM records WHERE job_uid=? AND "
                    "kind='traffic' ORDER BY t DESC LIMIT ?)",
                    (job_uid, job_uid, max_traffic_records_per_job),
                ).rowcount
            for (job_uid,) in self._conn.execute(
                "SELECT DISTINCT job_uid FROM records"
            ).fetchall():
                records_deleted += self._conn.execute(
                    "DELETE FROM records WHERE job_uid=? AND id NOT IN "
                    "(SELECT id FROM records WHERE job_uid=? "
                    "ORDER BY t DESC LIMIT ?)",
                    (job_uid, job_uid, max_records_per_job),
                ).rowcount
            runs_deleted = self._conn.execute(
                "DELETE FROM runs WHERE updated < ? AND job_uid NOT IN "
                "(SELECT DISTINCT job_uid FROM records)",
                (cutoff,),
            ).rowcount
            self._conn.commit()
        if records_deleted or runs_deleted:
            logger.info(
                "warehouse clean: %s records, %s runs",
                records_deleted, runs_deleted,
            )
        return {"records": records_deleted, "runs": runs_deleted}

    def close(self):
        with self._lock:
            self._conn.close()

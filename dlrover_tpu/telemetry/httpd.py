"""Tiny stdlib HTTP endpoint on the master: ``/metrics`` + ``/goodput.json``.

No third-party server, no framework: ``http.server.ThreadingHTTPServer``
on a daemon thread, bound to an ephemeral port by default
(``DLROVER_TELEMETRY_HTTP_PORT`` pins it).  Started by the local and
distributed job masters; the bound address is exported through
``DLROVER_TELEMETRY_HTTP_ADDR`` so in-process harnesses
and co-hosted tooling can discover it without plumbing.

``/metrics``        Prometheus text exposition of the default registry
                    (plus a ``dlrover_telemetry_info`` identity gauge)
``/goodput.json``   the online goodput accountant's live summary
``/diagnosis.json`` the DiagnosisManager's verdict history
``/profile``        start an on-demand jax.profiler trace capture
                    (``?seconds=N`` bounds the window; ``?status=1``
                    reports without starting).  Traces land under
                    ``<telemetry_dir>/profiles/`` so crash bundles
                    include them (telemetry/profiling.py).
``/servz``          the serving gateway's servput summary + queue /
                    KV-block occupancy (when a gateway is attached)
``/generate``       submit one generation request to the attached
                    gateway (``?prompt=1,2,3&budget=32&timeout=30``)
                    and wait for its completion — the smoke-test /
                    ops-probe path, not the bulk ingress
``/trace.json``     reconstruct one sampled request's cross-process
                    timeline (``?id=<trace_id>``; without ``id``, lists
                    recent trace ids) — see docs/TRACING.md
``/slo.json``       the SLO engine's burn-rate / error-budget snapshot
                    (when one is attached)
``/healthz``        serving readiness probe (200 while >=1 live replica
                    takes dispatch, else 503; fleet size, standby
                    count, brownout level and queue depth in the body)
``/statusz``        the discovery handshake: this endpoint's role, pid,
                    rank/uid identity plus the list of paths it serves
                    and their schema versions — what the fleet
                    observer (observer/daemon.py) reads to key a
                    scrape source by (role, uid, pid) incarnation
``/fleetz.json``    the fleet observer's merged cross-process view
                    (when an ObserverDaemon is attached)
``/fleet_metrics``  the merged fleet registry in Prometheus text form
                    (when an ObserverDaemon is attached)
``/``               a one-line index

JSON responses are stamped with ``schema_version``, ``run`` and
``attempt`` so anything archived from these endpoints (debug bundles in
particular) stays self-describing.
"""

import json
import os
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, List, Optional

from dlrover_tpu.common.log import logger
from dlrover_tpu.telemetry import events as _events
from dlrover_tpu.telemetry import metrics as _metrics

ENV_HTTP_PORT = "DLROVER_TELEMETRY_HTTP_PORT"
ENV_HTTP_ADDR = "DLROVER_TELEMETRY_HTTP_ADDR"

# Last goodput summary computed by any server in this process — survives
# server stop so an in-process harness can read the final state after
# the master shuts down.
_last_goodput: Dict[str, Any] = {}
_last_lock = threading.Lock()


def last_goodput() -> Dict[str, Any]:
    with _last_lock:
        return dict(_last_goodput)


def _remember(summary: Dict[str, Any]):
    with _last_lock:
        _last_goodput.clear()
        _last_goodput.update(summary)


def response_stamp() -> Dict[str, Any]:
    """The self-description stamp every JSON endpoint carries."""
    return {
        "schema_version": _events.SCHEMA_VERSION,
        "run": os.environ.get("DLROVER_JOB_UID", ""),
        "attempt": int(os.environ.get("DLROVER_RESTART_COUNT", "0") or 0),
    }


class TelemetryHTTPServer:
    def __init__(
        self,
        registry: Optional["_metrics.MetricsRegistry"] = None,
        goodput_source: Optional[Callable[[], Dict[str, Any]]] = None,
        host: str = "0.0.0.0",
        port: Optional[int] = None,
        diagnosis_source: Optional[Callable[[], List[dict]]] = None,
        serve_sources: Optional[Dict[str, Callable]] = None,
        role: str = "",
        uid: str = "",
    ):
        self._registry = registry or _metrics.REGISTRY
        self._goodput_source = goodput_source
        self._diagnosis_source = diagnosis_source
        # {"servz": () -> dict, "generate": (prompt, budget, timeout)
        #  -> dict} — injected by the serving gateway.  An attached
        # ObserverDaemon adds {"fleetz": () -> dict, "fleet_metrics":
        # () -> str}.
        self._serve_sources = serve_sources or {}
        # /statusz identity: what a federating scraper keys this
        # process's metrics by.  The role default mirrors the event
        # writer's (telemetry/events.py).
        self._role = role or (
            "standby" if os.environ.get("DLROVER_STANDBY_FIFO")
            else "worker"
        )
        self._uid = uid
        self._host = host
        if port is None:
            port = int(os.environ.get(ENV_HTTP_PORT, "0") or 0)
        self._port = port
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None

    @property
    def port(self) -> int:
        if self._httpd is not None:
            return self._httpd.server_address[1]
        return self._port

    @property
    def addr(self) -> str:
        return f"127.0.0.1:{self.port}"

    def start(self) -> str:
        import os

        if self._httpd is not None:
            return self.addr
        server = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # noqa: A003 — stay quiet
                pass

            def _send(self, code: int, body: bytes, ctype: str):
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):  # noqa: N802 — http.server contract
                path = self.path.split("?", 1)[0]
                try:
                    if path == "/metrics":
                        stamp = response_stamp()
                        info = (
                            "# TYPE dlrover_telemetry_info gauge\n"
                            "dlrover_telemetry_info{"
                            f'schema_version="{stamp["schema_version"]}",'
                            f'run="{stamp["run"]}",'
                            f'attempt="{stamp["attempt"]}"'
                            "} 1\n"
                        )
                        body = (
                            server._registry.render() + info
                        ).encode()
                        self._send(
                            200, body,
                            "text/plain; version=0.0.4; charset=utf-8",
                        )
                    elif path == "/goodput.json":
                        summary = server._goodput()
                        self._send(
                            200,
                            json.dumps(summary).encode(),
                            "application/json",
                        )
                    elif path == "/diagnosis.json":
                        body = json.dumps(server._diagnosis()).encode()
                        self._send(200, body, "application/json")
                    elif path == "/profile":
                        code, payload = server._profile(self.path)
                        self._send(
                            code,
                            json.dumps(payload).encode(),
                            "application/json",
                        )
                    elif path == "/servz":
                        code, payload = server._servz()
                        self._send(
                            code,
                            json.dumps(payload).encode(),
                            "application/json",
                        )
                    elif path == "/generate":
                        code, payload = server._generate(self.path)
                        self._send(
                            code,
                            json.dumps(payload).encode(),
                            "application/json",
                        )
                    elif path == "/trace.json":
                        code, payload = server._trace(self.path)
                        self._send(
                            code,
                            json.dumps(payload).encode(),
                            "application/json",
                        )
                    elif path == "/healthz":
                        code, payload = server._healthz()
                        self._send(
                            code,
                            json.dumps(payload).encode(),
                            "application/json",
                        )
                    elif path == "/slo.json":
                        code, payload = server._slo()
                        self._send(
                            code,
                            json.dumps(payload).encode(),
                            "application/json",
                        )
                    elif path == "/statusz":
                        self._send(
                            200,
                            json.dumps(server.statusz()).encode(),
                            "application/json",
                        )
                    elif path == "/fleetz.json":
                        code, payload = server._fleetz()
                        self._send(
                            code,
                            json.dumps(payload).encode(),
                            "application/json",
                        )
                    elif path == "/fleet_metrics":
                        src = server._serve_sources.get("fleet_metrics")
                        if src is None:
                            self._send(
                                404, b"no observer attached\n",
                                "text/plain",
                            )
                        else:
                            self._send(
                                200, str(src()).encode(),
                                "text/plain; version=0.0.4; "
                                "charset=utf-8",
                            )
                    elif path == "/":
                        self._send(
                            200,
                            b"dlrover_tpu telemetry: /metrics "
                            b"/goodput.json /diagnosis.json /profile "
                            b"/servz /generate /trace.json /slo.json "
                            b"/healthz /statusz\n",
                            "text/plain",
                        )
                    else:
                        self._send(404, b"not found\n", "text/plain")
                except Exception as e:  # noqa: BLE001 — keep serving
                    try:
                        self._send(
                            500, f"error: {e}\n".encode(), "text/plain"
                        )
                    except OSError:
                        pass

        self._httpd = ThreadingHTTPServer((self._host, self._port), Handler)
        self._httpd.daemon_threads = True
        self._thread = threading.Thread(
            target=self._httpd.serve_forever,
            name="telemetry-http",
            daemon=True,
        )
        self._thread.start()
        os.environ[ENV_HTTP_ADDR] = self.addr
        logger.info("telemetry HTTP endpoint on %s", self.addr)
        return self.addr

    def _goodput(self) -> Dict[str, Any]:
        summary = dict(response_stamp())
        if self._goodput_source is not None:
            summary.update(self._goodput_source() or {})
        _remember(summary)
        return summary

    def _diagnosis(self) -> Dict[str, Any]:
        out = dict(response_stamp())
        verdicts: List[dict] = []
        if self._diagnosis_source is not None:
            verdicts = list(self._diagnosis_source() or [])
        out["verdicts"] = verdicts
        return out

    def _profile(self, raw_path: str):
        """GET /profile[?seconds=N][&status=1] → (http code, payload)."""
        from urllib.parse import parse_qs, urlsplit

        from dlrover_tpu.telemetry import profiling as _profiling

        qs = parse_qs(urlsplit(raw_path).query)
        out = dict(response_stamp())
        if "status" in qs:
            out.update(_profiling.trace_status())
            return 200, out
        try:
            seconds = float(qs.get("seconds", ["5"])[0])
        except ValueError:
            out.update(ok=False, error="bad seconds value")
            return 400, out
        result = _profiling.capture_trace(seconds)
        out.update(result)
        if result.get("ok"):
            return 200, out
        if result.get("error") == "trace already active":
            return 409, out
        return 500, out

    def _servz(self):
        out = dict(response_stamp())
        src = self._serve_sources.get("servz")
        if src is None:
            out["error"] = "no serving gateway attached"
            return 404, out
        out.update(src() or {})
        return 200, out

    def _generate(self, raw_path: str):
        """GET /generate?prompt=1,2,3[&budget=N][&timeout=S] — submit to
        the attached gateway and block (bounded) for the completion."""
        from urllib.parse import parse_qs, urlsplit

        out = dict(response_stamp())
        src = self._serve_sources.get("generate")
        if src is None:
            out["error"] = "no serving gateway attached"
            return 404, out
        qs = parse_qs(urlsplit(raw_path).query)
        try:
            prompt = [
                int(tok) for tok in qs.get("prompt", [""])[0].split(",")
                if tok.strip() != ""
            ]
            budget = int(qs.get("budget", ["32"])[0])
            timeout = float(qs.get("timeout", ["60"])[0])
        except ValueError:
            out.update(ok=False, error="bad prompt/budget/timeout")
            return 400, out
        if not prompt:
            out.update(ok=False, error="empty prompt")
            return 400, out
        result = src(prompt, budget, timeout)
        out.update(result)
        if result.get("shed"):
            return 429, out
        return (200 if result.get("ok") else 500), out

    def _trace(self, raw_path: str):
        """GET /trace.json?id=<trace_id> — reconstruct one sampled
        request's cross-process timeline.  Without ``id``, lists the
        trace ids currently in the in-process ring buffer."""
        from urllib.parse import parse_qs, urlsplit

        from dlrover_tpu.telemetry import tracing as _tracing

        out = dict(response_stamp())
        qs = parse_qs(urlsplit(raw_path).query)
        trace_id = qs.get("id", [""])[0].strip()
        src = self._serve_sources.get("trace")
        if not trace_id:
            out["recent_trace_ids"] = _tracing.recent_trace_ids()
            return 200, out
        result = (
            src(trace_id) if src is not None
            else _tracing.reconstruct(trace_id)
        )
        out.update(result or {})
        return (200 if out.get("found") else 404), out

    def _healthz(self):
        """GET /healthz — load-balancer readiness probe for the
        attached serving gateway: 200 while at least one live replica
        takes dispatch, 503 otherwise (fleet size, standby count,
        brownout level and queue depth ride the payload)."""
        out = dict(response_stamp())
        src = self._serve_sources.get("healthz")
        if src is None:
            out["error"] = "no serving gateway attached"
            return 404, out
        out.update(src() or {})
        return (200 if out.get("ready") else 503), out

    def _slo(self):
        out = dict(response_stamp())
        src = self._serve_sources.get("slo")
        if src is None:
            out["error"] = "no SLO engine attached"
            return 404, out
        out.update(src() or {})
        return 200, out

    def _fleetz(self):
        out = dict(response_stamp())
        src = self._serve_sources.get("fleetz")
        if src is None:
            out["error"] = "no observer attached"
            return 404, out
        out.update(src() or {})
        return 200, out

    def statusz(self) -> Dict[str, Any]:
        """GET /statusz — the observer's discovery handshake: identity
        (role / uid / pid / rank), schema versions, and the endpoint
        paths this httpd actually serves given what is attached."""
        out = dict(response_stamp())
        endpoints = [
            "/metrics", "/goodput.json", "/diagnosis.json", "/profile",
            "/trace.json", "/statusz",
        ]
        for key, ep in (
            ("servz", "/servz"), ("generate", "/generate"),
            ("healthz", "/healthz"), ("slo", "/slo.json"),
            ("fleetz", "/fleetz.json"),
            ("fleet_metrics", "/fleet_metrics"),
        ):
            if key in self._serve_sources:
                endpoints.append(ep)
        out.update(
            role=self._role,
            uid=self._uid,
            pid=os.getpid(),
            rank=int(os.environ.get("DLROVER_PROCESS_ID", "0") or 0),
            endpoints=endpoints,
            schema_versions={
                "events": _events.SCHEMA_VERSION,
                "metrics_exposition": "0.0.4",
            },
        )
        return out

    def stop(self):
        # Snapshot the final accountant state first: in-process callers
        # (the goodput harness) read it after the master is gone.
        try:
            self._goodput()
        except Exception:  # noqa: BLE001 — stopping regardless
            pass
        if self._httpd is not None:
            try:
                self._httpd.shutdown()
                self._httpd.server_close()
            except OSError:
                pass
            self._httpd = None
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

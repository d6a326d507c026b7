"""Inference gateway: admission control, commit journal, replica-fleet
supervision, servput accounting.

The gateway owns everything the decode engine must not care about:

* **admission control** — a token budget bounds the queue (prompt +
  budget tokens); past it, new requests are shed 429-style instead of
  building unbounded latency.  Per-request deadlines expire queued
  requests (shed) and cut off running ones (partial completion,
  ``finished_reason="deadline"``).
* **commit journal** — every token a replica reports is journaled
  per-request *before* it is client-visible.  The journal is the
  replay source of truth: when a decode worker dies (SIGKILL — no
  goodbye), its in-flight requests re-queue with ``prompt = original
  prompt + committed tokens`` and the SAME total budget, so the
  replacement worker resumes from the last committed token with zero
  lost and zero duplicated completions
  (``tests/test_serving_gateway.py``'s chaos drill).
* **fleet supervision** — replicas come from a factory and live in a
  :class:`~dlrover_tpu.serving.fleet.ReplicaSet`: N live replicas take
  least-loaded dispatch, K warm standbys wait pre-spawned so a death
  is repaired by sub-second *promotion* instead of a cold spawn.
  Health checking goes beyond ``alive()`` — consecutive poll failures
  against a live process (``serve_heartbeat_drop``) and
  wedged-but-alive workers whose engine stops ticking under load
  (``serve_replica_wedge``) eject the replica with a durable
  ``verdict`` event the doctor attributes.  An optional
  :class:`~dlrover_tpu.serving.fleet.FleetAutoscaler` resizes the
  fleet off the queue gauge + burning SLOs, and an optional
  :class:`~dlrover_tpu.serving.fleet.BrownoutController` walks the
  degradation ladder (budget caps → no prefix publish → priority
  shed) when capacity loss outruns the fleet.
* **servput** — every pump tick is classified into one of the five
  :data:`~dlrover_tpu.telemetry.servput.SERVE_PHASES` and noted into a
  :class:`~dlrover_tpu.telemetry.servput.ServputAccountant`; state
  transitions are emitted as ``serve_state`` telemetry events so the
  doctor reprices the same timeline offline.  Prometheus metrics
  (TTFT, TPOT, tokens, queue depth, KV-block occupancy, fleet and
  brownout gauges) publish into the default registry the master's
  ``/metrics`` endpoint serves.

The HTTP face (``/generate``, ``/servz``, ``/healthz``) plugs into the
telemetry httpd via :meth:`InferenceGateway.http_sources`.
"""

import collections
import json
import os
import subprocess
import sys
import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from dlrover_tpu.common import comm
from dlrover_tpu.common.faults import fault_point
from dlrover_tpu.common.log import logger
from dlrover_tpu.rpc.transport import TransportClient
from dlrover_tpu.serving.fleet import (
    BROWNOUT_RUNGS,
    ReplicaSet,
    _brownout_gauge,
)
from dlrover_tpu.telemetry import events as _events
from dlrover_tpu.telemetry import metrics as _metrics
from dlrover_tpu.telemetry import tracing as _tracing
from dlrover_tpu.telemetry.servput import ServputAccountant


def _ttft_hist():
    return _metrics.histogram(
        "dlrover_serve_ttft_seconds",
        "Time from submit to first committed token.",
    )


def _tpot_hist():
    return _metrics.histogram(
        "dlrover_serve_tpot_seconds",
        "Per-token latency after the first committed token.",
    )


def _tokens_counter():
    return _metrics.counter(
        "dlrover_serve_tokens_total",
        "Generated tokens committed to the journal.",
    )


def _shed_counter():
    return _metrics.counter(
        "dlrover_serve_shed_total",
        "Requests shed by admission control, by reason.",
    )


def _disruption_counter():
    return _metrics.counter(
        "dlrover_serve_disruptions_total",
        "Decode-replica deaths detected by the gateway.",
    )


def _queue_gauge():
    return _metrics.gauge(
        "dlrover_serve_queue_depth",
        "Requests waiting for a decode slot.",
    )


def _kv_gauge():
    return _metrics.gauge(
        "dlrover_serve_kv_blocks",
        "KV block-pool occupancy across live replicas, by state.",
    )


# ---------------------------------------------------------------------------
# Replicas
# ---------------------------------------------------------------------------


class LocalReplica:
    """In-process replica around a :class:`PagedServingEngine`.

    ``kill()`` drops the engine on the floor (no drain, no goodbye) —
    the in-process analog of SIGKILL for cheap chaos tests.
    """

    def __init__(self, engine, ticks_per_poll: int = 4):
        self._engine = engine
        self._ticks = ticks_per_poll
        self._alive = True
        self.uid = f"local-{uuid.uuid4().hex[:8]}"

    def submit(self, rid: int, prompt: List[int], gen_budget: int,
               orig_prompt_len: int, trace: str = "") -> Tuple[bool, str]:
        try:
            self._engine.submit(
                prompt, gen_budget=gen_budget, request_id=rid,
                orig_prompt_len=orig_prompt_len,
                trace=_tracing.from_wire(trace),
            )
            return True, ""
        except ValueError as e:
            return False, str(e)

    def poll(self) -> Dict[str, Any]:
        completions: List[dict] = []
        for _ in range(self._ticks):
            if not self._engine.has_work():
                break
            for c in self._engine.step():
                completions.append({
                    "request_id": c.request_id,
                    "tokens": list(c.tokens),
                    "prompt_len": c.prompt_len,
                    "finished_reason": c.finished_reason,
                })
        return {
            "emitted": self._engine.pop_emitted(),
            "completions": completions,
            "stats": self._engine.stats(),
        }

    def control(self, publish_prefix: Optional[bool] = None) -> bool:
        """Brownout knobs (fleet.py): currently just prefix-cache
        publishing on/off."""
        setter = getattr(self._engine, "set_prefix_publish", None)
        if publish_prefix is not None and setter is not None:
            setter(bool(publish_prefix))
        return True

    def alive(self) -> bool:
        return self._alive

    def kill(self) -> None:
        self._alive = False
        self._engine = None

    def stop(self) -> None:
        self._alive = False


class ProcessReplica:
    """A decode worker in its own OS process, reached over the 2-RPC
    transport.  Spawn blocks on the worker's ready-file handshake."""

    def __init__(
        self,
        workdir: str,
        worker_args: Optional[Dict[str, Any]] = None,
        spawn_timeout_s: float = 90.0,
        rpc_timeout_s: float = 60.0,
        extra_env: Optional[Dict[str, str]] = None,
    ):
        self.uid = f"proc-{uuid.uuid4().hex[:8]}"
        ready = os.path.join(workdir, f"{self.uid}.ready")
        cmd = [
            sys.executable, "-m", "dlrover_tpu.serving",
            "--ready-file", ready, "--name", self.uid,
        ]
        wargs = dict(worker_args or {})
        # Stream the worker's events/spans into the gateway's telemetry
        # directory so a sampled request's cross-process timeline
        # reconstructs from ONE directory.
        wargs.setdefault(
            "events_dir",
            getattr(_events.get_log(), "_dir", _events.telemetry_dir()),
        )
        for k, v in wargs.items():
            cmd += [f"--{str(k).replace('_', '-')}", str(v)]
        env = dict(os.environ)
        # extra_env reaches the worker before its imports run — the
        # chaos drills arm DLROVER_FAULTS in the child this way.  The
        # platform is inherited, never defaulted: a replica serves from
        # the CPU only where the environment asks for it.
        env.update(extra_env or {})
        self._log = open(os.path.join(workdir, f"{self.uid}.log"), "wb")
        self._proc = subprocess.Popen(
            cmd, env=env, stdout=self._log, stderr=subprocess.STDOUT
        )
        deadline = time.time() + spawn_timeout_s
        while not os.path.exists(ready):
            if self._proc.poll() is not None:
                raise RuntimeError(
                    f"decode worker died during spawn "
                    f"(rc={self._proc.returncode})"
                )
            if time.time() > deadline:
                self._proc.kill()
                raise TimeoutError("decode worker never became ready")
            time.sleep(0.05)
        with open(ready) as f:
            info = json.load(f)
        self.pid = int(info["pid"])
        self.port = int(info["port"])
        # {"platform", "kind", "count"} as the worker's JAX reports it.
        self.device: Dict[str, Any] = dict(info["device"])
        self._client = TransportClient(
            f"127.0.0.1:{self.port}", timeout=rpc_timeout_s
        )

    def submit(self, rid: int, prompt: List[int], gen_budget: int,
               orig_prompt_len: int, trace: str = "") -> Tuple[bool, str]:
        res = self._client.get(0, "gateway", comm.ServeSubmit(
            request_id=rid, prompt=list(prompt), gen_budget=gen_budget,
            orig_prompt_len=orig_prompt_len, trace=trace,
        ))
        return bool(res.accepted), res.reason

    def poll(self) -> Dict[str, Any]:
        p = self._client.get(0, "gateway", comm.ServePoll())
        return {
            "emitted": {int(k): list(v) for k, v in p.emitted.items()},
            "completions": list(p.completions),
            "stats": dict(p.stats),
        }

    def verify(self, tokens: List[int], prompt_len: int) -> Dict[str, Any]:
        """Score a finished completion against the plain forward of the
        worker's own weights (``comm.ServeVerify``)."""
        res = self._client.get(0, "gateway", comm.ServeVerify(
            tokens=list(tokens), prompt_len=int(prompt_len),
        ))
        return {"row_max": list(res.row_max), "margin": list(res.margin)}

    def control(self, publish_prefix: Optional[bool] = None) -> bool:
        flag = -1 if publish_prefix is None else int(bool(publish_prefix))
        res = self._client.get(
            0, "gateway", comm.ServeControl(publish_prefix=flag)
        )
        return bool(res.ok)

    def alive(self) -> bool:
        return self._proc.poll() is None

    def kill(self) -> None:
        try:
            self._proc.kill()  # SIGKILL — no goodbye
            self._proc.wait(timeout=10)
        except OSError:
            pass

    def stop(self) -> None:
        try:
            self._proc.terminate()
            self._proc.wait(timeout=5)
        except (OSError, subprocess.TimeoutExpired):
            self.kill()
        try:
            self._client.close()
        except Exception:  # noqa: BLE001 — best-effort teardown
            pass
        try:
            self._log.close()
        except OSError:
            pass


# ---------------------------------------------------------------------------
# Gateway
# ---------------------------------------------------------------------------


@dataclass
class _GwRequest:
    request_id: int
    prompt: List[int]            # ORIGINAL prompt, never mutated
    gen_budget: int              # total budget across replays
    submitted_at: float
    deadline_at: Optional[float] = None
    committed: List[int] = field(default_factory=list)  # the journal
    state: str = "queued"        # queued | running | done | shed
    finished_reason: str = ""
    replays: int = 0
    # Which replica uid is serving this request (replay re-assigns).
    assigned: str = ""
    # Brownout priority class: rung 3 sheds classes below
    # ``shed_below_priority`` at admission (0 = batch/background).
    priority: int = 1
    first_token_at: Optional[float] = None
    last_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    # Head-sampled trace context (None = unsampled; tracing.py).
    trace: Optional[_tracing.TraceContext] = None
    done_event: threading.Event = field(default_factory=threading.Event)

    def public(self) -> Dict[str, Any]:
        out = {
            "request_id": self.request_id,
            "state": self.state,
            "prompt_len": len(self.prompt),
            "n_gen": len(self.committed),
            "replays": self.replays,
        }
        if self.trace is not None:
            out["trace_id"] = self.trace.trace_id
        if self.state == "done":
            out.update(
                ok=True,
                tokens=list(self.prompt) + list(self.committed),
                finished_reason=self.finished_reason,
            )
        elif self.state == "shed":
            out.update(ok=False, shed=True, reason=self.finished_reason)
        return out


class InferenceGateway:
    """See the module docstring.  ``n_replicas`` live decode workers
    plus ``n_standbys`` warm standbys behind one factory; the standby
    pool is the respawn path."""

    def __init__(
        self,
        replica_factory: Callable[[], Any],
        *,
        max_queue_tokens: int = 4096,
        default_gen_budget: int = 32,
        default_deadline_s: Optional[float] = None,
        eos_id: Optional[int] = None,
        retention_s: Optional[float] = 600.0,
        max_replays: int = 5,
        slo_engine: Optional[Any] = None,
        n_replicas: int = 1,
        n_standbys: int = 0,
        spawn_attempts: int = 3,
        spawn_backoff_s: float = 0.2,
        heartbeat_misses: int = 3,
        wedge_timeout_s: float = 10.0,
        slow_factor: float = 0.0,
        slow_grace_s: float = 1.0,
        autoscaler: Optional[Any] = None,
        brownout: Optional[Any] = None,
        name: str = "gateway",
    ):
        self._max_queue_tokens = int(max_queue_tokens)
        self._default_budget = int(default_gen_budget)
        self._default_deadline = default_deadline_s
        # Must match the engine's eos_id: a reform can then close out
        # a request whose journal already ends in eos instead of
        # replaying it (the replay prompt would embed the eos and the
        # replacement worker would generate past it).
        self._eos_id = eos_id
        # How long done/shed requests stay retrievable via result();
        # None keeps them forever (unbounded memory on a long-running
        # gateway — only for tests/benches).
        self._retention_s = retention_s
        # A request that keeps replaying through reforms is poison (or
        # the fleet is melting) — past the cap it is shed with
        # reason="reform" instead of riding the requeue forever.
        self._max_replays = max(int(max_replays), 1)
        # Optional telemetry/slo.py engine, ticked from the pump so a
        # live gateway evaluates its SLOs without a second thread; its
        # burning() SLOs also feed the autoscaler.
        self._slo = slo_engine
        self.name = name

        self._fleet = ReplicaSet(
            replica_factory,
            target_live=n_replicas,
            target_standby=n_standbys,
            spawn_attempts=spawn_attempts,
            spawn_backoff_s=spawn_backoff_s,
            name=name,
        )
        # A poll failing this many consecutive times against a process
        # that still answers alive() is a dropped heartbeat — eject.
        self._heartbeat_misses = max(int(heartbeat_misses), 1)
        self._wedge_timeout_s = float(wedge_timeout_s)
        self._slow_factor = float(slow_factor)
        self._slow_grace_s = float(slow_grace_s)
        self._autoscaler = autoscaler
        self._brownout = brownout
        self._publish_prefix = True
        # Durable verdict sink (brain/warehouse.py) — attach_warehouse.
        self._warehouse: Optional[Any] = None
        self._job_uid = ""
        # Traffic pump: per-window arrival summaries (requests and
        # prompt+budget tokens), flushed from the tick into the
        # warehouse ``traffic`` kind — the decision plane's forecast
        # history.  Windows flush even when idle: zero-rate windows
        # are real shape data.
        self._traffic_window_s = 10.0
        self._traffic_tokens = 0
        self._traffic_requests = 0
        self._traffic_window_start = time.time()
        self.traffic_windows: List[dict] = []
        # Optional fitted TrafficForecast (brain/decision/forecast.py)
        # — attach_forecast; feeds the autoscaler's predictive term.
        self._forecast: Optional[Any] = None
        self._forecast_lead_s = 30.0

        self._lock = threading.RLock()
        # Serializes ticks; ``_lock`` is only held around state
        # mutation so clients stay responsive during replica
        # spawn/poll (see _tick).
        self._pump_lock = threading.Lock()
        self._requests: Dict[int, _GwRequest] = {}
        self._queue: "collections.deque[int]" = collections.deque()
        self._next_id = 0
        self._reforming = False
        self._last_stats: Dict[str, Any] = {}
        self._prefill_seen: Dict[str, float] = {}

        self.accountant = ServputAccountant()
        self._state: Optional[str] = None
        # In-memory serve_state/serve_request/verdict stream — what the
        # event log would hold; the doctor tests price straight from
        # this.
        self.events: List[dict] = []
        self.disruptions = 0
        self.shed_count = 0
        self.done_count = 0

        self._stop_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None

    @property
    def _replica(self):
        """First live replica — the pre-fleet single-replica view the
        drills poke (``gw._replica.kill()``); None when the fleet is
        empty."""
        live = self._fleet.live_members()
        return live[0].replica if live else None

    @property
    def fleet(self) -> ReplicaSet:
        return self._fleet

    def attach_warehouse(self, warehouse: Any, job_uid: str = "") -> None:
        """Mirror fleet verdicts (promotions, ejections, brownout
        transitions) into the Brain warehouse as incident rows."""
        self._warehouse = warehouse
        self._job_uid = job_uid or self.name

    def attach_forecast(self, forecast: Any,
                        lead_s: float = 30.0,
                        window_s: Optional[float] = None) -> None:
        """Attach a fitted traffic forecast so autoscaling turns
        predictive: each tick the autoscaler also sees the tokens the
        shape expects over the next ``lead_s`` (the warm-up lead), so
        standbys pre-warm ahead of a predicted ramp.  The reactive
        backlog path keeps working unchanged when the forecast is
        detached or errors."""
        self._forecast = forecast
        self._forecast_lead_s = float(lead_s)
        if window_s is not None:
            self._traffic_window_s = float(window_s)

    # -- events / accounting -----------------------------------------------
    def _note(self, state: str, t: Optional[float] = None) -> None:
        t = time.time() if t is None else t
        if state == self._state:
            return
        self._state = state
        self.accountant.note(state, t)
        self.events.append({"ev": "serve_state", "t": t, "state": state})
        _events.emit("serve_state", state=state, gw=self.name)

    def _req_event(self, phase: str, req: _GwRequest, **extra) -> None:
        rec = {
            "ev": "serve_request", "t": time.time(), "phase": phase,
            "rid": req.request_id, "n_gen": len(req.committed),
        }
        rec.update(extra)
        self.events.append(rec)
        _events.emit("serve_request", phase=phase, rid=req.request_id,
                     gw=self.name, **extra)

    def _verdict(self, action: str, reason: str,
                 nodes: Optional[List[list]] = None,
                 t: Optional[float] = None, **extra) -> None:
        """Durable fleet-health verdict: in-memory stream + event log +
        (when attached) a warehouse incident row."""
        t = time.time() if t is None else t
        nodes = [list(n) for n in (nodes or [])]
        rec = {"ev": "verdict", "t": t, "action": action,
               "reason": reason, "nodes": nodes}
        rec.update(extra)
        self.events.append(rec)
        _events.emit("verdict", action=action, reason=reason, nodes=nodes,
                     gw=self.name, **extra)
        if self._warehouse is not None:
            try:
                self._warehouse.add_incident(
                    self._job_uid or self.name, action, reason=reason,
                    nodes=nodes, t=t, extra=extra or None,
                )
            except TypeError:
                # Pre-decision-plane warehouse without ``extra``.
                try:
                    self._warehouse.add_incident(
                        self._job_uid or self.name, action,
                        reason=reason, nodes=nodes, t=t,
                    )
                except Exception as e:  # noqa: BLE001 — sink only
                    logger.warning(
                        "warehouse incident write failed: %s", e
                    )
            except Exception as e:  # noqa: BLE001 — telemetry sink only
                logger.warning("warehouse incident write failed: %s", e)

    def _flush_traffic(self, now: float) -> None:
        """Close the current arrival window when it has run its span:
        one summary row to the in-memory stream and (when attached)
        the warehouse ``traffic`` kind.  Called under ``_lock`` from
        the tick; the warehouse write is a parameterized sqlite insert
        — not blocking host I/O in the DLR011 sense."""
        window = now - self._traffic_window_start
        if window < self._traffic_window_s:
            return
        tokens = self._traffic_tokens
        requests = self._traffic_requests
        self._traffic_tokens = 0
        self._traffic_requests = 0
        self._traffic_window_start = now
        entry = {
            "ts": now,
            "source": self.name,
            "requests": requests,
            "tokens": tokens,
            "window_s": round(window, 3),
            "tokens_per_sec": (
                round(tokens / window, 3) if window > 0 else 0.0
            ),
        }
        self.traffic_windows.append(entry)
        if self._warehouse is not None:
            try:
                self._warehouse.add_traffic_summary(
                    self._job_uid or self.name, entry
                )
            except Exception as e:  # noqa: BLE001 — telemetry sink only
                logger.warning("warehouse traffic write failed: %s", e)

    # -- admission -----------------------------------------------------------
    def _queued_tokens(self) -> int:
        return sum(
            len(self._requests[rid].prompt) + self._requests[rid].gen_budget
            for rid in self._queue
        )

    def submit(
        self,
        prompt: List[int],
        gen_budget: Optional[int] = None,
        deadline_s: Optional[float] = None,
        priority: int = 1,
    ) -> Dict[str, Any]:
        """Admit or shed.  Returns ``{"ok": True, "request_id": rid}``
        or ``{"ok": False, "shed": True, "reason": ...}`` (the httpd
        maps ``shed`` to HTTP 429)."""
        budget = self._default_budget if gen_budget is None else int(gen_budget)
        if deadline_s is None:
            deadline_s = self._default_deadline
        now = time.time()
        with self._lock:
            # Arrival demand for the traffic pump: every submit counts
            # (shed requests are demand too — the forecast must see
            # the load the fleet failed to absorb, not just what it
            # admitted), priced pre-cap like admission's ``need``.
            self._traffic_tokens += len(prompt) + budget
            self._traffic_requests += 1
            level = self._brownout.level if self._brownout is not None else 0
            if level >= 3 and priority < self._brownout.shed_below_priority:
                # Rung 3: shed low-priority classes at the door so the
                # remaining capacity serves interactive traffic.
                self.shed_count += 1
                _shed_counter().inc(reason="brownout")
                rec = {"ev": "serve_request", "t": now, "phase": "shed",
                       "rid": -1, "reason": "brownout"}
                self.events.append(rec)
                _events.emit("serve_request", phase="shed", rid=-1,
                             gw=self.name, reason="brownout")
                return {"ok": False, "shed": True, "reason": "brownout"}
            if level >= 1:
                # Rung 1: cap generation budgets — shorter answers for
                # everyone beats 429s for some.
                budget = min(budget, self._brownout.gen_budget_cap)
            need = len(prompt) + budget
            if self._queued_tokens() + need > self._max_queue_tokens:
                self.shed_count += 1
                _shed_counter().inc(reason="queue_full")
                rec = {"ev": "serve_request", "t": now, "phase": "shed",
                       "rid": -1, "reason": "queue_full"}
                self.events.append(rec)
                _events.emit("serve_request", phase="shed", rid=-1,
                             gw=self.name, reason="queue_full")
                return {"ok": False, "shed": True, "reason": "queue_full"}
            rid = self._next_id
            self._next_id += 1
            req = _GwRequest(
                # int() per token: numpy scalars don't msgpack and the
                # journal must compare == to worker-returned tokens.
                request_id=rid, prompt=[int(t) for t in prompt],
                gen_budget=budget,
                submitted_at=now,
                deadline_at=(
                    (now + deadline_s) if deadline_s is not None else None
                ),
                priority=int(priority),
                trace=_tracing.start_trace(),
            )
            self._requests[rid] = req
            self._queue.append(rid)
            self._req_event("submitted", req, prompt_len=len(prompt),
                            budget=budget)
            _tracing.point(req.trace, "admission", rid=rid,
                           prompt_len=len(prompt), budget=budget)
            out = {"ok": True, "request_id": rid}
            if req.trace is not None:
                out["trace_id"] = req.trace.trace_id
            return out

    def result(self, rid: int) -> Dict[str, Any]:
        with self._lock:
            req = self._requests.get(rid)
            if req is None:
                return {"ok": False, "reason": f"unknown request {rid}"}
            return req.public()

    def get(self, rid: int, timeout_s: float = 60.0) -> Dict[str, Any]:
        """Block until ``rid`` finishes (done or shed).  Pumps inline
        when no background pump thread is running."""
        req = self._requests.get(rid)
        if req is None:
            return {"ok": False, "reason": f"unknown request {rid}"}
        deadline = time.time() + timeout_s
        while not req.done_event.is_set():
            if time.time() > deadline:
                return {"ok": False, "reason": "timeout", **req.public()}
            if self._thread is None:
                self.pump()
            else:
                req.done_event.wait(0.02)
        return req.public()

    # -- the pump ------------------------------------------------------------
    def pump(self, ticks: int = 1) -> None:
        for _ in range(ticks):
            self._tick()

    def _tick(self) -> None:
        # One tick at a time; ``_lock`` is held only around state
        # mutation, so submit()/result()/servz() stay responsive while
        # a replacement replica spawns (up to its spawn timeout) or a
        # poll RPC is in flight, and admission control keeps shedding
        # during a reform instead of queueing clients on the lock.
        with self._pump_lock:
            now = time.time()
            with self._lock:
                self._prune(now)
                # Backlog the tick STARTED with: dispatch drains the
                # queue into the replicas, so the post-dispatch residual
                # reads permanent zero — the brownout/autoscaler
                # pressure signal is the demand that piled up since the
                # last tick.
                backlog_tokens = self._queued_tokens()
                self._flush_traffic(now)
                dead = list(self._fleet.dead_members())
                for m in self._fleet.live_members():
                    if not self._safe_alive(m.replica):
                        dead.append(m)
                for m in dead:
                    self._begin_reform_member(m, now)
            for m in dead:
                try:
                    m.replica.kill()
                except Exception:  # noqa: BLE001 — it is already dead
                    pass
            # Repair the live pool: promotion first (the standby is
            # already spawned — sub-second), cold spawn only when the
            # standby pool is dry.  Spawn failure is no longer
            # terminal: retried (with backoff) inside spawn_blocking,
            # then again next tick.
            repaired = []
            while self._fleet.live_deficit() > 0:
                m = self._fleet.promote(now)
                if m is not None:
                    if not self._safe_alive(m.replica):
                        # The standby died while idle — discard and
                        # try the next one.
                        self._fleet.detach(m)
                        try:
                            m.replica.kill()
                        except Exception:  # noqa: BLE001
                            pass
                        continue
                    repaired.append((m, "promotion"))
                    continue
                try:
                    replica = self._fleet.spawn_blocking()
                except Exception as e:  # noqa: BLE001 — retry next tick
                    logger.warning(
                        "replica spawn failed after retries: %s", e
                    )
                    break
                repaired.append(
                    (self._fleet.attach_live(replica, now), "cold_spawn")
                )
            if self._stop_evt.is_set():
                # stop() already ran while we were spawning; don't
                # leak the replacements.
                for m, _ in repaired:
                    self._fleet.detach(m)
                    try:
                        m.replica.stop()
                    except Exception:  # noqa: BLE001 — teardown
                        pass
                return
            # Top the standby pool back up off-thread — the next death
            # must also find a warm standby.
            self._fleet.replenish_async()
            fresh: List[Any] = []
            with self._lock:
                for m, how in repaired:
                    if how == "promotion":
                        self._verdict(
                            "serve_promote",
                            f"standby {m.uid} promoted to live",
                            nodes=[["serve", m.uid]],
                        )
                    if not self._publish_prefix:
                        fresh.append(m.replica)
                self._expire(time.time())
                self._dispatch()
                live = self._fleet.live_members()
            for replica in fresh:
                # New members must inherit the current brownout state.
                self._safe_control(replica, publish_prefix=False)
            if not live:
                return
            polls = [(m, self._safe_poll(m)) for m in live]
            publish_flip: Optional[bool] = None
            to_stop: List[Any] = []
            with self._lock:
                # Fresh clock after the polls: the repair branch above
                # can spend seconds cold-spawning a replacement, and
                # charging the post-recovery "serving" note at the
                # tick-START time would collapse the reform interval
                # to zero.
                now = time.time()
                busy_uids = {
                    r.assigned for r in self._requests.values()
                    if r.state == "running" and r.assigned
                }
                any_tokens = False
                prefill_delta = 0.0
                agg: Dict[str, Any] = {}
                for m, progress in polls:
                    if progress is None:
                        m.poll_misses += 1
                        if not self._safe_alive(m.replica):
                            # Plain death — reform next tick (this tick
                            # stays charged to the pre-death state
                            # until the reform note lands; detection
                            # latency is real).
                            m.dead = True
                            m.dead_reason = "died"
                        elif m.poll_misses >= self._heartbeat_misses:
                            m.dead = True
                            m.dead_reason = "serve_heartbeat_drop"
                            self._verdict(
                                "serve_heartbeat_drop",
                                f"replica {m.uid}: {m.poll_misses} "
                                "consecutive poll failures with the "
                                "process alive",
                                nodes=[["serve", m.uid]],
                            )
                        continue
                    m.note_poll(progress.get("stats"), now,
                                busy=m.uid in busy_uids)
                    any_tokens = self._fold(m, progress, now) or any_tokens
                    seen = self._prefill_seen.get(m.uid, 0.0)
                    prefill = float(
                        (m.stats or {}).get("prefill_tokens", 0) or 0
                    )
                    prefill_delta += max(prefill - seen, 0.0)
                    self._prefill_seen[m.uid] = prefill
                    for k, v in (m.stats or {}).items():
                        if isinstance(v, bool) or not isinstance(
                            v, (int, float)
                        ):
                            agg[k] = v
                        else:
                            agg[k] = agg.get(k, 0) + v
                self._last_stats = agg
                for m, action, reason in self._fleet.health_verdicts(
                    now, busy_uids,
                    wedge_timeout_s=self._wedge_timeout_s,
                    slow_factor=self._slow_factor,
                    slow_grace_s=self._slow_grace_s,
                ):
                    if not m.dead:
                        m.dead = True
                        m.dead_reason = action
                        self._verdict(action, reason,
                                      nodes=[["serve", m.uid]])
                self._classify(any_tokens, prefill_delta, now)
                self._gauges()
                if self._brownout is not None:
                    pressure = max(
                        backlog_tokens, self._queued_tokens()
                    ) / max(self._max_queue_tokens, 1)
                    level = self._brownout.update(pressure, now)
                    if level is not None:
                        _brownout_gauge().set(level)
                        self._verdict(
                            "serve_brownout",
                            f"level {level} ({BROWNOUT_RUNGS[level]}) at "
                            f"queue pressure {pressure:.2f}",
                            level=level,
                        )
                    want_publish = self._brownout.level < 2
                    if want_publish != self._publish_prefix:
                        self._publish_prefix = want_publish
                        publish_flip = want_publish
                if self._autoscaler is not None:
                    burning: List[str] = []
                    if self._slo is not None and hasattr(
                        self._slo, "burning"
                    ):
                        try:
                            burning = list(self._slo.burning(now))
                        except Exception:  # noqa: BLE001 — advisory
                            burning = []
                    forecast_tokens = None
                    if self._forecast is not None:
                        try:
                            lead = self._forecast_lead_s
                            rate = self._forecast.predict(
                                now, lead_s=lead, horizon_s=lead
                            )
                            forecast_tokens = float(rate) * lead
                        except Exception:  # noqa: BLE001 — advisory;
                            forecast_tokens = None  # fall back reactive
                    queue_now = max(backlog_tokens, self._queued_tokens())
                    # Input snapshot BEFORE decide(): the timers a
                    # decision was made against, not post-reset state.
                    scale_snap = None
                    if hasattr(self._autoscaler, "snapshot"):
                        try:
                            scale_snap = self._autoscaler.snapshot(now)
                        except Exception:  # noqa: BLE001 — advisory
                            scale_snap = None
                    decide_kwargs = {}
                    if forecast_tokens is not None:
                        decide_kwargs["forecast_tokens"] = forecast_tokens
                    target = self._autoscaler.decide(
                        now,
                        queue_tokens=queue_now,
                        target_live=self._fleet.target_live,
                        burning=burning,
                        **decide_kwargs,
                    )
                    if target is not None:
                        prev = self._fleet.target_live
                        self._fleet.target_live = target
                        decisions = getattr(
                            self._autoscaler, "decisions", None
                        )
                        mode = (
                            decisions[-1].get("mode", "reactive")
                            if decisions else "reactive"
                        )
                        self._verdict(
                            "serve_scale",
                            f"fleet target {prev} -> {target} "
                            f"(queue={backlog_tokens} tokens, "
                            f"burning={burning}, mode={mode})",
                            mode=mode,
                            snapshot={
                                "backlog_tokens": backlog_tokens,
                                "queue_tokens": float(queue_now),
                                "burning": list(burning),
                                "forecast_tokens": forecast_tokens,
                                "autoscaler": scale_snap,
                            },
                        )
                        if target < prev:
                            # Drain idle replicas only — a busy member
                            # finishes its work and shrinks later.
                            idle = [
                                m for m in self._fleet.live_members()
                                if m.uid not in busy_uids
                            ]
                            excess = (
                                len(self._fleet.live_members()) - target
                            )
                            for m in idle[: max(excess, 0)]:
                                if self._fleet.standby_deficit() > 0:
                                    self._fleet.demote(m)
                                else:
                                    self._fleet.detach(m)
                                    to_stop.append(m.replica)
            if publish_flip is not None:
                for m in self._fleet.live_members():
                    self._safe_control(
                        m.replica, publish_prefix=publish_flip
                    )
            for replica in to_stop:
                try:
                    replica.stop()
                except Exception:  # noqa: BLE001 — teardown
                    pass
            if self._slo is not None:
                # Outside _lock: the engine reads the metrics registry,
                # never gateway state.
                try:
                    self._slo.maybe_tick(time.time())
                except Exception as e:  # noqa: BLE001 — SLO eval must
                    logger.warning("slo tick failed: %s", e)  # not kill
                    # the pump.

    def _safe_alive(self, replica) -> bool:
        try:
            return replica is not None and bool(replica.alive())
        except Exception:  # noqa: BLE001 — a broken probe is a dead replica
            return False

    def _safe_poll(self, member) -> Optional[Dict[str, Any]]:
        try:
            # Chaos hook: a `raise` action here is indistinguishable
            # from the worker's heartbeat dropping on the wire.
            fault_point("serve_heartbeat_drop", replica=member.uid)
            return member.replica.poll()
        except Exception as e:  # noqa: BLE001 — RPC edge
            logger.warning("replica poll failed (%s): %s", member.uid, e)
            return None

    def _safe_control(self, replica, **kwargs) -> None:
        try:
            ctl = getattr(replica, "control", None)
            if ctl is not None:
                ctl(**kwargs)
        except Exception as e:  # noqa: BLE001 — next tick retries
            logger.warning("replica control failed (%s): %s",
                           getattr(replica, "uid", "?"), e)

    def _begin_reform_member(self, member, now: float) -> None:
        """Bookkeeping half of a reform, under the lock: detach the
        dead member and requeue ITS in-flight requests (the rest of
        the fleet keeps serving) for replay from their last committed
        token.  The caller kills the old replica and repairs the pool
        OUTSIDE the lock."""
        self._fleet.detach(member)
        self.disruptions += 1
        _disruption_counter().inc()
        self._note("reform", now)
        self._reforming = True
        self._prefill_seen.pop(member.uid, None)
        inflight = sorted(
            (rid for rid, r in self._requests.items()
             if r.state == "running" and r.assigned == member.uid),
            key=lambda rid: self._requests[rid].submitted_at,
        )
        for rid in reversed(inflight):
            req = self._requests[rid]
            req.assigned = ""
            if len(req.committed) >= req.gen_budget:
                # Fully generated before the worker died, the
                # completion just never arrived: close it out from
                # the journal — nothing to replay.
                self._complete(req, "budget", now)
                continue
            if (self._eos_id is not None and req.committed
                    and req.committed[-1] == self._eos_id):
                # The journal already ends in eos: replaying would
                # embed the eos in the prompt and the replacement
                # worker (which only checks eos on freshly sampled
                # tokens) would generate past it.  Close out from the
                # journal instead.
                self._complete(req, "eos", now)
                continue
            if req.replays + 1 > self._max_replays:
                # Poison guard: a request that has ridden this many
                # reforms is shed, not requeued forever.
                self._shed(req, "reform")
                continue
            req.state = "queued"
            req.replays += 1
            self._queue.appendleft(rid)
            self._req_event("replay", req)
            _tracing.point(req.trace, "reform_replay",
                           rid=req.request_id, replay=req.replays,
                           n_gen=len(req.committed))

    def _prune(self, now: float) -> None:
        """Drop done/shed requests past the retention window — the
        journal only matters while a request can still replay, and an
        unpruned dict grows (and is scanned by _expire) forever."""
        if self._retention_s is None:
            return
        stale = [
            rid for rid, r in self._requests.items()
            if r.state in ("done", "shed") and r.finished_at is not None
            and now - r.finished_at > self._retention_s
        ]
        for rid in stale:
            del self._requests[rid]

    def _expire(self, now: float) -> None:
        for rid in list(self._queue):
            req = self._requests[rid]
            if req.deadline_at is not None and now > req.deadline_at:
                self._queue.remove(rid)
                self._shed(req, "deadline")
        for req in self._requests.values():
            if (req.state == "running" and req.deadline_at is not None
                    and now > req.deadline_at):
                # Past-deadline answer is worthless to the client: cut
                # it off with whatever the journal holds.  The worker
                # keeps decoding; its eventual completion is stale.
                self._complete(req, "deadline", now)

    def _shed(self, req: _GwRequest, reason: str) -> None:
        req.state = "shed"
        req.finished_reason = reason
        req.finished_at = time.time()
        self.shed_count += 1
        _shed_counter().inc(reason=reason)
        self._req_event("shed", req, reason=reason)
        req.done_event.set()

    def _complete(self, req: _GwRequest, reason: str, now: float) -> None:
        if req.state in ("done", "shed"):
            return
        req.state = "done"
        req.finished_reason = reason
        req.finished_at = now
        self.done_count += 1
        self._req_event("finished", req, reason=reason)
        _tracing.point(req.trace, "done", rid=req.request_id,
                       reason=reason, n_gen=len(req.committed))
        req.done_event.set()

    def _dispatch(self) -> None:
        """Least-loaded dispatch: each queued request goes to the live
        replica with the fewest queued tokens (running prompt+budget),
        KV-block occupancy as the tie-break."""
        candidates = self._fleet.live_members()
        if not candidates:
            return
        load = {m.uid: 0 for m in candidates}
        for r in self._requests.values():
            if r.state == "running" and r.assigned in load:
                load[r.assigned] += len(r.prompt) + r.gen_budget
        while self._queue and candidates:
            rid = self._queue[0]
            req = self._requests[rid]
            m = min(candidates, key=lambda c: (
                load[c.uid],
                float((c.stats or {}).get("blocks_active", 0) or 0),
            ))
            replay_prompt = list(req.prompt) + list(req.committed)
            try:
                ok, reason = m.replica.submit(
                    rid, replay_prompt, req.gen_budget, len(req.prompt),
                    trace=_tracing.to_wire(req.trace),
                )
            except (TypeError, ValueError) as e:
                # Encoding/validation failure is the REQUEST's fault,
                # not the replica's — shed it, or a poisoned request
                # would respawn workers forever.
                self._queue.popleft()
                self._shed(req, f"rejected: {e}")
                continue
            except Exception as e:  # noqa: BLE001 — RPC edge
                logger.warning("replica submit failed (%s): %s",
                               m.uid, e)
                # This member is gone; the rest of the fleet keeps
                # taking dispatch, and the reform runs next tick.
                m.dead = True
                m.dead_reason = "submit_rpc"
                candidates = [c for c in candidates if c is not m]
                load.pop(m.uid, None)
                continue
            self._queue.popleft()
            if ok:
                req.state = "running"
                req.assigned = m.uid
                load[m.uid] += len(req.prompt) + req.gen_budget
                if req.trace is not None:
                    now = time.time()
                    _tracing.emit_span(
                        req.trace.child(), "queue",
                        now - req.submitted_at, rid=rid,
                        replay=req.replays,
                    )
                    _tracing.point(
                        req.trace, "dispatch", rid=rid, replica=m.uid,
                    )
            else:
                # Validation rejects are permanent (prompt too long,
                # request can never fit the pool) — shed, don't loop.
                self._shed(req, f"rejected: {reason}")

    def _fold(self, member, progress: Dict[str, Any], now: float) -> bool:
        """Journal newly committed tokens; close out completions."""
        any_tokens = False
        replica = member.uid
        for rid, toks in progress.get("emitted", {}).items():
            req = self._requests.get(int(rid))
            if req is None or req.state != "running" or not toks:
                continue
            if req.assigned and req.assigned != replica:
                # Stale emission from a member the request replayed
                # away from — the journal already holds these tokens.
                continue
            room = req.gen_budget - len(req.committed)
            toks = list(toks)[: max(room, 0)]
            if not toks:
                continue
            any_tokens = True
            exemplar = (
                req.trace.trace_id if req.trace is not None else None
            )
            if req.first_token_at is None:
                req.first_token_at = now
                _ttft_hist().observe(
                    now - req.submitted_at, exemplar=exemplar,
                    replica=replica,
                )
                rest = toks[1:]
            else:
                rest = toks
            if rest and req.last_token_at is not None:
                per_tok = (now - req.last_token_at) / len(rest)
                for _ in rest:
                    _tpot_hist().observe(
                        per_tok, exemplar=exemplar, replica=replica
                    )
            req.last_token_at = now
            req.committed.extend(toks)
            _tokens_counter().inc(len(toks))
            _tracing.point(req.trace, "commit", rid=req.request_id,
                           n_tokens=len(toks),
                           n_gen=len(req.committed))
        for c in progress.get("completions", []):
            req = self._requests.get(int(c.get("request_id", -1)))
            if req is None or req.state != "running":
                continue  # stale (replayed or already cut off)
            if req.assigned and req.assigned != replica:
                continue
            expect = list(req.prompt) + list(req.committed)
            got = list(c.get("tokens", []))
            if got != expect:
                # Journal is authoritative — a mismatch can only come
                # from a completion racing a replay boundary.
                logger.warning(
                    "completion/journal mismatch for rid %d "
                    "(%d vs %d tokens); journal wins",
                    req.request_id, len(got), len(expect),
                )
            self._complete(req, str(c.get("finished_reason", "")), now)
        return any_tokens

    def _classify(self, any_tokens: bool, prefill_delta: float,
                  now: float) -> None:
        has_work = bool(
            self._queue
            or any(r.state == "running" for r in self._requests.values())
        )
        if any_tokens:
            self._reforming = False
            self._note("serving", now)
        elif self._reforming:
            self._note("reform", now)
        elif prefill_delta > 0:
            self._note("prefill_bound", now)
        elif has_work:
            self._note("queue_wait", now)
        else:
            self._note("idle", now)

    def _gauges(self) -> None:
        _queue_gauge().set(len(self._queue))
        for key in ("blocks_active", "blocks_cached", "blocks_free"):
            if key in self._last_stats:
                _kv_gauge().set(
                    float(self._last_stats[key]), state=key.split("_", 1)[1]
                )

    # -- faces ---------------------------------------------------------------
    def servz(self) -> Dict[str, Any]:
        with self._lock:
            states = collections.Counter(
                r.state for r in self._requests.values()
            )
            live = self._fleet.live_members()
            return {
                "servput": self.accountant.summary(now=time.time()),
                "state": self._state,
                "queue_depth": len(self._queue),
                "requests": dict(states),
                "disruptions": self.disruptions,
                "shed": self.shed_count,
                "replica": live[0].uid if live else None,
                "fleet": {
                    "live": [m.uid for m in live],
                    "standby": self._fleet.standby_count(),
                    "target_live": self._fleet.target_live,
                    "target_standby": self._fleet.target_standby,
                    "promotions": self._fleet.promotions,
                    "cold_spawns": self._fleet.cold_spawns,
                },
                "brownout_level": (
                    self._brownout.level
                    if self._brownout is not None else 0
                ),
                "engine": dict(self._last_stats),
                # p50/p95/p99 across every replica label-set — the
                # at-a-glance latency block next to the raw counters.
                "latency": {
                    "ttft_s": _metrics.aggregate_summary(_ttft_hist()),
                    "tpot_s": _metrics.aggregate_summary(_tpot_hist()),
                },
            }

    def healthz(self) -> Dict[str, Any]:
        """Readiness for external load balancers: ready iff at least
        one live replica is taking dispatch and the gateway is not
        shutting down.  Served as ``GET /healthz`` (200/503)."""
        with self._lock:
            live = self._fleet.live_members()
            level = (
                self._brownout.level if self._brownout is not None else 0
            )
            return {
                "ready": bool(live) and not self._stop_evt.is_set(),
                "live": len(live),
                "replicas": [m.uid for m in live],
                "standby": self._fleet.standby_count(),
                "target_replicas": self._fleet.target_live,
                "target_standby": self._fleet.target_standby,
                "brownout_level": level,
                "brownout_rung": BROWNOUT_RUNGS[level],
                "queue_depth": len(self._queue),
                "disruptions": self.disruptions,
            }

    def http_sources(self) -> Dict[str, Callable]:
        """Plug into ``TelemetryHTTPServer(serve_sources=...)``."""

        def _generate(prompt, budget, timeout):
            res = self.submit(prompt, gen_budget=budget)
            if not res.get("ok"):
                return res
            return self.get(res["request_id"], timeout_s=timeout)

        def _trace(trace_id):
            return _tracing.reconstruct(
                trace_id, events_dir=_events.telemetry_dir()
            )

        sources = {
            "servz": self.servz, "generate": _generate, "trace": _trace,
            "healthz": self.healthz,
        }
        if self._slo is not None:
            sources["slo"] = self._slo.snapshot
        return sources

    # -- lifecycle ------------------------------------------------------------
    def start(self, interval_s: float = 0.0) -> None:
        """Background pump loop (the serving master's thread)."""
        if self._thread is not None:
            return

        def _loop():
            while not self._stop_evt.is_set():
                self._tick()
                if interval_s:
                    self._stop_evt.wait(interval_s)
                elif self._state in ("idle", None):
                    self._stop_evt.wait(0.01)

        self._thread = threading.Thread(
            target=_loop, name="gateway-pump", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop_evt.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        self._fleet.stop_all()

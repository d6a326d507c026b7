"""Replica-fleet tests (docs/SERVING.md): warm standbys, health-checked
failover, autoscaling, and the brownout degradation ladder.

Covers the PR 15 acceptance bars:

* ``spawn_with_retry`` — bounded attempts, retry counter, last failure
  re-raises;
* ``FleetAutoscaler`` — dwell + cooldown hysteresis (never flaps),
  burning-SLO override, one-step shrink;
* ``BrownoutController`` — rungs engage immediately, release one at a
  time only after the pressure has stayed below the hysteresis
  threshold for a dwell window;
* ``ReplicaSet`` health verdicts — wedge (alive but no engine progress
  under load) and slow-replica (EMA tick rate vs fleet median);
* gateway fleet behavior against scripted fake replicas: least-loaded
  dispatch, heartbeat-drop / wedge ejection with durable verdicts the
  doctor attributes, the brownout ladder end to end, sub-second standby
  promotion with background replenishment, submit() responsiveness
  while the pump cold-spawns, retention pruning under sustained
  shedding, and ``GET /healthz`` over the telemetry httpd;
* the real-process drill: SIGKILL one replica of a 2-live + 1-standby
  ``ProcessReplica`` fleet mid-traffic — zero lost or duplicated
  completions, repair by promotion (no cold spawn), and strictly fewer
  servput points lost than the same kill against a dry standby pool.
"""

import itertools
import json
import os
import signal
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from dlrover_tpu.serving.fleet import (
    BROWNOUT_RUNGS,
    BrownoutController,
    FleetAutoscaler,
    ReplicaSet,
    _brownout_gauge,
    _spawn_retry_counter,
    spawn_with_retry,
)
from dlrover_tpu.serving.gateway import InferenceGateway, ProcessReplica
from dlrover_tpu.telemetry.httpd import TelemetryHTTPServer
from dlrover_tpu.telemetry.servput import serve_incidents

pytestmark = pytest.mark.serve

BUDGET = 12


class FakeReplica:
    """Scripted in-process replica: deterministic one-token-per-poll
    emission, full control over liveness / poll failures / tick
    progress.  The fleet logic's wind tunnel — no engine, no jax."""

    _ids = itertools.count()

    def __init__(self):
        self.uid = f"fake-{next(FakeReplica._ids)}"
        self._alive = True
        self._reqs = {}
        self._ticks = 0
        self.wedged = False      # answer polls but freeze the engine
        self.fail_polls = 0      # raise on the next N polls
        self.controls = []       # publish_prefix flags received
        self.submits = []        # rids accepted

    def submit(self, rid, prompt, gen_budget, orig_prompt_len, trace=""):
        self.submits.append(rid)
        self._reqs[rid] = {
            "prompt": list(prompt), "budget": int(gen_budget), "done": 0,
        }
        return True, ""

    def poll(self):
        if self.fail_polls > 0:
            self.fail_polls -= 1
            raise ConnectionError("poll dropped")
        if self.wedged:
            return {
                "emitted": {}, "completions": [],
                "stats": {"ticks": self._ticks},
            }
        self._ticks += 1
        emitted, completions = {}, []
        for rid, st in list(self._reqs.items()):
            emitted[rid] = [100 + st["done"]]
            st["done"] += 1
            if st["done"] >= st["budget"]:
                completions.append({
                    "request_id": rid,
                    "tokens": st["prompt"] + [
                        100 + i for i in range(st["budget"])
                    ],
                    "prompt_len": len(st["prompt"]),
                    "finished_reason": "budget",
                })
                del self._reqs[rid]
        return {
            "emitted": emitted, "completions": completions,
            "stats": {"ticks": self._ticks},
        }

    def control(self, publish_prefix=None):
        self.controls.append(publish_prefix)
        return True

    def alive(self):
        return self._alive

    def kill(self):
        self._alive = False

    def stop(self):
        self._alive = False


def fake_gateway(slow_after=None, slow_s=0.6, **kw):
    """Gateway over a FakeReplica factory.  ``slow_after=N`` makes
    every spawn past the Nth sleep ``slow_s`` — a deterministic stand-in
    for a real process spawn's cost."""
    fakes = []

    def factory():
        if slow_after is not None and len(fakes) >= slow_after:
            time.sleep(slow_s)
        r = FakeReplica()
        fakes.append(r)
        return r

    kw.setdefault("default_gen_budget", 4)
    kw.setdefault("retention_s", None)
    return InferenceGateway(factory, **kw), fakes


def _http_get(addr, path):
    try:
        with urllib.request.urlopen(
            f"http://{addr}{path}", timeout=10
        ) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


class TestSpawnRetry:
    def test_retries_then_succeeds_and_counts(self):
        calls = []

        def factory():
            calls.append(1)
            if len(calls) < 3:
                raise RuntimeError("flaky spawn")
            return "replica"

        before = _spawn_retry_counter().value()
        out = spawn_with_retry(factory, attempts=4, backoff_s=0.0)
        assert out == "replica"
        assert len(calls) == 3
        assert _spawn_retry_counter().value() == before + 2

    def test_exhausted_attempts_reraise_last(self):
        def factory():
            raise RuntimeError("always broken")

        before = _spawn_retry_counter().value()
        with pytest.raises(RuntimeError, match="always broken"):
            spawn_with_retry(factory, attempts=2, backoff_s=0.0)
        # Only attempts-1 retries are counted; the last failure raises.
        assert _spawn_retry_counter().value() == before + 1


class TestFleetAutoscaler:
    def test_grow_needs_dwell_then_cooldown_blocks_flap(self):
        a = FleetAutoscaler(
            min_replicas=1, max_replicas=4, tokens_per_replica=100,
            up_dwell_s=1.0, down_dwell_s=1.0, cooldown_s=5.0,
        )
        # Pressure must HOLD for the dwell window before a grow.
        assert a.decide(0.0, queue_tokens=350, target_live=1) is None
        assert a.decide(0.5, queue_tokens=350, target_live=1) is None
        assert a.decide(1.0, queue_tokens=350, target_live=1) == 4
        # Reversal right after: the dwell is met at t=3.5 but the
        # cooldown from the grow still holds — no flap.
        assert a.decide(2.0, queue_tokens=0, target_live=4) is None
        assert a.decide(3.5, queue_tokens=0, target_live=4) is None
        # Past the cooldown: shrink ONE step at a time.
        assert a.decide(6.5, queue_tokens=0, target_live=4) == 3
        assert [d["action"] for d in a.decisions] == ["grow", "shrink"]

    def test_dwell_resets_when_pressure_drops(self):
        a = FleetAutoscaler(
            tokens_per_replica=100, up_dwell_s=1.0, cooldown_s=0.0,
        )
        assert a.decide(0.0, queue_tokens=300, target_live=1) is None
        # Pressure vanished mid-dwell: the clock resets.
        assert a.decide(0.5, queue_tokens=0, target_live=1) is None
        assert a.decide(1.1, queue_tokens=300, target_live=1) is None
        assert a.decide(2.2, queue_tokens=300, target_live=1) == 3

    def test_burning_slo_forces_capacity(self):
        a = FleetAutoscaler(
            tokens_per_replica=10_000, up_dwell_s=0.0, cooldown_s=0.0,
        )
        # Queue alone wants 1 replica; a burning SLO asks for one more.
        assert a.decide(
            0.0, queue_tokens=0, target_live=1, burning=["ttft_p95"]
        ) == 2

    def test_bounds_validated(self):
        with pytest.raises(ValueError):
            FleetAutoscaler(min_replicas=0)
        with pytest.raises(ValueError):
            FleetAutoscaler(min_replicas=3, max_replicas=2)


class TestBrownoutController:
    def test_engages_immediately_releases_one_rung_at_a_time(self):
        b = BrownoutController(
            enter=(0.5, 0.7, 0.85), exit_ratio=0.5, down_dwell_s=1.0,
        )
        assert b.update(0.9, 0.0) == 3  # straight to the top rung
        # Below the release threshold, but the dwell is not met yet.
        assert b.update(0.1, 0.2) is None
        assert b.update(0.1, 0.9) is None
        assert b.update(0.1, 1.3) == 2  # one rung, not a cliff
        # Each release restarts the dwell clock for the next rung.
        assert b.update(0.1, 1.4) is None
        assert b.update(0.1, 2.5) == 1
        assert b.update(0.1, 3.0) is None
        assert b.update(0.1, 4.1) == 0
        assert b.update(0.1, 9.0) is None  # healthy stays healthy
        assert [t["level"] for t in b.transitions] == [3, 2, 1, 0]
        assert b.transitions[0]["rung"] == BROWNOUT_RUNGS[3]

    def test_release_dwell_resets_on_pressure_spike(self):
        b = BrownoutController(
            enter=(0.5, 0.7, 0.85), exit_ratio=0.5, down_dwell_s=1.0,
        )
        assert b.update(0.6, 0.0) == 1
        assert b.update(0.1, 0.1) is None
        # A spike above the release threshold resets the dwell clock.
        assert b.update(0.4, 0.5) is None
        assert b.update(0.1, 1.2) is None  # dwell restarted at t=1.2
        assert b.update(0.1, 1.8) is None
        assert b.update(0.1, 2.3) == 0

    def test_validation(self):
        with pytest.raises(ValueError):
            BrownoutController(enter=(0.7, 0.5, 0.85))
        with pytest.raises(ValueError):
            BrownoutController(exit_ratio=0.0)


class TestReplicaSetHealth:
    def test_wedge_needs_running_work(self):
        rs = ReplicaSet(FakeReplica, target_live=1)
        m = rs.attach_live(FakeReplica(), now=0.0)
        m.note_poll({"ticks": 5}, 0.0, busy=True)   # baseline
        m.note_poll({"ticks": 5}, 20.0, busy=True)  # frozen under load
        v = rs.health_verdicts(20.0, [m.uid], wedge_timeout_s=10.0)
        assert len(v) == 1
        member, action, reason = v[0]
        assert member is m and action == "serve_replica_wedge"
        assert m.uid in reason
        # The same frozen ticks on an IDLE replica are legitimate.
        assert rs.health_verdicts(20.0, [], wedge_timeout_s=10.0) == []

    def test_idle_poll_refreshes_progress(self):
        rs = ReplicaSet(FakeReplica, target_live=1)
        m = rs.attach_live(FakeReplica(), now=0.0)
        m.note_poll({"ticks": 5}, 0.0, busy=False)
        m.note_poll({"ticks": 5}, 19.0, busy=False)  # idle: clock moves
        m.note_poll({"ticks": 5}, 20.0, busy=True)
        assert rs.health_verdicts(
            20.5, [m.uid], wedge_timeout_s=10.0
        ) == []

    def test_slow_replica_vs_fleet_median(self):
        rs = ReplicaSet(FakeReplica, target_live=3)
        fast1 = rs.attach_live(FakeReplica(), 0.0)
        fast2 = rs.attach_live(FakeReplica(), 0.0)
        slow = rs.attach_live(FakeReplica(), 0.0)
        fast1.rate, fast2.rate, slow.rate = 10.0, 9.0, 1.0
        # slow_factor=0 disables (single-replica gateways, no baseline).
        assert rs.health_verdicts(0.0, [], slow_factor=0.0) == []
        # First sighting starts the grace clock, no verdict yet.
        assert rs.health_verdicts(
            0.0, [], slow_factor=3.0, slow_grace_s=1.0
        ) == []
        v = rs.health_verdicts(
            1.5, [], slow_factor=3.0, slow_grace_s=1.0
        )
        assert [(x[0], x[1]) for x in v] == [(slow, "serve_slow_replica")]

    def test_promote_and_background_replenish(self):
        rs = ReplicaSet(FakeReplica, target_live=1, target_standby=2)
        rs.attach_live(FakeReplica(), 0.0)
        rs.replenish_async()
        deadline = time.time() + 5
        while rs.standby_count() < 2 and time.time() < deadline:
            time.sleep(0.01)
        assert rs.standby_count() == 2
        m = rs.promote(1.0)
        assert m is not None and m.role == "live"
        assert rs.promotions == 1 and rs.standby_count() == 1
        rs.replenish_async()
        deadline = time.time() + 5
        while rs.standby_count() < 2 and time.time() < deadline:
            time.sleep(0.01)
        assert rs.standby_count() == 2
        rs.stop_all()
        assert rs.live_members() == [] and rs.standby_members() == []


class TestGatewayFleet:
    def test_least_loaded_dispatch_spreads(self):
        gw, fakes = fake_gateway(n_replicas=2)
        try:
            gw.pump()
            assert len(fakes) == 2
            rids = [
                gw.submit([1, 2, 3])["request_id"] for _ in range(4)
            ]
            gw.pump()
            assigned = {gw._requests[r].assigned for r in rids}
            assert assigned == {fakes[0].uid, fakes[1].uid}
            assert sorted(len(f.submits) for f in fakes) == [2, 2]
        finally:
            gw.stop()

    def test_heartbeat_drop_ejects_with_verdict(self):
        gw, fakes = fake_gateway(n_replicas=1, heartbeat_misses=2,
                                 spawn_backoff_s=0.0)
        try:
            gw.pump()
            victim = fakes[0]
            rid = gw.submit([1, 2, 3])["request_id"]
            gw.pump()  # dispatch + one healthy poll
            # Poll RPCs start failing while alive() stays True: the
            # wedged-network case alive() alone can never see.
            victim.fail_polls = 10 ** 6
            gw.pump()  # miss 1
            gw.pump()  # miss 2 -> ejected
            verdicts = [
                e for e in gw.events
                if e.get("ev") == "verdict"
                and e.get("action") == "serve_heartbeat_drop"
            ]
            assert verdicts and victim.uid in verdicts[0]["reason"]
            assert verdicts[0]["nodes"] == [["serve", victim.uid]]
            out = gw.get(rid, timeout_s=10)
            assert out["ok"] and out["n_gen"] == 4
            assert gw.disruptions == 1
            assert len(fakes) == 2 and rid in fakes[1].submits
            # The doctor names the trigger from the durable verdict.
            from dlrover_tpu import doctor
            report = doctor.diagnose(
                doctor.SourceData(events=gw.events)
            )
            incidents = report["serving"]["incidents"]
            assert incidents
            assert incidents[0]["trigger"] == "serve_heartbeat_drop"
        finally:
            gw.stop()

    def test_wedged_replica_ejected_with_verdict(self):
        gw, fakes = fake_gateway(n_replicas=1, wedge_timeout_s=0.05,
                                 spawn_backoff_s=0.0)
        try:
            gw.pump()
            victim = fakes[0]
            rid = gw.submit([1, 2, 3])["request_id"]
            gw.pump()  # dispatch + baseline poll (ticks advance)
            victim.wedged = True  # polls answer, engine frozen
            deadline = time.time() + 5
            while time.time() < deadline:
                gw.pump()
                if any(
                    e.get("action") == "serve_replica_wedge"
                    for e in gw.events if e.get("ev") == "verdict"
                ):
                    break
                time.sleep(0.02)
            verdicts = [
                e for e in gw.events
                if e.get("ev") == "verdict"
                and e.get("action") == "serve_replica_wedge"
            ]
            assert verdicts and victim.uid in verdicts[0]["reason"]
            out = gw.get(rid, timeout_s=10)
            assert out["ok"] and out["n_gen"] == 4
            assert gw.disruptions == 1 and len(fakes) == 2
            incs = serve_incidents(gw.events)
            assert incs and incs[0]["trigger"] == "serve_replica_wedge"
        finally:
            gw.stop()

    def test_brownout_ladder_engages_and_releases(self):
        brown = BrownoutController(
            enter=(0.3, 0.5, 0.7), exit_ratio=0.5, down_dwell_s=0.05,
            gen_budget_cap=4, shed_below_priority=1,
        )
        gw, fakes = fake_gateway(
            n_replicas=1, max_queue_tokens=100, default_gen_budget=10,
            brownout=brown,
        )
        try:
            gw.pump()
            # Flood: 6 * (3 prompt + 10 budget) = 78 tokens -> 0.78
            # pressure -> straight to rung 3.
            for _ in range(6):
                assert gw.submit([1, 2, 3])["ok"]
            gw.pump()
            assert brown.level == 3
            assert _brownout_gauge().value() == 3
            levels = [
                e["level"] for e in gw.events
                if e.get("ev") == "verdict"
                and e.get("action") == "serve_brownout"
            ]
            assert levels == [3]
            # Rung 3: low-priority classes bounce at the door.
            out = gw.submit([9, 9], priority=0)
            assert out["shed"] and out["reason"] == "brownout"
            # Rung 1 (active under rung 3): budgets are capped.
            rid = gw.submit([9, 9], priority=1)["request_id"]
            assert gw._requests[rid].gen_budget == 4
            # Rung 2: prefix publishing disabled on every live replica.
            assert fakes[0].controls[-1] is False
            # Drain -> pressure 0 -> hysteretic release, one rung per
            # dwell window, never a cliff.
            deadline = time.time() + 10
            while brown.level > 0 and time.time() < deadline:
                gw.pump()
                time.sleep(0.02)
            assert brown.level == 0
            assert [t["level"] for t in brown.transitions] == [3, 2, 1, 0]
            # Publishing came back when the ladder dropped below rung 2.
            assert fakes[0].controls[-1] is True
            assert gw.get(rid, timeout_s=10)["ok"]
        finally:
            gw.stop()

    def test_autoscaler_resizes_fleet_with_verdicts(self):
        # Non-zero shrink dwell + cooldown: the grow must survive the
        # ticks between it and the live pool catching up.
        auto = FleetAutoscaler(
            min_replicas=1, max_replicas=3, tokens_per_replica=20,
            up_dwell_s=0.0, down_dwell_s=0.15, cooldown_s=0.1,
        )
        gw, fakes = fake_gateway(
            n_replicas=1, autoscaler=auto, max_queue_tokens=1000,
            default_gen_budget=17,
        )
        try:
            gw.pump()
            for _ in range(3):
                gw.submit([1, 2, 3])  # 3 * 20 tokens -> wants 3 replicas
            gw.pump()
            assert gw.fleet.target_live == 3
            gw.pump()  # the repair loop grows the live pool
            assert len(gw.fleet.live_members()) == 3
            # Drain: the queue empties, the autoscaler walks the fleet
            # back down one step at a time, stopping idle replicas.
            deadline = time.time() + 10
            while time.time() < deadline:
                gw.pump()
                if (
                    len(gw.fleet.live_members()) == 1
                    and gw.fleet.target_live == 1
                ):
                    break
                time.sleep(0.01)
            assert gw.fleet.target_live == 1
            assert len(gw.fleet.live_members()) == 1
            scales = [
                e for e in gw.events
                if e.get("ev") == "verdict"
                and e.get("action") == "serve_scale"
            ]
            assert len(scales) >= 3  # 1 grow + 2 one-step shrinks
        finally:
            gw.stop()

    def test_promotion_is_subsecond_with_slow_replenish(self):
        # Spawns past the initial live+standby pair sleep 0.6s — the
        # replacement standby's cost must land on the replenisher
        # thread, never the pump.
        gw, fakes = fake_gateway(slow_after=2, slow_s=0.6,
                                 n_replicas=1, n_standbys=1)
        try:
            gw.pump()
            deadline = time.time() + 5
            while gw.fleet.standby_count() < 1 and time.time() < deadline:
                time.sleep(0.01)
            assert gw.fleet.standby_count() == 1
            rid = gw.submit([1, 2, 3])["request_id"]
            gw.pump()
            fakes[0].kill()
            t0 = time.time()
            gw.pump()
            elapsed = time.time() - t0
            assert gw.fleet.promotions == 1
            assert elapsed < 0.5  # promotion, not the 0.6s spawn
            out = gw.get(rid, timeout_s=10)
            assert out["ok"] and out["replays"] == 1
            promote = [
                e for e in gw.events
                if e.get("ev") == "verdict"
                and e.get("action") == "serve_promote"
            ]
            assert promote
            deadline = time.time() + 5
            while gw.fleet.standby_count() < 1 and time.time() < deadline:
                gw.pump()
                time.sleep(0.02)
            assert gw.fleet.standby_count() == 1
        finally:
            gw.stop()

    def test_submit_responsive_while_pump_cold_spawns(self):
        gw, fakes = fake_gateway(slow_after=1, slow_s=0.8, n_replicas=1)
        try:
            gw.pump()
            gw.start()
            fakes[0].kill()
            time.sleep(0.15)  # pump thread enters the 0.8s cold spawn
            t0 = time.time()
            res = gw.submit([1, 2])
            elapsed = time.time() - t0
            assert res["ok"] and elapsed < 0.4
            assert gw.result(res["request_id"])["state"] in (
                "queued", "running"
            )
            assert gw.get(res["request_id"], timeout_s=10)["ok"]
        finally:
            gw.stop()

    def test_retention_prunes_while_brownout_sheds(self):
        brown = BrownoutController(
            enter=(0.1, 0.2, 0.3), exit_ratio=0.5, down_dwell_s=60.0,
            gen_budget_cap=3, shed_below_priority=1,
        )
        gw, fakes = fake_gateway(
            n_replicas=1, retention_s=0.05, max_queue_tokens=60,
            brownout=brown,
        )
        try:
            gw.pump()
            rids = [
                gw.submit([1, 2, 3])["request_id"] for _ in range(4)
            ]
            gw.pump()
            assert brown.level == 3
            for _ in range(10):
                out = gw.submit([9], priority=0)
                assert out["shed"] and out["reason"] == "brownout"
            assert gw.shed_count >= 10
            outs = [gw.get(r, timeout_s=10) for r in rids]
            assert all(o["ok"] for o in outs)
            time.sleep(0.06)
            gw.pump()  # retention pass: the journal dict stays bounded
            assert all(r not in gw._requests for r in rids)
            assert gw.result(rids[0])["ok"] is False
            assert brown.level == 3  # the 60s dwell held it engaged
        finally:
            gw.stop()

    def test_healthz_readiness_over_http(self):
        gw, fakes = fake_gateway(n_replicas=1)
        srv = TelemetryHTTPServer(serve_sources=gw.http_sources())
        addr = srv.start()
        try:
            # No live replica yet -> not ready.
            code, body = _http_get(addr, "/healthz")
            assert code == 503 and body["ready"] is False
            gw.pump()
            code, body = _http_get(addr, "/healthz")
            assert code == 200 and body["ready"] is True
            assert body["live"] == 1 and body["replicas"] == [
                fakes[0].uid
            ]
            assert body["standby"] == 0
            assert body["brownout_rung"] == "none"
            assert "queue_depth" in body and "schema_version" in body
            gw.stop()
            code, body = _http_get(addr, "/healthz")
            assert code == 503 and body["ready"] is False
        finally:
            srv.stop()
            gw.stop()


class TestFleetPromotionDrill:
    def test_sigkill_repairs_by_promotion_then_cold_spawn(self, tmp_path):
        """The acceptance drill, with real decode-worker processes:
        SIGKILL one replica of a 2-live + 1-standby fleet mid-traffic.
        Zero lost or duplicated completions (exact greedy-reference
        match), repair by promotion with no cold spawn, and — after
        draining the standby pool and killing again — repair by a cold
        spawn.  An order of events, not a race: what each repair costs
        in seconds is the chip's to say (ROADMAP R7), not this CPU's."""
        pytest.importorskip("jax")
        from dlrover_tpu import doctor
        from dlrover_tpu.rl.serving import ContinuousBatchingEngine
        from dlrover_tpu.serving.worker import build_tiny_model

        rng = np.random.default_rng(0)
        prompts = [
            [int(t) for t in rng.integers(1, 64, size=n)]
            for n in (5, 23, 17, 9)
        ]
        model, params = build_tiny_model()
        eng = ContinuousBatchingEngine(
            model, params, slots=4, max_len=64, max_prompt=40,
            temperature=1e-6, seed=0,
        )
        done = eng.generate(prompts, gen_budget=BUDGET)
        ref = [done[r].tokens for r in sorted(done)]

        wargs = dict(
            vocab=64, hidden=32, intermediate=64, layers=2, heads=2,
            kv_heads=2, slots=4, max_len=64, block_size=16, seed=0,
            temperature=1e-6,
        )

        def factory():
            return ProcessReplica(str(tmp_path), worker_args=wargs)

        def kill_mid_wave(gw):
            """Submit the prompts and SIGKILL a replica while one of
            them is running on it.  The workers decode between pumps,
            so a wave can finish before a pump sees it in flight: then
            another is sent, and no clock decides the outcome."""
            for _ in range(10):
                rids = [gw.submit(p)["request_id"] for p in prompts]
                deadline = time.time() + 120
                while time.time() < deadline:
                    gw.pump()
                    reqs = [gw._requests[r] for r in rids]
                    busy = {
                        q.assigned for q in reqs if q.state == "running"
                    }
                    victim = next(
                        (m for m in gw.fleet.live_members()
                         if m.uid in busy), None,
                    )
                    if victim is not None:
                        os.kill(victim.replica.pid, signal.SIGKILL)
                        time.sleep(0.2)
                        return rids
                    if all(q.state == "done" for q in reqs):
                        break
            pytest.fail("no wave was ever seen in flight")

        gw = InferenceGateway(
            factory, n_replicas=2, n_standbys=1,
            default_gen_budget=BUDGET, max_queue_tokens=4096,
        )
        try:
            gw.pump()  # cold-spawn the live pool, kick the replenisher
            deadline = time.time() + 120
            while gw.fleet.standby_count() < 1 and time.time() < deadline:
                time.sleep(0.1)
            assert gw.fleet.standby_count() == 1
            cold_baseline = gw.fleet.cold_spawns

            # Wave 1: kill mid-traffic with a warm standby ready.
            rids = kill_mid_wave(gw)
            outs = [gw.get(r, timeout_s=180) for r in rids]
            assert all(o["ok"] for o in outs)
            assert [o["tokens"] for o in outs] == ref  # zero lost/dup
            assert gw.fleet.promotions == 1
            assert gw.fleet.cold_spawns == cold_baseline  # promotion only
            assert gw.disruptions == 1

            # The replenisher restores the warm pool in the background.
            deadline = time.time() + 120
            while gw.fleet.standby_count() < 1 and time.time() < deadline:
                time.sleep(0.1)
            assert gw.fleet.standby_count() == 1

            # Wave 2: drain the standby pool first — the same kill now
            # repairs through a blocking cold spawn.
            gw.fleet.target_standby = 0
            for m in list(gw.fleet.standby_members()):
                gw.fleet.detach(m)
                m.replica.stop()
            rids2 = kill_mid_wave(gw)
            outs2 = [gw.get(r, timeout_s=180) for r in rids2]
            assert all(o["ok"] for o in outs2)
            assert [o["tokens"] for o in outs2] == ref
            assert gw.fleet.promotions == 1  # unchanged
            assert gw.fleet.cold_spawns == cold_baseline + 1
            assert gw.disruptions == 2

            incs = serve_incidents(gw.events)
            assert len(incs) == 2
            assert incs[0]["recovery"] == "promotion"
            assert incs[1]["recovery"] == "cold_spawn"

            report = doctor.diagnose(doctor.SourceData(events=gw.events))
            serving = report["serving"]
            assert serving is not None and len(serving["incidents"]) == 2
            md = doctor.render_markdown(report)
            assert "promotion" in md and "cold_spawn" in md
        finally:
            gw.stop()

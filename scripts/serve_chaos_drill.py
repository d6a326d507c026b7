"""Report-only serving-fleet chaos drill.

Runs the warm-standby acceptance story end to end against scripted
in-process replicas (the fleet logic's wind tunnel — no engine, no
jax), with a deterministic ``COLD_SPAWN_S`` sleep in the replica
factory modeling a real decode worker's spawn+compile cost:

1. wave 1 — kill a busy replica of a 2-live + 1-standby fleet: repair
   by warm-standby **promotion** (the spawn cost was paid off the
   critical path by the background replenisher);
2. wave 2 — drain the standby pool, kill again: repair by blocking
   **cold spawn**;
3. a brownout episode on a small single-replica gateway: flood to rung
   3, then drain and watch the hysteretic release back to 0.

The servput accountant prices both reforms against the same pricing
(telemetry/servput.py) and the final JSON line carries the tentpole's
number — the promoted reform must lose strictly fewer points than the
cold one.  All fleet verdicts (promotion, brownout transitions) land
in a throwaway Brain warehouse — wave verdicts live through
``attach_warehouse``, brownout verdicts through ``ingest_events`` —
and the drill smokes ``fleet_report()`` to see that ``brain report``
renders them as incident rows.

Never gates (tier-1 owns the real-process SIGKILL drill in
tests/test_serving_fleet.py); this is an operator's "failover still
promotes and brownout still releases" receipt on scripted replicas.
Forced CPU, pure host-side, never touches a chip.
"""

import itertools
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from dlrover_tpu.brain.warehouse import TelemetryWarehouse  # noqa: E402
from dlrover_tpu.serving.fleet import BrownoutController  # noqa: E402
from dlrover_tpu.serving.gateway import InferenceGateway  # noqa: E402
from dlrover_tpu.telemetry.servput import serve_incidents  # noqa: E402

BUDGET = 12
COLD_SPAWN_S = 0.35  # stands in for process spawn + jit warmup
# serve_incidents attributes recovery from verdicts within ±2s of the
# incident window (_TRIGGER_LOOKBACK_S); waves closer than that would
# cross-attribute each other's serve_promote.
WAVE_GAP_S = 2.2


class ScriptedReplica:
    """Deterministic one-token-per-poll replica (tests' FakeReplica)."""

    _ids = itertools.count()

    def __init__(self):
        self.uid = f"drill-{next(ScriptedReplica._ids)}"
        self._alive = True
        self._reqs = {}
        self._ticks = 0

    def submit(self, rid, prompt, gen_budget, orig_prompt_len, trace=""):
        self._reqs[rid] = {
            "prompt": list(prompt), "budget": int(gen_budget), "done": 0,
        }
        return True, ""

    def poll(self):
        if not self._alive:
            raise ConnectionError("replica killed")
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
        return True

    def alive(self):
        return self._alive

    def kill(self):
        self._alive = False

    def stop(self):
        self._alive = False


def factory():
    time.sleep(COLD_SPAWN_S)
    return ScriptedReplica()


PROMPTS = [[1 + (i * 7 + j) % 50 for j in range(n)]
           for i, n in enumerate((5, 23, 17, 9))]


def run_wave(gw):
    """Submit the mixture, kill a busy replica mid-flight, drain.

    Scripted replicas restart their token script on replay, so the
    zero-loss check is structural (the journal's contract): every
    request finishes with its prompt intact and EXACTLY gen_budget
    generated tokens — none lost to the kill, none double-committed by
    the replay."""
    rids = [gw.submit(p)["request_id"] for p in PROMPTS]
    deadline = time.time() + 30
    while time.time() < deadline:
        gw.pump()
        if sum(len(gw._requests[r].committed) for r in rids) >= 6:
            break
    busy = {
        gw._requests[r].assigned for r in rids
        if gw._requests[r].state == "running"
    }
    victim = next(m for m in gw.fleet.live_members() if m.uid in busy)
    victim.replica.kill()
    outs = [gw.get(r, timeout_s=30) for r in rids]
    return all(
        o.get("ok")
        and o["tokens"][:len(p)] == list(p)
        and len(o["tokens"]) == len(p) + BUDGET
        for o, p in zip(outs, PROMPTS)
    )


def wait_for_standby(gw, n=1, timeout_s=30):
    deadline = time.time() + timeout_s
    while gw.fleet.standby_count() < n and time.time() < deadline:
        time.sleep(0.05)
    return gw.fleet.standby_count() >= n


def brownout_episode():
    """Flood a tiny gateway to rung 3, drain, verify hysteretic exit."""
    brown = BrownoutController(
        enter=(0.3, 0.5, 0.7), exit_ratio=0.5, down_dwell_s=0.05,
        gen_budget_cap=4, shed_below_priority=1,
    )
    gw = InferenceGateway(
        lambda: ScriptedReplica(), n_replicas=1, n_standbys=0,
        default_gen_budget=10, max_queue_tokens=100, retention_s=None,
        brownout=brown,
    )
    try:
        gw.pump()
        for _ in range(6):
            gw.submit([1, 2, 3])
        gw.pump()
        peak = brown.level
        shed = not gw.submit([4], priority=0).get("ok")
        deadline = time.time() + 30
        while brown.level > 0 and time.time() < deadline:
            gw.pump()
            time.sleep(0.02)
        return {
            "peak": peak,
            "released": brown.level == 0,
            "low_priority_shed_at_peak": shed,
            "transitions": [tr["level"] for tr in brown.transitions],
        }, list(gw.events)
    finally:
        gw.stop()


def main() -> int:
    out = {"ok": False}

    gw = InferenceGateway(
        factory, n_replicas=2, n_standbys=1,
        default_gen_budget=BUDGET, max_queue_tokens=4096,
        retention_s=None,
    )
    db = os.path.join(
        tempfile.mkdtemp(prefix="serve_chaos_"), "drill.sqlite"
    )
    wh = TelemetryWarehouse(db)
    gw.attach_warehouse(wh, job_uid="serve-chaos-drill")
    try:
        gw.pump()  # cold-spawn the live pool, kick the replenisher
        if not wait_for_standby(gw):
            out["error"] = "standby pool never warmed"
            print(json.dumps(out))
            return 1
        cold_baseline = gw.fleet.cold_spawns  # initial pool + standby

        wave1_ok = run_wave(gw)  # warm standby -> promotion
        wave1_cold = gw.fleet.cold_spawns
        if not wait_for_standby(gw):
            out["error"] = "replenisher never restored the standby"
            print(json.dumps(out))
            return 1
        time.sleep(WAVE_GAP_S)

        # Drain the warm pool: the same kill now cold-spawns.
        gw.fleet.target_standby = 0
        for m in list(gw.fleet.standby_members()):
            gw.fleet.detach(m)
            m.replica.stop()
        wave2_ok = run_wave(gw)

        incs = serve_incidents(gw.events)
        out["zero_loss"] = bool(wave1_ok and wave2_ok)
        out["promotions"] = gw.fleet.promotions
        # Reform-path cold spawns only: the initial pool and the
        # background replenisher are off the critical path.
        out["wave1_cold_spawns"] = wave1_cold - cold_baseline
        out["wave2_cold_spawns"] = gw.fleet.cold_spawns - wave1_cold
        out["disruptions"] = gw.disruptions
        out["incidents"] = len(incs)
        if len(incs) >= 2:
            out["promoted_recovery"] = incs[0]["recovery"]
            out["cold_recovery"] = incs[1]["recovery"]
            out["promoted_reform_pts"] = round(
                incs[0]["servput_points"], 3
            )
            out["cold_reform_pts"] = round(incs[1]["servput_points"], 3)
            out["delta_pts"] = round(
                incs[1]["servput_points"] - incs[0]["servput_points"], 3
            )

        out["brownout"], brown_events = brownout_episode()
        wh.ingest_events("serve-chaos-drill", brown_events)

        freq = wh.incident_frequency("serve-chaos-drill")
        out["warehouse_incidents"] = sum(freq.values())
        out["warehouse_triggers"] = freq
        report = wh.fleet_report()
        out["report_renders_incidents"] = bool(
            report.get("incident_frequency", {}).get("serve_promote")
            and report.get("incident_frequency", {}).get("serve_brownout")
        )

        out["ok"] = bool(
            out["zero_loss"]
            and out["promotions"] == 1
            and out["wave1_cold_spawns"] == 0
            and out["wave2_cold_spawns"] == 1
            and len(incs) == 2
            and incs[0]["recovery"] == "promotion"
            and incs[1]["recovery"] == "cold_spawn"
            and out.get("delta_pts", 0) > 0
            and out["brownout"]["peak"] == 3
            and out["brownout"]["released"]
            and out["brownout"]["low_priority_shed_at_peak"]
            and out["report_renders_incidents"]
        )
    finally:
        gw.stop()
        wh.close()
        try:
            os.remove(db)
            os.rmdir(os.path.dirname(db))
        except OSError:
            pass
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())

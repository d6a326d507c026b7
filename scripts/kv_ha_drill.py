"""Report-only KV high-availability drill.

Runs the always-on embedding-service story end to end against
in-process shard servers on loopback RPC (no subprocesses, no jax
device work — the replication plane's wind tunnel):

1. a replicated shard (kv-0 primary + follower, sync chain-delta
   replication at epoch 1) and a chain-durable unreplicated shard
   (kv-1) take a zipfian write/read mixture; bounded-staleness reads
   route to the follower and the anti-entropy digest scan reports it
   clean;
2. the primary dies: the health ladder walks to ``unhealthy``, the HA
   manager runs a lease-fenced **promotion** (epoch 2, zero key
   movement), and every previously acked row is still served — the
   sync chain means acked == replicated;
3. kv-1 dies with no follower: the fallback rung is a **chain
   restore** (respawn + replay the durability chain + replace the ring
   seat).  Both recoveries are priced wall-clock and the final JSON
   line carries the tentpole's number — promotion must be strictly
   cheaper than the chain restore it makes unnecessary.

All ``kv_failover`` verdicts land in a throwaway Brain warehouse via
``ingest_events``, the promoted shard's hot-key top-K summary lands
via ``add_kv_summary``, and the drill smokes ``fleet_report()`` to see
that ``brain report`` renders the failover incidents and the hot-key
skew rows.

Never gates (tier-1 owns the real-process SIGKILL promotion drill in
tests/test_kv_replication.py); this is an operator's "promotion
still works and the freshness plane still accounts" receipt: its
seconds are the CPU's and prove no speed.  Forced CPU, pure host-side, never touches a chip.
"""

import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

from dlrover_tpu.brain.warehouse import TelemetryWarehouse  # noqa: E402
from dlrover_tpu.kv_service import (  # noqa: E402
    KvHaManager,
    KvShardServer,
    ShardedKvClient,
)

DIM = 16
JOB = "kv-ha-drill"


def _zipf_head(keys, n=64):
    """The hot head of the keyspace: repeated gathers on these rows
    make the per-shard top-K accounting show real skew."""
    return keys[: min(n, len(keys))]


def main() -> int:
    out = {"ok": False}
    events = []

    def emit(ev, **kw):
        events.append({"ev": ev, **kw})

    tmp = tempfile.mkdtemp(prefix="kv_ha_drill_")
    chain_dir = os.path.join(tmp, "chain-kv-1")
    db = os.path.join(tmp, "drill.sqlite")
    os.makedirs(chain_dir, exist_ok=True)

    primary = KvShardServer(
        "kv-0", dim=DIM, slots=2, port=0, role="primary", epoch=1, seed=3
    ).start()
    follower = KvShardServer(
        "kv-0-f0", dim=DIM, slots=2, port=0, role="follower", epoch=1,
        seed=5,
    ).start()
    shard1 = KvShardServer(
        "kv-1", dim=DIM, slots=2, port=0, chain_dir=chain_dir,
        durability="apply", seed=7,
    ).start()
    replacement = None
    client = ShardedKvClient(
        {
            "kv-0": f"localhost:{primary.port}",
            "kv-1": f"localhost:{shard1.port}",
        },
        dim=DIM,
        staleness_bound=0,
        rpc_timeout=10.0,
    )
    ha = KvHaManager(client, emit=emit, miss_limit=2, poll_timeout=1.0)
    wh = TelemetryWarehouse(db)
    try:
        cfg = ha.configure(
            "kv-0", {f"localhost:{follower.port}": "kv-0-f0"},
            epoch=1, mode="sync",
        )
        out["followers"] = len(cfg["followers"])

        # -- traffic: every insert acked through the sync chain --------
        rng = np.random.RandomState(11)
        keys = (np.arange(6000, dtype=np.int64) * 13) + 1
        oracle = rng.randn(len(keys), DIM).astype(np.float32)
        for lo in range(0, len(keys), 500):
            client.insert(keys[lo:lo + 500], oracle[lo:lo + 500])
        head = _zipf_head(keys)
        for _ in range(5):  # the zipfian head: hot-key fodder
            client.lookup(head)

        # -- bounded-staleness reads route to the caught-up follower ---
        client.refresh_replica_state("kv-0")
        got, found = client.lookup(keys)
        out["zero_loss_pre_failover"] = bool(
            found.all() and np.allclose(got, oracle, rtol=1e-6)
        )
        out["replica_reads"] = int(client.rpc_counts.get("kv-0-f0", 0))
        out["anti_entropy"] = ha.anti_entropy("kv-0")

        # -- kill the primary; walk the miss ladder to the trigger -----
        primary.stop(grace=0)
        health, deadline = "ok", time.monotonic() + 30
        while health != "unhealthy" and time.monotonic() < deadline:
            health = ha.poll("kv-0")
        out["health"] = health
        summary = ha.promote("kv-0")
        out["promotion"] = {
            "recovery": summary["recovery"],
            "epoch": summary["epoch"],
            "unavailable_s": round(summary["unavailable_s"], 4),
        }

        # -- zero acked-write loss + writes at the new epoch -----------
        got, found = client.lookup(keys)
        out["zero_loss"] = bool(
            found.all() and np.allclose(got, oracle, rtol=1e-6)
        )
        fresh = (np.arange(64, dtype=np.int64) * 13) + 7
        client.insert(fresh, np.ones((len(fresh), DIM), np.float32))
        _, ffound = client.lookup(fresh)
        out["post_failover_writes"] = bool(ffound.all())

        # -- price the fallback rung: kill kv-1, chain-restore it ------
        shard1.stop(grace=0)
        t0 = time.monotonic()
        replacement = KvShardServer(
            "kv-1", dim=DIM, slots=2, port=0, chain_dir=chain_dir,
            durability="apply", seed=99,
        ).start()
        cr = ha.chain_restore("kv-1", f"localhost:{replacement.port}")
        chain_restore_s = time.monotonic() - t0
        out["chain_restore"] = {
            "recovery": cr["recovery"],
            "restored_rows": cr.get("restored_rows"),
            "unavailable_s": round(chain_restore_s, 4),
        }
        got, found = client.lookup(keys)
        out["zero_loss_chain_restore"] = bool(
            found.all() and np.allclose(got, oracle, rtol=1e-6)
        )
        out["promotion_beats_chain_restore"] = bool(
            summary["unavailable_s"] < chain_restore_s
        )

        # -- verdicts + hot keys into the warehouse; smoke the report --
        wh.ingest_events(JOB, events)
        wh.add_kv_summary(JOB, follower.hot_key_summary())
        freq = wh.incident_frequency(JOB)
        out["warehouse_triggers"] = freq
        report = wh.fleet_report()
        out["report_renders_incidents"] = bool(
            report.get("incident_frequency", {}).get("kv_failover")
        )
        out["report_renders_hot_keys"] = bool(report.get("kv_hot_keys"))

        out["ok"] = bool(
            out["zero_loss_pre_failover"]
            and out["replica_reads"] > 0
            and out["anti_entropy"] == {"kv-0-f0": "clean"}
            and out["health"] == "unhealthy"
            and out["promotion"]["recovery"] == "promotion"
            and out["promotion"]["epoch"] == 2
            and out["zero_loss"]
            and out["post_failover_writes"]
            and out["zero_loss_chain_restore"]
            and out["promotion_beats_chain_restore"]
            and freq.get("kv_failover", 0) >= 2
            and out["report_renders_incidents"]
            and out["report_renders_hot_keys"]
        )
    finally:
        client.close()
        for srv in (primary, follower, shard1, replacement):
            if srv is not None:
                try:
                    srv.stop(grace=0)
                except Exception:  # noqa: BLE001 — already stopped
                    pass
        wh.close()
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())

"""Real-process shard entrypoint.

``python -m dlrover_tpu.kv_service --name kv-0 --dim 32 --ready-file f``
starts one :class:`KvShardServer` on an ephemeral port and writes a
JSON ready file ``{"name", "port", "http_port", "pid", "restored_rows",
"recovery_s"}`` once serving — the same handshake idiom as the CPU
harness (``runtime/harness.py``).  Used by ``scripts/kv_ha_drill.py``
and the chaos tests, which need the shard to be a genuinely separate OS
process (its own GIL, its own C++ store, killable with SIGKILL).
"""

import argparse
import json
import os
import signal
import sys
import time

from dlrover_tpu.kv_service.server import KvShardServer


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="dlrover_tpu kv shard server")
    ap.add_argument("--name", required=True, help="stable shard name (kv-0)")
    ap.add_argument("--dim", type=int, required=True)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--http-port", type=int, default=None,
                    help="serving-time lookup endpoint (0=ephemeral, "
                         "omit=disabled)")
    ap.add_argument("--chain-dir", default=None,
                    help="delta-chain directory; restores on start")
    ap.add_argument("--durability", default="none",
                    choices=("none", "interval", "apply"))
    ap.add_argument("--save-every", type=int, default=64)
    ap.add_argument("--full-interval", type=int, default=16)
    ap.add_argument("--max-deltas", type=int, default=64)
    ap.add_argument("--init-scale", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--role", default="primary",
                    choices=("primary", "follower"),
                    help="replication role (follower shards only accept "
                         "replication links and read-only gathers)")
    ap.add_argument("--epoch", type=int, default=0,
                    help="initial lease epoch (0 = unreplicated legacy)")
    ap.add_argument("--repl-mode", default="sync",
                    choices=("sync", "async", "manual"),
                    help="how the primary pushes to followers")
    ap.add_argument("--ready-file", default=None,
                    help="write a JSON handshake here once serving")
    args = ap.parse_args(argv)

    server = KvShardServer(
        name=args.name,
        dim=args.dim,
        slots=args.slots,
        port=args.port,
        init_scale=args.init_scale,
        seed=args.seed,
        chain_dir=args.chain_dir,
        durability=args.durability,
        save_every=args.save_every,
        full_interval=args.full_interval,
        max_deltas=args.max_deltas,
        http_port=args.http_port,
        role=args.role,
        epoch=args.epoch,
        repl_mode=args.repl_mode,
    )
    server.start()

    stop = {"flag": False}

    def _term(signum, frame):
        stop["flag"] = True

    signal.signal(signal.SIGTERM, _term)
    signal.signal(signal.SIGINT, _term)

    if args.ready_file:
        payload = {
            "name": args.name,
            "port": server.port,
            "http_port": server.http_port,
            "pid": os.getpid(),
            "restored_rows": server.restored_rows,
            "recovery_s": server.recovery_s,
            "role": server.role,
            "epoch": server.lease_epoch,
        }
        tmp = args.ready_file + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, args.ready_file)

    try:
        while not stop["flag"]:
            time.sleep(0.2)
    finally:
        server.stop(grace=1.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""Backfill the telemetry warehouse from the repo's flat perf history.

Ingests ``perf_history.jsonl`` (every round's throughput entry, measured
or blind) and the ``BENCH_r0*.json`` harness outputs, so rounds 1..N are
queryable through ``python -m dlrover_tpu.brain report`` and the
warm-start API from day one.

    python scripts/warehouse_backfill.py --db WAREHOUSE.sqlite

Idempotence note: re-running appends duplicate perf records (the ledger
is append-only and entries carry no unique id); backfill into a fresh db
or let retention cap growth.
"""

import argparse
import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from dlrover_tpu.brain.warehouse import TelemetryWarehouse  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser("warehouse-backfill")
    p.add_argument(
        "--db", default="WAREHOUSE.sqlite",
        help="warehouse sqlite path (created if missing)",
    )
    p.add_argument(
        "--root", default=None,
        help="directory holding perf_history.jsonl / BENCH_r0*.json "
        "(default: the repo root)",
    )
    args = p.parse_args(argv)
    wh = TelemetryWarehouse(args.db)
    try:
        counts = wh.backfill(root=args.root)
    finally:
        wh.close()
    print(json.dumps({"db": args.db, **counts}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

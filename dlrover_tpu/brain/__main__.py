"""``python -m dlrover_tpu.brain`` — the Brain's operator CLI.

Subcommands:

``report``     render the telemetry warehouse as a fleet report
               (markdown to stdout; ``--json`` for machine-readable)
``backfill``   ingest the repo's flat perf history (perf_history.jsonl +
               BENCH_r0*.json) into a warehouse db
``plan``       what-if capacity planner: price a proposed fleet
               (replicas, standbys, chip generation) against recorded
               traffic in servput points, with a drafted config diff
``serve``      run the Brain gRPC server (delegates to ``brain.main``)

``python -m dlrover_tpu.brain.main`` keeps working as the bare server
entrypoint for existing deployments.
"""

import argparse
import json
import os
import sys

from dlrover_tpu.brain.warehouse import (
    TelemetryWarehouse,
    default_warehouse_path,
)


def _add_db_arg(p: argparse.ArgumentParser):
    p.add_argument(
        "--db", default=None,
        help="warehouse sqlite path (default: $DLROVER_WAREHOUSE_DB, else "
        "the telemetry dir's warehouse.sqlite)",
    )


def parse_args(argv=None):
    p = argparse.ArgumentParser("dlrover-tpu-brain")
    sub = p.add_subparsers(dest="cmd", required=True)

    rep = sub.add_parser("report", help="render the fleet report")
    _add_db_arg(rep)
    rep.add_argument(
        "--json", dest="json_out", default=None, metavar="PATH",
        help="also write the report as JSON ('-' = stdout instead of "
        "markdown)",
    )
    rep.add_argument(
        "--md", dest="md_out", default=None, metavar="PATH",
        help="also write the markdown report to a file",
    )

    bf = sub.add_parser(
        "backfill", help="ingest perf_history.jsonl + BENCH_r0*.json"
    )
    _add_db_arg(bf)
    bf.add_argument(
        "--root", default=None,
        help="repo root holding the flat files (default: autodetect)",
    )

    pl = sub.add_parser(
        "plan", help="price a proposed fleet against recorded traffic"
    )
    _add_db_arg(pl)
    pl.add_argument("--replicas", type=int, required=True,
                    help="proposed max live replicas")
    pl.add_argument("--standbys", type=int, required=True,
                    help="proposed warm-standby pool size")
    pl.add_argument("--chip-gen", default="v5e",
                    help="chip generation to price on (tpu/v5e/v5p/v6e)")
    pl.add_argument("--job", default="",
                    help="restrict traffic history to one job uid")
    pl.add_argument("--n-params", type=int, default=1_000_000_000,
                    help="model size for the roofline capacity fallback")
    pl.add_argument("--lead-s", type=float, default=30.0,
                    help="pre-warm lead the predictive replay uses")
    pl.add_argument(
        "--json", dest="json_out", default=None, metavar="PATH",
        help="also write the plan as JSON ('-' = stdout instead of "
        "markdown)",
    )
    pl.add_argument(
        "--md", dest="md_out", default=None, metavar="PATH",
        help="also write the markdown plan to a file",
    )

    srv = sub.add_parser("serve", help="run the Brain gRPC server")
    srv.add_argument("rest", nargs=argparse.REMAINDER,
                     help="arguments for dlrover_tpu.brain.main")
    return p.parse_args(argv)


def cmd_report(args) -> int:
    from dlrover_tpu.brain.report import (
        build_report,
        render_json,
        render_markdown,
    )

    db = args.db or default_warehouse_path()
    if db != ":memory:" and not os.path.exists(db):
        print(f"warehouse db not found: {db}", file=sys.stderr)
        return 2
    wh = TelemetryWarehouse(db)
    try:
        report = build_report(wh)
    finally:
        wh.close()
    md = render_markdown(report)
    js = render_json(report)
    if args.json_out == "-":
        print(js)
    else:
        print(md, end="")
        if args.json_out:
            with open(args.json_out, "w", encoding="utf-8") as f:
                f.write(js + "\n")
    if args.md_out:
        with open(args.md_out, "w", encoding="utf-8") as f:
            f.write(md)
    return 0


def cmd_backfill(args) -> int:
    db = args.db or default_warehouse_path()
    wh = TelemetryWarehouse(db)
    try:
        counts = wh.backfill(root=args.root)
    finally:
        wh.close()
    print(json.dumps({"db": db, **counts}))
    return 0


def cmd_plan(args) -> int:
    from dlrover_tpu.brain.decision import (
        plan_capacity,
        render_plan_markdown,
    )

    db = args.db or default_warehouse_path()
    if db != ":memory:" and not os.path.exists(db):
        print(f"warehouse db not found: {db}", file=sys.stderr)
        return 2
    wh = TelemetryWarehouse(db)
    try:
        plan = plan_capacity(
            wh,
            replicas=args.replicas,
            standbys=args.standbys,
            chip_gen=args.chip_gen,
            job_uid=args.job,
            n_params=args.n_params,
            lead_s=args.lead_s,
        )
    finally:
        wh.close()
    md = render_plan_markdown(plan)
    js = json.dumps(plan, indent=2, sort_keys=True, default=str)
    if args.json_out == "-":
        print(js)
    else:
        print(md, end="")
        if args.json_out:
            with open(args.json_out, "w", encoding="utf-8") as f:
                f.write(js + "\n")
    if args.md_out:
        with open(args.md_out, "w", encoding="utf-8") as f:
            f.write(md)
    return 0


def cmd_serve(args) -> int:
    from dlrover_tpu.brain import main as brain_main

    rest = list(args.rest)
    if rest and rest[0] == "--":
        rest = rest[1:]
    brain_main.main(rest)
    return 0


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.cmd == "report":
        return cmd_report(args)
    if args.cmd == "backfill":
        return cmd_backfill(args)
    if args.cmd == "plan":
        return cmd_plan(args)
    return cmd_serve(args)


if __name__ == "__main__":
    sys.exit(main())

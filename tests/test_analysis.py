"""Tests for dlrover_tpu.analysis — the AST invariant checker.

Each checker is exercised against a seeded-violation fixture and its
clean twin (tests/analysis_fixtures/), plus the suppression pragma, the
--select/--ignore CLI surface, and the acceptance criteria from the
issue: the checked-in tree lints clean, and re-introducing the PR 3
frombuffer bug is caught.
"""

import json
import os
import textwrap

import pytest

from dlrover_tpu.analysis import run_paths
from dlrover_tpu.analysis.cli import main as cli_main

pytestmark = pytest.mark.analysis

HERE = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(HERE)
FIXTURES = os.path.join(HERE, "analysis_fixtures")


def fx(*parts):
    return os.path.join(FIXTURES, *parts)


def run_fixture(name, **kw):
    kw.setdefault("project_root", REPO_ROOT)
    return run_paths([fx(name)], **kw)


def codes(report):
    return [f.code for f in report.findings]


@pytest.fixture(scope="module")
def package_run():
    """One run of every checker over ``dlrover_tpu/``, and its seconds."""
    import time

    start = time.monotonic()
    report = run_paths(
        [os.path.join(REPO_ROOT, "dlrover_tpu")], project_root=REPO_ROOT
    )
    return report, time.monotonic() - start


class TestDonationChecker:
    def test_bad_fixture_flagged(self):
        report = run_fixture("donation_bad.py")
        got = codes(report)
        assert got.count("DLR001") >= 3  # return, container return, sink
        assert set(got) == {"DLR001"}

    def test_clean_twin_passes(self):
        assert not run_fixture("donation_clean.py").findings

    def test_reintroducing_pr3_frombuffer_bug_is_caught(self, tmp_path):
        """Acceptance criterion: the pre-fix shm_loader consumer shape —
        frombuffer views yielded in a dict — must flag DLR001."""
        src = textwrap.dedent(
            """
            import numpy as np

            def batches(self, metas):
                for slot, meta in metas:
                    batch = {}
                    buf = self._shms[slot].buf
                    for key, (dtype, shape, off) in meta.items():
                        batch[key] = np.frombuffer(
                            buf, dtype=dtype, offset=off
                        ).reshape(shape)
                    yield batch
            """
        )
        p = tmp_path / "regressed_loader.py"
        p.write_text(src)
        report = run_paths([str(p)], project_root=REPO_ROOT)
        assert "DLR001" in codes(report)
        (finding,) = [f for f in report.findings if f.code == "DLR001"]
        assert "yield" in finding.message


class TestTelemetrySchemaChecker:
    def test_bad_fixture_flagged(self):
        report = run_fixture("telemetry_bad.py")
        got = codes(report)
        assert got.count("DLR002") == 4  # emit typo + 3 comparison typos
        messages = " ".join(f.message for f in report.findings)
        assert "rendezvouz" in messages
        assert "compile_beginn" in messages
        assert "preemptt" in messages
        assert "bundel" in messages

    def test_clean_twin_passes(self):
        assert not run_fixture("telemetry_clean.py").findings

    def test_unknown_emit_literal_fails_analysis(self, tmp_path):
        """Canary: the closed schema stays closed — ANY emit literal not
        in EVENT_TYPES must produce a DLR002, so schema growth always
        goes through events.py."""
        p = tmp_path / "newcomer.py"
        p.write_text(
            "def run(emit):\n"
            '    emit("flight_checkpoint", rank=0)\n'
        )
        report = run_paths([str(p)], project_root=REPO_ROOT)
        assert codes(report) == ["DLR002"]
        (finding,) = report.findings
        assert "flight_checkpoint" in finding.message


class TestFaultPointChecker:
    def test_bad_project_flagged(self):
        root = fx("fault_bad_project")
        report = run_paths([root], project_root=root)
        got = codes(report)
        # undocumented + unexercised (same call site) + ghost doc row
        assert got.count("DLR003") == 3
        messages = " ".join(f.message for f in report.findings)
        assert "undocumented_point" in messages
        assert "ghost_point" in messages

    def test_clean_project_passes(self):
        root = fx("fault_clean_project")
        assert not run_paths([root], project_root=root).findings


class TestThreadSharedStateChecker:
    def test_bad_fixture_flagged(self):
        report = run_fixture("threads_bad.py")
        got = codes(report)
        assert got.count("DLR004") == 2  # Poller race + annotated Shared
        messages = " ".join(f.message for f in report.findings)
        assert "_count" in messages
        assert "shared-across-threads" in messages

    def test_clean_twin_passes(self):
        assert not run_fixture("threads_clean.py").findings


class TestRpcPolicyChecker:
    def test_bad_fixture_flagged(self):
        report = run_fixture("rpc_bad.py")
        got = codes(report)
        assert "DLR005" in got  # unmarked MasterClient.get_status
        assert "DLR006" in got  # uninterruptible 60 s poll loop

    def test_clean_twin_passes(self):
        assert not run_fixture("rpc_clean.py").findings


class TestCheckpointIoChecker:
    def test_bad_fixture_flagged(self):
        report = run_fixture(os.path.join("checkpoint", "ckpt_io_bad.py"))
        got = codes(report)
        # wb open + mode="a" open + os.open(O_WRONLY) + dynamic mode
        assert got.count("DLR007") == 4
        assert set(got) == {"DLR007"}

    def test_clean_twin_passes(self):
        report = run_fixture(os.path.join("checkpoint", "ckpt_io_clean.py"))
        assert not report.findings

    def test_outside_checkpoint_package_is_exempt(self, tmp_path):
        p = tmp_path / "free_writer.py"
        p.write_text(
            "def dump(path, blob):\n"
            "    with open(path, 'wb') as f:\n"
            "        f.write(blob)\n"
        )
        report = run_paths([str(p)], project_root=REPO_ROOT)
        assert "DLR007" not in codes(report)

    def test_storage_py_itself_is_exempt(self, tmp_path):
        d = tmp_path / "checkpoint"
        d.mkdir()
        p = d / "storage.py"
        p.write_text(
            "def write(path, blob):\n"
            "    with open(path, 'wb') as f:\n"
            "        f.write(blob)\n"
        )
        report = run_paths([str(p)], project_root=REPO_ROOT)
        assert "DLR007" not in codes(report)

    def test_reintroducing_bare_kv_savez_write_is_caught(self, tmp_path):
        """Acceptance canary: the pre-fix kv_checkpoint shape — writing
        the npz via a bare tmp-file open under checkpoint/ — must flag
        DLR007."""
        d = tmp_path / "checkpoint"
        d.mkdir()
        p = d / "kv_checkpoint.py"
        p.write_text(
            "import numpy as np\n"
            "def write_atomic(path, arrays):\n"
            "    with open(path + '.tmp', 'wb') as f:\n"
            "        np.savez(f, **arrays)\n"
        )
        report = run_paths([str(p)], project_root=REPO_ROOT)
        assert "DLR007" in codes(report)


class TestDecisionDeterminismChecker:
    def test_bad_fixture_flagged(self):
        report = run_fixture(os.path.join("decision", "decision_bad.py"))
        got = codes(report)
        # time.time + random.choice + datetime.now + np.random.normal;
        # the `# dlr: nondet`-annotated random.random() is exempt
        assert got.count("DLR013") == 4
        assert set(got) == {"DLR013"}
        messages = " ".join(f.message for f in report.findings)
        assert "wall clock" in messages
        assert "randomness" in messages

    def test_clean_twin_passes(self):
        report = run_fixture(
            os.path.join("decision", "decision_clean.py")
        )
        assert not report.findings

    def test_outside_decision_package_is_exempt(self, tmp_path):
        p = tmp_path / "pump.py"
        p.write_text(
            "import time\n"
            "def tick():\n"
            "    return time.time()\n"
        )
        report = run_paths([str(p)], project_root=REPO_ROOT)
        assert "DLR013" not in codes(report)

    def test_real_decision_package_is_clean(self):
        import glob as _glob

        pkg = os.path.join(
            REPO_ROOT, "dlrover_tpu", "brain", "decision"
        )
        files = sorted(_glob.glob(os.path.join(pkg, "*.py")))
        assert files
        report = run_paths(files, project_root=REPO_ROOT)
        assert "DLR013" not in codes(report)


class TestPromHygieneChecker:
    def test_bad_fixture_flagged(self):
        report = run_fixture("prom_bad.py")
        got = codes(report)
        # prefix + counter-suffix on the same call, counter suffix,
        # histogram suffix, step label, pid-derived label
        assert got.count("DLR008") == 6
        assert set(got) == {"DLR008"}
        messages = " ".join(f.message for f in report.findings)
        assert "dlrover_" in messages
        assert "_total" in messages
        assert "unit suffix" in messages
        assert "cardinality" in messages

    def test_clean_twin_passes(self):
        assert not run_fixture("prom_clean.py").findings

    def test_gauge_suffix_exempt(self, tmp_path):
        p = tmp_path / "gauges.py"
        p.write_text(
            "def publish(metrics):\n"
            '    metrics.gauge("dlrover_node_memory_mb", "m").set(1.0)\n'
        )
        report = run_paths([str(p)], project_root=REPO_ROOT)
        assert "DLR008" not in codes(report)

    def test_step_valued_label_is_caught(self, tmp_path):
        """The cardinality rule sees through the kwarg name: any label
        whose value derives from a step counter is flagged."""
        p = tmp_path / "sneaky.py"
        p.write_text(
            "def publish(metrics, state):\n"
            '    metrics.counter("dlrover_beats_total", "b").inc(\n'
            "        phase=str(state.global_step)\n"
            "    )\n"
        )
        report = run_paths([str(p)], project_root=REPO_ROOT)
        assert codes(report) == ["DLR008"]


class TestSqlHygieneChecker:
    def test_bad_fixture_flagged(self):
        report = run_fixture("sql_bad.py")
        got = codes(report)
        # connect outside the store layer, f-string, %-format,
        # .format(), and value-splicing concatenation
        assert got.count("DLR009") == 5
        assert set(got) == {"DLR009"}
        messages = " ".join(f.message for f in report.findings)
        assert "store layer" in messages
        assert "parameter" in messages

    def test_clean_twin_passes(self):
        assert not run_fixture("sql_clean.py").findings

    def test_store_layer_may_connect(self, tmp_path):
        """brain/store.py and brain/warehouse.py are the sanctioned
        sqlite owners — connects there are not findings."""
        brain = tmp_path / "dlrover_tpu" / "brain"
        brain.mkdir(parents=True)
        p = brain / "warehouse.py"
        p.write_text(
            "import sqlite3\n"
            "def open_db(path):\n"
            "    return sqlite3.connect(path)\n"
        )
        report = run_paths([str(p)], project_root=str(tmp_path))
        assert "DLR009" not in codes(report)

    def test_dynamic_sql_in_store_layer_still_flagged(self, tmp_path):
        """The store layer may own the connection, but spliced SQL is
        banned everywhere — including inside brain/store.py."""
        brain = tmp_path / "dlrover_tpu" / "brain"
        brain.mkdir(parents=True)
        p = brain / "store.py"
        p.write_text(
            "def lookup(conn, uid):\n"
            "    conn.execute(f\"SELECT * FROM t WHERE id='{uid}'\")\n"
        )
        report = run_paths([str(p)], project_root=str(tmp_path))
        assert codes(report) == ["DLR009"]


class TestKvBatchChecker:
    def test_bad_fixture_flagged(self):
        report = run_fixture("kv_rpc_bad.py")
        got = codes(report)
        # wrapped single-element, bare var over key iterable,
        # comprehension, keyword-argument apply
        assert got.count("DLR010") == 4
        assert set(got) == {"DLR010"}
        messages = " ".join(f.message for f in report.findings)
        assert "per-key" in messages
        assert "ONE call" in messages

    def test_clean_twin_passes(self):
        assert not run_fixture("kv_rpc_clean.py").findings

    def test_per_owner_fanout_is_not_per_key(self, tmp_path):
        """The client's own idiom — partition once, one RPC per shard
        owner — must never flag, even though it loops over a dict of
        owners calling a wire method with the loop variable."""
        p = tmp_path / "fanout.py"
        p.write_text(
            "def fanout(client, ring, keys):\n"
            "    parts = ring.partition(keys)\n"
            "    for owner, batch in parts.items():\n"
            "        client.gather(batch)\n"
        )
        report = run_paths([str(p)], project_root=str(tmp_path))
        assert not report.findings

    def test_marker_waives_deliberate_per_key_probe(self, tmp_path):
        p = tmp_path / "probe.py"
        p.write_text(
            "def probe(kv_client, keys):\n"
            "    for k in keys:\n"
            "        kv_client.lookup([k])  # dlr: kv-per-key\n"
        )
        report = run_paths([str(p)], project_root=str(tmp_path))
        assert not report.findings

    def test_kv_service_package_is_clean(self):
        """The shipped client/server/reshard code must satisfy its own
        batching rule."""
        pkg = os.path.join(REPO_ROOT, "dlrover_tpu", "kv_service")
        files = [
            os.path.join(pkg, f) for f in sorted(os.listdir(pkg))
            if f.endswith(".py")
        ]
        report = run_paths(files, project_root=REPO_ROOT, select=["DLR010"])
        assert not report.findings


class TestLeaseFenceChecker:
    def test_bad_fixture_flagged(self):
        report = run_fixture("kv_fence_bad.py")
        got = codes(report)
        # unfenced apply, unfenced import, unfenced init-gather,
        # fence-after-apply (ordering violation)
        assert got.count("DLR014") == 4
        assert set(got) == {"DLR014"}
        messages = " ".join(f.message for f in report.findings)
        assert "split brain" in messages
        assert "lease epoch" in messages

    def test_clean_twin_passes(self):
        assert not run_fixture("kv_fence_clean.py").findings

    def test_unfenced_marker_waives_bootstrap_path(self, tmp_path):
        p = tmp_path / "bootstrap.py"
        p.write_text(
            "class KvSeedServer:\n"
            "    def seed(self, keys, rows):\n"
            "        self.table.import_rows(keys, rows)"
            "  # dlr: unfenced\n"
        )
        report = run_paths([str(p)], project_root=str(tmp_path))
        assert not report.findings

    def test_non_server_class_may_mutate_freely(self, tmp_path):
        """Only the wire surface owns the invariant — a checkpoint
        manager importing rows during restore has no remote writer to
        fence."""
        p = tmp_path / "ckpt.py"
        p.write_text(
            "class KvCheckpointManager:\n"
            "    def restore(self, keys, rows):\n"
            "        self.table.import_rows(keys, rows)\n"
        )
        report = run_paths([str(p)], project_root=str(tmp_path))
        assert "DLR014" not in codes(report)

    def test_epoch_comparison_counts_as_fence(self, tmp_path):
        """The replication push handler fences by comparing the message
        epoch against its lease directly — no _fence() call."""
        p = tmp_path / "push.py"
        p.write_text(
            "class KvShardServer:\n"
            "    def push(self, msg):\n"
            "        if msg.epoch < self._lease_epoch:\n"
            "            return 'stale_epoch'\n"
            "        self.table.import_rows(msg.keys, msg.rows)\n"
        )
        report = run_paths([str(p)], project_root=str(tmp_path))
        assert not report.findings

    def test_shipped_kv_service_is_fenced(self):
        """Acceptance criterion: every mutation path in the shipped
        shard server checks the lease before applying."""
        pkg = os.path.join(REPO_ROOT, "dlrover_tpu", "kv_service")
        files = [
            os.path.join(pkg, f) for f in sorted(os.listdir(pkg))
            if f.endswith(".py")
        ]
        report = run_paths(files, project_root=REPO_ROOT, select=["DLR014"])
        assert not report.findings


class TestServeHotLoopChecker:
    def test_bad_fixture_flagged(self):
        report = run_fixture("serve_bad.py")
        got = codes(report)
        # jit-in-step, print, sleep, open, json.dump, subprocess.run
        assert got.count("DLR011") == 6
        assert set(got) == {"DLR011"}
        messages = " ".join(f.message for f in report.findings)
        assert "retraces" in messages
        assert "stalls every in-flight slot" in messages

    def test_clean_twin_passes(self):
        assert not run_fixture("serve_clean.py").findings

    def test_non_serving_class_may_block(self, tmp_path):
        """Only serving-tier classes own the tick contract — a batch
        report builder's step() can sleep all it wants."""
        p = tmp_path / "offline.py"
        p.write_text(
            "import time\n"
            "class ReportBuilder:\n"
            "    def step(self):\n"
            "        time.sleep(1.0)\n"
        )
        report = run_paths([str(p)], project_root=str(tmp_path))
        assert not report.findings

    def test_serving_package_is_clean(self):
        """The shipped engine/gateway/worker ticks must satisfy their
        own hot-loop rule."""
        pkg = os.path.join(REPO_ROOT, "dlrover_tpu", "serving")
        files = [
            os.path.join(pkg, f) for f in sorted(os.listdir(pkg))
            if f.endswith(".py")
        ]
        files.append(
            os.path.join(REPO_ROOT, "dlrover_tpu", "rl", "serving.py")
        )
        report = run_paths(files, project_root=REPO_ROOT, select=["DLR011"])
        assert not report.findings


class TestTraceCtxChecker:
    def test_bad_fixture_flagged(self):
        report = run_fixture("trace_bad.py")
        got = codes(report)
        # 2 untraced request declarations + 2 trace-dropping call sites
        assert got.count("DLR012") == 4
        assert set(got) == {"DLR012"}
        messages = " ".join(f.message for f in report.findings)
        assert "ServeSubmit" in messages
        assert "KvGatherRequest" in messages
        assert "no-trace" in messages

    def test_clean_twin_passes(self):
        assert not run_fixture("trace_clean.py").findings

    def test_dropping_trace_from_gateway_submit_is_caught(self, tmp_path):
        """Acceptance canary: regressing the gateway's submit RPC to a
        bare ServeSubmit(...) must flag DLR012."""
        p = tmp_path / "regressed_gateway.py"
        p.write_text(
            "from dlrover_tpu.common import comm\n"
            "def submit(client, rid, prompt):\n"
            "    return client.get(0, 'gateway', comm.ServeSubmit(\n"
            "        request_id=rid, prompt=prompt, gen_budget=8))\n"
        )
        report = run_paths([str(p)], project_root=REPO_ROOT)
        assert "DLR012" in codes(report)

    def test_shipped_wire_paths_are_clean(self):
        """The shipped serving/kv wire code must thread trace context
        through every hop (or carry an explicit waiver)."""
        report = run_paths(
            [os.path.join(REPO_ROOT, "dlrover_tpu")],
            project_root=REPO_ROOT,
            select=["DLR012"],
        )
        assert not report.findings


class TestSuppression:
    def test_noqa_moves_finding_to_suppressed(self):
        report = run_fixture("suppressed.py")
        assert not report.findings
        assert len(report.suppressed) == 1
        assert report.suppressed[0].code == "DLR001"
        assert report.exit_code == 0

    def test_noqa_is_code_specific(self, tmp_path):
        p = tmp_path / "wrong_code.py"
        p.write_text(
            "import numpy as np\n"
            "def load(buf):\n"
            "    v = np.frombuffer(buf, dtype=np.int8)\n"
            "    return v  # dlr: noqa[DLR005]\n"
        )
        report = run_paths([str(p)], project_root=REPO_ROOT)
        assert codes(report) == ["DLR001"]  # wrong code: not suppressed

    def test_bare_noqa_suppresses_everything(self, tmp_path):
        p = tmp_path / "bare.py"
        p.write_text(
            "import numpy as np\n"
            "def load(buf):\n"
            "    v = np.frombuffer(buf, dtype=np.int8)\n"
            "    return v  # dlr: noqa\n"
        )
        report = run_paths([str(p)], project_root=REPO_ROOT)
        assert not report.findings
        assert len(report.suppressed) == 1


class TestSelectIgnore:
    def test_select_narrows_to_one_code(self):
        report = run_fixture("rpc_bad.py", select=["DLR005"])
        assert set(codes(report)) == {"DLR005"}

    def test_ignore_drops_a_code(self):
        report = run_fixture("rpc_bad.py", ignore=["DLR006"])
        assert "DLR006" not in codes(report)
        assert "DLR005" in codes(report)

    def test_select_accepts_prefix(self):
        report = run_fixture("rpc_bad.py", select=["DLR"])
        assert "DLR005" in codes(report)
        assert "DLR006" in codes(report)


class TestCli:
    def test_json_output_and_exit_code(self, capsys):
        rc = cli_main(
            [fx("donation_bad.py"), "--json", "--project-root", REPO_ROOT]
        )
        assert rc == 1
        payload = json.loads(capsys.readouterr().out)
        assert payload["counts"]["DLR001"] >= 3
        assert all(f["code"] == "DLR001" for f in payload["findings"])

    def test_clean_file_exits_zero(self, capsys):
        rc = cli_main(
            [fx("donation_clean.py"), "--project-root", REPO_ROOT]
        )
        assert rc == 0

    def test_select_flag(self, capsys):
        rc = cli_main(
            [
                fx("rpc_bad.py"),
                "--select", "DLR006",
                "--json",
                "--project-root", REPO_ROOT,
            ]
        )
        assert rc == 1
        payload = json.loads(capsys.readouterr().out)
        assert set(payload["counts"]) == {"DLR006"}

    def test_missing_path_exits_two(self, capsys):
        assert cli_main(["/nonexistent/nowhere.py"]) == 2

    def test_list_checkers(self, capsys):
        assert cli_main(["--list-checkers"]) == 0
        out = capsys.readouterr().out
        for code in (
            "DLR001", "DLR002", "DLR003", "DLR004", "DLR005", "DLR007",
            "DLR008", "DLR010", "DLR011", "DLR012", "DLR014",
        ):
            assert code in out


class TestRealTree:
    def test_checked_in_tree_lints_clean(self, capsys):
        """Acceptance criterion: the repo's own package has zero
        unsuppressed findings."""
        rc = cli_main(
            [
                os.path.join(REPO_ROOT, "dlrover_tpu"),
                "--project-root", REPO_ROOT,
            ]
        )
        assert rc == 0, capsys.readouterr().out


class TestFixedRuntimeBehavior:
    """The remediation itself, not just the lint verdicts."""

    def test_speed_monitor_mutations_hold_the_lock(self):
        from dlrover_tpu.master.monitor.speed_monitor import SpeedMonitor

        mon = SpeedMonitor()
        real = mon._lock
        entries = []

        class RecordingLock:
            def __enter__(self):
                entries.append(True)
                real.acquire()
                return self

            def __exit__(self, *exc):
                real.release()
                return False

        mon._lock = RecordingLock()
        mon.collect_global_step(5, 1.0)
        mon.set_target_worker_num(2)
        mon.add_running_worker("worker", 0)
        mon.remove_running_worker("worker", 0)
        mon.reduce_target_worker_num(1)
        mon.reset_running_speed_monitor()
        assert len(entries) >= 6

    def test_stats_reporter_job_metrics_append_holds_the_lock(self):
        from dlrover_tpu.master.stats.reporter import LocalStatsReporter

        rep = LocalStatsReporter()
        real = rep._metrics_lock
        entries = []

        class RecordingLock:
            def __enter__(self):
                entries.append(True)
                real.acquire()
                return self

            def __exit__(self, *exc):
                real.release()
                return False

        rep._metrics_lock = RecordingLock()
        rep.report_job_metrics(object())
        assert entries
        assert len(rep.job_metrics) == 1

    def test_ray_watcher_stop_interrupts_watch(self):
        from dlrover_tpu.master.watcher.ray_watcher import ActorWatcher

        class FakeClient:
            def list_job_actors(self):
                return []

        watcher = ActorWatcher("job", FakeClient(), poll_interval=60.0)
        watcher.stop()
        # Pre-fix this spun forever in time.sleep(60); now the stop
        # event short-circuits both the loop test and the wait.
        assert list(watcher.watch()) == []


# ---------------------------------------------------------------------------
# Whole-program engine (PR 19): call graph + DLR015-018 + gate helpers.
# ---------------------------------------------------------------------------


def _graph_for(tmp_path, files):
    """Build a ProgramGraph over a throwaway package ``gpkg``."""
    from dlrover_tpu.analysis.core import (
        Project,
        SourceFile,
        collect_files,
    )
    from dlrover_tpu.analysis.graph import get_graph

    pkg = tmp_path / "gpkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    for name, src in files.items():
        (pkg / name).write_text(textwrap.dedent(src))
    sfs = [SourceFile(p) for p in collect_files([str(tmp_path)])]
    return get_graph(Project(sfs, str(tmp_path)))


class TestProgramGraph:
    def test_import_cycle_resolves_both_directions(self, tmp_path):
        graph = _graph_for(
            tmp_path,
            {
                "a.py": """
                    from gpkg import b

                    def ping():
                        return b.pong()
                """,
                "b.py": """
                    from gpkg import a

                    def pong():
                        return a.ping()
                """,
            },
        )
        assert [e.callee for e in graph.edges_from("gpkg.a.ping")] == [
            "gpkg.b.pong"
        ]
        assert [e.callee for e in graph.edges_from("gpkg.b.pong")] == [
            "gpkg.a.ping"
        ]

    def test_attribute_call_resolves_through_ctor_assignment(
        self, tmp_path
    ):
        graph = _graph_for(
            tmp_path,
            {
                "helpers.py": """
                    class Helper:
                        def do(self):
                            return 1
                """,
                "owner.py": """
                    from gpkg.helpers import Helper

                    class Owner:
                        def __init__(self):
                            self._helper = Helper()

                        def run(self):
                            return self._helper.do()
                """,
            },
        )
        callees = [
            e.callee for e in graph.edges_from("gpkg.owner.Owner.run")
        ]
        assert "gpkg.helpers.Helper.do" in callees
        ci = graph.classes["gpkg.owner.Owner"]
        assert ci.attr_types["_helper"] == "gpkg.helpers.Helper"

    def test_self_dispatch_follows_inheritance(self, tmp_path):
        graph = _graph_for(
            tmp_path,
            {
                "mod.py": """
                    class Base:
                        def shared(self):
                            return 1

                    class Child(Base):
                        def tick(self):
                            return self.shared()
                """,
            },
        )
        callees = [
            e.callee for e in graph.edges_from("gpkg.mod.Child.tick")
        ]
        assert callees == ["gpkg.mod.Base.shared"]

    def test_unresolvable_calls_yield_no_edges(self, tmp_path):
        """Under-approximation: an untyped parameter's method call must
        not invent an edge."""
        graph = _graph_for(
            tmp_path,
            {
                "mod.py": """
                    def drive(thing):
                        return thing.step()
                """,
            },
        )
        assert graph.edges_from("gpkg.mod.drive") == []


class TestDonationXModChecker:
    def test_bad_fixture_flagged_across_modules(self):
        report = run_fixture("taint_xmod_bad", select=["DLR015"])
        assert codes(report).count("DLR015") == 5
        chained = [
            f for f in report.findings if "taint crosses" in f.message
        ]
        assert chained, "expected at least one cross-module chain"
        sinks = [
            f for f in report.findings
            if "which hands it to jax.device_put" in f.message
        ]
        assert sinks, "expected a transitive device_put sink finding"

    def test_local_findings_stay_with_dlr001(self):
        report = run_fixture("taint_xmod_bad")
        got = codes(report)
        assert got.count("DLR015") == 5
        assert got.count("DLR001") == 3
        # No escape is double-reported under both codes.
        keyed = {(f.path, f.line, f.col) for f in report.findings}
        assert len(keyed) == len(report.findings)

    def test_clean_twin_passes_including_retraction(self):
        """The clean twin routes a view through a helper that
        materializes a copy.  DLR001's local wrapping heuristic alone
        would flag the call; the summary-aware pass proves the copy and
        retracts it, so the twin must be fully clean — under every
        checker, not just DLR015."""
        assert not run_fixture("taint_xmod_clean").findings


class TestHotPathChecker:
    def test_bad_fixture_flags_transitive_blocking(self):
        report = run_fixture("hot_path_bad", select=["DLR016"])
        assert codes(report).count("DLR016") == 4
        messages = " ".join(f.message for f in report.findings)
        assert "transitively reaches" in messages
        assert " via " in messages  # per-edge path is reported
        assert "time.sleep()" in messages
        assert "_lock.acquire()" in messages

    def test_clean_twin_passes(self):
        assert not run_fixture("hot_path_clean").findings


class TestLockOrderChecker:
    def test_bad_fixture_flags_cycle_and_slow_holds(self):
        report = run_fixture("lock_bad", select=["DLR017"])
        assert codes(report).count("DLR017") == 4
        messages = " ".join(f.message for f in report.findings)
        assert "lock-order cycle" in messages
        assert "held across threading.Thread" in messages
        assert "held across time.sleep()" in messages
        assert "non-reentrant lock" in messages  # self-loop

    def test_clean_twin_passes(self):
        """Consistent order, slow work outside the lock, an RLock for
        the reentrant path, and one ``# dlr: lock-held`` waiver."""
        assert not run_fixture("lock_clean").findings


class TestWireSchemaChecker:
    def test_bad_fixture_flags_drift(self):
        report = run_fixture("wire_bad", select=["DLR018"])
        assert codes(report).count("DLR018") == 4
        messages = " ".join(f.message for f in report.findings)
        assert "Ping" in messages  # removed message
        assert "shard_id" in messages  # removed (renamed) field
        assert "epoch" in messages  # new field without default
        assert report.extras["comm_schema"]["status"] == "drift"

    def test_clean_twin_is_additive(self):
        report = run_fixture("wire_clean")
        assert not report.findings
        verdict = report.extras["comm_schema"]
        assert verdict["status"] == "additive"
        assert verdict["added_messages"] == ["Pong"]
        assert verdict["added_fields"] == ["KvPut.ttl_s"]

    def test_real_comm_matches_snapshot(self):
        report = run_paths(
            [os.path.join(REPO_ROOT, "dlrover_tpu", "common", "comm.py")],
            select=["DLR018"],
            project_root=REPO_ROOT,
        )
        assert not report.findings
        assert report.extras["comm_schema"]["status"] == "ok"
        assert report.extras["comm_schema"]["messages"] > 50

    def test_renamed_field_in_real_schema_is_caught(self, tmp_path):
        """Acceptance criterion: copy the shipped comm.py, rename one
        @comm_message field, keep the shipped snapshot — DLR018 fails."""
        import shutil

        src = os.path.join(REPO_ROOT, "dlrover_tpu", "common", "comm.py")
        text = open(src).read()
        assert "node_id: int" in text
        mutated = text.replace("node_id: int", "node_ident: int", 1)
        (tmp_path / "comm.py").write_text(mutated)
        shutil.copy(
            os.path.join(
                REPO_ROOT, "tests", "analysis_fixtures",
                "comm_schema.json",
            ),
            tmp_path / "comm_schema.json",
        )
        report = run_paths(
            [str(tmp_path)], select=["DLR018"],
            project_root=str(tmp_path),
        )
        assert "DLR018" in codes(report)
        messages = " ".join(f.message for f in report.findings)
        assert "node_id" in messages
        assert report.extras["comm_schema"]["status"] == "drift"


class TestGateHelpers:
    def test_pragma_budget_growth_fails(self):
        from dlrover_tpu.analysis.gate import pragma_budget

        verdict = pragma_budget({"DLR001": 3}, {"DLR001": 1})
        assert not verdict["ok"]
        assert verdict["grew"] == ["DLR001: 1 -> 3"]

    def test_pragma_budget_shrink_and_missing_baseline_pass(self):
        from dlrover_tpu.analysis.gate import pragma_budget

        assert pragma_budget({"DLR001": 1}, {"DLR001": 5})["ok"]
        assert pragma_budget({"DLR001": 9}, None)["ok"]

    def test_analysis_summary_carries_schema_and_budget(self):
        from dlrover_tpu.analysis.gate import analysis_summary

        payload = {
            "findings": [],
            "suppressed": [
                {"code": "DLR001"}, {"code": "DLR001"},
                {"code": "DLR004"},
            ],
            "counts": {},
            "checked_files": 7,
            "extras": {"comm_schema": {"status": "ok", "messages": 9}},
        }
        summary = analysis_summary(
            payload, 0, budget={"DLR001": 2, "DLR004": 1}
        )
        assert summary["ok"]
        assert summary["suppressed_counts"] == {"DLR001": 2, "DLR004": 1}
        assert summary["pragma_budget"]["ok"]
        assert summary["comm_schema"]["status"] == "ok"
        grown = analysis_summary(payload, 0, budget={"DLR001": 1})
        assert not grown["ok"]
        assert not grown["pragma_budget"]["ok"]


class TestTree:
    def test_package_is_clean_within_pragma_budget(self, package_run):
        """The gate every PR meets: no finding over ``dlrover_tpu/``, no
        code suppressed more often than the committed budget allows
        (``pragma_budget.json``, re-baselined by hand in review), and a
        wire schema byte-compatible with its snapshot."""
        from dlrover_tpu.analysis.gate import analysis_summary

        with open(fx("pragma_budget.json")) as f:
            budget = json.load(f)
        report, _ = package_run
        payload = report.to_dict()
        summary = analysis_summary(payload, report.exit_code, budget=budget)
        assert summary["finding_count"] == 0, payload["findings"]
        assert summary["pragma_budget"]["grew"] == []
        assert summary["comm_schema"]["status"] == "ok"
        assert summary["ok"]


class TestCliWholeProgram:
    def test_sarif_output_is_valid(self, capsys):
        rc = cli_main(
            [
                fx("lock_bad"), "--sarif",
                "--project-root", REPO_ROOT,
            ]
        )
        assert rc == 1
        doc = json.loads(capsys.readouterr().out)
        assert doc["version"] == "2.1.0"
        run = doc["runs"][0]
        assert run["tool"]["driver"]["name"] == "dlrover-tpu-analysis"
        rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
        assert "DLR017" in rule_ids
        results = [
            r for r in run["results"] if r["ruleId"] == "DLR017"
        ]
        assert len(results) == 4
        loc = results[0]["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"].endswith("gateway.py")
        assert loc["region"]["startLine"] > 0

    def test_update_comm_schema_writes_snapshot(self, tmp_path, capsys):
        comm = tmp_path / "comm.py"
        comm.write_text(
            "def comm_message(cls):\n"
            "    return cls\n"
            "\n"
            "@comm_message\n"
            "class Hello:\n"
            "    node: int\n"
            "    rank: int = 0\n"
        )
        rc = cli_main(
            [
                str(tmp_path), "--update-comm-schema",
                "--project-root", str(tmp_path),
            ]
        )
        assert rc == 0
        snap_path = os.path.join(
            str(tmp_path), "tests", "analysis_fixtures",
            "comm_schema.json",
        )
        snap = json.load(open(snap_path))
        assert snap["messages"]["Hello"]["node"]["default"] is False
        assert snap["messages"]["Hello"]["rank"]["default"] is True
        # The freshly written snapshot makes the same tree lint clean.
        report = run_paths(
            [str(tmp_path)], select=["DLR018"],
            project_root=str(tmp_path),
        )
        assert not report.findings
        assert report.extras["comm_schema"]["status"] == "ok"

    def test_changed_only_with_no_changes_exits_zero(
        self, tmp_path, capsys
    ):
        import subprocess

        bad = open(fx("donation_bad.py")).read()
        (tmp_path / "mod.py").write_text(bad)
        env_flags = [
            "-c", "user.email=t@e.st", "-c", "user.name=t",
        ]
        subprocess.run(
            ["git", "init", "-q"], cwd=tmp_path, check=True
        )
        subprocess.run(
            ["git", *env_flags, "add", "."], cwd=tmp_path, check=True
        )
        subprocess.run(
            ["git", *env_flags, "commit", "-q", "-m", "seed"],
            cwd=tmp_path, check=True,
        )
        # Full run fails; --changed-only with a clean worktree passes.
        assert cli_main(
            [str(tmp_path), "--project-root", str(tmp_path)]
        ) == 1
        capsys.readouterr()
        rc = cli_main(
            [
                str(tmp_path), "--changed-only",
                "--project-root", str(tmp_path),
            ]
        )
        assert rc == 0
        assert "no python files changed" in capsys.readouterr().out.lower()

    def test_changed_only_scopes_to_dirty_files(self, tmp_path, capsys):
        import subprocess

        (tmp_path / "clean.py").write_text(
            open(fx("donation_bad.py")).read()
        )
        (tmp_path / "dirty.py").write_text("x = 1\n")
        subprocess.run(
            ["git", "init", "-q"], cwd=tmp_path, check=True
        )
        subprocess.run(
            ["git", "-c", "user.email=t@e.st", "-c", "user.name=t",
             "add", "."],
            cwd=tmp_path, check=True,
        )
        subprocess.run(
            ["git", "-c", "user.email=t@e.st", "-c", "user.name=t",
             "commit", "-q", "-m", "seed"],
            cwd=tmp_path, check=True,
        )
        (tmp_path / "dirty.py").write_text("y = 2\n")
        # clean.py's DLR001s are outside the changed set.
        rc = cli_main(
            [
                str(tmp_path), "--changed-only", "--json",
                "--project-root", str(tmp_path),
            ]
        )
        assert rc == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["findings"] == []

    def test_changed_only_outside_git_falls_back_to_full_run(
        self, tmp_path, capsys
    ):
        (tmp_path / "mod.py").write_text(
            open(fx("donation_bad.py")).read()
        )
        rc = cli_main(
            [
                str(tmp_path), "--changed-only",
                "--project-root", str(tmp_path),
            ]
        )
        assert rc == 1  # fell back to analyzing everything


class TestWholeProgramRealTree:
    def test_new_codes_lint_clean_on_shipped_package(self, package_run):
        report, _ = package_run
        whole_program = ("DLR015", "DLR016", "DLR017", "DLR018")
        assert not [
            (f.code, f.path, f.line) for f in report.findings
            if f.code in whole_program
        ]
        assert report.extras["comm_schema"]["status"] == "ok"

    def test_whole_repo_run_fits_time_budget(self, package_run):
        """Issue budget: the full engine (graph build + 18 checkers)
        over the repo in under 30s on one vCPU."""
        report, elapsed = package_run
        assert not report.findings
        assert elapsed < 30.0, f"analysis took {elapsed:.1f}s"

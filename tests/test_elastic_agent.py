"""Elastic agent + tpurun tests.

Reference test analogs: dlrover/python/tests/test_elastic_training_agent.py
— same strategy: a real local master + real agent, worker subprocesses are
tiny scripts, failures injected via env (SURVEY.md §4).
"""

import json
import os
import sys
import textwrap
import time

import pytest

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.agent.training_agent import (
    ElasticLaunchConfig,
    ElasticTrainingAgent,
    MasterRendezvousHandler,
    NodeCheckElasticAgent,
    RendezvousOutcome,
    WorkerState,
    launch_agent,
)
from dlrover_tpu.common.constants import NodeEnv, RendezvousName
from dlrover_tpu.launch import elastic_run
from dlrover_tpu.master.local_master import LocalJobMaster


@pytest.fixture()
def master():
    m = LocalJobMaster(port=0, node_num=1)
    m.run(blocking=False)
    yield m
    m.stop()


@pytest.fixture()
def client(master):
    c = MasterClient(master.addr, node_id=0, node_type="worker")
    assert c.ready(10)
    return c


def _write_script(tmp_path, body: str) -> str:
    path = tmp_path / "train_stub.py"
    path.write_text(textwrap.dedent(body))
    return str(path)


class TestRendezvousOutcome:
    def test_rank_offset(self):
        out = RendezvousOutcome(1, {0: 4, 1: 4, 2: 2}, node_rank=1)
        assert out.world_size == 10
        assert out.rank_offset == 4
        assert out.num_nodes == 3

    def test_handler_completes(self, master, client):
        client.report_rdzv_params(1, 1, 0.5, 1)
        handler = MasterRendezvousHandler(
            RendezvousName.TRAINING, 0, 2, client, join_timeout=10
        )
        out = handler.next_rendezvous()
        assert out.world == {0: 2}
        assert out.rank_offset == 0


class TestElasticTrainingAgent:
    def test_successful_run_env_contract(self, master, client, tmp_path):
        """Workers get the full JAX distributed triple and exit cleanly."""
        client.report_rdzv_params(1, 1, 0.5, 1)
        marker = tmp_path / "env"
        script = _write_script(
            tmp_path,
            f"""
            import json, os, sys
            rank = os.environ["DLROVER_PROCESS_ID"]
            out = {{k: v for k, v in os.environ.items()
                   if k.startswith("DLROVER_")}}
            with open({str(marker)!r} + rank + ".json", "w") as f:
                json.dump(out, f)
            sys.exit(0)
            """,
        )
        config = ElasticLaunchConfig(
            min_nodes=1, max_nodes=1, nproc_per_node=2,
            monitor_interval=0.2, rdzv_timeout=15, accelerator="cpu",
        )
        agent = ElasticTrainingAgent(
            config, [sys.executable, script], client
        )
        state = agent.run()
        assert state == WorkerState.SUCCEEDED
        envs = []
        for rank in range(2):
            with open(f"{marker}{rank}.json") as f:
                envs.append(json.load(f))
        assert envs[0][NodeEnv.NUM_PROCESSES] == "2"
        assert envs[0][NodeEnv.COORDINATOR_ADDR]
        assert envs[0][NodeEnv.COORDINATOR_ADDR] == envs[1][
            NodeEnv.COORDINATOR_ADDR
        ]
        assert {e[NodeEnv.PROCESS_ID] for e in envs} == {"0", "1"}
        assert envs[0][NodeEnv.LOCAL_NUM_PROCESSES] == "2"

    def test_restart_on_failure_then_succeed(self, master, client, tmp_path):
        """First incarnation fails; the agent reports, re-rendezvouses and
        the retry succeeds (reference _invoke_run FAILED branch)."""
        client.report_rdzv_params(1, 1, 0.5, 1)
        script = _write_script(
            tmp_path,
            """
            import os, sys
            if os.environ["DLROVER_RESTART_COUNT"] == "0":
                sys.exit(3)
            sys.exit(0)
            """,
        )
        config = ElasticLaunchConfig(
            min_nodes=1, max_nodes=1, nproc_per_node=1,
            monitor_interval=0.2, rdzv_timeout=15, max_restarts=2,
        )
        agent = ElasticTrainingAgent(
            config, [sys.executable, script], client
        )
        state = agent.run()
        assert state == WorkerState.SUCCEEDED
        assert agent._worker_group.restart_count == 1

    def test_retries_exhausted(self, master, client, tmp_path):
        client.report_rdzv_params(1, 1, 0.5, 1)
        script = _write_script(tmp_path, "import sys; sys.exit(1)\n")
        config = ElasticLaunchConfig(
            min_nodes=1, max_nodes=1, nproc_per_node=1,
            monitor_interval=0.2, rdzv_timeout=15, max_restarts=1,
        )
        agent = ElasticTrainingAgent(
            config, [sys.executable, script], client
        )
        assert agent.run() == WorkerState.FAILED

    def test_membership_change_restarts(self, master, client, tmp_path):
        """A waiting node triggers a restart into a new world."""
        client.report_rdzv_params(1, 2, 0.5, 1)
        script = _write_script(
            tmp_path,
            """
            import os, sys, time
            if os.environ["DLROVER_RESTART_COUNT"] == "0":
                time.sleep(30)  # killed by the membership restart
            sys.exit(0)
            """,
        )
        config = ElasticLaunchConfig(
            min_nodes=1, max_nodes=2, nproc_per_node=1,
            monitor_interval=0.2, rdzv_timeout=15,
        )
        agent = ElasticTrainingAgent(
            config, [sys.executable, script], client
        )
        import threading

        def late_joiner():
            time.sleep(1.0)
            # A second node joins the waiting set -> membership change.
            c2 = MasterClient(master.addr, node_id=1, node_type="worker")
            c2.join_rendezvous(1, 1, RendezvousName.TRAINING)

        t = threading.Thread(target=late_joiner, daemon=True)
        t.start()
        state = agent.run()
        assert state == WorkerState.SUCCEEDED
        assert agent._worker_group.restart_count >= 1


class TestHotStandby:
    def test_promotion_skips_cold_start(self, master, client, tmp_path):
        """A SIGKILLed worker is replaced by the parked warm standby:
        the replacement reports it came through standby_barrier (no cold
        start), carries the bumped restart count, and a fresh standby is
        spawned behind it."""
        import signal as _signal

        client.report_rdzv_params(1, 1, 0.5, 1)
        marker_dir = tmp_path / "markers"
        marker_dir.mkdir()
        script = _write_script(
            tmp_path,
            f"""
            import os, sys, time
            sys.path.insert(0, {os.getcwd()!r})
            from dlrover_tpu.agent.standby import (
                is_standby, standby_barrier,
            )
            was = is_standby()
            msg = standby_barrier()
            kind = "standby" if was else "fresh"
            restart = os.environ.get("DLROVER_RESTART_COUNT", "?")
            with open(
                os.path.join({str(marker_dir)!r},
                             f"{{kind}}_{{os.getpid()}}"), "w"
            ) as f:
                f.write(restart)
            if kind == "fresh" and restart == "0":
                time.sleep(60)  # incarnation 0 waits to be killed
            sys.exit(0)
            """,
        )
        config = ElasticLaunchConfig(
            min_nodes=1, max_nodes=1, nproc_per_node=1,
            monitor_interval=0.2, rdzv_timeout=15, max_restarts=2,
            hot_standby=True,
        )
        agent = ElasticTrainingAgent(
            config, [sys.executable, script], client
        )
        import threading

        def kill_active():
            deadline = time.time() + 20
            while time.time() < deadline:
                fresh = [
                    f for f in os.listdir(marker_dir)
                    if f.startswith("fresh_")
                ]
                # wait for the ACTIVE worker marker AND a parked standby
                if fresh and agent._standby is not None and \
                        agent._standby.ready():
                    pid = int(fresh[0].split("_")[1])
                    os.kill(pid, _signal.SIGKILL)
                    return
                time.sleep(0.1)

        t = threading.Thread(target=kill_active, daemon=True)
        t.start()
        state = agent.run()
        assert state == WorkerState.SUCCEEDED
        markers = sorted(os.listdir(marker_dir))
        promoted = [m for m in markers if m.startswith("standby_")]
        assert promoted, f"no standby promotion happened: {markers}"
        # the promoted worker saw the bumped restart count
        with open(marker_dir / promoted[0]) as f:
            assert f.read() == "1"
        assert agent._worker_group.restart_count == 1

    def test_standby_barrier_noop_for_normal_worker(self, monkeypatch):
        from dlrover_tpu.agent import standby

        monkeypatch.delenv(standby.FIFO_ENV, raising=False)
        assert standby.standby_barrier() is None
        assert not standby.is_standby()


class TestNodeCheck:
    def test_node_check_pass(self, master, client, tmp_path):
        client.report_rdzv_params(1, 1, 0.5, 1)
        config = ElasticLaunchConfig(
            min_nodes=1, max_nodes=1, nproc_per_node=1, rdzv_timeout=15,
        )
        checker = NodeCheckElasticAgent(
            config,
            client,
            check_entrypoint=[sys.executable, "-c", "pass"],
            check_timeout=20,
        )
        assert checker.run() is True

    def test_node_check_mock_error_excludes(self, master, client, tmp_path):
        client.report_rdzv_params(1, 1, 0.5, 1)
        config = ElasticLaunchConfig(
            min_nodes=1, max_nodes=1, nproc_per_node=1, rdzv_timeout=15,
        )
        checker = NodeCheckElasticAgent(
            config,
            client,
            check_entrypoint=[sys.executable, "-c", "raise SystemExit(1)"],
            check_timeout=20,
        )
        assert checker.run() is False

    def test_workload_mock_error_env(self, monkeypatch):
        from dlrover_tpu.trainer import node_check

        monkeypatch.setenv(NodeEnv.MOCK_ERR_RANK, "0")
        monkeypatch.setenv(NodeEnv.NODE_RANK, "0")
        with pytest.raises(RuntimeError):
            node_check.mock_error()
        monkeypatch.setenv(NodeEnv.NODE_RANK, "1")
        node_check.mock_error()  # other ranks unaffected


class TestTpurunCLI:
    def test_parse_nnodes(self):
        assert elastic_run._parse_nnodes("4") == (4, 4)
        assert elastic_run._parse_nnodes("2:8") == (2, 8)

    def test_end_to_end_local(self, tmp_path, monkeypatch):
        """tpurun forks a local master, runs a 2-proc script to success."""
        monkeypatch.delenv(NodeEnv.MASTER_ADDR, raising=False)
        MasterClient._reset_singleton()
        marker = tmp_path / "done"
        script = _write_script(
            tmp_path,
            f"""
            import os
            open({str(marker)!r} + os.environ["DLROVER_PROCESS_ID"],
                 "w").close()
            """,
        )
        rc = elastic_run.main(
            [
                "--nnodes", "1",
                "--nproc_per_node", "2",
                "--accelerator", "cpu",
                "--monitor-interval", "0.2",
                script,
            ]
        )
        assert rc == 0
        assert os.path.exists(f"{marker}0")
        assert os.path.exists(f"{marker}1")


class TestAutoTunning:
    def test_tuner_started_and_workers_get_config_path(
        self, master, client, tmp_path, monkeypatch
    ):
        """--auto_tunning analog (reference elastic_run.py): the agent
        runs the ParalConfigTuner and workers inherit the config-file
        path env so ElasticDataLoader can watch it."""
        from dlrover_tpu.common.constants import ConfigPath

        monkeypatch.delenv(ConfigPath.ENV_PARAL_CONFIG, raising=False)
        client.report_rdzv_params(1, 1, 0.5, 1)
        marker = tmp_path / "env"
        script = _write_script(
            tmp_path,
            f"""
            import json, os, sys
            with open({str(marker)!r} + ".json", "w") as f:
                json.dump(dict(os.environ), f)
            sys.exit(0)
            """,
        )
        config = ElasticLaunchConfig(
            min_nodes=1, max_nodes=1, nproc_per_node=1,
            monitor_interval=0.2, rdzv_timeout=15, auto_tunning=True,
        )
        agent = ElasticTrainingAgent(
            config, [sys.executable, script], client
        )
        state = agent.run()
        assert state == WorkerState.SUCCEEDED
        assert agent._paral_tuner is not None
        path = agent._paral_tuner.config_path
        assert config.run_id in path
        import json as _json

        with open(f"{marker}.json") as f:
            worker_env = _json.load(f)
        assert worker_env[ConfigPath.ENV_PARAL_CONFIG] == path

    def test_cli_flag_parses(self):
        from dlrover_tpu.launch.elastic_run import parse_args

        args = parse_args(["--auto-tunning", "train.py"])
        assert args.auto_tunning
        args = parse_args(["--auto-tuning", "train.py"])
        assert args.auto_tunning
        args = parse_args(["train.py"])
        assert not args.auto_tunning

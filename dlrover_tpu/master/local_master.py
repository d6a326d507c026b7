"""In-process/local job master.

Reference parity: ``dlrover/python/master/local_master.py:118``
(LocalJobMaster) — the piece that makes the whole control plane testable on
one machine and lets ``tpurun`` work without K8s: rank-0's launcher forks
(or embeds) this master, agents connect over localhost gRPC.
"""

import threading
import time
from typing import Optional

from dlrover_tpu.common.constants import DefaultValues
from dlrover_tpu.common.global_context import Context
from dlrover_tpu.common.log import logger
from dlrover_tpu.master.elastic_training.elastic_ps import ElasticPsService
from dlrover_tpu.master.elastic_training.kv_store import SyncService
from dlrover_tpu.master.diagnosis.diagnosis import (
    DiagnosisManager,
    Diagnostician,
    HangInferenceOperator,
)
from dlrover_tpu.master.elastic_training.rdzv_manager import (
    ElasticTrainingRendezvousManager,
    NetworkCheckRendezvousManager,
)
from dlrover_tpu.master.monitor.speed_monitor import SpeedMonitor
from dlrover_tpu.master.node.local_job_manager import LocalJobManager
from dlrover_tpu.master.servicer import MasterServicer
from dlrover_tpu.master.shard.task_manager import TaskManager
from dlrover_tpu.rpc.transport import MasterTransport
from dlrover_tpu.telemetry.httpd import TelemetryHTTPServer

_context = Context.singleton_instance()


class LocalJobMaster:
    def __init__(self, port: int = 0, node_num: int = 1):
        self.speed_monitor = SpeedMonitor()
        self.task_manager = TaskManager(speed_monitor=self.speed_monitor)
        self.job_manager = LocalJobManager(
            node_num=node_num, task_manager=self.task_manager
        )
        self.rdzv_managers = {
            m.name: m
            for m in (
                ElasticTrainingRendezvousManager(),
                NetworkCheckRendezvousManager(),
            )
        }
        self.sync_service = SyncService(
            get_alive_nodes=self.job_manager.get_alive_node_ids
        )
        self.elastic_ps_service = ElasticPsService()
        self.diagnosis_manager = DiagnosisManager(
            Diagnostician([HangInferenceOperator(self.speed_monitor)])
        )
        # Job-local telemetry warehouse: single-job runs build cross-job
        # history too (brain/warehouse.py; DLROVER_WAREHOUSE=0 disables,
        # DLROVER_WAREHOUSE_DB overrides the telemetry-dir default).
        self.warehouse = self._open_warehouse()
        if self.warehouse is not None:
            self.diagnosis_manager.attach_warehouse(self.warehouse)
        self.servicer = MasterServicer(
            task_manager=self.task_manager,
            job_manager=self.job_manager,
            speed_monitor=self.speed_monitor,
            rdzv_managers=self.rdzv_managers,
            sync_service=self.sync_service,
            elastic_ps_service=self.elastic_ps_service,
            diagnosis_manager=self.diagnosis_manager,
            warehouse=self.warehouse,
        )
        self.transport = MasterTransport(self.servicer, port=port)
        self.port = self.transport.port
        self.telemetry_http = TelemetryHTTPServer(
            goodput_source=self.servicer.goodput_accountant.summary,
            diagnosis_source=self.diagnosis_manager.verdict_history,
        )
        self._stop = threading.Event()
        self._run_thread: Optional[threading.Thread] = None

    @staticmethod
    def _open_warehouse():
        import os
        import platform

        from dlrover_tpu.brain import warehouse as _wh

        if not _wh.enabled():
            return None
        try:
            wh = _wh.TelemetryWarehouse(_wh.default_warehouse_path())
            job_uid = os.environ.get("DLROVER_JOB_UID", "") or "local"
            versions = {"python": platform.python_version()}
            try:
                # The metadata, not the module: the master shares a
                # process with an agent that must stay off JAX.
                from importlib import metadata

                versions["jax"] = metadata.version("jax")
            except metadata.PackageNotFoundError:
                pass  # a jax-less master is fine
            wh.register_run(
                job_uid,
                run=os.environ.get("DLROVER_JOB_UID", ""),
                attempt=int(
                    os.environ.get("DLROVER_RESTART_COUNT", "0") or 0
                ),
                versions=versions,
            )
            return wh
        except Exception:  # noqa: BLE001 — warehousing is advisory
            logger.warning("job-local warehouse unavailable", exc_info=True)
            return None

    @property
    def addr(self) -> str:
        return f"127.0.0.1:{self.port}"

    def prepare(self):
        self.task_manager.start()
        self.job_manager.start()
        self.transport.start()
        self.diagnosis_manager.start_observing()
        try:
            self.telemetry_http.start()
        except OSError:  # port taken — observability is best-effort
            logger.warning("telemetry HTTP endpoint failed to start",
                           exc_info=True)

    def run(self, blocking: bool = False):
        self.prepare()
        if blocking:
            self._run_loop()
        else:
            self._run_thread = threading.Thread(
                target=self._run_loop, name="local-master-loop", daemon=True
            )
            self._run_thread.start()

    def _run_loop(self):
        """Light master tick: finish when training data exhausted.

        Also ticks the hyperparam auto-tune (distributed mode does this
        from JobAutoScaler) so tpurun's embedded master grows the batch
        into reported HBM headroom the same way a cluster master does."""
        while not self._stop.wait(_context.tick_interval):
            try:
                self.job_manager.tune_parallel_config()
            except Exception:  # noqa: BLE001 — tuning must not kill master
                logger.warning("auto-tune tick failed", exc_info=True)
            if self.task_manager.finished():
                logger.info("All training tasks finished; master exiting")
                break

    def stop(self):
        self._stop.set()
        self.diagnosis_manager.stop_observing()
        self.task_manager.stop()
        self.job_manager.stop()
        self.transport.stop(grace=1)
        self.telemetry_http.stop()
        if self.warehouse is not None:
            # Final goodput interval, then release the sqlite handle.
            self.servicer.flush_warehouse()
            self.warehouse.close()


def start_local_master(port: int = 0, node_num: int = 1) -> LocalJobMaster:
    master = LocalJobMaster(port=port, node_num=node_num)
    master.run(blocking=False)
    return master

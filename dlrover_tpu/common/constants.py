"""Framework-wide constants and environment-variable contracts.

Reference parity: ``dlrover/python/common/constants.py`` (NodeType,
NodeStatus, NodeEventType, NodeEnv, ...).  Re-designed for TPU jobs: the
accelerator classification is TPU-first and the per-node env contract carries the
JAX distributed-initialization triple (coordinator, num_processes,
process_id) instead of torch-elastic's MASTER_ADDR/RANK pair.
"""


class PlatformType:
    KUBERNETES = "k8s"
    LOCAL = "local"
    GKE_TPU = "gke_tpu"
    RAY = "ray"


class Accelerators:
    TPU = "tpu"
    CPU = "cpu"  # tests / virtual meshes
    GPU = "gpu"  # compat shim only


class DistributionStrategy:
    """How workers coordinate — drives which node managers the master runs."""

    LOCAL = "Local"
    PS = "ParameterServerStrategy"
    ALLREDUCE = "AllreduceStrategy"
    CUSTOM = "CustomStrategy"


class OptimizeMode:
    MANUAL = "manual"
    SINGLE_JOB = "single-job"
    CLUSTER = "cluster"  # brain-backed


class NodeType:
    MASTER = "master"
    WORKER = "worker"
    # Parameter-server style roles kept for the sparse/recsys path.
    PS = "ps"
    CHIEF = "chief"
    EVALUATOR = "evaluator"
    ALL = [MASTER, WORKER, PS, CHIEF, EVALUATOR]


class NodeStatus:
    INITIAL = "initial"
    PENDING = "pending"
    RUNNING = "running"
    FINISHED = "finished"
    FAILED = "failed"
    DELETED = "deleted"
    SUCCEEDED = "succeeded"
    BREAKED = "breaked"  # node exited abnormally without pod failure
    UNKNOWN = "unknown"

    END_STATUS = [FINISHED, FAILED, DELETED, SUCCEEDED]


class NodeEventType:
    ADDED = "added"
    MODIFIED = "modified"
    DELETED = "deleted"
    ERROR = "error"


class NodeExitReason:
    """Why a node terminated — drives the relaunch decision.

    Reference: exit-code classification in
    ``dlrover/python/elastic_agent/torch/training.py:357-361`` and pod-event
    conversion in ``master/watcher/k8s_watcher.py:64-110``.
    """

    KILLED = "killed"
    OOM = "oom"
    FATAL_ERROR = "fatal_error"
    HARDWARE_ERROR = "hardware_error"  # always relaunch on a fresh node
    PREEMPTED = "preempted"
    UNKNOWN_ERROR = "unknown_error"
    SUCCEEDED = "succeeded"

    RELAUNCHABLE = [KILLED, OOM, HARDWARE_ERROR, PREEMPTED]


class JobExitReason:
    SUCCEEDED = "succeeded"
    CODE_ERROR = "code_error"
    OOM = "oom"
    HANG = "hang"
    UNKNOWN = "unknown"


class NodeEnv:
    """Environment-variable contract between agent and workers."""

    JOB_NAME = "DLROVER_JOB_NAME"
    JOB_UID = "DLROVER_JOB_UID"
    NODE_ID = "DLROVER_NODE_ID"
    NODE_RANK = "DLROVER_NODE_RANK"
    NODE_NUM = "DLROVER_NODE_NUM"
    NODE_TYPE = "DLROVER_NODE_TYPE"
    MASTER_ADDR = "DLROVER_MASTER_ADDR"
    # JAX distributed triple handed to every worker process.
    COORDINATOR_ADDR = "DLROVER_COORDINATOR_ADDR"
    PROCESS_ID = "DLROVER_PROCESS_ID"
    NUM_PROCESSES = "DLROVER_NUM_PROCESSES"
    LOCAL_PROCESS_ID = "DLROVER_LOCAL_PROCESS_ID"
    LOCAL_NUM_PROCESSES = "DLROVER_LOCAL_NUM_PROCESSES"
    # Restart bookkeeping.
    RESTART_COUNT = "DLROVER_RESTART_COUNT"
    RELAUNCHED = "DLROVER_RELAUNCHED_POD"
    # Fault-injection hook used by tests / node-check (reference:
    # MOCK_ERR_RANK in trainer/torch/node_check/utils.py:50).
    MOCK_ERR_RANK = "DLROVER_MOCK_ERR_RANK"
    # Deterministic chaos injection (common/faults.py): a spec string
    # arming fault_point() hooks, plus the replay seed for ~prob specs.
    FAULTS = "DLROVER_FAULTS"
    FAULTS_SEED = "DLROVER_FAULTS_SEED"
    # Published node IP (scheduler/operator-provided): preferred over the
    # UDP-connect autodetection, which breaks on air-gapped CI hosts.
    NODE_IP = "DLROVER_NODE_IP"
    # Auto-config knobs.
    AUTO_CONFIG = "DLROVER_AUTO_CONFIG"
    GRPC_MAX_MESSAGE = "DLROVER_GRPC_MAX_MESSAGE"
    # Telemetry channel (telemetry/events.py, telemetry/httpd.py own the
    # defaults; names mirrored here for the env contract in one place).
    TELEMETRY_DIR = "DLROVER_TELEMETRY_DIR"
    TELEMETRY = "DLROVER_TELEMETRY"
    TELEMETRY_HTTP_PORT = "DLROVER_TELEMETRY_HTTP_PORT"
    TELEMETRY_HTTP_ADDR = "DLROVER_TELEMETRY_HTTP_ADDR"


class TrainingExceptionLevel:
    ERROR = "error"
    WARNING = "warning"
    INFO = "info"
    RDZV_ERROR = "rdzv_error"
    PROCESS_ERROR = "process_error"
    NODE_ERROR = "node_error"


class RendezvousName:
    TRAINING = "elastic-training"
    NETWORK_CHECK = "network-check"


class NetworkFailureReason:
    NODE_FAILURE = "node_failure"
    WAITING_NODE = "waiting_node"
    NO_INIT = "not_initialized"


class GRPC:
    MAX_SEND_MESSAGE_LENGTH = 1 << 28  # 256 MB
    MAX_RECEIVE_MESSAGE_LENGTH = 1 << 28


class DefaultValues:
    SERVICE_PORT = 0  # pick a free port
    MASTER_TICK_INTERVAL = 30  # seconds, master run-loop period
    HEARTBEAT_TIMEOUT = 300  # dead-node detection window
    RDZV_TIMEOUT = 600
    RELAUNCH_MAX_NUM = 3
    SEC_TO_WAIT_FAILED_PS = 600
    HANG_CHECK_INTERVAL = 180
    HANG_DOWNTIME = 30 * 60
    SPEED_RECORD_NUM = 50
    AUTO_SCALE_INTERVAL = 1800
    SHARD_TIMEOUT = 300  # reassign a DOING shard after this many seconds
    CKPT_COMMIT_TIMEOUT = 600
    # Hang-watchdog escalation ladder (agent/watchdog.py): no step
    # progress for warn → dump → restart-world seconds.
    HANG_WARN_AFTER = 120.0
    HANG_DUMP_AFTER = 300.0
    HANG_RESTART_AFTER = 600.0


class ConfigPath:
    """Where the agent drops tuned runtime configs for the trainer to watch.

    Reference: ``elastic_agent/config/paral_config_tuner.py:30`` writes a
    JSON `ParallelConfig`; the trainer's dataloader re-reads it.
    """

    ENV_PARAL_CONFIG = "DLROVER_PARAL_CONFIG_PATH"
    PARAL_CONFIG = "/tmp/dlrover_tpu/paral_config.json"
    ENV_RUNTIME_METRICS = "DLROVER_RUNTIME_METRICS_PATH"
    RUNTIME_METRICS = "/tmp/dlrover_tpu/runtime_metrics.json"


class CheckpointConstant:
    TRACKER_FILE = "latest_checkpointed_iteration.txt"
    STEP_DONE_DIR = "._dlrover_ckpt_stage"
    MODEL_STATES_NAME = "model_states"
    OPTIM_STATES_NAME = "optim_states"
    SAVE_EVENT = "save"
    UPDATE_SHARD_EVENT = "update_shard"
    EXIT_EVENT = "exit"


class JobConstant:
    RDZV_JOIN_TIMEOUT_DEFAULT = 600
    INSUFFICIENT_NODES_TIMEOUT = 3600
    NODE_CHECK_TIMEOUT = 300
    TRAINING_AGENT_LOOP_INTERVAL = 15
    MASTER_CLIENT_GRPC_TIMEOUT = 10
    MASTER_CLIENT_MAX_RETRY = 3
    # Cap on TOTAL retry wall-time (sleeps only): a worker must fail its
    # RPC within this budget rather than retry into a master that is
    # being replaced (the caller's own timeout handling takes over).
    MASTER_CLIENT_RETRY_WALL_TIME = 30.0

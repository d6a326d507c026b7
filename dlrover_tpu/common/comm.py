"""RPC message layer: typed dataclass messages over msgpack.

Reference parity: ``dlrover/python/common/grpc.py:129-466`` — there, ~40
dataclasses are pickled into a single ``Message.data`` bytes field.  We keep
the same two-RPC design (``report``/``get`` multiplexing typed messages) but
serialize with msgpack + a class registry instead of pickle, so the control
plane never executes arbitrary bytecode from the wire.

Every message type is a dataclass registered via ``@comm_message``.  Encoding
embeds ``_cls``; decoding looks the class up and reconstructs it (recursively
for nested registered dataclasses).
"""

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import msgpack

_MESSAGE_REGISTRY: Dict[str, type] = {}


def comm_message(cls):
    """Register a dataclass as a wire message."""
    cls = dataclass(cls)
    _MESSAGE_REGISTRY[cls.__name__] = cls
    return cls


def _encode(obj):
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        d = {"_cls": type(obj).__name__}
        for f in dataclasses.fields(obj):
            d[f.name] = _encode(getattr(obj, f.name))
        return d
    if isinstance(obj, dict):
        return {k: _encode(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_encode(v) for v in obj]
    return obj


def _decode(obj):
    if isinstance(obj, dict):
        if "_cls" in obj:
            cls = _MESSAGE_REGISTRY.get(obj["_cls"])
            if cls is None:
                raise ValueError(f"unknown message class {obj['_cls']}")
            kwargs = {
                k: _decode(v) for k, v in obj.items() if k != "_cls"
            }
            field_names = {f.name for f in dataclasses.fields(cls)}
            kwargs = {k: v for k, v in kwargs.items() if k in field_names}
            return cls(**kwargs)
        return {k: _decode(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_decode(v) for v in obj]
    return obj


def serialize_message(msg) -> bytes:
    return msgpack.packb(_encode(msg), use_bin_type=True)


def deserialize_message(data: bytes):
    if not data:
        return None
    return _decode(msgpack.unpackb(data, raw=False, strict_map_key=False))


# ---------------------------------------------------------------------------
# Generic envelope carried by the 2-RPC pipe.
# ---------------------------------------------------------------------------


@comm_message
class BaseRequest:
    node_id: int = -1
    node_type: str = ""
    data: bytes = b""
    # Shared-secret job token (transport-level auth): checked by the
    # server when it was started with one; see docs/SECURITY.md.
    token: str = ""


@comm_message
class BaseResponse:
    success: bool = False
    reason: str = ""
    data: bytes = b""


# ---------------------------------------------------------------------------
# Data-shard messages (reference: TaskRequest/Task/ShardCheckpoint ...).
# ---------------------------------------------------------------------------


@comm_message
class Shard:
    name: str = ""  # dataset name
    start: int = 0
    end: int = 0
    record_indices: Optional[List[int]] = None


@comm_message
class Task:
    task_id: int = -1
    task_type: str = ""  # "training" | "evaluation" | "wait" | ""
    shard: Shard = field(default_factory=Shard)

    @property
    def exists(self) -> bool:
        return self.task_id >= 0


@comm_message
class TaskRequest:
    dataset_name: str = ""


@comm_message
class TaskResult:
    dataset_name: str = ""
    task_id: int = -1
    success: bool = True
    err_message: str = ""


@comm_message
class DatasetShardParams:
    batch_size: int = 0
    num_epochs: int = 1
    dataset_size: int = 0
    shuffle: bool = False
    num_minibatches_per_shard: int = 2
    dataset_name: str = ""
    task_type: str = "training"
    storage_type: str = "table"


@comm_message
class ShardCheckpointRequest:
    dataset_name: str = ""


@comm_message
class ShardCheckpoint:
    dataset_name: str = ""
    content: str = ""  # JSON blob of splitter + queue state


@comm_message
class DatasetEpochRequest:
    dataset_name: str = ""


@comm_message
class DatasetEpoch:
    epoch: int = 0


# ---------------------------------------------------------------------------
# Rendezvous messages.
# ---------------------------------------------------------------------------


@comm_message
class RendezvousParams:
    min_nodes: int = 1
    max_nodes: int = 1
    waiting_timeout: float = 600
    node_unit: int = 1
    join_timeout: float = 600


@comm_message
class JoinRendezvousRequest:
    node_id: int = 0
    node_rank: int = 0
    local_world_size: int = 1
    rdzv_name: str = ""
    node_ip: str = ""


@comm_message
class RendezvousState:
    round: int = 0
    completed: bool = False
    # world: {node_rank: local_world_size}
    world: Dict[int, int] = field(default_factory=dict)


@comm_message
class CommWorldRequest:
    node_id: int = 0
    rdzv_name: str = ""


@comm_message
class WaitingNodeNumRequest:
    node_id: int = 0
    local_world_size: int = 1
    rdzv_name: str = ""


@comm_message
class WaitingNodeNum:
    waiting_num: int = 0


@comm_message
class NetworkReadyRequest:
    pass


@comm_message
class NetworkCheckResult:
    node_id: int = 0
    normal: bool = True
    elapsed_time: float = 0.0


@comm_message
class StragglerExistRequest:
    pass


@comm_message
class NetworkStatus:
    nodes: List[int] = field(default_factory=list)
    reason: str = ""


@comm_message
class JoinRendezvousResponse:
    round: int = 0


@comm_message
class CoordinatorReport:
    """A node (re-)elected the jax.distributed coordinator endpoint."""

    node_id: int = 0
    rdzv_name: str = ""
    rdzv_round: int = 0
    addr: str = ""
    epoch: int = 0


@comm_message
class CoordinatorStateRequest:
    rdzv_name: str = ""


@comm_message
class CoordinatorState:
    """Master-side view of coordinator churn for operators/diagnosis."""

    addr: str = ""
    epoch: int = 0
    node_rank: int = -1
    rdzv_round: int = -1
    reelections: int = 0


# ---------------------------------------------------------------------------
# Node / failure / heartbeat messages.
# ---------------------------------------------------------------------------


@comm_message
class NodeMeta:
    node_type: str = ""
    node_id: int = 0
    rank: int = 0
    addr: str = ""
    memory: float = 0.0
    cpu_percent: float = 0.0
    tpu_stats: Dict[str, float] = field(default_factory=dict)


@comm_message
class NodeAddress:
    node_type: str = ""
    node_id: int = 0
    addr: str = ""


@comm_message
class NodeFailure:
    node_type: str = ""
    node_id: int = 0
    restart_count: int = 0
    error_data: str = ""
    level: str = ""


@comm_message
class NodePreemption:
    """The node's SIGTERM grace handler fired: deregister it and mark
    the rendezvous round so the next reform skips the dying host."""

    node_type: str = ""
    node_id: int = 0
    node_rank: int = -1
    reason: str = "preempted"


@comm_message
class HeartBeat:
    node_id: int = 0
    timestamp: float = 0.0


@comm_message
class HeartbeatResponse:
    action: str = ""  # "" | "restart" | "stop"


@comm_message
class NodeEventMessage:
    event_type: str = ""
    node_type: str = ""
    node_id: int = 0
    reason: str = ""


# ---------------------------------------------------------------------------
# Metrics / stats messages.
# ---------------------------------------------------------------------------


@comm_message
class GlobalStep:
    timestamp: float = 0.0
    step: int = 0
    worker_num: int = 0


@comm_message
class ResourceStats:
    memory: float = 0.0
    cpu_percent: float = 0.0
    tpu_stats: Dict[str, float] = field(default_factory=dict)


@comm_message
class ModelInfo:
    num_params: int = 0
    flops_per_step: float = 0.0
    batch_size: int = 0
    seq_len: int = 0


@comm_message
class TrainingHyperParamsReport:
    """Trainer -> master: base optimizer hyperparams + model card.

    Seeds the master's auto-tune loop (hyperparam strategy generator) with
    the trainer's REAL base LR/WD — so the sqrt(batch-ratio) rescale has a
    nonzero base — and the real model dimensions, so activation-memory
    sizing does not fall back to the mock default card.  Reference analog:
    the torch trainer reporting its config via ``report_model_info``.
    (Named ...Report to avoid colliding with the metrics dataclass
    ``stats.training_metrics.TrainingHyperParams`` in the wire registry,
    which resolves classes by bare name.)
    """

    learning_rate: float = 0.0
    weight_decay: float = 0.0
    # {block_size, n_layer, n_heads, n_embd} — any subset; missing keys
    # keep their current (default-card) values.
    model_config: Dict[str, int] = field(default_factory=dict)


@comm_message
class TrainingHangRequest:
    pass


@comm_message
class TrainingStatus:
    is_hanged: bool = False


# ---------------------------------------------------------------------------
# KV-store messages (rendezvous store substrate).
# ---------------------------------------------------------------------------


@comm_message
class KeyValuePair:
    key: str = ""
    value: bytes = b""


@comm_message
class KeyValueRequest:
    key: str = ""


# ---------------------------------------------------------------------------
# Elastic-run / config messages.
# ---------------------------------------------------------------------------


@comm_message
class ParallelConfig:
    dataloader_num_workers: int = 2
    dataloader_batch_size: int = 0
    # Batch size this config was derived from (informational / for
    # logging; reference: DataLoaderConfig.last_batch_size).  Do NOT
    # rescale LR from it — learning_rate below already carries the
    # master's sqrt(batch ratio) rescale; apply it as-is.
    dataloader_last_batch_size: int = 0
    gradient_accumulation: int = 1
    # Optimizer auto-tune (reference: OptimizerConfig), pre-scaled by the
    # master — consume verbatim; 0.0 = untouched.
    learning_rate: float = 0.0
    weight_decay: float = 0.0
    version: int = 0


@comm_message
class ParallelConfigRequest:
    pass


@comm_message
class CheckpointReady:
    step: int = 0
    num_shards: int = 0


@comm_message
class RestorableStepsReport:
    """Rank -> master: the checkpoint steps this node verified it can
    restore from (recovery consensus, docs/CHECKPOINT.md).  ``round_id``
    partitions consensus epochs so reports from an earlier restart never
    bleed into the next one's decision."""

    node_rank: int = 0
    round_id: int = 0
    steps: List[int] = field(default_factory=list)


@comm_message
class RestoreDecisionRequest:
    """Rank -> master poll: has every rank reported for ``round_id``?"""

    round_id: int = 0
    world_size: int = 0


@comm_message
class RestoreDecision:
    """Master -> rank: the highest step verifiable on EVERY reporting
    rank (-1 = no common step; cold start).  ``ready`` is False until
    ``world_size`` distinct ranks reported."""

    ready: bool = False
    step: int = -1
    reported: int = 0


@comm_message
class PsClusterVersionRequest:
    """Worker asks for the global PS cluster version (TF-PS elasticity)."""

    pass


@comm_message
class PsClusterVersion:
    version: int = 0


@comm_message
class PsNodeVersion:
    """Worker reports the PS cluster version it is now running on."""

    node_id: int = 0
    version: int = 0


@comm_message
class PsClusterSpecRequest:
    pass


@comm_message
class PsClusterSpec:
    ps_addrs: List[str] = field(default_factory=list)


@comm_message
class Empty:
    pass


@comm_message
class SyncJoin:
    sync_name: str = ""
    node_id: int = 0
    node_type: str = ""


@comm_message
class SyncFinishRequest:
    sync_name: str = ""


@comm_message
class SyncResult:
    success: bool = False


@comm_message
class ScaleResult:
    success: bool = False


# ---------------------------------------------------------------------------
# Brain service messages (reference: dlrover/proto/brain.proto).
# ---------------------------------------------------------------------------


@comm_message
class BrainJobMeta:
    job_uuid: str = ""
    name: str = ""
    resources: Dict[str, Any] = field(default_factory=dict)
    # merge ``resources`` into the stored dict instead of replacing it
    # (used for late hyperparam reports without clobbering sizing info)
    merge_resources: bool = False


@comm_message
class BrainJobFinish:
    job_uuid: str = ""
    status: str = "completed"


@comm_message
class BrainRuntimeRecord:
    job_uuid: str = ""
    timestamp: float = 0.0
    speed: float = 0.0
    step: int = 0
    worker_num: int = 0
    node_cpu: Dict[str, float] = field(default_factory=dict)
    node_memory: Dict[str, float] = field(default_factory=dict)
    node_tpu: Dict[str, Any] = field(default_factory=dict)


@comm_message
class BrainOptimizeRequest:
    job_uuid: str = ""
    stage: str = ""
    config: Dict[str, Any] = field(default_factory=dict)
    # PS node name -> allocated CPU cores (utilization denominator).
    ps_alloc_cpu: Dict[str, float] = field(default_factory=dict)
    # OOM-recovery path: node names that died of OOM.
    oom_nodes: List[str] = field(default_factory=list)


@comm_message
class BrainPlanMsg:
    # role -> {"count": n, "cpu": c, "memory": mb}
    group_resources: Dict[str, Any] = field(default_factory=dict)
    # node name -> {"cpu": c, "memory": mb}
    node_resources: Dict[str, Any] = field(default_factory=dict)


@comm_message
class BrainOptimizeResponse:
    plans: List[Any] = field(default_factory=list)


@comm_message
class BrainHyperParamsRequest:
    """Master -> Brain: recommend initial hyperparams by mining similar
    completed jobs' recorded configs + throughputs."""

    job_uuid: str = ""
    name: str = ""


@comm_message
class BrainHyperParamsResponse:
    found: bool = False
    batch_size: int = 0
    learning_rate: float = 0.0
    weight_decay: float = 0.0
    # median speed of the job the recommendation came from
    speed: float = 0.0
    source_job: str = ""


# ---------------------------------------------------------------------------
# Telemetry: event-stream shipping + online goodput (docs/OBSERVABILITY.md).
# ---------------------------------------------------------------------------


@comm_message
class TelemetryEvents:
    """Agent -> master: a batch of telemetry event records (plain dicts,
    schema in telemetry/events.py) tailed from the node's per-rank JSONL
    logs.  Folded into the master's online goodput accountant."""

    events: List[Dict[str, Any]] = field(default_factory=list)


@comm_message
class GoodputRequest:
    # include per-rank phase segments in the reply
    detail: bool = False


@comm_message
class GoodputSummary:
    """The accountant's live summary (same payload /goodput.json serves)."""

    data: Dict[str, Any] = field(default_factory=dict)


@comm_message
class BrainRunMeta:
    """Master -> Brain: register a run in the telemetry warehouse
    (job uuid, run/attempt, config fingerprint, software versions)."""

    job_uuid: str = ""
    run: str = ""
    attempt: int = 0
    config: Dict[str, Any] = field(default_factory=dict)
    versions: Dict[str, Any] = field(default_factory=dict)
    fingerprint: str = ""


@comm_message
class BrainWarehouseBatch:
    """Master -> Brain: a batch of durable telemetry warehouse records
    (dicts with kind/t/run/attempt/rank/trigger/value/payload, schema in
    brain/warehouse.py)."""

    job_uuid: str = ""
    records: List[Dict[str, Any]] = field(default_factory=list)


# ---------------------------------------------------------------------------
# Sharded KvVariable service messages (kv_service/, docs/KV_SERVICE.md).
# Bulk payloads ride as raw little-endian bytes (int64 keys, f32 rows) so
# msgpack never walks per-element — one gather batch is two bytes blobs.
# ---------------------------------------------------------------------------


@comm_message
class KvGatherRequest:
    """Client -> shard: gather one owner's slice of a batch.

    ``init`` selects gather-or-init (training reads: missing keys are
    initialized and inserted) vs gather-or-zeros (serving lookups:
    read-only, missing keys come back zero + found=0).
    """

    table: str = ""
    keys: bytes = b""  # int64 little-endian
    init: bool = True
    # Trace-context propagation (telemetry/tracing.py): empty when the
    # gather is unsampled.  Old peers drop the field in _decode.
    trace: str = ""
    # Lease fencing (kv_service/replication.py): init-gathers create
    # rows, so they are mutations and carry the writer's epoch.  0 means
    # the shard is unreplicated (legacy mode, never fenced).
    epoch: int = 0


@comm_message
class KvRows:
    """Shard -> client: dense rows for the requested keys, in request
    order.  ``found`` is one byte per key (only meaningful for
    read-only lookups; gather-or-init always finds)."""

    values: bytes = b""  # float32 little-endian, len(keys) * dim
    found: bytes = b""  # uint8, one per key
    dim: int = 0
    version: int = 0
    # Replication state piggybacked on every response so the client's
    # staleness view refreshes for free: ``applied`` is the serving
    # table's replication mark (followers: primary version applied
    # through; primaries: own version).  ``refused`` flags a fenced
    # init-gather (stale epoch / deposed primary) — rows are empty.
    applied: int = 0
    refused: bool = False


@comm_message
class KvApplyRequest:
    """Client -> shard: sparse update for one owner's slice.

    ``optimizer`` names a KvVariable apply method suffix ("adam",
    "adagrad", …) or "insert" / "scatter_add" for raw writes.  Scalar
    hyperparameters ride in ``hparams``; array args never do.
    """

    table: str = ""
    keys: bytes = b""  # int64 little-endian
    values: bytes = b""  # float32 little-endian, len(keys) * dim
    optimizer: str = "insert"
    hparams: Dict[str, float] = field(default_factory=dict)
    step: int = 0
    trace: str = ""  # tracing.TraceContext wire form ("" = unsampled)
    # The writer's lease epoch (0 = unreplicated legacy mode).  A shard
    # holding a newer lease refuses the mutation — the split-brain
    # guard: a deposed primary's late writes never land.
    epoch: int = 0


@comm_message
class KvApplyResult:
    applied: int = 0
    version: int = 0
    durable: bool = False
    # Fencing refusal: nothing was applied; ``epoch`` is the shard's
    # current lease so the caller can learn how stale it is.
    refused: bool = False
    epoch: int = 0


@comm_message
class KvShardStatsRequest:  # dlr: no-trace — stats poll, not a request path
    reset_busy: bool = False


@comm_message
class KvShardStats:
    """Shard -> caller: capacity + durability counters for the bench
    harness, the reshard planner, and /kvz."""

    name: str = ""
    table: str = ""
    rows: int = 0
    dim: int = 0
    slots: int = 0
    version: int = 0
    busy_s: Dict[str, float] = field(default_factory=dict)
    served_rows: Dict[str, int] = field(default_factory=dict)
    rpcs: Dict[str, int] = field(default_factory=dict)
    recovery_s: float = -1.0
    restored_rows: int = 0
    chain_length: int = 0
    # Replication / lease state (kv_service/replication.py).
    role: str = "primary"  # "primary" | "follower" | "deposed"
    epoch: int = 0
    applied: int = 0  # followers: primary version applied through
    repl_lag_s: float = -1.0  # max follower ack age (primaries only)
    # Hot-key top-K accounting: [[key, count], ...] hottest first —
    # the warehouse's shard-skew signal (Brain shard splitting).
    hot_keys: List[List[int]] = field(default_factory=list)


@comm_message
class KvSaveRequest:  # dlr: no-trace — control plane, not a request path
    """Force a checkpoint link now (full or delta per the manager's
    cadence); used by reshard before planned membership changes."""

    step: int = 0
    epoch: int = 0  # writer's lease epoch (0 = unreplicated)


@comm_message
class KvSaveResult:
    kind: str = ""  # "full" | "delta" | "none"
    step: int = 0


@comm_message
class KvImportRequest:  # dlr: no-trace — control plane, not a request path
    """Reshard -> shard: bulk-import migrated rows (row = (1+slots)*dim
    floats, same layout as KvVariable.export_rows)."""

    table: str = ""
    keys: bytes = b""  # int64 little-endian
    rows: bytes = b""  # float32 little-endian, len(keys)*(1+slots)*dim
    freqs: bytes = b""  # int64 little-endian, optional (empty = skip)
    epoch: int = 0  # writer's lease epoch (0 = unreplicated)


@comm_message
class KvExportRequest:  # dlr: no-trace — control plane, not a request path
    """Reshard -> shard: export rows owned by *other* names under the
    new ring (scale event migration).  ``names`` is the new membership;
    ``self_name`` is the exporting shard's own name."""

    table: str = ""
    names: List[str] = field(default_factory=list)
    self_name: str = ""


@comm_message
class KvExportResult:
    keys: bytes = b""
    rows: bytes = b""
    freqs: bytes = b""
    owners: List[str] = field(default_factory=list)
    counts: List[int] = field(default_factory=list)


# -- replication + lease fencing (kv_service/replication.py) ---------------


@comm_message
class KvReplPushRequest:
    """Primary -> follower: one link of the chain-delta replication
    stream.  ``kind="base"`` is the bootstrap full export (``prev_seq``
    ignored); ``kind="delta"`` carries ``delta_export_rows`` output and
    requires the follower to be exactly at ``prev_seq``.  Sequence
    numbers are the primary table's version marks — the same marks the
    on-disk delta chain uses, so the replication stream and the
    durability chain describe the same history.  ``trace`` carries the
    originating mutation's trace context so update-to-serve freshness
    exemplars link back to one request."""

    table: str = ""
    primary: str = ""
    kind: str = "delta"  # "base" | "delta"
    prev_seq: int = 0
    seq: int = 0
    epoch: int = 0
    keys: bytes = b""  # int64 little-endian
    rows: bytes = b""  # float32 little-endian, len(keys)*(1+slots)*dim
    freqs: bytes = b""  # int64 little-endian
    digest: str = ""  # blake2b over the payload (PR 6 link integrity)
    trace: str = ""


@comm_message
class KvReplAck:  # dlr: no-trace — reply; the push request carries the trace
    """Follower -> primary (as the push RPC's reply): ``applied`` is
    the follower's replication mark after the link.  On refusal
    (``ok=False``) the primary re-exports from ``applied`` and pushes
    again — the refuse-and-re-request loop for digest mismatches and
    sequence gaps."""

    ok: bool = True
    reason: str = ""  # "" | "stale_epoch" | "digest" | "gap" | "not_follower"
    applied: int = 0
    epoch: int = 0
    durable: bool = False  # follower persisted the link to its own chain


@comm_message
class KvReplStateRequest:  # dlr: no-trace — control plane, not a request path
    table: str = ""


@comm_message
class KvReplState:  # dlr: no-trace — control-plane reply, not a request path
    """Shard -> caller: replication/lease snapshot — what the HA
    manager reads to pick a promotion winner and what the client reads
    to seed its staleness view."""

    name: str = ""
    role: str = "primary"
    epoch: int = 0
    applied: int = 0  # followers: primary mark applied through
    version: int = 0  # local table version
    followers: Dict[str, Dict[str, float]] = field(default_factory=dict)


@comm_message
class KvLeaseRequest:  # dlr: no-trace — control plane, not a request path
    """HA manager -> shard: install a lease.  ``role="primary"``
    promotes (the shard starts accepting fenced mutations at ``epoch``),
    ``role="follower"`` demotes, ``role="deposed"`` fences a stale
    primary — it refuses every mutation from then on, whatever epoch
    the writer carries."""

    epoch: int = 0
    role: str = ""  # "primary" | "follower" | "deposed"


@comm_message
class KvLeaseResult:
    ok: bool = True
    epoch: int = 0
    role: str = ""
    applied: int = 0  # the shard's replication mark at the transition


@comm_message
class KvReplConfigRequest:  # dlr: no-trace — control plane, not a request path
    """HA manager -> primary: attach/detach a follower.  Attaching
    bootstraps it with a base link, then streams deltas."""

    add_follower: str = ""  # follower addr ("host:port")
    remove_follower: str = ""
    follower_name: str = ""
    mode: str = ""  # "sync" | "manual" | "async" ("" = keep current)


@comm_message
class KvReplConfigResult:
    ok: bool = True
    followers: List[str] = field(default_factory=list)
    error: str = ""


@comm_message
class KvDigestRequest:  # dlr: no-trace — anti-entropy scan, control plane
    """Order-independent full-table digest (keys + rows, freqs
    excluded — read-path frequency bumps never replicate)."""

    table: str = ""


@comm_message
class KvDigest:  # dlr: no-trace — anti-entropy reply, control plane
    digest: str = ""
    rows: int = 0
    version: int = 0
    applied: int = 0


# ---------------------------------------------------------------------------
# Serving-gateway messages (serving/, docs/SERVING.md).  The gateway is
# the client; the decode worker hosts a MasterTransport servicer.  All
# traffic rides the same 2-RPC get/report pipe as the control plane.
# ---------------------------------------------------------------------------


@comm_message
class ServeSubmit:
    """Gateway -> worker: admit one generation request.

    ``request_id`` is the GATEWAY's id (stable across worker
    incarnations); after a worker death the replay incarnation carries
    ``prompt = original prompt + committed tokens`` with
    ``orig_prompt_len`` still naming the original boundary, so the
    TOTAL ``gen_budget`` accounting survives the replay.
    """

    request_id: int = -1
    prompt: List[int] = field(default_factory=list)
    gen_budget: int = 64
    orig_prompt_len: int = -1
    trace: str = ""  # tracing.TraceContext wire form ("" = unsampled)


@comm_message
class ServeSubmitResult:
    accepted: bool = False
    reason: str = ""


@comm_message
class ServePoll:  # dlr: no-trace — batch poll, spans no single request
    """Gateway -> worker: collect progress since the last poll.
    ``max_ticks`` bounds inline engine stepping for workers without a
    pump thread (0 = the worker pumps itself)."""

    max_ticks: int = 0


@comm_message
class ServeControl:  # dlr: no-trace — fleet-wide knob, spans no request
    """Gateway -> worker: runtime knob changes (brownout ladder,
    serving/fleet.py).  ``publish_prefix``: -1 = leave unchanged,
    0 = stop publishing prefix-cache entries, 1 = resume."""

    publish_prefix: int = -1


@comm_message
class ServeControlResult:
    ok: bool = False


@comm_message
class ServeVerify:  # dlr: no-trace — an offline check, spans no request
    """Gateway -> worker: score a finished completion (``tokens`` =
    prompt + completion) against the plain, non-paged forward of the
    worker's own weights.  Only the process that holds the chip can run
    that reference, so the check lives in the replica."""

    tokens: List[int] = field(default_factory=list)
    prompt_len: int = 0


@comm_message
class ServeVerifyResult:
    """Per generated position: the reference row's maximum logit, and how
    far below it the token the engine chose sits (0.0 = the argmax)."""

    row_max: List[float] = field(default_factory=list)
    margin: List[float] = field(default_factory=list)


@comm_message
class ServeProgress:
    """Worker -> gateway: newly generated tokens per request id (the
    gateway's commit journal feed), finished completions (plain dicts
    mirroring ``rl.serving.Completion``), and engine/pool stats."""

    emitted: Dict[int, List[int]] = field(default_factory=dict)
    completions: List[Dict[str, Any]] = field(default_factory=list)
    stats: Dict[str, Any] = field(default_factory=dict)
    worker_uid: str = ""

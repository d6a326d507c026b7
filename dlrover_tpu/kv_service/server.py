"""One shard of the distributed KvVariable service.

A :class:`KvShardServer` wraps a single host-RAM
:class:`~dlrover_tpu.native.kv_variable.KvVariable` behind the generic
2-RPC transport (``rpc/transport.py`` — same ``get``/``report`` surface
the master uses, shared-secret token included), plus:

* **Durability** — an optional :class:`KvCheckpointManager` delta chain
  (``checkpoint/kv_checkpoint.py``).  ``durability="apply"`` persists a
  chain link *before* acking each mutation — including rows an
  init-gather creates, which the client's forward pass consumes
  immediately — so a replacement shard that restores base + deltas has
  every acked row — the zero-lost-rows guarantee the chaos drill
  verifies.  ``durability="interval"`` saves
  every ``save_every`` applies (cheap, bounded loss window);
  ``"none"`` is bench mode.
* **Capacity accounting** — per-op busy-seconds measured around the
  table call only (queue/decode excluded), as **thread CPU time**
  (``time.thread_time``): on a colocated CI box, wall clock around the
  op would charge a shard for timeslices the OS gave its neighbours,
  making aggregate capacity look flat.  CPU time is what the shard
  actually spends serving (``/kvz`` reports it per op; no cell
  measures the service yet, ROADMAP S8).
* **Serving-time HTTP lookup** — the telemetry-httpd pattern:
  ``/lookup?keys=1,2,3`` (read-only gather-or-zeros) and ``/kvz``
  stats, for online traffic that shouldn't speak gRPC.

The shard never routes: clients own the ring.  A mis-routed write is
still applied (the store is a plain key space) — routing correctness is
the client's contract, asserted in tests.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, Optional

import numpy as np

from dlrover_tpu.common import comm
from dlrover_tpu.common.faults import fault_point
from dlrover_tpu.common.log import logger
from dlrover_tpu.kv_service.replication import (
    ChainReplicator,
    link_digest,
    table_digest,
)
from dlrover_tpu.native.kv_variable import KvVariable
from dlrover_tpu.rpc.transport import MasterTransport
from dlrover_tpu.telemetry import metrics as _metrics
from dlrover_tpu.telemetry import tracing as _tracing

__all__ = ["KvShardServer"]

# Optimizer apply methods that take the global step (bias-correction).
_STEPPED = frozenset({"adam", "group_adam", "amsgrad", "adahessian"})

_LATENCY_BUCKETS = (
    1e-5, 3e-5, 1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2, 0.1, 0.3, 1.0, 3.0,
)


def _server_metrics():
    return {
        "gather_seconds": _metrics.histogram(
            "dlrover_kv_server_gather_seconds",
            "Shard-side gather service time (table busy only).",
            buckets=_LATENCY_BUCKETS,
        ),
        "apply_seconds": _metrics.histogram(
            "dlrover_kv_server_apply_seconds",
            "Shard-side sparse-apply service time (table busy only).",
            buckets=_LATENCY_BUCKETS,
        ),
        "rows_total": _metrics.counter(
            "dlrover_kv_server_rows_total",
            "Rows served by this shard, by op (gather/apply/import).",
        ),
        "rows_gauge": _metrics.gauge(
            "dlrover_kv_server_table_rows",
            "Live row count of the shard's KvVariable.",
        ),
        "fence_refused_total": _metrics.counter(
            "dlrover_kv_fence_refused_total",
            "Mutations refused by the lease fence, by reason "
            "(stale_epoch/not_primary).",
        ),
    }


class _HotKeyTopK:
    """Bounded per-shard hot-key accounting (ROADMAP item 4's first
    half — the input Brain-driven shard splitting needs).

    Gathers append their ``np.unique`` (key, count) pairs to a pending
    list; folding into the count dict happens off the gather path — at
    snapshot time or when the pending list overflows — so the bench hot
    loop pays one C-speed unique per batch and no Python dict loop.
    On overflow the dict is pruned to its top half: a cheap
    Space-Saving-style sketch whose top-K survives pruning for the
    zipfian traffic it exists to detect.
    """

    def __init__(self, k: int = 32, cap: int = 4096):
        self.k = int(k)
        self._cap = max(2 * self.k, int(cap))
        self._lock = threading.Lock()
        self._counts: Dict[int, int] = {}
        self._pending: list = []
        self._total = 0

    def note(self, keys: np.ndarray):
        if self.k <= 0 or len(keys) == 0:
            return
        uniq, counts = np.unique(keys, return_counts=True)
        with self._lock:
            self._pending.append((uniq, counts))
            self._total += int(len(keys))
            if len(self._pending) > 256:
                self._fold_locked()

    def _fold_locked(self):
        for uniq, counts in self._pending:
            for key, n in zip(uniq.tolist(), counts.tolist()):
                self._counts[key] = self._counts.get(key, 0) + n
        self._pending = []
        if len(self._counts) > self._cap:
            keep = sorted(
                self._counts.items(), key=lambda kv: kv[1], reverse=True
            )[: self._cap // 2]
            self._counts = dict(keep)

    def top(self, k: Optional[int] = None):
        with self._lock:
            self._fold_locked()
            ranked = sorted(
                self._counts.items(), key=lambda kv: kv[1], reverse=True
            )
            return [
                [int(key), int(n)]
                for key, n in ranked[: k if k is not None else self.k]
            ]

    def skew(self) -> float:
        """Fraction of all gathered keys landing on the single hottest
        key — the saturates-one-shard signal."""
        with self._lock:
            self._fold_locked()
            if not self._counts or self._total == 0:
                return 0.0
            return max(self._counts.values()) / self._total


class _Stats:
    """Lock-guarded per-op busy-seconds / rows / rpc counters."""

    def __init__(self):
        self._lock = threading.Lock()
        self.busy_s: Dict[str, float] = {}
        self.served_rows: Dict[str, int] = {}
        self.rpcs: Dict[str, int] = {}

    def add(self, op: str, busy: float, rows: int):
        with self._lock:
            self.busy_s[op] = self.busy_s.get(op, 0.0) + busy
            self.served_rows[op] = self.served_rows.get(op, 0) + rows
            self.rpcs[op] = self.rpcs.get(op, 0) + 1

    def snapshot(self, reset_busy: bool = False):
        with self._lock:
            out = (
                dict(self.busy_s),
                dict(self.served_rows),
                dict(self.rpcs),
            )
            if reset_busy:
                self.busy_s.clear()
                self.served_rows.clear()
                self.rpcs.clear()
            return out


class _KvShardServicer:
    """The transport-facing half: ``get``/``report`` dispatch."""

    def __init__(self, server: "KvShardServer"):
        self._server = server
        self._get_handlers = {
            comm.KvGatherRequest: server._handle_gather,
            comm.KvApplyRequest: server._handle_apply,
            comm.KvShardStatsRequest: server._handle_stats,
            comm.KvSaveRequest: server._handle_save,
            comm.KvImportRequest: server._handle_import,
            comm.KvExportRequest: server._handle_export,
            comm.KvReplPushRequest: server._handle_repl_push,
            comm.KvLeaseRequest: server._handle_lease,
            comm.KvReplConfigRequest: server._handle_repl_config,
            comm.KvReplStateRequest: server._handle_repl_state,
            comm.KvDigestRequest: server._handle_digest,
        }

    def get(self, node_id: int, node_type: str, message):
        handler = self._get_handlers.get(type(message))
        if handler is None:
            raise ValueError(
                f"kv shard: unsupported message {type(message).__name__}"
            )
        return handler(message)

    def report(self, node_id: int, node_type: str, message) -> bool:
        # Mutations also ride get() so callers see the typed result;
        # report() is kept for fire-and-forget applies.
        handler = self._get_handlers.get(type(message))
        if handler is None:
            return False
        handler(message)
        return True


class KvShardServer:
    """One named shard: KvVariable + RPC + delta-chain durability."""

    def __init__(
        self,
        name: str,
        dim: int,
        slots: int = 2,
        port: int = 0,
        init_scale: float = 0.05,
        seed: int = 0,
        chain_dir: Optional[str] = None,
        durability: str = "none",
        save_every: int = 64,
        full_interval: int = 16,
        max_deltas: int = 64,
        token: Optional[str] = None,
        table_name: str = "embedding",
        http_port: Optional[int] = None,
        role: str = "primary",
        epoch: int = 0,
        repl_mode: str = "sync",
        hot_key_k: int = 32,
        emit=None,
        canary_keys: int = 0,
    ):
        if durability not in ("none", "interval", "apply"):
            raise ValueError(f"unknown durability mode {durability!r}")
        if role not in ("primary", "follower"):
            raise ValueError(f"unknown shard role {role!r}")
        self.name = name
        self.table_name = table_name
        self.table = KvVariable(
            dim, slots=slots, init_scale=init_scale, seed=seed
        )
        self._durability = durability
        self._save_every = max(1, int(save_every))
        self._apply_count = 0
        self._save_step = 0
        self._save_lock = threading.Lock()
        self._stats = _Stats()
        self._metrics = _server_metrics()
        self.recovery_s = -1.0
        self.restored_rows = 0
        self._token = token
        self._emit = emit
        # -- replication role + lease fence.  epoch 0 is unreplicated
        # legacy mode: the fence never fires, so single-owner deploys
        # (every pre-replication test and bench) are untouched.
        self._role = role
        self._lease_epoch = int(epoch)
        self._applied_mark = 0  # follower: primary mark applied through
        self._repl_mode = repl_mode
        self._repl: Optional[ChainReplicator] = None
        self._hot = _HotKeyTopK(k=hot_key_k)
        # Reserved black-box probe table (observer/canary.py): sentinel
        # keys 1..canary_keys with a deterministic fill, looked up via
        # ``/lookup?table=__canary__`` so probes exercise the real
        # gather path without ever touching live embeddings.
        self.canary_table: Optional[KvVariable] = None
        if canary_keys > 0:
            self.canary_table = KvVariable(
                dim, slots=0, init_scale=0.0, seed=seed
            )
            keys = np.arange(1, int(canary_keys) + 1, dtype=np.int64)
            values = np.outer(
                keys.astype(np.float32), np.ones(dim, np.float32)
            ) * 1e-3
            self.canary_table.insert(keys, values)  # dlr: unfenced

        self._ckpt = None
        if chain_dir:
            from dlrover_tpu.checkpoint.kv_checkpoint import (
                KvCheckpointManager,
            )

            self._ckpt = KvCheckpointManager(
                self.table,
                chain_dir,
                full_interval=full_interval,
                max_deltas=max_deltas,
            )
            t0 = time.perf_counter()
            if self._ckpt.restore():
                self.recovery_s = time.perf_counter() - t0
                self.restored_rows = len(self.table)
                logger.info(
                    "kv shard %s restored %d rows in %.3fs (chain len %d)",
                    name, self.restored_rows, self.recovery_s,
                    self._ckpt.chain_length,
                )

        self._transport = MasterTransport(
            _KvShardServicer(self), port=port, token=token
        )
        self.port = self._transport.port
        self._http = None
        self._http_port = http_port

    # -- lifecycle ---------------------------------------------------------

    def start(self):
        self._transport.start()
        if self._http_port is not None:
            self._start_http(self._http_port)
        return self

    def stop(self, grace: Optional[float] = None):
        if self._repl is not None:
            self._repl.stop()
            self._repl.clear()
        self._transport.stop(grace)
        if self._http is not None:
            try:
                self._http.shutdown()
                self._http.server_close()
            except OSError:
                pass
            self._http = None
        if self.canary_table is not None:
            self.canary_table.close()
        self.table.close()

    @property
    def http_port(self) -> int:
        return self._http.server_address[1] if self._http else 0

    # -- replication + lease fencing ---------------------------------------

    @property
    def role(self) -> str:
        return self._role

    @property
    def lease_epoch(self) -> int:
        return self._lease_epoch

    def _repl_mark(self) -> int:
        """The primary version mark this shard has applied through: a
        follower reports the stream position, a primary its own table
        version (they are the same numbering — table version marks)."""
        if self._role == "follower":
            return self._applied_mark
        return int(self.table.version)

    def _fence(self, msg_epoch: int) -> Optional[str]:
        """The lease check every mutation passes before touching the
        table.  Returns a refusal reason, or None to admit.

        ``epoch 0`` on both sides means unreplicated legacy mode and is
        never fenced.  Once a lease is installed, only the exact lease
        epoch writes: a deposed primary (or a client holding its stale
        token) is refused here — the split-brain half of zero
        acked-write loss.
        """
        if self._role != "primary":
            self._metrics["fence_refused_total"].inc(reason="not_primary")
            return "not_primary"
        # Chaos: kv_stale_epoch forces the refusal path end-to-end
        # (arm with noop) without needing a real deposed primary.
        if fault_point(
            "kv_stale_epoch", shard=self.name, epoch=int(msg_epoch)
        ):
            self._metrics["fence_refused_total"].inc(reason="stale_epoch")
            return "stale_epoch"
        if self._lease_epoch and int(msg_epoch) != self._lease_epoch:
            self._metrics["fence_refused_total"].inc(reason="stale_epoch")
            return "stale_epoch"
        return None

    def _ensure_repl(self, mode: Optional[str] = None) -> ChainReplicator:
        if self._repl is None:
            want = mode or self._repl_mode
            self._repl = ChainReplicator(
                self.table,
                self.name,
                table_name=self.table_name,
                epoch=self._lease_epoch,
                mode=want,
                token=self._token,
                emit=self._emit,
            )
            if want == "async":
                self._repl.start()
        elif mode:
            self._repl.set_mode(mode)
        return self._repl

    @property
    def replicator(self) -> Optional[ChainReplicator]:
        return self._repl

    def _replicate(self, trace: str = ""):
        """Feed the stream after an applied mutation.  sync mode raises
        on a failed push, which fails the caller's RPC — so nothing gets
        acked that a follower didn't apply (zero acked-write loss)."""
        if self._repl is not None and self._role == "primary":
            self._repl.on_mutation(trace=trace)

    # -- RPC handlers ------------------------------------------------------

    def _handle_gather(self, msg: comm.KvGatherRequest) -> comm.KvRows:
        keys = np.frombuffer(msg.keys, dtype="<i8")
        ctx = _tracing.from_wire(getattr(msg, "trace", ""))
        wall_t0 = time.perf_counter()
        self._hot.note(keys)
        t0 = time.thread_time()
        inserted = False
        if msg.init:
            # Init-gathers create rows, so they are mutations: fenced
            # like an apply.  Read-only gathers are never fenced — a
            # follower serving bounded-staleness reads lands below.
            if self._fence(msg.epoch) is not None:
                return comm.KvRows(
                    dim=self.table.dim,
                    version=self.table.version,
                    applied=self._repl_mark(),
                    refused=True,
                )
            version_before = self.table.version
            values = self.table.gather_or_init(keys)
            found = np.ones(len(keys), np.uint8)
            # Row creation bumps the table version; freq bumps on
            # existing rows don't, so warm gathers stay save-free.
            inserted = self.table.version != version_before
        else:
            values, found_b = self.table.gather_or_zeros(keys)
            found = found_b.astype(np.uint8)
        busy = time.thread_time() - t0
        self._stats.add("gather", busy, len(keys))
        # An init-gather that created rows is a mutation the client
        # consumes immediately (its forward pass uses the random init).
        # durability="apply" must persist it like any other acked
        # mutation, or a crash-and-restore re-rolls those rows with
        # different values.  Outside the busy window: save I/O is not
        # table service time.
        if inserted and self._durability == "apply":
            self._maybe_save(0)
        if inserted:
            self._replicate(trace=getattr(msg, "trace", ""))
        self._metrics["gather_seconds"].observe(
            busy, exemplar=ctx.trace_id if ctx else None
        )
        self._metrics["rows_total"].inc(len(keys), op="gather")
        if ctx is not None:
            _tracing.emit_span(
                ctx.child(), "kv_serve",
                time.perf_counter() - wall_t0,
                shard=self.name, n_keys=len(keys), busy=busy,
            )
        return comm.KvRows(
            values=np.ascontiguousarray(values, "<f4").tobytes(),
            found=found.tobytes(),
            dim=self.table.dim,
            version=self.table.version,
            applied=self._repl_mark(),
        )

    def _handle_apply(self, msg: comm.KvApplyRequest) -> comm.KvApplyResult:
        reason = self._fence(msg.epoch)
        if reason is not None:
            return comm.KvApplyResult(
                applied=0,
                version=self.table.version,
                durable=False,
                refused=True,
                epoch=self._lease_epoch,
            )
        # Keys are owned (not a view): counts derived from them ride
        # back in the ack, and nothing leaving this frame may keep the
        # request buffer alive (DLR001).  8 bytes/row — noise next to
        # the table op.  The value matrix stays a view: it is consumed
        # synchronously by the C call and never escapes.
        keys = np.frombuffer(msg.keys, dtype="<i8").copy()
        values = np.frombuffer(msg.values, dtype="<f4").reshape(
            len(keys), self.table.dim
        )
        ctx = _tracing.from_wire(getattr(msg, "trace", ""))
        wall_t0 = time.perf_counter()
        t0 = time.thread_time()
        if msg.optimizer == "insert":
            self.table.insert(keys, values)
        elif msg.optimizer == "scatter_add":
            self.table.scatter_add(keys, values)
        else:
            kwargs = dict(msg.hparams)
            if "nesterov" in kwargs:  # rides the wire as a float
                kwargs["nesterov"] = bool(kwargs["nesterov"])
            if msg.optimizer in _STEPPED:
                kwargs["step"] = max(1, int(msg.step))
            apply_fn = getattr(self.table, f"apply_{msg.optimizer}", None)
            if apply_fn is None:
                raise ValueError(f"unknown optimizer {msg.optimizer!r}")
            apply_fn(keys, values, **kwargs)
        busy = time.thread_time() - t0
        self._stats.add("apply", busy, len(keys))
        self._metrics["apply_seconds"].observe(
            busy, exemplar=ctx.trace_id if ctx else None
        )
        self._metrics["rows_total"].inc(len(keys), op="apply")
        if ctx is not None:
            _tracing.emit_span(
                ctx.child(), "kv_serve_apply",
                time.perf_counter() - wall_t0,
                shard=self.name, n_keys=len(keys), busy=busy,
            )
        durable = self._maybe_save(msg.step)
        self._replicate(trace=getattr(msg, "trace", ""))
        return comm.KvApplyResult(
            applied=len(keys),
            version=self.table.version,
            durable=durable,
            epoch=self._lease_epoch,
        )

    def _handle_stats(
        self, msg: comm.KvShardStatsRequest
    ) -> comm.KvShardStats:
        busy, rows, rpcs = self._stats.snapshot(reset_busy=msg.reset_busy)
        self._metrics["rows_gauge"].set(len(self.table))
        return comm.KvShardStats(
            name=self.name,
            table=self.table_name,
            rows=len(self.table),
            dim=self.table.dim,
            slots=self.table.slots,
            version=self.table.version,
            busy_s=busy,
            served_rows=rows,
            rpcs=rpcs,
            recovery_s=self.recovery_s,
            restored_rows=self.restored_rows,
            chain_length=self._ckpt.chain_length if self._ckpt else 0,
            role=self._role,
            epoch=self._lease_epoch,
            applied=self._repl_mark(),
            repl_lag_s=self._repl.max_lag_s() if self._repl else -1.0,
            hot_keys=self._hot.top(),
        )

    def _handle_save(self, msg: comm.KvSaveRequest) -> comm.KvSaveResult:
        if self._fence(msg.epoch) is not None:
            return comm.KvSaveResult(kind="refused", step=msg.step)
        if self._ckpt is None:
            return comm.KvSaveResult(kind="none", step=msg.step)
        with self._save_lock:
            self._save_step = max(self._save_step + 1, int(msg.step))
            kind = self._ckpt.save(self._save_step)
        return comm.KvSaveResult(kind=kind, step=self._save_step)

    def _handle_import(self, msg: comm.KvImportRequest) -> comm.KvApplyResult:
        if self._fence(msg.epoch) is not None:
            return comm.KvApplyResult(
                applied=0,
                version=self.table.version,
                durable=False,
                refused=True,
                epoch=self._lease_epoch,
            )
        # Owned for the same reason as in _handle_apply: the ack carries
        # a count derived from keys.
        keys = np.frombuffer(msg.keys, dtype="<i8").copy()
        row_floats = (1 + self.table.slots) * self.table.dim
        rows = np.frombuffer(msg.rows, dtype="<f4").reshape(
            len(keys), row_floats
        )
        freqs = (
            np.frombuffer(msg.freqs, dtype="<i8")
            if msg.freqs
            else None
        )
        t0 = time.thread_time()
        self.table.import_rows(keys, rows, freqs=freqs)
        self._stats.add("import", time.thread_time() - t0, len(keys))
        self._metrics["rows_total"].inc(len(keys), op="import")
        durable = self._maybe_save(0, force=self._durability == "apply")
        self._replicate(trace=getattr(msg, "trace", ""))
        return comm.KvApplyResult(
            applied=len(keys),
            version=self.table.version,
            durable=durable,
            epoch=self._lease_epoch,
        )

    def _handle_export(self, msg: comm.KvExportRequest) -> comm.KvExportResult:
        """Rows that belong to *other* owners under the new membership —
        the scale-event migration source.  The store has no per-key
        delete, so exported rows stay resident here until frequency
        eviction reclaims them; routing never reads them again."""
        from dlrover_tpu.kv_service.routing import HashRing

        ring = HashRing(msg.names)
        keys, rows, freqs, _mark = self.table.export_rows()
        if len(keys) == 0:
            return comm.KvExportResult()
        owner_idx = ring.owner_indices(keys)
        self_name = msg.self_name or self.name
        moved = np.array(
            [ring.names[i] != self_name for i in owner_idx], dtype=bool
        )
        out_names = []
        out_counts = []
        key_chunks = []
        row_chunks = []
        freq_chunks = []
        for i, owner in enumerate(ring.names):
            sel = moved & (owner_idx == i)
            n = int(np.count_nonzero(sel))
            if n == 0:
                continue
            out_names.append(owner)
            out_counts.append(n)
            key_chunks.append(keys[sel])
            row_chunks.append(rows[sel])
            freq_chunks.append(freqs[sel].astype(np.int64))
        if not out_names:
            return comm.KvExportResult()
        return comm.KvExportResult(
            keys=np.concatenate(key_chunks).astype("<i8").tobytes(),
            rows=np.ascontiguousarray(
                np.concatenate(row_chunks), "<f4"
            ).tobytes(),
            freqs=np.concatenate(freq_chunks).astype("<i8").tobytes(),
            owners=out_names,
            counts=out_counts,
        )

    # -- replication handlers ----------------------------------------------

    def _handle_repl_push(
        self, msg: comm.KvReplPushRequest
    ) -> comm.KvReplAck:
        """Apply one replication link (follower side).

        Refusals carry the follower's actual applied mark so the
        primary can re-export from there — the refuse-and-re-request
        loop.  Epoch ordering is the fence's mirror image: links from
        an *older* epoch are a deposed primary leaking late writes and
        are refused; a *newer* epoch is a promotion this follower
        hasn't heard about yet, and the lease is learned from the
        stream itself.
        """
        if self._role != "follower":
            return comm.KvReplAck(
                ok=False,
                reason="not_follower",
                applied=self._repl_mark(),
                epoch=self._lease_epoch,
            )
        if int(msg.epoch) < self._lease_epoch:
            self._metrics["fence_refused_total"].inc(reason="stale_epoch")
            return comm.KvReplAck(
                ok=False,
                reason="stale_epoch",
                applied=self._applied_mark,
                epoch=self._lease_epoch,
            )
        if int(msg.epoch) > self._lease_epoch:
            self._lease_epoch = int(msg.epoch)
        if link_digest(msg.keys, msg.rows, msg.freqs) != msg.digest:
            return comm.KvReplAck(
                ok=False,
                reason="digest",
                applied=self._applied_mark,
                epoch=self._lease_epoch,
            )
        if msg.kind == "delta" and int(msg.prev_seq) != self._applied_mark:
            return comm.KvReplAck(
                ok=False,
                reason="gap",
                applied=self._applied_mark,
                epoch=self._lease_epoch,
            )
        keys = np.frombuffer(msg.keys, dtype="<i8")
        t0 = time.thread_time()
        if len(keys):
            row_floats = (1 + self.table.slots) * self.table.dim
            rows = np.frombuffer(msg.rows, dtype="<f4").reshape(
                len(keys), row_floats
            )
            freqs = (
                np.frombuffer(msg.freqs, dtype="<i8") if msg.freqs else None
            )
            self.table.import_rows(keys, rows, freqs=freqs)
        # An empty link still advances the mark: a version bump whose
        # delta scan found nothing new (the empty-delta-link edge case).
        self._applied_mark = int(msg.seq)
        self._stats.add("repl", time.thread_time() - t0, len(keys))
        self._metrics["rows_total"].inc(len(keys), op="repl")
        # A follower with its own chain persists the link (it may be
        # promoted later and must restore what it acked).
        durable = False
        if len(keys):
            durable = self._maybe_save(0, force=self._durability == "apply")
        ctx = _tracing.from_wire(getattr(msg, "trace", ""))
        if ctx is not None:
            _tracing.emit_span(
                ctx.child(), "kv_repl_apply", time.thread_time() - t0,
                shard=self.name, n_keys=len(keys), seq=int(msg.seq),
            )
        return comm.KvReplAck(
            ok=True,
            applied=self._applied_mark,
            epoch=self._lease_epoch,
            durable=durable,
        )

    def _handle_lease(self, msg: comm.KvLeaseRequest) -> comm.KvLeaseResult:
        """Install a lease: the promotion ladder's write instrument.

        ``role="primary"`` turns a follower into the new primary (its
        table — every acked mutation, sync-replicated — simply starts
        serving under the new epoch).  ``role="deposed"`` fences a
        reachable old primary so its in-flight writers bounce.
        """
        applied = self._repl_mark()
        if msg.role == "primary":
            self._role = "primary"
            self._lease_epoch = int(msg.epoch)
            self._ensure_repl().set_epoch(self._lease_epoch)
        elif msg.role == "follower":
            self._role = "follower"
            self._lease_epoch = int(msg.epoch)
            # A demoted primary keeps no downstream: its old followers
            # re-attach to the new primary.
            if self._repl is not None:
                self._repl.clear()
            self._applied_mark = 0
        elif msg.role == "deposed":
            self._role = "deposed"
            self._lease_epoch = int(msg.epoch)
        else:
            return comm.KvLeaseResult(
                ok=False,
                epoch=self._lease_epoch,
                role=self._role,
                applied=applied,
            )
        logger.info(
            "kv shard %s: lease %s@%d installed",
            self.name, msg.role, int(msg.epoch),
        )
        return comm.KvLeaseResult(
            ok=True,
            epoch=self._lease_epoch,
            role=self._role,
            applied=applied,
        )

    def _handle_repl_config(
        self, msg: comm.KvReplConfigRequest
    ) -> comm.KvReplConfigResult:
        if self._role != "primary":
            return comm.KvReplConfigResult(
                ok=False, followers=[], error="not_primary"
            )
        repl = self._ensure_repl(mode=msg.mode or None)
        ok = True
        if msg.add_follower:
            ok = repl.add_follower(msg.add_follower, name=msg.follower_name)
        if msg.remove_follower:
            repl.remove_follower(msg.remove_follower)
        return comm.KvReplConfigResult(
            ok=ok,
            followers=repl.followers(),
            error="" if ok else "bootstrap_failed",
        )

    def _handle_repl_state(
        self, msg: comm.KvReplStateRequest
    ) -> comm.KvReplState:
        return comm.KvReplState(
            name=self.name,
            role=self._role,
            epoch=self._lease_epoch,
            applied=self._repl_mark(),
            version=int(self.table.version),
            followers=self._repl.lag() if self._repl else {},
        )

    def _handle_digest(self, msg: comm.KvDigestRequest) -> comm.KvDigest:
        d = table_digest(self.table)
        return comm.KvDigest(
            digest=d["digest"],
            rows=d["rows"],
            version=d["version"],
            applied=self._repl_mark(),
        )

    # -- durability --------------------------------------------------------

    def _maybe_save(self, step: int, force: bool = False) -> bool:
        if self._ckpt is None or self._durability == "none":
            return False
        with self._save_lock:
            self._apply_count += 1
            due = (
                force
                or self._durability == "apply"
                or self._apply_count % self._save_every == 0
            )
            if not due:
                return False
            # Chain files are named by step (kv-<step>.delta.npz) —
            # repeated saves at the same training step would overwrite
            # a link the manifest still references.  Keep the saved
            # step strictly monotonic regardless of what callers send.
            self._save_step = max(self._save_step + 1, int(step))
            self._ckpt.save(self._save_step)
            return True

    # -- serving-time HTTP lookup -----------------------------------------

    def _start_http(self, port: int):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
        from urllib.parse import parse_qs, urlsplit

        server = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # noqa: A003 — stay quiet
                pass

            def _send(self, code: int, payload: dict):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _send_text(self, code: int, text: str, ctype: str):
                body = text.encode()
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):  # noqa: N802 — http.server contract
                path, _, query = self.path.partition("?")
                try:
                    if path == "/lookup":
                        qs = parse_qs(query)
                        raw = qs.get("keys", [""])[0]
                        table = qs.get("table", [""])[0]
                        try:
                            keys = np.array(
                                [int(k) for k in raw.split(",") if k],
                                dtype=np.int64,
                            )
                        except ValueError:
                            self._send(400, {"error": "bad keys"})
                            return
                        out = server.lookup_json(keys, table=table)
                        self._send(400 if out.get("error") else 200, out)
                    elif path == "/kvz":
                        stats = server._handle_stats(
                            comm.KvShardStatsRequest()
                        )
                        self._send(
                            200,
                            {
                                "name": stats.name,
                                "rows": stats.rows,
                                "version": stats.version,
                                "busy_s": stats.busy_s,
                                "served_rows": stats.served_rows,
                                "rpcs": stats.rpcs,
                                "recovery_s": stats.recovery_s,
                                "chain_length": stats.chain_length,
                                "role": stats.role,
                                "epoch": stats.epoch,
                                "applied": stats.applied,
                                "repl_lag_s": stats.repl_lag_s,
                                "hot_keys": stats.hot_keys,
                                "hot_key_skew": server._hot.skew(),
                                "latency": {
                                    "gather_s": _metrics.aggregate_summary(
                                        server._metrics["gather_seconds"]
                                    ),
                                    "apply_s": _metrics.aggregate_summary(
                                        server._metrics["apply_seconds"]
                                    ),
                                },
                            },
                        )
                    elif path == "/statusz":
                        self._send(200, server.statusz())
                    elif path == "/metrics":
                        # ONLY this shard's own metric families: when a
                        # shard shares a process (and so the global
                        # registry) with a gateway or trainer, exposing
                        # the full registry here would double-count
                        # every shared series under federation.
                        self._send_text(
                            200,
                            _metrics.render_subset(
                                server._metrics.values()
                            ),
                            "text/plain; version=0.0.4; charset=utf-8",
                        )
                    else:
                        self._send(404, {"error": "not found"})
                except Exception as e:  # noqa: BLE001 — keep serving
                    try:
                        self._send(500, {"error": str(e)})
                    except OSError:
                        pass

        self._http = ThreadingHTTPServer(("0.0.0.0", port), Handler)
        self._http.daemon_threads = True
        threading.Thread(
            target=self._http.serve_forever,
            name=f"kv-http-{self.name}",
            daemon=True,
        ).start()
        logger.info(
            "kv shard %s lookup endpoint on :%d", self.name, self.http_port
        )

    def hot_key_summary(self) -> dict:
        """Warehouse-shaped hot-key row (``add_kv_summary`` input): the
        per-shard skew signal Brain-driven shard splitting consumes."""
        return {
            "source": "hot_keys",
            "owner": self.name,
            "rows": len(self.table),
            "top": self._hot.top(),
            "hot_key_skew": self._hot.skew(),
        }

    def statusz(self) -> dict:
        """The observer's discovery handshake on the shard httpd —
        same shape as TelemetryHTTPServer.statusz."""
        from dlrover_tpu.telemetry import events as _tl_events
        from dlrover_tpu.telemetry.httpd import response_stamp

        out = dict(response_stamp())
        out.update(
            role="kv",
            uid=self.name,
            pid=os.getpid(),
            rank=int(os.environ.get("DLROVER_PROCESS_ID", "0") or 0),
            endpoints=["/lookup", "/kvz", "/statusz", "/metrics"],
            schema_versions={
                "events": _tl_events.SCHEMA_VERSION,
                "metrics_exposition": "0.0.4",
            },
            table=self.table_name,
            shard_role=self._role,
            epoch=self._lease_epoch,
            canary_table=self.canary_table is not None,
        )
        return out

    def lookup_json(self, keys: np.ndarray, table: str = "") -> dict:
        """Read-only lookup (gather-or-zeros: never mutates the table).

        ``table="__canary__"`` routes to the reserved sentinel table so
        black-box probes exercise this exact path without reading live
        embeddings; any other non-default name is refused."""
        target = self.table
        if table and table != self.table_name:
            if table == "__canary__" and self.canary_table is not None:
                target = self.canary_table
            else:
                return {"error": f"unknown table {table!r}"}
        t0 = time.thread_time()
        values, found = target.gather_or_zeros(keys)
        busy = time.thread_time() - t0
        self._stats.add("lookup", busy, len(keys))
        self._metrics["gather_seconds"].observe(busy)
        self._metrics["rows_total"].inc(len(keys), op="lookup")
        return {
            "keys": [int(k) for k in keys],
            "values": [[float(x) for x in row] for row in values],
            "found": [bool(f) for f in found],
            "dim": target.dim,
        }

"""Trainer-side Flash Checkpoint engine for JAX pytrees.

Reference parity: ``dlrover/trainer/torch/flash_checkpoint/engine.py:135``
(CheckpointEngine.save_to_memory: state dict → shm, notify agent queue;
load = shm-first, storage fallback) + the FSDP flat-ckpt reshard-on-restore
(``atorch/utils/fsdp_save_util.py``).

TPU mapping: the "state dict" is any pytree of ``jax.Array``s (TrainState).
``save_to_memory`` snapshots the state on the device and hands its
*addressable shards* to a stager thread, which runs the save as one
pipeline: each shard is checksummed and copied into the agent's shm block,
with its global layout (shape + index), while the next is still leaving
the chip (HBM→host over PCIe).  Restore pastes shards from any saved mesh
layout into arrays sharded for the *current* mesh — elastic restarts with a
different world size reshard transparently.
"""

import collections
import os
import pickle
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.common.log import logger
from dlrover_tpu.common.multi_process import SharedLock, SharedQueue
from dlrover_tpu.checkpoint.deletion import strategy_meta as _strategy_meta
from dlrover_tpu.checkpoint.ckpt_saver import (
    EVENT_QUEUE,
    FACTORY_QUEUE,
    SHM_LOCK,
    CheckpointEvent,
    CheckpointEventType,
    SaverConfig,
    list_shard_files,
)
from dlrover_tpu.checkpoint.shm_handler import (
    SharedMemoryHandler,
    ShmCorruptError,
    _ShardEntry,
    largest_first,
)
from dlrover_tpu.checkpoint.storage import (
    CheckpointStorage,
    PosixDiskStorage,
    read_tracker,
    step_dir,
)


def _slices_to_bounds(index, shape) -> Tuple[Tuple[int, int], ...]:
    """Normalize a shard's index (tuple of slices) to (start, stop) pairs."""
    bounds = []
    for sl, dim in zip(index, shape):
        start = 0 if sl.start is None else sl.start
        stop = dim if sl.stop is None else sl.stop
        bounds.append((start, stop))
    return tuple(bounds)


# Transfers of one device started and not yet arrived.  Measured on a v5e
# (PERF.md §6, PR 30): started all at once, the transfers share the link
# and little has arrived whole until the end of the drain, so the host's
# work is left for the tail (1.95 GB: tail 0.17 s against 0.05, save 0.95 s
# against 0.85; 5.84 GB: 6.2 s against 3.4-4.5).  With two in flight the
# shards arrive one by one and the link always has the next queued.
_IN_FLIGHT = 2


class _InTransit:
    """One shard of a snapshot on its way to the host: it knows its shape
    and dtype, and ``np.asarray`` of it returns the bytes once they have
    arrived, having started the next transfer that waited for its device."""

    def __init__(self, data, waiting: collections.deque):
        self.shape, self.dtype = data.shape, data.dtype
        self._data, self._waiting = data, waiting

    def __array__(self, dtype=None, copy=None):
        host = np.asarray(self._data)
        _start_next(self._waiting)
        return host


def _start_next(waiting: collections.deque):
    if waiting:
        waiting.popleft().copy_to_host_async()


def begin_host_transfer(state) -> Dict[Tuple, Any]:
    """Start the HBM→host drain of a snapshot; return what the stager's
    pipeline consumes: ``{(keystr, shard_idx): _ShardEntry | leaf}`` in the
    order of the tree (their order in the block), every replica-0 shard an
    entry whose ``data`` is :class:`_InTransit`.

    ``copy_to_host_async`` (returns at once; the DMA runs beside whatever
    the trainer computes next) is called here on the first ``_IN_FLIGHT``
    shards of each device, in the order the pipeline takes them
    (``shm_handler.largest_first``), and on the next of a device as one of
    its shards arrives."""
    flat, _ = jax.tree_util.tree_flatten_with_path(state)
    tree: Dict[Tuple, Any] = {}
    device_of: Dict[Tuple, Any] = {}
    for path, leaf in flat:
        key = jax.tree_util.keystr(path)
        if isinstance(leaf, jax.Array):
            gshape = tuple(leaf.shape)
            for i, shard in enumerate(leaf.addressable_shards):
                if shard.replica_id != 0:
                    continue
                bounds = _slices_to_bounds(shard.index, gshape)
                tree[(key, i)] = _ShardEntry(shard.data, gshape, bounds)
                device_of[(key, i)] = shard.device
        else:
            tree[(key, -1)] = leaf
    waiting: Dict[Any, collections.deque] = {}
    for key in largest_first({k: tree[k] for k in device_of}):
        waiting.setdefault(device_of[key], collections.deque()).append(
            tree[key].data
        )
    for key, device in device_of.items():
        tree[key].data = _InTransit(tree[key].data, waiting[device])
    for queue in waiting.values():
        for _ in range(_IN_FLIGHT):
            _start_next(queue)
    return tree


def load_storage_host_tree(
    storage: CheckpointStorage,
    checkpoint_dir: str,
    step: Optional[int] = None,
):
    """Read a committed checkpoint's shard files into the flat host tree
    ``{(keystr, "rankTag:idx"): entry}`` — the single implementation of
    the shard-tag disambiguation convention, shared by the engine's
    storage fallback and the selective pretrained restore.  Returns
    ``(step, host)`` or None when nothing is committed."""
    step = step if step is not None else read_tracker(
        storage, checkpoint_dir
    )
    if step is None:
        return None
    host: Dict[Tuple, Any] = {}
    sdir = step_dir(checkpoint_dir, step)
    shards = list_shard_files(storage, sdir)
    if not shards:
        return None
    for fname in shards:
        blob = storage.read(os.path.join(sdir, fname))
        if blob is None:
            raise IOError(
                f"committed checkpoint step {step} is missing shard "
                f"{fname} — refusing a partial restore"
            )
        tree: Dict[Tuple, Any] = pickle.loads(blob)
        # Disambiguate same-(key, idx) pairs across ranks.
        tag = fname.removesuffix(".pkl")
        for (key, idx), val in tree.items():
            host[(key, f"{tag}:{idx}")] = val
    return step, host


class _DeviceSnapshot:
    """Donation guard: device-side copy of a state pytree.

    The train step typically donates its input state buffers
    (``donate_argnums``), which invalidates them the moment the next step
    is dispatched — an async HBM→host drain reading the *live* state
    would race with that.  Snapshotting first sidesteps it: one jitted
    identity-copy produces fresh buffers we own (HBM→HBM at memory
    bandwidth, dispatch returns in ms), and the slow drain reads the
    snapshot while training proceeds.  Costs one transient state copy of
    HBM — the reference pays the same in pinned host memory
    (``ckpt_saver.py`` shm double buffer).
    """

    def __init__(self):
        self._copy = jax.jit(lambda leaves: [jnp.copy(x) for x in leaves])

    def take(self, state):
        flat, treedef = jax.tree_util.tree_flatten(state)
        arrays = [
            (i, x) for i, x in enumerate(flat) if isinstance(x, jax.Array)
        ]
        copies = self._copy([x for _, x in arrays])
        for (i, _), c in zip(arrays, copies):
            flat[i] = c
        return jax.tree_util.tree_unflatten(treedef, flat)


class _AsyncStager:
    """Single-slot, latest-wins staging worker (the host-side half of the
    double buffer): while it drains step N's snapshot into shm, the
    trainer may already submit step N+1.  An overwritten pending step is
    logged and dropped — shm only ever needs the newest state — but a
    requested persist is carried forward to the superseding step so a
    disk save is never silently lost.
    """

    def __init__(self, process_fn: Callable[[int, Dict, bool], bool]):
        self._process = process_fn
        self._cond = threading.Condition()
        self._pending: Optional[Tuple[int, Dict, bool]] = None
        self._inflight: Optional[int] = None
        self._last_ok = True
        self._failed_sticky = False
        self._stopped = False
        self._thread = threading.Thread(
            target=self._run, name="ckpt-stager", daemon=True
        )
        self._thread.start()

    def busy(self) -> bool:
        with self._cond:
            return self._pending is not None or self._inflight is not None

    def consume_failure(self) -> bool:
        """True once per staging failure since the last check — lets the
        next save call surface an async error to its caller."""
        with self._cond:
            failed, self._failed_sticky = self._failed_sticky, False
            return failed

    def submit(self, step: int, tree: Dict, persist: bool):
        with self._cond:
            if self._stopped:
                raise RuntimeError("checkpoint stager is stopped")
            if self._pending is not None:
                # Only memory-only saves ever land here (persist dispatch
                # waits for idle first — see _dispatch_save), so dropping
                # the older pending entry cannot lose a disk save or
                # desynchronize the cross-rank persist barrier.
                old_step, _, old_persist = self._pending
                persist = persist or old_persist
                logger.warning(
                    "checkpoint staging of step %s superseded by step %s "
                    "(saves arriving faster than the drain)",
                    old_step, step,
                )
            self._pending = (step, tree, persist)
            self._cond.notify_all()

    def _run(self):
        while True:
            with self._cond:
                while self._pending is None and not self._stopped:
                    self._cond.wait()
                if self._pending is None:
                    return
                step, tree, persist = self._pending
                self._pending = None
                self._inflight = step
            ok = False
            try:
                ok = bool(self._process(step, tree, persist))
            except Exception:  # noqa: BLE001 — staging must not die
                logger.error(
                    "checkpoint staging failed at step %s", step,
                    exc_info=True,
                )
            # Staged, skipped or failed: this thread keeps nothing of the
            # snapshot while it waits for the next submit.
            del tree
            with self._cond:
                self._inflight = None
                self._last_ok = ok
                if not ok:
                    self._failed_sticky = True
                self._cond.notify_all()

    def wait(self, timeout: float = 300.0) -> bool:
        """Drain everything submitted so far; True iff the last staging
        that ran succeeded (or none ever ran)."""
        deadline = time.time() + timeout
        with self._cond:
            while self._pending is not None or self._inflight is not None:
                remaining = deadline - time.time()
                if remaining <= 0:
                    return False
                self._cond.wait(remaining)
            return self._last_ok

    def stop(self, timeout: float = 60.0):
        with self._cond:
            self._stopped = True
            self._cond.notify_all()
        self._thread.join(timeout)


def _assemble(entries: List[_ShardEntry], key: str = "") -> np.ndarray:
    """Paste shard entries into the global array; refuse partial coverage
    (an uncovered region must never silently restore as garbage)."""
    first = entries[0]
    if first.global_shape is None or first.index is None:
        return first.data
    out = np.zeros(first.global_shape, dtype=first.data.dtype)
    covered = 0
    seen = set()
    for e in entries:
        slices = tuple(slice(a, b) for a, b in e.index)
        out[slices if slices else ...] = e.data
        if e.index not in seen:  # GSPMD shards tile regularly; no overlaps
            seen.add(e.index)
            covered += int(np.prod([b - a for a, b in e.index] or [1]))
    total = int(np.prod(first.global_shape or (1,)))
    if covered < total:
        raise ValueError(
            f"incomplete checkpoint for {key!r}: shards cover {covered} of "
            f"{total} elements (missing shard files or foreign-host shm)"
        )
    return out


def _upload_copies(sharding) -> bool:
    """Whether ``device_put`` onto this sharding's devices copies the host
    bytes into device memory.  A TPU's upload always lands in HBM.  The CPU
    backend may alias an aligned host buffer instead, and a donating step
    then frees memory it never owned (the crash recorded in
    ``shm_handler.load_state_dict``): there a view is copied first."""
    return all(d.platform != "cpu" for d in sharding.device_set)


def _upload(arrays: List[np.ndarray], targets: List[Any]) -> List[jax.Array]:
    """Start the host→device transfer of each array onto its target (a
    sharding or a device); returns before the bytes have landed."""
    return jax.device_put(arrays, targets)


def _saved_shards(entries: List[_ShardEntry], sharding):
    """``[(device, data)]`` when every shard the target sharding places on
    this process's devices was saved with exactly that shard's bounds (on
    one chip: one entry covering the whole array), else None.  Decided from
    the saved bounds against the wanted bounds, never from an option."""
    shape = entries[0].global_shape
    if shape is None or not sharding.is_fully_addressable:
        # A sharding that spans processes stays with _assemble: its
        # coverage check is what sends a multi-host shm restore to storage.
        return None
    by_bounds = {e.index: e for e in entries if e.global_shape == shape}
    shards = []
    for device, index in sharding.addressable_devices_indices_map(
        shape
    ).items():
        bounds = _slices_to_bounds(index, shape)
        entry = by_bounds.get(bounds)
        if entry is None:
            return None
        # The staged copy of a 0-d array is 1-d (np.ascontiguousarray).
        shards.append((device, entry.data.reshape([b - a for a, b in bounds])))
    return shards


def _place_leaf(entries: List[_ShardEntry], key: str, sharding):
    """One leaf of the shm block → ``(value, "direct" | "assembled")``.
    The entries' data are views into the block.

    Direct: the saved shards are the wanted shards, so each is uploaded as
    it lies (no ``_assemble`` copy of the array onto itself).  Anything
    else — layout changed across the restart, partial coverage, entries
    without bounds, a leaf that stays on the host — goes through
    ``_assemble`` and its coverage check, which pastes into owned memory."""
    shards = None if sharding is None else _saved_shards(entries, sharding)
    if shards is None:
        arr = _assemble(entries, key)
        if arr is entries[0].data:  # an entry without bounds, as it lies
            arr = arr.copy()
        if sharding is None:
            return arr, "assembled"
        return _upload([arr], [sharding])[0], "assembled"
    datas = [data for _, data in shards]
    if not _upload_copies(sharding):
        datas = [d.copy() for d in datas]
    if len(shards) == 1:
        return _upload(datas, [sharding])[0], "direct"
    return jax.make_array_from_single_device_arrays(
        entries[0].global_shape, sharding,
        _upload(datas, [device for device, _ in shards]),
    ), "direct"


def _flatten_target(abstract_state, shardings=None):
    """``(flat, treedef, targets)`` of the tree a restore fills: the leaves
    with their key paths, and for each the sharding its array is uploaded
    to (``shardings`` if given, else the leaf's own) or None for a leaf
    that stays on the host."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_state)
    if shardings is not None:
        targets = jax.tree_util.tree_leaves(shardings)
        assert len(targets) == len(flat), (
            "shardings tree does not match state tree"
        )
    else:
        targets = [
            leaf.sharding if isinstance(leaf, jax.Array) else None
            for _, leaf in flat
        ]
    return flat, treedef, targets


def host_tree_to_state(
    host: Dict[Tuple, Any],
    abstract_state,
    shardings=None,
):
    """Rebuild a pytree from saved entries, resharding to `shardings`.

    `abstract_state` provides the treedef + leaf key paths (e.g. the freshly
    initialized TrainState); function-valued leaves survive untouched.
    """
    # Group saved shard entries by leaf key.
    grouped: Dict[str, List[_ShardEntry]] = {}
    objects: Dict[str, Any] = {}
    for (key, idx), value in host.items():
        if isinstance(value, _ShardEntry):
            grouped.setdefault(key, []).append(value)
        else:
            objects[key] = value

    flat, treedef, targets = _flatten_target(abstract_state, shardings)
    leaves = []
    # Batch ALL host→device uploads into one device_put call at the end:
    # jax pipelines the transfers (per-leaf puts each pay dispatch
    # latency and serialize the DMA streams).
    puts: List[Tuple[int, np.ndarray, Any]] = []
    for i, (path, leaf) in enumerate(flat):
        key = jax.tree_util.keystr(path)
        if key in grouped:
            arr = _assemble(grouped[key], key)
            if targets[i] is not None:
                puts.append((i, arr, targets[i]))
                leaves.append(None)
            else:
                leaves.append(arr)
        elif key in objects:
            leaves.append(objects[key])
        else:
            leaves.append(leaf)  # not in checkpoint (e.g. function leaf)
    if puts:
        uploaded = jax.device_put(
            [a for _, a, _ in puts], [s for _, _, s in puts]
        )
        for (i, _, _), value in zip(puts, uploaded):
            leaves[i] = value
    return jax.tree_util.tree_unflatten(treedef, leaves)


class CheckpointEngine:
    """Stages state into shm and coordinates the agent-side saver.

    ``sync_fn``: optional cross-process barrier (master kv-store) ensuring
    every rank staged the same step before the SAVE event is queued —
    reference's all-rank-ready allreduce (``engine.py:52-91``).
    """

    def __init__(
        self,
        checkpoint_dir: str,
        storage: Optional[CheckpointStorage] = None,
        local_shard_id: int = 0,
        local_shard_num: int = 1,
        global_shard_num: int = 1,
        node_rank: int = 0,
        sync_fn: Optional[Callable[[int], bool]] = None,
        start_saver: bool = False,
        deletion_strategy=None,
    ):
        self.checkpoint_dir = checkpoint_dir
        self.storage = storage or PosixDiskStorage()
        self._local_shard_id = local_shard_id
        self._node_rank = node_rank
        self._global_shard_num = global_shard_num
        self._sync_fn = sync_fn
        if start_saver:
            # Single-process mode (no agent): host the saver in-process.
            from dlrover_tpu.checkpoint.ckpt_saver import AsyncCheckpointSaver

            AsyncCheckpointSaver.start_async_saving_ckpt()
        self._factory_queue = SharedQueue(name=FACTORY_QUEUE, create=False)
        self._factory_queue.put(
            SaverConfig(
                checkpoint_dir=checkpoint_dir,
                storage_meta=self.storage.get_class_meta(),
                local_shard_num=local_shard_num,
                global_shard_num=global_shard_num,
                node_rank=node_rank,
                deletion_strategy=_strategy_meta(deletion_strategy),
            )
        )
        from dlrover_tpu.checkpoint.shm_handler import job_uid_for

        uid = job_uid_for(checkpoint_dir)
        self._shm_handler = SharedMemoryHandler(
            shard_id=local_shard_id, job_uid=uid
        )
        self._shm_lock = SharedLock(
            name=f"{SHM_LOCK}_{uid}_{local_shard_id}"
        )
        self._event_queue = SharedQueue(
            name=f"{EVENT_QUEUE}_{uid}", create=False
        )
        self._last_queued_step: Optional[int] = None
        self.last_restore: Dict[str, Any] = {}
        self.last_save: Dict[str, Any] = {}
        self._snapshot = _DeviceSnapshot()
        self._stager = _AsyncStager(self._stage_to_shm)

    # -- save -----------------------------------------------------------
    def _stage_to_shm(self, step: int, tree: Dict, persist: bool) -> bool:
        """Stager-thread body: one pipeline from the chip into the agent's
        shm block (``shm_handler.save_state_dict``: each shard is
        checksummed and copied while the next is still arriving), under
        ``_shm_lock`` from the first byte written to the last, and (for
        persists) the SAVE event once every rank staged this step.

        ``last_save`` and the end of the ``ckpt_stage`` span say what it
        took: ``bytes``, ``leaves``, ``drain_s`` (until the last shard had
        left the chip; the log line's ``drain``), ``tail_s`` (from there
        until shm holds the step; the log line's ``memcpy``) and
        ``overlap_s``, the checksum + copy seconds that ran before the last
        arrival: ``overlap_s / (overlap_s + tail_s)`` is near 1 where the
        drain hid the host's work and 0 where the two ran one after the
        other."""
        from dlrover_tpu.telemetry.spans import span

        with span("ckpt_stage", step=step) as extra:
            t0 = time.monotonic()
            if not self._shm_lock.acquire(timeout=60):
                logger.warning(
                    "shm lock busy; skipping save at step %s", step
                )
                extra["ok"] = False
                return False
            lock_wait = time.monotonic() - t0
            try:
                took = self._shm_handler.save_state_dict(step, tree)
            finally:
                self._shm_lock.release()
            took["drain_s"] += lock_wait  # the transfers ran meanwhile
            extra.update(took)
        self.last_save = dict(took, step=step)
        logger.info(
            "step %s staged to shm (drain %.3fs, memcpy %.3fs, all "
            "off the training thread)",
            step, took["drain_s"], took["tail_s"],
        )
        if persist:
            if self._sync_fn is not None and not self._sync_fn(step):
                logger.warning(
                    "step %s: rank sync failed; not persisting", step
                )
                return False
            if self._local_shard_id == 0:
                self._event_queue.put(
                    CheckpointEvent(CheckpointEventType.SAVE, step=step)
                )
        return True

    def _dispatch_save(self, step: int, state, persist: bool) -> bool:
        """The only work on the training thread: device-side snapshot
        (donation guard) + async D2H enqueue — milliseconds, not the
        transfer time.  Reference economics: the torch saver's ~0.5 s
        blocking time is its GPU→pinned-shm memcpy
        (``ckpt_saver.py:517``); ours is an HBM→HBM copy dispatch.

        HBM backpressure: at most ONE snapshot is ever alive, and less
        than one for most of its drain: the pipeline drops each shard's
        device copy as it is staged, and the stager keeps nothing once a
        save is staged, skipped or failed.  A memory-only save arriving
        while the previous drain is in flight is skipped *without taking a
        snapshot* (shm would be overwritten by the next save anyway).  A
        PERSIST save instead waits for the stager to go idle — this bounds
        HBM and, critically, guarantees every rank processes the identical
        sequence of persist steps, so the cross-rank ``sync_fn`` barrier
        can never see mismatched steps.

        Returns False when this save was skipped OR when a *previous*
        async staging failed (sticky — dispatch itself cannot know its
        own outcome yet)."""
        prev_failed = self._stager.consume_failure()
        if prev_failed:
            logger.warning(
                "a previous async checkpoint staging FAILED; reporting "
                "degradation on this save (step %s)", step,
            )
        if self._stager.busy():
            if not persist:
                logger.info(
                    "step %s memory save skipped: previous drain still "
                    "in flight", step,
                )
                return False
            # Persist must not be dropped: block until the drain frees
            # (bounded by one drain time — the backpressure is the cost
            # of never losing a disk save).  A wedged drain (hung
            # transfer / shm lock) must FAIL this save: snapshotting on
            # top of it would break the at-most-one-snapshot HBM bound and
            # let ranks stage diverging persist-step sequences, wedging
            # the cross-rank sync barrier.
            if not self._stager.wait() and self._stager.busy():
                # wait() also returns False when the drain FINISHED but the
                # last staging failed — that case is already surfaced via
                # consume_failure() and the stager is idle, so proceeding is
                # safe.  Only a still-busy stager means a genuine wedge.
                logger.error(
                    "step %s persist save ABORTED: previous drain did not "
                    "finish within its timeout", step,
                )
                return False
        t0 = time.time()
        snap = self._snapshot.take(state)
        self._stager.submit(step, begin_host_transfer(snap), persist)
        del snap  # the stager's tree holds the only references now
        logger.info(
            "step %s save dispatched in %.1f ms (drain continues in "
            "background)", step, (time.time() - t0) * 1e3,
        )
        return not prev_failed

    def save_to_memory(self, step: int, state, block: bool = False) -> bool:
        """Non-blocking by default: snapshot + async drain; the training
        thread only pays the dispatch cost.  ``block=True`` restores the
        old synchronous contract (wait until shm actually holds step)."""
        if not self._dispatch_save(step, state, persist=False):
            return False
        return self._stager.wait() if block else True

    def save_to_storage(self, step: int, state, block: bool = False) -> bool:
        ok = self._dispatch_save(step, state, persist=True)
        # wait_saver_idle tracks the DISK commit for this step even though
        # the SAVE event is queued from the stager thread later.
        self._last_queued_step = step
        if block:
            return self._stager.wait() and ok
        return ok

    # -- load -----------------------------------------------------------
    def load(self, abstract_state, shardings=None, step: Optional[int] = None):
        """Verified restore ladder: shm (crc-checked) → tracker step →
        newest step whose manifest fully verifies.  Returns (step, state)
        or (None, abstract_state) when nothing restorable exists.

        ``step`` pins the restore to a consensus-agreed step (recovery
        consensus, docs/CHECKPOINT.md): shm is only used when it holds
        exactly that step, and storage restore targets it first.

        ``last_restore`` says afterwards where the state came from and how
        its leaves were placed (the ``restore`` span reports it)."""
        self._count_restore("none", 0, 0, 0)
        # An in-flight async staging must land before we read shm.
        if not self._stager.wait():
            logger.warning(
                "async staging did not finish cleanly before restore: "
                "shm may hold an OLDER step than the last save dispatched"
            )
        restored = self._restore_from_memory(abstract_state, shardings, step)
        if restored is not None:
            return restored
        loaded = self._load_from_storage(step)
        if loaded is None:
            return None, abstract_state
        step, host = loaded
        state = host_tree_to_state(host, abstract_state, shardings)
        wanted = {
            jax.tree_util.keystr(path)
            for path, _ in _flatten_target(abstract_state)[0]
        }
        saved = [
            (key, entry) for (key, _), entry in host.items()
            if isinstance(entry, _ShardEntry) and key in wanted
        ]
        self._count_restore(
            "storage", sum(e.data.nbytes for _, e in saved),
            0, len({key for key, _ in saved}),
        )
        return step, state

    def _count_restore(self, source, nbytes, direct, assembled):
        from dlrover_tpu.checkpoint import integrity

        self.last_restore = {
            "source": source, "bytes": int(nbytes),
            "direct_leaves": direct, "assembled_leaves": assembled,
        }
        leaves = integrity._metric("dlrover_ckpt_restore_leaves_total")
        leaves.inc(direct, path="direct")
        leaves.inc(assembled, path="assembled")

    def _restore_from_memory(self, abstract_state, shardings, step):
        """The shm rung: ``(step, state)`` or None (→ storage).

        Every tensor is crc-checked where it lies in the mapped block and
        uploaded from there, leaf by leaf, the check of the next leaves
        running while the previous ones upload; nothing state-sized is
        allocated on the host.  A tensor's bytes reach ``device_put`` only
        after its own check passed; one mismatch anywhere refuses the
        whole block and drops the leaves already uploaded.

        ``_shm_lock`` is held until the uploads have LANDED
        (``jax.block_until_ready``), not only while bytes are copied out:
        the uploads read from views that alias the block, and the next
        save rewrites it."""
        try:
            self._shm_lock.acquire()
        except Exception:  # noqa: BLE001 — shm gone is a normal cold start
            return None
        # Deliberate hold: _shm_lock is the cross-process mutex whose
        # entire purpose is to cover this read — releasing it before the
        # uploads land would let the saver rewrite the bytes in flight.
        tensors = None
        try:
            try:
                opened = self._shm_handler.verified_views()
            except Exception:  # noqa: BLE001 — no block yet: cold start
                return None
            if opened is None:
                return None
            meta, objects, tensors = opened
            if step is not None and meta.step != step:
                logger.info(
                    "shm holds step %s but the world agreed on step %s; "
                    "skipping the in-memory restore", meta.step, step,
                )
                return None
            state = self._place_verified(
                meta, objects, tensors, abstract_state, shardings
            )
        except ShmCorruptError:
            return None  # verdict emitted; the uploaded leaves die here
        except ValueError:
            # Local shm doesn't cover the full state (sharding changed
            # across the restart, or multi-host shm) → storage has it all.
            logger.info(
                "shm restore incomplete for this layout; falling back "
                "to storage"
            )
            return None
        finally:
            if tensors is not None:
                tensors.close()
            self._shm_lock.release()
        return meta.step, state

    def _place_verified(
        self, meta, objects, tensors, abstract_state, shardings
    ):
        """Fill the target tree from the verified stream: a leaf is placed
        as soon as its last saved shard is verified, and the tree is
        returned once every upload has landed."""
        flat, treedef, targets = _flatten_target(abstract_state, shardings)
        slot = {
            jax.tree_util.keystr(path): i for i, (path, _) in enumerate(flat)
        }
        leaves = [leaf for _, leaf in flat]
        for (key, _), value in objects.items():
            if key in slot:
                leaves[slot[key]] = value
        missing = collections.Counter(t.path[0] for t in meta.tensors)
        parts: Dict[str, List[_ShardEntry]] = {}
        counts = {"direct": 0, "assembled": 0}
        nbytes = 0
        for (key, _), entry in tensors:
            missing[key] -= 1
            if key not in slot:
                continue
            parts.setdefault(key, []).append(entry)
            if missing[key]:
                continue
            entries = parts.pop(key)
            i = slot[key]
            leaves[i], how = _place_leaf(entries, key, targets[i])
            counts[how] += 1
            nbytes += sum(e.data.nbytes for e in entries)
        jax.block_until_ready(leaves)  # dlr: lock-held
        self._count_restore(
            "shm", nbytes, counts["direct"], counts["assembled"]
        )
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def _load_from_storage(self, step: Optional[int] = None):
        """Walk the restore ladder: requested/tracker step first, then
        every older (and manifest-sealed newer) step newest-first.  Each
        candidate is digest-verified BEFORE its bytes are deserialized or
        uploaded; corrupt steps are quarantined and never retried."""
        from dlrover_tpu.checkpoint import integrity

        storage, root = self.storage, self.checkpoint_dir
        tracker = read_tracker(storage, root)
        candidates = integrity.ladder_candidates(storage, root)
        if step is not None:
            candidates = [step] + [c for c in candidates if c != step]
        first = candidates[0] if candidates else None
        for cand in candidates:
            res = integrity.verify_step(storage, root, cand)
            if res.status == "corrupt":
                integrity.quarantine_step(storage, root, cand, res.reason)
                continue
            if res.status == "missing":
                continue
            if res.status == "legacy" and (
                tracker is None or cand > tracker
            ):
                # No manifest and not covered by the tracker: either an
                # in-flight save (newer than tracker) or an uncommitted
                # orphan — not restorable, but not evidence of rot.
                continue
            try:
                loaded = load_storage_host_tree(storage, root, cand)
            except (IOError, pickle.UnpicklingError, EOFError) as e:
                integrity.quarantine_step(
                    storage, root, cand, f"load failed: {e}"
                )
                continue
            if loaded is None:
                continue
            if cand != first:
                integrity._metric(
                    "dlrover_ckpt_restore_fallback_total"
                ).inc()
                logger.warning(
                    "restore ladder fell back from step %s to verified "
                    "step %s", first, cand,
                )
            return loaded
        return None

    def wait_staging(self, timeout: float = 300.0) -> bool:
        """Block until every async save dispatched so far reached shm."""
        return self._stager.wait(timeout)

    def wait_saver_idle(self, timeout: float = 60.0) -> bool:
        """Block until the last queued DISK save is *committed* (tracker
        flipped) — an empty event queue only means the saver popped the
        event, not that the persist finished."""
        target = self._last_queued_step
        if target is None:
            return True
        deadline = time.time() + timeout  # ONE budget for both phases
        if not self._stager.wait(timeout):
            return False
        while True:
            # At least one tracker read even if staging ate the budget —
            # the commit may have landed during the drain.
            committed = read_tracker(self.storage, self.checkpoint_dir)
            if committed is not None and committed >= target:
                return True
            if time.time() >= deadline:
                return False
            time.sleep(0.05)

    def close(self):
        self._stager.stop()
        self._shm_handler.close()
        self._shm_lock.close()
        self._event_queue.close()
        self._factory_queue.close()

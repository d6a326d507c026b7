"""Shared-memory staging buffer for Flash Checkpoint.

Reference parity: ``dlrover/python/elastic_agent/torch/ckpt_saver.py:209``
(SharedMemoryHandler: TensorMeta dict + one shm buffer per local shard).

TPU twist: what lands in shm are the *host copies of this process's
addressable array shards* (`jax.Array.addressable_shards`) plus their global
layout (shape/dtype/index), so a restore can paste shards back under a
different mesh — the reference's FSDP flat-ckpt reshard
(``atorch/utils/fsdp_save_util.py``) done the JAX way.

Buffer layout: ``[8B meta_len][pickled meta, padded][tensor bytes ...]``.
A save is one pipeline (:meth:`SharedMemoryHandler.save_state_dict`): each
tensor is checksummed and copied into its place while the next is still
arriving from the chip.  It zeroes the header first and writes it last, so
a block is either whole or one that no reader opens.  The step is also
mirrored in a SharedDict so the agent can inspect it without touching the
buffer while a write is in flight.
"""

import dataclasses
import math
import os
import pickle
import struct
import time
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Iterator, List, Optional, Tuple

import numpy as np

from dlrover_tpu.common.faults import fault_point

from dlrover_tpu.common.log import logger
from dlrover_tpu.common.multi_process import (
    SharedDict,
    SharedLock,
    SharedMemory,
    create_shared_memory,
)

_HEADER = struct.Struct("<Q")
# Threads that checksum tensors (and, in a save, copy them) beside the
# transfers of a restore or a save: zlib.crc32 and np.copyto release the
# GIL, and four keep ahead of one chip's host link.
_VERIFY_WORKERS = 4
# What every crc32 reads in the meta that is pickled to size the block,
# before a byte has arrived: no crc32 pickles wider, so the real meta fits
# the place reserved for it.
_CRC_WIDEST = 0xFFFFFFFF


@dataclasses.dataclass
class TensorMeta:
    """One array shard inside the shm buffer."""

    path: Tuple[Any, ...]  # pytree key path
    shape: Tuple[int, ...]  # local (shard) shape
    dtype: str
    offset: int
    nbytes: int
    global_shape: Optional[Tuple[int, ...]] = None
    index: Optional[Tuple[Tuple[int, Optional[int]], ...]] = None
    # (start, stop) per dim of this shard within the global array
    crc32: Optional[int] = None  # digest of the tensor bytes as staged


@dataclasses.dataclass
class ShmMeta:
    step: int
    tensors: List[TensorMeta]
    objects: bytes  # pickled dict of non-array leaves {path: value}
    total_bytes: int
    created: float = 0.0
    objects_crc32: Optional[int] = None


class ShmCorruptError(Exception):
    """A tensor in the shm block failed its crc32 (verdict already out)."""


def _leaf_entries(host_tree: Dict[Tuple, Any]):
    """Split {path: leaf} into array entries and plain-object entries."""
    arrays, objects = {}, {}
    for path, leaf in host_tree.items():
        if isinstance(leaf, _ShardEntry):
            arrays[path] = leaf
        elif isinstance(leaf, np.ndarray):
            arrays[path] = _ShardEntry(leaf, None, None)
        else:
            objects[path] = leaf
    return arrays, objects


@dataclasses.dataclass
class _ShardEntry:
    """Host ndarray + its placement in the global array (None = replicated).
    On its way into a save ``data`` may still be in transit from the chip:
    anything with a shape and a dtype whose ``np.asarray`` returns the bytes
    once they have arrived."""

    data: np.ndarray
    global_shape: Optional[Tuple[int, ...]]
    index: Optional[Tuple[Tuple[int, Optional[int]], ...]]


def _nbytes(data) -> int:
    return math.prod(data.shape) * np.dtype(data.dtype).itemsize


def largest_first(arrays: Dict[Tuple, _ShardEntry]) -> List[Tuple]:
    """The order in which a save takes its tensors off the chip (their order
    in the block stays the tree's): largest first, so that what remains
    after the last arrival is a small tensor's checksum and copy.  Whoever
    starts the transfers starts them in this order."""
    return sorted(arrays, key=lambda path: -_nbytes(arrays[path].data))


def _crc_matches(t: TensorMeta, data: np.ndarray) -> bool:
    """The one crc check of both readers: the tensor's bytes, as they lie
    in ``data``, against the digest staged with them."""
    expected = getattr(t, "crc32", None)
    if expected is None or not t.nbytes:
        return True
    return zlib.crc32(data.reshape(-1).view(np.uint8)) == expected


def _default_job_uid() -> str:
    # Must match the socket namespacing (multi_process._sock_path) so the
    # shm block and the lock guarding it always belong to the same job.
    return os.environ.get("DLROVER_JOB_UID", "local")


def job_uid_for(checkpoint_dir: str) -> str:
    """Job uid scoping the shm namespace.  Without an explicit job uid the
    checkpoint dir is the identity — otherwise two unrelated local runs on
    one host would attach the same 'local' segment and one could "resume"
    from the other's in-memory checkpoint."""
    explicit = os.environ.get("DLROVER_JOB_UID")
    if explicit:
        return explicit
    import hashlib

    digest = hashlib.md5(
        os.path.abspath(checkpoint_dir).encode()
    ).hexdigest()[:10]
    return f"local_{digest}"


class SharedMemoryHandler:
    """Owns one shm block + its meta dict; one per local shard (process)."""

    def __init__(self, shard_id: int = 0, job_uid: Optional[str] = None):
        self._shard_id = shard_id
        job_uid = job_uid or _default_job_uid()
        self._shm_name = f"dlrover_tpu_ckpt_{job_uid}_{shard_id}"
        self.shared_memory: Optional[SharedMemory] = None
        self._attached_gen = -1
        self.meta_dict = SharedDict(
            name=f"ckpt_meta_{job_uid}_{shard_id}", create=False
        )

    # The process that *creates* the control-plane ends (the agent) calls
    # create_master(); trainers attach with the default constructor.
    @classmethod
    def create_master(cls, shard_id: int = 0, job_uid: Optional[str] = None):
        handler = cls.__new__(cls)
        handler._shard_id = shard_id
        job_uid = job_uid or _default_job_uid()
        handler._shm_name = f"dlrover_tpu_ckpt_{job_uid}_{shard_id}"
        handler.shared_memory = None
        handler._attached_gen = -1
        handler.meta_dict = SharedDict(
            name=f"ckpt_meta_{job_uid}_{shard_id}", create=True
        )
        return handler

    # -- write path (trainer) -------------------------------------------
    def save_state_dict(
        self, step: int, tree: Dict[Tuple, Any]
    ) -> Dict[str, Any]:
        """Stage a ``{path: _ShardEntry | ndarray | obj}`` dict into shm as
        one pipeline; returns what it took: ``bytes``, ``leaves``,
        ``drain_s`` (until the last tensor had arrived), ``tail_s`` (from
        there until shm holds the step) and ``overlap_s`` (checksum + copy
        seconds that ran before the last arrival).

        Shapes and dtypes place every tensor, in the order of ``tree``,
        before a byte has arrived.  Then, :func:`largest_first`: wait for
        one tensor (``np.asarray``; a host array has arrived already), hand
        it to a small pool that takes its crc32 from the host copy and
        copies it into place, and wait for the next meanwhile.  ``tree`` is
        CONSUMED: it is emptied at once and each entry dropped as it is
        staged, and with it the last reference to a device copy.

        The caller holds the shm lock from the first byte to the last.  The
        header is zeroed first and written last, after the meta with every
        crc32, and ``meta_dict``'s ``step`` after that: a save cut short
        anywhere (a kill, a lost transfer, the ``ckpt_stage_cut`` fault
        point) leaves a block that every reader REFUSES (``load_meta`` is
        None: the restore goes to storage), never the previous step and
        never a mixture of two."""
        t_start = time.monotonic()
        arrays, objects = _leaf_entries(tree)
        tree.clear()
        obj_blob = pickle.dumps(objects, protocol=pickle.HIGHEST_PROTOCOL)
        metas: Dict[Tuple, TensorMeta] = {}
        offset = 0
        for path, entry in arrays.items():
            metas[path] = TensorMeta(
                path=path,
                # A 0-d array is staged 1-d, as both readers expect it.
                shape=tuple(entry.data.shape) or (1,),
                dtype=str(np.dtype(entry.data.dtype)),
                offset=offset,
                nbytes=_nbytes(entry.data),
                global_shape=entry.global_shape,
                index=entry.index,
                # Digest rides with the meta so the agent's persist
                # and the flash-restore both verify the shm bytes
                # they read are the bytes the trainer staged.
                crc32=_CRC_WIDEST,
            )
            offset += metas[path].nbytes
        meta = ShmMeta(
            step=step,
            tensors=list(metas.values()),
            objects=obj_blob,
            total_bytes=offset,
            created=time.time(),
            objects_crc32=zlib.crc32(obj_blob),
        )
        # The pickled meta's length varies with the crc32s' values, known
        # only at the end: it gets the room of its widest form and is padded
        # to it (pickle stops at its STOP opcode), so the tensors' base is
        # settled now and readers find it as ever (header + meta_len).
        reserved = len(pickle.dumps(meta, protocol=pickle.HIGHEST_PROTOCOL))
        base = _HEADER.size + reserved
        need = base + offset
        self._ensure_size(need)
        buf = self.shared_memory.buf
        buf[: _HEADER.size] = _HEADER.pack(0)

        def stage(arr: np.ndarray, tmeta: TensorMeta) -> Tuple[float, float]:
            began = time.monotonic()
            src = np.ascontiguousarray(arr).reshape(-1).view(np.uint8)
            tmeta.crc32 = zlib.crc32(src)
            if tmeta.nbytes:
                # Straight into the shm mapping: no tobytes() intermediate.
                np.copyto(
                    np.frombuffer(
                        buf, dtype=np.uint8, count=tmeta.nbytes,
                        offset=base + tmeta.offset,
                    ),
                    src,
                )
            return began, time.monotonic()

        pool = ThreadPoolExecutor(
            _VERIFY_WORKERS, thread_name_prefix="ckpt-stage"
        )
        try:
            staging = []
            for k, path in enumerate(largest_first(arrays)):
                entry = arrays.pop(path)
                arr = np.asarray(entry.data)  # returns on arrival
                staging.append(pool.submit(stage, arr, metas[path]))
                del entry, arr
                fault_point(
                    "ckpt_stage_cut", step=step, shard=self._shard_id, leaf=k
                )
            arrived = time.monotonic()
            spans = [done.result() for done in staging]
        finally:
            pool.shutdown(wait=True, cancel_futures=True)
        if offset and fault_point(
            "ckpt_shm_corrupt", step=step, shard=self._shard_id
        ):
            # Simulated shm scribble (stray write / DMA corruption): flip
            # one byte in the first tensor so its crc32 no longer matches.
            buf[base] = buf[base] ^ 0xFF
        meta_blob = pickle.dumps(meta, protocol=pickle.HIGHEST_PROTOCOL)
        if len(meta_blob) > reserved:
            raise RuntimeError(
                f"shm meta outgrew its place ({len(meta_blob)} > "
                f"{reserved} bytes)"
            )
        buf[_HEADER.size : base] = meta_blob.ljust(reserved, b"\0")
        buf[: _HEADER.size] = _HEADER.pack(reserved)
        self.meta_dict.update(
            {
                "step": step,
                "total_bytes": need,
                "shm_gen": self._attached_gen,
            }
        )
        return {
            "bytes": offset,
            "leaves": len({path[0] for path in metas}),
            "drain_s": arrived - t_start,
            "tail_s": time.monotonic() - arrived,
            "overlap_s": sum(
                max(0.0, min(ended, arrived) - began)
                for began, ended in spans
            ),
        }

    def _ensure_size(self, need: int):
        if self._attached_gen < 0:
            # First touch in this process: learn the current generation.
            self._attached_gen = int(self.meta_dict.get("shm_gen", 0) or 0)
        if self.shared_memory is None:
            # Attach to any pre-existing block (e.g. a restarted trainer
            # re-joining an agent that kept the buffer alive) so a regrow
            # below goes through the unlink+gen-bump path — otherwise other
            # processes would keep reading the old unlinked inode.
            self.shared_memory = create_shared_memory(
                self._shm_name, create=False
            )
        if self.shared_memory is not None and self.shared_memory.size >= need:
            return
        if self.shared_memory is not None:
            self.shared_memory.close()
            self.shared_memory.unlink()
            # Regrow = new inode under the same name; bump the generation so
            # every other attached process re-maps instead of reading the
            # old unlinked block.
            self._attached_gen += 1
        # 10% headroom so tiny growth (new opt state) doesn't re-alloc.
        self.shared_memory = create_shared_memory(
            self._shm_name, create=True, size=int(need * 1.1) + 4096
        )

    # -- read path (agent saver / restore) -------------------------------
    def attach(self) -> bool:
        gen = int(self.meta_dict.get("shm_gen", 0) or 0)
        if self.shared_memory is not None and gen != self._attached_gen:
            # Writer regrew the block: drop the stale mapping.
            self.shared_memory.close()
            self.shared_memory = None
        if self.shared_memory is None:
            self.shared_memory = create_shared_memory(
                self._shm_name, create=False
            )
            self._attached_gen = gen
        return self.shared_memory is not None

    def load_meta(self) -> Optional[ShmMeta]:
        if not self.attach():
            return None
        buf = self.shared_memory.buf
        (meta_len,) = _HEADER.unpack(bytes(buf[: _HEADER.size]))
        if meta_len == 0 or meta_len > self.shared_memory.size:
            return None
        return pickle.loads(
            bytes(buf[_HEADER.size : _HEADER.size + meta_len])
        )

    def _tensor_base(self) -> int:
        """Offset of the first tensor byte: header + pickled meta."""
        (meta_len,) = _HEADER.unpack(
            bytes(self.shared_memory.buf[: _HEADER.size])
        )
        return _HEADER.size + meta_len

    def load_state_dict(
        self, verify: bool = True
    ) -> Optional[Tuple[int, Dict[Tuple, Any]]]:
        """Return (step, {path: _ShardEntry|obj}) from shm, or None.

        ``verify=True`` (default) checks every tensor's crc32 recorded at
        staging time — a corrupted shm snapshot is REFUSED (returns None,
        so callers fall through to verified storage) rather than handed
        to ``device_put``.

        The arrays are owned copies that outlive the shm lock: the reader
        of the agent's saver, which writes to storage after releasing it.
        The trainer's restore reads through :meth:`verified_views`."""
        meta = self.load_meta()
        if meta is None:
            return None
        base = self._tensor_base()
        if verify and not self._verify_objects(meta):
            return None
        out: Dict[Tuple, Any] = dict(pickle.loads(meta.objects))
        buf = self.shared_memory.buf
        for t in meta.tensors:
            # Restored arrays MUST own their memory: a bytes-backed
            # np.frombuffer view hands jax.device_put an interior pointer
            # into a Python bytes object, and on the CPU backend the
            # zero-copy path + train-step donation then frees/reuses that
            # pointer — glibc heap corruption (SIGSEGV/SIGABRT on the
            # first donated step after every shm restore hit).  A fresh
            # numpy allocation is naturally aligned, writeable, and safe
            # to donate.
            arr = np.empty(t.shape, dtype=np.dtype(t.dtype))
            np.copyto(
                arr.reshape(-1).view(np.uint8),
                np.frombuffer(
                    buf, dtype=np.uint8, count=t.nbytes,
                    offset=base + t.offset,
                ),
            )
            if verify and not _crc_matches(t, arr):
                self._emit_corrupt_verdict(meta.step, t.path)
                return None
            out[t.path] = _ShardEntry(arr, t.global_shape, t.index)
        return meta.step, out

    def verified_views(
        self,
    ) -> Optional[Tuple[ShmMeta, Dict[Tuple, Any], Iterator]]:
        """The restore's reader: ``(meta, objects, tensors)`` or None.

        ``tensors`` yields ``(path, _ShardEntry)`` in the order staged,
        each entry's ``data`` a read-only VIEW into the mapped block whose
        crc32 was checked where it lies — no owned copy of the state.  A
        small pool checks the next tensors while the caller uploads the
        ones already yielded (zlib releases the GIL).  The first mismatch
        emits the ``ckpt_shm_corrupt`` verdict and raises
        :class:`ShmCorruptError`: the caller drops what it built and falls
        through to storage.  The object blob is checked before anything is
        returned.  There is no unverified variant.

        The views alias memory the next save rewrites: the caller holds
        the shm lock until every byte it took has left the block (the
        engine: until ``jax.block_until_ready`` on the uploads), and on a
        backend whose ``device_put`` may alias host memory it copies first
        (see the comment in :meth:`load_state_dict`)."""
        meta = self.load_meta()
        if meta is None or not self._verify_objects(meta):
            return None
        objects: Dict[Tuple, Any] = dict(pickle.loads(meta.objects))
        return meta, objects, self._iter_verified(meta)

    def _iter_verified(self, meta: ShmMeta) -> Iterator:
        base = self._tensor_base()
        buf = self.shared_memory.buf

        def check(t: TensorMeta):
            view = np.frombuffer(
                buf, dtype=np.uint8, count=t.nbytes, offset=base + t.offset
            ).view(np.dtype(t.dtype)).reshape(t.shape)
            view.flags.writeable = False
            # The one view that leaves this module uncopied: the engine
            # uploads it under the shm lock, and copies first wherever
            # device_put may alias host memory (engine._upload_copies).
            return view, _crc_matches(t, view)  # dlr: noqa[DLR001]

        pool = ThreadPoolExecutor(
            _VERIFY_WORKERS, thread_name_prefix="ckpt-crc"
        )
        try:
            for t, done in zip(
                meta.tensors, [pool.submit(check, t) for t in meta.tensors]
            ):
                view, ok = done.result()
                if not ok:
                    self._emit_corrupt_verdict(meta.step, t.path)
                    raise ShmCorruptError(meta.step, t.path)
                yield t.path, _ShardEntry(view, t.global_shape, t.index)
        finally:
            pool.shutdown(wait=True, cancel_futures=True)

    def _verify_objects(self, meta: ShmMeta) -> bool:
        expected = getattr(meta, "objects_crc32", None)
        if expected is None or zlib.crc32(meta.objects) == expected:
            return True
        self._emit_corrupt_verdict(meta.step, "objects")
        return False

    def _emit_corrupt_verdict(self, step: int, what: Any):
        logger.error(
            "shm shard %s: step %s tensor %s failed crc32 verification — "
            "refusing the in-memory restore (storage fallback)",
            self._shard_id, step, what,
        )
        try:
            from dlrover_tpu.telemetry import events as tevents

            tevents.emit(
                "verdict",
                action="ckpt_shm_corrupt",
                step=step,
                shard=self._shard_id,
            )
        except Exception:  # noqa: BLE001 — telemetry must not break load
            pass

    def empty(self) -> bool:
        return self.load_meta() is None

    def close(self, unlink: bool = False):
        if self.shared_memory is not None:
            self.shared_memory.close()
            if unlink:
                self.shared_memory.unlink()
            self.shared_memory = None
        self.meta_dict.close()

"""Checkpoint trust (ISSUE 6): digests + step manifests, quarantine,
the verified restore ladder, retention sparing, shm crc verification,
the kv delta-chain link verification, storage durability primitives,
and the recovery-consensus RPC (report → intersect → max).
"""

import json
import os
import time

import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.checkpoint import integrity
from dlrover_tpu.checkpoint.storage import (
    TRACKER_FILE,
    PosixDiskStorage,
    durable_write,
    fsync_dir,
    read_tracker,
    step_dir,
)
from dlrover_tpu.common import comm
from dlrover_tpu.common.faults import corrupt_file


@pytest.fixture(autouse=True)
def _iso(isolated_ipc):
    """Fresh saver singleton + per-test IPC namespace for the classes
    that touch the flash-checkpoint machinery; harmless for the rest."""
    yield


@pytest.fixture()
def storage():
    return PosixDiskStorage()


def _seal_step(storage, root, step, files=None):
    """Write shard files + a matching manifest for one step dir."""
    files = files or {"shard_0.pkl": b"payload-%d" % step}
    records = []
    for name, blob in files.items():
        storage.write(blob, os.path.join(step_dir(root, step), name))
        records.append(integrity.file_record(name, blob))
    integrity.write_manifest(storage, root, step, records)
    return files


def _set_tracker(storage, root, step):
    durable_write(storage, str(step), os.path.join(root, TRACKER_FILE))


# -- digests ------------------------------------------------------------------


class TestDigests:
    def test_crc32_default(self, monkeypatch):
        monkeypatch.delenv("DLROVER_CKPT_DIGEST", raising=False)
        assert integrity.digest_alg() == "crc32"
        d = integrity.compute_digest(b"hello")
        assert len(d) == 8
        assert d == integrity.compute_digest(b"hello")
        assert d != integrity.compute_digest(b"hellp")

    def test_sha256_opt_in(self, monkeypatch):
        monkeypatch.setenv("DLROVER_CKPT_DIGEST", "sha256")
        assert integrity.digest_alg() == "sha256"
        assert len(integrity.compute_digest(b"hello")) == 64
        # Unknown algs fall back rather than crash the commit path.
        monkeypatch.setenv("DLROVER_CKPT_DIGEST", "md5sum")
        assert integrity.digest_alg() == "crc32"

    def test_file_record_describes_intended_bytes(self):
        rec = integrity.file_record("shard_0.pkl", b"abc")
        assert rec["file"] == "shard_0.pkl"
        assert rec["size"] == 3
        assert rec["digest"] == integrity.compute_digest(b"abc", rec["alg"])


# -- manifest + verify_step ---------------------------------------------------


class TestVerifyStep:
    def test_ok_roundtrip(self, tmp_path, storage):
        root = str(tmp_path)
        _seal_step(storage, root, 5, {"a.pkl": b"aa", "b.pkl": b"bb"})
        res = integrity.verify_step(storage, root, 5)
        assert res.ok and res.usable and res.files == 2
        manifest = integrity.read_manifest(storage, root, 5)
        assert manifest["step"] == 5
        assert [r["file"] for r in manifest["files"]] == ["a.pkl", "b.pkl"]

    def test_missing_dir(self, tmp_path, storage):
        res = integrity.verify_step(storage, str(tmp_path), 9)
        assert res.status == "missing" and not res.usable

    def test_legacy_without_manifest(self, tmp_path, storage):
        root = str(tmp_path)
        storage.write(b"x", os.path.join(step_dir(root, 3), "shard_0.pkl"))
        res = integrity.verify_step(storage, root, 3)
        assert res.status == "legacy" and res.usable and not res.ok

    def test_unreadable_manifest_is_corrupt_not_legacy(
        self, tmp_path, storage
    ):
        root = str(tmp_path)
        _seal_step(storage, root, 3)
        storage.write(b"\x00not json", integrity.manifest_path(root, 3))
        assert integrity.verify_step(storage, root, 3).status == "corrupt"
        # Valid JSON of the wrong shape is corrupt too.
        storage.write(b"[1, 2]", integrity.manifest_path(root, 3))
        assert integrity.read_manifest(storage, root, 3) == {}

    def test_bitflip_caught_by_digest(self, tmp_path, storage):
        root = str(tmp_path)
        _seal_step(storage, root, 4, {"shard_0.pkl": b"A" * 64})
        assert corrupt_file(
            os.path.join(step_dir(root, 4), "shard_0.pkl"), mode="bitflip"
        )
        res = integrity.verify_step(storage, root, 4)
        assert res.status == "corrupt" and "digest" in res.reason

    def test_truncation_caught_by_size(self, tmp_path, storage):
        root = str(tmp_path)
        _seal_step(storage, root, 4, {"shard_0.pkl": b"A" * 64})
        assert corrupt_file(
            os.path.join(step_dir(root, 4), "shard_0.pkl"), mode="truncate"
        )
        res = integrity.verify_step(storage, root, 4)
        assert res.status == "corrupt" and "size" in res.reason

    def test_missing_listed_file(self, tmp_path, storage):
        root = str(tmp_path)
        _seal_step(storage, root, 4, {"a.pkl": b"a", "b.pkl": b"b"})
        storage.remove(os.path.join(step_dir(root, 4), "b.pkl"))
        res = integrity.verify_step(storage, root, 4)
        assert res.status == "corrupt" and "missing" in res.reason

    def test_shallow_verify_checks_existence_only(self, tmp_path, storage):
        root = str(tmp_path)
        _seal_step(storage, root, 4, {"shard_0.pkl": b"A" * 64})
        corrupt_file(
            os.path.join(step_dir(root, 4), "shard_0.pkl"), mode="bitflip"
        )
        # deep=False (the retention guard) only proves the files exist.
        assert integrity.verify_step(storage, root, 4, deep=False).ok
        storage.remove(os.path.join(step_dir(root, 4), "shard_0.pkl"))
        assert (
            integrity.verify_step(storage, root, 4, deep=False).status
            == "corrupt"
        )


# -- quarantine ---------------------------------------------------------------


class TestQuarantine:
    def test_rename_and_listing(self, tmp_path, storage):
        from dlrover_tpu.checkpoint.deletion import list_step_dirs

        root = str(tmp_path)
        _seal_step(storage, root, 7)
        assert integrity.quarantine_step(storage, root, 7, "test rot")
        assert not storage.exists(step_dir(root, 7))
        assert storage.exists(step_dir(root, 7) + ".corrupt")
        assert integrity.list_quarantined(storage, root) == [
            "checkpoint-7.corrupt"
        ]
        # Quarantined dirs never count as restorable steps.
        assert list_step_dirs(storage, root) == []

    def test_requarantine_drops_the_newer_bad_copy(self, tmp_path, storage):
        root = str(tmp_path)
        _seal_step(storage, root, 7)
        integrity.quarantine_step(storage, root, 7, "first")
        _seal_step(storage, root, 7)  # a retry re-created the step dir
        integrity.quarantine_step(storage, root, 7, "second")
        assert not storage.exists(step_dir(root, 7))
        assert storage.exists(step_dir(root, 7) + ".corrupt")

    def test_already_quarantined_counts_as_done(self, tmp_path, storage):
        root = str(tmp_path)
        _seal_step(storage, root, 7)
        storage.move(step_dir(root, 7), step_dir(root, 7) + ".corrupt")
        assert integrity.quarantine_step(storage, root, 7, "race loser")


# -- the ladder ---------------------------------------------------------------


class TestLadder:
    def test_candidates_newest_first_matches_consensus_order(
        self, tmp_path, storage
    ):
        root = str(tmp_path)
        for s in (1, 5, 9):
            _seal_step(storage, root, s)
        assert integrity.ladder_candidates(storage, root) == [9, 5, 1]
        # The tracker does NOT reorder: a sealed step above it must win
        # (ckpt_stale_tracker), and the solo ladder must rank the same
        # disk exactly like locally_verified_steps does for consensus.
        _set_tracker(storage, root, 5)
        assert integrity.ladder_candidates(storage, root) == [9, 5, 1]
        assert integrity.locally_verified_steps(storage, root) == [9, 5, 1]

    def test_locally_verified_steps(self, tmp_path, storage):
        root = str(tmp_path)
        _seal_step(storage, root, 2)
        _seal_step(storage, root, 6)
        # legacy below tracker: restorable; legacy above: in-flight, not.
        storage.write(b"x", os.path.join(step_dir(root, 1), "shard_0.pkl"))
        storage.write(b"x", os.path.join(step_dir(root, 8), "shard_0.pkl"))
        # corrupt: excluded.
        _seal_step(storage, root, 4, {"shard_0.pkl": b"B" * 32})
        corrupt_file(
            os.path.join(step_dir(root, 4), "shard_0.pkl"), mode="bitflip"
        )
        _set_tracker(storage, root, 6)
        assert integrity.locally_verified_steps(storage, root) == [6, 2, 1]
        # A verified manifest ABOVE the tracker is restorable (lost flip).
        _seal_step(storage, root, 9)
        assert integrity.locally_verified_steps(storage, root) == [
            9, 6, 2, 1,
        ]
        # quarantine=True also renames what it rejects.
        integrity.locally_verified_steps(storage, root, quarantine=True)
        assert storage.exists(step_dir(root, 4) + ".corrupt")

    def test_no_tracker_excludes_legacy(self, tmp_path, storage):
        root = str(tmp_path)
        storage.write(b"x", os.path.join(step_dir(root, 1), "shard_0.pkl"))
        _seal_step(storage, root, 3)
        assert integrity.locally_verified_steps(storage, root) == [3]


# -- retention sparing --------------------------------------------------------


class TestRetentionSparing:
    def test_newest_verified_step_survives_keep_n(self, tmp_path, storage):
        from dlrover_tpu.checkpoint.deletion import (
            KeepLatestStepStrategy,
            apply_deletion_strategy,
        )

        root = str(tmp_path)
        _seal_step(storage, root, 1)
        _seal_step(storage, root, 2)
        # Step 3 committed but manifest-less (legacy): keep-1 nominates
        # 1 and 2, but 2 is the newest VERIFIED step — spared.
        storage.write(b"x", os.path.join(step_dir(root, 3), "shard_0.pkl"))
        victims = apply_deletion_strategy(
            storage, root, 3, KeepLatestStepStrategy(max_to_keep=1)
        )
        assert victims == [1]
        assert not storage.exists(step_dir(root, 1))
        assert storage.exists(step_dir(root, 2))
        assert storage.exists(step_dir(root, 3))


# -- scrubber -----------------------------------------------------------------


class TestScrubber:
    def test_run_once_quarantines_rot(self, tmp_path, storage):
        from dlrover_tpu.checkpoint.scrubber import CheckpointScrubber

        root = str(tmp_path)
        _seal_step(storage, root, 1)
        _seal_step(storage, root, 2, {"shard_0.pkl": b"C" * 48})
        corrupt_file(
            os.path.join(step_dir(root, 2), "shard_0.pkl"), mode="bitflip"
        )
        # Newer than tracker without a manifest: in-flight, skipped.
        storage.write(b"x", os.path.join(step_dir(root, 3), "shard_0.pkl"))
        _set_tracker(storage, root, 2)
        out = CheckpointScrubber(storage, root, max_steps=3).run_once()
        assert out == {"ok": [1], "corrupt": [2], "skipped": [3]}
        assert storage.exists(step_dir(root, 2) + ".corrupt")

    def test_start_stop(self, tmp_path, storage):
        from dlrover_tpu.checkpoint.scrubber import CheckpointScrubber

        s = CheckpointScrubber(storage, str(tmp_path), interval_s=1.0)
        s.start()
        s.start()  # idempotent
        s.stop()
        assert s._thread is None


# -- storage durability primitives -------------------------------------------


class TestStorageDurability:
    def test_durable_write_and_fallback(self, tmp_path, storage):
        p = str(tmp_path / "tracker.txt")
        durable_write(storage, "42", p)
        assert storage.read(p) == b"42"

        class _NoDurable(PosixDiskStorage):
            def write(self, content, path):  # predates the durable kwarg
                PosixDiskStorage.write(self, content, path)

        durable_write(_NoDurable(), "43", p)
        assert storage.read(p) == b"43"

    def test_move_and_sync_tree(self, tmp_path, storage):
        src = str(tmp_path / "a")
        storage.write(b"x", os.path.join(src, "f"))
        assert storage.move(src, str(tmp_path / "b"))
        assert storage.read(str(tmp_path / "b" / "f")) == b"x"
        storage.sync_tree(str(tmp_path / "b"))
        storage.sync_tree(str(tmp_path / "missing"))  # no-op, no raise
        fsync_dir(str(tmp_path / "nope"))  # no-op, no raise
        # ABC default: storages without rename degrade gracefully.
        from dlrover_tpu.checkpoint.storage import CheckpointStorage

        assert CheckpointStorage.move(storage, "a", "b") is False

    def test_corrupt_file_helper(self, tmp_path):
        p = tmp_path / "blob"
        p.write_bytes(b"A" * 64)
        assert corrupt_file(str(p), mode="bitflip")
        data = p.read_bytes()
        assert len(data) == 64 and data != b"A" * 64
        assert sum(a != b for a, b in zip(data, b"A" * 64)) == 1
        assert corrupt_file(str(p), mode="truncate")
        assert len(p.read_bytes()) == 32
        assert not corrupt_file(str(tmp_path / "missing"), mode="bitflip")


# -- recovery consensus: fake-client unit tier --------------------------------


class _FakeConsensusClient:
    def __init__(self, decisions, fail_report=False):
        self.reports = []
        self.polls = 0
        self._decisions = list(decisions)
        self._fail_report = fail_report

    def report_restorable_steps(self, node_rank, steps, round_id=0):
        if self._fail_report:
            raise ConnectionError("master gone")
        self.reports.append((node_rank, round_id, list(steps)))
        return True

    def get_restore_decision(self, round_id=0, world_size=1):
        self.polls += 1
        if len(self._decisions) > 1:
            return self._decisions.pop(0)
        return self._decisions[0]


class TestNegotiate:
    def test_agrees_once_everyone_reported(self):
        client = _FakeConsensusClient(
            [
                comm.RestoreDecision(ready=False, step=-1, reported=1),
                comm.RestoreDecision(ready=True, step=7, reported=2),
            ]
        )
        step = integrity.negotiate(
            client, node_rank=0, steps=[3, 7], world_size=2,
            round_id=4, timeout=5.0, poll=0.01,
        )
        assert step == 7
        assert client.reports == [(0, 4, [3, 7])]
        assert client.polls == 2

    def test_empty_intersection_is_cold_start(self):
        client = _FakeConsensusClient(
            [comm.RestoreDecision(ready=True, step=-1, reported=2)]
        )
        assert (
            integrity.negotiate(
                client, node_rank=0, steps=[], world_size=2, poll=0.01
            )
            is None
        )

    def test_timeout_falls_back_to_local_ladder(self):
        client = _FakeConsensusClient(
            [comm.RestoreDecision(ready=False, step=-1, reported=1)]
        )
        t0 = time.time()
        assert (
            integrity.negotiate(
                client, node_rank=0, steps=[1], world_size=2,
                timeout=0.1, poll=0.02,
            )
            is None
        )
        assert time.time() - t0 < 5.0

    def test_report_failure_degrades_not_wedges(self):
        client = _FakeConsensusClient([], fail_report=True)
        assert (
            integrity.negotiate(
                client, node_rank=0, steps=[1], world_size=1
            )
            is None
        )


# -- recovery consensus: master round trip ------------------------------------


class TestConsensusServicer:
    @pytest.fixture()
    def master(self):
        from dlrover_tpu.master.local_master import LocalJobMaster

        m = LocalJobMaster(port=0, node_num=1)
        m.run(blocking=False)
        yield m
        m.stop()

    @pytest.fixture()
    def client(self, master):
        from dlrover_tpu.agent.master_client import MasterClient

        c = MasterClient(master.addr, node_id=0, node_type="worker")
        assert c.ready(10)
        return c

    def test_decision_is_max_of_intersection(self, client):
        assert client.report_restorable_steps(
            node_rank=0, steps=[3, 5, 9], round_id=2
        )
        d = client.get_restore_decision(round_id=2, world_size=2)
        assert not d.ready and d.reported == 1
        assert client.report_restorable_steps(
            node_rank=1, steps=[5, 9, 11], round_id=2
        )
        d = client.get_restore_decision(round_id=2, world_size=2)
        assert d.ready and d.step == 9 and d.reported == 2

    def test_rank_rereport_overwrites(self, client):
        client.report_restorable_steps(node_rank=0, steps=[9], round_id=3)
        client.report_restorable_steps(node_rank=0, steps=[5], round_id=3)
        d = client.get_restore_decision(round_id=3, world_size=1)
        assert d.ready and d.step == 5

    def test_disjoint_sets_decide_minus_one(self, client):
        client.report_restorable_steps(node_rank=0, steps=[1], round_id=4)
        client.report_restorable_steps(node_rank=1, steps=[2], round_id=4)
        d = client.get_restore_decision(round_id=4, world_size=2)
        assert d.ready and d.step == -1
        # negotiate() maps -1 to None (cold start).
        assert (
            integrity.negotiate(
                client, node_rank=0, steps=[1], world_size=2,
                round_id=4, poll=0.01,
            )
            is None
        )

    def test_rounds_are_pruned(self, client):
        for rid in range(10, 16):
            client.report_restorable_steps(
                node_rank=0, steps=[rid], round_id=rid
            )
        # Only the newest 4 rounds survive.
        assert not client.get_restore_decision(
            round_id=10, world_size=1
        ).ready
        d = client.get_restore_decision(round_id=15, world_size=1)
        assert d.ready and d.step == 15

    def test_negotiate_end_to_end(self, client):
        client.report_restorable_steps(
            node_rank=1, steps=[5, 9], round_id=7
        )
        step = integrity.negotiate(
            client, node_rank=0, steps=[3, 5, 9], world_size=2,
            round_id=7, timeout=10.0, poll=0.05,
        )
        assert step == 9


# -- shm crc verification -----------------------------------------------------


class TestShmCrcVerification:
    def test_corrupted_tensor_refused(self):
        from dlrover_tpu.checkpoint.shm_handler import (
            _HEADER,
            SharedMemoryHandler,
            _ShardEntry,
        )

        uid = f"shmcrc{os.getpid()}_{time.time_ns()}"
        h = SharedMemoryHandler.create_master(shard_id=0, job_uid=uid)
        try:
            arr = np.arange(64, dtype=np.float32)
            h.save_state_dict(3, {("w", 0): _ShardEntry(arr, None, None)})
            step, tree = h.load_state_dict()
            assert step == 3
            np.testing.assert_array_equal(tree[("w", 0)].data, arr)
            # Scribble one payload byte (a stray write / DMA error).
            buf = h.shared_memory.buf
            (meta_len,) = _HEADER.unpack(bytes(buf[: _HEADER.size]))
            base = _HEADER.size + meta_len
            buf[base] = buf[base] ^ 0xFF
            assert h.load_state_dict() is None  # refused, storage fallback
            # verify=False is the explicit escape hatch (forensics only).
            loaded = h.load_state_dict(verify=False)
            assert loaded is not None and loaded[0] == 3
        finally:
            h.close(unlink=True)

    def test_objects_blob_crc(self):
        from dlrover_tpu.checkpoint.shm_handler import (
            SharedMemoryHandler,
            _ShardEntry,
            ShmMeta,
        )

        uid = f"shmobj{os.getpid()}_{time.time_ns()}"
        h = SharedMemoryHandler.create_master(shard_id=0, job_uid=uid)
        try:
            meta = ShmMeta(
                step=1, tensors=[], objects=b"blob", total_bytes=0,
                objects_crc32=123456,  # wrong on purpose
            )
            assert not h._verify_objects(meta)
            import zlib

            meta.objects_crc32 = zlib.crc32(b"blob")
            assert h._verify_objects(meta)
        finally:
            h.close(unlink=True)


class TestShmRestoreRefusesCorruption:
    """The streaming restore (ISSUE 25) keeps the trust invariant: every
    tensor is verified before ITS bytes reach the uploader, one mismatch
    anywhere refuses the whole block, and the ladder goes on to storage."""

    TENSORS = ["a", "b", "c", "d", "e", "step"]  # staged in this order

    @staticmethod
    def _state(v):
        tree = {
            k: jnp.full((32, 16), float(v) + i, jnp.float32)
            for i, k in enumerate("abcde")
        }
        tree.update(step=jnp.asarray(v, jnp.int32), tag=f"v{v}")
        return tree

    @staticmethod
    def _scribble(handler, where, index):
        """Flip one byte of tensor ``index``, or of the object blob."""
        meta = handler.load_meta()
        buf = handler.shared_memory.buf
        base = handler._tensor_base()
        if where == "objects":
            at = bytes(buf[:base]).index(meta.objects) + len(meta.objects) // 2
        else:
            t = meta.tensors[index]
            at = base + t.offset + t.nbytes // 2
        buf[at] = buf[at] ^ 0xFF

    @pytest.mark.parametrize(
        "where,index",
        [("first", 0), ("middle", 2), ("last", 5), ("objects", None)],
    )
    def test_corrupt_block_is_refused_whole(
        self, tmp_path, monkeypatch, where, index
    ):
        from dlrover_tpu.checkpoint import Checkpointer, StorageType
        from dlrover_tpu.checkpoint import engine as engine_mod
        from dlrover_tpu.common import faults
        from dlrover_tpu.telemetry import events as tevents

        tdir = str(tmp_path / "telemetry")
        monkeypatch.setenv(tevents.ENV_TELEMETRY_DIR, tdir)
        tevents.reset()
        real_upload, uploads = engine_mod._upload, []

        def uploader(arrays, targets):
            uploads.extend(np.array(a) for a in arrays)
            return real_upload(arrays, targets)

        monkeypatch.setattr(engine_mod, "_upload", uploader)
        ckpt = Checkpointer(str(tmp_path / "ckpt"), start_saver=True)
        try:
            assert ckpt.save_checkpoint(1, self._state(1), StorageType.DISK)
            assert ckpt.wait(timeout=90)
            if where == "first":
                # The fault flips a byte of the first tensor as it lands.
                faults.install("ckpt_shm_corrupt:*:noop")
            assert ckpt.save_checkpoint(
                2, self._state(2), StorageType.MEMORY, block=True
            )
            engine = ckpt._engine
            if where != "first":
                self._scribble(engine._shm_handler, where, index)
            meta = engine._shm_handler.load_meta()
            assert meta.step == 2
            assert [t.path[0].strip("[]'") for t in meta.tensors] == (
                self.TENSORS
            )
            assert engine._restore_from_memory(
                self._state(0), None, None
            ) is None
            # Only the tensors BEFORE the corrupt one were uploaded (the
            # leaves in flight were dropped), and each of those verified.
            assert len(uploads) == (index or 0)
            for i, arr in enumerate(uploads):
                want = 2 if self.TENSORS[i] == "step" else 2.0 + i
                assert (arr == want).all()
            step, state = ckpt.load_checkpoint(self._state(0))
        finally:
            faults.reset()
            ckpt.close()
            tevents.reset()
        # Nothing of step 2 came back: the load made the same refused
        # attempt, then restored step 1 from storage (host_tree_to_state).
        assert step == 1 and len(uploads) == 2 * (index or 0)
        assert state["tag"] == "v1" and int(state["step"]) == 1
        for i, k in enumerate("abcde"):
            assert (np.asarray(state[k]) == 1.0 + i).all()
        assert engine.last_restore["source"] == "storage"
        assert engine.last_restore["direct_leaves"] == 0
        assert engine.last_restore["assembled_leaves"] == 6
        verdicts = [
            e for e in tevents.read_dir(tdir)
            if e["ev"] == "verdict" and e.get("action") == "ckpt_shm_corrupt"
        ]
        # One verdict per refused attempt (the direct call and the load).
        assert [v["step"] for v in verdicts] == [2, 2]


# -- kv delta chain link verification -----------------------------------------


class TestKvChainCorruption:
    def _chain(self, tmp_path):
        from dlrover_tpu.checkpoint.kv_checkpoint import KvCheckpointManager
        from dlrover_tpu.native.kv_variable import KvVariable

        kv = KvVariable(dim=4, slots=2, init_scale=0.0)
        mgr = KvCheckpointManager(kv, str(tmp_path), full_interval=10)
        kv.insert([1, 2], np.ones((2, 4), np.float32))
        assert mgr.save(step=1) == "full"
        kv.insert([3], 2 * np.ones((1, 4), np.float32))
        assert mgr.save(step=2) == "delta"
        return kv

    def _fresh_restore(self, tmp_path):
        from dlrover_tpu.checkpoint.kv_checkpoint import KvCheckpointManager
        from dlrover_tpu.native.kv_variable import KvVariable

        fresh = KvVariable(dim=4, slots=2, init_scale=0.0)
        ok = KvCheckpointManager(fresh, str(tmp_path)).restore()
        return ok, fresh

    def test_deterministic_file_naming_and_digest_records(self, tmp_path):
        self._chain(tmp_path)
        # The in-memory savez path produces EXACTLY the named files — no
        # numpy-version-dependent tmp suffixes, no stray tmp leftovers.
        assert sorted(os.listdir(tmp_path)) == [
            "MANIFEST.json", "kv-1.full.npz", "kv-2.delta.npz",
        ]
        manifest = json.loads((tmp_path / "MANIFEST.json").read_text())
        for entry in manifest["chain"]:
            blob = (tmp_path / entry["file"]).read_bytes()
            assert entry["size"] == len(blob)
            assert entry["digest"] == integrity.compute_digest(blob)

    def _assert_sealed_prefix(self, tmp_path, fresh):
        """A bad TRAILING link is the expected crash-mid-append shape:
        restore drops it, serves the sealed prefix (base keys 1,2 but
        never the torn link's key 3), and re-commits the truncated
        manifest with the mark rolled back.  Rot anywhere EARLIER in
        the chain still aborts the whole restore
        (test_truncated_base_link_aborts)."""
        _, found = fresh.gather_or_zeros([1, 2])
        assert found.all()
        _, found3 = fresh.gather_or_zeros([3])
        assert not found3.any()
        manifest = json.loads((tmp_path / "MANIFEST.json").read_text())
        assert len(manifest["chain"]) == 1
        assert manifest["mark"] == manifest["chain"][-1]["mark"]

    def test_bitflipped_trailing_link_restores_sealed_prefix(
        self, tmp_path
    ):
        self._chain(tmp_path)
        assert corrupt_file(str(tmp_path / "kv-2.delta.npz"), mode="bitflip")
        ok, fresh = self._fresh_restore(tmp_path)
        assert ok
        self._assert_sealed_prefix(tmp_path, fresh)

    def test_truncated_base_link_aborts(self, tmp_path):
        self._chain(tmp_path)
        assert corrupt_file(str(tmp_path / "kv-1.full.npz"), mode="truncate")
        ok, fresh = self._fresh_restore(tmp_path)
        # kv-1 is NOT the trailing link — mid-chain rot must abort
        # before any row imports: no half-restored table.
        assert not ok and len(fresh) == 0

    def test_missing_trailing_link_restores_sealed_prefix(self, tmp_path):
        self._chain(tmp_path)
        os.remove(tmp_path / "kv-2.delta.npz")
        ok, fresh = self._fresh_restore(tmp_path)
        assert ok
        self._assert_sealed_prefix(tmp_path, fresh)

    def test_unreadable_trailing_npz_restores_sealed_prefix(self, tmp_path):
        # Digest matches but the payload is not an npz: the torn-write
        # tolerance must not let garbage import half a link.
        self._chain(tmp_path)
        garbage = b"PK\x03\x04 not actually an npz"
        (tmp_path / "kv-2.delta.npz").write_bytes(garbage)
        manifest = json.loads((tmp_path / "MANIFEST.json").read_text())
        manifest["chain"][-1]["size"] = len(garbage)
        manifest["chain"][-1]["digest"] = integrity.compute_digest(garbage)
        (tmp_path / "MANIFEST.json").write_text(json.dumps(manifest))
        ok, fresh = self._fresh_restore(tmp_path)
        assert ok
        self._assert_sealed_prefix(tmp_path, fresh)

    def test_clean_chain_still_restores(self, tmp_path):
        self._chain(tmp_path)
        ok, fresh = self._fresh_restore(tmp_path)
        assert ok
        got, found = fresh.gather_or_zeros([1, 2, 3])
        assert found.all()


# -- end-to-end: the ladder falls back past on-disk rot -----------------------


class TestRestoreLadderEndToEnd:
    def _state(self, step):
        return {
            "w": jnp.arange(8, dtype=jnp.float32) * step,
            "step": jnp.asarray(step),
        }

    def test_bit_rot_falls_back_to_older_verified_step(self, tmp_path):
        from dlrover_tpu.checkpoint import Checkpointer, StorageType
        from dlrover_tpu.checkpoint.ckpt_saver import (
            AsyncCheckpointSaver,
            shard_file,
        )

        root = str(tmp_path / "ckpt")
        ckpt = Checkpointer(root, start_saver=True)
        try:
            for step in (1, 2):
                assert ckpt.save_checkpoint(
                    step, self._state(step), StorageType.DISK
                )
                assert ckpt.wait(timeout=60)
            assert ckpt.latest_persisted_step() == 2
        finally:
            ckpt.close()
            AsyncCheckpointSaver.reset()
        # Bit rot AFTER commit: flip a byte in the committed newest step.
        assert corrupt_file(shard_file(root, 2, 0), mode="bitflip")

        ckpt2 = Checkpointer(root, start_saver=True)
        try:
            assert ckpt2.verified_steps() == [1]
            step, state = ckpt2.load_checkpoint(self._state(0))
            assert step == 1
            np.testing.assert_array_equal(
                np.asarray(state["w"]), np.arange(8, dtype=np.float32)
            )
            assert int(state["step"]) == 1
            # The rotted step was quarantined, never silently reused.
            assert os.path.isdir(step_dir(root, 2) + ".corrupt")
            assert not os.path.exists(step_dir(root, 2))
        finally:
            ckpt2.close()
            AsyncCheckpointSaver.reset()

"""Flash Checkpoint: IPC primitives, shm staging, async persist + commit,
shm-first restore, and reshard-on-load across a changed mesh (reference test
analog: ``dlrover/python/tests/test_ckpt_saver.py``,
``dlrover/trainer/tests/torch/checkpoint_egine_test.py``)."""

import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.common import multi_process as mp


@pytest.fixture(autouse=True)
def _isolated_ipc(isolated_ipc):
    """Checkpoint-IPC isolation (tests/conftest.py) for every test."""
    yield


class TestIpcPrimitives:
    def test_shared_lock(self):
        server = mp.SharedLock(name="l1", create=True)
        client = mp.SharedLock(name="l1")
        assert client.acquire()
        assert client.locked()
        assert not server.acquire(blocking=False)
        client.release()
        assert server.acquire(blocking=False)
        server.release()
        server.close()

    def test_shared_lock_broken_by_dead_owner(self):
        """A process SIGKILLed while holding the lock must not wedge it
        (trainer crash mid shm memcpy)."""
        import subprocess
        import sys

        server = mp.SharedLock(name="l_dead", create=True)
        # The child acquires the lock then dies without releasing.
        code = (
            "import os\n"
            "from dlrover_tpu.common import multi_process as mp\n"
            "lock = mp.SharedLock(name='l_dead')\n"
            "assert lock.acquire()\n"
            "os._exit(9)\n"
        )
        env = dict(os.environ)
        subprocess.run(
            [sys.executable, "-c", code], env=env, check=False, timeout=30
        )
        assert server.locked()
        # Blocked acquire detects the dead owner and breaks the lock.
        assert server.acquire(timeout=10)
        server.release()
        server.close()

    def test_shared_queue(self):
        server = mp.SharedQueue(name="q1", create=True)
        client = mp.SharedQueue(name="q1")
        client.put({"a": 1})
        server.put("two")
        assert client.get(timeout=5) == {"a": 1}
        assert client.get(timeout=5) == "two"
        assert client.empty()
        server.close()

    def test_shared_dict(self):
        server = mp.SharedDict(name="d1", create=True)
        client = mp.SharedDict(name="d1")
        client.set("k", [1, 2])
        assert server.get("k") == [1, 2]
        client.update({"x": 9})
        assert client.copy() == {"k": [1, 2], "x": 9}
        server.close()

    def test_shared_memory_survives_tracker(self):
        shm = mp.create_shared_memory("test_shm_block", create=True, size=64)
        shm.buf[:4] = b"abcd"
        other = mp.create_shared_memory("test_shm_block", create=False)
        assert bytes(other.buf[:4]) == b"abcd"
        other.close()
        shm.close()
        shm.unlink()


    def test_attaching_maps_the_pages_at_once(self):
        """A process that attaches to an existing block gets it with the
        pages already in its page table (one MAP_POPULATE, no fault a
        page as the restore walks it), and still shares the bytes.  So
        does the process that creates one, before the save's threads fill
        it."""
        size = 8 << 20
        name = f"test_shm_populated_{os.getpid()}"

        def resident_kb(shm):
            # Rss of this handle's own mapping, by its address.
            start = np.frombuffer(shm.buf, np.uint8).__array_interface__[
                "data"][0]
            with open("/proc/self/smaps") as f:
                lines = f.read().splitlines()
            at = next(i for i, l in enumerate(lines)
                      if l.startswith(f"{start:x}-"))
            rss = next(l for l in lines[at:] if l.startswith("Rss:"))
            return int(rss.split()[1])

        shm = mp.create_shared_memory(name, create=True, size=size)
        try:
            assert resident_kb(shm) >= size // 1024  # nothing written yet
            assert bytes(shm.buf[:4]) == bytes(4)
            shm.buf[:size] = b"\x01" * size
            other = mp.create_shared_memory(name, create=False)
            assert resident_kb(other) >= size // 1024  # nothing touched yet
            assert bytes(other.buf[-4:]) == b"\x01" * 4
            other.buf[:4] = b"abcd"
            assert bytes(shm.buf[:4]) == b"abcd"
            other.close()
        finally:
            shm.close()
            shm.unlink()


class TestShmHandler:
    def test_roundtrip(self):
        from dlrover_tpu.checkpoint.shm_handler import (
            SharedMemoryHandler,
            _ShardEntry,
        )

        master = SharedMemoryHandler.create_master(shard_id=7)
        writer = SharedMemoryHandler(shard_id=7)
        w = np.arange(12, dtype=np.float32).reshape(3, 4)
        tree = {
            ("w", 0): _ShardEntry(w, (6, 4), ((0, 3), (0, 4))),
            ("step", -1): 42,
        }
        took = writer.save_state_dict(5, tree)
        assert tree == {}  # consumed: the save keeps no leaf alive
        assert took["bytes"] == w.nbytes and took["leaves"] == 1
        step, loaded = master.load_state_dict()
        assert step == 5
        np.testing.assert_array_equal(loaded[("w", 0)].data, w)
        assert loaded[("w", 0)].index == ((0, 3), (0, 4))
        assert loaded[("step", -1)] == 42
        writer.close()
        master.close(unlink=True)


def _make_state(mesh_cfg, devices, seed=0):
    import optax

    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.parallel.sharding import PRESET_RULES
    from dlrover_tpu.trainer.step import create_sharded_state

    mesh = build_mesh(mesh_cfg, devices)
    rules = PRESET_RULES["fsdp_tp"]
    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    model = LlamaModel(cfg)
    batch = {
        "input_ids": jnp.zeros((8, 16), jnp.int32),
        "labels": jnp.zeros((8, 16), jnp.int32),
    }
    state, shardings = create_sharded_state(
        model, optax.adam(1e-3), mesh, rules, jax.random.key(seed), batch
    )
    return state, shardings, mesh


class TestFlashCheckpoint:
    def test_save_restore_memory(self, tmp_path, devices8):
        from dlrover_tpu.checkpoint import Checkpointer, StorageType
        from dlrover_tpu.parallel.mesh import MeshConfig

        state, shardings, _ = _make_state(MeshConfig(dp=2, fsdp=2, tp=2), devices8)
        ckpt = Checkpointer(str(tmp_path / "ckpt"), start_saver=True)
        assert ckpt.save_checkpoint(3, state, StorageType.MEMORY)
        step, restored = ckpt.load_checkpoint(state, shardings)
        assert step == 3
        a = jax.tree_util.tree_leaves(state.params)[0]
        b = jax.tree_util.tree_leaves(restored.params)[0]
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        ckpt.close()

    def test_async_persist_and_commit(self, tmp_path, devices8):
        from dlrover_tpu.checkpoint import Checkpointer, StorageType
        from dlrover_tpu.parallel.mesh import MeshConfig

        root = str(tmp_path / "ckpt")
        state, shardings, _ = _make_state(MeshConfig(dp=2, fsdp=2, tp=2), devices8)
        ckpt = Checkpointer(root, start_saver=True)
        assert ckpt.save_checkpoint(7, state, StorageType.DISK)
        deadline = time.time() + 30
        while time.time() < deadline:
            if ckpt.latest_persisted_step() == 7:
                break
            time.sleep(0.1)
        assert ckpt.latest_persisted_step() == 7
        ckpt.close()

    def test_reshard_on_restore(self, tmp_path, devices8):
        """Save under fsdp=2,tp=2; restore under fsdp=4 (changed world)."""
        from dlrover_tpu.checkpoint import Checkpointer, StorageType
        from dlrover_tpu.parallel.mesh import MeshConfig

        root = str(tmp_path / "ckpt")
        state, _, _ = _make_state(MeshConfig(dp=2, fsdp=2, tp=2), devices8)
        ckpt = Checkpointer(root, start_saver=True)
        ckpt.save_checkpoint(11, state, StorageType.DISK)
        deadline = time.time() + 30
        while time.time() < deadline and ckpt.latest_persisted_step() != 11:
            time.sleep(0.1)
        ckpt.close()
        from dlrover_tpu.checkpoint.ckpt_saver import AsyncCheckpointSaver

        AsyncCheckpointSaver.reset()

        # New world: different mesh factorization, fresh params.
        state2, shardings2, _ = _make_state(
            MeshConfig(dp=2, fsdp=4, tp=1), devices8, seed=1
        )
        ckpt2 = Checkpointer(root, start_saver=True)
        # shm of the new job is empty → storage fallback + reshard.
        step, restored = ckpt2.load_checkpoint(state2, shardings2)
        assert step == 11
        orig = jax.tree_util.tree_flatten_with_path(state.params)[0]
        new = dict(jax.tree_util.tree_flatten_with_path(restored.params)[0])
        expected = dict(
            jax.tree_util.tree_flatten_with_path(shardings2.params)[0]
        )
        for path, leaf in orig:
            got = new[path]
            # Restored arrays must carry the NEW world's sharding, not the
            # saved one — that's the reshard-on-restore contract.
            assert got.sharding.is_equivalent_to(
                expected[path], got.ndim
            ), f"{path}: {got.sharding} != requested {expected[path]}"
            np.testing.assert_array_equal(np.asarray(leaf), np.asarray(got))
        assert int(restored.step) == int(state.step)
        ckpt2.close()

    def test_training_proceeds_while_staging_in_flight(
        self, tmp_path, devices8
    ):
        """The async-staging contract: save dispatch is cheap, training
        steps (which DONATE the state buffers) keep running while the
        drain is in flight, and the staged checkpoint holds the values
        from dispatch time — not the donated-over ones."""
        from dlrover_tpu.checkpoint import Checkpointer, StorageType
        from dlrover_tpu.parallel.mesh import MeshConfig

        state, shardings, _ = _make_state(
            MeshConfig(dp=2, fsdp=2, tp=2), devices8
        )

        @jax.jit
        def bump(params):
            return jax.tree.map(lambda x: x + 1.0, params)

        saved_leaf = np.asarray(jax.tree_util.tree_leaves(state.params)[0])
        ckpt = Checkpointer(str(tmp_path / "ckpt"), start_saver=True)
        assert ckpt.save_checkpoint(21, state, StorageType.MEMORY)
        # Training continues immediately: mutate params several times
        # while the drain races in the background.
        params = state.params
        for _ in range(3):
            params = bump(params)
        state = state.replace(params=params)
        assert ckpt.wait_staging()
        step, restored = ckpt.load_checkpoint(state, shardings)
        assert step == 21
        got = np.asarray(jax.tree_util.tree_leaves(restored.params)[0])
        np.testing.assert_array_equal(got, saved_leaf)  # NOT +3
        ckpt.close()

    def test_donated_state_survives_async_save(self, tmp_path, devices8):
        """Hard mode: the very buffers passed to save are donated to the
        next jitted step right after dispatch.  The device snapshot
        (donation guard) must have detached the drain from them."""
        from dlrover_tpu.checkpoint import Checkpointer, StorageType
        from dlrover_tpu.parallel.mesh import MeshConfig

        state, shardings, _ = _make_state(
            MeshConfig(dp=2, fsdp=2, tp=2), devices8
        )

        @jax.jit
        def consume(params):
            return jax.tree.map(lambda x: x * 0.0, params)

        consume_donating = jax.jit(
            lambda p: jax.tree.map(lambda x: x * 0.0, p), donate_argnums=0
        )
        saved_leaf = np.asarray(jax.tree_util.tree_leaves(state.params)[0])
        ckpt = Checkpointer(str(tmp_path / "ckpt2"), start_saver=True)
        assert ckpt.save_checkpoint(5, state, StorageType.MEMORY)
        zeroed = consume_donating(state.params)  # donates saved buffers
        assert ckpt.wait_staging()
        state = state.replace(params=zeroed)
        step, restored = ckpt.load_checkpoint(state, shardings)
        assert step == 5
        got = np.asarray(jax.tree_util.tree_leaves(restored.params)[0])
        np.testing.assert_array_equal(got, saved_leaf)
        ckpt.close()

    def test_memory_save_skipped_under_backpressure(
        self, tmp_path, devices8
    ):
        """While a drain is in flight, a memory-only save is skipped
        (returns False, takes no snapshot) — at most one snapshot of the
        state ever lives in HBM; a persist instead waits and lands."""
        from dlrover_tpu.checkpoint import Checkpointer, StorageType
        from dlrover_tpu.parallel.mesh import MeshConfig

        state, shardings, _ = _make_state(
            MeshConfig(dp=2, fsdp=2, tp=2), devices8
        )
        ckpt = Checkpointer(str(tmp_path / "ckpt"), start_saver=True)
        engine = ckpt._engine
        gate = threading.Event()
        orig = engine._stage_to_shm

        def slow_stage(step, work, persist):
            gate.wait(10)
            return orig(step, work, persist)

        engine._stager._process = slow_stage
        assert ckpt.save_checkpoint(1, state, StorageType.MEMORY)
        # drain gated open -> busy; memory save must skip
        assert not ckpt.save_checkpoint(2, state, StorageType.MEMORY)
        gate.set()
        assert ckpt.wait_staging()
        # persist while idle works and commits
        assert ckpt.save_checkpoint(3, state, StorageType.DISK)
        assert ckpt.wait()
        assert ckpt.latest_persisted_step() == 3
        ckpt.close()

    def test_async_failure_surfaces_on_next_save(self, tmp_path, devices8):
        """A background staging failure is sticky: the NEXT save call
        returns False so trainers notice degradation."""
        from dlrover_tpu.checkpoint import Checkpointer, StorageType
        from dlrover_tpu.parallel.mesh import MeshConfig

        state, _, _ = _make_state(
            MeshConfig(dp=2, fsdp=2, tp=2), devices8
        )
        ckpt = Checkpointer(str(tmp_path / "ckpt"), start_saver=True)
        engine = ckpt._engine
        engine._stager._process = lambda step, work, persist: False
        assert ckpt.save_checkpoint(1, state, StorageType.MEMORY)
        assert not ckpt.wait_staging()
        assert not ckpt.save_checkpoint(2, state, StorageType.MEMORY)
        ckpt.close()

    def test_latest_wins_carries_persist_forward(self, tmp_path, devices8):
        """A pending persist superseded by a newer save must still reach
        disk (with the newer step)."""
        from dlrover_tpu.checkpoint.engine import _AsyncStager

        seen = []
        gate = threading.Event()

        def slow_process(step, work, persist):
            gate.wait(5)
            seen.append((step, persist))
            return True

        stager = _AsyncStager(slow_process)
        stager.submit(1, lambda: {}, True)   # picked up, blocked on gate
        time.sleep(0.2)
        stager.submit(2, lambda: {}, True)   # pending persist
        stager.submit(3, lambda: {}, False)  # supersedes 2, inherits persist
        gate.set()
        assert stager.wait(10)
        stager.stop()
        assert seen == [(1, True), (3, True)]

    def test_breakpoint_save(self, tmp_path, devices8):
        """MEMORY-only save is persisted by save_shm_to_storage (the SIGTERM
        / failure path)."""
        from dlrover_tpu.checkpoint import Checkpointer, StorageType
        from dlrover_tpu.checkpoint.ckpt_saver import AsyncCheckpointSaver
        from dlrover_tpu.parallel.mesh import MeshConfig

        root = str(tmp_path / "ckpt")
        state, _, _ = _make_state(MeshConfig(dp=2, fsdp=2, tp=2), devices8)
        ckpt = Checkpointer(root, start_saver=True)
        ckpt.save_checkpoint(13, state, StorageType.MEMORY)
        assert ckpt.wait_staging()  # async drain must land in shm first
        deadline = time.time() + 10
        while time.time() < deadline:
            saver = AsyncCheckpointSaver.get_ckpt_saver()
            if saver is not None:
                break
            time.sleep(0.05)
        assert saver is not None
        saver.save_shm_to_storage()
        assert ckpt.latest_persisted_step() == 13
        ckpt.close()


# -- the restore reads shm in place (ISSUE 25) --------------------------------


def _mixed_tree(scale=1.0):
    """f32, bf16, a 0-d step, an empty array and two non-array leaves."""
    return {
        "w": jnp.arange(64 * 32, dtype=jnp.float32).reshape(64, 32) * scale,
        "h": (jnp.arange(16 * 8, dtype=jnp.float32) * scale)
        .astype(jnp.bfloat16).reshape(16, 8),
        "step": jnp.asarray(int(7 * scale), jnp.int32),
        "empty": jnp.zeros((0, 4), jnp.float32),
        "note": "saved" if scale == 1.0 else f"x{scale}",
        "lr": 0.5 * scale,
    }


def _assert_trees_bit_equal(got, want):
    got_flat = jax.tree_util.tree_flatten_with_path(got)[0]
    want_flat = jax.tree_util.tree_flatten_with_path(want)[0]
    assert [p for p, _ in got_flat] == [p for p, _ in want_flat]
    for (path, a), (_, b) in zip(got_flat, want_flat):
        if isinstance(b, jax.Array):
            assert isinstance(a, jax.Array), path
            assert a.dtype == b.dtype and a.shape == b.shape, path
            assert a.sharding.is_equivalent_to(b.sharding, b.ndim), path
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), path
        else:
            assert a == b, path


@pytest.fixture()
def engine(tmp_path):
    from dlrover_tpu.checkpoint.engine import CheckpointEngine

    eng = CheckpointEngine(str(tmp_path / "ckpt"), start_saver=True)
    yield eng
    eng.close()


def _block_bytes(eng):
    """The whole mapped block as bytes (drop it before the block closes)."""
    return np.frombuffer(eng._shm_handler.shared_memory.buf, np.uint8)


def _second_lock(eng):
    from dlrover_tpu.checkpoint.ckpt_saver import SHM_LOCK
    from dlrover_tpu.checkpoint.shm_handler import job_uid_for

    return mp.SharedLock(
        name=f"{SHM_LOCK}_{job_uid_for(eng.checkpoint_dir)}_0"
    )


class TestRestoreInPlace:
    def test_bit_equal_to_the_copying_reader(self, engine):
        """The streaming restore and ``load_state_dict`` +
        ``host_tree_to_state`` (the agent's reader, the storage rung's
        builder) give the same tree, bit for bit."""
        from dlrover_tpu.checkpoint.engine import host_tree_to_state

        saved, target = _mixed_tree(), _mixed_tree(scale=3.0)
        assert engine.save_to_memory(5, saved, block=True)
        step, got = engine.load(target)
        old_step, host = engine._shm_handler.load_state_dict()
        want = host_tree_to_state(host, target)
        assert step == old_step == 5
        _assert_trees_bit_equal(got, want)
        _assert_trees_bit_equal(got, saved)
        assert engine.last_restore == {
            "source": "shm", "direct_leaves": 4, "assembled_leaves": 0,
            "bytes": 64 * 32 * 4 + 16 * 8 * 2 + 4,
        }

    def test_views_reach_an_uploader_that_copies(self, engine, monkeypatch):
        """Where the upload copies into device memory (a TPU), the arrays
        handed to it are views into the block, and the restore allocates
        nothing state-sized on the host."""
        import tracemalloc

        from dlrover_tpu.checkpoint import engine as engine_mod

        big = {
            "a": jnp.ones((2048, 512), jnp.float32),
            "b": jnp.full((2048, 512), 2.0, jnp.float32),
            "step": jnp.asarray(3, jnp.int32),
        }
        assert engine.save_to_memory(1, big, block=True)
        handed, large_allocs = [], []

        def uploader(arrays, targets):
            block = _block_bytes(engine)
            handed.extend(
                (a.nbytes, np.shares_memory(a, block), a.flags.writeable)
                for a in arrays
            )
            del block
            return [jnp.zeros(()) for _ in arrays]

        def counting(fn):
            def wrapper(shape, *args, **kw):
                out = fn(shape, *args, **kw)
                if out.nbytes >= 1 << 20:
                    large_allocs.append(out.nbytes)
                return out
            return wrapper

        monkeypatch.setattr(engine_mod, "_upload_copies", lambda s: True)
        monkeypatch.setattr(engine_mod, "_upload", uploader)
        monkeypatch.setattr(np, "empty", counting(np.empty))
        monkeypatch.setattr(np, "zeros", counting(np.zeros))
        tracemalloc.start()
        try:
            step, _ = engine.load(big)
            peak = tracemalloc.get_traced_memory()[1]
        finally:
            tracemalloc.stop()
        assert step == 1
        assert handed == [
            (2048 * 512 * 4, True, False), (2048 * 512 * 4, True, False),
            (4, True, False),
        ]
        assert large_allocs == []
        # 8 MB of state; imports and the event log stay far under a leaf.
        assert peak < 2 << 20, f"restore allocated {peak} bytes on the host"

    def test_cpu_uploads_own_their_memory(self, engine, monkeypatch):
        """The crash recorded in ``shm_handler.load_state_dict`` stays
        guarded: on the CPU backend, whose ``device_put`` may alias host
        memory, the uploaded arrays are owned copies; they survive a
        donating jit and a second save into the same block."""
        from dlrover_tpu.checkpoint import engine as engine_mod

        first = {k: v for k, v in _mixed_tree().items()
                 if isinstance(v, jax.Array)}
        second = jax.tree.map(lambda x: x + 1, first)
        assert engine.save_to_memory(1, first, block=True)
        real_upload, aliased = engine_mod._upload, []

        def uploader(arrays, targets):
            block = _block_bytes(engine)
            aliased.extend(np.shares_memory(a, block) for a in arrays)
            del block
            return real_upload(arrays, targets)

        monkeypatch.setattr(engine_mod, "_upload", uploader)
        step, restored = engine.load(second)
        assert step == 1 and aliased == [False] * 4
        bump = jax.jit(
            lambda t: jax.tree.map(lambda x: x + 1, t), donate_argnums=0
        )
        bumped = bump(restored)
        assert engine.save_to_memory(2, second, block=True)  # same block
        bumped = bump(bumped)
        _assert_trees_bit_equal(
            bumped, jax.tree.map(lambda x: x + 2, first)
        )
        step, again = engine.load(first)
        assert step == 2
        _assert_trees_bit_equal(again, second)

    def test_lock_is_held_until_the_upload_has_landed(
        self, engine, monkeypatch
    ):
        """Another process's handle on ``_shm_lock`` cannot take it while
        the uploader runs, nor while the restore waits for the transfers:
        the uploads read from views the next save would rewrite."""
        from dlrover_tpu.checkpoint import engine as engine_mod

        other = _second_lock(engine)
        seen = []

        def try_lock(when):
            got = other.acquire(blocking=False)
            seen.append((when, got))
            if got:
                other.release()

        class InFlight:
            def block_until_ready(self):
                try_lock("landing")
                return self

        def uploader(arrays, targets):
            try_lock("uploading")
            return [InFlight() for _ in arrays]

        tree = {"w": jnp.ones((8, 8)), "step": jnp.asarray(1, jnp.int32)}
        assert engine.save_to_memory(1, tree, block=True)
        monkeypatch.setattr(engine_mod, "_upload", uploader)
        step, _ = engine.load(tree)
        try_lock("returned")
        other.close()
        assert step == 1
        assert seen == [
            ("uploading", False), ("uploading", False),
            ("landing", False), ("landing", False), ("returned", True),
        ]


# -- the save is one pipeline (ISSUE 30) ---------------------------------------


class _Gated:
    """Stand-in for a shard in transit whose arrival the test gates:
    ``np.asarray`` of it calls ``gate`` first, then lets the bytes land."""

    def __init__(self, data, gate):
        self.shape, self.dtype = data.shape, data.dtype
        self._data, self._gate = data, gate

    def __array__(self, dtype=None, copy=None):
        self._gate()
        return np.asarray(self._data)


def _gate_transfers(monkeypatch, gate_of):
    """Every shard the engine hands its stager from now on arrives through
    ``gate_of(i)()``, ``i`` its place in the tree."""
    from dlrover_tpu.checkpoint import engine as engine_mod

    real_begin = engine_mod.begin_host_transfer

    def begin(snap):
        tree = real_begin(snap)
        entries = [v for v in tree.values() if hasattr(v, "global_shape")]
        for i, entry in enumerate(entries):
            entry.data = _Gated(entry.data, gate_of(i))
        return tree

    monkeypatch.setattr(engine_mod, "begin_host_transfer", begin)


def _random_leaves(sizes):
    rng = np.random.default_rng(0)
    return {
        f"leaf{i}": jnp.asarray(
            rng.integers(0, 255, size=n, dtype=np.uint8).view(np.float32)
        )
        for i, n in enumerate(sizes)
    }


def _saved_case(case, devices8):
    """``(saved, target, shardings)``: a mixed tree on one device (f32,
    bf16, 0-d, a zero-size array, two non-array leaves), or a train state
    with several shards a leaf on the 8 virtual devices."""
    if case == "mixed":
        return _mixed_tree(), _mixed_tree(scale=3.0), None
    from dlrover_tpu.parallel.mesh import MeshConfig

    cfg = MeshConfig(dp=2, fsdp=2, tp=2)
    saved, shardings, _ = _make_state(cfg, devices8)
    return saved, _make_state(cfg, devices8, seed=1)[0], shardings


# The benchmark reads the engine's two log lines with these
# (benchmarks/workers/train_worker.py::_StagedLines; tier-1 does not import
# the benchmark's files).
_STAGED_RE = r"step (\d+) staged to shm \(drain ([0-9.]+)s, memcpy ([0-9.]+)s"
_SKIPPED_RE = r"step (\d+) memory save skipped"


class TestSavePipeline:
    def test_leaf_is_in_the_block_before_the_next_is_released(
        self, engine, tmp_path, monkeypatch
    ):
        """Arrivals gated by the test: leaf k+1 is released only once leaf
        k's bytes are seen in the block, so the overlap is observed, not
        timed.  All the while the header is zero (no reader opens the
        block); the meta, with the crc32 of the bytes staged, comes last;
        the ``ckpt_stage`` span and ``last_save`` say what it took."""
        import zlib

        from dlrover_tpu.checkpoint.shm_handler import _HEADER
        from dlrover_tpu.telemetry import events as tevents

        tdir = str(tmp_path / "telemetry")
        monkeypatch.setenv(tevents.ENV_TELEMETRY_DIR, tdir)
        tevents.reset()
        state = _random_leaves((512, 4096, 1024, 2048))
        host = {k: np.asarray(v) for k, v in state.items()}
        taken = [1, 3, 2, 0]  # largest first
        seen = []

        def gate_of(i):
            def gate():
                buf = engine._shm_handler.shared_memory.buf
                (header,) = _HEADER.unpack(bytes(buf[: _HEADER.size]))
                before = taken[: taken.index(i)]
                staged = not before
                deadline = time.time() + 10
                while not staged and time.time() < deadline:
                    staged = host[f"leaf{before[-1]}"].tobytes() in bytes(buf)
                seen.append((i, staged, header))
            return gate

        try:
            # An earlier save, so the header starts out non-zero.
            assert engine.save_to_memory(1, {"old": jnp.ones(8)}, block=True)
            _gate_transfers(monkeypatch, gate_of)
            assert engine.save_to_memory(2, state, block=True)
        finally:
            tevents.reset()
        assert seen == [(i, True, 0) for i in taken]
        meta = engine._shm_handler.load_meta()
        assert meta.step == 2
        # In the block they lie in the order of the tree.
        assert [t.path[0] for t in meta.tensors] == [
            f"['leaf{i}']" for i in range(4)
        ]
        assert [t.offset for t in meta.tensors] == [0, 512, 4608, 5632]
        assert [t.crc32 for t in meta.tensors] == [
            zlib.crc32(host[f"leaf{i}"].tobytes()) for i in range(4)
        ]
        took = engine.last_save
        assert took["step"] == 2 and took["leaves"] == 4
        assert took["bytes"] == 512 + 4096 + 1024 + 2048
        assert took["overlap_s"] > 0 and took["tail_s"] > 0
        ends = [
            e for e in tevents.read_dir(tdir)
            if e["ev"] == "span_end" and e.get("name") == "ckpt_stage"
        ]
        assert [e["step"] for e in ends] == [1, 2]
        for field in ("bytes", "leaves", "drain_s", "tail_s", "overlap_s"):
            assert ends[1][field] == took[field], field
        assert ends[1]["drain_s"] + ends[1]["tail_s"] <= ends[1]["dur"]

    @pytest.mark.parametrize("reader", ["load_state_dict", "verified_views"])
    @pytest.mark.parametrize("case", ["mixed", "sharded8"])
    def test_reads_back_bit_equal(self, engine, devices8, case, reader):
        """What the pipeline staged comes back bit for bit through the
        agent's reader and through the restore's: mixed dtypes, an empty
        leaf, non-array objects, and leaves of several shards."""
        from dlrover_tpu.checkpoint.engine import host_tree_to_state

        saved, target, shardings = _saved_case(case, devices8)
        assert engine.save_to_memory(6, saved, block=True)
        meta = engine._shm_handler.load_meta()
        arrays = [
            x for x in jax.tree_util.tree_leaves(saved)
            if isinstance(x, jax.Array)
        ]
        assert meta.total_bytes == sum(x.nbytes for x in arrays)
        assert engine.last_save["leaves"] == len(arrays)
        sizes = [t.nbytes for t in meta.tensors]
        if case == "sharded8":
            assert len(meta.tensors) > len(arrays)  # several shards a leaf
        else:
            assert 0 in sizes and len(meta.tensors) == len(arrays)
        if reader == "load_state_dict":
            step, host = engine._shm_handler.load_state_dict()
            got = host_tree_to_state(host, target, shardings)
        else:
            step, got = engine.load(target, shardings)
            assert engine.last_restore["source"] == "shm"
        assert step == 6
        _assert_trees_bit_equal(got, saved)

    @pytest.mark.parametrize("cut", ["fault_point", "lost_transfer"])
    def test_save_cut_short_leaves_a_block_no_reader_opens(
        self, engine, monkeypatch, cut
    ):
        """A save that stops after its first leaf (the ``ckpt_stage_cut``
        fault point, or a transfer that never arrives) leaves a block that
        both readers refuse: not the previous step, not a mixture.  The
        next save stages as ever."""
        from dlrover_tpu.checkpoint.shm_handler import _HEADER
        from dlrover_tpu.common import faults

        first = _random_leaves((2048, 1024, 512))
        second = jax.tree.map(lambda x: x + 1, first)
        assert engine.save_to_memory(1, first, block=True)
        handler = engine._shm_handler
        assert handler.load_state_dict()[0] == 1
        if cut == "fault_point":
            faults.install("ckpt_stage_cut:*:raise@1")
        else:
            def gate_of(i):
                def lost():
                    if i == 1:  # the second taken: leaf0 is on its way in
                        raise RuntimeError("transfer lost")
                return lost

            _gate_transfers(monkeypatch, gate_of)
        try:
            assert not engine.save_to_memory(2, second, block=True)
            if cut == "fault_point":
                assert [r["ctx"]["leaf"] for r in faults.fired()] == [0]
        finally:
            faults.reset()
            monkeypatch.undo()
        block = bytes(handler.shared_memory.buf)
        assert _HEADER.unpack(block[: _HEADER.size]) == (0,)
        assert handler.load_state_dict() is None
        assert handler.verified_views() is None
        step, state = engine.load(first)
        assert step is None and state is first  # nothing in storage either
        # The failure is reported once, on the next save, which itself
        # is dispatched and lands.
        assert not engine.save_to_memory(3, second)
        assert engine.wait_staging()
        step, got = engine.load(first)
        assert step == 3
        _assert_trees_bit_equal(got, second)

    @pytest.mark.parametrize("outcome", ["staged", "failed"])
    def test_stager_keeps_nothing_of_the_snapshot(
        self, engine, monkeypatch, outcome
    ):
        """Once a save is staged (or has failed) no device copy of the
        snapshot is alive: not its leaves, not the shards handed to the
        stager, which waits for the next submit holding nothing."""
        import gc
        import weakref

        from dlrover_tpu.checkpoint import engine as engine_mod

        refs = []
        real_take = engine._snapshot.take
        real_begin = engine_mod.begin_host_transfer

        def take(state):
            snap = real_take(state)
            refs.extend(
                weakref.ref(x) for x in jax.tree_util.tree_leaves(snap)
            )
            return snap

        def begin(snap):
            tree = real_begin(snap)
            refs.extend(weakref.ref(e.data._data) for e in tree.values())
            return tree

        monkeypatch.setattr(engine._snapshot, "take", take)
        monkeypatch.setattr(engine_mod, "begin_host_transfer", begin)
        if outcome == "failed":
            def gate_of(i):
                def lost():
                    if i == 1:
                        raise RuntimeError("transfer lost")
                return lost

            _gate_transfers(monkeypatch, gate_of)
        state = {
            k: v for k, v in _mixed_tree().items() if isinstance(v, jax.Array)
        }
        ok = engine.save_to_memory(1, state, block=True)
        assert ok == (outcome == "staged")
        gc.collect()
        assert len(refs) == 2 * len(state)
        assert [r() for r in refs] == [None] * len(refs)
        # The state itself was never the snapshot.
        assert all(not x.is_deleted() for x in state.values())

    def test_transfers_start_two_a_device_largest_first(
        self, devices8, monkeypatch
    ):
        """``_IN_FLIGHT`` transfers a device are started at dispatch, in the
        order the pipeline takes them, and the next of a device as one of
        its shards arrives: never the whole snapshot at once."""
        from dlrover_tpu.checkpoint import engine as engine_mod
        from dlrover_tpu.checkpoint.shm_handler import largest_first

        sizes = {"a": 16, "b": 64, "c": 32, "d": 48, "e": 8}
        mesh = jax.sharding.Mesh(np.array(devices8[:2]), ("x",))
        split = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("x")
        )
        state = {
            k: jax.device_put(jnp.arange(n, dtype=jnp.float32), split)
            for k, n in sizes.items()
        }
        started = []
        real_start = engine_mod._start_next

        def start_next(waiting):
            started.extend(list(waiting)[:1])
            real_start(waiting)

        monkeypatch.setattr(engine_mod, "_start_next", start_next)
        tree = engine_mod.begin_host_transfer(state)
        assert list(tree) == [(f"['{k}']", i) for k in sizes for i in (0, 1)]
        order = largest_first(tree)
        assert [key[0][2] for key in order] == list("bbddccaaee")
        in_flight = 2 * engine_mod._IN_FLIGHT  # two devices
        taken = [id(tree[key].data._data) for key in order]
        assert sorted(map(id, started)) == sorted(taken[:in_flight])
        for n, key in enumerate(order):
            got = np.asarray(tree[key].data)
            want = np.asarray(state[key[0][2]]).reshape(2, -1)[key[1]]
            assert got.tobytes() == want.tobytes()
            # One arrived: the next of its device, if any waits, is started.
            assert len(started) == min(in_flight + n + 1, len(order))
        assert sorted(map(id, started)) == sorted(taken)  # each once

    def test_log_lines_read_as_the_benchmark_reads_them(self, engine):
        """The "staged" and "skipped" lines still match the benchmark's
        regular expressions; ``drain`` and ``memcpy`` are the pipeline's
        ``drain_s`` and ``tail_s``."""
        import logging
        import re

        from dlrover_tpu.common.log import logger

        lines = []

        class Lines(logging.Handler):
            def emit(self, record):
                lines.append(record.getMessage())

        handler, level = Lines(), logger.level
        logger.addHandler(handler)
        logger.setLevel(logging.INFO)
        gate = threading.Event()
        stage = engine._stage_to_shm

        def gated(step, tree, persist):
            gate.wait(10)
            return stage(step, tree, persist)

        engine._stager._process = gated
        tree = _mixed_tree()
        try:
            assert engine.save_to_memory(8, tree)
            assert not engine.save_to_memory(9, tree)  # a drain in flight
            gate.set()
            assert engine.wait_staging()
        finally:
            gate.set()
            logger.removeHandler(handler)
            logger.setLevel(level)
        staged = [m for m in map(re.compile(_STAGED_RE).search, lines) if m]
        assert [m.group(1) for m in staged] == ["8"]
        took = engine.last_save
        assert abs(float(staged[0].group(2)) - took["drain_s"]) < 1e-3
        assert abs(float(staged[0].group(3)) - took["tail_s"]) < 1e-3
        skipped = [m for m in map(re.compile(_SKIPPED_RE).search, lines) if m]
        assert [m.group(1) for m in skipped] == ["9"]


def _bounds_sets(sharding, shape):
    from dlrover_tpu.checkpoint.engine import _slices_to_bounds

    return {
        _slices_to_bounds(index, shape)
        for index in sharding.devices_indices_map(shape).values()
    }


class TestDirectOrAssembled:
    """Saved under fsdp=2 × tp=2 on the 8 virtual devices: the same mesh
    takes every leaf's shards as saved; another mesh pastes the leaves
    whose shard bounds changed.  The span and the counter say which."""

    @pytest.mark.parametrize(
        "restore_mesh", ["same", "other"], ids=["same-mesh", "other-mesh"]
    )
    def test_path_by_saved_bounds(
        self, tmp_path, devices8, monkeypatch, restore_mesh
    ):
        from dlrover_tpu.checkpoint import Checkpointer, StorageType, integrity
        from dlrover_tpu.parallel.mesh import MeshConfig
        from dlrover_tpu.telemetry import events as tevents

        tdir = str(tmp_path / "telemetry")
        monkeypatch.setenv(tevents.ENV_TELEMETRY_DIR, tdir)
        tevents.reset()
        saved_cfg = MeshConfig(dp=2, fsdp=2, tp=2)
        state, shardings, _ = _make_state(saved_cfg, devices8)
        ckpt = Checkpointer(str(tmp_path / "ckpt"), start_saver=True)
        try:
            assert ckpt.save_checkpoint(
                4, state, StorageType.MEMORY, block=True
            )
            target, want = _make_state(
                saved_cfg if restore_mesh == "same"
                else MeshConfig(dp=2, fsdp=4, tp=1),
                devices8, seed=1,
            )[:2]
            counter = integrity._metric("dlrover_ckpt_restore_leaves_total")
            before = {
                p: counter.value(path=p) for p in ("direct", "assembled")
            }
            step, restored = ckpt.load_checkpoint(target, want)
        finally:
            ckpt.close()
            tevents.reset()
        assert step == 4
        saved_sh = jax.tree_util.tree_leaves(shardings)
        want_sh = jax.tree_util.tree_leaves(want)
        leaves = jax.tree_util.tree_leaves(state)
        got = jax.tree_util.tree_leaves(restored)
        direct = 0
        for leaf, new, old_s, new_s in zip(leaves, got, saved_sh, want_sh):
            if not isinstance(leaf, jax.Array):
                continue
            assert new.sharding.is_equivalent_to(new_s, leaf.ndim)
            np.testing.assert_array_equal(np.asarray(leaf), np.asarray(new))
            direct += _bounds_sets(new_s, leaf.shape) <= _bounds_sets(
                old_s, leaf.shape
            )
        arrays = sum(isinstance(x, jax.Array) for x in leaves)
        if restore_mesh == "same":
            assert direct == arrays
        else:
            assert 0 < direct < arrays  # scalars and norms keep their bounds
        ends = [
            e for e in tevents.read_dir(tdir) if e["ev"] == "restore_end"
        ]
        assert len(ends) == 1
        assert ends[0]["source"] == "shm" and ends[0]["step"] == 4
        assert ends[0]["direct_leaves"] == direct
        assert ends[0]["assembled_leaves"] == arrays - direct
        assert ends[0]["bytes"] == sum(
            x.nbytes for x in leaves if isinstance(x, jax.Array)
        )
        assert counter.value(path="direct") - before["direct"] == direct
        assert (
            counter.value(path="assembled") - before["assembled"]
            == arrays - direct
        )

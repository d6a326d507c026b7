"""Same-host IPC primitives: unix-socket-served lock/queue/dict plus a
resource-tracker-safe shared-memory block.

Reference parity: ``dlrover/python/common/multi_process.py:225,346,453,537``
(SharedLock/SharedQueue/SharedDict/SharedMemory) — the substrate of Flash
Checkpoint.  The *server* ends live in the long-lived agent process
(``tpurun``); trainer worker processes attach as clients, so queue/dict state
survives worker restarts — exactly the property elastic training needs.

Protocol: length-prefixed pickled ``(method, kwargs)`` request →
``(ok, value)`` response over a unix stream socket under
``/tmp/dlrover_tpu_sock/``.  Pickle is acceptable here: both ends are
processes of the same job on the same host behind filesystem permissions.
"""

import mmap
import os
import pickle
import queue
import shutil
import socket
import socketserver
import struct
import threading
import time
from multiprocessing import shared_memory, resource_tracker
from typing import Any, Dict, Optional

from dlrover_tpu.common.log import logger

SOCKET_TMP_DIR = os.environ.get(
    "DLROVER_SOCK_DIR", "/tmp/dlrover_tpu_sock"
)

_LEN = struct.Struct("<I")


def clear_sock_dir():
    shutil.rmtree(SOCKET_TMP_DIR, ignore_errors=True)


def _sock_path(kind: str, name: str) -> str:
    job = os.environ.get("DLROVER_JOB_UID", "local")
    path = os.path.join(SOCKET_TMP_DIR, job, f"{kind}_{name}.sock")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    return path


def _send_msg(sock: socket.socket, obj: Any):
    data = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    sock.sendall(_LEN.pack(len(data)) + data)


def _recv_msg(sock: socket.socket) -> Any:
    header = _recv_exact(sock, _LEN.size)
    (size,) = _LEN.unpack(header)
    return pickle.loads(_recv_exact(sock, size))


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("socket closed mid-message")
        buf.extend(chunk)
    return bytes(buf)


def retry_socket(func):
    """Client calls retry while the server end is (re)starting."""

    def wrapper(self, *args, **kwargs):
        retry = kwargs.pop("retry", 30)
        for i in range(retry):
            try:
                return func(self, *args, **kwargs)
            except (FileNotFoundError, ConnectionError, OSError):
                if i == retry - 1:
                    raise
                time.sleep(0.5)

    return wrapper


class _Handler(socketserver.StreamRequestHandler):
    def handle(self):
        try:
            while True:
                try:
                    method, kwargs = _recv_msg(self.request)
                except (ConnectionError, EOFError):
                    return
                try:
                    value = self.server.comm_obj.handle(method, kwargs)
                    _send_msg(self.request, (True, value))
                except Exception as e:  # noqa: BLE001 — fault barrier
                    _send_msg(self.request, (False, repr(e)))
        except BrokenPipeError:
            return


class _Server(socketserver.ThreadingUnixStreamServer):
    daemon_threads = True
    allow_reuse_address = True


class LocalSocketComm:
    """Base for lock/queue/dict: one side creates (serves), others attach."""

    KIND = "comm"

    def __init__(self, name: str = "", create: bool = False):
        self._name = name
        self._path = _sock_path(self.KIND, name)
        self._create = create
        self._server: Optional[_Server] = None
        self._client_lock = threading.Lock()
        self._client: Optional[socket.socket] = None
        if create:
            if os.path.exists(self._path):
                os.unlink(self._path)
            self._server = _Server(self._path, _Handler)
            self._server.comm_obj = self
            threading.Thread(
                target=self._server.serve_forever,
                name=f"{self.KIND}-{name}-server",
                daemon=True,
            ).start()

    @property
    def is_server(self) -> bool:
        return self._server is not None

    def handle(self, method: str, kwargs: Dict[str, Any]):
        return getattr(self, f"_h_{method}")(**kwargs)

    def _connect(self) -> socket.socket:
        # The server (agent saver thread) and its clients (trainer engines)
        # start concurrently; tolerate the listener not being up yet with a
        # bounded retry instead of failing the first save of a job.
        deadline = time.time() + 10.0
        while True:
            sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            try:
                sock.connect(self._path)
                return sock
            except (ConnectionRefusedError, FileNotFoundError):
                sock.close()
                if time.time() >= deadline:
                    raise
                time.sleep(0.1)

    @retry_socket
    def _request(self, method: str, **kwargs):
        if self.is_server:
            return self.handle(method, kwargs)
        with self._client_lock:
            if self._client is None:
                self._client = self._connect()
            try:
                _send_msg(self._client, (method, kwargs))
                ok, value = _recv_msg(self._client)
            except (ConnectionError, OSError):
                self._client.close()
                self._client = None
                raise
        if not ok:
            raise RuntimeError(f"{self.KIND} {method} failed: {value}")
        return value

    def close(self):
        if self._client is not None:
            self._client.close()
            self._client = None
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None
            if os.path.exists(self._path):
                os.unlink(self._path)

    def unlink(self):
        self.close()


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except PermissionError:
        return True


class SharedLock(LocalSocketComm):
    """Cross-process mutex guarding the shm buffer during reads/writes.

    Owner-tracked: acquire records the client's pid, and a blocked acquire
    breaks the lock if the owning process died mid-critical-section (a
    trainer SIGKILLed during its shm memcpy must not wedge checkpointing
    forever — the exact crash Flash Checkpoint exists to survive).
    """

    KIND = "lock"

    def __init__(self, name: str = "", create: bool = False):
        super().__init__(name, create)
        if create:
            self._lock = threading.Lock()
            self._owner_pid = 0
            # Guards owner bookkeeping: acquire/steal/release must be
            # atomic w.r.t. each other (handler threads race).
            self._meta_lock = threading.Lock()

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        return bool(
            self._request(
                "acquire",
                owner=os.getpid(),
                blocking=blocking,
                timeout=timeout,
            )
        )

    def release(self):
        self._request("release", owner=os.getpid())

    def locked(self) -> bool:
        return bool(self._request("locked"))

    def _h_acquire(
        self, owner: int = 0, blocking: bool = True, timeout: float = -1
    ) -> bool:
        deadline = (
            time.time() + timeout if (blocking and timeout > 0) else None
        )
        while True:
            with self._meta_lock:
                if self._lock.acquire(blocking=False):
                    self._owner_pid = owner
                    return True
                holder = self._owner_pid
                if holder and not _pid_alive(holder):
                    # Compare-and-break under the meta lock: only steal if
                    # the dead pid is STILL the recorded owner (another
                    # waiter may have broken + re-acquired in between).
                    logger.warning(
                        "lock %s owner pid %s is dead; breaking the lock",
                        self._name, holder,
                    )
                    self._owner_pid = owner
                    return True  # lock stays held; ownership transferred
            if not blocking:
                return False
            if deadline is not None and time.time() >= deadline:
                return False
            time.sleep(0.05)

    def _h_release(self, owner: int = 0):
        with self._meta_lock:
            if owner and self._owner_pid and owner != self._owner_pid:
                # Stale release (e.g. from a waiter that observed a now-
                # replaced owner): ignore rather than yank a live holder.
                return
            self._owner_pid = 0
            try:
                self._lock.release()
            except RuntimeError:
                pass

    def _h_locked(self) -> bool:
        return self._lock.locked()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()


class SharedQueue(LocalSocketComm):
    """Cross-process FIFO (checkpoint events trainer → agent saver)."""

    KIND = "queue"

    def __init__(self, name: str = "", create: bool = False, maxsize: int = 0):
        super().__init__(name, create)
        if create:
            self._queue: queue.Queue = queue.Queue(maxsize)

    def put(self, obj, block: bool = True, timeout: Optional[float] = None):
        self._request("put", obj=obj, block=block, timeout=timeout)

    def get(self, block: bool = True, timeout: Optional[float] = None):
        # Long-poll server-side in slices so one slow get doesn't wedge the
        # handler thread forever when the queue is shut down.
        deadline = None if timeout is None else time.time() + timeout
        while True:
            wait = 1.0
            if deadline is not None:
                wait = min(wait, deadline - time.time())
                if wait <= 0:
                    raise queue.Empty
            found, obj = self._request("get", timeout=max(wait, 0.01))
            if found:
                return obj
            if not block:
                raise queue.Empty

    def qsize(self) -> int:
        return int(self._request("qsize"))

    def empty(self) -> bool:
        return bool(self._request("empty"))

    def _h_put(self, obj, block=True, timeout=None):
        self._queue.put(obj, block=block, timeout=timeout)

    def _h_get(self, timeout=1.0):
        try:
            return True, self._queue.get(timeout=timeout)
        except queue.Empty:
            return False, None

    def _h_qsize(self):
        return self._queue.qsize()

    def _h_empty(self):
        return self._queue.empty()


class SharedDict(LocalSocketComm):
    """Cross-process dict (checkpoint tensor metadata trainer → agent)."""

    KIND = "dict"

    def __init__(self, name: str = "", create: bool = False):
        super().__init__(name, create)
        if create:
            self._dict: Dict[Any, Any] = {}
            self._dict_lock = threading.Lock()

    def set(self, key, value):
        self._request("set", key=key, value=value)

    def get(self, key, default=None):
        return self._request("get", key=key, default=default)

    def update(self, other: Dict):
        self._request("update", other=other)

    def pop(self, key, default=None):
        return self._request("pop", key=key, default=default)

    def copy(self) -> Dict:
        return self._request("copy")

    def _h_set(self, key, value):
        with self._dict_lock:
            self._dict[key] = value

    def _h_get(self, key, default=None):
        with self._dict_lock:
            return self._dict.get(key, default)

    def _h_update(self, other):
        with self._dict_lock:
            self._dict.update(other)

    def _h_pop(self, key, default=None):
        with self._dict_lock:
            return self._dict.pop(key, default)

    def _h_copy(self):
        with self._dict_lock:
            return dict(self._dict)


class SharedMemory(shared_memory.SharedMemory):
    """POSIX shm whose lifetime is owned by the *agent*, not the resource
    tracker: worker processes must be able to die (and restart) without the
    tracker unlinking the checkpoint buffer under the agent.

    Reference parity: ``common/multi_process.py:537`` (monkeypatched
    unregister).  Python 3.12 has no ``track=False``, so deregister rather
    than monkeypatch globally.
    """

    def __init__(self, name=None, create=False, size=0):
        super().__init__(name=name, create=create, size=size)
        try:
            resource_tracker.unregister(self._name, "shared_memory")
        except Exception:  # noqa: BLE001 — tracker may not know the block
            pass
        self._populate()

    def _populate(self):
        """Map the block with its pages already in this process's page
        table (``MAP_POPULATE``).  Whoever attaches is about to read or
        rewrite the whole block, and on the chip's host a page fault costs
        9 µs: the crc pass over a lazily mapped 5.84 GB checkpoint took
        13.8 s on one thread and 5.8 s on four, over a populated one 1.8 s
        and 0.57 s, and populating took 1 ms (PERF.md, PR 25).  Whoever
        creates one is about to fill it from the save's threads, and a
        block first touched by several threads at once is one the next
        process is slow to attach (PERF.md §6, PR 30)."""
        try:
            populated = mmap.mmap(
                self._fd, self._size,
                flags=mmap.MAP_SHARED | mmap.MAP_POPULATE,
            )
        except (OSError, ValueError):
            return  # the lazy mapping of the base class stays
        self._buf.release()
        self._mmap.close()
        self._mmap, self._buf = populated, memoryview(populated)

    def unlink(self):
        """Unlink guarded: racing unlinks across processes are fine."""
        try:
            super().unlink()
        except FileNotFoundError:
            pass


def create_shared_memory(name: str, create: bool, size: int = 0):
    """Open-or-create helper: returns None when attaching to a block that
    does not exist yet (trainer asking before the first save)."""
    if not create:
        try:
            return SharedMemory(name=name)
        except FileNotFoundError:
            return None
    try:
        return SharedMemory(name=name, create=True, size=size)
    except FileExistsError:
        existing = SharedMemory(name=name)
        if existing.size >= size:
            return existing
        existing.close()
        existing.unlink()
        return SharedMemory(name=name, create=True, size=size)

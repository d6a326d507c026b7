"""Decode worker: a :class:`PagedServingEngine` behind the 2-RPC pipe.

The gateway (``serving/gateway.py``) is the client; each decode worker
hosts a :class:`~dlrover_tpu.rpc.transport.MasterTransport` servicer
answering two typed messages — ``ServeSubmit`` (admit a request) and
``ServePoll`` (collect newly generated tokens, completions and engine
stats).  A background pump thread drives the engine, so poll RPCs never
block behind device dispatches.

Workers carry **no parameter payload over the wire**: the model and its
params are derived deterministically from ``(config args, seed)`` at
startup (:func:`build_tiny_model`), so a SIGKILLed worker's replacement
— spawned with the same CLI args — reproduces the exact same greedy
tokens.  That determinism is what makes the gateway's replay-from-last-
committed-token drill byte-exact (``tests/test_serving_gateway.py``).
"""

import os
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from dlrover_tpu.common import comm
from dlrover_tpu.common.faults import fault_point
from dlrover_tpu.common.log import logger
from dlrover_tpu.rpc.transport import MasterTransport
from dlrover_tpu.serving.engine import PagedServingEngine
from dlrover_tpu.telemetry import tracing as _tracing


def build_tiny_model(
    vocab_size: int = 64,
    hidden_size: int = 32,
    intermediate_size: int = 64,
    num_layers: int = 2,
    num_heads: int = 2,
    num_kv_heads: int = 2,
    max_seq_len: int = 64,
    seed: int = 0,
):
    """(model, params) derived purely from config + seed — the worker's
    startup path AND the test harness's reference path, so both sides
    hold bit-identical weights without shipping arrays."""
    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel

    cfg = LlamaConfig.tiny(
        vocab_size=vocab_size,
        hidden_size=hidden_size,
        intermediate_size=intermediate_size,
        num_layers=num_layers,
        num_heads=num_heads,
        num_kv_heads=num_kv_heads,
        max_seq_len=max_seq_len,
        dtype=jnp.float32,
        param_dtype=jnp.float32,
        scan_layers=False,
        attention_impl="dot",
    )
    model = LlamaModel(cfg)
    params = model.init(
        jax.random.key(seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def reference_margins(
    forward, params, tokens: List[int], prompt_len: int, max_len: int
):
    """The plain reference for a finished completion: ONE non-paged,
    cache-free forward over prompt + completion.  Returns ``(row_max,
    margin)`` per generated position — the reference row's maximum logit
    and how far below it the token the engine chose sits.  Exact token
    equality is too strict a check on seeded weights, whose logits
    nearly tie; a margin within the dtype's tolerance is not.

    ``forward`` is the server's one ``jax.jit(model.apply)``.  A sequence
    is no longer than the engine would have served, and is padded to
    ``max_len`` (causal attention: the tail changes no earlier row), so
    the forward is traced and compiled for one shape, once."""
    if not 0 < prompt_len < len(tokens) <= max_len:
        raise ValueError(
            f"verify takes 0 < prompt_len < len(tokens) <= {max_len}, "
            f"got prompt_len={prompt_len}, {len(tokens)} tokens"
        )
    n = len(tokens)
    ids = jnp.asarray(tokens + [0] * (max_len - n), jnp.int32)[None, :]
    logits = forward({"params": params}, ids)[0]
    # Row t predicts token t + 1.
    rows = logits[prompt_len - 1: n - 1].astype(jnp.float32)
    chosen = jnp.take_along_axis(
        rows, ids[0, prompt_len:n, None], axis=-1
    )[:, 0]
    row_max = rows.max(axis=-1)
    return row_max.tolist(), (row_max - chosen).tolist()


def warmup_engine(model, params, **engine_kw) -> None:
    """Pre-compile the serving tick before the worker signals ready.

    Runs a throwaway engine of the same geometry through one tiny
    prompt per prefill-chunk bucket plus a couple of decode ticks; the
    jitted tick builders are cached per geometry (engine.py), so the
    real engine's first request then hits the jit cache.  This is what
    makes a pre-spawned standby replica a *warm* standby: promotion
    must not pay multi-second compiles inside the reform window."""
    eng = PagedServingEngine(model, params, **engine_kw)
    chunk = eng._chunk
    for n in sorted({chunk, max(1, chunk // 2), max(1, chunk // 4)}):
        eng.submit([1] * n, gen_budget=2)
    while eng.has_work():
        eng.step()


class ServingWorkerServer:
    """One decode replica: engine + transport + pump thread."""

    def __init__(
        self,
        model,
        params,
        *,
        port: int = 0,
        slots: int = 4,
        max_len: int = 64,
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        chunk_size: Optional[int] = None,
        eos_id: Optional[int] = None,
        temperature: float = 1e-6,
        seed: int = 0,
        pump_idle_s: float = 0.005,
        tick_delay_s: float = 0.0,
    ):
        self._params, self._max_len = params, max_len
        self._reference = jax.jit(model.apply)  # ServeVerify's forward
        self._engine = PagedServingEngine(
            model,
            params,
            slots=slots,
            max_len=max_len,
            block_size=block_size,
            num_blocks=num_blocks,
            chunk_size=chunk_size,
            eos_id=eos_id,
            temperature=temperature,
            seed=seed,
        )
        # One lock serializes engine mutation: the pump thread's step()
        # vs the RPC handlers' submit/pop (DLR011: the handlers never do
        # device work — they only move host lists).
        self._lock = threading.Lock()
        self._completions: List[Dict[str, Any]] = []
        self._uid = f"{os.getpid()}-{int(time.time() * 1000)}"
        self._pump_idle_s = pump_idle_s
        # Deliberate per-tick brake (chaos/SLO drills: a slowed replica
        # drives TTFT into burn without touching the model).
        self._tick_delay_s = max(float(tick_delay_s), 0.0)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._transport = MasterTransport(self, port=port)
        self.port = self._transport.port

    # -- servicer contract (rpc/transport.py) ------------------------------
    def get(self, node_id: int, node_type: str, message):
        if isinstance(message, comm.ServeSubmit):
            try:
                with self._lock:
                    self._engine.submit(
                        list(message.prompt),
                        gen_budget=message.gen_budget,
                        request_id=message.request_id,
                        orig_prompt_len=message.orig_prompt_len,
                        trace=_tracing.from_wire(
                            getattr(message, "trace", "")
                        ),
                    )
                return comm.ServeSubmitResult(accepted=True)
            except ValueError as e:
                return comm.ServeSubmitResult(accepted=False, reason=str(e))
        if isinstance(message, comm.ServeVerify):
            # Outside the engine lock: the reference forward shares no
            # state with the engine, and the pump must keep ticking.
            row_max, margin = reference_margins(
                self._reference, self._params, list(message.tokens),
                int(message.prompt_len), self._max_len,
            )
            return comm.ServeVerifyResult(row_max=row_max, margin=margin)
        if isinstance(message, comm.ServeControl):
            with self._lock:
                if message.publish_prefix >= 0:
                    self._engine.set_prefix_publish(
                        bool(message.publish_prefix)
                    )
            return comm.ServeControlResult(ok=True)
        if isinstance(message, comm.ServePoll):
            with self._lock:
                for _ in range(message.max_ticks):
                    if not self._engine.has_work():
                        break
                    self._collect(self._engine.step())
                emitted = self._engine.pop_emitted()
                completions, self._completions = self._completions, []
                stats = self._engine.stats()
            return comm.ServeProgress(
                emitted={int(k): list(v) for k, v in emitted.items()},
                completions=completions,
                stats={k: _plain(v) for k, v in stats.items()},
                worker_uid=self._uid,
            )
        raise ValueError(f"unhandled serve message {type(message).__name__}")

    def report(self, node_id: int, node_type: str, message) -> bool:
        return True

    # -- pump --------------------------------------------------------------
    def _collect(self, done) -> None:
        for c in done:
            self._completions.append({
                "request_id": c.request_id,
                "tokens": list(c.tokens),
                "prompt_len": c.prompt_len,
                "finished_reason": c.finished_reason,
                "submitted_at": c.submitted_at,
                "finished_at": c.finished_at,
            })

    def _pump(self) -> None:
        while not self._stop.is_set():
            # Chaos hook OUTSIDE the lock: a `stall` action here wedges
            # the tick loop (no engine progress) while the RPC handlers
            # stay responsive and alive() stays True — the exact
            # wedged-but-alive shape the fleet's health check ejects.
            fault_point("serve_replica_wedge", worker=self._uid)
            with self._lock:
                stepped = False
                if self._engine.has_work():
                    self._collect(self._engine.step())
                    stepped = True
            if stepped:
                if self._tick_delay_s:
                    self._stop.wait(self._tick_delay_s)
                continue
            self._stop.wait(self._pump_idle_s)

    def start(self) -> None:
        self._transport.start()
        self._thread = threading.Thread(
            target=self._pump, name="serve-pump", daemon=True
        )
        self._thread.start()
        logger.info("serving worker %s on port %s", self._uid, self.port)

    def stop(self, grace: float = 1.0) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=grace)
            self._thread = None
        self._transport.stop(grace)


def _plain(v):
    """Stats values → msgpack-safe scalars."""
    if isinstance(v, bool) or v is None or isinstance(v, str):
        return v
    if isinstance(v, int):
        return int(v)
    if isinstance(v, float):
        return float(v)
    try:
        return float(v)
    except (TypeError, ValueError):
        return str(v)

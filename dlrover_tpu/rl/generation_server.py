"""External rollout-generation server: the vLLM-backend analog.

Reference parity: ``atorch/atorch/rl/vllm_backend.py:49`` — RLHF
experience generation delegated to a separate inference-server process,
with the trainer pushing fresh actor weights between PPO iterations.
TPU mapping: the server is a plain process holding its own copy of the
actor on its own devices; the transport is the framework's msgpack RPC
(``rpc/transport.py``), so the whole path is the same wire stack the
control plane uses — no extra dependency and the same typed-message
discipline.

Server:  ``python -m dlrover_tpu.rl.generation_server --port P \
          --model-factory pkg.module:factory``
Client:  ``ExternalGenerationBackend("host:P")`` — a callable matching
``RLHFEngine``'s ``generation_backend`` contract; it pushes the actor
params whenever they changed (content-hashed), then requests tokens.
"""

import argparse
import hashlib
import threading
import time
from typing import Dict, Optional, Tuple

import numpy as np

from dlrover_tpu.common.comm import comm_message
from dlrover_tpu.common.log import logger
from dlrover_tpu.data.coworker import decode_batch, encode_batch
from dlrover_tpu.rpc.transport import MasterTransport, TransportClient


# -- wire messages ----------------------------------------------------------


@comm_message
class GenerateRollouts:
    prompts: bytes = b""  # encode_batch({"prompts": (b, p) int32})
    gen_len: int = 32
    temperature: float = 1.0
    seed: int = 0


@comm_message
class RolloutsReply:
    # encode_batch({"tokens": (b, p+g) int32, "mask": (b, p+g) f32})
    data: bytes = b""
    params_version: int = 0


@comm_message
class PushActorParams:
    blob: bytes = b""  # npz of {keystr: array}
    version: int = 0


@comm_message
class GenServerStatusRequest:
    pass


@comm_message
class GenServerStatus:
    params_version: int = 0
    ready: bool = False
    generated: int = 0


# Wire framing is data/coworker.py's no-pickle npz codec
# (encode_batch/decode_batch) — one implementation, one drift surface.


def pack_params(params) -> bytes:
    import jax

    flat = {
        jax.tree_util.keystr(p): np.asarray(v)
        for p, v in jax.tree_util.tree_flatten_with_path(params)[0]
    }
    return encode_batch(flat)


def unpack_params(blob: bytes, like) -> object:
    """Rebuild the params pytree of ``like``'s structure from the blob."""
    import jax

    flat = decode_batch(blob)
    leaves = []
    for p, _ in jax.tree_util.tree_flatten_with_path(like)[0]:
        leaves.append(flat[jax.tree_util.keystr(p)])
    treedef = jax.tree_util.tree_structure(like)
    return jax.tree_util.tree_unflatten(treedef, leaves)


# -- server -----------------------------------------------------------------


class GenerationServicer:
    """get/report endpoint pair, same protocol as the master servicer.

    ``continuous_slots > 0`` serves rollouts through the
    continuous-batching slot pool (``rl/serving.py``) instead of one
    monolithic batch: a rollout request larger than the pool streams
    through ``slots`` KV caches with mid-flight turnover, so server
    memory is bounded by the pool — the vLLM-backend serving property
    (reference vllm_backend.py:49) on static TPU shapes."""

    def __init__(self, model, continuous_slots: int = 0,
                 max_len: int = 512, max_prompt: int = 128):
        self.model = model
        self.params = None
        self.params_version = 0
        self.generated = 0
        self._continuous_slots = continuous_slots
        self._max_len = max_len
        self._max_prompt = max_prompt
        # (params, version) must change together: generation snapshots
        # them atomically so a concurrent push can never make the reply
        # claim a version the tokens were not sampled under.
        self._params_lock = threading.Lock()

    def report(self, node_id, node_type, message) -> bool:
        if isinstance(message, PushActorParams):
            if self.params is None:
                # first push defines the tree structure
                import jax.numpy as jnp

                flat = {
                    k: jnp.asarray(v)
                    for k, v in decode_batch(message.blob).items()
                }
                params = self._tree_from_flat(flat)
            else:
                params = unpack_params(message.blob, self.params)
            with self._params_lock:
                self.params = params
                self.params_version = message.version
            logger.info("actor params v%s received", message.version)
            return True
        raise ValueError(f"unknown report {type(message).__name__}")

    def _generate_continuous(self, params, prompts, message):
        """Stream a (b, p) rollout batch through the slot pool; returns
        the same fixed-shape (tokens, mask) contract as the batch
        sampler.  The pool is sized to p + gen_len exactly, so every
        request runs its full budget (no eos in the rollout protocol)
        and rows come back uniform — a request the server's --max-len
        cannot hold fails LOUDLY instead of returning truncated rows the
        mask would claim are generated."""
        import numpy as np

        from dlrover_tpu.rl.serving import ContinuousBatchingEngine

        b, p = prompts.shape
        total = p + message.gen_len
        if total > self._max_len:
            raise RuntimeError(
                f"rollout needs p+gen_len={total} but the server was "
                f"started with max_len={self._max_len}; raise --max-len"
            )
        engine = ContinuousBatchingEngine(
            self.model, params,
            slots=min(self._continuous_slots, b),
            max_len=total,
            max_prompt=max(p, 1),
            temperature=message.temperature,
            seed=message.seed,
        )
        out = engine.generate(
            [list(map(int, row)) for row in prompts],
            gen_budget=message.gen_len,
        )
        tokens = np.zeros((b, total), np.int32)
        for i, rid in enumerate(sorted(out)):
            row = out[rid].tokens
            assert len(row) == total, (len(row), total)
            tokens[i] = row
        mask = np.concatenate(
            [np.zeros((b, p), np.float32),
             np.ones((b, message.gen_len), np.float32)], axis=1,
        )
        return tokens, mask

    @staticmethod
    def _tree_from_flat(flat: Dict[str, object]):
        """keystr like ``['a']['b']`` -> nested dict tree."""
        root: Dict = {}
        for key, value in flat.items():
            parts = [
                p.strip("'\"")
                for p in key.strip("[]").split("][")
            ]
            node = root
            for part in parts[:-1]:
                node = node.setdefault(part, {})
            node[parts[-1]] = value
        return root

    def get(self, node_id, node_type, message):
        if isinstance(message, GenServerStatusRequest):
            return GenServerStatus(
                params_version=self.params_version,
                ready=self.params is not None,
                generated=self.generated,
            )
        if isinstance(message, GenerateRollouts):
            with self._params_lock:
                params = self.params
                version = self.params_version
            if params is None:
                raise RuntimeError(
                    "no actor params pushed yet (PushActorParams)"
                )
            import jax
            import jax.numpy as jnp

            from dlrover_tpu.rl.generation import sample_tokens

            prompts = jnp.asarray(
                decode_batch(message.prompts)["prompts"]
            )
            if self._continuous_slots > 0:
                tokens, mask = self._generate_continuous(
                    params, np.asarray(prompts), message
                )
            else:
                tokens, mask = sample_tokens(
                    self.model.apply,
                    params,
                    prompts,
                    jax.random.key(message.seed),
                    message.gen_len,
                    message.temperature,
                )
            self.generated += int(prompts.shape[0])
            return RolloutsReply(
                data=encode_batch(
                    {
                        "tokens": np.asarray(tokens),
                        "mask": np.asarray(mask),
                    }
                ),
                params_version=version,
            )
        raise ValueError(f"unknown get {type(message).__name__}")


class GenerationServer:
    def __init__(self, model, port: int = 0, continuous_slots: int = 0,
                 max_len: int = 512, max_prompt: int = 128):
        self.servicer = GenerationServicer(
            model, continuous_slots=continuous_slots,
            max_len=max_len, max_prompt=max_prompt,
        )
        self.transport = MasterTransport(self.servicer, port=port)
        self.port = self.transport.port

    @property
    def addr(self) -> str:
        return f"127.0.0.1:{self.port}"

    def start(self):
        self.transport.start()
        logger.info("generation server on %s", self.addr)

    def stop(self):
        self.transport.stop(grace=1)


# -- client backend ---------------------------------------------------------


class ExternalGenerationBackend:
    """``generation_backend`` callable backed by a remote server.

    Pushes the actor params when (and only when) their content changed —
    the analog of the reference's vLLM weight reload between PPO
    iterations.
    """

    def __init__(self, addr: str, timeout: float = 60.0):
        self._client = TransportClient(addr, timeout=timeout)
        self._digest: Optional[str] = None
        self._version = 0
        self._last_leaves: Optional[tuple] = None

    def ready(self, timeout: float = 30.0) -> bool:
        return self._client.ready(timeout)

    def sync_params(self, params) -> int:
        import jax

        leaves = tuple(jax.tree_util.tree_leaves(params))
        # Fast path: identical leaf OBJECTS mean no update happened —
        # skip the full device->host serialize.  Strong references are
        # held, so object addresses cannot be recycled under us, and the
        # path only applies to immutable jax.Arrays (a mutable numpy
        # leaf could change content without changing identity).
        if (
            self._last_leaves is not None
            and len(leaves) == len(self._last_leaves)
            and all(
                a is b for a, b in zip(leaves, self._last_leaves)
            )
            and all(isinstance(x, jax.Array) for x in leaves)
        ):
            return self._version
        blob = pack_params(params)
        digest = hashlib.sha256(blob).hexdigest()
        if digest != self._digest:
            ok = self._client.report(
                0, "rl",
                PushActorParams(blob=blob, version=self._version + 1),
            )
            if not ok:
                raise RuntimeError(
                    "generation server rejected the actor-params push"
                )
            # bump/record only after the server confirmed — a failed
            # push must not leave the client version ahead of the server
            self._version += 1
            self._digest = digest
        # The identity fast-path may only be armed once the server provably
        # holds this content (push confirmed, or digest already matched); a
        # failed push must force a re-serialize on the retry, or rollouts
        # silently run on stale actor weights.
        self._last_leaves = leaves
        return self._version

    def __call__(
        self, params, prompts, rng, gen_len: int, temperature: float
    ) -> Tuple[np.ndarray, np.ndarray]:
        import jax

        self.sync_params(params)
        seed = int(
            jax.random.randint(rng, (), 0, np.iinfo(np.int32).max)
        )
        reply = self._client.get(
            0,
            "rl",
            GenerateRollouts(
                prompts=encode_batch(
                    {"prompts": np.asarray(prompts)}
                ),
                gen_len=gen_len,
                temperature=temperature,
                seed=seed,
            ),
        )
        if reply.params_version != self._version:
            raise RuntimeError(
                f"server generated with stale params "
                f"(v{reply.params_version}, pushed v{self._version})"
            )
        data = decode_batch(reply.data)
        return data["tokens"], data["mask"]

    def status(self) -> GenServerStatus:
        return self._client.get(0, "rl", GenServerStatusRequest())

    def close(self):
        self._client.close()


# -- CLI --------------------------------------------------------------------


def _resolve_factory(spec: str):
    module_name, _, attr = spec.partition(":")
    import importlib

    module = importlib.import_module(module_name)
    return getattr(module, attr or "model_factory")


def main(argv=None):
    p = argparse.ArgumentParser("dlrover-tpu-generation-server")
    p.add_argument("--port", type=int, default=0)
    p.add_argument(
        "--model-factory",
        required=True,
        help="pkg.module:callable returning the actor flax module",
    )
    p.add_argument(
        "--ready-file", default="",
        help="touch this path once serving (for supervisors)",
    )
    p.add_argument(
        "--continuous-slots", type=int, default=0,
        help="serve rollouts through a continuous-batching slot pool of "
             "this size (0 = monolithic batch sampling); bounds server "
             "KV memory at slots x max_len regardless of request size",
    )
    p.add_argument(
        "--max-len", type=int, default=512,
        help="continuous mode: largest p+gen_len the pool will hold; a "
             "rollout needing more fails loudly rather than truncating",
    )
    args = p.parse_args(argv)
    from dlrover_tpu.common.platform import configure_compile_cache

    configure_compile_cache()
    model = _resolve_factory(args.model_factory)()
    server = GenerationServer(
        model, port=args.port, continuous_slots=args.continuous_slots,
        max_len=args.max_len,
    )
    server.start()
    if args.ready_file:
        with open(args.ready_file, "w") as f:
            f.write(str(server.port))
    try:
        while True:
            time.sleep(60)
    except KeyboardInterrupt:
        server.stop()


if __name__ == "__main__":
    main()

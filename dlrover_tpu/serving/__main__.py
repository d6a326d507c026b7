"""Real-process decode-worker entrypoint.

``python -m dlrover_tpu.serving --ready-file f --vocab 64 ...`` builds
the deterministic tiny model from the CLI args (no parameter shipping
— see ``worker.build_tiny_model``), starts a
:class:`~dlrover_tpu.serving.worker.ServingWorkerServer` on an
ephemeral port and writes a JSON ready file ``{"name", "port", "pid",
"uid", "device"}`` once serving — the same handshake idiom as the kv shard
entrypoint (``kv_service/__main__.py``).  Used by the gateway's
``ProcessReplica`` and the SIGKILL chaos drill, which need the decode
worker to be a genuinely separate OS process (killable with SIGKILL).
"""

import argparse
import json
import os
import signal
import sys
import time

from dlrover_tpu.serving.worker import (
    ServingWorkerServer,
    build_tiny_model,
    warmup_engine,
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="dlrover_tpu serving decode worker"
    )
    ap.add_argument("--name", default="decode-0")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--intermediate", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=2)
    ap.add_argument("--kv-heads", type=int, default=2)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="KV pool blocks (0 = dense-equivalent default)")
    ap.add_argument("--chunk-size", type=int, default=0,
                    help="prefill chunk width (0 = block size)")
    ap.add_argument("--eos-id", type=int, default=-1,
                    help="eos token (-1 = none)")
    ap.add_argument("--temperature", type=float, default=1e-6)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ready-file", default=None,
                    help="write a JSON handshake here once serving")
    ap.add_argument("--events-dir", default=None,
                    help="telemetry directory to stream events/spans "
                         "into (default: the process-global one)")
    ap.add_argument("--tick-sleep-s", type=float, default=0.0,
                    help="deliberate per-tick brake for SLO/chaos "
                         "drills (0 = full speed)")
    args = ap.parse_args(argv)

    if args.events_dir:
        from dlrover_tpu.telemetry import events as _events

        # One stream per incarnation (rank = pid) so a SIGKILLed
        # replica's replacement never appends to its predecessor's file.
        _events.configure(
            directory=args.events_dir, role="decode", rank=os.getpid()
        )

    import jax

    from dlrover_tpu.common.platform import configure_compile_cache

    # A respawned replica must find what its predecessor compiled.
    configure_compile_cache()
    model, params = build_tiny_model(
        vocab_size=args.vocab,
        hidden_size=args.hidden,
        intermediate_size=args.intermediate,
        num_layers=args.layers,
        num_heads=args.heads,
        num_kv_heads=args.kv_heads,
        max_seq_len=args.max_len,
        seed=args.seed,
    )
    engine_kw = dict(
        slots=args.slots,
        max_len=args.max_len,
        block_size=args.block_size,
        num_blocks=args.num_blocks or None,
        chunk_size=args.chunk_size or None,
        eos_id=None if args.eos_id < 0 else args.eos_id,
        temperature=args.temperature,
        seed=args.seed,
    )
    # Compile before the ready handshake: the gateway may promote this
    # replica mid-reform and its first request must not pay the jit.
    warmup_engine(model, params, **engine_kw)
    server = ServingWorkerServer(
        model,
        params,
        port=args.port,
        tick_delay_s=args.tick_sleep_s,
        **engine_kw,
    )
    server.start()

    stop = {"flag": False}

    def _term(signum, frame):
        stop["flag"] = True

    signal.signal(signal.SIGTERM, _term)
    signal.signal(signal.SIGINT, _term)

    if args.ready_file:
        devices = jax.devices()
        payload = {
            "name": args.name,
            "port": server.port,
            "pid": os.getpid(),
            "uid": server._uid,
            # What this replica serves from, as JAX reports it: a worker
            # on the wrong device must not be able to say nothing.
            "device": {
                "platform": devices[0].platform,
                "kind": devices[0].device_kind,
                "count": len(devices),
            },
        }
        tmp = args.ready_file + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, args.ready_file)

    try:
        while not stop["flag"]:
            time.sleep(0.2)
    finally:
        server.stop(grace=1.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Training worker for the goodput harness (run under tpurun).

Instrumented flagship-architecture training loop: logs a timeline event
stream (worker_start / restore_done / step) to the JSONL file named by
``GOODPUT_EVENTS`` so ``goodput.py`` can reconstruct productive time and
per-recovery breakdowns.  Checkpoints through the Flash Checkpoint engine:
async MEMORY save every step (dispatch-only cost), DISK persist every
``GOODPUT_DISK_EVERY`` steps; on start it does the shm-first restore and
resumes from the last staged step — the product behavior under test.

Reference analog: the torch trainers the reference's goodput story is
measured on (``dlrover/README.md:55-56``).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_T_START = time.time()  # before any heavy import — part of recovery cost

if os.environ.get("GOODPUT_TRACE_STALL"):
    import faulthandler

    faulthandler.dump_traceback_later(
        float(os.environ["GOODPUT_TRACE_STALL"]), repeat=True
    )

EVENTS = os.environ["GOODPUT_EVENTS"]
DEADLINE = float(os.environ["GOODPUT_DEADLINE"])
RESTART = int(os.environ.get("DLROVER_RESTART_COUNT", "0"))


def emit(ev: str, **kw):
    kw.update(ev=ev, t=time.time(), pid=os.getpid(), restart=RESTART)
    with open(EVENTS, "a") as f:
        f.write(json.dumps(kw) + "\n")


# Tag standby starts so the analyzer can tell real (re)starts from
# pre-warmed spares parking in the background.
_IS_STANDBY = bool(os.environ.get("DLROVER_STANDBY_FIFO"))
emit("worker_start", t_override=_T_START, standby=_IS_STANDBY)


def _promote_telemetry_stream(restart: int):
    """A promoted standby IS the worker now: rebind the process-global
    telemetry log from the quarantined "standby" stream onto the worker
    stream (events.EventLog defaults role="standby" while
    DLROVER_STANDBY_FIFO is set) and mark the incarnation change."""
    try:
        from dlrover_tpu.telemetry import events as tevents

        tevents.configure(role="worker", attempt=restart)
        tevents.emit("process_start", promoted=True)
    except Exception:  # noqa: BLE001 — harness telemetry is best-effort
        pass


def main():
    global RESTART
    import jax

    from dlrover_tpu.common.platform import virtual_cpu_devices

    # CPU mode trains on a virtual mesh; a no-op on any other platform.
    virtual_cpu_devices(int(os.environ.get("GOODPUT_NDEV", "8")))

    import jax.numpy as jnp
    import numpy as np
    import optax

    from dlrover_tpu.agent.standby import standby_barrier
    from dlrover_tpu.checkpoint import Checkpointer, StorageType
    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.parallel.sharding import PRESET_RULES
    from dlrover_tpu.trainer.step import (
        create_sharded_state,
        make_train_step,
    )

    ckpt_dir = os.environ["GOODPUT_CKPT_DIR"]
    disk_every = int(os.environ.get("GOODPUT_DISK_EVERY", "25"))
    seq = int(os.environ.get("GOODPUT_SEQ", "256"))
    batch = int(os.environ.get("GOODPUT_BATCH", "4"))
    layers = int(os.environ.get("GOODPUT_LAYERS", "4"))
    hidden = int(os.environ.get("GOODPUT_HIDDEN", "384"))
    vocab = int(os.environ.get("GOODPUT_VOCAB", "8192"))

    # Standby parking phase.  "post_warmup" (default): park after state
    # build + compile — the fastest promotion, but needs its own devices
    # (virtual CPU mesh).  "pre_device": park after the heavy imports but
    # BEFORE the first backend touch — the single-real-chip mode, where
    # the active worker owns the chip and the standby may not acquire it;
    # promotion pays device init + (persistent-cache) compile, but never
    # interpreter start + imports (the "cold-warm" split, round-3 verdict
    # #2: the cold start is not irreducible).
    park_early = (
        os.environ.get("GOODPUT_STANDBY_PHASE", "post_warmup")
        == "pre_device"
    )
    activation = None
    if park_early:
        activation = standby_barrier()  # no backend touch above this line
        if activation is not None:
            RESTART = int(activation.get("restart_count", RESTART))
            emit("activated", phase="pre_device")
            _promote_telemetry_stream(RESTART)

    devices = jax.devices()
    platform = devices[0].platform
    mesh = build_mesh(
        MeshConfig(dp=1, fsdp=-1) if len(devices) > 1 else MeshConfig(dp=-1),
        devices,
    )
    cfg = LlamaConfig(
        vocab_size=vocab,
        hidden_size=hidden,
        intermediate_size=hidden * 8 // 3,
        num_layers=layers,
        num_heads=max(hidden // 64, 1),
        num_kv_heads=max(hidden // 64, 1),
        max_seq_len=seq,
        attention_impl="splash" if platform == "tpu" else "dot",
        scan_layers=False,
        logits_f32_output=False,
    )
    model = LlamaModel(cfg)
    # dp on the virtual CPU mesh: fsdp's per-layer all-gathers are
    # pathological when 8 "devices" share one CPU (measured 10.3s vs
    # 5.7s per step); elasticity — the subject here — is sharding-
    # agnostic, and the multi-chip shardings are certified separately by
    # __graft_entry__.dryrun_multichip.
    rules = PRESET_RULES[os.environ.get("GOODPUT_RULES", "dp")]
    rng = np.random.RandomState(1234)
    ids = rng.randint(0, vocab, size=(batch, seq + 1))
    sample = {
        "input_ids": jnp.asarray(ids[:, :-1], jnp.int32),
        "labels": jnp.asarray(ids[:, 1:], jnp.int32),
    }
    opt = optax.adamw(3e-4, b2=0.95)
    state, shardings = create_sharded_state(
        model, opt, mesh, rules, jax.random.key(0), sample
    )
    train_step = make_train_step(model, mesh, rules, shardings)
    emit(
        "init_done",
        platform=platform,
        n_devices=len(devices),
        jax_platforms=os.environ.get("JAX_PLATFORMS", ""),
    )

    # Save arrays only — TrainState's apply_fn/tx are code, rebuilt here.
    def view(s):
        return {"params": s.params, "opt_state": s.opt_state, "step": s.step}

    view_shardings = view(shardings)

    # Compile warmup on the INIT state (discarded on restore) — in a
    # standby this runs before parking, taking compilation off the
    # recovery critical path entirely.
    warm_state, metrics = train_step(state, sample)
    float(metrics["loss"])  # the fetch waits for the step
    # Also warm the POST-RESTORE input-layout variant: a checkpoint
    # restore feeds device_put arrays, whose layouts differ from jit
    # outputs — without this, the first step after restore recompiles
    # (~6s measured), putting compilation back on the recovery path.
    roundtrip = jax.device_put(
        jax.tree.map(lambda x: np.asarray(x), view(warm_state)),
        view_shardings,
    )
    warm_state2, metrics = train_step(
        state.replace(**roundtrip), sample
    )
    float(metrics["loss"])
    # Attach the checkpoint engine and compile its snapshot path BEFORE
    # parking: post-promotion the first save must be dispatch-only.
    ckpt = Checkpointer(ckpt_dir)
    ckpt.warmup(view(warm_state2))
    emit("warmup_done")

    was_standby = _IS_STANDBY
    if not park_early:
        activation = standby_barrier()  # parks here if this is the standby
        if activation is not None:
            RESTART = int(activation.get("restart_count", RESTART))
            emit("activated", phase="post_warmup")
            _promote_telemetry_stream(RESTART)

    t0 = time.time()
    step, restored = ckpt.load_checkpoint(view(state), view_shardings)
    restore_latency = time.time() - t0
    if step is not None:
        state = state.replace(**restored)
    else:
        state = warm_state  # nothing checkpointed yet: keep warm progress
    start_step = int(step) if step is not None else 1
    emit(
        "restore_done",
        step=start_step,
        latency=restore_latency,
        hit=step is not None,
        was_standby=was_standby,
    )

    n = start_step
    if step is None:
        ckpt.save_checkpoint(n, view(state), StorageType.MEMORY)

    while time.time() < DEADLINE:
        t = time.time()
        state, metrics = train_step(state, sample)
        float(metrics["loss"])
        n += 1
        dt = time.time() - t
        to_disk = n % disk_every == 0
        ckpt.save_checkpoint(
            n, view(state),
            StorageType.DISK if to_disk else StorageType.MEMORY,
        )
        emit("step", step=n, dt=dt, disk=to_disk)
        # One write per step into the product telemetry channel too —
        # publish_progress stamps the snapshot AND emits the telemetry
        # "step" event the online goodput accountant attributes from.
        from dlrover_tpu.agent.monitor.progress import publish_progress

        publish_progress(n)
    # flush the in-flight staging so the next incarnation (if the window
    # is extended) restores the newest step, then leave promptly.
    ckpt.wait_staging(timeout=30)
    emit("worker_exit", step=n)
    ckpt.close()


if __name__ == "__main__":
    main()

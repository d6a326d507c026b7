"""Training worker of ``chip_smoke.py`` (run under ``tpurun``).

One incarnation trains, the next resumes (``DLROVER_RESTART_COUNT`` says
which this process is; a restart that finds nothing to restore fails).

* first incarnation: build the sharded state, take ``steps`` steps of
  ``trainer/step.py::make_train_step`` on one fixed seeded batch, save to
  shared memory after step ``save_at`` (blocking, so shm holds exactly that
  step), step on, report, then wait to be SIGKILLed;
* resumed incarnation: restore from shm, step twice and report the losses
  at ``save_at + 1`` and ``save_at + 2`` for the parent to hold against the
  killed incarnation's (the second depends on the restored moments).

With ``chips == 4`` the first incarnation also steps the same seed and
batch on a one-device mesh first, and reports how the state is spread.

Everything it learns goes to the JSONL file named by ``CHIP_SMOKE_EVENTS``;
the parent, which never imports JAX, judges it.  A standby parks before its
first backend touch: the active worker owns the chip.
"""

import json
import os
import sys
import time

_T_START = time.time()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

EVENTS = os.environ["CHIP_SMOKE_EVENTS"]
SPEC = json.loads(os.environ["CHIP_SMOKE_SPEC"])


def emit(ev: str, **kw):
    kw.update(ev=ev, t=time.time(), pid=os.getpid())
    with open(EVENTS, "a") as f:
        f.write(json.dumps(kw) + "\n")


emit("start", t_start=_T_START,
     standby=bool(os.environ.get("DLROVER_STANDBY_FIFO")))


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from flax.linen import partitioning as nn_partitioning

    from dlrover_tpu.agent.standby import standby_barrier
    from dlrover_tpu.checkpoint import Checkpointer, StorageType
    from dlrover_tpu.common.constants import NodeEnv
    from dlrover_tpu.common.platform import virtual_cpu_devices
    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh, use_mesh
    from dlrover_tpu.parallel.sharding import PRESET_RULES
    from dlrover_tpu.telemetry import metrics as tmetrics
    from dlrover_tpu.trainer.step import (
        create_sharded_state,
        make_train_step,
    )

    cache_events = {"hits": 0, "misses": 0}

    def _on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(_on_event)

    # No backend touch above this line: a standby parks here while the
    # active worker holds the chip.
    if standby_barrier() is not None:
        emit("activated")
    chips = int(SPEC["chips"])
    virtual_cpu_devices(chips)  # the CPU rehearsal's mesh; no-op on a TPU
    devices = jax.devices()
    emit(
        "device",
        platform=devices[0].platform,
        kind=devices[0].device_kind,
        count=len(devices),
        jax_platforms=os.environ.get("JAX_PLATFORMS", ""),
        cache_dir=jax.config.jax_compilation_cache_dir,
    )
    if len(devices) != chips:
        raise RuntimeError(f"need {chips} devices, JAX reports {devices}")

    cfg = LlamaConfig.llama2_7b(
        **SPEC["widths"],
        num_layers=SPEC["layers"],
        max_seq_len=SPEC["seq"],
        attention_impl="splash",
        scan_layers=False,
        logits_f32_output=False,
    )
    model = LlamaModel(cfg)
    ids = np.random.RandomState(SPEC["seed"]).randint(
        0, cfg.vocab_size, size=(SPEC["batch"], SPEC["seq"] + 1)
    )
    batch = {
        "input_ids": jnp.asarray(ids[:, :-1], jnp.int32),
        "labels": jnp.asarray(ids[:, 1:], jnp.int32),
    }
    steps, save_at = int(SPEC["steps"]), int(SPEC["save_at"])

    def build(mesh_cfg, rules_name, devs):
        mesh = build_mesh(mesh_cfg, devs)
        rules = PRESET_RULES[rules_name]
        state, shardings = create_sharded_state(
            model, optax.adamw(SPEC["lr"], b2=0.95), mesh, rules,
            jax.random.key(SPEC["seed"]), batch,
        )
        step_fn = make_train_step(model, mesh, rules, shardings)
        return mesh, rules, state, shardings, step_fn

    def timed_step(step_fn, state):
        t0 = time.time()
        state, metrics = step_fn(state, batch)
        return state, float(metrics["loss"]), time.time() - t0

    # Save arrays only — TrainState's apply_fn/tx are code, rebuilt here.
    def view(s):
        return {"params": s.params, "opt_state": s.opt_state, "step": s.step}

    # The agent's count of this worker's incarnations (a promoted
    # standby gets it with its activation).
    restart = int(os.environ.get(NodeEnv.RESTART_COUNT, "0"))
    reference_losses = None
    if chips == 4 and restart == 0:
        # The comparison: same seed, same batch, one device.  First, and
        # freed before the sharded state is built, so both fit.
        _, _, ref_state, _, ref_step = build(
            MeshConfig(dp=-1), "dp", devices[:1]
        )
        reference_losses = []
        for _ in range(steps):
            ref_state, loss, _dt = timed_step(ref_step, ref_state)
            reference_losses.append(loss)
        del ref_state, ref_step

    if chips == 4:
        mesh_cfg, rules_name = MeshConfig(dp=1, fsdp=2, tp=2), "fsdp_tp"
    else:
        mesh_cfg, rules_name = MeshConfig(dp=-1), "dp"
    mesh, rules, state, shardings, step_fn = build(
        mesh_cfg, rules_name, devices
    )
    ckpt = Checkpointer(os.environ["CHIP_SMOKE_CKPT_DIR"])

    if restart > 0:
        t0 = time.time()
        restored_step, restored = ckpt.load_checkpoint(
            view(state), view(shardings)
        )
        restore_s = time.time() - t0
        if restored_step is None:
            raise RuntimeError(
                f"restart {restart} found nothing restorable in shm"
            )
        state = state.replace(**restored)
        del restored
        hits_before = cache_events["hits"]
        state, loss, first_step_s = timed_step(step_fn, state)
        step_cache_hits = cache_events["hits"] - hits_before
        t_first = time.time()
        state, loss2, _dt = timed_step(step_fn, state)
        emit(
            "resumed",
            restored_step=int(restored_step),
            restore_s=restore_s,
            step=int(state.step),
            losses=[loss, loss2],
            t_first_step=t_first,
            first_step_s=first_step_s,
            step_cache_hits=step_cache_hits,
            cache=dict(cache_events),
            shard_devices=_shard_devices(state),
        )
        ckpt.close()
        return

    losses, step_s = [], []
    report = {}
    for n in range(1, steps + 1):
        state, loss, dt = timed_step(step_fn, state)
        losses.append(loss)
        step_s.append(dt)
        if n == 1:
            with nn_partitioning.axis_rules(list(rules)), use_mesh(mesh):
                compiled = step_fn.jitted.lower(state, batch).compile()
            text = compiled.as_text()
            mem = compiled.memory_analysis()
            report.update(
                has_tpu_custom_call="tpu_custom_call" in text,
                collectives=[
                    op for op in ("all-gather", "reduce-scatter",
                                  "all-reduce") if op in text
                ],
                argument_bytes=int(mem.argument_size_in_bytes),
                temp_bytes=int(mem.temp_size_in_bytes),
                shard_devices=_shard_devices(state),
            )
        if n == save_at:
            t0 = time.time()
            if not ckpt.save_checkpoint(
                n, view(state), StorageType.MEMORY, block=True
            ):
                raise RuntimeError(f"shm save at step {n} failed")
            report["save_s"] = time.time() - t0
            report["bytes_in_use"] = _bytes_in_use(devices)
    fallback = tmetrics.REGISTRY.get("dlrover_attention_fallback_total")
    stats = devices[0].memory_stats() or {}
    emit(
        "trained",
        losses=losses,
        reference_losses=reference_losses,
        compile_s=step_s[0],
        steady_step_s=min(step_s[1:]),
        n_params=int(sum(x.size for x in jax.tree.leaves(state.params))),
        state_bytes=int(
            sum(x.nbytes for x in jax.tree.leaves(view(state)))
        ),
        peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        bytes_limit=stats.get("bytes_limit"),
        attention_fallbacks={
            dict(key).get("reason", ""): v
            for _name, key, v in (fallback.samples() if fallback else [])
        },
        cache=dict(cache_events),
        saved_step=save_at,
        **report,
    )
    # The parent SIGKILLs this process now; the agent brings up the next.
    while True:
        time.sleep(1)


def _shard_devices(state):
    """Device ids holding the shards of the largest parameter."""
    import jax

    big = max(jax.tree.leaves(state.params), key=lambda x: x.size)
    return sorted({s.device.id for s in big.addressable_shards})


def _bytes_in_use(devices):
    return [
        (d.memory_stats() or {}).get("bytes_in_use") for d in devices
    ]


if __name__ == "__main__":
    main()

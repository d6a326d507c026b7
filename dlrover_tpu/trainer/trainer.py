"""High-level Trainer: auto-acceleration + flash checkpoint + elasticity.

Reference parity: ``atorch/trainer/atorch_trainer.py:136`` (``AtorchTrainer``,
HF-Trainer-style loop with atorch acceleration, flash-ckpt async saves,
logging) and ``trainer/atorch_args.py`` (``AtorchArguments``).
"""

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, Optional

import jax
import numpy as np

from dlrover_tpu.auto import auto_accelerate
from dlrover_tpu.common.log import logger


@dataclass
class TrainingArguments:
    """Knobs of the training loop (reference ``AtorchArguments``)."""

    max_steps: int = 1000
    log_interval: int = 10
    eval_interval: int = 0  # 0 = no eval
    save_interval: int = 0  # 0 = no checkpointing
    ckpt_dir: str = ""
    memory_save_interval: int = 1  # flash-ckpt to shm every N steps
    load_strategy: Any = None  # auto_accelerate strategy; None = search
    # Dry-run measure the top-k searched strategies (0 disables; the
    # search engine's measurement default only applies when the engine is
    # built without an explicit value, so keep this aligned).
    measure_top_k: int = 2
    rng_seed: int = 0
    # Loss-spike detection (reference atorch loss_spike_utils): a step whose
    # loss exceeds spike_factor x the running mean is logged and counted.
    spike_factor: float = 3.0
    spike_window: int = 50
    # Timed-collective ICI probe period in steps (0 disables): feeds the
    # master's runtime straggler diagnosis via the agent monitor
    # (agent/monitor/collective.py).  Multi-device workers only; each
    # probe costs a few ms.
    collective_probe_interval: int = 500
    # Runtime trace capture (reference: atorch wires torch.profiler into
    # its trainer; here jax.profiler emits a TensorBoard/Perfetto-
    # compatible trace of XLA device ops + host dispatch).  Captures
    # profile_steps steps starting AT step profile_at_step (0 = off)
    # into profile_dir.
    profile_at_step: int = 0
    profile_steps: int = 3
    profile_dir: str = "/tmp/dlrover_tpu_trace"
    # Sequence packing (data/packing.py): > 0 treats ``train_batches``
    # as a DOCUMENT stream (1-D token arrays, dicts with 'tokens', or
    # row-batches thereof) and packs it into rows of this length with
    # per-document position reset, segment ids and the boundary-loss
    # mask.  The attention stack runs segment-sparse (Σᵢ sᵢ² not s²)
    # and the step-phase profiler carries the cost model's
    # packed-vs-dense predicted tokens/s on every record.
    pack_sequences: int = 0
    pack_batch_size: int = 8
    pack_open_bins: int = 16


@dataclass
class TrainerState:
    global_step: int = 0
    epoch: int = 0
    loss_history: list = field(default_factory=list)
    spikes: int = 0
    tokens_seen: int = 0


class Trainer:
    """Train a flax model over batches with one call.

    ``train_batches`` yields dicts of numpy/jax arrays (the shapes of the
    first batch fix the compiled program).  Elasticity comes from the
    pieces this composes: a master-backed sharding client for data (pass
    an ``ElasticDataset``) and flash checkpointing for state.
    """

    def __init__(
        self,
        model,
        args: TrainingArguments,
        train_batches: Iterable[Dict[str, Any]],
        eval_batches: Optional[Iterable[Dict[str, Any]]] = None,
        optimizer=None,
        loss_fn: Optional[Callable] = None,
        checkpointer=None,
        sharding_client=None,
        sample_batch: Optional[Dict[str, Any]] = None,
        elastic_trainer=None,
        callbacks=None,
    ):
        self.args = args
        self._model = model
        if args.pack_sequences > 0:
            from dlrover_tpu.data.packing import packed_lm_batches

            train_batches = packed_lm_batches(
                train_batches,
                args.pack_sequences,
                args.pack_batch_size,
                open_bins=args.pack_open_bins,
            )
        self._train_batches = train_batches
        self._eval_batches = eval_batches
        self._checkpointer = checkpointer
        self._sharding_client = sharding_client
        # Optional ElasticTrainer: grad-accum policy + consumer of the
        # master's optimizer auto-tune (polled at log cadence).
        self._elastic_trainer = elastic_trainer
        # HF-style callbacks (trainer/callbacks.py); any hook returning
        # callbacks.STOP ends training at the next step boundary.
        self._callbacks = list(callbacks or [])
        self._tracing = False
        self.state = TrainerState()

        if sample_batch is None:
            train_iter = iter(train_batches)
            sample_batch = next(train_iter)
            self._first_batch = sample_batch
            self._train_iter = train_iter
        else:
            self._first_batch = None
            self._train_iter = iter(train_batches)
        self._sample_batch = sample_batch

        # A restarted worker must find what its predecessor compiled.
        from dlrover_tpu.common.platform import configure_compile_cache

        configure_compile_cache()
        ok, result, strategy = auto_accelerate(
            model,
            optimizer=optimizer,
            sample_batch=_to_jax(sample_batch),
            loss_fn=loss_fn,
            load_strategy=args.load_strategy,
            measure_top_k=args.measure_top_k,
            rng_seed=args.rng_seed,
            # The framework train/eval steps handle the chunked fused-CE
            # hidden-states contract, so "auto" selection is safe here.
            fused_ce_auto=True,
        )
        if not ok:
            raise RuntimeError(f"auto_accelerate failed for {strategy}")
        self.accelerated = result
        self.strategy = strategy
        self.train_state = result.state
        logger.info("Trainer strategy: %s", strategy.opt_names())

    # ------------------------------------------------------------------
    def _fire(self, hook: str, *hook_args) -> bool:
        """Invoke a callback hook on every callback; True = stop."""
        from dlrover_tpu.trainer.callbacks import STOP

        stop = False
        for cb in self._callbacks:
            try:
                if getattr(cb, hook)(self.state, *hook_args) == STOP:
                    logger.info(
                        "%s requested stop from %s",
                        type(cb).__name__, hook,
                    )
                    stop = True
            except Exception:
                logger.exception("callback %s.%s failed",
                                 type(cb).__name__, hook)
        return stop

    def train(self) -> TrainerState:
        try:
            return self._train_loop()
        finally:
            self._stop_trace()

    def _install_collective_split(self, profiler, wus_plan):
        """Weight-update sharding's overlap scheduler is active: split
        the profiler's device phase into compute/collective using the
        cost model's fraction (modeled — each record carries the
        ``collective_split`` source label)."""
        try:
            from dlrover_tpu.telemetry import costmodel

            delta = costmodel.predict_wus_delta(self.train_state, wus_plan)
            n_params = int(sum(
                np.prod(p.shape)
                for p in jax.tree.leaves(self.train_state.params)
            ))
            ids = (self._first_batch or {}).get("input_ids")
            tokens = int(np.prod(ids.shape)) if ids is not None else 8192
            frac = costmodel.wus_collective_fraction(
                delta, n_params, tokens_per_step=tokens,
                backend=costmodel.attached_generation(),
            )
            if frac is not None:
                profiler.set_collective_fraction(frac, source="costmodel")
                logger.info(
                    "wus %s over %s: modeled collective fraction %.3f, "
                    "opt HBM saved/chip %.1f MiB",
                    wus_plan.mode, "x".join(wus_plan.axes), frac,
                    delta["opt_hbm_bytes_saved_per_chip"] / 2**20,
                )
        except KeyError as e:
            logger.info("wus collective split not modeled: %s", e)
        except Exception:  # noqa: BLE001 — advisory only
            logger.exception("wus collective split install failed")

    def _install_packed_prediction(self, profiler):
        """pack_sequences is on: annotate every step-phase record with
        the cost model's packed (mask-aware Σᵢ sᵢ²) vs dense-causal
        predicted tokens/s, from the sample batch's observed segment
        ids — the honest-MFU half of the packed pipeline."""
        seg = (self._sample_batch or {}).get("segment_ids")
        if seg is None:
            return
        try:
            from dlrover_tpu.telemetry import costmodel

            cfg = getattr(
                getattr(self.accelerated, "model", None), "cfg", None
            ) or getattr(self._model, "cfg", None)
            heads = getattr(cfg, "num_heads", 0)
            layers = getattr(cfg, "num_layers", 0)
            head_dim = getattr(cfg, "resolved_head_dim", 0) or getattr(
                cfg, "head_dim", 0
            )
            if not (heads and layers and head_dim):
                return
            n_params = int(sum(
                np.prod(p.shape)
                for p in jax.tree.leaves(self.train_state.params)
            ))
            pred = costmodel.packed_vs_dense_prediction(
                n_params, np.asarray(seg), heads, head_dim, layers,
                backend=costmodel.attached_generation(),
            )
            profiler.set_packed_prediction(
                pred["packed_pred_tok_s"], pred["dense_pred_tok_s"],
                source="costmodel",
            )
            logger.info(
                "packed cost model: attention FLOPs %.2e packed vs "
                "%.2e dense (%.2fx reduction), predicted %.0f vs %.0f "
                "tok/s, packing efficiency %.3f",
                pred["attn_flops_packed"], pred["attn_flops_dense"],
                pred["reduction"], pred["packed_pred_tok_s"],
                pred["dense_pred_tok_s"], pred["packing_efficiency"],
            )
        except KeyError as e:
            logger.info("packed prediction not modeled: %s", e)
        except Exception:  # noqa: BLE001 — advisory only
            logger.exception("packed prediction install failed")

    def _train_loop(self) -> TrainerState:
        from dlrover_tpu.agent.monitor.progress import publish_progress
        from dlrover_tpu.telemetry.profiling import (
            get_step_profiler,
            update_memory_watermarks,
        )

        args = self.args
        self._maybe_resume()
        stop = self._fire("on_train_begin")
        t0 = time.perf_counter()
        window_tokens = 0
        profiler = get_step_profiler()
        wus_plan = getattr(self.accelerated, "wus_plan", None)
        if wus_plan is not None:
            self._install_collective_split(profiler, wus_plan)
        if args.pack_sequences > 0:
            self._install_packed_prediction(profiler)
        while not stop and self.state.global_step < args.max_steps:
            self._maybe_trace(self.state.global_step + 1)
            profiler.begin_step()
            batch = self._next_batch()
            if batch is None:
                break
            profiler.mark_data()
            sharded = self.accelerated.shard_batch(_to_jax(batch))
            self.train_state, metrics = self.accelerated.train_step(
                self.train_state, sharded
            )
            profiler.mark_dispatch()
            self.state.global_step += 1
            # float() blocks until the device finishes the step, so the
            # profiler's device phase ends here.
            loss = float(metrics["loss"])
            profiler.end_step(self.state.global_step)
            self._track_loss(loss)
            ids = batch.get("input_ids")
            if ids is not None:
                n_tok = int(np.prod(ids.shape))
                self.state.tokens_seen += n_tok
                window_tokens += n_tok

            step = self.state.global_step
            # One write per step: the progress snapshot feeds the hang
            # watchdog AND emits the telemetry "step" event internally.
            publish_progress(step)
            stop = self._fire("on_step_end", {"loss": loss, "step": step})
            if args.log_interval and step % args.log_interval == 0:
                dt = time.perf_counter() - t0
                tok_s = window_tokens / max(dt, 1e-9)
                logger.info(
                    "step %d loss %.4f | %.0f tok/s", step, loss, tok_s
                )
                stop = self._fire(
                    "on_log", {"loss": loss, "tok_s": tok_s, "step": step}
                ) or stop
                t0, window_tokens = time.perf_counter(), 0
                if self._elastic_trainer is not None:
                    new_tx = self._elastic_trainer.poll_optimizer_update()
                    if new_tx is not None:
                        # Same chain structure -> opt_state (moments)
                        # stays valid; only hyperparams change.
                        self.train_state = self.train_state.replace(
                            tx=new_tx
                        )
                # Snapshot chip HBM stats for the agent's resource monitor
                # (host-side file; the agent can't query the TPU runtime).
                from dlrover_tpu.agent.monitor.resource import (
                    export_tpu_metrics,
                )

                export_tpu_metrics(step=step)
                update_memory_watermarks()
            if (
                args.collective_probe_interval
                and step % args.collective_probe_interval == 0
            ):
                # Runtime ICI health sample -> agent monitor -> master's
                # collective-straggler diagnosis (the training-time
                # continuation of the pre-flight network check).
                from dlrover_tpu.agent.monitor.collective import (
                    export_collective_metrics,
                )

                export_collective_metrics(step=step)
            if self._sharding_client is not None:
                self._sharding_client.report_training_step(step)
                self._sharding_client.report_batch_done()
            if self._maybe_checkpoint(step):
                stop = self._fire("on_save", step) or stop
            if (
                args.eval_interval
                and self._eval_batches is not None
                and step % args.eval_interval == 0
            ):
                eval_loss = self.evaluate()
                logger.info("step %d eval_loss %.4f", step, eval_loss)
                stop = self._fire("on_evaluate", eval_loss) or stop
        self._fire("on_train_end")
        return self.state

    def evaluate(self) -> float:
        losses = []
        for batch in self._eval_batches:
            sharded = self.accelerated.shard_batch(_to_jax(batch))
            out = self.accelerated.eval_step(self.train_state, sharded)
            losses.append(float(out["loss"]))
        return float(np.mean(losses)) if losses else float("nan")

    # ------------------------------------------------------------------
    def _next_batch(self):
        if self._first_batch is not None:
            batch, self._first_batch = self._first_batch, None
            return batch
        try:
            return next(self._train_iter)
        except StopIteration:
            return None

    def _track_loss(self, loss: float):
        hist = self.state.loss_history
        window = hist[-self.args.spike_window:]
        if (
            len(window) >= 10
            and loss > self.args.spike_factor * float(np.mean(window))
        ):
            self.state.spikes += 1
            logger.warning(
                "Loss spike at step %d: %.4f (window mean %.4f)",
                self.state.global_step, loss, float(np.mean(window)),
            )
        hist.append(loss)
        del hist[: -max(self.args.spike_window * 2, 100)]

    def _maybe_checkpoint(self, step: int) -> bool:
        """Returns True when a save happened (drives on_save)."""
        if self._checkpointer is None:
            return False
        args = self.args
        to_disk = bool(args.save_interval) and step % args.save_interval == 0
        to_mem = (
            bool(args.memory_save_interval)
            and step % args.memory_save_interval == 0
        )
        if not (to_disk or to_mem):
            return False
        from dlrover_tpu.checkpoint.checkpointer import StorageType

        # Save a plain array pytree — TrainState's static fields (apply_fn,
        # tx) are not serializable and are rebuilt from code on restore.
        payload = {
            "params": self.train_state.params,
            "opt_state": self.train_state.opt_state,
            "step": self.train_state.step,
        }
        ok = self._checkpointer.save_checkpoint(
            step,
            payload,
            storage_type=StorageType.DISK if to_disk else StorageType.MEMORY,
        )
        if not ok:
            # Skipped under drain backpressure, or a PREVIOUS async
            # staging failed (sticky signal).  Either way nothing new is
            # durably staged for this step: don't fire on_save for a
            # checkpoint that doesn't exist.
            logger.warning(
                "checkpoint save at step %s not staged (backpressure or "
                "earlier staging failure)", step,
            )
        return ok

    def _maybe_trace(self, next_step: int):
        """Start/stop the jax.profiler trace window around
        [profile_at_step, profile_at_step + profile_steps)."""
        args = self.args
        if not args.profile_at_step:
            return
        if next_step == args.profile_at_step and not self._tracing:
            import jax

            jax.profiler.start_trace(args.profile_dir)
            self._tracing = True
            logger.info(
                "profiler trace started (steps %d-%d) -> %s",
                next_step,
                next_step + args.profile_steps - 1,
                args.profile_dir,
            )
        elif (
            self._tracing
            and next_step >= args.profile_at_step + args.profile_steps
        ):
            self._stop_trace()

    def _stop_trace(self):
        if getattr(self, "_tracing", False):
            import jax

            jax.profiler.stop_trace()
            self._tracing = False
            logger.info(
                "profiler trace written to %s", self.args.profile_dir
            )

    def _maybe_resume(self):
        if self._checkpointer is None:
            return
        try:
            view = {
                "params": self.train_state.params,
                "opt_state": self.train_state.opt_state,
                "step": self.train_state.step,
            }
            abstract = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    jnp_shape(x), getattr(x, "dtype", None)
                ),
                view,
            )
            shardings = {
                "params": self.accelerated.state_shardings.params,
                "opt_state": self.accelerated.state_shardings.opt_state,
                "step": self.accelerated.state_shardings.step,
            }
            step, restored = self._checkpointer.load_checkpoint(
                abstract, shardings
            )
        except Exception:
            logger.info("No checkpoint to resume from")
            return
        if step is not None and restored is not None:
            self.train_state = self.train_state.replace(
                params=restored["params"],
                opt_state=restored["opt_state"],
                step=restored["step"],
            )
            self.state.global_step = int(step)
            logger.info("Resumed from checkpoint at step %s", step)


def jnp_shape(x):
    return tuple(getattr(x, "shape", ()))


def _to_jax(batch: Dict[str, Any]) -> Dict[str, Any]:
    import jax.numpy as jnp

    return {
        k: jnp.asarray(v) if not hasattr(v, "sharding") else v
        for k, v in batch.items()
    }

"""Pipeline parallelism as pure GSPMD: sharded stage dim + circular shift.

Reference parity: ``atorch/modules/distributed_modules/compilers/
pipe_compiler/`` (PiPPy graph split + torch RPC micro-batch schedule,
``PipelineStage.py:989`` 1F1B, ``StageInterleaver.py:124``).  TPU redesign:
no graph compiler and no RPC.  The layer stack is grouped into
``num_stages`` groups whose params carry a leading ``stage`` logical axis
sharded over the ``pp`` mesh axis (DCN-tolerant, per the mesh's axis
order).  The schedule runs as an unrolled loop of ticks; activations live
in a ``(stage, ...)`` buffer sharded the same way, and the inter-stage
hand-off is ``jnp.roll`` on that sharded dim — which XLA lowers to the
neighbor ``CollectivePermute`` the reference implements with
point-to-point sends (asserted against compiled HLO in
``tests/test_moe_pipeline.py``).

Schedules — and why they differ from the reference's:

- ``"gpipe"``: all-forward-then-all-backward.  Autodiff saves every tick's
  stage activations, so live memory grows with M (microbatches).
- ``"1f1b"``: the reference's 1F1B exists to (a) bound live activations to
  O(stages) instead of O(microbatches) and (b) interleave fwd/bwd compute.
  Under GSPMD the whole pipeline is ONE traced program: the fwd/bwd
  interleaving (b) is the XLA latency-hiding scheduler's decision, made
  from the dependency graph — a hand-written schedule cannot beat it and
  has no program-level knob.  Property (a), the actual memory win, IS
  expressible: remat each stage tick (``jax.checkpoint``) so backward
  recomputes a tick's internals from its input, bounding live activations
  to the (stage,)-buffer chain.  ``schedule="1f1b"`` does exactly that
  (verified by compiled peak-memory comparison in the tests).
  The same analysis applies to Megatron-style interleaved stages: with
  all virtual stages resident per device and one fused program, splitting
  each device's layers into v round-robin groups only lengthens the
  software pipeline (M + vS - 1 ticks at identical per-tick cost) without
  changing what XLA may overlap, so it is deliberately not implemented.

Exactness: with M microbatches and S stages the result equals the
sequential layer stack; the (S-1)/(M+S-1) bubble is the usual GPipe cost
and shrinks with more microbatches.

Weight-update sharding overlap (``parallel/wus.py``): in ``"gather"``
mode params live scattered over the replica axes between steps, and the
step's FIRST op is the all-gather constraint back to the base layout
(``WusPlan.gather_params`` in ``trainer/step.py``).  Because the whole
pipeline is one traced program, that gather has no data dependency on
the early ticks of the schedule — stage k's weights are only needed at
tick k — so the latency-hiding scheduler runs later stages' param
gathers underneath the first microbatches' forward compute.  The bubble
that 1F1B's warm-up ticks can't avoid becomes the window that hides the
ZeRO all-gather; no tick-loop change is needed here, which is the point:
the overlap is a *placement* property (gather at step top, scattered
storage layout) expressed entirely in sharding annotations.
"""

from typing import Any, Optional, Type

import flax.linen as nn
import jax.numpy as jnp

from dlrover_tpu.parallel.sharding import constrain


class Pipeline(nn.Module):
    """Wraps a per-layer block module into a pipelined layer stack.

    ``block_cls`` must follow the scan-body protocol:
    ``block_cls(cfg)(x, positions, segment_ids) -> (x, None)``.
    """

    block_cls: Type[nn.Module]
    cfg: Any
    num_layers: int
    num_stages: int
    num_microbatches: int
    schedule: str = "gpipe"  # "gpipe" | "1f1b" (remat-per-tick)

    @nn.compact
    def __call__(self, x, positions, segment_ids: Optional[Any] = None):
        S, M = self.num_stages, self.num_microbatches
        if self.num_layers % S != 0:
            raise ValueError(
                f"{self.num_layers} layers not divisible by {S} stages"
            )
        b, s, h = x.shape
        if b % M != 0:
            raise ValueError(f"batch {b} not divisible by {M} microbatches")
        mb = b // M
        layers_per_stage = self.num_layers // S

        # Params: (stage, layers_per_stage, ...) — stage dim sharded on pp.
        # `intermediates` is declared at both boundaries so sown MoE losses
        # survive; the cfg scales them by 1/M because every microbatch sows
        # its own copy per layer (M per-microbatch sums ≈ the full-batch sum).
        import dataclasses as _dc

        cfg = self.cfg
        if _dc.is_dataclass(cfg) and getattr(cfg, "num_experts", 1) > 1:
            cfg = _dc.replace(
                cfg, moe_loss_scale=getattr(cfg, "moe_loss_scale", 1.0) / M
            )
        per_stage = nn.scan(
            self.block_cls,
            variable_axes={"params": 0, "intermediates": 0},
            split_rngs={"params": True},
            in_axes=(nn.broadcast, nn.broadcast),
            length=layers_per_stage,
            metadata_params={nn.PARTITION_NAME: "layers"},
        )
        staged_cls = nn.vmap(
            per_stage,
            variable_axes={"params": 0, "intermediates": 0},
            split_rngs={"params": True},
            in_axes=(0, 0, 0),
            metadata_params={nn.PARTITION_NAME: "stage"},
        )
        if self.schedule == "1f1b":
            # Remat each tick: backward recomputes the tick's stage
            # internals from its (stage,)-buffer input, bounding live
            # activations to the buffer chain — 1F1B's memory property
            # (see module docstring).  Wrapping the class here keeps the
            # "stages" param path identical across schedules.
            staged_cls = nn.remat(staged_cls, prevent_cse=False)
        elif self.schedule != "gpipe":
            raise ValueError(f"unknown pipeline schedule {self.schedule}")
        stages = staged_cls(cfg, name="stages")

        x_mb = x.reshape(M, mb, s, h)
        pos_mb = positions.reshape(M, mb, s)
        if segment_ids is None:
            # The block treats segment id 0 everywhere as "one document",
            # which is exactly the no-segment-ids semantics.
            seg_mb = jnp.zeros((M, mb, s), jnp.int32)
        else:
            seg_mb = segment_ids.reshape(M, mb, s)

        def constrain_stages(buf, trailing):
            return constrain(buf, ("stage",) + trailing)

        state = jnp.zeros((S, mb, s, h), x.dtype)
        state_pos = jnp.zeros((S, mb, s), pos_mb.dtype)
        state_seg = jnp.zeros((S, mb, s), jnp.int32)

        outputs = []
        for t in range(M + S - 1):
            if t < M:  # feed the next microbatch into stage 0
                state = state.at[0].set(x_mb[t])
                state_pos = state_pos.at[0].set(pos_mb[t])
                state_seg = state_seg.at[0].set(seg_mb[t])
            else:
                # Drain ticks: the roll recycles the last stage's output
                # into slot 0.  Zero it — otherwise that dead computation
                # leaks gradients through sown MoE losses.
                state = state.at[0].set(jnp.zeros((mb, s, h), x.dtype))
            state = constrain_stages(state, ("batch", "seq", "act_embed"))
            y, _ = stages(state, state_pos, state_seg)
            y = constrain_stages(y, ("batch", "seq", "act_embed"))
            if t >= S - 1:  # microbatch t-(S-1) exits the last stage
                outputs.append(y[-1])
            # Hand each stage's output to its successor: a CollectivePermute
            # on the pp-sharded dim.  Position/segment buffers ride along.
            state = jnp.roll(y, 1, axis=0)
            state_pos = jnp.roll(state_pos, 1, axis=0)
            state_seg = jnp.roll(state_seg, 1, axis=0)

        out = jnp.stack(outputs, axis=0).reshape(b, s, h)
        return constrain(out, ("batch", "seq", "act_embed"))

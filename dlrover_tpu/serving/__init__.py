"""Production inference gateway (docs/SERVING.md).

The serving tier around the model's KV-cache decode path:

* :mod:`paged_cache` — block-pool KV allocator with a hash-consed
  prefix cache (cache memory scales with actual sequence lengths);
* :mod:`engine` — :class:`PagedServingEngine`, continuous batching with
  chunked prefill interleaved into the decode tick (one mixed dispatch
  per tick);
* :mod:`gateway` — :class:`InferenceGateway`, admission control
  (token-budget queueing, deadlines, 429-style shed), fleet
  supervision with SIGKILL replay from the last committed token, and
  the servput accountant wiring;
* :mod:`fleet` — :class:`ReplicaSet` (live replicas + warm standbys,
  spawn retry, wedge/slow health verdicts),
  :class:`FleetAutoscaler` (hysteretic sizing off queue + SLO burn)
  and :class:`BrownoutController` (the degradation ladder);
* :mod:`worker` — the real-process decode worker
  (``python -m dlrover_tpu.serving``) behind the 2-RPC transport.

``rl/serving.py`` stays as the minimal slot-pool reference engine.
"""

# Names resolve on first use: the gateway lives in a process that must
# never import JAX (a decode worker owns the chip), the engine needs it.
from dlrover_tpu.common.lazy import lazy_exports

_LAZY = {
    "BlockPool": "dlrover_tpu.serving.paged_cache",
    "PagedServingEngine": "dlrover_tpu.serving.engine",
    "BROWNOUT_RUNGS": "dlrover_tpu.serving.fleet",
    "BrownoutController": "dlrover_tpu.serving.fleet",
    "FleetAutoscaler": "dlrover_tpu.serving.fleet",
    "ReplicaSet": "dlrover_tpu.serving.fleet",
    "spawn_with_retry": "dlrover_tpu.serving.fleet",
    "InferenceGateway": "dlrover_tpu.serving.gateway",
    "LocalReplica": "dlrover_tpu.serving.gateway",
    "ProcessReplica": "dlrover_tpu.serving.gateway",
}

__all__ = sorted(_LAZY)
__getattr__ = lazy_exports(__name__, _LAZY)

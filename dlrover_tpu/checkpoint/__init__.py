"""Flash Checkpoint.  Names resolve on first use: the agent-side saver
(``ckpt_saver``) lives in a process that must never import JAX, while
the trainer-side engine needs it."""

from dlrover_tpu.common.lazy import lazy_exports

_LAZY = {
    "Checkpointer": "dlrover_tpu.checkpoint.checkpointer",
    "StorageType": "dlrover_tpu.checkpoint.checkpointer",
    "CheckpointEngine": "dlrover_tpu.checkpoint.engine",
    "CheckpointStorage": "dlrover_tpu.checkpoint.storage",
    "PosixDiskStorage": "dlrover_tpu.checkpoint.storage",
}

__all__ = sorted(_LAZY)
__getattr__ = lazy_exports(__name__, _LAZY)

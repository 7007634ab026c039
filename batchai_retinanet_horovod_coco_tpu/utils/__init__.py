"""Host-side utilities: checkpointing, metrics, atomic artifact IO.

The reference's equivalents (SURVEY.md §5): Keras ``ModelCheckpoint`` on
rank 0 (§5.4), TensorBoard scalar callbacks + Horovod MetricAverage (§5.5),
and nothing for profiling beyond stdout (§5.1).

Attribute access is lazy (PEP 562): ``utils.checkpoint`` imports jax, but
``utils.atomicio`` must stay importable from jax-free processes (shm decode
workers, obs.trace, the analysis package) — an eager ``from ...checkpoint
import`` here would drag jax into all of them.
"""

from typing import Any

__all__ = ["CheckpointManager", "latest_step"]


def __getattr__(name: str) -> Any:
    if name in ("CheckpointManager", "latest_step"):
        from batchai_retinanet_horovod_coco_tpu.utils import checkpoint

        return getattr(checkpoint, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

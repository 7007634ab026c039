"""comm/: the gradient-communication subsystem (ISSUE 13).

Owns how gradients (and ZeRO weight updates) cross the interconnect:

- ``config.CommConfig`` — the policy layer (compress mode, error
  feedback, overlap, bucket sizing, per-stage overrides);
- ``compress`` — bucketed int8/bf16 collectives with error feedback,
  the DP ``reduce_tree`` and the ZeRO ``zero_gather_updates`` layouts,
  EF-state init/partition rules, and the static bytes-on-wire plan
  (tests/unit/test_comm.py holds int8 to 0.65 of the exact bytes);
- ``overlap`` — custom-VJP staging that issues each schedule stage's
  compressed collective from inside the backward pass.

Consumers: ``train/step.py`` (both mesh step flavors),
``utils/cli.py``/``train.py`` (flag surface), ``obs/`` (EF health
gauges + the ``ef_residual_spike`` SLO rule), and the collective-safety
lint rule (this package's public reducers are collective call sites).
"""

from batchai_retinanet_horovod_coco_tpu.comm.config import (
    CommConfig,
    STAGES,
    stage_of,
)
from batchai_retinanet_horovod_coco_tpu.comm.compress import (
    CommPlan,
    bucket_state_key,
    bucketed_pmean,
    comm_metrics,
    init_comm_state,
    plan_buckets,
    reduce_bucket_hierarchical,
    reduce_tree,
    state_partition_specs,
    zero_gather_updates,
)

__all__ = [
    "STAGES",
    "CommConfig",
    "CommPlan",
    "bucket_state_key",
    "bucketed_pmean",
    "comm_metrics",
    "init_comm_state",
    "plan_buckets",
    "reduce_bucket_hierarchical",
    "reduce_tree",
    "stage_of",
    "state_partition_specs",
    "zero_gather_updates",
]

"""Worker for the fault-injection test (SURVEY.md §5.3).

Run by test_fault_injection.py:
    python fault_worker.py <out_dir> <total_steps> <die_before_step>

Trains a tiny RetinaNet on a 4-virtual-device CPU mesh with checkpointing
every 2 steps and per-step JSONL loss logging.  ``die_before_step > 0``
injects the fault: the process SIGKILLs itself (no cleanup, no atexit — the
same abrupt death as a preempted/failed host) right before fetching the
batch for that step.  The relaunch (same command, die_before_step=0)
auto-resumes from the latest complete checkpoint; batches are a pure
function of the step index, so the post-resume loss trajectory must be
bitwise identical to an uninterrupted golden run — which is exactly the
fail-stop + job-retry recovery model of the reference stack (Batch AI
restarts the mpirun job from the last snapshot), minus the lost work.
"""

import json
import os
import signal
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
).strip()

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import optax  # noqa: E402


def batch_for_step(step: int, hw, batch_size: int):
    """Deterministic batch for a given global step (resume-safe stream)."""
    from batchai_retinanet_horovod_coco_tpu.data.pipeline import Batch

    rng = np.random.default_rng(1000 + step)
    images = rng.normal(0, 1, (batch_size, *hw, 3)).astype(np.float32)
    boxes = np.tile(
        np.array([[8.0, 8.0, 40.0, 40.0]], np.float32), (batch_size, 1, 1)
    )
    return Batch(
        images=images,
        gt_boxes=boxes,
        gt_labels=np.ones((batch_size, 1), np.int32),
        gt_mask=np.ones((batch_size, 1), bool),
        image_ids=np.arange(batch_size, dtype=np.int64),
        scales=np.ones((batch_size,), np.float32),
        valid=np.ones((batch_size,), bool),
    )


def main(out_dir: str, total_steps: int, die_before_step: int):
    from batchai_retinanet_horovod_coco_tpu.models import (
        RetinaNetConfig,
        build_retinanet,
    )
    from batchai_retinanet_horovod_coco_tpu.parallel import make_mesh
    from batchai_retinanet_horovod_coco_tpu.train import create_train_state
    from batchai_retinanet_horovod_coco_tpu.train.loop import (
        LoopConfig,
        run_training,
    )
    from batchai_retinanet_horovod_coco_tpu.utils import checkpoint as ckpt_lib
    from batchai_retinanet_horovod_coco_tpu.obs.events import EventSink

    hw = (64, 64)
    batch_size = 4
    model = build_retinanet(
        RetinaNetConfig(
            num_classes=3, backbone="resnet_test", fpn_channels=16,
            head_width=16, head_depth=1, dtype=np.float32,
        )
    )
    state = create_train_state(
        model, optax.sgd(1e-2, momentum=0.9), (1, *hw, 3), jax.random.key(0)
    )

    ckpt_dir = os.path.join(out_dir, "ckpt")
    start = ckpt_lib.latest_step(ckpt_dir) or 0

    def stream():
        step = start
        while True:
            step += 1
            if step == die_before_step:
                os.kill(os.getpid(), signal.SIGKILL)  # abrupt host death
            yield batch_for_step(step, hw, batch_size)

    state = run_training(
        model, state, stream(), 3,
        LoopConfig(
            total_steps=total_steps,
            log_every=1,
            checkpoint_every=2,
            checkpoint_dir=ckpt_dir,
            resume=True,
        ),
        mesh=make_mesh(),
        logger=EventSink(os.path.join(out_dir, "logs"), stdout=False),
    )

    param_sum = float(
        sum(float(np.sum(np.asarray(x))) for x in jax.tree.leaves(state.params))
    )
    with open(os.path.join(out_dir, "result.json"), "w") as f:
        json.dump({"param_sum": param_sum, "step": int(state.step)}, f)


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), int(sys.argv[3]))

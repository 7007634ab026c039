"""What a train step is trained ON: the seam between the step's flavours
(train/step.py), the loop (train/loop.py) and a kind of model.

A task says what a host batch's device fields are, which shape is the
step's compile bucket, what example initialises the parameters, and how a
batch becomes a loss: ``loss_fn(model, bucket)`` returns
``(state, params, batch) -> (loss, (metrics, new_batch_stats))``, which every
step flavour differentiates.  Everything else - the jit and its donation,
the gradient norm and the clip chain, the update, numerics, the prefetch
thread, logs, checkpoints, ``compiled_step()`` and the spans - is the
step's and the loop's, shared by every task.

``DetectionTask`` is RetinaNet (images, boxes, anchors, focal loss) on every
mesh; ``LMTask`` is next-token prediction over packed documents
(models/granite_hybrid.py, models/deepseek_v2.py) on one device: its
sharding comes with its own issue.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from batchai_retinanet_horovod_coco_tpu import losses as losses_lib
from batchai_retinanet_horovod_coco_tpu.data import pipeline as pipeline_lib
from batchai_retinanet_horovod_coco_tpu.ops import anchors as anchors_lib
from batchai_retinanet_horovod_coco_tpu.ops import matching as matching_lib
from batchai_retinanet_horovod_coco_tpu.train.state import TrainState, model_variables

LossFn = Callable[[TrainState, Any, dict[str, Any]], tuple[jnp.ndarray, tuple[dict, Any]]]


def _forward_and_loss(
    model,
    state: TrainState,
    params,
    images: jnp.ndarray,
    gt_boxes: jnp.ndarray,
    gt_labels: jnp.ndarray,
    gt_mask: jnp.ndarray,
    anchors: jnp.ndarray,
    loss_config: losses_lib.LossConfig,
    matching_config: matching_lib.MatchingConfig,
    train: bool,
):
    variables = dict(model_variables(state), params=params)
    has_bn = "batch_stats" in variables
    # uint8 batches normalize here, on device (data/pipeline.normalize_images).
    with jax.named_scope("backbone"):
        images = pipeline_lib.normalize_images(images)

    # NHWC-direct loss path: raw per-level head outputs, no anchor-major
    # retile/concat (losses.total_loss_compact_nhwc — measured ~4 ms/step
    # of layout traffic at the flagship bucket).  The Pallas focal kernel
    # consumes the concatenated (B, A, K) form instead.
    return_levels = False if loss_config.pallas_focal else "nhwc"
    apply_kwargs = dict(train=train, return_levels=return_levels)
    if has_bn and train:
        outputs, mutated = model.apply(
            variables, images, mutable=["batch_stats"], **apply_kwargs
        )
        new_batch_stats = mutated["batch_stats"]
    else:
        outputs = model.apply(variables, images, **apply_kwargs)
        new_batch_stats = state.batch_stats

    # On-device target assignment; no gradients flow into the matching.
    # Compact form: integer labels instead of a dense (A, K) one-hot — the
    # focal loss fuses the implicit one-hot (losses.focal_loss_compact).
    # Batched entrypoint: fused Pallas assignment on TPU, vmapped XLA
    # elsewhere (ops/matching.py).
    # Planar (B, 4, A) box targets on the NHWC path: dense lane layout end
    # to end instead of the 32x-padded 4-minor form (ops.matching docstring).
    planar = return_levels == "nhwc"
    with jax.named_scope("assign"):
        targets = matching_lib.anchor_targets_compact_batched(
            anchors, gt_boxes, gt_labels, gt_mask, matching_config,
            planar_box_targets=planar,
        )
        targets = jax.tree.map(lax.stop_gradient, targets)

    with jax.named_scope("loss"):
        if return_levels == "nhwc":
            metrics = losses_lib.total_loss_compact_nhwc(
                outputs["cls_levels"],
                outputs["box_levels"],
                targets.matched_labels,
                targets.box_targets,
                targets.state,
                model.config.anchors_per_location,
                loss_config,
                planar_box_targets=True,
            )
        else:
            metrics = losses_lib.total_loss_compact(
                outputs["cls_logits"],
                outputs["box_deltas"],
                targets.matched_labels,
                targets.box_targets,
                targets.state,
                loss_config,
            )
        metrics["num_pos"] = jnp.sum(
            (targets.state == matching_lib.POSITIVE).astype(jnp.float32)
        )
    return metrics["loss"], (metrics, new_batch_stats)


class _Task:
    """What the step and the loop ask of a task."""

    name: str
    scopes: tuple[str, ...]  # the STEP_SCOPES (train/step.py) its forward and loss enter (LMTask: its model's)
    batch_fields: tuple[str, ...]  # of a host batch, as the step's batch dict has them
    example_dtype: Any  # of create_train_state's example input
    supports_mesh: bool

    def host_arrays(self, batch) -> dict[str, Any]:
        return {k: getattr(batch, k) for k in self.batch_fields}

    def run_meta(self, model, bucket) -> dict[str, Any]:
        """What the loop's ``run_meta`` instant says of ``model``'s step for
        ``bucket`` besides the devices."""
        del model, bucket
        return {}


@dataclasses.dataclass(frozen=True)
class DetectionTask(_Task):
    """RetinaNet training: uint8 images and padded boxes in, anchors a
    compile-time constant of the (H, W) bucket, on-device assignment,
    focal + smooth-L1."""

    num_classes: int
    loss_config: losses_lib.LossConfig = losses_lib.LossConfig()
    matching_config: matching_lib.MatchingConfig = matching_lib.MatchingConfig()
    anchor_config: anchors_lib.AnchorConfig | None = None

    name = "detection"
    scopes = ("backbone", "fpn", "heads", "assign", "loss")
    batch_fields = ("images", "gt_boxes", "gt_labels", "gt_mask")
    example_dtype = jnp.float32  # an image
    supports_mesh = True

    def describe(self, batch) -> tuple[tuple[int, ...], int, Any]:
        """``(bucket, examples, ids)`` of a host batch: the (H, W) a step is
        compiled for, the images in it, their source ids."""
        return tuple(batch.images.shape[1:3]), batch.images.shape[0], batch.image_ids

    def loss_fn(self, model, bucket) -> LossFn:
        anchors = jnp.asarray(anchors_lib.anchors_for_image_shape(
            bucket, self.anchor_config or anchors_lib.AnchorConfig()))

        def loss_of(state, params, batch):
            return _forward_and_loss(
                model, state, params,
                batch["images"], batch["gt_boxes"], batch["gt_labels"],
                batch["gt_mask"], anchors, self.loss_config,
                self.matching_config, train=True,
            )

        return loss_of


@dataclasses.dataclass(frozen=True)
class LMTask(_Task):
    """Next-token prediction over packed documents (data/tokens.py): the
    loss counts the positions whose next token lies in the same document.

    What differs from one language model to the next is the MODEL's to
    supply (models/granite_hybrid.py, models/deepseek_v2.py,
    models/nemotron_h.py): the scopes its
    step enters (``model.scopes``), what ``run_meta`` says of its step
    (``model.run_meta(bucket)``), and ``model.loss(params, tokens,
    segment_ids) -> (loss, scalars)``: the next-token cross-entropy plus
    whatever the model adds to it (a mixture of experts' balance loss), and
    the scalars of the step that the loop logs (``loss``, ``tokens_counted``,
    a model's routing counters)."""

    name = "lm"
    batch_fields = ("tokens", "segment_ids")
    example_dtype = jnp.int32  # token ids
    supports_mesh = False

    def describe(self, batch) -> tuple[tuple[int, ...], int, Any]:
        """The bucket is (sequences, tokens per sequence)."""
        return tuple(batch.tokens.shape), batch.tokens.shape[0], batch.sequence_ids

    def run_meta(self, model, bucket) -> dict[str, Any]:
        return model.run_meta(bucket)

    def loss_fn(self, model, bucket) -> LossFn:
        del bucket  # nothing of the program depends on it but the shapes

        def loss_of(state, params, batch):
            loss, scalars = model.loss(params, batch["tokens"], batch["segment_ids"])
            return loss, (scalars, state.batch_stats)

        return loss_of

"""RetinaNet assembly: backbone → FPN → shared heads → concatenated outputs.

Parity target: keras-retinanet's ``retinanet()`` graph builder (SURVEY.md M1).
The training model outputs, per image, dense per-anchor classification logits
(A, K) and box deltas (A, 4), concatenated over pyramid levels P3→P7 in the
SAME anchor order as ``ops.anchors.anchors_for_image_shape``: level-major,
then row-major over (y, x), then the 9 anchors of a location.  This ordering
contract is what lets targets/anchors be plain constants alongside the model
outputs; it is locked in by tests (tests/unit/test_model.py).

Unlike the reference there is no separate "bbox model" conversion step
(SURVEY.md M3): inference is just another jitted function over the same
params (evaluate/detect.py) since decode+NMS are ordinary device ops here.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from batchai_retinanet_horovod_coco_tpu.models.fpn import FPN
from batchai_retinanet_horovod_coco_tpu.models.heads import BoxHead, ClassificationHead
from batchai_retinanet_horovod_coco_tpu.models.resnet import ResNet
from batchai_retinanet_horovod_coco_tpu.ops.anchors import AnchorConfig


@dataclasses.dataclass(frozen=True)
class RetinaNetConfig:
    num_classes: int = 80
    backbone: str = "resnet50"
    norm_kind: str = "gn"  # "gn" | "bn" | "frozen_bn"  (see models/resnet.py)
    # Stem formulation (models/resnet.py StemConv): space_to_depth is the
    # MLPerf-equivalent reformulation of the 7x7/2 conv — identical math,
    # measured 3.7% faster end-to-end on v5e (the plain 3-channel stem runs
    # the MXU at ~4% occupancy).  "conv" restores the canonical form.
    stem: str = "space_to_depth"
    # Width-packed stage2 (models/resnet.py): the C=64 stage runs with W
    # pairs folded into channels so its convs fill the 128-lane MXU —
    # math-identical, same param tree.  MEASURED NEGATIVE at the flagship
    # bucket on v5e (58.3 vs 60.7 imgs/s at b8: stage2 is mostly
    # bandwidth-bound there, so the packed kernels' 2x MACs cost more than
    # the lane-occupancy win; PARITY.md round 3).  Kept as an exact,
    # tested reformulation for narrow-channel-bound shapes/hardware.
    # ResNet backbones only; needs W_img divisible by 8.
    pack_width: bool = False
    # "avg" swaps the ResNet stem maxpool for a tie-free avg pool — a
    # diagnostic config for gradient-parity tests under GSPMD spatial
    # partitioning (models/resnet.py ResNet.stem_pool); requires
    # stem="conv".  ResNet backbones only.
    stem_pool: str = "max"
    fpn_channels: int = 256
    head_width: int = 256
    head_depth: int = 4
    prior_prob: float = 0.01
    anchor: AnchorConfig = AnchorConfig()
    dtype: Any = jnp.bfloat16

    @property
    def anchors_per_location(self) -> int:
        return self.anchor.num_anchors_per_location


_BACKBONE_STAGES = {
    "resnet18": None,  # not a bottleneck net; unsupported, kept for error msg
    "resnet50": (3, 4, 6, 3),
    "resnet101": (3, 4, 23, 3),
    "resnet152": (3, 8, 36, 3),
    # One block per stage: for fast CI on the virtual CPU mesh only.
    "resnet_test": (1, 1, 1, 1),
}

BACKBONES = tuple(
    k for k, v in _BACKBONE_STAGES.items() if v is not None
) + (
    "mobilenet", "mobilenet050", "vgg16", "vgg19",
    "densenet121", "densenet169", "densenet201",
)


def build_backbone(cfg: "RetinaNetConfig"):
    """Backbone registry: every entry returns a module producing
    {"c3", "c4", "c5"} at strides 8/16/32 (the FPN input contract).

    The reference library's backbone families (SURVEY.md M2: ResNet primary;
    mobilenet/vgg siblings in keras_retinanet/models/).  ``norm_kind`` and
    ``stem`` apply where the architecture has them (VGG has no norm layers;
    only ResNet has the 7x7/2 stem the space_to_depth mode reformulates).
    """
    name = cfg.backbone
    stages = _BACKBONE_STAGES.get(name)
    if cfg.pack_width and stages is None:
        raise ValueError(
            f"pack_width is a ResNet-stage2 reformulation; backbone "
            f"{name!r} does not support it"
        )
    if cfg.stem_pool != "max" and stages is None:
        # Mirror the pack_width guard above: a diagnostic knob that only
        # the ResNet stem implements must not be silently ignored.
        raise ValueError(
            f"stem_pool={cfg.stem_pool!r} is only supported by ResNet "
            f"backbones, not {name!r}"
        )
    if stages is not None:
        return ResNet(
            stage_sizes=stages,
            norm_kind=cfg.norm_kind,
            dtype=cfg.dtype,
            stem=cfg.stem,
            pack_width=cfg.pack_width,
            stem_pool=cfg.stem_pool,
            name="backbone",
        )
    if name in ("mobilenet", "mobilenet050"):
        from batchai_retinanet_horovod_coco_tpu.models.mobilenet import (
            MobileNetV1,
        )

        return MobileNetV1(
            alpha=0.5 if name == "mobilenet050" else 1.0,
            norm_kind=cfg.norm_kind,
            dtype=cfg.dtype,
            name="backbone",
        )
    if name in ("vgg16", "vgg19"):
        from batchai_retinanet_horovod_coco_tpu.models.vgg import VGG

        return VGG(
            stage_sizes=(2, 2, 3, 3, 3) if name == "vgg16" else (2, 2, 4, 4, 4),
            dtype=cfg.dtype,
            name="backbone",
        )
    if name in ("densenet121", "densenet169", "densenet201"):
        from batchai_retinanet_horovod_coco_tpu.models.densenet import (
            DENSENET_STAGES,
            DenseNet,
        )

        return DenseNet(
            stage_sizes=DENSENET_STAGES[name],
            norm_kind=cfg.norm_kind,
            dtype=cfg.dtype,
            name="backbone",
        )
    raise ValueError(f"unsupported backbone: {name!r}")


class RetinaNet(nn.Module):
    config: RetinaNetConfig

    @nn.compact
    def __call__(
        self,
        images: jnp.ndarray,
        train: bool = False,
        return_levels: bool | str = False,
    ) -> dict[str, Any]:
        """(B, H, W, 3) float images → {"cls_logits": (B, A, K), "box_deltas": (B, A, 4)}.

        ``return_levels=True`` returns the PER-LEVEL anchor-major outputs
        instead ({"cls_levels": tuple of (B, A_l, K), "box_levels": ...},
        P3→P7 in anchor order) and skips the concatenation.
        ``return_levels="nhwc"`` returns the RAW conv outputs per level
        ((B, h_l, w_l, A·K) / (B, h_l, w_l, A·4)) — no anchor-major retile,
        no concat; the train step consumes this via
        ``losses.total_loss_compact_nhwc`` (the retile+concat+split complex
        measured ~4 ms of the b8 flagship step, round-3 profile).
        """
        cfg = self.config
        # named_scope: the slices a device trace is read by
        # (train/step.py::STEP_SCOPES; SURVEY.md §5.1).
        with jax.named_scope("backbone"):
            features = build_backbone(cfg)(images, train=train)
        with jax.named_scope("fpn"):
            pyramid = FPN(
                channels=cfg.fpn_channels, dtype=cfg.dtype, name="fpn"
            )(features)

        cls_head = ClassificationHead(
            num_classes=cfg.num_classes,
            anchors_per_location=cfg.anchors_per_location,
            width=cfg.head_width,
            depth=cfg.head_depth,
            prior_prob=cfg.prior_prob,
            dtype=cfg.dtype,
            name="cls_head",
        )
        box_head = BoxHead(
            anchors_per_location=cfg.anchors_per_location,
            width=cfg.head_width,
            depth=cfg.head_depth,
            dtype=cfg.dtype,
            name="box_head",
        )

        flatten = return_levels != "nhwc"
        cls_out, box_out = [], []
        with jax.named_scope("heads"):
            for level in cfg.anchor.levels:  # P3 → P7, matching anchor order
                feat = pyramid[f"p{level}"]
                with jax.named_scope("cls"):
                    cls_out.append(cls_head(feat, flatten=flatten))
                with jax.named_scope("box"):
                    box_out.append(box_head(feat, flatten=flatten))

        if return_levels == "nhwc":
            # Raw dtype (bf16): an f32 cast here would double the final
            # head convs' output writes (~516 MB/step at the flagship
            # bucket); the nhwc loss casts f32 inside its elementwise
            # fusion instead.
            return {"cls_levels": tuple(cls_out), "box_levels": tuple(box_out)}
        if return_levels:
            # Losses run in f32; cast per level (fuses into the head convs).
            return {
                "cls_levels": tuple(o.astype(jnp.float32) for o in cls_out),
                "box_levels": tuple(o.astype(jnp.float32) for o in box_out),
            }
        return {
            # Losses run in f32; cast once here so downstream ops are f32.
            "cls_logits": jnp.concatenate(cls_out, axis=1).astype(jnp.float32),
            "box_deltas": jnp.concatenate(box_out, axis=1).astype(jnp.float32),
        }


def build_retinanet(config: RetinaNetConfig | None = None) -> RetinaNet:
    return RetinaNet(config=config or RetinaNetConfig())

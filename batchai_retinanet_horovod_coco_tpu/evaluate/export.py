"""Serialized inference artifacts (the ``convert_model.py`` equivalent).

The reference ships ``bin/convert_model.py`` (SURVEY.md M3): turn a training
snapshot into a self-contained inference model (``retinanet_bbox``: forward →
decode → clip → NMS) that runs without the training code.  In this framework
inference is just another jitted function over the same params, so conversion
becomes *export*: lower the full detection program (including on-device NMS)
to serialized StableHLO via ``jax.export``, with the trained parameters baked
in as constants.  The artifact is loadable with nothing but jax — no model
code, no framework import — and can be lowered for several platforms at once
(e.g. ``("cpu", "tpu")``), the analogue of the reference's one ``.h5`` that
ran wherever Keras did.

One artifact is produced per static input shape (batch, H, W) — the price of
compiled static shapes (SURVEY.md §7.3 hard part 1); the manifest records the
shapes so callers route images to the right program, exactly as the training
pipeline routes into shape buckets.

Layout of an export directory:

    manifest.json                     shapes, detect config, class names
    detector_<H>x<W>_b<B>.stablehlo   one serialized program per bucket
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from batchai_retinanet_horovod_coco_tpu.utils.atomicio import (
    atomic_write_bytes,
    atomic_write_text,
)
from batchai_retinanet_horovod_coco_tpu.evaluate.detect import (
    DetectConfig,
    make_detect_fn,
)

_MANIFEST = "manifest.json"


def _artifact_name(hw: tuple[int, int], batch_size: int) -> str:
    return f"detector_{hw[0]}x{hw[1]}_b{batch_size}.stablehlo"


def export_detector(
    state,
    model,
    image_hw: tuple[int, int],
    batch_size: int,
    config: DetectConfig = DetectConfig(),
    platforms: tuple[str, ...] | None = None,
    input_dtype: Any = jnp.uint8,
) -> bytes:
    """Serialize one detection program (params baked in) for one bucket.

    The exported callable maps ``images (B, H, W, 3) uint8`` (raw pipeline
    format; normalization happens inside, as in training) to the Detections
    tuple ``(boxes, scores, labels, valid)``.
    """
    from jax import export as jax_export

    detect = make_detect_fn(model, image_hw, config)
    # Bake the train state in as closure constants; the artifact is
    # self-contained like the reference's converted .h5.
    fn = jax.jit(lambda images: tuple(detect(state, images)))
    spec = jax.ShapeDtypeStruct((batch_size, *image_hw, 3), input_dtype)
    kwargs = {} if platforms is None else {"platforms": tuple(platforms)}
    return jax_export.export(fn, **kwargs)(spec).serialize()


def export_model(
    state,
    model,
    output_dir: str,
    buckets: tuple[tuple[int, int], ...],
    batch_size: int | tuple[int, ...] = 1,
    config: DetectConfig = DetectConfig(),
    platforms: tuple[str, ...] | None = None,
    class_names: list[str] | None = None,
    label_to_cat_id: dict[int, int] | None = None,
    image_min_side: int | None = None,
    image_max_side: int | None = None,
    version: str | None = None,
) -> str:
    """Export one detection artifact per (shape bucket, batch size) + a
    manifest.

    ``batch_size`` may be a tuple — the serve-side dynamic batcher
    (serve/) pads a partial batch up to the SMALLEST exported size that
    fits it, so exporting e.g. ``(1, 8)`` lets a lone straggler request
    run at batch 1 instead of paying a full 8-wide pad.  ``image_min_side``
    / ``image_max_side`` record the resize rule the model was evaluated
    under: a server routing raw images into buckets must use them, not its
    own defaults (manifest-driven routing, same discipline as the anchor
    config).  Returns the manifest path.
    """
    os.makedirs(output_dir, exist_ok=True)
    batch_sizes = (
        (batch_size,) if isinstance(batch_size, int) else tuple(batch_size)
    )
    entries = []
    for hw in buckets:
        for b in batch_sizes:
            name = _artifact_name(hw, b)
            data = export_detector(
                state, model, hw, b, config, platforms=platforms
            )
            # Atomic: the manifest names this file; a torn artifact must
            # never be loadable under its published name (ISSUE 11 rule).
            atomic_write_bytes(os.path.join(output_dir, name), data)
            entries.append(
                {"file": name, "height": hw[0], "width": hw[1],
                 "batch_size": b}
            )
    manifest = {
        "format": "jax.export.stablehlo.v1",
        "input": "uint8 RGB (B, H, W, 3), raw pixels (normalization inside)",
        "output": ["boxes", "scores", "labels", "valid"],
        "artifacts": entries,
        "detect_config": {
            "score_threshold": config.score_threshold,
            "iou_threshold": config.iou_threshold,
            "pre_nms_size": config.pre_nms_size,
            "max_detections": config.max_detections,
            "nms_impl": config.nms_impl,
            "nms_block_k": config.nms_block_k,
        },
        # Anchors parameterize box decoding INSIDE the artifact; recorded so
        # the artifact is self-describing (a consumer regenerating anchors,
        # e.g. for target assignment, must use these, not the defaults).
        "anchor_config": dataclasses.asdict(config.anchor),
        # Inference-time resize rule (serve routing): raw images are
        # resized/bucketed with THESE sides, exactly as the eval pipeline
        # that produced the model's metrics did.  None on legacy exports.
        "image_min_side": image_min_side,
        "image_max_side": image_max_side,
        # Rollout identity (ISSUE 12): the serve fleet's canary gate and
        # router attribute per-replica health/weight by this; loaders
        # fall back to the export dir's basename when absent.
        "version": version,
        "class_names": class_names,
        "label_to_cat_id": (
            {str(k): v for k, v in label_to_cat_id.items()}
            if label_to_cat_id
            else None
        ),
    }
    path = os.path.join(output_dir, _MANIFEST)
    # The manifest is the export's commit record (serve/engine.from_export
    # trusts it): written atomically, and LAST — after every artifact it
    # names exists on disk.
    atomic_write_text(path, json.dumps(manifest, indent=2))
    return path


@dataclasses.dataclass
class LoadedDetector:
    """A deserialized export directory: shape-routed detection callables."""

    manifest: dict
    _fns: dict[tuple[int, int, int], Callable]

    def buckets(self) -> list[tuple[int, int, int]]:
        return sorted(self._fns)

    def bucket_shapes(self) -> list[tuple[int, int]]:
        """The distinct (H, W) buckets across all exported batch sizes."""
        return sorted({(h, w) for _b, h, w in self._fns})

    def batch_sizes(self, hw: tuple[int, int]) -> list[int]:
        """Exported batch sizes for one (H, W) bucket, ascending."""
        return sorted(b for b, h, w in self._fns if (h, w) == hw)

    def fn(self, batch_size: int, hw: tuple[int, int]):
        """The raw callable for one exact (batch, H, W) program."""
        return self._fns[(batch_size, *hw)]

    def warmup(self) -> None:
        """Run every exported program once on zeros so the deserialized
        executables are loaded/autotuned before real traffic (the serve
        engine's startup AOT warm)."""
        import jax

        for b, h, w in self.buckets():
            jax.block_until_ready(
                self._fns[(b, h, w)](np.zeros((b, h, w, 3), np.uint8))
            )

    def __call__(self, images: np.ndarray):
        """Run the artifact matching ``images.shape`` exactly."""
        b, h, w = images.shape[:3]
        fn = self._fns.get((b, h, w))
        if fn is None:
            raise ValueError(
                f"no exported program for input shape {(b, h, w)}; "
                f"available: {self.buckets()}"
            )
        return fn(images)


def load_model(output_dir: str) -> LoadedDetector:
    """Load an export directory produced by ``export_model``.

    Needs only jax — neither the model code nor the checkpoint.
    """
    from jax import export as jax_export

    with open(os.path.join(output_dir, _MANIFEST)) as f:
        manifest = json.load(f)
    fns: dict[tuple[int, int, int], Callable] = {}
    for entry in manifest["artifacts"]:
        with open(os.path.join(output_dir, entry["file"]), "rb") as f:
            exported = jax_export.deserialize(f.read())
        key = (entry["batch_size"], entry["height"], entry["width"])
        fns[key] = exported.call
    return LoadedDetector(manifest=manifest, _fns=fns)

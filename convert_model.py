#!/usr/bin/env python
"""Convert a training snapshot into serialized inference artifacts.

Parity with keras-retinanet's ``bin/convert_model.py`` (SURVEY.md M3): the
reference turned a training ``.h5`` into an inference model with anchors,
box decoding, clipping, and NMS appended.  Here the equivalent is exporting
the jitted detection program (forward → decode → clip → on-device batched
NMS, evaluate/detect.py) to self-contained StableHLO with the trained params
baked in — loadable with jax alone, no framework code (evaluate/export.py).

    python convert_model.py --snapshot-path ckpts --output exported \
        --num-classes 80 --backbone resnet50 --norm frozen_bn

One artifact is written per static shape bucket; ``--platforms cpu,tpu``
lowers each for several backends at once.
"""

from __future__ import annotations

import argparse
import os
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    p.add_argument("--snapshot-path", required=True,
                   help="orbax checkpoint directory (train.py --snapshot-path)")
    p.add_argument("--output", required=True, help="export directory")
    p.add_argument("--num-classes", type=int, required=True)
    from batchai_retinanet_horovod_coco_tpu.models.retinanet import BACKBONES

    p.add_argument("--backbone", default="resnet50", choices=BACKBONES)
    p.add_argument("--norm", default="gn", choices=["gn", "bn", "frozen_bn"])
    p.add_argument("--stem", default="space_to_depth",
                   choices=["conv", "space_to_depth", "space_to_depth4"],
                   help="stem formulation (param layout is identical; "
                        "either loads any snapshot)")
    p.add_argument("--f32", action="store_true",
                   help="compute in float32 (default bfloat16)")
    p.add_argument("--batch-size", type=int, default=None,
                   help="single exported batch size (shorthand for "
                        "--batch-sizes N; default 1)")
    p.add_argument("--batch-sizes", default=None, metavar="B1,B2",
                   help="comma-separated batch sizes: one artifact per "
                        "(bucket, batch) — the serve batcher pads a "
                        "partial batch to the smallest exported size "
                        "that fits it (serve/engine.py)")
    p.add_argument("--buckets", default=None, metavar="HxW,HxW",
                   help="explicit (H, W) shape buckets (e.g. "
                        "800x1344,1344x800); default: the pipeline's "
                        "default_buckets for the image sides, i.e. the "
                        "shapes an eval run actually emits")
    p.add_argument("--image-min-side", type=int, default=800)
    p.add_argument("--image-max-side", type=int, default=1333)
    p.add_argument("--score-threshold", type=float, default=0.05)
    p.add_argument("--nms-threshold", type=float, default=0.5)
    p.add_argument("--max-detections", type=int, default=300)
    from batchai_retinanet_horovod_coco_tpu.utils.cli import add_anchor_flags

    add_anchor_flags(p)
    p.add_argument("--export-version", default=None, metavar="VERSION",
                   help="rollout identity recorded in the manifest (the "
                        "serve fleet's router/canary gate attributes "
                        "per-replica health by it; default: the export "
                        "directory's basename at load time)")
    p.add_argument("--platforms", default=None,
                   help="comma-separated lowering targets, e.g. cpu,tpu "
                        "(default: the current backend only)")
    p.add_argument("--platform", default="auto",
                   choices=["auto", "cpu", "tpu"],
                   help="backend to run the export trace on")
    return p


def parse_buckets(text: str) -> tuple[tuple[int, int], ...]:
    """'800x1344,1344x800' → ((800, 1344), (1344, 800))."""
    buckets = []
    for part in text.split(","):
        part = part.strip()
        if not part:
            continue
        try:
            h, w = part.lower().split("x")
            buckets.append((int(h), int(w)))
        except ValueError:
            raise SystemExit(f"--buckets: not an HxW shape: {part!r}")
    if not buckets:
        raise SystemExit("--buckets: empty bucket list")
    return tuple(buckets)


def parse_batch_sizes(args) -> tuple[int, ...]:
    if args.batch_sizes is not None and args.batch_size is not None:
        raise SystemExit("pass --batch-size OR --batch-sizes, not both")
    if args.batch_sizes is not None:
        try:
            sizes = tuple(
                int(v) for v in args.batch_sizes.split(",") if v.strip()
            )
        except ValueError:
            raise SystemExit(
                f"--batch-sizes: not an int list: {args.batch_sizes!r}"
            )
        if not sizes or any(b < 1 for b in sizes):
            raise SystemExit(f"--batch-sizes: bad sizes {args.batch_sizes!r}")
        return tuple(sorted(set(sizes)))
    return (args.batch_size if args.batch_size is not None else 1,)


def main(argv: list[str] | None = None) -> str:
    args = build_parser().parse_args(argv)

    import jax

    if args.platform != "auto":
        jax.config.update("jax_platforms", args.platform)
    from batchai_retinanet_horovod_coco_tpu.utils.backend import (
        announce_devices,
        enable_compile_cache,
    )

    enable_compile_cache()
    announce_devices("convert_model")

    import jax.numpy as jnp
    import optax

    from batchai_retinanet_horovod_coco_tpu.data.pipeline import default_buckets
    from batchai_retinanet_horovod_coco_tpu.evaluate.detect import DetectConfig
    from batchai_retinanet_horovod_coco_tpu.evaluate.export import export_model
    from batchai_retinanet_horovod_coco_tpu.models import (
        RetinaNetConfig,
        build_retinanet,
    )
    from batchai_retinanet_horovod_coco_tpu.train import create_train_state
    from batchai_retinanet_horovod_coco_tpu.utils.checkpoint import (
        CheckpointManager,
        latest_step,
    )

    if latest_step(args.snapshot_path) is None:
        raise SystemExit(f"no checkpoint found under {args.snapshot_path}")

    from batchai_retinanet_horovod_coco_tpu.utils.cli import resolve_anchor_config

    # Flags + the anchor config train.py persisted beside the checkpoint
    # (conflicting flags abort; no flags = the saved config).
    anchor_config = resolve_anchor_config(args, args.snapshot_path)
    model = build_retinanet(
        RetinaNetConfig(
            num_classes=args.num_classes,
            backbone=args.backbone,
            norm_kind=args.norm,
            stem=args.stem,
            anchor=anchor_config,
            dtype=jnp.float32 if args.f32 else jnp.bfloat16,
        )
    )
    buckets = (
        parse_buckets(args.buckets)
        if args.buckets
        else default_buckets(args.image_min_side, args.image_max_side)
    )
    batch_sizes = parse_batch_sizes(args)
    state = create_train_state(
        model, optax.sgd(0.01), (1, *buckets[0], 3), jax.random.key(0)
    )
    # Metadata-driven restore: only params/batch_stats/step are needed, so
    # the snapshot's optimizer never has to be reconstructed here.
    restored = CheckpointManager(args.snapshot_path).restore_arrays()
    state = state.replace(
        step=restored["step"],
        params=restored["params"],
        batch_stats=restored["batch_stats"],
    )
    print(f"restored step {int(state.step)} from {args.snapshot_path}")

    platforms = tuple(args.platforms.split(",")) if args.platforms else None
    manifest = export_model(
        state,
        model,
        args.output,
        buckets,
        batch_sizes,
        DetectConfig(
            score_threshold=args.score_threshold,
            iou_threshold=args.nms_threshold,
            max_detections=args.max_detections,
            anchor=anchor_config,
        ),
        platforms=platforms,
        image_min_side=args.image_min_side,
        image_max_side=args.image_max_side,
        version=args.export_version,
    )
    sizes = {
        e: os.path.getsize(os.path.join(args.output, e))
        for e in os.listdir(args.output)
    }
    for name, size in sorted(sizes.items()):
        print(f"  {name}: {size / 1e6:.1f} MB")
    print(f"wrote {manifest}")
    return manifest


if __name__ == "__main__":
    main()

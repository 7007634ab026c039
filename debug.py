#!/usr/bin/env python
"""Dataset/anchor debugging CLI.

Parity with keras-retinanet's ``bin/debug.py`` (SURVEY.md M12), rethought for
a headless TPU VM: instead of an interactive cv2 window it (a) prints
per-image anchor-assignment statistics (positives / negatives / ignored, by
the same on-device matching the train step uses), and (b) optionally writes
annotated JPEGs (gt boxes green, positive anchors blue) to ``--output-dir``.

Usage:
  python debug.py coco /data/coco [--limit 8] [--output-dir /tmp/vis]
  python debug.py synthetic [--limit 8]
  python debug.py buckets /data/coco/annotations/instances_train2017.json
  python debug.py nans NUMERICS_DUMP.json

``nans`` is the numerics-triage driver (ISSUE 10): pretty-print the
NUMERICS_DUMP.json the train loop's abort path landed (obs/numerics.py
``provenance`` — first non-finite layer/loss term, batch source ids,
per-layer stats; no ``--debug-nans`` rerun was needed to produce it).
The localization logic lives ENTIRELY in obs/numerics.py — this
subcommand is a thin formatter over ``load_dump``/``format_dump``.

``buckets`` derives the EXACT static-bucket shares for a dataset from the
annotation file alone (COCO records carry width/height; nothing is
decoded): for every image it applies the reference resize rule + bucket
pick the pipeline uses (data/pipeline.resize_scale/pick_bucket) and prints
per-bucket image counts/shares.
"""

from __future__ import annotations

import argparse
import os

import jax
import numpy as np


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    sub = p.add_subparsers(dest="dataset_type", required=True)
    coco = sub.add_parser("coco")
    coco.add_argument("coco_path")
    coco.add_argument("--annotations", default="annotations/instances_train2017.json")
    coco.add_argument("--images", default="train2017")
    synth = sub.add_parser("synthetic")
    synth.add_argument("--synthetic-root", default="/tmp/synthetic_coco_debug")
    synth.add_argument("--synthetic-images", type=int, default=8)
    synth.add_argument("--synthetic-size", type=int, default=256)
    nans = sub.add_parser(
        "nans", help="triage a NUMERICS_DUMP.json (obs/numerics.py)"
    )
    nans.add_argument("dump_file", help="path to a NUMERICS_DUMP.json "
                      "written by the train loop's non-finite abort")
    nans.add_argument("--json", action="store_true", dest="as_json",
                      help="re-emit the dump as one JSON line (machine "
                           "consumers) instead of the human triage view")
    bk = sub.add_parser("buckets")
    bk.add_argument("annotation_file")
    bk.add_argument("--image-min-side", type=int, default=800)
    bk.add_argument("--image-max-side", type=int, default=1333)
    for sp in (coco, synth):
        sp.add_argument("--limit", type=int, default=8)
        sp.add_argument("--image-min-side", type=int, default=800)
        sp.add_argument("--image-max-side", type=int, default=1333)
        sp.add_argument("--max-gt", type=int, default=None,
                        help="gt padding; default auto-sizes to the dataset")
        sp.add_argument("--output-dir", default=None)
        # Same anchor surface as train.py (utils/cli.py), so assignment
        # statistics reflect the anchors a run would actually train with.
        from batchai_retinanet_horovod_coco_tpu.utils.cli import add_anchor_flags

        add_anchor_flags(sp)
    return p


def bucket_shares(
    annotation_file: str, min_side: int, max_side: int
) -> dict[str, dict]:
    """Per-bucket image counts/shares for a COCO-format annotation file.

    Pure metadata pass (width/height from the records; no image decode):
    for each image, apply the pipeline's own resize rule and bucket pick
    (data/pipeline.resize_scale/pick_bucket over
    default_buckets(min_side, max_side)) and tally.
    """
    from batchai_retinanet_horovod_coco_tpu.data import CocoDataset
    from batchai_retinanet_horovod_coco_tpu.data.pipeline import (
        bucket_for_source,
        default_buckets,
    )

    dataset = CocoDataset(annotation_file, image_dir=".")
    buckets = default_buckets(min_side, max_side)
    counts: dict[tuple[int, int], int] = {b: 0 for b in buckets}
    for rec in dataset.records:
        counts[
            bucket_for_source(
                rec.height, rec.width, min_side, max_side, buckets
            )
        ] += 1
    total = max(sum(counts.values()), 1)
    return {
        f"{b[0]}x{b[1]}": {"count": n, "share": n / total}
        for b, n in counts.items()
    }


def _run_buckets(args) -> dict:
    shares = bucket_shares(
        args.annotation_file, args.image_min_side, args.image_max_side
    )
    for name, row in shares.items():
        print(f"{name}: {row['count']} images ({row['share']:.1%})")
    return {"shares": shares}


def _run_nans(args) -> dict:
    """Thin driver over obs/numerics.py — no tree-walk lives here."""
    import json

    from batchai_retinanet_horovod_coco_tpu.obs import numerics

    dump = numerics.load_dump(args.dump_file)
    if args.as_json:
        print(json.dumps(dump, sort_keys=True))
    else:
        print(numerics.format_dump(dump))
    return dump


def main(argv=None) -> list[dict]:
    args = build_parser().parse_args(argv)
    # Host debugging tool: tiny per-image ops, not worth a TPU round trip.
    jax.config.update("jax_platforms", "cpu")

    if args.dataset_type == "nans":
        return [_run_nans(args)]
    if args.dataset_type == "buckets":
        return [_run_buckets(args)]

    from batchai_retinanet_horovod_coco_tpu.data import (
        CocoDataset,
        PipelineConfig,
        build_pipeline,
        make_synthetic_coco,
    )
    from batchai_retinanet_horovod_coco_tpu.ops import anchors as anchors_lib
    from batchai_retinanet_horovod_coco_tpu.ops import matching as matching_lib

    if args.dataset_type == "synthetic":
        size = (args.synthetic_size, args.synthetic_size)
        ann = make_synthetic_coco(
            args.synthetic_root, num_images=args.synthetic_images,
            image_size=size, split="train",
        )
        dataset = CocoDataset(ann, os.path.join(args.synthetic_root, "train"))
        args.image_min_side = min(args.image_min_side, size[0])
        args.image_max_side = min(args.image_max_side, size[1])
    else:
        dataset = CocoDataset(
            os.path.join(args.coco_path, args.annotations),
            os.path.join(args.coco_path, args.images),
        )

    from batchai_retinanet_horovod_coco_tpu.data.pipeline import (
        default_buckets,
        resolve_max_gt,
    )
    from batchai_retinanet_horovod_coco_tpu.utils.cli import make_anchor_config

    anchor_config = make_anchor_config(args)
    buckets = default_buckets(args.image_min_side, args.image_max_side)
    pipe = build_pipeline(
        dataset,
        PipelineConfig(
            batch_size=1, buckets=buckets, min_side=args.image_min_side,
            max_side=args.image_max_side,
            max_gt=resolve_max_gt(args.max_gt, dataset),
            shuffle=False, hflip_prob=0.0, num_workers=2,
        ),
        train=False,
    )

    assign = jax.jit(
        lambda anchors, b, l, m: matching_lib.anchor_targets(
            anchors, b, l, m, dataset.num_classes, matching_lib.MatchingConfig()
        ),
        static_argnums=(),
    )
    anchor_cache: dict[tuple[int, int], np.ndarray] = {}
    report: list[dict] = []
    for batch in pipe:
        if len(report) >= args.limit:
            break
        hw = batch.images.shape[1:3]
        if hw not in anchor_cache:
            anchor_cache[hw] = anchors_lib.anchors_for_image_shape(
                hw, anchor_config
            )
        anchors = anchor_cache[hw]
        targets = assign(
            anchors, batch.gt_boxes[0], batch.gt_labels[0], batch.gt_mask[0]
        )
        state = np.asarray(targets.state)
        rec = {
            "image_id": int(batch.image_ids[0]),
            "gt": int(batch.gt_mask[0].sum()),
            "anchors": int(state.size),
            "positive": int((state == matching_lib.POSITIVE).sum()),
            "ignored": int((state == matching_lib.IGNORE).sum()),
        }
        rec["negative"] = rec["anchors"] - rec["positive"] - rec["ignored"]
        report.append(rec)
        print(
            f"image {rec['image_id']}: {rec['gt']} gt, {rec['anchors']} anchors "
            f"→ {rec['positive']} pos / {rec['ignored']} ignore / {rec['negative']} neg",
            flush=True,
        )
        if args.output_dir:
            _write_vis(args.output_dir, batch, anchors, state)

    unmatched = [r for r in report if r["gt"] > 0 and r["positive"] == 0]
    if unmatched:
        print(f"WARNING: {len(unmatched)} image(s) with gt but NO positive anchors")
    return report


def _write_vis(out_dir: str, batch, anchors: np.ndarray, state: np.ndarray) -> None:
    from PIL import Image, ImageDraw

    from batchai_retinanet_horovod_coco_tpu.data.pipeline import (
        IMAGENET_MEAN,
        IMAGENET_STD,
    )

    os.makedirs(out_dir, exist_ok=True)
    if batch.images.dtype == np.uint8:  # pipeline default: raw uint8
        im = Image.fromarray(batch.images[0])
    else:  # host_normalize=True: invert the ImageNet normalization
        img = (batch.images[0] * IMAGENET_STD + IMAGENET_MEAN) * 255.0
        im = Image.fromarray(np.clip(img, 0, 255).astype(np.uint8))
    draw = ImageDraw.Draw(im)
    from batchai_retinanet_horovod_coco_tpu.ops.matching import POSITIVE

    for a in anchors[state == POSITIVE]:
        draw.rectangle([float(v) for v in a], outline=(60, 120, 255))
    for box, valid in zip(batch.gt_boxes[0], batch.gt_mask[0]):
        if valid:
            draw.rectangle([float(v) for v in box], outline=(40, 220, 40), width=2)
    im.save(os.path.join(out_dir, f"{int(batch.image_ids[0]):012d}.jpg"))


if __name__ == "__main__":
    main()

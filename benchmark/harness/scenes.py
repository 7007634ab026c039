"""Seeded synthetic scenes: coloured rectangles on low-contrast noise.

A copy of the renderer in the program's ``data/synthetic.py`` (which writes
JPEGs to disk); this one renders straight into uint8 arrays.  Uniform noise
alone has no spatial structure, so a detector's score field over it is flat;
rectangles give edges and regions at the scales the anchors cover.
"""

from __future__ import annotations

import numpy as np


def _color(label: int) -> np.ndarray:
    # A fixed, well-spread colour per class id (80 classes and more).
    return np.array(
        [(label * 67 + 29) % 256, (label * 131 + 71) % 256, (label * 197 + 113) % 256],
        np.uint8,
    )


def random_boxes(rng, hw, count_range, size_range, num_classes):
    """``bench.py::make_batch``'s box distribution: corners uniform over the
    image less a 64 px margin, sides uniform in ``size_range``, clipped."""
    h, w = hw
    n = int(rng.integers(count_range[0], count_range[1] + 1))
    xy = rng.uniform(0, [max(1, w - 64), max(1, h - 64)], (n, 2))
    wh = rng.uniform(size_range[0], size_range[1], (n, 2))
    boxes = np.empty((n, 4), np.float32)
    boxes[:, 0], boxes[:, 1] = xy[:, 0], xy[:, 1]
    boxes[:, 2] = np.minimum(xy[:, 0] + wh[:, 0], w)
    boxes[:, 3] = np.minimum(xy[:, 1] + wh[:, 1], h)
    labels = rng.integers(0, num_classes, n).astype(np.int32)
    return boxes, labels


def render(rng, hw, boxes, labels) -> np.ndarray:
    """(H, W, 3) uint8: noise in [90, 120), one filled rectangle per box."""
    h, w = hw
    canvas = rng.integers(90, 120, size=(h, w, 3), dtype=np.uint8)
    for (x1, y1, x2, y2), label in zip(boxes, labels):
        canvas[int(y1):max(int(y1) + 1, int(y2)), int(x1):max(int(x1) + 1, int(x2))] = _color(int(label))
    return canvas


def scene(rng, hw, count_range=(4, 23), size_range=(16, 256), num_classes=80):
    boxes, labels = random_boxes(rng, hw, count_range, size_range, num_classes)
    return render(rng, hw, boxes, labels), boxes, labels


def labelled_batches(seed, n_batches, batch, hw, max_gt, **kw) -> list[dict]:
    """Train batches: images plus padded ground truth (max_gt rows)."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_batches):
        images = np.empty((batch, *hw, 3), np.uint8)
        gt_boxes = np.zeros((batch, max_gt, 4), np.float32)
        gt_labels = np.zeros((batch, max_gt), np.int32)
        gt_mask = np.zeros((batch, max_gt), bool)
        for b in range(batch):
            images[b], boxes, labels = scene(rng, hw, **kw)
            n = min(len(boxes), max_gt)
            gt_boxes[b, :n], gt_labels[b, :n], gt_mask[b, :n] = boxes[:n], labels[:n], True
        out.append(
            {"images": images, "gt_boxes": gt_boxes, "gt_labels": gt_labels, "gt_mask": gt_mask}
        )
    return out

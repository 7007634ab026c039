"""Operations and bytes the algorithm needs, from shapes alone.

Model FLOPs count the convolutions of the published architecture (2 per
multiply-accumulate), nothing recomputed and nothing the compiler adds, so
the number does not move when the program changes.  Training counts the
forward once and the backward twice (input and weight gradients).
"""

from __future__ import annotations

import math


def _conv(h, w, k, cin, cout, stride=1):
    """(MACs, out_h, out_w) of a k x k convolution, output ceil(d / stride)."""
    oh, ow = math.ceil(h / stride), math.ceil(w / stride)
    return oh * ow * k * k * cin * cout, oh, ow


def _resnet(h, w, stage_sizes):
    """Bottleneck ResNet v1.5 (stride on the 3x3), He et al. 2015 table 1.
    The 7x7/2 stem is counted in its published form; the program's
    space-to-depth stem does 192 instead of 147 MACs per output."""
    macs, h, w = _conv(h, w, 7, 3, 64, 2)
    h, w = math.ceil(h / 2), math.ceil(w / 2)  # 3x3/2 max pool
    cin, feats = 64, {}
    for stage, blocks in enumerate(stage_sizes):
        f = 64 * 2**stage
        for b in range(blocks):
            s = 2 if (b == 0 and stage > 0) else 1
            m1, _, _ = _conv(h, w, 1, cin, f)
            m2, oh, ow = _conv(h, w, 3, f, f, s)
            m3, _, _ = _conv(oh, ow, 1, f, 4 * f)
            macs += m1 + m2 + m3
            if b == 0:
                macs += _conv(h, w, 1, cin, 4 * f, s)[0]
            h, w, cin = oh, ow, 4 * f
        feats[stage + 2] = (h, w, cin)
    return macs, feats


def _fpn_and_heads(feats, model):
    """FPN (Lin et al. 2017, keras-retinanet form: P6 from C5, P7 from P6)
    and the two shared subnets over P3..P7."""
    c = model["fpn_channels"]
    macs, levels = 0, {}
    for l in (3, 4, 5):
        h, w, cin = feats[l]
        macs += _conv(h, w, 1, cin, c)[0] + _conv(h, w, 3, c, c)[0]
        levels[l] = (h, w)
    h, w, cin = feats[5]
    m6, h6, w6 = _conv(h, w, 3, cin, c, 2)
    m7, h7, w7 = _conv(h6, w6, 3, c, c, 2)
    macs += m6 + m7
    levels[6], levels[7] = (h6, w6), (h7, w7)
    fpn = macs
    hw_, d, a, k = model["head_width"], model["head_depth"], model["anchors_per_location"], model["num_classes"]
    heads = 0
    for h, w in levels.values():
        tower = _conv(h, w, 3, c, hw_)[0] + (d - 1) * _conv(h, w, 3, hw_, hw_)[0]
        heads += 2 * tower + _conv(h, w, 3, hw_, a * k)[0] + _conv(h, w, 3, hw_, a * 4)[0]
    anchors = sum(h * w for h, w in levels.values()) * a
    return fpn, heads, anchors


def forward_macs(model: dict, hw: tuple[int, int]) -> dict:
    """Per image: MACs of backbone, FPN and heads, and the anchor count."""
    h, w = hw
    if model["backbone_family"] == "resnet":
        backbone, feats = _resnet(h, w, model["stage_sizes"])
    else:
        raise ValueError(f"no FLOP model for backbone family {model['backbone_family']!r}")
    fpn, heads, anchors = _fpn_and_heads(feats, model)
    return {"backbone": backbone, "fpn": fpn, "heads": heads,
            "total": backbone + fpn + heads, "anchors": anchors}


def train_flops_per_image(model: dict, hw) -> float:
    return 3 * 2.0 * forward_macs(model, hw)["total"]


def assign_fused_cost(batch: int, anchors: int, max_gt: int) -> dict:
    """The fused anchor->gt assignment (``ops/pallas/matching.py::assign_fused``):
    per anchor-gt pair an IoU (12 ops), the running max and argmax (2), the
    one-hot row lookup (8 rows x 2) and the per-gt best anchor (2); it reads
    the anchors (4 f32) once per image and writes 8 f32 rows per anchor."""
    return {
        "ops": float(batch) * anchors * max_gt * 32,
        "bytes": float(batch) * (anchors * (4 + 8) * 4 + max_gt * (6 + 8 + 8) * 4),
    }


def roofline_share(cost: dict, seconds: float, peaks: dict) -> dict:
    """Least time the chip could take over the time it took."""
    t_ops = cost["ops"] / peaks["flops_bf16"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {
        "share": max(t_ops, t_bytes) / seconds,
        "bound": "bytes" if t_bytes >= t_ops else "ops",
    }

"""The language models ``train.py lm-synthetic`` and the benchmark build,
picked by a preset's name or by the ``model_type`` of a published
``config.json``'s keys."""

from __future__ import annotations

import json


def build_language_model(spec: str | dict, **overrides):
    """``spec``: ``tiny`` / ``tiny-moe`` (the CPU tests' presets), the path of
    a JSON file with the published keys, or those keys as a dict.
    ``overrides`` replace fields of the model's config (``dtype``)."""
    import dataclasses

    from batchai_retinanet_horovod_coco_tpu.models import deepseek_v2, granite_hybrid

    presets = {"tiny": (granite_hybrid.GraniteHybrid, granite_hybrid.TINY),
               "tiny-moe": (deepseek_v2.DeepseekV2, deepseek_v2.TINY)}
    if isinstance(spec, str) and spec in presets:
        model, config = presets[spec]
        return model(dataclasses.replace(config, **overrides))
    if isinstance(spec, str):
        with open(spec) as f:
            spec = json.load(f)
    by_type = {"granitemoehybrid": (granite_hybrid.GraniteHybrid, granite_hybrid.GraniteHybridConfig),
               "deepseek_v2": (deepseek_v2.DeepseekV2, deepseek_v2.DeepseekV2Config)}
    if spec.get("model_type") not in by_type:
        raise ValueError(f"model_type {spec.get('model_type')!r}: lm-synthetic trains {sorted(by_type)}")
    model, config = by_type[spec["model_type"]]
    return model(config.from_hf(spec, **overrides))

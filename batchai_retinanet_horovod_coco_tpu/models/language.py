"""The language models ``train.py lm-synthetic`` and the benchmark build,
picked by a preset's name or by the ``model_type`` of a published
``config.json``'s keys.  A model's module is imported when it is asked for."""

from __future__ import annotations

import dataclasses
import importlib
import json

# preset or ``model_type`` -> (module under models/, the model's class, its config: a class or the preset)
PRESETS = {"tiny": ("granite_hybrid", "GraniteHybrid", "TINY"),
           "tiny-moe": ("deepseek_v2", "DeepseekV2", "TINY"),
           "tiny-nemotron": ("nemotron_h", "NemotronH", "TINY"),
           "tiny-keye": ("keye_vl2", "KeyeVL2", "TINY"),
           "tiny-olmo": ("olmo_hybrid", "OlmoHybrid", "TINY"),
           "tiny-afmoe": ("afmoe", "Afmoe", "TINY")}
BY_TYPE = {"granitemoehybrid": ("granite_hybrid", "GraniteHybrid", "GraniteHybridConfig"),
           "deepseek_v2": ("deepseek_v2", "DeepseekV2", "DeepseekV2Config"),
           "nemotron_h": ("nemotron_h", "NemotronH", "NemotronHConfig"),
           "KeyeVL2": ("keye_vl2", "KeyeVL2", "KeyeVL2Config"),
           "olmo_hybrid": ("olmo_hybrid", "OlmoHybrid", "OlmoHybridConfig"),
           "afmoe": ("afmoe", "Afmoe", "AfmoeConfig")}


def _load(entry):
    module = importlib.import_module(f"batchai_retinanet_horovod_coco_tpu.models.{entry[0]}")
    return getattr(module, entry[1]), getattr(module, entry[2])


def build_language_model(spec: str | dict, **overrides):
    """``spec``: ``tiny`` / ``tiny-moe`` / ``tiny-nemotron`` / ``tiny-keye`` / ``tiny-olmo`` /
    ``tiny-afmoe`` (the CPU tests' presets), the path of a JSON file with the published keys, or those keys
    as a dict.  ``overrides`` replace fields of the model's config (``dtype``)."""
    if isinstance(spec, str) and spec in PRESETS:
        model, config = _load(PRESETS[spec])
        return model(dataclasses.replace(config, **overrides))
    if isinstance(spec, str):
        with open(spec) as f:
            spec = json.load(f)
    if spec.get("model_type") not in BY_TYPE:
        raise ValueError(f"model_type {spec.get('model_type')!r}: lm-synthetic trains {sorted(BY_TYPE)}")
    model, config = _load(BY_TYPE[spec["model_type"]])
    return model(config.from_hf(spec, **overrides))

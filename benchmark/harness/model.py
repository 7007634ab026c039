"""The system under test, built from a configuration file."""

from __future__ import annotations

import functools

MODEL_KEYS = ("backbone", "norm_kind", "fpn_channels", "head_width", "head_depth",
              "num_classes", "prior_prob")


def build_model(config: dict):
    import jax.numpy as jnp

    from batchai_retinanet_horovod_coco_tpu.models import RetinaNetConfig, build_retinanet

    m = config["model"]
    model = build_retinanet(RetinaNetConfig(
        dtype=getattr(jnp, m["dtype"]), **{k: m[k] for k in MODEL_KEYS}
    ))
    if model.config.anchors_per_location != m["anchors_per_location"]:
        raise ValueError("the program's anchors per location differ from the configuration's")
    return model


def state_maker(model, tx, init_hw=(64, 64)):
    """``make(seed) -> TrainState``: weights (and optimizer slots) made on
    the device in ONE jitted call from the seed, in the types they are
    served in.  The model is fully convolutional, so a small example shape
    gives the parameters every bucket uses."""
    import jax

    from batchai_retinanet_horovod_coco_tpu.train import create_train_state

    init = jax.jit(functools.partial(create_train_state, model, tx, (1, *init_hw, 3)))
    return lambda seed: init(jax.random.key(seed))

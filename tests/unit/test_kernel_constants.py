"""A kernel's parameters are constants, stated once (PR 45 deleted the
per-device registry that used to fill ``None`` config fields): each config
default IS the value the registry's built-in defaults held, equals its
kernel module's constant where the config states a literal to stay free of
Pallas, and a default-built program is the explicitly-pinned program.
"""

import sys

import jax
import jax.numpy as jnp
import pytest

from batchai_retinanet_horovod_coco_tpu import losses
from batchai_retinanet_horovod_coco_tpu.evaluate.detect import (
    DetectConfig,
    make_detect_fn,
)
from batchai_retinanet_horovod_coco_tpu.ops import matching


def _from_state_batch_sizes():
    import inspect

    from batchai_retinanet_horovod_coco_tpu.serve.engine import DetectEngine

    return inspect.signature(DetectEngine.from_state).parameters["batch_sizes"].default


def _kernel_constant(module: str, name: str):
    import importlib

    return getattr(importlib.import_module(f"batchai_retinanet_horovod_coco_tpu.ops.pallas.{module}"), name)


# (what reads the default, the value the registry defaulted to, the kernel module's constant or None)
DEFAULTS = {
    "MatchingConfig.pallas_tile_a": (lambda: matching.MatchingConfig().pallas_tile_a, 8192, ("matching", "TILE_A")),
    "LossConfig.focal_fwd_tile_a": (lambda: losses.LossConfig().focal_fwd_tile_a, 8192, ("focal", "FWD_TILE_A")),
    "LossConfig.focal_bwd_tile_a": (lambda: losses.LossConfig().focal_bwd_tile_a, 4096, ("focal", "BWD_TILE_A")),
    "LossConfig.pallas_focal": (lambda: losses.LossConfig().pallas_focal, False, None),
    "DetectConfig.pre_nms_size": (lambda: DetectConfig().pre_nms_size, 1000, None),
    "DetectConfig.nms_impl": (lambda: DetectConfig().nms_impl, "xla", None),
    "DetectConfig.nms_block_k": (lambda: DetectConfig().nms_block_k, 256, ("nms", "DEFAULT_BLOCK_K")),
    "DetectEngine.from_state.batch_sizes": (_from_state_batch_sizes, (8,), None),
}


@pytest.mark.parametrize("field", sorted(DEFAULTS))
def test_a_default_is_the_value_and_its_kernels_constant(field):
    read, value, constant = DEFAULTS[field]
    got = read()
    assert got == value and type(got) is type(value)
    if constant is not None:
        assert got == _kernel_constant(*constant)


def test_matching_still_chooses_its_kernel_from_the_backend():
    """``fused_pallas=None`` is not a third state of the kind that went: it
    is "fused on a TPU, jax.numpy elsewhere", read from the backend."""
    assert matching.MatchingConfig().fused_pallas is None


@pytest.mark.parametrize("devices", [1, 4])
def test_the_default_detect_program_is_the_pinned_one(tiny_model_and_state, devices):
    from batchai_retinanet_horovod_coco_tpu.parallel import make_mesh

    model, state = tiny_model_and_state
    mesh = None if devices == 1 else make_mesh(devices)
    images = jax.ShapeDtypeStruct((4, 64, 64, 3), jnp.uint8)

    def text(config):
        return make_detect_fn(model, (64, 64), config, mesh=mesh).lower(state, images).as_text()

    pinned = DetectConfig(pre_nms_size=1000, nms_impl="xla", nms_block_k=256)
    assert text(DetectConfig()) == text(pinned)


@pytest.mark.parametrize("others", [{}, {"pre_nms_size": 64, "nms_block_k": 128}])
def test_a_typod_nms_impl_raises(others):
    with pytest.raises(ValueError, match="nms_impl must be 'xla' or 'pallas'"):
        DetectConfig(nms_impl="palas", **others)


def test_building_a_step_looks_nothing_up(tiny_model_and_state, capfd):
    import batchai_retinanet_horovod_coco_tpu.serve.engine  # noqa: F401
    import batchai_retinanet_horovod_coco_tpu.train.loop  # noqa: F401
    from batchai_retinanet_horovod_coco_tpu.train.step import make_train_step

    model, state = tiny_model_and_state
    batch = dict(images=jnp.zeros((2, 64, 64, 3), jnp.uint8), gt_boxes=jnp.zeros((2, 2, 4)),
                 gt_labels=jnp.zeros((2, 2), jnp.int32), gt_mask=jnp.zeros((2, 2), bool))
    make_train_step(model, (64, 64), 3, donate_state=False).lower(state, batch)
    # the registry's lookup said so on stderr where it found no file for the device
    assert "fallback" not in capfd.readouterr().err
    assert not [m for m in sys.modules if m.startswith("batchai_retinanet_horovod_coco_tpu.") and ".tune" in m]

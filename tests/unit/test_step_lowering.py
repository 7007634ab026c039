"""Detection's step flavours lower to the text they lowered to before the
task seam (train/task.py): single device, data-parallel, compressed
gradients with and without overlap, ZeRO, ZeRO with the compressed gather,
and the spatial step, for each norm kind, with and without the numerics
summary - 42 programs.  ``tests/fixtures/detection_step_lowering.json`` holds
the sha256 of each ``lowered.as_text()`` as the PARENT of the PR that
brought the seam gave it (this file run as a script with that tree on the
path wrote it); a later PR that means to change a detection step rewrites
the fixture the same way and says so.
"""

import hashlib
import itertools
import json
import os

import jax
import jax.numpy as jnp
import pytest

FIXTURE = os.path.join(os.path.dirname(__file__), "..", "fixtures", "detection_step_lowering.json")
HW, NUM_CLASSES, BATCH = (64, 64), 3, 4
CASES = list(itertools.product(("frozen_bn", "bn", "gn"), (False, True)))


def flavour_hashes(norm: str, numerics: bool) -> dict[str, str]:
    from batchai_retinanet_horovod_coco_tpu.comm import CommConfig
    from batchai_retinanet_horovod_coco_tpu.comm.compress import init_comm_state
    from batchai_retinanet_horovod_coco_tpu.models import RetinaNetConfig, build_retinanet
    from batchai_retinanet_horovod_coco_tpu.obs.numerics import NumericsConfig
    from batchai_retinanet_horovod_coco_tpu.parallel import make_mesh, zero
    from batchai_retinanet_horovod_coco_tpu.parallel.mesh import DATA_AXIS, make_mesh_2d
    from batchai_retinanet_horovod_coco_tpu.train import create_train_state
    from batchai_retinanet_horovod_coco_tpu.train.optim import OptimizerConfig, make_optimizer
    from batchai_retinanet_horovod_coco_tpu.train.step import make_train_step, make_train_step_spatial

    batch = dict(images=jnp.zeros((BATCH, *HW, 3), jnp.uint8),
                 gt_boxes=jnp.tile(jnp.asarray([[8.0, 8.0, 40.0, 40.0]]), (BATCH, 2, 1)),
                 gt_labels=jnp.ones((BATCH, 2), jnp.int32), gt_mask=jnp.ones((BATCH, 2), bool))
    model = build_retinanet(RetinaNetConfig(num_classes=NUM_CLASSES, backbone="resnet_test", norm_kind=norm,
                                            fpn_channels=16, head_width=16, head_depth=1, dtype=jnp.float32))
    optimizer = lambda **kw: make_optimizer(OptimizerConfig(schedule="constant", warmup_steps=0), **kw)[0]
    state = create_train_state(model, optimizer(), (1, *HW, 3), jax.random.key(0))
    mesh = make_mesh(4)
    kw = dict(donate_state=False, numerics=NumericsConfig(enabled=numerics))
    out = {}

    def put(name, step, st):
        out[f"{name}/{norm}/numerics={numerics}"] = hashlib.sha256(step.lower(st, batch).as_text().encode()).hexdigest()

    put("single", make_train_step(model, HW, NUM_CLASSES, **kw), state)
    put("dp4", make_train_step(model, HW, NUM_CLASSES, mesh=mesh, **kw), state)
    for overlap in (False, True):
        comm = CommConfig(compress="int8", overlap=overlap)
        residuals = {k: jnp.asarray(v) for k, v in init_comm_state(state.params, comm, 4).items()}
        put(f"comm4/overlap={overlap}", make_train_step(model, HW, NUM_CLASSES, mesh=mesh, comm=comm, **kw),
            state.replace(comm_state=residuals))
    tx = optimizer(shard_clip_axis=DATA_AXIS)
    zstate = create_train_state(model, tx, (1, *HW, 3), jax.random.key(0), init_opt_state=False)
    zstate = zstate.replace(opt_state=zero.init_sharded_opt_state(tx, zstate.params, mesh))
    put("zero4", make_train_step(model, HW, NUM_CLASSES, mesh=mesh, shard_weight_update=True, **kw), zstate)
    put("zero_comm4", make_train_step(model, HW, NUM_CLASSES, mesh=mesh, shard_weight_update=True,
                                      comm=CommConfig(compress="int8"), **kw), zstate)
    put("spatial", make_train_step_spatial(model, HW, NUM_CLASSES, mesh=make_mesh_2d(2, 2),
                                           allow_data_axis_divergence=True, **kw), state)
    return out


@pytest.mark.parametrize("norm,numerics", CASES)
def test_detection_step_flavours_lower_to_the_recorded_text(norm, numerics):
    with open(FIXTURE) as f:
        recorded = json.load(f)
    got = flavour_hashes(norm, numerics)
    assert len(got) == 7 and set(got) <= set(recorded)
    assert {k: recorded[k] for k in got} == got


SIGNATURES = {
    "make_train_step": ("model", "image_hw", "num_classes", "mesh", "loss_config", "matching_config", "anchor_config",
                        "donate_state", "shard_weight_update", "comm", "topology", "numerics", "task"),
    "run_training": ("model", "state", "batches", "num_classes", "config", "mesh", "loss_config", "matching_config",
                     "anchor_config", "schedule", "eval_fn", "logger", "shard_weight_update", "comm", "topology",
                     "allow_data_axis_divergence", "task"),
}


@pytest.mark.parametrize("name", sorted(SIGNATURES))
def test_the_train_paths_parameters_are_the_recorded_names(name):
    """PR 28 took a deprecated alias out of both; the next parameter
    either gains is a diff of this tuple, read by a reviewer."""
    import inspect

    from batchai_retinanet_horovod_coco_tpu.train.loop import run_training
    from batchai_retinanet_horovod_coco_tpu.train.step import make_train_step

    fn = {"make_train_step": make_train_step, "run_training": run_training}[name]
    assert tuple(inspect.signature(fn).parameters) == SIGNATURES[name]


if __name__ == "__main__":  # with the tree to record on PYTHONPATH and 8 CPU devices
    hashes = {}
    for case in CASES:
        hashes.update(flavour_hashes(*case))
    with open(FIXTURE, "w") as f:
        json.dump(hashes, f, indent=1, sort_keys=True)
    print(f"{len(hashes)} flavours recorded from {jax.__name__} {jax.__version__}")

"""With ``window=None`` the train steps of the four accepted cells that run
``ops/attention.py``'s splash kernels are the programs the parent of PR 46
lowered: the whole step (model, loss, counters, optimizer, numerics) lowered for
the TPU platform at the cell's sizes, every Mosaic kernel's body printed without
source locations (a moved line is no change), against the hashes recorded from
the parent's tree (``tests/fixtures/splash_cells_step_lowering.json``).
PR 47 recorded the four again (the fixture says how and why): it rewrote
``lm_layers.next_token_loss``, which every one of these steps ends in, and the texts
differ from its parent's in the loss's lines and its gradient's alone; what PR 46
held, that ``window=None`` changes nothing, the recorded texts still hold for every
PR after it."""

import base64
import hashlib
import json
import os
import re
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from batchai_retinanet_horovod_coco_tpu.models import lm_layers
from batchai_retinanet_horovod_coco_tpu.models.language import build_language_model
from batchai_retinanet_horovod_coco_tpu.obs.numerics import NumericsConfig
from batchai_retinanet_horovod_coco_tpu.train import create_train_state
from batchai_retinanet_horovod_coco_tpu.train.optim import OptimizerConfig, make_optimizer
from batchai_retinanet_horovod_coco_tpu.train.step import make_train_step
from batchai_retinanet_horovod_coco_tpu.train.task import LMTask

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIXTURE = os.path.join(REPO, "tests", "fixtures", "splash_cells_step_lowering.json")
CELLS = {"granite-h-train-pack8k": ("granite-4.0-h-micro-p1", "lm-train-pack8k"),
         "dsv2-lite-train-pack8k": ("deepseek-v2-lite-ep8", "lm-moe-train-pack8k-b2"),
         "nemo3-nano-train-pack8k": ("nemotron-3-nano-30b-ep16", "lm-hybrid-moe-train-pack8k-b2"),
         "olmo-hybrid-train-pack8k": ("olmo-hybrid-7b-p1", "lm-linear-train-pack8k-fixed")}


def _json(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


def _without_locations(text: str) -> tuple[str, int]:
    """``text`` with every Mosaic kernel's body (MLIR bytecode that holds the file,
    line and column of each operation) printed without them, and how many there were."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def body(match):
        context = mlir.make_ir_context()
        context.allow_unregistered_dialects = True
        with context:
            return ir.Module.parse(base64.b64decode(match.group(1))).operation.get_asm(enable_debug_info=False)

    return re.subn(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body, text)


def _lowered_step(cell: str) -> str:
    cfg, t = _json("benchmark", "configs", CELLS[cell][0] + ".json"), _json("benchmark", "traffic", CELLS[cell][1] + ".json")
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            mock.patch.object(lm_layers, "device_memory_limit", lambda: 16_909_336_064):
        model, task = build_language_model(cfg, dtype=jnp.bfloat16), LMTask()
        tx, _ = make_optimizer(OptimizerConfig(
            optimizer="adamw", schedule="constant", warmup_steps=0, base_lr=t["lr"], world_size=1, adam_b2=t["adam_b2"],
            adam_eps=t["adam_eps"], weight_decay=t["weight_decay"], clip_global_norm=t["clip_global_norm"]))
        bucket = (t["per_chip_batch"], t["seq_len"])
        state = jax.eval_shape(lambda key: create_train_state(model, tx, bucket, key, example_dtype=task.example_dtype),
                               jax.random.key(0))
        step = make_train_step(model, bucket, 0, numerics=NumericsConfig(enabled=True), task=task, donate_state=True)
        batch = {k: jax.ShapeDtypeStruct(bucket, jnp.int32) for k in ("tokens", "segment_ids")}
        return step.trace(state, batch).lower(lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("cell", CELLS)
def test_the_accepted_splash_cells_step_is_the_program_the_parent_of_pr_46_lowered(cell):
    text, kernels = _without_locations(_lowered_step(cell))
    assert kernels >= 3 and text.count("tpu_custom_call") >= kernels  # attention's three kernels a layer at least
    assert hashlib.sha256(text.encode()).hexdigest() == _json("tests", "fixtures", "splash_cells_step_lowering.json")[cell]

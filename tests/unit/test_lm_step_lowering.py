"""The language-model task takes its scopes, ``run_meta`` and loss from the
model (PR 30), and RMSNorm, the matmul, the gated MLP, the lookup, the head
and the loss moved to ``models/lm_layers.py``: granite's step still lowers to
the text it lowered to before, byte for byte.
``tests/fixtures/granite_step_lowering.json`` holds the sha256 of
``lowered.as_text()`` as PR 29's tree gave it (this file's ``lowered_hash``
run with that tree on the path); a later PR that means to change granite's
step records it again the same way and says so.

``tests/fixtures/lm_expert_step_lowering.json`` holds the same for the two
mixture-of-experts models' tiny steps (``tiny-moe``: DeepSeek-V2, ``tiny-nemotron``:
Nemotron-H) as PR 35's tree gave them, recorded by PR 38, which added a fourth
language model beside them, functions to ``ops/moe.py`` and ``ops/rope.py`` and
names beneath ``attention`` in ``STEP_SCOPES``: none of that may reach their steps.
PR 44 recorded ``tiny-moe``'s two again: ``gated_mlp`` names its product with
``gate_up`` (``lm_layers.MLP_GATE_UP``; the CPU's policy does not list the name), and
the text of DeepSeek-V2's step is the old one but for the NUMBERS MLIR gives to
repeated private functions from the first shared expert on (``@silu_208`` is
``@silu_209``, and so on: no line differs once ``_<n>`` is cut from the symbols);
granite's and Nemotron-H's texts hold byte for byte.
PR 47 recorded all six again, this file's two functions run on its tree:
``lm_layers.next_token_loss`` names the target's logit by comparison with the
vocabulary's index (no gather whose gradient is a scatter-add), so every language
model's step changes, in that function's lines and its gradient's alone; the parent's
tree (38762b9) still gave the six hashes recorded before."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

FIXTURE = os.path.join(os.path.dirname(__file__), "..", "fixtures", "granite_step_lowering.json")


def lowered_hash(numerics: bool) -> str:
    from batchai_retinanet_horovod_coco_tpu.models import granite_hybrid
    from batchai_retinanet_horovod_coco_tpu.obs.numerics import NumericsConfig
    from batchai_retinanet_horovod_coco_tpu.train import create_train_state
    from batchai_retinanet_horovod_coco_tpu.train.optim import OptimizerConfig, make_optimizer
    from batchai_retinanet_horovod_coco_tpu.train.step import make_train_step
    from batchai_retinanet_horovod_coco_tpu.train.task import LMTask

    model = granite_hybrid.GraniteHybrid(granite_hybrid.TINY)
    tx = make_optimizer(OptimizerConfig(optimizer="adamw", schedule="constant", warmup_steps=0))[0]
    state = create_train_state(model, tx, (1, 8), jax.random.key(0), example_dtype=LMTask.example_dtype)
    seg = jnp.asarray(np.repeat([[0, 1, 2]], [20, 30, 14], axis=1), jnp.int32)
    batch = {"tokens": jnp.zeros((1, 64), jnp.int32), "segment_ids": seg}
    step = make_train_step(model, (1, 64), None, task=LMTask(), donate_state=False,
                           numerics=NumericsConfig(enabled=numerics))
    return hashlib.sha256(step.lower(state, batch).as_text().encode()).hexdigest()


@pytest.mark.parametrize("numerics", [False, True])
def test_granites_step_lowers_to_the_recorded_text(numerics):
    with open(FIXTURE) as f:
        recorded = json.load(f)
    assert lowered_hash(numerics) == recorded[f"numerics={numerics}"]


EXPERT_FIXTURE = os.path.join(os.path.dirname(__file__), "..", "fixtures", "lm_expert_step_lowering.json")


def expert_lowered_hash(preset: str, numerics: bool) -> str:
    from batchai_retinanet_horovod_coco_tpu.models.language import build_language_model
    from batchai_retinanet_horovod_coco_tpu.obs.numerics import NumericsConfig
    from batchai_retinanet_horovod_coco_tpu.train import create_train_state
    from batchai_retinanet_horovod_coco_tpu.train.optim import OptimizerConfig, make_optimizer
    from batchai_retinanet_horovod_coco_tpu.train.step import make_train_step
    from batchai_retinanet_horovod_coco_tpu.train.task import LMTask

    model = build_language_model(preset)
    tx = make_optimizer(OptimizerConfig(optimizer="adamw", schedule="constant", warmup_steps=0))[0]
    state = create_train_state(model, tx, (1, 8), jax.random.key(0), example_dtype=LMTask.example_dtype)
    seg = jnp.asarray(np.repeat([[0, 1, 2], [0, 1, 1]], [20, 30, 14], axis=1), jnp.int32)
    batch = {"tokens": jnp.zeros((2, 64), jnp.int32), "segment_ids": seg}
    step = make_train_step(model, (2, 64), None, task=LMTask(), donate_state=False,
                           numerics=NumericsConfig(enabled=numerics))
    return hashlib.sha256(step.lower(state, batch).as_text().encode()).hexdigest()


@pytest.mark.parametrize("numerics", [False, True])
@pytest.mark.parametrize("preset", ["tiny-moe", "tiny-nemotron"])
def test_the_expert_models_steps_lower_to_the_recorded_text(preset, numerics):
    with open(EXPERT_FIXTURE) as f:
        recorded = json.load(f)
    assert expert_lowered_hash(preset, numerics) == recorded[f"{preset},numerics={numerics}"]

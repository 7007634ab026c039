"""What the language-model cell's ``correct`` notices: step 1 of the program
through the shared train step, held to the float32 reference by the kind's
own report and the CELL'S OWN limits (``lm-train-pack8k.json``), at the tiny
size on the CPU.  The program as stated passes; each mutation fails."""

import dataclasses
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from batchai_retinanet_horovod_coco_tpu.data.tokens import PackedTokensConfig, packed_token_batches  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.models import granite_hybrid as gh  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.obs.numerics import NumericsConfig  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.train import create_train_state  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.train.step import make_train_step  # noqa: E402

from test_benchmark_lm_cell import TINY_MODEL as TINY, TINY_TRAFFIC  # noqa: E402

BLOCKS = TINY_TRAFFIC["reference_blocks"]


def _json(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def kind():
    spec = importlib.util.spec_from_file_location("kind_lm_train_loop",
                                                  os.path.join(REPO, "benchmark", "kinds", "lm_train_loop.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def config():
    return dict(_json("benchmark", "configs", "granite-4.0-h-micro-p1.json"), **TINY)


@pytest.fixture(scope="module")
def traffic():
    # Adam's eps is far under every gradient of the tiny model, as the
    # cell's 1e-8 is under the published model's: the tiny model's
    # gradients are 1e3 times smaller.
    return dict(_json("benchmark", "traffic", "lm-train-pack8k.json"), adam_eps=TINY_TRAFFIC["adam_eps"])


@pytest.fixture(scope="module")
def batch():
    return next(packed_token_batches(PackedTokensConfig(128, 64, 2, doc_len_median=16, doc_len_min=4, seed=5)))


def step_one(kind, config, traffic, batch, program_config=None, program_traffic=None, wrap=None, skip_update=False):
    """The report of step 1: the reference reads ``config`` and ``traffic``;
    the program is built from ``program_config`` and ``program_traffic``
    (default the same) and ``wrap``-ped."""
    model, task, tx = kind.build(program_config or config, program_traffic or traffic)
    if wrap is not None:
        model = wrap(model)
    state = create_train_state(model, tx, (1, 8), jax.random.key(11), example_dtype=task.example_dtype)
    before = state.params
    step = make_train_step(model, batch.tokens.shape, None, task=task, donate_state=False,
                           numerics=NumericsConfig(enabled=True))
    new_state, metrics = step(state, task.host_arrays(batch))
    logged = {k: float(v) for k, v in metrics.items()}
    after = jax.device_get(before if skip_update else new_state.params)
    report = kind.first_step_report(config, traffic, logged, after, before, batch, BLOCKS)
    return report, kind.first_step_problems(report, traffic["tolerances"])


def test_the_program_as_stated_is_correct(kind, config, traffic, batch):
    report, problems = step_one(kind, config, traffic, batch)
    assert problems == [], problems
    assert report["update"]["held_share"] > 0.999 and set(report["seconds"]) == {"reference", "norms", "update"}


class _Wrapped(gh.GraniteHybrid):
    """The program with its parameters changed on the way into ``apply``."""

    def __init__(self, model, change):
        super().__init__(model.config)
        self.change = change

    def apply(self, variables, tokens, segment_ids, train=False):
        return super().apply({"params": self.change(variables["params"])}, tokens, segment_ids, train=train)


def _without_d(params):
    return dict(params, mamba={k: dict(v, D=jnp.zeros_like(v["D"])) for k, v in params["mamba"].items()})


def _loss_in_bf16(logits, tokens, segment_ids):
    """``next_token_loss`` with the sum over the vocabulary done in bfloat16."""
    counted = (segment_ids[:, 1:] == segment_ids[:, :-1]).astype(jnp.float32)
    logits = logits[:, :-1].astype(jnp.bfloat16)
    picked = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    nll = (jax.nn.logsumexp(logits, axis=-1) - picked).astype(jnp.float32)
    return jnp.sum(nll * counted) / jnp.maximum(jnp.sum(counted), 1.0), jnp.sum(counted)


@pytest.fixture
def fresh_traces():
    """A mutation patched into the model has to be traced: the layers are
    ``jax.checkpoint``-ed, and their traces are cached by function."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("mutation", [
    "fp8_matmuls", "loss_reduced_in_bf16", "no_state_reset", "no_d_skip", "no_residual_multiplier",
    "no_logits_scaling", "skipped_update", "doubled_rate", "decay_on_every_leaf"])
def test_a_mutation_of_the_program_is_not_correct(kind, config, traffic, batch, mutation, monkeypatch, fresh_traces):
    kw = {}
    if mutation == "fp8_matmuls":  # the nearest precision below the one stated: the cell's control
        from benchmark.harness import lm_control

        monkeypatch.setattr(gh, "_operand", gh._operand)  # put back after the control's patch
        lm_control.lower_the_precision()
    elif mutation == "loss_reduced_in_bf16":  # a float32 reduction done in bfloat16
        monkeypatch.setattr(gh, "next_token_loss", _loss_in_bf16)
    elif mutation == "no_state_reset":  # the scan runs on across document boundaries
        chunked = gh.ssd.ssd_chunked
        monkeypatch.setattr(gh.ssd, "ssd_chunked",
                            lambda x, dt, a, b, c, seg, chunk: chunked(x, dt, a, b, c, jnp.zeros_like(seg), chunk))
    elif mutation == "no_d_skip":
        kw["wrap"] = lambda model: _Wrapped(model, _without_d)
    elif mutation == "no_residual_multiplier":  # the 0.22 left out
        kw["program_config"] = dict(config, residual_multiplier=1.0)
    elif mutation == "no_logits_scaling":  # the 1/8 left out
        kw["program_config"] = dict(config, logits_scaling=1.0)
    elif mutation == "skipped_update":
        kw["skip_update"] = True
    elif mutation == "doubled_rate":  # the optimizer at twice the rate the cell declares
        kw["program_traffic"] = dict(traffic, lr=2 * traffic["lr"])
    elif mutation == "decay_on_every_leaf":
        from batchai_retinanet_horovod_coco_tpu.train import optim

        monkeypatch.setattr(optim, "decays", lambda params: jax.tree.map(lambda p: True, params))
    report, problems = step_one(kind, config, traffic, batch, **kw)
    assert problems, (mutation, report)
    assert all(p.startswith("first step's") for p in problems)


def test_the_flop_model_counts_what_the_configuration_says(config):
    from benchmark.harness import lm_flops

    published = _json("benchmark", "configs", "granite-4.0-h-micro-p1.json")
    held = published["parameters_held"]
    pairs = lm_flops.attention_pairs([[[0] * 8192]])
    assert pairs == 8192 * 8193 / 2
    fwd = lm_flops.forward_flops_per_sequence(published, 8192, pairs)
    # 2 FLOPs per parameter in a matmul per token; the convolution, norms and biases are not matmuls
    matmul_params = (9 * (held["mamba_mixer"] - 4352 * 4 - 4352 - 192 - 4096) + held["attention_mixer"]
                     + 10 * held["mlp"] + 12544 * 2048)
    matmuls = fwd["mamba_matmuls"] + fwd["attention_matmuls"] + fwd["mlp"] + fwd["lm_head"]
    assert matmuls == 2.0 * 8192 * matmul_params
    assert fwd["ssd"] == 5.0 * 8192 * 9 * 64 * 64 * 128
    assert fwd["attention_pairs"] == 2.0 * pairs * 2 * 2048
    train = lm_flops.train_flops_per_sequence(published, 8192, pairs)
    assert train["total"] == pytest.approx(3 * fwd["total"]) and 3.8e13 < train["total"] < 4.1e13
    # two documents need fewer pairs than one
    assert lm_flops.attention_pairs([[[0] * 4096 + [1] * 4096]]) == 2 * (4096 * 4097 / 2)

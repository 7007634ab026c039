"""What the Olmo-Hybrid cell's ``correct`` notices: step 1 of the program through
the shared train step, held to the float32 reference (the recurrence token by
token) by the kind's own report and the CELL'S OWN limits
(``lm-linear-train-pack8k-fixed.json``, but for the loss's: see ``traffic``), at the
tiny size on the CPU.  The program as stated passes; each mutation fails."""

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
from batchai_retinanet_horovod_coco_tpu.models import lm_layers  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.models import olmo_hybrid as oh  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.obs.numerics import NumericsConfig  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.ops import delta_rule  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.train import create_train_state  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.train.step import make_train_step  # noqa: E402

from test_benchmark_olmo_cell import TINY_MODEL as TINY, TINY_TOLERANCES, TINY_TRAFFIC  # noqa: E402

BLOCKS = TINY_TRAFFIC["reference_blocks"]


def _json(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def kind():
    spec = importlib.util.spec_from_file_location(
        "kind_lm_linear_train_loop", os.path.join(REPO, "benchmark", "kinds", "lm_linear_train_loop.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def config():
    return dict(_json("benchmark", "configs", "olmo-hybrid-7b-p1.json"), **TINY)


@pytest.fixture(scope="module")
def traffic():
    # Adam's eps is far under every gradient of the tiny model, as the cell's 1e-8 is under the published model's.
    t = dict(_json("benchmark", "traffic", "lm-linear-train-pack8k-fixed.json"), adam_eps=TINY_TRAFFIC["adam_eps"])
    return dict(t, tolerances=dict(t["tolerances"], **TINY_TOLERANCES))


@pytest.fixture(scope="module")
def batch():
    return next(packed_token_batches(PackedTokensConfig(128, 64, 2, doc_len_median=16, doc_len_min=4, seed=5)))


def step_one(kind, config, traffic, batch, program_config=None, program_traffic=None, skip_update=False):
    """The report of step 1: the reference reads ``config`` and ``traffic``; the
    program is built from ``program_config`` and ``program_traffic`` (default the
    same)."""
    model, task, tx = kind.build(program_config or config, program_traffic or traffic)
    state = create_train_state(model, tx, (1, 8), jax.random.key(11), example_dtype=task.example_dtype)
    before = state.params
    step = make_train_step(model, batch.tokens.shape, None, task=task, donate_state=False,
                           numerics=NumericsConfig(enabled=True))
    new_state, metrics = step(state, task.host_arrays(batch))
    logged = {k: float(v) for k, v in metrics.items()}
    after = jax.device_get(before if skip_update else new_state.params)
    report = kind.first_step_report(config, traffic, logged, after, before, batch, config["delta_rule_chunk"], BLOCKS)
    return report, kind.first_step_problems(report, traffic["tolerances"])


def test_the_program_as_stated_is_correct(kind, config, traffic, batch):
    report, problems = step_one(kind, config, traffic, batch)
    assert problems == [], problems
    assert report["update"]["held_share"] > 0.99 and set(report["seconds"]) == {"reference", "norms", "update"}
    assert report["gdn/state_norm_max"]["reference_f32"] > 0


@pytest.fixture
def fresh_traces():
    """A mutation patched into the model has to be traced: the layers are
    ``jax.checkpoint``-ed, and their traces are cached by function."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _decay_after_the_write(q, k, v, log_a, b, seg, chunk):
    """``S_t = a_t (S_{t-1} (I - b k k^T) + b v k^T)``: the decay applied after the
    rank-one term instead of before, token by token."""
    first = jnp.concatenate([jnp.ones_like(seg[:, :1], bool), seg[:, 1:] != seg[:, :-1]], axis=1)

    def token(state, x):
        q_t, k_t, v_t, la_t, b_t, f_t = x
        state = jnp.where(f_t[:, None, None, None], 0.0, state)
        read = jnp.einsum("bhvk,bhk->bhv", state, k_t)
        state = state + (b_t[..., None] * (v_t - read))[..., None] * k_t[..., None, :]
        state = jnp.exp(la_t)[..., None, None] * state
        return state, jnp.einsum("bhvk,bhk->bhv", state, q_t)

    f32 = lambda a: jnp.moveaxis(a.astype(jnp.float32), 1, 0)
    state0 = jnp.zeros((q.shape[0], q.shape[2], v.shape[-1], q.shape[-1]), jnp.float32)
    state, o = jax.lax.scan(token, state0, (f32(q), f32(k), f32(v), f32(log_a), f32(b), jnp.moveaxis(first, 1, 0)))
    return jnp.moveaxis(o, 0, 1), jnp.sqrt(jnp.max(jnp.sum(state * state, axis=(-2, -1))))


MUTATIONS = ["fp8_matmuls", "fp8_state", "no_state_reset", "no_conv_reset", "b_without_its_2", "no_l2_norm_of_k",
             "no_l2_norm_of_q", "decay_after_the_write", "no_output_gate", "no_head_norm", "norm_before_the_sublayer",
             "no_qk_norm", "skipped_update", "doubled_rate", "decay_on_every_leaf"]


@pytest.mark.parametrize("mutation", MUTATIONS)
def test_a_mutation_of_the_program_is_not_correct(kind, config, traffic, batch, mutation, monkeypatch, fresh_traces):
    kw = {}
    if mutation in ("fp8_matmuls", "fp8_state"):  # the nearest precision below the one stated: the cell's controls
        from batchai_retinanet_horovod_coco_tpu.ops.pallas import delta_rule as kernels
        from benchmark.harness import olmo_control

        for module, name in ((oh, "_operand"), (oh, "_state_operand"), (kernels, "_state_operand")):
            monkeypatch.setattr(module, name, getattr(module, name))  # put back after the control's patch
        olmo_control.lower_the_precision("operands" if mutation == "fp8_matmuls" else "state")
    elif mutation == "no_state_reset":  # the delta rule runs on across document boundaries
        rule = delta_rule.gated_delta_rule
        monkeypatch.setattr(delta_rule, "gated_delta_rule",
                            lambda q, k, v, la, b, seg, chunk: rule(q, k, v, la, b, jnp.zeros_like(seg), chunk))
    elif mutation == "no_conv_reset":  # the convolution reaches into the previous document
        conv = lm_layers.document_conv_silu
        monkeypatch.setattr(lm_layers, "document_conv_silu", lambda x, w, b, seg: conv(x, w, b, jnp.zeros_like(seg)))
    elif mutation == "b_without_its_2":  # linear_allow_neg_eigval ignored
        kw["program_config"] = dict(config, linear_allow_neg_eigval=False)
    elif mutation in ("no_l2_norm_of_k", "no_l2_norm_of_q"):
        normalised, which = oh._l2_normalised, mutation[-1]
        # q is the call that is given its scale; k the one that is not
        monkeypatch.setattr(oh, "_l2_normalised", lambda x, scale=1.0: (
            x.astype(jnp.float32) * scale if (scale != 1.0) == (which == "q") else normalised(x, scale)))
    elif mutation == "decay_after_the_write":
        monkeypatch.setattr(delta_rule, "gated_delta_rule", _decay_after_the_write)
    elif mutation == "no_output_gate":
        monkeypatch.setattr(jax.nn, "silu", jax.nn.silu)
        silu, seen = jax.nn.silu, []

        def silu_but_the_gates(x):  # the gate is the only float32 (batch, T, heads, value size) it is given
            if x.ndim == 4 and x.dtype == jnp.float32:
                seen.append(x.shape)
                return jnp.ones_like(x)
            return silu(x)

        monkeypatch.setattr(oh.jax.nn, "silu", silu_but_the_gates)
    elif mutation == "no_head_norm":  # the per-head RMSNorm of the delta rule's output left out
        norm = oh._rms_norm
        monkeypatch.setattr(oh, "_rms_norm", lambda x, w, eps: x * w if x.ndim == 4 else norm(x, w, eps))
    elif mutation == "norm_before_the_sublayer":  # the usual placement instead of the family's
        def pre_norm_layer(cfg, layer_kind, mixer_params, mlp_params, norms, x, segment_ids):
            u = oh._rms_norm(x, norms["mixer"], cfg.rms_norm_eps)
            if layer_kind == oh.LINEAR:
                mixed, counters = oh._gdn_mixer(cfg, mixer_params, u, segment_ids)
            else:
                mixed, counters = oh._attention_mixer(cfg, mixer_params, u, segment_ids), None
            h = x + mixed.astype(x.dtype)
            u = oh._rms_norm(h, norms["mlp"], cfg.rms_norm_eps)
            return h + lm_layers.gated_mlp(oh._cast(cfg), mlp_params, u).astype(x.dtype), counters

        monkeypatch.setattr(oh, "_layer", pre_norm_layer)
    elif mutation == "no_qk_norm":  # full attention without its two norms over the whole projection
        mixer = oh._attention_mixer

        def without_its_norms(cfg, p, u, segment_ids):
            with pytest.MonkeyPatch.context() as inner:
                inner.setattr(oh, "_rms_norm", lambda x, w, eps: x)
                return mixer(cfg, p, u, segment_ids)

        monkeypatch.setattr(oh, "_attention_mixer", without_its_norms)
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
    if mutation == "no_output_gate":
        assert seen and all(s[-1] == config["linear_value_head_dim"] for s in seen)

"""What the Trinity cell's ``correct`` notices: step 1 of the program through the
shared train step, held to the float32 reference by the kind's own report and
the CELL'S OWN limits (``lm-swa-moe-train-doc16k-b1.json``, but for what 128
tokens force: ``TINY_TOLERANCES``), at the tiny size on the CPU.  The program as
stated passes; each mutation fails."""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from batchai_retinanet_horovod_coco_tpu.data.tokens import PackedTokensConfig, packed_token_batches  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.models import afmoe as af  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.models import lm_layers  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.obs.numerics import NumericsConfig  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.ops import attention, moe, rope  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.train import create_train_state  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.train.step import make_train_step  # noqa: E402

from test_benchmark_trinity_cell import CONFIG, MIX, TINY_MODEL as TINY, TINY_TOLERANCES, TINY_TRAFFIC  # noqa: E402

BLOCKS = TINY_TRAFFIC["reference_blocks"]
# a selection bias that is not zero (a row an expert layer), so that a bias that reaches the weights shows
BIAS = [[0.06, -0.04, 0.0, 0.05, -0.06, 0.02, 0.04, -0.02], [-0.05, 0.06, 0.03, -0.02, 0.0, 0.04, -0.06, 0.02],
        [0.01, 0.02, -0.03, 0.04, -0.05, 0.06, -0.01, 0.0]]


def _json(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def kind():
    spec = importlib.util.spec_from_file_location(
        "kind_lm_swa_moe_train_loop", os.path.join(REPO, "benchmark", "kinds", "lm_swa_moe_train_loop.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def config():
    """The tiny model computing in float32: a mutation is a fault of the
    mathematics, and is shown against the cell's limits without the noise that
    128 tokens in bfloat16 put on the router's gradient.  The control, and
    ``test_the_program_as_stated...`` in bfloat16, are the precision's own tests."""
    return dict(_json("benchmark", "configs", CONFIG + ".json"), **TINY, compute_dtype="float32", expert_bias=BIAS)


@pytest.fixture(scope="module")
def traffic():
    # Adam's eps far under every gradient of the tiny model, as the cell's 1e-8 is under the published model's
    t = dict(_json("benchmark", "traffic", MIX + ".json"), adam_eps=TINY_TRAFFIC["adam_eps"])
    return dict(t, tolerances=dict(t["tolerances"], **TINY_TOLERANCES))


@pytest.fixture(scope="module")
def batch():
    """Two sequences of 64 tokens, each one document (the cell's layout): four times the window of 16 keys."""
    return next(packed_token_batches(PackedTokensConfig(128, 64, 2, doc_len_median=16, doc_len_min=64, seed=5)))


def step_one(kind, config, traffic, batch, program_config=None, program_traffic=None, skip_update=False):
    """The report of step 1: the reference reads ``config`` and ``traffic``; the
    program is built from ``program_config`` and ``program_traffic`` (default
    the same)."""
    from benchmark.kinds import lm_moe_train_loop

    model, task, tx = lm_moe_train_loop.build(program_config or config, program_traffic or traffic)
    state = create_train_state(model, tx, (1, 8), jax.random.key(11), example_dtype=task.example_dtype)
    before = state.params
    step = make_train_step(model, batch.tokens.shape, None, task=task, donate_state=False,
                           numerics=NumericsConfig(enabled=True))
    new_state, metrics = step(state, task.host_arrays(batch))
    logged = {k: float(v) for k, v in metrics.items()}
    after = jax.device_get(before if skip_update else new_state.params)
    picks = np.asarray(model.picks(before, batch.tokens, batch.segment_ids))
    report = kind.first_step_report(config, traffic, logged, after, before, batch, picks, BLOCKS)
    report["run_shares"] = kind.run_shares_report(config, logged, batch.segment_ids,
                                                  kind.kernel_blocks(model, batch.segment_ids.shape))
    return report, kind.first_step_problems(report, traffic["tolerances"])


def test_the_program_as_stated_is_correct(kind, config, traffic, batch):
    assert not batch.segment_ids.any()
    report, problems = step_one(kind, config, traffic, batch)
    assert problems == [], problems
    assert report["update"]["held_share"] > 0.99 and set(report["seconds"]) == {"reference", "norms", "update"}
    assert report["picks_differ"] == {"by_layer": [0.0, 0.0, 0.0], "max": 0.0}
    assert {v["rel"] for v in report["rows"].values()} == {0.0} and report["rows"]["held"]["program"] > 0
    assert set(report) == {"loss", "grad_norm", *(f"gnorm/{g}" for g in kind.GROUPS), "picks_differ", "rows",
                           "run_shares", "update", "seconds"}
    assert report["run_shares"] == {"kernel": False, "logged": []}


def test_the_program_as_stated_in_bfloat16_is_correct(kind, config, traffic, batch):
    report, problems = step_one(kind, config, traffic, batch, program_config=dict(config, compute_dtype="bfloat16"))
    assert problems == [], problems
    assert 0 <= report["picks_differ"]["max"] <= traffic["tolerances"]["picks_differ_max"]


def test_a_logged_share_of_a_window_that_is_masked_and_not_skipped_is_not_correct(kind, config, traffic, batch):
    """On the chip the report carries the two run shares: a window kernel that
    runs every causal pair logs 1.0 where the layout's count says 7 of 10."""
    report, _ = step_one(kind, config, traffic, batch)
    blocks = {"full": (16, 16), "window": (16, 16)}
    causal, visible = kind.block_pairs_with_a_visible_pair(batch.segment_ids, 16, 16, config["sliding_window"])
    assert (causal, visible) == (2 * 10, 2 * 7)
    good = {"attn/block_pairs_run_share": 1.0, "attn/window_block_pairs_run_share": float(np.float32(0.7))}
    report["run_shares"] = kind.run_shares_report(config, good, batch.segment_ids, blocks)
    assert kind.first_step_problems(report, traffic["tolerances"]) == []
    for bad in (dict(good, **{"attn/window_block_pairs_run_share": 1.0}),
                dict(good, **{"attn/block_pairs_run_share": 0.7}), {"attn/block_pairs_run_share": 1.0}):
        report["run_shares"] = kind.run_shares_report(config, bad, batch.segment_ids, blocks)
        problems = kind.first_step_problems(report, traffic["tolerances"])
        assert problems and all("block pairs that hold a visible pair" in p for p in problems), problems
    report["run_shares"] = {"kernel": False, "logged": ["attn/block_pairs_run_share"]}
    assert any("no kernel and yet logged" in p for p in kind.first_step_problems(report, traffic["tolerances"]))


@pytest.fixture
def fresh_traces():
    """A mutation patched into the model has to be traced: the layers are
    ``jax.checkpoint``-ed, and their traces are cached by function."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _absent_experts_on_a_held_experts_weights(dispatch):
    """``moe.dispatch`` that sends the pairs of every ABSENT expert to the first
    held expert instead of leaving them out."""

    def everything_here(picks, held, experts):
        first = jnp.asarray(held[0], picks.dtype)
        is_held = jnp.isin(picks, jnp.asarray(held, picks.dtype))
        return dispatch(jnp.where(is_held, picks, first), held, experts)

    return everything_here


def _rerouted(change):
    """``moe.route_sigmoid`` with its result changed by ``change(routing, bias, scale)``."""
    route = moe.route_sigmoid

    def mutated(u, w_gate, k, bias, scale):
        return change(route(u, w_gate, k, bias, scale), bias, scale)

    return mutated


def _without_output_norms(cfg, kind_, index, attn_p, mlp_p, norms, x, segment_ids, positions):
    """``af._layer`` with the two norms on the sublayers' OUTPUT left out."""
    eps = cfg.rms_norm_eps
    a = af._attention(cfg, kind_, attn_p, lm_layers.rms_norm(x, norms["attention_in"], eps), segment_ids, positions)
    h = x + a.astype(x.dtype)
    u = lm_layers.rms_norm(h, norms["mlp_in"], eps)
    if index is None:
        return h + lm_layers.gated_mlp(af._cast(cfg), mlp_p, u).astype(x.dtype), None
    f, routed = af._moe(cfg, index, *mlp_p, u)
    return h + f.astype(x.dtype), routed


MUTATIONS = ["fp8_matmuls", "window_one_key_shorter", "window_one_key_longer", "window_ignored",
             "window_applied_in_the_full_layer", "rotation_in_the_full_layer", "no_rotation_in_a_sliding_layer",
             "no_attention_gate", "no_q_k_norm", "no_output_norms", "no_sqrt_d_on_the_embedding",
             "route_scale_left_out", "weights_not_normalised", "bias_added_to_the_weights",
             "absent_experts_rows_on_a_held_experts_weights", "shared_expert_dropped", "skipped_update", "doubled_rate"]


@pytest.mark.parametrize("mutation", MUTATIONS)
def test_a_mutation_of_the_program_is_not_correct(kind, config, traffic, batch, mutation, monkeypatch, fresh_traces):
    kw = {}
    if mutation == "fp8_matmuls":  # the nearest precision below the one stated: the cell's control
        from benchmark.harness import afmoe_control

        monkeypatch.setattr(af, "_operand", af._operand)  # put back after the control's patch
        afmoe_control.lower_the_precision()
        kw["program_config"] = dict(config, compute_dtype="bfloat16")
    elif mutation == "window_one_key_shorter":  # the query itself not counted among the window's keys
        kw["program_config"] = dict(config, sliding_window=config["sliding_window"] - 1)
    elif mutation == "window_one_key_longer":
        kw["program_config"] = dict(config, sliding_window=config["sliding_window"] + 1)
    elif mutation == "window_ignored":  # every layer sees its whole causal past
        packed = attention.packed_causal_attention
        monkeypatch.setattr(attention, "packed_causal_attention",
                            lambda q, k, v, seg, scale, block, window=None: packed(q, k, v, seg, scale, block))
    elif mutation == "window_applied_in_the_full_layer":
        packed = attention.packed_causal_attention
        monkeypatch.setattr(attention, "packed_causal_attention", lambda q, k, v, seg, scale, block, window=None: packed(
            q, k, v, seg, scale, block, window=config["sliding_window"]))
    elif mutation == "rotation_in_the_full_layer":  # the full layer rotated as a sliding one, its mask left alone
        attend, packed = af._attention, attention.packed_causal_attention

        def rotated(cfg, kind_, p, u, seg, positions):
            if kind_ != af.FULL:
                return attend(cfg, kind_, p, u, seg, positions)
            with pytest.MonkeyPatch.context() as inner:
                inner.setattr(attention, "packed_causal_attention",
                              lambda q, k, v, s, scale, block, window=None: packed(q, k, v, s, scale, block))
                return attend(cfg, af.SLIDING, p, u, seg, positions)

        monkeypatch.setattr(af, "_attention", rotated)
    elif mutation == "no_rotation_in_a_sliding_layer":
        monkeypatch.setattr(rope, "apply_rotary_halves", lambda x, angles: x)
    elif mutation == "no_attention_gate":
        sigmoid, seen = jax.nn.sigmoid, []

        def sigmoid_but_the_gates(x):  # the gate's argument is the only (batch, T, heads x size) it is given
            if x.ndim == 3:
                seen.append(x.shape)
                return jnp.ones_like(x)
            return sigmoid(x)

        monkeypatch.setattr(jax.nn, "sigmoid", sigmoid_but_the_gates)
    elif mutation == "no_q_k_norm":  # the per-head norms are the only ones of four dimensions
        norm = lm_layers.rms_norm
        monkeypatch.setattr(lm_layers, "rms_norm", lambda x, w, eps: x if x.ndim == 4 else norm(x, w, eps))
    elif mutation == "no_output_norms":
        monkeypatch.setattr(af, "_layer", _without_output_norms)
    elif mutation == "no_sqrt_d_on_the_embedding":
        kw["program_config"] = dict(config, mup_enabled=False)
    elif mutation == "route_scale_left_out":
        kw["program_config"] = dict(config, route_scale=1.0)
    elif mutation == "weights_not_normalised":  # route_norm false: the picked sigmoids as they are, x the scale
        monkeypatch.setattr(moe, "route_sigmoid", _rerouted(lambda r, bias, scale: r._replace(
            weights=scale * jnp.take_along_axis(r.scores, r.picks, axis=-1))))
    elif mutation == "bias_added_to_the_weights":  # the weights from score + bias, as the picks are
        def biased(r, bias, scale):
            picked = jnp.take_along_axis(r.scores + bias, r.picks, axis=-1)
            return r._replace(weights=scale * picked / jnp.sum(picked, axis=-1, keepdims=True))

        monkeypatch.setattr(moe, "route_sigmoid", _rerouted(biased))
    elif mutation == "absent_experts_rows_on_a_held_experts_weights":
        monkeypatch.setattr(moe, "dispatch", _absent_experts_on_a_held_experts_weights(moe.dispatch))
    elif mutation == "shared_expert_dropped":  # the shared expert's product is the narrow one
        gated, narrow = lm_layers.gated_mlp, 2 * config["moe_intermediate_size"]
        monkeypatch.setattr(lm_layers, "gated_mlp", lambda cast, p, u: (
            jnp.zeros_like(u) if p["gate_up"].shape[-1] == narrow else gated(cast, p, u)))
    elif mutation == "skipped_update":
        kw["skip_update"] = True
    elif mutation == "doubled_rate":  # the optimizer at twice the rate the cell declares
        kw["program_traffic"] = dict(traffic, lr=2 * traffic["lr"])
    report, problems = step_one(kind, config, traffic, batch, **kw)
    assert problems, (mutation, {k: v.get("rel") for k, v in report.items() if isinstance(v, dict) and "rel" in v})
    assert all(p.startswith("first step's") for p in problems)
    if mutation == "no_attention_gate":
        assert seen and all(s[-1] == config["num_attention_heads"] * config["head_dim"] for s in seen)


def test_the_rooflines_read_the_kernels_of_the_very_steps_they_time():
    """A made-up device plane: eight runs of the step program after the profiler
    started at step 16, the steady stretch runs 3-7 (steps 19-23); in each,
    grouped products of 3 ms; the counter was fetched at steps 12, 16, 20, 24."""
    import types

    from benchmark.harness import afmoe_flops, afmoe_trace
    from benchmark.harness import trace_reduce as tr

    ms = 1_000_000
    modules = [tr.Event("jit_train_step", 20 * i * ms, (20 * i + 19) * ms) for i in range(8)]
    names = ["gmm.3", "tgmm", "fusion.7", "gmm"]
    ops = [tr.Event(name, m.start + k * ms, m.start + (k + 1) * ms) for m in modules for k, name in enumerate(names)]
    trace = tr.Trace([tr.DevicePlane("tpu0", ops, modules)], [])
    cfg = _json("benchmark", "configs", CONFIG + ".json")
    traffic = _json("benchmark", "traffic", MIX + ".json")
    facts = {"trace_from": 16, "moe_rows_logged": [[12, 900.0], [16, 1000.0], [20, 1400.0], [24, 2200.0]],
             "attention_window_pairs_per_step": 31_458_304.0, "attention_full_pairs_per_step": 134_225_920.0}
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    slices = {"ms": {"attention": 400.0}, "by_scope": {"attention": {"window_core": {"fwd": 20.0, "bwd": 60.0},
                                                                     "full_core": {"fwd": 21.0, "bwd": 63.0}}}}
    run = types.SimpleNamespace(config=cfg, traffic=traffic)
    ctx = types.SimpleNamespace(trace=trace, window=(modules[2].start, modules[6].end), facts=facts, peaks=peaks, run=run,
                                module_pattern=lambda: "train_step", _program_slices=slices)
    kernel_ms, rows = afmoe_trace.gmm_ms_and_rows(ctx)
    assert kernel_ms == pytest.approx(3.0)  # gmm.3, tgmm and gmm; not the fusion
    assert rows == pytest.approx((1300 + 1400 + 1600 + 1800 + 2000) / 5)  # steps 19..23 by interpolation
    assert afmoe_trace.slice_ms(ctx, "attention") == 400.0
    assert afmoe_trace.slice_ms(ctx, "attention", ("window_core",)) == 80.0
    assert afmoe_trace.slice_ms(ctx, "attention", ("full_core",)) == 84.0 and afmoe_trace.slice_ms(ctx, "moe") == 0.0
    # 100% is the roofline: the four window layers' visible pairs take 31.4 ms at the peak, the full layer's 33.5
    window = afmoe_trace.attention_roofline_pct(ctx, "window_core", "attention_window_pairs_per_step", afmoe_flops.SLIDING)
    full = afmoe_trace.attention_roofline_pct(ctx, "full_core", "attention_full_pairs_per_step", afmoe_flops.FULL)
    assert window == pytest.approx(100 * 6 * 2.0 * 31_458_304 * 4 * 32 * 128 / 197e12 / 80e-3) and 39 < window < 40
    assert full == pytest.approx(100 * 6 * 2.0 * 134_225_920 * 32 * 128 / 197e12 / 84e-3) and 39 < full < 41
    # a program that is not this model's step (no scopes of its), or a run that was not traced: nothing, no error
    other = types.SimpleNamespace(trace=trace, window=ctx.window, facts=facts, peaks=peaks, run=run,
                                  module_pattern=ctx.module_pattern, _program_slices=None)
    assert afmoe_trace.gmm_ms_and_rows(other) is None and afmoe_trace.slice_ms(other, "attention") is None
    assert afmoe_trace.attention_roofline_pct(other, "window_core", "attention_window_pairs_per_step", afmoe_flops.SLIDING) is None
    untraced = types.SimpleNamespace(trace=None, window=None, facts=facts, peaks=peaks, run=run, _program_slices=slices)
    assert afmoe_trace.gmm_ms_and_rows(untraced) is None
    no_peaks = types.SimpleNamespace(trace=trace, window=ctx.window, facts=facts, peaks=None, run=run, _program_slices=slices)
    assert afmoe_trace.attention_roofline_pct(no_peaks, "full_core", "attention_full_pairs_per_step", afmoe_flops.FULL) is None


def test_every_reader_of_the_cell_returns_nothing_against_another_programs_step():
    """The ten readers against a context whose step is not this model's (the
    parent's programs): nothing, and no error."""
    import types

    for name in ("trinity_step.attention_ms", "trinity_step.window_core_ms", "trinity_step.full_core_ms",
                 "trinity_step.dense_mlp_ms", "trinity_step.router_ms", "trinity_step.experts_ms",
                 "trinity_step.shared_ms", "trinity_gmm_roofline", "trinity_window_attn_roofline",
                 "trinity_full_attn_roofline"):
        spec = importlib.util.spec_from_file_location(
            "reader_" + name.replace(".", "_"), os.path.join(REPO, "benchmark", "layer_metrics", name + ".py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        ctx = types.SimpleNamespace(trace=None, window=None, facts={}, peaks={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
                                    run=None, _program_slices=None)
        assert module.read(ctx) is None, name

"""What the Nemotron-H cell's ``correct`` notices: step 1 of the program
through the shared train step, held to the float32 reference by the kind's own
report and the CELL'S OWN limits (``lm-hybrid-moe-train-pack8k-b2.json``), at
the tiny size on the CPU.  The program as stated passes; each mutation fails,
by the limit named beside it."""

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
from batchai_retinanet_horovod_coco_tpu.models import lm_layers  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.models import nemotron_h as nh  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.obs.numerics import NumericsConfig  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.ops import moe  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.train import create_train_state  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.train.step import make_train_step  # noqa: E402

from test_benchmark_nemotron_cell import CONFIG, MIX, TINY_MODEL as TINY, TINY_TRAFFIC  # noqa: E402

BLOCKS = TINY_TRAFFIC["reference_blocks"]
# a selection bias that is not zero (a row an expert layer), so that a bias that reaches the weights shows
BIAS = [[0.06, -0.04, 0.0, 0.05, -0.06, 0.02, 0.04, -0.02], [-0.05, 0.06, 0.03, -0.02, 0.0, 0.04, -0.06, 0.02]]


def _json(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def kind():
    spec = importlib.util.spec_from_file_location(
        "kind_lm_hybrid_moe_train_loop", os.path.join(REPO, "benchmark", "kinds", "lm_hybrid_moe_train_loop.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def config():
    """The tiny model computing in float32: a mutation is a fault of the
    mathematics, and is shown against the cell's limits without the noise
    that 128 tokens in bfloat16 put on the router's gradient.  The control,
    and ``test_the_program_as_stated...`` in bfloat16, are the precision's
    own tests."""
    return dict(_json("benchmark", "configs", CONFIG + ".json"), **TINY, compute_dtype="float32", router_bias=BIAS)


@pytest.fixture(scope="module")
def traffic():
    # Adam's eps far under every gradient of the tiny model, as the cell's 1e-8 is under the published model's
    return dict(_json("benchmark", "traffic", MIX + ".json"), adam_eps=TINY_TRAFFIC["adam_eps"])


@pytest.fixture(scope="module")
def batch():
    return next(packed_token_batches(PackedTokensConfig(128, 64, 2, doc_len_median=16, doc_len_min=4, seed=5)))


def _sharpened(params):
    """At d = 64 the scores of N(0, 0.02^2) projections are 0.04: every
    softmax is flat, and a mixer's X, B and C of 0.01 with dt of 0.01 leave
    its scan a hundredth of its ``D X`` skip, so nothing of positions, groups
    or the gate norm shows.  Queries and keys x 8 give scores of order 1; the
    convolution's taps x 30 and dt near 1 give a scan as large as the skip,
    as the published widths give them."""
    attention = {name: dict(layer, q=8.0 * layer["q"], k=8.0 * layer["k"]) for name, layer in params["attention"].items()}
    mamba = {name: dict(layer, conv_w=30.0 * layer["conv_w"], dt_bias=layer["dt_bias"] + 4.0, A_log=layer["A_log"] - 2.0)
             for name, layer in params["mamba"].items()}
    return dict(params, attention=attention, mamba=mamba)


def step_one(kind, config, traffic, batch, program_config=None, program_traffic=None, skip_update=False):
    """The report of step 1: the reference reads ``config`` and ``traffic``;
    the program is built from ``program_config`` and ``program_traffic``
    (default the same)."""
    from benchmark.kinds import lm_moe_train_loop

    model, task, tx = lm_moe_train_loop.build(program_config or config, program_traffic or traffic)
    state = create_train_state(model, tx, (1, 8), jax.random.key(11), example_dtype=task.example_dtype)
    state = state.replace(params=_sharpened(state.params))
    before = state.params
    step = make_train_step(model, batch.tokens.shape, None, task=task, donate_state=False,
                           numerics=NumericsConfig(enabled=True))
    new_state, metrics = step(state, task.host_arrays(batch))
    logged = {k: float(v) for k, v in metrics.items()}
    after = jax.device_get(before if skip_update else new_state.params)
    picks = np.asarray(model.picks(before, batch.tokens, batch.segment_ids))
    report = kind.first_step_report(config, traffic, logged, after, before, batch, picks, BLOCKS)
    return report, kind.first_step_problems(report, traffic["tolerances"])


def test_the_program_as_stated_is_correct(kind, config, traffic, batch):
    report, problems = step_one(kind, config, traffic, batch)
    assert problems == [], problems
    assert report["update"]["held_share"] > 0.99 and set(report["seconds"]) == {"reference", "norms", "update"}
    assert report["picks_differ"] == {"by_layer": [0.0, 0.0], "max": 0.0}
    assert {v["rel"] for v in report["rows"].values()} == {0.0} and report["rows"]["held"]["program"] > 0
    assert set(report) == {"loss", "grad_norm", *(f"gnorm/{g}" for g in kind.GROUPS), "picks_differ", "rows", "update",
                           "seconds"}


def test_the_program_as_stated_in_bfloat16_is_correct_but_for_what_128_tokens_force(kind, config, traffic, batch):
    """bfloat16 inputs make some token pick another expert than the float32
    reference; of 128 tokens one is 0.8% of a layer's, and moves the norm of
    the router's and the routed experts' gradients, the emptiest expert's
    count and the signs of their elements as it does not among the 16 384 of
    the published sizes.  Every other limit of the cell holds at the tiny
    size too."""
    report, problems = step_one(kind, config, traffic, batch, program_config=dict(config, compute_dtype="bfloat16"))
    forced = ("gnorm/router", "gnorm/experts", "rows routed here", "update: 0.9")  # the last: sign agreement
    assert all(any(word in p for word in forced) for p in problems), problems
    assert 0 <= report["picks_differ"]["max"] <= traffic["tolerances"]["picks_differ_max"]
    assert min(v for g, v in report["update"]["sign_agreement"].items() if g not in ("router", "experts")) > 0.97


@pytest.fixture
def fresh_traces():
    """A mutation patched into the model has to be traced: the layers are
    ``jax.checkpoint``-ed, and their traces are cached by function."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _absent_experts_on_a_held_experts_weights(dispatch):
    """``moe.dispatch`` that sends the pairs of every ABSENT expert to the
    first held expert instead of leaving them out."""

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


# mutation -> a word of the limit that must name it
MUTATIONS = {
    "fp8_matmuls": "",  # the cell's control: whichever limit sees it
    "relu_for_relu_squared": "gnorm/shared",
    "weights_not_normalised": "gnorm/experts",
    "scale_of_2_5_left_out": "gnorm/experts",
    "bias_added_to_the_weights": "gnorm/experts",
    "gate_norm_over_the_whole_width": "gnorm/mamba",
    "a_head_reads_the_wrong_group": "update: 0.",  # a permutation moves no norm: the update's signs see it (0.85)
    "rotary_applied": "gnorm/attention",
    "shared_expert_dropped": "gnorm/shared",
    "absent_experts_rows_on_a_held_experts_weights": "gnorm/experts",
    "router_in_bfloat16": None,  # NO limit of the cell sees it at this size: see its branch
    "state_carried_into_the_next_document": "gnorm/",  # every group downstream of the mixers, by 1-5%
    "skipped_update": "update",
    "doubled_rate": "update",
}


@pytest.mark.parametrize("mutation", MUTATIONS)
def test_a_mutation_of_the_program_is_not_correct(kind, config, traffic, batch, mutation, monkeypatch, fresh_traces):
    kw = {}
    if mutation == "fp8_matmuls":  # the nearest precision below the one stated: the cell's control
        from benchmark.harness import nemotron_control

        monkeypatch.setattr(nh, "_operand", nh._operand)  # put back after the control's patch
        nemotron_control.lower_the_precision()
    elif mutation == "relu_for_relu_squared":  # routed and shared alike
        monkeypatch.setattr(moe, "_relu2", jax.nn.relu)
        monkeypatch.setattr(lm_layers, "relu2_mlp", lambda cast, p, u: lm_layers.matmul(
            cast, jax.nn.relu(lm_layers.matmul(cast, u, p["up"])), p["down"]))
    elif mutation == "weights_not_normalised":  # norm_topk_prob false: the picked sigmoids as they are, x 2.5
        monkeypatch.setattr(moe, "route_sigmoid", _rerouted(lambda r, bias, scale: r._replace(
            weights=scale * jnp.take_along_axis(r.scores, r.picks, axis=-1))))
    elif mutation == "scale_of_2_5_left_out":
        monkeypatch.setattr(moe, "route_sigmoid", _rerouted(lambda r, bias, scale: r._replace(weights=r.weights / scale)))
    elif mutation == "bias_added_to_the_weights":  # the weights from score + bias, as the picks are
        def biased(r, bias, scale):
            picked = jnp.take_along_axis(r.scores + bias, r.picks, axis=-1)
            return r._replace(weights=scale * picked / jnp.sum(picked, axis=-1, keepdims=True))

        monkeypatch.setattr(moe, "route_sigmoid", _rerouted(biased))
    elif mutation == "gate_norm_over_the_whole_width":  # Granite's: all inner channels normed together
        monkeypatch.setattr(nh, "_group_norm", lambda c, y, w: lm_layers.rms_norm(y, w, c.layer_norm_epsilon))
    elif mutation == "a_head_reads_the_wrong_group":  # the groups of B and C in the other order
        scan = nh.ssd.ssd_chunked
        monkeypatch.setattr(nh.ssd, "ssd_chunked", lambda x, dt, a, b, c, *rest: scan(
            x, dt, a, b[:, :, ::-1], c[:, :, ::-1], *rest))
    elif mutation == "rotary_applied":  # the other reading of the row: the program rotates, the reference does not
        kw["program_config"] = dict(config, attention_rotary=True)
    elif mutation == "shared_expert_dropped":
        monkeypatch.setattr(lm_layers, "relu2_mlp", lambda cast, p, u: jnp.zeros_like(u))
    elif mutation == "absent_experts_rows_on_a_held_experts_weights":
        monkeypatch.setattr(moe, "dispatch", _absent_experts_on_a_held_experts_weights(moe.dispatch))
    elif mutation == "router_in_bfloat16":
        def route_bf16(u, w_gate, k, bias, scale):
            logits = jnp.dot(u.astype(jnp.bfloat16), w_gate.astype(jnp.bfloat16)).astype(jnp.float32)
            scores = jax.nn.sigmoid(logits.astype(jnp.bfloat16)).astype(jnp.float32)
            _, picks = jax.lax.top_k(scores + bias, k)
            picked = jnp.take_along_axis(scores, picks, axis=-1)
            return moe.Routing(scores, picks.astype(jnp.int32), scale * picked / jnp.sum(picked, -1, keepdims=True),
                               jnp.zeros((w_gate.shape[-1],), jnp.int32))

        monkeypatch.setattr(moe, "route_sigmoid", route_bf16)  # nothing else is rounded: the router alone
        # Other experts are picked (where the float32 program picks the reference's, to the token) and every
        # score carries 8 bits.  ``picks_differ_max`` cannot hold it: the limit has to let through what bfloat16
        # INPUTS of a float32 router do at the published sizes, and 8 bits of a weight move a norm by 0.3%.
        # What notices is that picks differ AT ALL in a float32 program (PERF.md section 7, as dsv2's cell).
        report, problems = step_one(kind, config, traffic, batch)
        assert report["picks_differ"]["max"] > 0.01 and min(report["picks_differ"]["by_layer"]) > 0, report["picks_differ"]
        assert all("gnorm/" in p or "rows routed here" in p for p in problems), problems
        return
    elif mutation == "state_carried_into_the_next_document":  # the scan and the convolution see one long document
        scan, conv = nh.ssd.ssd_chunked, lm_layers.document_conv_silu
        monkeypatch.setattr(nh.ssd, "ssd_chunked", lambda x, dt, a, b, c, seg, chunk: scan(
            x, dt, a, b, c, jnp.zeros_like(seg), chunk))
        monkeypatch.setattr(lm_layers, "document_conv_silu", lambda x, w, b, seg: conv(x, w, b, jnp.zeros_like(seg)))
    elif mutation == "skipped_update":
        kw["skip_update"] = True
    elif mutation == "doubled_rate":  # the optimizer at twice the rate the cell declares
        kw["program_traffic"] = dict(traffic, lr=2 * traffic["lr"])
    report, problems = step_one(kind, config, traffic, batch, **kw)
    assert problems, (mutation, report)
    assert all(p.startswith("first step's") for p in problems)
    assert any(MUTATIONS[mutation] in p for p in problems), (mutation, problems)


def test_the_two_rooflines_read_the_kernels_of_the_very_steps_they_time():
    """A made-up device plane: eight runs of the step program after the
    profiler started at step 16, the steady stretch runs 3-7 (steps 19-23);
    in each, grouped products of 3 ms and scan kernels of 4 ms; the counter
    was fetched at steps 12, 16, 20, 24."""
    import types

    from benchmark.harness import nemotron_flops, nemotron_trace
    from benchmark.harness import trace_reduce as tr

    ms = 1_000_000
    modules = [tr.Event("jit_train_step", 20 * i * ms, (20 * i + 19) * ms) for i in range(8)]
    names = ["gmm.3", "tgmm", "fusion.7", "gmm", "ssd_scan_fwd", "ssd_scan_fwd.1", "ssd_scan_bwd", "ssd_scan_bwd.2"]
    ops = [tr.Event(name, m.start + k * ms, m.start + (k + 1) * ms) for m in modules for k, name in enumerate(names)]
    trace = tr.Trace([tr.DevicePlane("tpu0", ops, modules)], [])
    facts = {"trace_from": 16, "moe_rows_logged": [[12, 900.0], [16, 1000.0], [20, 1400.0], [24, 2200.0]]}
    ctx = types.SimpleNamespace(trace=trace, window=(modules[2].start, modules[6].end), facts=facts,
                                module_pattern=lambda: "train_step", _program_slices={"ms": {}, "by_scope": {}})
    kernel_ms, rows = nemotron_trace.gmm_ms_and_rows(ctx)
    assert kernel_ms == pytest.approx(3.0)  # gmm.3, tgmm and gmm; not the fusion
    assert rows == pytest.approx((1300 + 1400 + 1600 + 1800 + 2000) / 5)  # steps 19..23 by interpolation
    assert nemotron_trace.ssd_ms(ctx) == pytest.approx(4.0)
    # a program that is not this model's step (no scopes of its), or a run that was not traced: nothing, no error
    other = types.SimpleNamespace(trace=trace, window=ctx.window, facts=facts, module_pattern=ctx.module_pattern,
                                  _program_slices=None)
    assert nemotron_trace.gmm_ms_and_rows(other) is None and nemotron_trace.ssd_ms(other) is None
    untraced = types.SimpleNamespace(trace=None, window=None, facts=facts, _program_slices=ctx._program_slices)
    assert nemotron_trace.gmm_ms_and_rows(untraced) is None and nemotron_trace.ssd_ms(untraced) is None
    assert nemotron_trace.slice_ms(other, "mamba") is None and nemotron_trace.slice_ms(ctx, "mamba") == 0.0
    # 100% is the roofline: a step's scans at the bytes bound
    cfg = _json("benchmark", "configs", CONFIG + ".json")
    cost = nemotron_flops.ssd_cost_per_step(cfg, 16384, 256)
    assert max(cost["ops"] / 197e12, cost["bytes"] / 819e9) * 1e3 == pytest.approx(7.37, abs=0.01)

"""What the mixture-of-experts cell's ``correct`` notices: step 1 of the
program through the shared train step, held to the float32 reference by the
kind's own report and the CELL'S OWN limits (``lm-moe-train-pack8k-b2.json``),
at the tiny size on the CPU.  The program as stated passes; each mutation
fails, by the limit named beside it."""

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
from batchai_retinanet_horovod_coco_tpu.models import deepseek_v2 as ds  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.obs.numerics import NumericsConfig  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.ops import moe  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.train import create_train_state  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.train.step import make_train_step  # noqa: E402

from test_benchmark_moe_cell import CONFIG, MIX, TINY_MODEL as TINY, TINY_TRAFFIC  # noqa: E402

BLOCKS = TINY_TRAFFIC["reference_blocks"]


def _json(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def kind():
    spec = importlib.util.spec_from_file_location("kind_lm_moe_train_loop",
                                                  os.path.join(REPO, "benchmark", "kinds", "lm_moe_train_loop.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def config():
    """The tiny model computing in float32: a mutation is a fault of the
    mathematics, and is shown against the cell's limits without the noise
    that 128 tokens in bfloat16 put on the router's gradient (one token that
    picks another expert is 0.8% of a layer's tokens; the published sizes
    have 16 384).  The control, and ``test_the_program_as_stated...`` in
    bfloat16, are the precision's own tests."""
    return dict(_json("benchmark", "configs", CONFIG + ".json"), **TINY, compute_dtype="float32")


@pytest.fixture(scope="module")
def traffic():
    # Adam's eps far under every gradient of the tiny model, as the cell's 1e-8 is under the published model's
    return dict(_json("benchmark", "traffic", MIX + ".json"), adam_eps=TINY_TRAFFIC["adam_eps"])


@pytest.fixture(scope="module")
def batch():
    return next(packed_token_batches(PackedTokensConfig(128, 64, 2, doc_len_median=16, doc_len_min=4, seed=5)))


def _sharpened(params):
    """At d = 64 the scores of N(0, 0.02^2) projections are 0.04: every
    softmax is flat and nothing of the scale or of the positions shows.  The
    query and key projections x 8 and x 4 give scores of 1.3, as the published
    widths give them (2048 inputs, heads of 192)."""
    scale = {"q": 8.0, "kv_a": 4.0, "kv_b": 4.0}
    return dict(params, attention={name: {k: w * scale.get(k, 1.0) for k, w in layer.items()}
                                   for name, layer in params["attention"].items()})


def step_one(kind, config, traffic, batch, program_config=None, program_traffic=None, skip_update=False):
    """The report of step 1: the reference reads ``config`` and ``traffic``;
    the program is built from ``program_config`` and ``program_traffic``
    (default the same)."""
    model, task, tx = kind.build(program_config or config, program_traffic or traffic)
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
    assert report["update"]["held_share"] > 0.999 and set(report["seconds"]) == {"reference", "norms", "update"}
    assert report["picks_differ"] == {"by_layer": [0.0, 0.0], "max": 0.0}
    assert {v["rel"] for v in report["rows"].values()} == {0.0} and report["rows"]["held"]["program"] > 0


def test_the_program_as_stated_in_bfloat16_is_correct_but_for_what_128_tokens_force(kind, config, traffic, batch):
    """bfloat16 inputs make some token pick another expert than the float32
    reference; of 128 tokens one is 0.8% of a layer's, and moves the norm of
    the router's and the routed experts' gradients and the signs of their
    elements as it does not among the 16 384 of the published sizes.  Every
    other limit of the cell holds at the tiny size too."""
    report, problems = step_one(kind, config, traffic, batch, program_config=dict(config, compute_dtype="bfloat16"))
    forced = ("gnorm/router", "gnorm/experts", "update: 0.9")  # the last: sign agreement, smallest in the router
    assert all(any(word in p for word in forced) for p in problems), problems
    assert 0 < report["picks_differ"]["max"] <= traffic["tolerances"]["picks_differ_max"]
    assert min(v for g, v in report["update"]["sign_agreement"].items() if g not in ("router", "experts")) > 0.975


@pytest.fixture
def fresh_traces():
    """A mutation patched into the model has to be traced: the layers are
    ``jax.checkpoint``-ed, and their traces are cached by function."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _capacity_of_an_average_share(experts):
    """``moe.experts`` behind a buffer sized for the AVERAGE load: the rows of
    an expert beyond tokens x k / experts_total are dropped (zeros come back),
    as a capacity factor of 1 drops them."""

    def clipped(xs, gate_up, down, plan, how, interpret=False):
        y = experts(xs, gate_up, down, plan, how, interpret)
        capacity = xs.shape[0] // 16  # the tiny model's 16 experts
        ends = jnp.cumsum(plan.group_sizes)
        row = jnp.arange(xs.shape[0])
        group = jnp.searchsorted(ends, row, side="right")
        rank = row - jnp.concatenate([jnp.zeros((1,), ends.dtype), ends])[group]
        return jnp.where((rank < capacity)[:, None], y, 0)

    return clipped


def _absent_experts_on_a_held_experts_weights(dispatch):
    """``moe.dispatch`` that sends the pairs of every ABSENT expert to the
    first held expert instead of leaving them out."""

    def everything_here(picks, held, experts):
        first = jnp.asarray(held[0], picks.dtype)
        is_held = jnp.isin(picks, jnp.asarray(held, picks.dtype))
        return dispatch(jnp.where(is_held, picks, first), held, experts)

    return everything_here


# mutation -> a word of the limit that must name it
MUTATIONS = {
    "fp8_matmuls": "",  # the cell's control: whichever limit sees it
    "shared_expert_dropped": "gnorm/shared",
    "routed_weights_renormalised": "gnorm/experts",
    "m_squared_left_out_of_the_scale": "gnorm/attention",
    "keys_rotated_by_their_place_in_the_sequence": "gnorm/attention",
    "rows_dropped_over_an_average_sized_buffer": "gnorm/experts",
    "auxiliary_loss_left_out": "aux_loss",
    "router_in_bfloat16": "",  # at this size by gnorm/experts (0.0090 against 0.0075): see the note at its branch
    "absent_experts_rows_on_a_held_experts_weights": "gnorm/experts",
    "router_leaning_toward_the_held_experts": "rows routed here (held)",  # the timed step's own counters
    "skipped_update": "update",
    "doubled_rate": "update",
}


@pytest.mark.parametrize("mutation", MUTATIONS)
def test_a_mutation_of_the_program_is_not_correct(kind, config, traffic, batch, mutation, monkeypatch, fresh_traces):
    kw = {}
    if mutation == "fp8_matmuls":  # the nearest precision below the one stated: the cell's control
        from benchmark.harness import moe_lm_control

        monkeypatch.setattr(ds, "_operand", ds._operand)  # put back after the control's patch
        moe_lm_control.lower_the_precision()
    elif mutation == "shared_expert_dropped":
        gated = ds.lm_layers.gated_mlp
        monkeypatch.setattr(ds.lm_layers, "gated_mlp",
                            lambda cast, p, u: gated(cast, p, u) * (0.0 if p["gate_up"].shape[-1] == 2 * 2 * 32 else 1.0))
    elif mutation == "routed_weights_renormalised":  # norm_topk_prob true: the picked scores made to sum to 1
        route = moe.route
        monkeypatch.setattr(moe, "route", lambda u, w, k: (lambda r: r._replace(
            weights=r.weights / jnp.sum(r.weights, axis=-1, keepdims=True)))(route(u, w, k)))
    elif mutation == "m_squared_left_out_of_the_scale":  # 24^-1/2 alone: YaRN's mscale_all_dim term forgotten
        monkeypatch.setattr(ds.DeepseekV2Config, "softmax_scale", property(
            lambda self: (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5))
    elif mutation == "keys_rotated_by_their_place_in_the_sequence":
        # Positions that merely run on across documents change NO output (rotary scores depend on distances:
        # tests/unit/test_deepseek_v2.py), so that fault cannot be seen by any comparison.  What can: the keys
        # placed by their index in the sequence while the queries restart with their document.
        rotary = ds.rope.apply_rotary
        monkeypatch.setattr(ds.rope, "apply_rotary", lambda x, pos, f, s=1.0: rotary(
            x, jnp.broadcast_to(jnp.arange(pos.shape[1]), pos.shape) if x.shape[2] == 1 else pos, f, s))
    elif mutation == "rows_dropped_over_an_average_sized_buffer":
        monkeypatch.setattr(moe, "experts", _capacity_of_an_average_share(moe.experts))
    elif mutation == "auxiliary_loss_left_out":
        kw["program_config"] = dict(config, aux_loss_alpha=0.0)
    elif mutation == "router_in_bfloat16":
        # Scores from bfloat16 logits: other experts are picked (here 2-5% of the tokens, where the float32
        # program has none) and every score carries 8 bits.  ``picks_differ_max`` cannot hold it: the limit has
        # to let through what bfloat16 INPUTS of a float32 router do at the published sizes (5-10%).  What
        # notices at this size is the norm of the routed experts' gradient, by little.
        def route_bf16(u, w, k):
            logits = jnp.dot(u.astype(jnp.bfloat16), w.astype(jnp.bfloat16)).astype(jnp.float32)
            scores = jax.nn.softmax(logits, axis=-1)
            weights, picks = jax.lax.top_k(scores, k)
            return moe.Routing(scores, picks.astype(jnp.int32), weights, jnp.zeros((w.shape[-1],), jnp.int32))

        monkeypatch.setattr(moe, "route", route_bf16)  # nothing else is rounded: the router alone
    elif mutation == "absent_experts_rows_on_a_held_experts_weights":
        monkeypatch.setattr(moe, "dispatch", _absent_experts_on_a_held_experts_weights(moe.dispatch))
    elif mutation == "router_leaning_toward_the_held_experts":  # their logits raised: more rows land here
        lean = jnp.zeros((16,), jnp.float32).at[jnp.asarray(config["experts_held"])].set(0.05)

        def route_leaning(u, w, k):
            logits = jnp.dot(u.astype(jnp.float32), w, precision=jax.lax.Precision.HIGHEST) + lean
            scores = jax.nn.softmax(logits, axis=-1)
            weights, picks = jax.lax.top_k(scores, k)
            return moe.Routing(scores, picks.astype(jnp.int32), weights, jnp.zeros((w.shape[-1],), jnp.int32))

        monkeypatch.setattr(moe, "route", route_leaning)
    elif mutation == "skipped_update":
        kw["skip_update"] = True
    elif mutation == "doubled_rate":  # the optimizer at twice the rate the cell declares
        kw["program_traffic"] = dict(traffic, lr=2 * traffic["lr"])
    report, problems = step_one(kind, config, traffic, batch, **kw)
    assert problems, (mutation, report)
    assert all(p.startswith("first step's") for p in problems)
    assert any(MUTATIONS[mutation] in p for p in problems), (mutation, problems)


def test_positions_that_run_on_across_documents_cannot_be_seen(kind, config, traffic, batch, monkeypatch, fresh_traces):
    """ISSUE 30 asked for this mutation to fail.  It cannot: rotary scores
    depend on the distance of query and key alone, and both lie in one
    document, so the program computes the same function (to the rounding of
    larger float32 angles).  Kept as a test so that nobody looks for it again."""
    monkeypatch.setattr(ds.rope, "document_positions", lambda s: jnp.broadcast_to(jnp.arange(s.shape[1]), s.shape))
    _, problems = step_one(kind, config, traffic, batch)
    assert problems == []


def test_the_flop_model_counts_what_the_configuration_says():
    from benchmark.harness import lm_flops, moe_lm_flops

    published = _json("benchmark", "configs", CONFIG + ".json")
    held = published["parameters_held"]
    tokens, rows = 2 * 8192, 5 * 12288.0  # a step; the average share of its picks over five expert layers
    pairs = 2 * lm_flops.attention_pairs([[[0] * 8192]])
    fwd = moe_lm_flops.forward_flops_per_step(published, tokens, pairs, rows)
    # 2 FLOPs per parameter in a matmul per token, outside the routed experts: norms are no matmuls
    attention = held["attention"] - 512
    assert fwd["attention_matmuls"] == 2.0 * tokens * 6 * attention
    assert fwd["dense_mlp"] == 2.0 * tokens * 3 * 2048 * 10944
    assert fwd["shared_experts"] + fwd["router"] == 2.0 * tokens * 5 * (3 * 2048 * 2816 + 2048 * 64)
    assert fwd["lm_head"] == 2.0 * tokens * 12800 * 2048
    # the routed experts by the rows really routed here: an expert's three matrices a row
    assert fwd["routed_experts"] == 2.0 * rows * 3 * 2048 * 1408
    assert moe_lm_flops.forward_flops_per_step(published, tokens, pairs, 2 * rows)["routed_experts"] == 2 * fwd["routed_experts"]
    assert fwd["attention_pairs"] == 2.0 * pairs * 6 * 16 * (192 + 128)
    train = moe_lm_flops.train_flops_per_step(published, tokens, pairs, rows)
    assert train["total"] == pytest.approx(3 * fwd["total"]) and 3.5e13 < train["total"] < 6e13
    # the kernel runs every product forward twice (the layer is recomputed) and its two gradients
    cost = moe_lm_flops.gmm_cost_per_step(published, rows)
    assert cost["ops"] == pytest.approx(4 * fwd["routed_experts"])
    assert cost["ops"] / 197e12 > cost["bytes"] / 819e9  # the operations bound applies


def test_the_kernels_roofline_reads_the_rows_of_the_very_steps_it_times():
    """A made-up device plane: eight runs of the step program after the
    profiler started at step 20, the steady stretch runs 3-7 (steps 23-27),
    kernel calls of 2 ms in each; the counter was fetched at steps 20, 24, 28."""
    import types

    from benchmark.harness import moe_lm_trace
    from benchmark.harness import trace_reduce as tr

    ms = 1_000_000
    modules = [tr.Event("jit_train_step", 10 * i * ms, (10 * i + 9) * ms) for i in range(8)]
    ops = [tr.Event(name, m.start + k * ms, m.start + (k + 1) * ms)
           for m in modules for k, name in enumerate(["gmm.3", "tgmm", "fusion.7", "gmm"])]
    trace = tr.Trace([tr.DevicePlane("tpu0", ops, modules)], [])
    ctx = types.SimpleNamespace(trace=trace, window=(modules[2].start, modules[6].end),
                                module_pattern=lambda: "train_step",
                                facts={"trace_from": 20, "moe_rows_logged": [[16, 900.0], [20, 1000.0], [24, 1400.0],
                                                                             [28, 2200.0]]})
    kernel_ms, rows = moe_lm_trace.gmm_ms_and_rows(ctx)
    assert kernel_ms == pytest.approx(3.0)  # gmm.3, tgmm and gmm; not the fusion
    # steps 23..27: 1300, 1400, 1600, 1800, 2000 by interpolation between the fetched steps
    assert rows == pytest.approx((1300 + 1400 + 1600 + 1800 + 2000) / 5)
    # a program without the counter (the parent's), or a run that was not traced: nothing, and no error
    assert moe_lm_trace.gmm_ms_and_rows(types.SimpleNamespace(trace=trace, window=ctx.window, facts={})) is None
    assert moe_lm_trace.gmm_ms_and_rows(types.SimpleNamespace(trace=None, window=None, facts=ctx.facts)) is None

"""What the Keye-VL-2.0 cell's ``correct`` notices: step 1 of the program through
the shared train step, held to the float32 reference by the kind's own report
and the CELL'S OWN limits (``lm-dsa-moe-train-doc16k-b1.json``), at the tiny size
on the CPU.  The program as stated passes; each mutation fails, by the limit
named beside it."""

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
from batchai_retinanet_horovod_coco_tpu.models import keye_vl2 as kv  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.models import lm_layers  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.obs.numerics import NumericsConfig  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.ops import moe  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.ops import sparse_attention as sparse  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.train import create_train_state  # noqa: E402
from batchai_retinanet_horovod_coco_tpu.train.step import make_train_step  # noqa: E402

from test_benchmark_keye_cell import CONFIG, MIX, TINY_MODEL as TINY, TINY_TOLERANCES, TINY_TRAFFIC  # noqa: E402

BLOCKS = TINY_TRAFFIC["reference_blocks"]


def _json(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def kind():
    spec = importlib.util.spec_from_file_location(
        "kind_lm_dsa_moe_train_loop", os.path.join(REPO, "benchmark", "kinds", "lm_dsa_moe_train_loop.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def config():
    """The tiny model computing in float32: a mutation is a fault of the
    mathematics, and is shown against the cell's limits without the noise that
    128 tokens in bfloat16 put on the router's gradient.  The controls, and
    ``test_the_program_as_stated...`` in bfloat16, are the precision's own tests."""
    return dict(_json("benchmark", "configs", CONFIG + ".json"), **TINY, compute_dtype="float32")


@pytest.fixture(scope="module")
def traffic():
    # Adam's eps far under every gradient of the tiny model, as the cell's 1e-8 is under the published model's
    cell = _json("benchmark", "traffic", MIX + ".json")
    return dict(cell, adam_eps=TINY_TRAFFIC["adam_eps"], tolerances=dict(cell["tolerances"], **TINY_TOLERANCES))


@pytest.fixture(scope="module")
def batch():
    """Packed documents, so that a selection that leaves its document shows."""
    return next(packed_token_batches(PackedTokensConfig(128, 64, 2, doc_len_median=40, doc_len_min=26, seed=5)))


def _sharpened(params):
    """At d = 64 the scores of N(0, 0.02^2) projections are 0.04: every softmax
    is flat and the indexer's scores lie within a hair of one another.  The
    indexer's three matrices x 20 give index scores of order 1 that few
    roundings can reorder, as the published widths give them (the main
    attention's per-head norms make its scores of order 1 at any width)."""
    indexer = {name: dict(layer, q=20.0 * layer["q"], k=20.0 * layer["k"], w=20.0 * layer["w"])
               for name, layer in params["indexer"].items()}
    return dict(params, indexer=indexer)


def step_one(kind, config, traffic, batch, program_config=None, program_traffic=None, skip_update=False):
    """The report of step 1: the reference reads ``config`` and ``traffic``; the
    program is built from ``program_config`` and ``program_traffic`` (default
    the same)."""
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
    picks, selection = jax.jit(model.picks_and_selection)(before, batch.tokens, batch.segment_ids)
    report = kind.first_step_report(config, traffic, logged, after, before, batch, np.asarray(picks), selection, BLOCKS)
    return report, kind.first_step_problems(report, traffic["tolerances"])


def test_the_program_as_stated_is_correct(kind, config, traffic, batch):
    report, problems = step_one(kind, config, traffic, batch)
    assert problems == [], problems
    assert report["update"]["held_share"] > 0.99 and set(report["seconds"]) == {"reference", "norms", "update"}
    assert report["picks_differ"] == {"by_layer": [0.0, 0.0, 0.0], "max": 0.0}
    assert {v["rel"] for v in report["rows"].values()} == {0.0} and report["rows"]["held"]["program"] > 0
    s = report["selection"]
    assert s["differ_share_max"] == 0.0 and s["distance_max"] == 0.0 and s["outside_allowed"] == 0
    assert s["pairs"]["program"] == s["pairs"]["reference_f32"] and s["selected_share"]["rel"] < 1e-6
    assert set(report) == {"loss", "aux_loss", "kl_loss", "grad_norm", *(f"gnorm/{g}" for g in kind.GROUPS),
                           "picks_differ", "selection", "threshold_ties", "rows", "update", "seconds"}


def test_the_program_as_stated_in_bfloat16_is_correct_but_for_what_128_tokens_force(kind, config, traffic, batch):
    """bfloat16 inputs make some token pick another expert and some query
    another key than the float32 reference; of 128 tokens one is 0.8% of a
    layer's, and moves the norm of the router's and the routed experts'
    gradients, the emptiest expert's count and the signs of their elements as it
    does not among the 16 384 of the published sizes.  Every other limit of the
    cell holds at the tiny size too, the selection's among them."""
    report, problems = step_one(kind, config, traffic, batch, program_config=dict(config, compute_dtype="bfloat16"))
    forced = ("gnorm/router", "gnorm/experts", "rows routed here", "update: 0.9")  # the last: sign agreement
    assert all(any(word in p for word in forced) for p in problems), problems
    assert 0 <= report["picks_differ"]["max"] <= traffic["tolerances"]["picks_differ_max"]
    assert 0 < report["selection"]["differ_share_max"] <= traffic["tolerances"]["selection_differ_max"]
    assert 0 < report["selection"]["distance_max"] <= traffic["tolerances"]["selection_distance_max"]


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


# mutation -> a word of the limit that must name it
MUTATIONS = {
    "fp8_matmuls": "",  # the cell's first control: whichever limit sees it
    "fp8_index_scores": "selection",  # the cell's second control: the selection moves
    "indexer_left_out": "selected pairs",  # every causal key of the document is kept
    "topk_off_by_one": "selected pairs",
    "selection_leaves_the_document": "outside the causal past",
    "kl_target_not_renormalised": "kl_loss",  # the heads' probabilities summed, not their mean
    "kl_gradient_leaves_the_indexer": "gnorm/",  # the indexer reads the layer's input WITH its gradient
    "language_loss_reaches_the_indexer": "gnorm/indexer",  # the scores as a bias of the main attention
    "q_k_norms_left_out": "gnorm/attention",
    "weights_not_renormalised": "gnorm/experts",
    "balance_loss_a_layer_at_a_time": "aux_loss",
    "absent_experts_rows_on_a_held_experts_weights": "gnorm/experts",
    "skipped_update": "update",
    "doubled_rate": "update",
}


@pytest.mark.parametrize("mutation", MUTATIONS)
def test_a_mutation_of_the_program_is_not_correct(kind, config, traffic, batch, mutation, monkeypatch, fresh_traces):
    kw = {}
    if mutation in ("fp8_matmuls", "fp8_index_scores"):  # the nearest precision below the one stated: the controls
        from benchmark.harness import keye_control

        control = "operands" if mutation == "fp8_matmuls" else "scores"
        name = keye_control.PATCHED[control]
        monkeypatch.setattr(kv, name, getattr(kv, name))  # put back after the control's patch
        keye_control.lower_the_precision(control)
    elif mutation == "indexer_left_out":  # no threshold: every allowed key passes
        everything = lambda scores, seg, topk, q_block=0: sparse.Thresholds(
            jnp.full(scores.shape[:2], sparse.INT32_MIN, jnp.int32), jnp.full(scores.shape[:2], scores.shape[-1], jnp.int32),
            jnp.zeros(scores.shape[:2], bool))
        monkeypatch.setattr(sparse, "thresholds", everything)
    elif mutation == "topk_off_by_one":
        kw["program_config"] = dict(config, sa_config=dict(config["sa_config"], topk=config["sa_config"]["topk"] + 1))
    elif mutation == "selection_leaves_the_document":
        allowed = sparse.allowed_pairs
        monkeypatch.setattr(sparse, "allowed_pairs", lambda seg, rows=None, keys=None: allowed(
            jnp.zeros_like(seg), rows, keys))
    elif mutation == "kl_target_not_renormalised":
        attend = sparse.selected_attention
        heads = config["num_attention_heads"]
        monkeypatch.setattr(sparse, "selected_attention", lambda *a, **k: (
            lambda out, probs: (out, heads * probs))(*attend(*a, **k)))
    elif mutation == "kl_gradient_leaves_the_indexer":
        monkeypatch.setattr(kv, "_detached", lambda u: u)
    elif mutation == "language_loss_reaches_the_indexer":  # the scores, differentiably, into the layer's output
        attend = sparse.sparse_attention

        def leaking(q, k, v, q_idx, k_idx, w, seg, **kwargs):
            a = attend(q, k, v, q_idx, k_idx, w, seg, **kwargs)
            leak = jnp.mean(sparse.index_scores(q_idx, k_idx, w, kwargs["index_scale"], kwargs["q_block"]), axis=-1)
            through = (leak - jax.lax.stop_gradient(leak))[..., None, None]  # nothing of the value, all of the gradient
            return a._replace(out=a.out + through.astype(a.out.dtype))

        monkeypatch.setattr(sparse, "sparse_attention", leaking)
    elif mutation == "q_k_norms_left_out":
        norm = lm_layers.rms_norm
        monkeypatch.setattr(lm_layers, "rms_norm", lambda x, w, eps: x if x.ndim == 4 else norm(x, w, eps))
    elif mutation == "weights_not_renormalised":  # norm_topk_prob false: the picked scores as they are
        monkeypatch.setattr(moe, "route_renormalised", moe.route)
    elif mutation == "balance_loss_a_layer_at_a_time":  # the sum of the layers' own terms, not the term of their means
        monkeypatch.setattr(moe, "global_balance_loss", lambda counts, mean_scores, tokens: counts.shape[-1] * jnp.sum(
            jax.lax.stop_gradient(counts.astype(jnp.float32) / tokens) * mean_scores))
    elif mutation == "absent_experts_rows_on_a_held_experts_weights":
        monkeypatch.setattr(moe, "dispatch", _absent_experts_on_a_held_experts_weights(moe.dispatch))
    elif mutation == "skipped_update":
        kw["skip_update"] = True
    elif mutation == "doubled_rate":  # the optimizer at twice the rate the cell declares
        kw["program_traffic"] = dict(traffic, lr=2 * traffic["lr"])
    report, problems = step_one(kind, config, traffic, batch, **kw)
    assert problems, (mutation, report)
    assert all(p.startswith("first step's") for p in problems)
    assert any(MUTATIONS[mutation] in p for p in problems), (mutation, problems)


def test_the_readers_read_the_scopes_and_the_kernels_of_the_very_steps_they_time():
    """A made-up device plane: eight runs of the step program after the profiler
    started at step 16, the steady stretch runs 3-7 (steps 19-23); in each,
    grouped products of 3 ms; the counter was fetched at steps 12, 16, 20, 24."""
    import types

    from benchmark.harness import keye_flops, keye_trace
    from benchmark.harness import trace_reduce as tr

    ms = 1_000_000
    modules = [tr.Event("jit_train_step", 20 * i * ms, (20 * i + 19) * ms) for i in range(8)]
    names = ["gmm.3", "tgmm", "fusion.7", "gmm", "dsa_attention_fwd"]
    ops = [tr.Event(name, m.start + k * ms, m.start + (k + 1) * ms) for m in modules for k, name in enumerate(names)]
    trace = tr.Trace([tr.DevicePlane("tpu0", ops, modules)], [])
    facts = {"trace_from": 16, "moe_rows_logged": [[12, 900.0], [16, 1000.0], [20, 1400.0], [24, 2200.0]]}
    by_scope = {"attention": {"attention_core": {"fwd": 30.0, "bwd": 90.0}, "indexer": {"fwd": 10.0, "bwd": 30.0}}}
    ctx = types.SimpleNamespace(trace=trace, window=(modules[2].start, modules[6].end), facts=facts,
                                module_pattern=lambda: "train_step",
                                _program_slices={"ms": {"attention": 200.0}, "by_scope": by_scope})
    kernel_ms, rows = keye_trace.gmm_ms_and_rows(ctx)
    assert kernel_ms == pytest.approx(3.0)  # gmm.3, tgmm and gmm; not the fusion
    assert rows == pytest.approx((1300 + 1400 + 1600 + 1800 + 2000) / 5)  # steps 19..23 by interpolation
    assert keye_trace.slice_ms(ctx, "attention") == 200.0 and keye_trace.slice_ms(ctx, "moe") == 0.0
    assert keye_trace.slice_ms(ctx, "attention", ("attention_core",)) == 120.0
    assert keye_trace.slice_ms(ctx, "attention", ("indexer", "select")) == 40.0
    # a program that is not this model's step (no scopes of its), or a run that was not traced: nothing, no error
    other = types.SimpleNamespace(trace=trace, window=ctx.window, facts=facts, module_pattern=ctx.module_pattern,
                                  _program_slices=None)
    assert keye_trace.gmm_ms_and_rows(other) is None and keye_trace.slice_ms(other, "attention") is None
    untraced = types.SimpleNamespace(trace=None, window=None, facts=facts, _program_slices=ctx._program_slices)
    assert keye_trace.gmm_ms_and_rows(untraced) is None
    # 100% is the roofline: the model's attention of a step over the selected pairs, at the operations bound
    cfg = _json("benchmark", "configs", CONFIG + ".json")
    selected = keye_flops.selected_pairs([np.zeros((1, 16384), np.int32)], 2048)
    cost = keye_flops.attention_core_cost_per_step(cfg, 16384, selected)
    assert max(cost["ops"] / 197e12, cost["bytes"] / 819e9) * 1e3 == pytest.approx(62.8, abs=0.1)
    causal = 16384 * 16385 / 2
    cost = keye_flops.indexer_cost_per_step(cfg, 16384, causal)
    assert max(cost["ops"] / 197e12, cost["bytes"] / 819e9) * 1e3 == pytest.approx(42.5, abs=0.1)

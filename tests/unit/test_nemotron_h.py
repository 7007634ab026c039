"""Nemotron-H on a share of its experts (models/nemotron_h.py, ops/ssd.py with
grouped B and C, ops/moe.py's sigmoid router and squared-ReLU experts,
ops/attention.py) against the plain float32 reference
(benchmark/reference/nemotron_h.py) on seeded weights at the tiny size: the
pattern ``MEM*E`` at d = 64, 4 mamba heads of 16 in 2 groups, state 16, 4
query heads on 2 key/value heads, 8 experts of width 24 of which 2 are held,
3 a token, a shared expert of 48, vocabulary 128, sequences of 64 tokens with
1-5 documents."""

import dataclasses
import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batchai_retinanet_horovod_coco_tpu.models import lm_layers
from batchai_retinanet_horovod_coco_tpu.models import nemotron_h as nh
from batchai_retinanet_horovod_coco_tpu.models.language import build_language_model
from batchai_retinanet_horovod_coco_tpu.ops import moe
from batchai_retinanet_horovod_coco_tpu.train import create_train_state
from batchai_retinanet_horovod_coco_tpu.train.optim import OptimizerConfig, make_optimizer
from batchai_retinanet_horovod_coco_tpu.train.step import make_train_step
from batchai_retinanet_horovod_coco_tpu.train.task import LMTask
from benchmark.reference import nemotron_h as reference

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
T = 64
# what ``run_meta`` says of a recomputed layer's keeps where no device states a memory limit (the CPU)
NOTHING_MORE = {"layer_keeps": "attention_residuals,dsa_threshold", "mlp_gate_up_layers": 0, "mlp_gate_up_bytes": 0}
BIAS = ((0.06, -0.04, 0.0, 0.05, -0.06, 0.02, 0.04, -0.02), (-0.05, 0.06, 0.03, -0.02, 0.0, 0.04, -0.06, 0.02))
F32 = dataclasses.replace(nh.TINY, dtype=jnp.float32, router_bias=BIAS)
DOCS = ([20, 30, 14], [7, 57], [64], [5, 9, 21, 17, 12])  # documents per sequence
GROUPS = ("embed", "mamba", "attention", "router", "experts", "shared", "norms", "head")


def hf_of(config: nh.NemotronHConfig) -> dict:
    """The published keys the reference reads, for a program configuration."""
    keys = ("vocab_size", "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "mamba_num_heads",
            "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel", "num_experts_per_tok",
            "routed_scaling_factor", "layer_norm_epsilon", "rope_theta", "attention_rotary")
    return dict({k: getattr(config, k) for k in keys}, hybrid_override_pattern=config.pattern,
                num_hidden_layers=len(config.pattern), n_routed_experts=len(config.experts_held),
                n_routed_experts_total=config.experts_total, router_bias=[list(r) for r in config.router_bias])


def seeded(config=F32, seed=0):
    """Parameters with every leaf moved off its initial value (norm scales and D are 1, biases 0 as initialised)."""
    params = nh.init_params(config, jax.random.key(seed))
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    return jax.tree.unflatten(treedef, [x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])


def batch(rows=(0, 1, 3), vocab=128, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, vocab, (len(rows), T)).astype(np.int32)
    seg = np.stack([np.repeat(np.arange(len(DOCS[r])), DOCS[r]) for r in rows]).astype(np.int32)
    return tokens, seg


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def leaves_rel(got, wanted) -> dict:
    """The relative distance of every leaf, by its path."""
    flat = jax.tree_util.tree_leaves_with_path(wanted)
    return {jax.tree_util.keystr(path): rel(a, b) for (path, b), a in zip(flat, jax.tree.leaves(got), strict=True)}


@pytest.fixture(scope="module")
def reference_step():
    params, (tokens, seg) = seeded(), batch()
    loss, grads = reference.loss_and_grads(hf_of(F32), params, tokens, seg, F32.experts_held)
    return params, tokens, seg, float(loss), grads


@pytest.fixture(scope="module")
def program_step(reference_step):
    params, tokens, seg = reference_step[:3]
    model = nh.NemotronH(F32)
    (loss, scalars), grads = jax.jit(jax.value_and_grad(lambda p: model.loss(p, tokens, seg), has_aux=True))(params)
    return float(loss), {k: float(v) for k, v in scalars.items()}, grads


def test_logits_and_loss_equal_the_references(reference_step, program_step):
    params, tokens, seg, ref_loss, _ = reference_step
    logits = nh.NemotronH(F32).apply({"params": params}, tokens, seg)
    assert logits.dtype == jnp.float32 and logits.shape == (3, T, 128)
    assert rel(logits, reference.forward(hf_of(F32), params, tokens, seg, F32.experts_held)) < 2e-6
    loss, scalars, _ = program_step
    assert loss == pytest.approx(ref_loss, rel=2e-6) and scalars["loss"] == loss
    assert set(scalars) == {"loss", "tokens_counted", "moe/rows_held", "moe/rows_max_expert", "moe/rows_min_expert"}
    # the counters: rows routed to the 2 held of 8 experts in 2 expert layers of 3 x 64 tokens x 3 picks
    assert 0 < scalars["moe/rows_min_expert"] <= scalars["moe/rows_max_expert"] <= 3 * T
    assert 0.1 < scalars["moe/rows_held"] / (2 * 3 * T * 3) < 0.5


LEAVES = sorted(jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_leaves_with_path(
    jax.eval_shape(lambda: nh.init_params(F32, jax.random.key(0)))))


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_leafs_gradient_equals_the_references(reference_step, program_step, leaf):
    assert leaves_rel(program_step[2], reference_step[4])[leaf] < 2e-5


def test_the_gradient_has_the_eight_groups_and_every_one_is_alive(reference_step):
    grads = reference_step[4]
    assert sorted(grads) == sorted(GROUPS)
    assert all(float(jnp.linalg.norm(x)) > 0 for x in jax.tree.leaves(grads))
    assert len(LEAVES) == 2 * 8 + 4 + 2 * (1 + 2 + 2) + 6 + 2


def test_bfloat16_compute_stays_near_the_reference(reference_step):
    """bfloat16 activations and operands through five layers: the loss is
    ln 128 plus a little and hardly feels it; a gradient group by about 1%,
    the router's and the routed experts' by more (a token whose third and
    fourth scores lie within the rounding picks another expert)."""
    params, tokens, seg, ref_loss, ref_grads = reference_step
    model = nh.NemotronH(dataclasses.replace(F32, dtype=jnp.bfloat16))
    (loss, _), grads = jax.value_and_grad(lambda p: model.loss(p, tokens, seg), has_aux=True)(params)
    assert float(loss) == pytest.approx(ref_loss, rel=2e-3)
    for group in GROUPS:
        a, b = jax.tree.leaves(grads[group]), jax.tree.leaves(ref_grads[group])
        off = float(np.sqrt(sum(jnp.sum(jnp.square(x - y)) for x, y in zip(a, b)) / sum(jnp.sum(jnp.square(y)) for y in b)))
        assert off < (0.3 if group in ("router", "experts") else 0.04), (group, off)


def test_the_reference_by_layer_and_in_blocks_equals_the_reference_direct(reference_step):
    params, tokens, seg, ref_loss, ref_grads = reference_step
    loss, grads, picks = reference.loss_and_grads_by_layer(hf_of(F32), params, tokens, seg, F32.experts_held,
                                                           head_block=2, scan_block=16)
    assert float(loss) == pytest.approx(ref_loss, rel=1e-6)
    assert max(leaves_rel(grads, ref_grads).values()) < 1e-5
    assert picks.shape == (3, 2, T, 3)  # (batch, expert layers, T, k)
    np.testing.assert_array_equal(np.sort(np.asarray(picks).transpose(1, 0, 2, 3), axis=-1),
                                  np.sort(np.asarray(nh.NemotronH(F32).picks(params, tokens, seg)), axis=-1))


# ---- the expert layer -----------------------------------------------------------


def _layer_input(seed=3):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(2, T, F32.hidden_size)), jnp.float32)


def _expert_layer(params, name="layer_1"):
    return tuple(params[g][name] for g in ("router", "experts", "shared"))


def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """A 4-way split of the 8 experts: the routed parts of the four chips
    summed, plus the shared expert counted ONCE, equal the reference's whole
    layer (``held`` = all 8): the weights are normalised over all three picks
    on every chip, whoever holds them."""
    whole = dataclasses.replace(F32, experts_held=tuple(range(8)))
    router, experts, shared = _expert_layer(seeded(whole))
    u = _layer_input()
    bias = jnp.asarray(BIAS[0])
    with jax.default_matmul_precision("highest"):
        shared_part = lm_layers.relu2_mlp(lambda x: x, shared, u)
        total = shared_part
        for chip in range(4):
            held = (2 * chip, 2 * chip + 1)
            mine = jax.tree.map(lambda w: w[jnp.asarray(held)], experts)
            out, (rows, _) = nh._moe(dataclasses.replace(F32, experts_held=held), 0, router, mine, shared, u)
            assert rows.shape == (2,)
            total = total + (out - shared_part)
        uncut = jnp.stack([reference.moe(hf_of(whole), router, experts, shared, x, whole.experts_held, bias)[0]
                           for x in u])
    assert rel(total, uncut) < 2e-6
    # and one share alone is the reference's partial sum for that share
    with jax.default_matmul_precision("highest"):
        held = (4, 5)
        mine = jax.tree.map(lambda w: w[jnp.asarray(held)], experts)
        out = nh._moe(dataclasses.replace(F32, experts_held=held), 0, router, mine, shared, u)[0]
        partial = jnp.stack([reference.moe(hf_of(whole), router, mine, shared, x, held, bias)[0] for x in u])
    assert rel(out, partial) < 2e-6 and rel(out, uncut) > 0.05


def test_the_routers_bias_moves_picks_and_never_weights_and_the_weights_sum_to_the_scale():
    router, _, _ = _expert_layer(seeded())
    u = _layer_input().reshape(-1, F32.hidden_size)
    biased = moe.route_sigmoid(u, router["gate"], 3, jnp.asarray(BIAS[0]), 2.5)
    plain = moe.route_sigmoid(u, router["gate"], 3, jnp.zeros((8,)), 2.5)
    changed = np.any(np.sort(np.asarray(biased.picks), -1) != np.sort(np.asarray(plain.picks), -1), axis=-1)
    assert 0.1 < changed.mean() < 0.9  # the bias moves picks ...
    np.testing.assert_allclose(np.asarray(biased.weights).sum(-1), 2.5, rtol=1e-6)  # ... six (here three) weights sum to 2.5 ...
    scores = np.take_along_axis(np.asarray(biased.scores), np.asarray(biased.picks), axis=-1)
    np.testing.assert_allclose(np.asarray(biased.weights), 2.5 * scores / scores.sum(-1, keepdims=True), rtol=1e-6)
    same = ~changed  # ... and where the picks are the same, so are the weights (to the order of a sum of three)
    by_expert = lambda r: np.take_along_axis(np.asarray(r.weights), np.argsort(np.asarray(r.picks), -1), axis=-1)
    np.testing.assert_allclose(by_expert(biased)[same], by_expert(plain)[same], rtol=5e-7)
    # the reference's gate reads the same picks and weights
    weights, picks = reference.gate(hf_of(F32), router, u, jnp.asarray(BIAS[0]))
    np.testing.assert_array_equal(np.sort(np.asarray(picks), -1), np.sort(np.asarray(biased.picks), -1))
    np.testing.assert_allclose(np.take_along_axis(np.asarray(weights), np.asarray(biased.picks), axis=-1),
                               np.asarray(biased.weights), rtol=1e-5)


@pytest.mark.parametrize("to", ["held", "absent"])
def test_the_model_layer_drops_no_token_under_a_forced_router(to):
    params = seeded()
    _, experts, shared = _expert_layer(params)
    gate = np.zeros((F32.hidden_size, F32.experts_total), np.float32)
    gate[0, [0, 1, 2] if to == "held" else [5, 6, 7]] = [4.0, 5.0, 6.0]
    router = {"gate": jnp.asarray(gate)}
    u = _layer_input().at[..., 0].set(1.0)
    with jax.default_matmul_precision("highest"):
        out, (rows, picks) = nh._moe(F32, 0, router, experts, shared, u)
        wanted = jnp.stack([reference.moe(hf_of(F32), router, experts, shared, x, F32.experts_held,
                                          jnp.asarray(BIAS[0]))[0] for x in u])
    assert int(rows.sum()) == (2 * T * 2 if to == "held" else 0)  # experts 0 and 1 are held, 2 is not
    assert rel(out, wanted) < 2e-6


# ---- the mixer -------------------------------------------------------------------


def _mixer_params(params, name="layer_0"):
    """A mixer whose scan weighs as much as its ``D X`` skip: dt near 1 and
    not near 0.01, X, B and C of order 1 and not of order 0.05."""
    p = params["mamba"][name]
    return dict(p, dt_bias=p["dt_bias"] + 4.0, A_log=p["A_log"] - 2.0, conv_w=30.0 * p["conv_w"])


def _mixer(config, p, u, seg):
    with jax.default_matmul_precision("highest"):
        return nh._mamba(config, p, u, seg)


def test_a_head_reads_group_h_over_heads_per_group_and_a_permutation_of_the_groups_fails():
    """2 groups of 2 heads: the mixer against the reference's token-by-token
    scan; with the groups of B and C swapped (head h reading the OTHER group)
    it is far off."""
    params, (tokens, seg) = seeded(), batch()
    u = _layer_input(5)
    seg2 = jnp.asarray(seg[:2])
    p = _mixer_params(params)
    want = jnp.stack([reference.mamba(hf_of(F32), p, x, s) for x, s in zip(u, seg2)])
    assert rel(_mixer(F32, p, u, seg2), want) < 5e-6
    real = nh.ssd.ssd_chunked
    swapped = lambda x, dt, a, b, c, *rest: real(x, dt, a, b[:, :, ::-1], c[:, :, ::-1], *rest)
    with mock.patch.object(nh.ssd, "ssd_chunked", swapped):
        assert rel(_mixer(F32, p, u, seg2), want) > 0.1


def test_the_gate_norm_is_per_group_and_a_whole_width_norm_fails():
    params, (tokens, seg) = seeded(), batch()
    u = _layer_input(6)
    seg2 = jnp.asarray(seg[:2])
    p = _mixer_params(params)
    want = jnp.stack([reference.mamba(hf_of(F32), p, x, s) for x, s in zip(u, seg2)])
    whole = lambda config, y, w: lm_layers.rms_norm(y, w, config.layer_norm_epsilon)  # Granite's: all inner channels together
    with mock.patch.object(nh, "_group_norm", whole):
        assert rel(_mixer(F32, p, u, seg2), want) > 0.02
    assert rel(_mixer(F32, p, u, seg2), want) < 5e-6
    # by hand: each group's 32 channels have mean square 1 after the norm with unit scales
    y = jnp.asarray(np.random.default_rng(0).normal(size=(3, 64)) * np.r_[np.ones(32), 10 * np.ones(32)], jnp.float32)
    normed = np.asarray(nh._group_norm(F32, y, jnp.ones((64,)))).reshape(3, 2, 32)
    np.testing.assert_allclose((normed ** 2).mean(-1), 1.0, rtol=1e-4)


def test_the_attention_layer_has_no_positions_and_the_switch_turns_rotary_on():
    """[rotary] in both: without it a document's logits are those it gets
    alone whatever its place; with it the model still equals the reference
    (so the switch is the same switch), and differs from without."""
    params, (tokens, seg) = seeded(), batch()
    rotary = dataclasses.replace(F32, attention_rotary=True)
    plain_logits = nh.NemotronH(F32).apply({"params": params}, tokens, seg)
    rotary_logits = nh.NemotronH(rotary).apply({"params": params}, tokens, seg)
    assert rel(rotary_logits, reference.forward(hf_of(rotary), params, tokens, seg, F32.experts_held)) < 3e-6
    assert rel(rotary_logits, plain_logits) > 1e-3
    assert hf_of(rotary)[reference.ROTARY_KEY] is True and not hf_of(F32)[reference.ROTARY_KEY]


# ---- packing and the vocabulary's slice -------------------------------------------


def test_the_first_half_of_the_vocabulary_is_a_smaller_vocabulary():
    whole = dataclasses.replace(F32, vocab_size=256)
    params = seeded(whole)
    sliced = dict(params, embed={"embedding": params["embed"]["embedding"][:128]},
                  head={"rows": params["head"]["rows"][:128]})
    tokens, seg = batch()
    h_whole = nh.hidden_states(whole, params, tokens, seg)[0]
    h_slice = nh.hidden_states(F32, sliced, tokens, seg)[0]
    np.testing.assert_array_equal(np.asarray(h_whole), np.asarray(h_slice))
    np.testing.assert_allclose(np.asarray(nh.logits_of(whole, params, h_whole))[..., :128],
                               np.asarray(nh.logits_of(F32, sliced, h_slice)), rtol=1e-6, atol=1e-6)


def test_a_documents_logits_do_not_change_when_another_documents_tokens_do():
    """Scan state, convolution and attention all stop at a document's edge:
    the 30-token document of row 0 (behind 20 tokens of another, before 14 of
    a third) keeps its logits when the others' tokens change, and gets the
    same ones alone in a sequence of its own."""
    params = seeded()
    tokens, seg = batch(rows=(0,))
    model = nh.NemotronH(F32)
    packed = model.apply({"params": params}, tokens, seg)[0, 20:50]
    others = np.array(tokens)
    others[0, :20] = (others[0, :20] + 17) % 128
    others[0, 50:] = (others[0, 50:] + 5) % 128
    np.testing.assert_allclose(np.asarray(model.apply({"params": params}, others, seg)[0, 20:50]), np.asarray(packed),
                               rtol=2e-5, atol=2e-5)
    alone = model.apply({"params": params}, tokens[:, 20:50], np.zeros((1, 30), np.int32))[0]
    assert rel(packed, alone) < 5e-6
    moved = model.apply({"params": params}, others, seg)[0, :20]
    assert rel(moved, model.apply({"params": params}, tokens, seg)[0, :20]) > 1e-2  # the changed tokens do change theirs


# ---- the published configuration ---------------------------------------------

CONFIG_FILE = os.path.join(REPO, "benchmark", "configs", "nemotron-3-nano-30b-ep16.json")


def _published() -> dict:
    with open(CONFIG_FILE) as f:
        return json.load(f)


def test_the_benchmarks_configuration_holds_666_962_944_parameters():
    """The cut of ISSUE 32 by ``eval_shape``: nothing is allocated."""
    hf = _published()
    config = nh.NemotronHConfig.from_hf(hf)
    assert (config.experts_total, config.experts_held, config.num_experts_per_tok) == (128, tuple(range(8)), 6)
    assert config.pattern == "MEMEM*EME" == hf["published"]["hybrid_override_pattern"][:9]
    assert (config.mamba_d_inner, config.n_groups, config.routed_scaling_factor) == (4096, 8, 2.5)
    assert not config.attention_rotary and config.router_bias == () and config.mamba_chunk_size == 256
    shapes = jax.eval_shape(lambda key: nh.init_params(config, key), jax.random.key(0))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    layer = lambda i, *groups: sum(count(shapes[g].get(f"layer_{i}", {})) for g in (*groups, "norms"))
    held = hf["parameters_held"]
    mamba = shapes["mamba"]["layer_0"]
    assert mamba["in_proj"].shape == (2688, 10304) and count(mamba["in_proj"]) == 27_697_152
    assert (count(mamba["conv_w"]), count(mamba["conv_b"])) == (24_576, 6144)
    assert count([mamba["dt_bias"], mamba["A_log"], mamba["D"]]) == 192 and count(mamba["norm_w"]) == 4096
    assert count(mamba["out_proj"]) == 11_010_048
    assert layer(0, "mamba") == 38_744_896 == held["mamba_layer"]
    assert layer(5, "attention") == 23_399_040 == held["attention_layer"]
    assert count(shapes["router"]["layer_1"]) == 344_064 and count(shapes["shared"]["layer_1"]) == 19_955_712
    assert count(shapes["experts"]["layer_1"]) == 8 * 9_977_856 == held["routed_experts_of_a_layer"]
    assert layer(1, "router", "shared", "experts") == 100_125_312 == held["expert_layer"]
    assert layer(1, "router", "shared") == held["expert_layer_outside_the_routed_experts"]
    assert count(shapes["embed"]) + count(shapes["head"]) + shapes["norms"]["final"].size == 88_083_072
    assert count(shapes) == 666_962_944 == held["total"] == 4 * 38_744_896 + 23_399_040 + 4 * 100_125_312 + 88_083_072
    assert sorted(shapes) == sorted(GROUPS)
    assert shapes["router"]["layer_3"]["gate"].shape == (2688, 128)  # the router keeps its published width
    assert shapes["experts"]["layer_3"]["up"].shape == (8, 2688, 1856)  # the published width: no zero columns stored
    assert shapes["experts"]["layer_3"]["down"].shape == (8, 1856, 2688)
    assert [sorted(shapes[g]) for g in ("mamba", "attention", "experts")] == [
        [f"layer_{i}" for i in (0, 2, 4, 7)], ["layer_5"], [f"layer_{i}" for i in (1, 3, 6, 8)]]


def test_the_configuration_file_keeps_every_number_of_the_catalogs_row_but_the_reduced():
    hf = _published()
    assert hf["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    published = {"attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
                 "hidden_size": 2688, "intermediate_size": 1856, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
                 "mamba_num_heads": 64, "max_position_embeddings": 262144, "moe_intermediate_size": 1856,
                 "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8, "n_shared_experts": 1,
                 "norm_eps": 1e-05, "num_attention_heads": 32, "num_experts_per_tok": 6, "num_key_value_heads": 2,
                 "num_logits_to_keep": 1, "partial_rotary_factor": 1, "rope_theta": 10000,
                 "routed_scaling_factor": 2.5, "ssm_state_size": 128, "time_step_floor": 0.0001,
                 "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1, "model_type": "nemotron_h",
                 "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu", "norm_topk_prob": True}
    assert {k: hf[k] for k in published} == published
    assert (hf["num_hidden_layers"], hf["n_routed_experts"], hf["vocab_size"]) == (9, 8, 16384)
    assert hf["published"]["num_hidden_layers"] == 52 == len(hf["published"]["hybrid_override_pattern"])
    assert (hf["published"]["n_routed_experts"], hf["published"]["vocab_size"]) == (128, 131072) == (
        hf["n_routed_experts_total"], 8 * hf["vocab_size"])
    assert "16 chips" in hf["deployment"] and set(hf["assumed"]) >= {
        "weights", "e_score_correction_bias", "attention_positions", "dt_clamp", "rescale_prenorm_residual", "chunk_size"}


@pytest.mark.parametrize("key,value", [
    ("n_group", 8), ("topk_group", 4), ("mlp_hidden_act", "silu"), ("mamba_hidden_act", "gelu"),
    ("tie_word_embeddings", True), ("norm_topk_prob", False), ("n_shared_experts", 2), ("attention_bias", True),
    ("mamba_proj_bias", True), ("mlp_bias", True), ("use_bias", True), ("use_conv_bias", False),
    ("hybrid_override_pattern", "MEMEM*EM"), ("hybrid_override_pattern", "MEMEM*EM-"),
    ("experts_held", [0, 1, 2]), ("experts_held", [0, 1, 2, 3, 4, 5, 6, 128]), ("n_groups", 7),
])
def test_from_hf_refuses_what_it_does_not_compute(key, value):
    with pytest.raises(ValueError, match=key.split("_")[0]):
        nh.NemotronHConfig.from_hf(dict(_published(), **{key: value}))


def test_an_uncut_configuration_holds_every_expert_and_a_given_bias_is_kept():
    hf = {k: v for k, v in _published().items() if k not in ("experts_held", "n_routed_experts_total")}
    config = nh.NemotronHConfig.from_hf(dict(hf, n_routed_experts=128, router_bias=[[0.5] * 128] * 4))
    assert config.experts_total == 128 and config.experts_held == tuple(range(128))
    assert config.router_bias == ((0.5,) * 128,) * 4 and hash(config) is not None


# ---- the normal path ----------------------------------------------------------


def test_the_model_is_picked_by_model_type_or_preset():
    assert isinstance(build_language_model("tiny-nemotron"), nh.NemotronH)
    assert isinstance(build_language_model(CONFIG_FILE), nh.NemotronH)
    assert build_language_model(_published(), dtype=jnp.float32).config.dtype == jnp.float32
    assert build_language_model("tiny-nemotron").config == nh.TINY
    with pytest.raises(ValueError, match="nemotron_h"):  # the error lists what lm-synthetic trains
        build_language_model(dict(_published(), model_type="llama"))


def test_the_tiny_preset_has_all_three_kinds_two_groups_and_a_ragged_expert_width():
    c = nh.TINY
    assert set(c.pattern) == {"M", "E", "*"} and c.n_groups == 2 and (c.experts_total, len(c.experts_held)) == (8, 2)
    assert c.moe_intermediate_size % 128 and c.moe_intermediate_size % 16  # no multiple of a lane tile, nor of a packed sublane


def test_one_step_through_the_train_step_logs_the_counters_and_every_groups_norm():
    from batchai_retinanet_horovod_coco_tpu.obs.numerics import NumericsConfig

    model = nh.NemotronH(nh.TINY)
    tx = make_optimizer(OptimizerConfig(optimizer="adamw", schedule="constant", warmup_steps=0))[0]
    state = create_train_state(model, tx, (1, 8), jax.random.key(0), example_dtype=LMTask.example_dtype)
    tokens, seg = batch(rows=(0, 3))
    step = make_train_step(model, (2, T), None, task=LMTask(), donate_state=False,
                           numerics=NumericsConfig(enabled=True))
    new_state, metrics = step(state, {"tokens": jnp.asarray(tokens), "segment_ids": jnp.asarray(seg)})
    assert {"loss", "tokens_counted", "grad_norm", "moe/rows_held", "moe/rows_max_expert", "moe/rows_min_expert",
            *(f"gnorm/{g}" for g in GROUPS)} <= set(metrics)
    assert "moe/aux_loss" not in metrics  # the configuration has no balance loss
    assert int(new_state.step) == 1 and np.isfinite(float(metrics["loss"]))
    assert LMTask().run_meta(model, (2, T)) == {
        "attention_lowering": "xla", "ssd_lowering": "xla", "ssd_groups": 2, "conv_lowering": "xla",
        "moe_lowering": "xla", "moe_rows_lowering": "xla", "experts_held": 2, "experts_total": 8, **NOTHING_MORE}
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        meta = build_language_model(CONFIG_FILE).run_meta((2, 8192))
    assert meta == {"attention_lowering": "kernel", "attention_block_skip": "documents",
                    "attention_residuals": "kept", "ssd_lowering": "kernel",
                    "ssd_groups": 8, "conv_lowering": "kernel", "moe_lowering": "kernel",
                    "moe_rows_lowering": "kernel", "experts_held": 8, "experts_total": 128, **NOTHING_MORE}

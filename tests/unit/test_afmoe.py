"""Trinity (``afmoe``) on a share of its experts (models/afmoe.py: grouped-query
attention with a window and rotary positions in the sliding layers and neither
in the full ones, per-head q/k norms, a sigmoid gate on attention's output,
four norms a layer, the embedding times sqrt(d), a leading dense layer, then
``ops/moe.py``'s sigmoid router and gated experts with a shared one) against the
plain float32 reference (benchmark/reference/afmoe.py) on seeded weights at the
tiny size: layers sliding, sliding, full, sliding at d = 64, 4 query heads on 2
key/value heads of 16, a window of 16 keys, one dense layer of 96, 8 experts of
width 24 of which 2 are held, 3 a token, a shared expert of 24, vocabulary 128,
sequences of 64 tokens with 1-5 documents (shorter and longer than the window)."""

import dataclasses
import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batchai_retinanet_horovod_coco_tpu.models import afmoe as af
from batchai_retinanet_horovod_coco_tpu.models.language import build_language_model
from batchai_retinanet_horovod_coco_tpu.ops import attention, moe
from batchai_retinanet_horovod_coco_tpu.train import create_train_state
from batchai_retinanet_horovod_coco_tpu.train.optim import OptimizerConfig, make_optimizer
from batchai_retinanet_horovod_coco_tpu.train.step import STEP_SCOPES, make_train_step, scope_table
from batchai_retinanet_horovod_coco_tpu.train.task import LMTask
from benchmark.reference import afmoe as reference

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG_FILE = os.path.join(REPO, "benchmark", "configs", "trinity-mini-ep8.json")
T = 64
# what ``run_meta`` says of a recomputed layer's keeps where no device states a memory limit (the CPU)
NOTHING_MORE = {"layer_keeps": "attention_residuals,dsa_threshold", "mlp_gate_up_layers": 0, "mlp_gate_up_bytes": 0}
BIAS = ((0.06, -0.04, 0.0, 0.05, -0.06, 0.02, 0.04, -0.02), (-0.05, 0.06, 0.03, -0.02, 0.0, 0.04, -0.06, 0.02),
        (0.01, 0.02, -0.03, 0.04, -0.05, 0.06, -0.01, 0.0))
F32 = dataclasses.replace(af.TINY, dtype=jnp.float32, expert_bias=BIAS)
DOCS = ([20, 30, 14], [7, 57], [64], [5, 9, 21, 17, 12])  # documents per sequence: the window is 16 keys
GROUPS = ("embed", "attention", "dense_mlp", "router", "experts", "shared", "norms", "head")


def hf_of(config: af.AfmoeConfig) -> dict:
    """The published keys the reference reads, for a program configuration."""
    keys = ("vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
            "num_dense_layers", "sliding_window", "num_attention_heads", "num_key_value_heads", "head_dim",
            "num_experts_per_tok", "num_shared_experts", "route_scale", "mup_enabled", "rope_theta", "rms_norm_eps")
    return dict({k: getattr(config, k) for k in keys}, layer_types=list(config.layer_types),
                num_experts=len(config.experts_held), num_experts_total=config.experts_total,
                expert_bias=[list(r) for r in config.expert_bias])


def seeded(config=F32, seed=0):
    """Parameters with every leaf moved off its initial value (norm scales are 1 as initialised)."""
    params = af.init_params(config, jax.random.key(seed))
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
    model = af.Afmoe(F32)
    (loss, scalars), grads = jax.jit(jax.value_and_grad(lambda p: model.loss(p, tokens, seg), has_aux=True))(params)
    return float(loss), {k: float(v) for k, v in scalars.items()}, grads


def test_logits_and_loss_equal_the_references(reference_step, program_step):
    params, tokens, seg, ref_loss, _ = reference_step
    logits = af.Afmoe(F32).apply({"params": params}, tokens, seg)
    assert logits.dtype == jnp.float32 and logits.shape == (3, T, 128)
    assert rel(logits, reference.forward(hf_of(F32), params, tokens, seg, F32.experts_held)) < 2e-6
    loss, scalars, _ = program_step
    assert loss == pytest.approx(ref_loss, rel=2e-6) and scalars["loss"] == loss
    assert set(scalars) == {"loss", "tokens_counted", "moe/rows_held", "moe/rows_max_expert", "moe/rows_min_expert"}
    # the counters: rows routed to the 2 held of 8 experts in 3 expert layers of 3 x 64 tokens x 3 picks
    assert 0 < scalars["moe/rows_min_expert"] <= scalars["moe/rows_max_expert"] <= 3 * T
    assert 0.1 < scalars["moe/rows_held"] / (3 * 3 * T * 3) < 0.5


LEAVES = sorted(jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_leaves_with_path(
    jax.eval_shape(lambda: af.init_params(F32, jax.random.key(0)))))


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_leafs_gradient_equals_the_references(reference_step, program_step, leaf):
    assert leaves_rel(program_step[2], reference_step[4])[leaf] < 2e-5


def test_the_gradient_has_the_eight_groups_and_every_one_is_alive(reference_step):
    grads = reference_step[4]
    assert sorted(grads) == sorted(GROUPS)
    assert all(float(jnp.linalg.norm(x)) > 0 for x in jax.tree.leaves(grads))
    # four layers of 7 attention leaves and 4 norms, one dense MLP, three expert layers of 1 + 2 + 2, and 3 more
    assert len(LEAVES) == 4 * (7 + 4) + 2 + 3 * (1 + 2 + 2) + 3


def test_bfloat16_compute_stays_near_the_reference(reference_step):
    """bfloat16 activations and operands through four layers: the loss hardly
    feels it; a gradient group by about 1%, the router's and the routed
    experts' by more (a token whose third and fourth scores lie within the
    rounding picks another expert)."""
    params, tokens, seg, ref_loss, ref_grads = reference_step
    model = af.Afmoe(dataclasses.replace(F32, dtype=jnp.bfloat16))
    (loss, _), grads = jax.value_and_grad(lambda p: model.loss(p, tokens, seg), has_aux=True)(params)
    assert float(loss) == pytest.approx(ref_loss, rel=2e-3)
    for group in GROUPS:
        a, b = jax.tree.leaves(grads[group]), jax.tree.leaves(ref_grads[group])
        off = float(np.sqrt(sum(jnp.sum(jnp.square(x - y)) for x, y in zip(a, b)) / sum(jnp.sum(jnp.square(y)) for y in b)))
        assert off < (0.3 if group in ("router", "experts") else 0.05), (group, off)


def test_the_reference_by_layer_and_in_blocks_equals_the_reference_direct(reference_step):
    params, tokens, seg, ref_loss, ref_grads = reference_step
    loss, grads, picks = reference.loss_and_grads_by_layer(
        hf_of(F32), params, tokens, seg, F32.experts_held, q_block=16, head_block=2, logits_block=32)
    assert float(loss) == pytest.approx(ref_loss, rel=1e-6)
    assert max(leaves_rel(grads, ref_grads).values()) < 1e-5
    program = af.Afmoe(F32).picks(params, tokens, seg)
    assert picks.shape == (3, 3, T, 3) and program.shape == (3, 3, T, 3)
    np.testing.assert_array_equal(np.sort(np.asarray(program), -1), np.sort(np.asarray(picks).transpose(1, 0, 2, 3), -1))


def test_one_adamw_step_moves_every_leaf_as_the_references_gradient_says(reference_step):
    """The first AdamW update is ``-lr (g / (|g| + eps) + decay p)``: the program's
    step through ``make_train_step`` against that formula on the REFERENCE's
    gradient."""
    params, tokens, seg, _, ref_grads = reference_step
    model = af.Afmoe(F32)
    lr, decay, eps = 1e-3, 0.1, 1e-12
    tx = make_optimizer(OptimizerConfig(optimizer="adamw", schedule="constant", warmup_steps=0, base_lr=lr, world_size=1,
                                        adam_eps=eps, weight_decay=decay, clip_global_norm=1e9))[0]
    state = create_train_state(model, tx, (3, T), jax.random.key(0), example_dtype=LMTask.example_dtype)
    state = state.replace(params=params)
    step = make_train_step(model, (3, T), None, task=LMTask(), donate_state=False)
    new_state, _ = step(state, {"tokens": jnp.asarray(tokens), "segment_ids": jnp.asarray(seg)})
    for (path, p0), p1, g in zip(jax.tree_util.tree_leaves_with_path(params), jax.tree.leaves(new_state.params),
                                 jax.tree.leaves(ref_grads)):
        clear = jnp.abs(g) > 1e-7  # where the sign of the gradient is beyond its rounding
        want = -lr * (jnp.sign(g) + (decay if p0.ndim >= 2 else 0.0) * p0)
        np.testing.assert_allclose(np.where(clear, p1 - p0, 0.0), np.where(clear, want, 0.0), rtol=0, atol=2e-2 * lr,
                                   err_msg=jax.tree_util.keystr(path))


# ---- what the architecture is made of -------------------------------------------


def test_a_packed_document_alone_equals_the_same_document_inside_a_packed_sequence():
    """Windows, rotary positions and routing are the document's own: 30 tokens
    (longer than the window) get the logits alone that they get behind 20 and
    before 14 tokens of other documents."""
    rng = np.random.default_rng(5)
    params = seeded()
    document = rng.integers(0, 128, (1, 30)).astype(np.int32)
    model = af.Afmoe(dataclasses.replace(F32, attention_q_block=16))
    tokens = np.concatenate([rng.integers(0, 128, (1, 20)).astype(np.int32), document,
                             rng.integers(0, 128, (1, 14)).astype(np.int32)], axis=1)
    seg = np.repeat(np.arange(3), [20, 30, 14])[None].astype(np.int32)
    packed = jax.jit(model.apply)({"params": params}, tokens, seg)
    front = np.concatenate([document, rng.integers(0, 128, (1, 34)).astype(np.int32)], axis=1)
    alone = jax.jit(model.apply)({"params": params}, front, np.repeat(np.arange(2), [30, 34])[None].astype(np.int32))
    np.testing.assert_allclose(np.asarray(packed[:, 20:50]), np.asarray(alone[:, :30]), rtol=2e-5, atol=2e-5)
    # and another document's tokens do not reach it
    other = tokens.copy()
    other[:, :20] = (other[:, :20] + 1) % 128
    np.testing.assert_allclose(np.asarray(jax.jit(model.apply)({"params": params}, other, seg)[:, 20:]),
                               np.asarray(packed[:, 20:]), rtol=2e-5, atol=2e-5)


def _attention_out(config, kind, params, u, seg):
    positions = jnp.asarray(reference.document_positions(jnp.asarray(seg[0])))[None]
    return af._attention(config, kind, params["attention"]["layer_0"], u, jnp.asarray(seg), positions)


def test_the_two_kinds_of_layer_differ_by_the_window_and_by_the_rotation():
    """A sliding layer is the reference's with the window AND the rotation; a
    full layer's has neither: each of the four mixtures is another function."""
    params = seeded()
    seg = np.zeros((1, T), np.int32)
    u = jnp.asarray(np.random.default_rng(2).normal(size=(1, T, 64)), jnp.float32)
    hf = hf_of(F32)
    with jax.default_matmul_precision("highest"):
        for kind in (af.SLIDING, af.FULL):
            got = _attention_out(F32, kind, params, u, seg)
            want = reference.attention(hf, kind, params["attention"]["layer_0"], u[0], jnp.asarray(seg[0]))
            assert rel(got[0], want) < 2e-6, kind
        sliding, full = (_attention_out(F32, kind, params, u, seg) for kind in (af.SLIDING, af.FULL))
        assert rel(sliding, full) > 0.05
        # the window alone: a sliding layer whose window holds the whole sequence still rotates
        wide = _attention_out(dataclasses.replace(F32, sliding_window=T), af.SLIDING, params, u, seg)
        assert rel(wide, full) > 0.02 and rel(wide, sliding) > 0.02
        # within the first 16 tokens a window of 16 keys hides nothing
        np.testing.assert_allclose(np.asarray(wide[:, :16]), np.asarray(sliding[:, :16]), rtol=1e-5, atol=1e-6)


def test_the_gate_the_head_norms_the_output_norms_and_the_embedding_scale_are_all_felt():
    """Each of these leaves carries a gradient of its own, and the embedding
    enters the first layer sqrt(d) = 8 times its rows."""
    params, (tokens, seg) = seeded(), batch()
    model = af.Afmoe(F32)
    logits = model.apply({"params": params}, tokens, seg)
    for group, layer, leaf in (("attention", "layer_0", "gate"), ("attention", "layer_2", "q_norm"),
                               ("attention", "layer_1", "k_norm"), ("norms", "layer_0", "attention_out"),
                               ("norms", "layer_3", "mlp_out")):
        moved = jax.tree.map(lambda x: x, params)
        moved[group] = dict(moved[group], **{layer: dict(moved[group][layer], **{leaf: 1.5 * moved[group][layer][leaf]})})
        assert rel(model.apply({"params": moved}, tokens, seg), logits) > 1e-3, leaf
    plain = af.Afmoe(dataclasses.replace(F32, mup_enabled=False))
    scaled = dict(params, embed={"embedding": 8.0 * params["embed"]["embedding"]})
    np.testing.assert_allclose(np.asarray(plain.apply({"params": scaled}, tokens, seg)), np.asarray(logits),
                               rtol=1e-5, atol=1e-5)
    assert rel(plain.apply({"params": params}, tokens, seg), logits) > 0.05


def _layer_input(seed=3):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(2, T, F32.hidden_size)), jnp.float32)


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer_with_the_shared_expert_counted_once():
    """An 8-way split of 16 experts: the routed parts of the eight chips summed,
    plus the shared expert ONCE (every chip computes it alike), equal the
    reference's whole layer (``held`` = all 16)."""
    whole = dataclasses.replace(F32, experts_total=16, experts_held=tuple(range(16)), expert_bias=())
    params = seeded(whole)
    router, experts, shared = (params[g]["layer_1"] for g in ("router", "experts", "shared"))
    u = _layer_input()
    hf = hf_of(whole)
    with jax.default_matmul_precision("highest"):
        shared_part = jnp.stack([reference.mlp(shared, x) for x in u])
        total = 0.0
        for chip in range(8):
            held = (2 * chip, 2 * chip + 1)
            mine = jax.tree.map(lambda w: w[jnp.asarray(held)], experts)
            out, (rows, picks) = af._moe(dataclasses.replace(whole, experts_held=held), 0, router, mine, shared, u)
            assert rows.shape == (2,) and picks.shape == (2, T, 3)
            total = total + (out - shared_part)  # this chip's routed part alone
        total = total + shared_part
        uncut = jnp.stack([reference.moe(hf, router, experts, shared, x, whole.experts_held,
                                         reference.expert_bias(hf, 0))[0] for x in u])
    assert rel(total, uncut) < 2e-6
    # and one share alone is the reference's partial sum for that share
    with jax.default_matmul_precision("highest"):
        held = (4, 5)
        mine = jax.tree.map(lambda w: w[jnp.asarray(held)], experts)
        out = af._moe(dataclasses.replace(whole, experts_held=held), 0, router, mine, shared, u)[0]
        partial = jnp.stack([reference.moe(hf, router, mine, shared, x, held, reference.expert_bias(hf, 0))[0] for x in u])
    assert rel(out, partial) < 2e-6 and rel(out, uncut) > 0.05


def test_a_nonzero_expert_bias_moves_the_picks_and_never_the_weights():
    rng = np.random.default_rng(1)
    u, gate = jnp.asarray(rng.normal(size=(200, 16)), jnp.float32), jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    zero, bias = jnp.zeros((8,)), jnp.asarray(BIAS[0]) * 5
    plain, biased = moe.route_sigmoid(u, gate, 3, zero, 2.5), moe.route_sigmoid(u, gate, 3, bias, 2.5)
    assert 0.05 < float(np.mean(np.any(np.sort(plain.picks, -1) != np.sort(biased.picks, -1), axis=-1))) < 0.9
    for routing in (plain, biased):  # a token's weights: its picked SCORES over their sum, times the scale
        picked = jnp.take_along_axis(routing.scores, routing.picks, axis=-1)
        np.testing.assert_allclose(np.asarray(routing.weights), np.asarray(2.5 * picked / picked.sum(-1, keepdims=True)),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(routing.weights.sum(-1)), 2.5, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(plain.scores), np.asarray(biased.scores))
    # through the model: the configuration's rows reach their layers, and the program's picks are the reference's
    params, (tokens, seg) = seeded(), batch()
    with_bias = af.Afmoe(F32).picks(params, tokens, seg)
    without = af.Afmoe(dataclasses.replace(F32, expert_bias=())).picks(params, tokens, seg)
    assert 0.0 < float(np.mean(np.sort(with_bias, -1) != np.sort(without, -1))) < 0.5


def test_the_first_half_of_the_vocabulary_is_a_smaller_vocabulary():
    """A sliced vocabulary (the first rows of the embedding and of the untied
    head): on ids of the slice the hidden states are the whole model's and the
    logits are its logits over the slice."""
    whole = dataclasses.replace(F32, vocab_size=256)
    params = seeded(whole)
    sliced = dict(params, embed={"embedding": params["embed"]["embedding"][:128]},
                  head={"rows": params["head"]["rows"][:128]})
    tokens, seg = batch()
    h_whole = af.hidden_states(whole, params, tokens, seg)[0]
    h_slice = af.hidden_states(F32, sliced, tokens, seg)[0]
    np.testing.assert_array_equal(np.asarray(h_whole), np.asarray(h_slice))
    np.testing.assert_allclose(np.asarray(af.logits_of(whole, params, h_whole))[..., :128],
                               np.asarray(af.logits_of(F32, sliced, h_slice)), rtol=1e-6, atol=1e-6)


def test_the_held_layers_are_layers_one_to_four_of_a_deeper_model():
    """The cut in depth: the tiny model's four layers (one dense, then sliding,
    full, sliding) are layers 1-4 of a six-layer model with TWO leading dense
    layers and the same pattern: fed layer 0's output they give layer 4's."""
    deeper = dataclasses.replace(F32, num_hidden_layers=6, num_dense_layers=2, expert_bias=(),
                                 layer_types=(af.SLIDING, *F32.layer_types, af.FULL))
    cut = dataclasses.replace(F32, expert_bias=())
    params = seeded(deeper)
    tokens, seg = batch()
    take = lambda group: {f"layer_{i - 1}": params[group][f"layer_{i}"] for i in range(1, 5) if f"layer_{i}" in params[group]}
    held = {g: take(g) for g in ("attention", "dense_mlp", "router", "experts", "shared")}
    held["norms"] = dict(take("norms"), final=params["norms"]["final"])
    held.update(embed=params["embed"], head=params["head"])
    assert jax.tree.structure(held) == jax.tree.structure(af.init_params(cut, jax.random.key(0)))
    hf = hf_of(deeper)
    with jax.default_matmul_precision("highest"):
        x = reference.embed(hf, params["embed"]["embedding"], jnp.asarray(tokens[0]))
        states = [x]
        for i in range(5):
            states.append(reference.layer(hf, i, *reference._layer_params(params, hf, i), states[-1], seg[0],
                                          deeper.experts_held)[0])
        # the program's four held layers on layer 0's output
        positions = reference.document_positions(jnp.asarray(seg[0]))[None]
        y, index = states[1][None], 0
        for i, kind in enumerate(cut.layer_types):
            dense = i < cut.num_dense_layers
            name = f"layer_{i}"
            mlp_p = held["dense_mlp"][name] if dense else (held["router"][name], held["experts"][name], held["shared"][name])
            y, _ = af._layer(cut, kind, None if dense else index, held["attention"][name], mlp_p, held["norms"][name], y,
                             jnp.asarray(seg[:1]), positions)
            index += not dense
    assert rel(y[0], states[5]) < 5e-6 and rel(states[5], states[1]) > 0.1


# ---- the configuration ----------------------------------------------------------


def _published() -> dict:
    with open(CONFIG_FILE) as f:
        return json.load(f)


def test_the_benchmarks_configuration_holds_705_473_792_parameters():
    """The cut of ISSUE 46 by ``eval_shape``: nothing is allocated."""
    hf = _published()
    config = af.AfmoeConfig.from_hf(hf)
    assert (config.experts_total, config.experts_held, config.num_experts_per_tok) == (128, tuple(range(16)), 8)
    assert config.layer_types == (af.SLIDING, af.SLIDING, af.FULL, af.SLIDING, af.SLIDING) and config.num_dense_layers == 1
    assert (config.sliding_window, config.route_scale, config.rope_theta, config.rms_norm_eps, config.mup_enabled) == (
        2048, 2.826, 10000, 1e-5, True)
    shapes = jax.eval_shape(lambda key: af.init_params(config, key), jax.random.key(0))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    by_layer = lambda group, i: count(shapes[group][f"layer_{i}"])
    held = hf["parameters_held"]
    assert by_layer("attention", 0) == 27_263_232 == held["attention"] == 3 * 2048 * 4096 + 2 * 2048 * 512 + 256
    assert by_layer("norms", 2) == 8192 == held["norms_of_a_layer"] and by_layer("dense_mlp", 0) == held["dense_mlp"]
    assert by_layer("attention", 0) + by_layer("norms", 0) + by_layer("dense_mlp", 0) == 65_020_160 == held["dense_layer"]
    assert by_layer("router", 1) == 262_144 == held["router"] and by_layer("shared", 4) == 6_291_456 == held["shared_expert"]
    assert by_layer("experts", 1) == 16 * 6_291_456 == 16 * held["a_routed_expert"]
    outside = sum(by_layer(g, 3) for g in ("attention", "norms", "router", "shared"))
    assert outside == 33_825_024 == held["expert_layer_outside_its_routed_experts"]
    assert outside + by_layer("experts", 3) == 134_488_320 == held["expert_layer"]
    assert count(shapes["embed"]) + count(shapes["head"]) + shapes["norms"]["final"].size == 102_500_352 == (
        held["embedding_head_and_final_norm"])
    assert count(shapes) == 705_473_792 == held["total"] == held["dense_layer"] + 4 * held["expert_layer"] + 102_500_352
    assert sorted(shapes) == sorted(GROUPS) and sorted(shapes["dense_mlp"]) == ["layer_0"]
    assert shapes["router"]["layer_3"]["gate"].shape == (2048, 128)  # the router keeps its published width
    assert shapes["experts"]["layer_3"]["gate_up"].shape == (16, 2048, 2 * 1024)
    assert shapes["embed"]["embedding"].shape == shapes["head"]["rows"].shape == (200192 // 8, 2048)


def test_the_configuration_file_keeps_every_number_of_the_catalogs_row_but_the_reduced():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Trinity-Mini")
    cfg = _published()
    assert cfg["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cfg.get(k, "absent") != v}
    assert differs == set(cfg["reduced"]) == {"num_hidden_layers", "num_dense_layers", "layer_types", "num_experts",
                                              "vocab_size"}
    assert cfg["layer_types"] == row["config"]["layer_types"][1:6] and cfg["vocab_size"] * 8 == row["vocab_size"]
    assert (cfg["num_experts"], cfg["num_experts_total"], cfg["experts_held"]) == (16, 128, list(range(16)))
    assert all("[assumed" not in v for v in cfg["assumed"].values()) and "not checked against the hub" in (
        cfg["assumed"]["not_checked_against_the_hub"])


@pytest.mark.parametrize("key,value", [
    ("n_group", 2), ("topk_group", 2), ("num_expert_groups", 4), ("num_limited_groups", 2), ("score_func", "softmax"),
    ("route_norm", False), ("hidden_act", "gelu"), ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}),
    ("tie_word_embeddings", True), ("layer_types", ["sliding_attention"] * 4),
    ("layer_types", ["sliding_attention"] * 4 + ["chunked_attention"]),
    ("experts_held", [0, 1, 2]), ("experts_held", list(range(15)) + [128]),
])
def test_from_hf_refuses_what_it_does_not_compute(key, value):
    with pytest.raises(ValueError, match=key.split("_")[0]):
        af.AfmoeConfig.from_hf(dict(_published(), **{key: value}))


def test_an_uncut_configuration_holds_every_expert_and_a_given_bias_is_kept():
    hf = {k: v for k, v in _published().items() if k not in ("experts_held", "num_experts_total")}
    config = af.AfmoeConfig.from_hf(dict(hf, num_experts=128, expert_bias=[[0.0] * 128] * 4))
    assert config.experts_total == 128 and config.experts_held == tuple(range(128))
    assert len(config.expert_bias) == 4 and len(config.expert_bias[0]) == 128
    assert af.AfmoeConfig.from_hf(hf_of(F32), dtype=jnp.float32, attention_q_block=32) == F32


@pytest.mark.parametrize("start", [None, 0.125])
def test_the_output_norms_scales_start_where_the_configuration_says_and_the_other_norms_at_one(start):
    """``output_norm_init`` (not a published key: the benchmark's file lists it under ``assumed``) is where the two
    norms on a layer's OUTPUTS start; left out, they start at 1 like every other norm."""
    config = af.AfmoeConfig.from_hf(dict(hf_of(F32), **({} if start is None else {"output_norm_init": start})),
                                    dtype=jnp.float32, attention_q_block=32)
    assert config.output_norm_init == (1.0 if start is None else start)
    params = af.init_params(config, jax.random.key(0))
    for name, norms in params["norms"].items():
        if name == "final":
            assert np.all(np.asarray(norms) == 1.0)
            continue
        for leaf, value in norms.items():
            assert np.all(np.asarray(value) == (config.output_norm_init if leaf.endswith("_out") else 1.0)), (name, leaf)
    assert all(np.all(np.asarray(p[n]) == 1.0) for p in params["attention"].values() for n in ("q_norm", "k_norm"))


# ---- the normal path ----------------------------------------------------------


def test_the_model_is_picked_by_model_type_or_preset():
    assert isinstance(build_language_model("tiny-afmoe"), af.Afmoe)
    assert build_language_model("tiny-afmoe").config == af.TINY
    assert isinstance(build_language_model(CONFIG_FILE), af.Afmoe)
    assert build_language_model(_published(), dtype=jnp.float32).config.dtype == jnp.float32
    with pytest.raises(ValueError, match="model_type 'llama'"):
        build_language_model(dict(_published(), model_type="llama"))


def test_the_tiny_preset_has_both_kinds_a_dense_layer_and_a_ragged_expert_width():
    c = af.TINY
    assert set(c.layer_types) == {af.SLIDING, af.FULL} and len(c.layer_types) == c.num_hidden_layers == 4
    assert c.num_dense_layers == 1 and c.experts_held == (0, 1) and c.experts_total == 8
    assert c.moe_intermediate_size % 128 and min(map(min, DOCS)) < c.sliding_window < max(map(max, DOCS))
    assert "1 dense" in af.Afmoe(c).describe() and "window of 16 keys" in af.Afmoe(c).describe()


def _state_and_batch():
    model = af.Afmoe(af.TINY)
    tx = make_optimizer(OptimizerConfig(optimizer="adamw", schedule="constant", warmup_steps=0))[0]
    state = create_train_state(model, tx, (1, 8), jax.random.key(0), example_dtype=LMTask.example_dtype)
    tokens, seg = batch(rows=(0, 3))
    return model, state, {"tokens": jnp.asarray(tokens), "segment_ids": jnp.asarray(seg)}


def test_the_models_scopes_reach_the_compiled_step_through_recomputation():
    """Forward, recomputed forward and backward keep the layer's scope and what
    lies beneath it; nothing of the other models' or of detection's is there."""
    model, state, arrays = _state_and_batch()
    compiled = make_train_step(model, (2, T), None, task=LMTask(), donate_state=False).lower(state, arrays).compile()
    table = scope_table(compiled)
    filed = {(s, d) for s, d, _ in table.values()}
    for s in (*model.scopes, "optimizer"):
        assert (s, "fwd") in filed, s
    assert {s for s, d in filed if d == "bwd"} >= {"embed", "attention", "dense_mlp", "moe", "lm_head", "loss"}
    assert not {"mamba", "mla", "mlp", "gdn", "backbone", "heads"} & {s for s, _ in filed}
    assert STEP_SCOPES["attention"][-2:] == ("window_core", "full_core")
    beneath = {"attention": ("window_core", "full_core"), "moe": tuple(n for n in STEP_SCOPES["moe"] if n != "aux")}
    for slice_, names in beneath.items():
        paths = {p for t, _, p in table.values() if t == slice_}
        for name in names:
            assert any(f"/{name}/" in p or p.endswith("/" + name) for p in paths), (slice_, name)
        assert not any("/indexer" in p or "/aux" in p for p in paths)


def test_one_step_through_the_train_step_logs_the_counters_and_every_groups_norm():
    from batchai_retinanet_horovod_coco_tpu.obs.numerics import NumericsConfig

    model, state, arrays = _state_and_batch()
    step = make_train_step(model, (2, T), None, task=LMTask(), donate_state=False,
                           numerics=NumericsConfig(enabled=True))
    new_state, metrics = step(state, arrays)
    assert {"loss", "tokens_counted", "grad_norm", "moe/rows_held", "moe/rows_max_expert", "moe/rows_min_expert",
            *(f"gnorm/{g}" for g in GROUPS)} <= set(metrics)
    assert not {"moe/aux_loss", attention.RUN_SHARE, attention.WINDOW_RUN_SHARE} & set(metrics)  # no balance loss; the CPU
    assert int(new_state.step) == 1 and np.isfinite(float(metrics["loss"]))
    assert LMTask().run_meta(model, (2, T)) == {
        "attention_lowering": "xla", "attention_edges": "xla", "attention_window": 16, "moe_lowering": "xla",
        "moe_rows_lowering": "xla",
        "experts_held": 2, "experts_total": 8, **NOTHING_MORE}
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):  # the cell's model and bucket: the kernels
        published = build_language_model(CONFIG_FILE)
        meta = published.run_meta((1, 16384))
        assert set(attention.step_counters(jnp.zeros((1, 16384), jnp.int32), 2048, 2)) == {
            attention.RUN_SHARE, attention.WINDOW_RUN_SHARE}
    assert meta == {
        "attention_lowering": "kernel", "attention_block_skip": "documents", "attention_residuals": "kept",
        "attention_edges": "kernel", "attention_window": 2048, "moe_lowering": "kernel", "moe_rows_lowering": "kernel", "experts_held": 16,
        "experts_total": 128, **NOTHING_MORE}

"""Keye-VL-2.0's language model on a share of its experts (models/keye_vl2.py,
ops/sparse_attention.py, ops/moe.py, ops/rope.py) against the plain float32
reference (benchmark/reference/keye_vl2.py) on seeded weights at the tiny size:
three layers at d = 64, 4 query / 2 key-value heads of 16, an indexer of 4 heads
of 8 that keeps 24 keys a query, 16 experts of width 32 of which 4 are held, 3 a
token, vocabulary 128, sequences of 64 tokens with 1-5 documents."""

import dataclasses
import functools
import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batchai_retinanet_horovod_coco_tpu.models import keye_vl2 as kv
from batchai_retinanet_horovod_coco_tpu.models.language import build_language_model
from batchai_retinanet_horovod_coco_tpu.ops import attention, moe
from batchai_retinanet_horovod_coco_tpu.ops import sparse_attention as sparse
from batchai_retinanet_horovod_coco_tpu.train import create_train_state
from batchai_retinanet_horovod_coco_tpu.train.optim import OptimizerConfig, make_optimizer
from batchai_retinanet_horovod_coco_tpu.train.step import STEP_SCOPES, make_train_step, scope_table
from batchai_retinanet_horovod_coco_tpu.train.task import LMTask
from benchmark.reference import keye_vl2 as reference

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG_FILE = os.path.join(REPO, "benchmark", "configs", "keye-vl2-30b-a3b-ep8.json")
T = 64
# what ``run_meta`` says of a recomputed layer's keeps where no device states a memory limit (the CPU)
NOTHING_MORE = {"layer_keeps": "attention_residuals,dsa_threshold", "mlp_gate_up_layers": 0, "mlp_gate_up_bytes": 0}
F32 = dataclasses.replace(kv.TINY, dtype=jnp.float32)
DOCS = ([20, 30, 14], [7, 57], [64], [5, 9, 21, 17, 12])  # documents per sequence
GROUPS = ("embed", "attention", "indexer", "router", "experts", "norms", "head")


def hf_of(config: kv.KeyeVL2Config) -> dict:
    """The published keys the reference reads, for a program configuration."""
    keys = ("vocab_size", "hidden_size", "moe_intermediate_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "num_experts_per_tok", "rms_norm_eps", "rope_theta",
            "router_aux_loss_coef", "indexer_loss_coef")
    return dict({k: getattr(config, k) for k in keys}, rope_scaling={"mrope_section": list(config.mrope_section)},
                sa_config={"indexer_num_heads": config.indexer_num_heads, "indexer_head_dim": config.indexer_head_dim,
                           "indexer_num_kv_heads": 1, "topk": config.indexer_topk},
                num_experts=len(config.experts_held), num_experts_total=config.experts_total)


def seeded(config=F32, seed=0):
    """Parameters with every leaf moved off its initial value (norm scales are 1 as
    initialised), the indexer's further: its scores then spread and few tie."""
    params = kv.init_params(config, jax.random.key(seed))
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.key(seed + 1), len(flat))
    moved = [x + (0.3 if path[0].key == "indexer" else 0.05) * jax.random.normal(k, x.shape)
             for (path, x), k in zip(flat, keys)]
    return jax.tree.unflatten(treedef, moved)


def batch(rows=(0, 1, 3), vocab=128, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, vocab, (len(rows), T)).astype(np.int32)
    seg = np.stack([np.repeat(np.arange(len(DOCS[r])), DOCS[r]) for r in rows]).astype(np.int32)
    return tokens, seg


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def group_rel(got, wanted, group):
    a, b = jax.tree.leaves(got[group]), jax.tree.leaves(wanted[group])
    return float(np.sqrt(sum(jnp.sum(jnp.square(x - y)) for x, y in zip(a, b)) / sum(jnp.sum(jnp.square(y)) for y in b)))


@pytest.fixture(scope="module")
def reference_step():
    params, (tokens, seg) = seeded(), batch()
    (loss, (aux, kl)), grads = reference.loss_and_grads(hf_of(F32), params, tokens, seg, F32.experts_held)
    return params, tokens, seg, float(loss), float(aux), float(kl), grads


@pytest.fixture(scope="module")
def program_step(reference_step):
    params, tokens, seg = reference_step[:3]
    model = kv.KeyeVL2(F32)
    (loss, scalars), grads = jax.jit(jax.value_and_grad(lambda p: model.loss(p, tokens, seg), has_aux=True))(params)
    return float(loss), {k: float(v) for k, v in scalars.items()}, grads


def test_logits_loss_and_both_auxiliary_losses_equal_the_references(reference_step, program_step):
    params, tokens, seg, ref_loss, ref_aux, ref_kl, _ = reference_step
    logits = jax.jit(kv.KeyeVL2(F32).apply)({"params": params}, tokens, seg)
    ref_logits, ref_selection = reference.forward(hf_of(F32), params, tokens, seg, F32.experts_held)
    assert rel(logits, ref_logits) < 2e-6
    loss, scalars, _ = program_step
    assert loss == pytest.approx(ref_loss, rel=2e-6) and scalars["loss"] == loss
    assert scalars["moe/aux_loss"] == pytest.approx(ref_aux, rel=2e-6) and 0 < ref_aux < 0.01 * ref_loss
    assert scalars["dsa/kl_loss"] == pytest.approx(ref_kl, rel=5e-6) and 0 < ref_kl < ref_loss
    # the counters: rows routed to the 4 held of 16 experts in 3 layers of 3 x 64 tokens x 3 picks
    assert 0 < scalars["moe/rows_min_expert"] <= scalars["moe/rows_max_expert"] <= 3 * T
    assert 0.1 < scalars["moe/rows_held"] / (3 * 3 * T * 3) < 0.5
    # the selection is exact: a query at position p of its document keeps min(p + 1, 24) keys, in every layer
    lengths = [n for r in (0, 1, 3) for n in DOCS[r]]
    selected = sum(sum(min(p + 1, 24) for p in range(n)) for n in lengths)
    causal = sum(n * (n + 1) // 2 for n in lengths)
    assert scalars["dsa/selected_share"] == pytest.approx(selected / causal, rel=1e-6)
    assert int(ref_selection.sum()) == 3 * selected


def test_the_selection_is_lax_top_ks_ties_included(reference_step):
    """The keys every query keeps, by bisection over the bit pattern, are the
    reference's ``lax.top_k`` of its masked scores in every layer; and on
    scores made to tie (rounded to halves, with both zeros) as well, where
    the lower position wins."""
    params, tokens, seg = reference_step[:3]
    _, selection = jax.jit(kv.KeyeVL2(F32).picks_and_selection)(params, tokens, seg)
    ref_selection = reference.forward(hf_of(F32), params, tokens, seg, F32.experts_held)[1]
    np.testing.assert_array_equal(np.asarray(selection), np.asarray(ref_selection))
    rng = np.random.default_rng(5)
    scores = jnp.asarray(np.round(rng.normal(size=(3, T, T)) * 2) / 2 * rng.choice([1.0, -1.0], (3, T, 1)), jnp.float32)
    assert bool(jnp.any(jnp.signbit(scores) & (scores == 0))) and bool(jnp.any(~jnp.signbit(scores) & (scores == 0)))
    @functools.partial(jax.jit, static_argnums=(0,))
    def select(topk, scores, seg):
        found = sparse.thresholds(scores, seg, topk, q_block=16)
        return found, sparse.selection_mask(scores, seg, found)

    for topk in (1, 5, 24, 64, 100):
        found, mask = select(topk, scores, jnp.asarray(seg))
        wanted = jnp.stack([reference.select(s, jnp.asarray(sg), topk)[0] for s, sg in zip(scores, seg)])
        np.testing.assert_array_equal(np.asarray(mask), np.asarray(wanted))
        assert bool(jnp.any(found.tied)) == (topk < 64)


@pytest.mark.parametrize("group", GROUPS)
def test_every_groups_gradient_equals_the_references(reference_step, program_step, group):
    assert group_rel(program_step[2], reference_step[6], group) < 5e-6


def test_bfloat16_compute_stays_near_the_reference_on_its_own_selection(reference_step):
    """bfloat16 activations and operands: a query whose 24th and 25th scores lie
    within the rounding keeps another key than the float32 reference (top-k is
    discontinuous), so the reference is handed the program's selection, as the
    benchmark's check hands it: loss and gradients then stay as near as dsv2's."""
    params, tokens, seg, ref_loss = reference_step[:4]
    model = kv.KeyeVL2(kv.TINY)
    (loss, scalars), grads = jax.jit(jax.value_and_grad(lambda p: model.loss(p, tokens, seg), has_aux=True))(params)
    _, selection = jax.jit(model.picks_and_selection)(params, tokens, seg)
    ref_selection = reference.forward(hf_of(F32), params, tokens, seg, F32.experts_held)[1]
    differ = float(jnp.mean(selection != ref_selection)) / float(jnp.mean(ref_selection))
    assert 0 < differ < 0.1
    (on_loss, (on_aux, on_kl)), on_grads = reference.loss_and_grads(
        hf_of(F32), params, tokens, seg, F32.experts_held, selection=list(selection))
    assert float(loss) == pytest.approx(float(on_loss), rel=2e-3) and float(on_loss) != ref_loss
    assert float(scalars["dsa/kl_loss"]) == pytest.approx(float(on_kl), rel=3e-2)
    assert float(scalars["moe/aux_loss"]) == pytest.approx(float(on_aux), rel=2e-2)
    for group in GROUPS:
        assert group_rel(grads, on_grads, group) < {"router": 0.25, "experts": 0.25, "indexer": 0.1}.get(group, 0.06), group


def test_the_reference_by_layer_and_in_blocks_equals_the_reference_direct(reference_step):
    params, tokens, seg, ref_loss, ref_aux, ref_kl, ref_grads = reference_step
    selection = reference.forward(hf_of(F32), params, tokens, seg, F32.experts_held)[1]
    (loss, (aux, kl)), grads, picks, reports = reference.loss_and_grads_by_layer(
        hf_of(F32), params, tokens, seg, F32.experts_held, selection=list(selection), head_block=2, q_block=16,
        score_block=8)
    assert float(loss) == pytest.approx(ref_loss, rel=1e-6) and float(aux) == pytest.approx(ref_aux, rel=1e-6)
    assert float(kl) == pytest.approx(ref_kl, rel=1e-5)
    for group in GROUPS:
        assert group_rel(grads, ref_grads, group) < 1e-5, group
    assert picks.shape == (3, 3, T, 3)  # (batch, layers, T, k)
    program_picks, _ = kv.KeyeVL2(F32).picks_and_selection(params, tokens, seg)
    np.testing.assert_array_equal(np.sort(np.asarray(picks).transpose(1, 0, 2, 3), axis=-1),
                                  np.sort(np.asarray(program_picks), axis=-1))
    # handed its own selection the reference finds nothing to report
    assert len(reports) == 3 and len(reports[0]) == 3
    assert all(int(r["differ"]) == 0 and int(r["outside_allowed"]) == 0 and float(r["distance_max"]) == 0.0
               for seq in reports for r in seq)
    # one key swapped for an unselected one: two pairs differ, and their scores' distance from the threshold shows
    s0 = np.array(selection[0][0])
    row = 49  # the 30th token of its document keeps 24 of 30 keys
    chosen, free = np.flatnonzero(s0[row]), np.flatnonzero(~s0[row][:row + 1] & (np.asarray(seg[0])[:row + 1] == seg[0][row]))
    s0[row, chosen[0]], s0[row, free[0]] = False, True
    swapped = [selection[0].at[0].set(jnp.asarray(s0)), *selection[1:]]
    reports = reference.loss_and_grads_by_layer(hf_of(F32), params, tokens, seg, F32.experts_held, selection=swapped)[3]
    assert int(reports[0][0]["differ"]) == 2 and 0 < float(reports[0][0]["distance_max"]) < 1
    assert float(reports[0][0]["deviations_max"]) > float(reports[0][0]["distance_max"])


def test_with_every_key_kept_the_layer_is_packed_causal_attention_and_the_target_its_heads_mean():
    """``topk >= T``: the selection is the causal mask inside the document, the
    selected attention is ``ops/attention.py::packed_causal_attention``'s
    arithmetic bit for bit, and the indexer's target is the dense mean of the
    heads' probabilities."""
    rng = np.random.default_rng(2)
    n = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q, k, v = n(2, T, 4, 16), n(2, T, 2, 16), n(2, T, 2, 16)
    q_idx, k_idx, w = n(2, T, 4, 8), n(2, T, 8), n(2, T, 4)
    seg = jnp.asarray(batch(rows=(0, 2))[1])
    got = jax.jit(functools.partial(sparse.sparse_attention, topk=T, scale=0.25, index_scale=0.2, q_block=32,
                                    with_mask=True))(q, k, v, q_idx, k_idx, w, seg)
    np.testing.assert_array_equal(np.asarray(got.mask), np.asarray(sparse.allowed_pairs(seg)))
    wanted = jax.jit(lambda q, k, v, seg: attention.packed_causal_attention(q, k, v, seg, 0.25, 32))(q, k, v, seg)
    np.testing.assert_array_equal(np.asarray(got.out), np.asarray(wanted))
    assert float(got.tied) == 0 and float(got.selected) == float(jnp.sum(sparse.allowed_pairs(seg)))
    scores = jnp.einsum("bqhd,bshd->bhqs", q, jnp.repeat(k, 2, axis=2)) * 0.25
    dense = jnp.mean(jax.nn.softmax(jnp.where(sparse.allowed_pairs(seg)[:, None], scores, -jnp.inf), axis=-1), axis=1)
    probs = sparse.selected_attention(q, k, v, got.mask, 0.25, 32)[1]
    np.testing.assert_allclose(np.asarray(probs), np.asarray(dense), rtol=2e-5, atol=1e-7)
    index = sparse.index_scores(q_idx, k_idx, w, 0.2, 32)
    assert float(got.kl) == pytest.approx(float(sparse.indexer_kl(index, got.mask, dense, 32)), rel=1e-5)


def test_no_gradient_of_the_language_model_loss_reaches_the_indexer_and_none_of_its_loss_leaves_it(reference_step):
    params, tokens, seg = reference_step[:3]
    only_lm = kv.KeyeVL2(dataclasses.replace(F32, indexer_loss_coef=0.0, router_aux_loss_coef=0.0))
    grads = jax.jit(jax.grad(lambda p: only_lm.loss(p, tokens, seg)[0]))(params)
    assert all(float(jnp.max(jnp.abs(x))) == 0.0 for x in jax.tree.leaves(grads["indexer"]))
    assert all(float(jnp.max(jnp.abs(x))) > 0.0 for g in GROUPS if g != "indexer" for x in jax.tree.leaves(grads[g]))
    only_kl = jax.jit(jax.grad(lambda p: kv.KeyeVL2(F32).loss(p, tokens, seg)[1]["dsa/kl_loss"]))(params)
    assert all(float(jnp.max(jnp.abs(x))) > 0.0 for x in jax.tree.leaves(only_kl["indexer"]))
    assert all(float(jnp.max(jnp.abs(x))) == 0.0 for g in GROUPS if g != "indexer" for x in jax.tree.leaves(only_kl[g]))


def test_a_document_packed_behind_others_selects_inside_itself_and_gets_the_logits_it_gets_alone():
    params = seeded()
    rng = np.random.default_rng(4)
    document = rng.integers(0, 128, (1, 30)).astype(np.int32)
    model = kv.KeyeVL2(dataclasses.replace(F32, attention_q_block=16))
    alone = jax.jit(model.apply)({"params": params}, document, np.zeros((1, 30), np.int32))
    tokens = np.concatenate([rng.integers(0, 128, (1, 20)).astype(np.int32), document,
                             rng.integers(0, 128, (1, 14)).astype(np.int32)], axis=1)
    seg = np.repeat(np.arange(3), [20, 30, 14])[None].astype(np.int32)
    packed = jax.jit(model.apply)({"params": params}, tokens, seg)
    np.testing.assert_allclose(np.asarray(packed[:, 20:50]), np.asarray(alone), rtol=2e-5, atol=2e-5)
    _, selection = jax.jit(model.picks_and_selection)(params, tokens, seg)
    same_document = seg[0][:, None] == seg[0][None, :]
    assert not bool(jnp.any(selection & ~jnp.asarray(same_document & np.tri(T, dtype=bool))))
    assert int(selection[0, 0, 49].sum()) == 24 and int(selection[0, 0, 25].sum()) == 6


def test_unequal_temporal_height_and_width_ids_turn_the_three_sections():
    """A token whose three ids differ (an image patch) turns each run of
    frequency pairs by its own id: the program follows the reference, and
    moving one id moves the logits."""
    params, (tokens, seg) = seeded(), batch(rows=(2,))
    rng = np.random.default_rng(6)
    ids = np.broadcast_to(np.arange(T), (3, 1, T)).copy()
    ids[1, 0, 10:40] = rng.integers(0, 8, 30)
    ids[2, 0, 10:40] = rng.integers(0, 8, 30)
    model = kv.KeyeVL2(F32)
    apply = jax.jit(lambda ids: model.apply({"params": params}, tokens, seg, position_ids=ids))
    got = apply(jnp.asarray(ids))
    wanted = reference.forward(hf_of(F32), params, tokens, seg, F32.experts_held, position_ids=jnp.asarray(ids))[0]
    assert rel(got, wanted) < 2e-6
    text = apply(kv.text_positions(jnp.asarray(seg)))
    assert rel(got, text) > 1e-3
    for section in (1, 2):  # each of the two spatial ids alone
        one = np.broadcast_to(np.arange(T), (3, 1, T)).copy()
        one[section] = ids[section]
        assert rel(apply(jnp.asarray(one)), text) > 1e-4


def _layer_input(seed=3):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(2, T, F32.hidden_size)), jnp.float32)


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """An 8-way split of the 16 experts: the routed parts of the eight chips
    summed equal the reference's whole layer (``held`` = all 16); nothing is
    computed alike on every chip here (no shared expert)."""
    whole = dataclasses.replace(F32, experts_held=tuple(range(16)))
    params = seeded(whole)
    router, experts = (params[g]["layer_1"] for g in ("router", "experts"))
    u = _layer_input()
    with jax.default_matmul_precision("highest"):
        total = 0.0
        for chip in range(8):
            held = (2 * chip, 2 * chip + 1)
            mine = jax.tree.map(lambda w: w[jnp.asarray(held)], experts)
            out, (counts, _, rows, _) = kv._moe(dataclasses.replace(F32, experts_held=held), router, mine, u)
            assert rows.shape == (2,) and int(counts.sum()) == 2 * T * 3
            total = total + out
        uncut = jnp.stack([reference.moe(hf_of(whole), router, experts, x, whole.experts_held)[0] for x in u])
    assert rel(total, uncut) < 2e-6
    # and one share alone is the reference's partial sum for that share
    with jax.default_matmul_precision("highest"):
        held = (4, 5)
        mine = jax.tree.map(lambda w: w[jnp.asarray(held)], experts)
        out = kv._moe(dataclasses.replace(F32, experts_held=held), router, mine, u)[0]
        partial = jnp.stack([reference.moe(hf_of(whole), router, mine, x, held)[0] for x in u])
    assert rel(out, partial) < 2e-6 and rel(out, uncut) > 0.05


def test_a_tokens_weights_add_up_to_one_whoever_holds_its_experts():
    rng = np.random.default_rng(1)
    u, gate = jnp.asarray(rng.normal(size=(50, 16)), jnp.float32), jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    plain, renormalised = moe.route(u, gate, 3), moe.route_renormalised(u, gate, 3)
    np.testing.assert_array_equal(np.asarray(plain.picks), np.asarray(renormalised.picks))
    np.testing.assert_allclose(np.asarray(renormalised.weights.sum(-1)), 1.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(renormalised.weights * plain.weights.sum(-1, keepdims=True)),
                               np.asarray(plain.weights), rtol=1e-6)


def test_the_first_half_of_the_vocabulary_is_a_smaller_vocabulary():
    """A sliced vocabulary (the first rows of the embedding and of the untied
    head): on ids of the slice the hidden states are the whole model's and the
    logits are its logits over the slice."""
    whole = dataclasses.replace(F32, vocab_size=256)
    params = seeded(whole)
    sliced = dict(params, embed={"embedding": params["embed"]["embedding"][:128]},
                  head={"rows": params["head"]["rows"][:128]})
    tokens, seg = batch()
    h_whole = kv.hidden_states(whole, params, tokens, seg)[0]
    h_slice = kv.hidden_states(F32, sliced, tokens, seg)[0]
    np.testing.assert_array_equal(np.asarray(h_whole), np.asarray(h_slice))
    np.testing.assert_allclose(np.asarray(kv.logits_of(whole, params, h_whole))[..., :128],
                               np.asarray(kv.logits_of(F32, sliced, h_slice)), rtol=1e-6, atol=1e-6)


# ---- the configuration ----------------------------------------------------------


def _published() -> dict:
    with open(CONFIG_FILE) as f:
        return json.load(f)


def test_the_benchmarks_configuration_holds_659_190_016_parameters():
    """The cut of ISSUE 38 by ``eval_shape``: nothing is allocated."""
    hf = _published()
    config = kv.KeyeVL2Config.from_hf(hf)
    assert (config.experts_total, config.experts_held, config.num_experts_per_tok) == (128, tuple(range(16)), 8)
    assert (config.indexer_num_heads, config.indexer_head_dim, config.indexer_topk) == (16, 64, 2048)
    assert config.mrope_section == (16, 24, 24) and config.indexer_sections == (8, 12, 12) and config.rope_theta == 1e7
    shapes = jax.eval_shape(lambda key: kv.init_params(config, key), jax.random.key(0))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    by_layer = lambda group, i: count(shapes[group][f"layer_{i}"])
    held = hf["parameters_held"]
    assert by_layer("attention", 0) == 18_874_624 == held["attention"]
    assert by_layer("indexer", 3) == 2_261_120 == held["indexer"] and by_layer("router", 5) == 262_144 == held["router"]
    assert by_layer("experts", 1) == 75_497_472 == held["routed_experts_of_a_layer"] and by_layer("norms", 2) == 4096
    assert sum(by_layer(g, 0) for g in ("attention", "indexer", "router", "experts", "norms")) == 96_899_456
    assert count(shapes["embed"]) + count(shapes["head"]) + shapes["norms"]["final"].size == 77_793_280
    assert count(shapes) == 659_190_016 == held["total"]
    assert sorted(shapes) == sorted(GROUPS)
    assert shapes["router"]["layer_3"]["gate"].shape == (2048, 128)  # the router keeps its published width
    assert shapes["experts"]["layer_3"]["gate_up"].shape == (16, 2048, 2 * 768)
    assert shapes["indexer"]["layer_0"]["q"].shape == (2048, 16 * 64) and shapes["indexer"]["layer_0"]["k"].shape == (2048, 64)


@pytest.mark.parametrize("key,value", [
    ("use_sliding_window", True), ("mlp_only_layers", [0]), ("decoder_sparse_step", 2), ("tie_word_embeddings", True),
    ("attention_bias", True), ("hidden_act", "gelu"), ("norm_topk_prob", False),
    ("rope_scaling", {"mrope_section": [16, 24, 24], "rope_type": "yarn"}),
    ("rope_scaling", {"mrope_section": [16, 24, 25], "rope_type": "default"}),
    ("sa_config", {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 2, "topk": 2048}),
    ("experts_held", [0, 1, 2]), ("experts_held", list(range(15)) + [128]),
])
def test_from_hf_refuses_what_it_does_not_compute(key, value):
    with pytest.raises(ValueError, match=key.split("_")[0]):
        kv.KeyeVL2Config.from_hf(dict(_published(), **{key: value}))


def test_an_uncut_configuration_holds_every_expert():
    hf = {k: v for k, v in _published().items() if k not in ("experts_held", "num_experts_total")}
    config = kv.KeyeVL2Config.from_hf(dict(hf, num_experts=128))
    assert config.experts_total == 128 and config.experts_held == tuple(range(128))


# ---- the normal path ----------------------------------------------------------


def test_the_model_is_picked_by_model_type_or_preset():
    assert isinstance(build_language_model("tiny-keye"), kv.KeyeVL2)
    assert isinstance(build_language_model(CONFIG_FILE), kv.KeyeVL2)
    assert build_language_model(_published(), dtype=jnp.float32).config.dtype == jnp.float32
    with pytest.raises(ValueError, match="model_type 'llama'"):
        build_language_model(dict(_published(), model_type="llama"))


def _state_and_batch():
    model = kv.KeyeVL2(kv.TINY)
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
    assert {s for s, d in filed if d == "bwd"} >= {"embed", "attention", "moe", "lm_head", "loss"}
    assert not {"mamba", "mla", "mlp", "dense_mlp", "backbone", "heads"} & {s for s, _ in filed}
    # the first four names beneath ``attention`` are this model's (the last two, since PR 46, models/afmoe.py's)
    beneath = {"attention": STEP_SCOPES["attention"][:4], "moe": tuple(n for n in STEP_SCOPES["moe"] if n != "shared")}
    assert beneath["attention"] == ("indexer", "select", "attention_core", "indexer_loss")
    assert STEP_SCOPES["attention"][4:] == ("window_core", "full_core")
    for slice_, names in beneath.items():
        paths = {p for t, _, p in table.values() if t == slice_}
        for name in names:
            assert any(f"/{name}/" in p or p.endswith("/" + name) for p in paths), (slice_, name)


def test_one_step_through_the_train_step_logs_the_counters_and_every_groups_norm():
    from batchai_retinanet_horovod_coco_tpu.obs.numerics import NumericsConfig

    model, state, arrays = _state_and_batch()
    step = make_train_step(model, (2, T), None, task=LMTask(), donate_state=False,
                           numerics=NumericsConfig(enabled=True))
    new_state, metrics = step(state, arrays)
    assert {"loss", "tokens_counted", "grad_norm", "moe/aux_loss", "moe/rows_held", "moe/rows_max_expert",
            "moe/rows_min_expert", "dsa/kl_loss", "dsa/selected_share", "dsa/threshold_ties",
            *(f"gnorm/{g}" for g in GROUPS)} <= set(metrics)
    assert int(new_state.step) == 1 and np.isfinite(float(metrics["loss"]))
    assert float(metrics["loss"]) > float(metrics["dsa/kl_loss"]) > float(metrics["moe/aux_loss"]) > 0
    assert 0 < float(metrics["dsa/selected_share"]) < 1 and float(metrics["gnorm/indexer"]) > 0
    assert LMTask().run_meta(model, (2, T)) == {
        "attention_lowering": "xla", "attention_block_skip": "causal", "dsa_topk": 24, "moe_lowering": "xla",
        "moe_rows_lowering": "xla", "experts_held": 4, "experts_total": 16, **NOTHING_MORE}
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):  # the cell's model and bucket: the kernels
        published = build_language_model(CONFIG_FILE)
        meta = published.run_meta((1, 16384))
        assert "attention_residuals" not in published.run_meta((1, 16384 + 1024))  # no whole runs: the xla lowering
    assert meta == {
        "attention_lowering": "kernel", "attention_block_skip": "causal", "attention_residuals": "kept",
        "dsa_topk": 2048, "moe_lowering": "kernel", "moe_rows_lowering": "kernel", "experts_held": 16,
        "experts_total": 128, **NOTHING_MORE}

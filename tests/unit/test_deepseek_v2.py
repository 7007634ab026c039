"""DeepSeek-V2 on a share of its experts (models/deepseek_v2.py, ops/moe.py,
ops/rope.py, ops/attention.py) against the plain float32 reference
(benchmark/reference/deepseek_v2.py) on seeded weights at the tiny size: one
dense and two expert layers at d = 64, 4 heads of 24 / 16, latent 32 + 8,
16 experts of width 32 of which 4 are held, 3 a token, vocabulary 128,
sequences of 64 tokens with 1-5 documents."""

import dataclasses
import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batchai_retinanet_horovod_coco_tpu.models import deepseek_v2 as ds
from batchai_retinanet_horovod_coco_tpu.models import lm_layers
from batchai_retinanet_horovod_coco_tpu.models.language import build_language_model
from batchai_retinanet_horovod_coco_tpu.ops import attention
from batchai_retinanet_horovod_coco_tpu.train import create_train_state
from batchai_retinanet_horovod_coco_tpu.train.optim import OptimizerConfig, make_optimizer
from batchai_retinanet_horovod_coco_tpu.train.step import STEP_SCOPES, make_train_step, scope_table
from batchai_retinanet_horovod_coco_tpu.train.task import LMTask
from benchmark.reference import deepseek_v2 as reference

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
T = 64
# what ``run_meta`` says of a recomputed layer's keeps where no device states a memory limit (the CPU)
NOTHING_MORE = {"layer_keeps": "attention_residuals,dsa_threshold", "mlp_gate_up_layers": 0, "mlp_gate_up_bytes": 0}
F32 = dataclasses.replace(ds.TINY, dtype=jnp.float32)
DOCS = ([20, 30, 14], [7, 57], [64], [5, 9, 21, 17, 12])  # documents per sequence
GROUPS = ("embed", "attention", "dense_mlp", "router", "experts", "shared", "norms", "head")


def hf_of(config: ds.DeepseekV2Config) -> dict:
    """The published keys the reference reads, for a program configuration."""
    keys = ("vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok", "first_k_dense_replace",
            "routed_scaling_factor", "aux_loss_alpha", "rms_norm_eps", "rope_theta")
    scaling = dict(type="yarn", factor=config.rope_factor, beta_fast=config.rope_beta_fast,
                   beta_slow=config.rope_beta_slow, mscale=config.rope_mscale,
                   mscale_all_dim=config.rope_mscale_all_dim,
                   original_max_position_embeddings=config.rope_original_positions)
    return dict({k: getattr(config, k) for k in keys}, rope_scaling=scaling,
                n_routed_experts=len(config.experts_held), n_routed_experts_total=config.experts_total)


def seeded(config=F32, seed=0):
    """Parameters with every leaf moved off its initial value (norm scales are 1 as initialised)."""
    params = ds.init_params(config, jax.random.key(seed))
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


def group_rel(got, wanted, group):
    a, b = jax.tree.leaves(got[group]), jax.tree.leaves(wanted[group])
    return float(np.sqrt(sum(jnp.sum(jnp.square(x - y)) for x, y in zip(a, b)) / sum(jnp.sum(jnp.square(y)) for y in b)))


@pytest.fixture(scope="module")
def reference_step():
    params, (tokens, seg) = seeded(), batch()
    (loss, aux), grads = reference.loss_and_grads(hf_of(F32), params, tokens, seg, F32.experts_held)
    return params, tokens, seg, float(loss), float(aux), grads


@pytest.fixture(scope="module")
def program_step(reference_step):
    params, tokens, seg = reference_step[:3]
    model = ds.DeepseekV2(F32)
    (loss, scalars), grads = jax.jit(jax.value_and_grad(lambda p: model.loss(p, tokens, seg), has_aux=True))(params)
    return float(loss), {k: float(v) for k, v in scalars.items()}, grads


def test_logits_loss_and_auxiliary_loss_equal_the_references(reference_step, program_step):
    params, tokens, seg, ref_loss, ref_aux, _ = reference_step
    logits = ds.DeepseekV2(F32).apply({"params": params}, tokens, seg)
    assert rel(logits, reference.forward(hf_of(F32), params, tokens, seg, F32.experts_held)) < 2e-6
    loss, scalars, _ = program_step
    assert loss == pytest.approx(ref_loss, rel=2e-6) and scalars["loss"] == loss
    assert scalars["moe/aux_loss"] == pytest.approx(ref_aux, rel=2e-6) and 0 < ref_aux < 0.01 * ref_loss
    # the counters: rows routed to the 4 held of 16 experts in 2 expert layers of 3 x 64 tokens x 3 picks
    assert 0 < scalars["moe/rows_min_expert"] <= scalars["moe/rows_max_expert"] <= 3 * T
    assert 0.1 < scalars["moe/rows_held"] / (2 * 3 * T * 3) < 0.5


@pytest.mark.parametrize("group", GROUPS)
def test_every_groups_gradient_equals_the_references(reference_step, program_step, group):
    assert group_rel(program_step[2], reference_step[5], group) < 5e-6


def test_bfloat16_compute_stays_near_the_reference(reference_step):
    """bfloat16 activations and operands: 8 bits of mantissa through three
    layers.  The loss is ln 128 plus a little and hardly feels it.  A
    gradient group feels it by about 1%, and the router's and the routed
    experts' by more: a token whose third and fourth scores lie within the
    rounding of the layer's input picks another expert than the float32
    reference (top-k is discontinuous), which changes whole rows of both."""
    params, tokens, seg, ref_loss, ref_aux, ref_grads = reference_step
    model = ds.DeepseekV2(ds.TINY)
    (loss, scalars), grads = jax.value_and_grad(lambda p: model.loss(p, tokens, seg), has_aux=True)(params)
    assert float(loss) == pytest.approx(ref_loss, rel=2e-3)
    assert float(scalars["moe/aux_loss"]) == pytest.approx(ref_aux, rel=2e-2)
    for group in GROUPS:
        assert group_rel(grads, ref_grads, group) < (0.25 if group in ("router", "experts") else 0.03), group


def test_the_reference_by_layer_and_in_blocks_equals_the_reference_direct(reference_step):
    params, tokens, seg, ref_loss, ref_aux, ref_grads = reference_step
    (loss, aux), grads, picks = reference.loss_and_grads_by_layer(hf_of(F32), params, tokens, seg, F32.experts_held,
                                                                  head_block=2)
    assert float(loss) == pytest.approx(ref_loss, rel=1e-6) and float(aux) == pytest.approx(ref_aux, rel=1e-6)
    for group in GROUPS:
        assert group_rel(grads, ref_grads, group) < 1e-5, group
    assert picks.shape == (3, 2, T, 3)  # (batch, expert layers, T, k)
    np.testing.assert_array_equal(np.sort(np.asarray(picks).transpose(1, 0, 2, 3), axis=-1),
                                  np.sort(np.asarray(ds.DeepseekV2(F32).picks(params, tokens, seg)), axis=-1))


def _layer_input(seed=3):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(2, T, F32.hidden_size)), jnp.float32)


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """An 8-way split of the 16 experts: the routed parts of the eight chips
    summed, plus the shared expert counted ONCE, equal the reference's whole
    layer (``held`` = all 16)."""
    whole = dataclasses.replace(F32, experts_held=tuple(range(16)))
    params = seeded(whole)
    router, experts, shared = (params[g]["layer_1"] for g in ("router", "experts", "shared"))
    u = _layer_input()
    with jax.default_matmul_precision("highest"):
        shared_part = lm_layers.gated_mlp(lambda x: x, shared, u)
        total = shared_part
        for chip in range(8):
            held = (2 * chip, 2 * chip + 1)
            share = dataclasses.replace(F32, experts_held=held)
            mine = jax.tree.map(lambda w: w[jnp.asarray(held)], experts)
            out, (_, rows, _) = ds._moe(share, router, mine, shared, u)
            assert rows.shape == (2,)
            total = total + (out - shared_part)
        uncut = jnp.stack([reference.moe(hf_of(whole), router, experts, shared, x, whole.experts_held)[0] for x in u])
    assert rel(total, uncut) < 2e-6
    # and one share alone is the reference's partial sum for that share
    with jax.default_matmul_precision("highest"):
        held = (4, 5)
        mine = jax.tree.map(lambda w: w[jnp.asarray(held)], experts)
        out = ds._moe(dataclasses.replace(F32, experts_held=held), router, mine, shared, u)[0]
        partial = jnp.stack([reference.moe(hf_of(whole), router, mine, shared, x, held)[0] for x in u])
    assert rel(out, partial) < 2e-6 and rel(out, uncut) > 0.05


@pytest.mark.parametrize("to", ["held", "absent"])
def test_the_model_layer_drops_no_token_under_a_forced_router(to):
    """A router forced to send every token's three picks to held experts (the
    buffer full: three times a chip's average share), and one forced to
    send none: both match the reference."""
    params = seeded()
    router, experts, shared = (params[g]["layer_1"] for g in ("router", "experts", "shared"))
    gate = np.zeros((F32.hidden_size, F32.experts_total), np.float32)
    gate[0, [1, 2, 3] if to == "held" else [9, 10, 11]] = [40.0, 41.0, 42.0]
    router = {"gate": jnp.asarray(gate)}
    u = _layer_input().at[..., 0].set(1.0)
    with jax.default_matmul_precision("highest"):
        out, (_, rows, picks) = ds._moe(F32, router, experts, shared, u)
        wanted = jnp.stack([reference.moe(hf_of(F32), router, experts, shared, x, F32.experts_held)[0] for x in u])
    assert int(rows.sum()) == (2 * T * 3 if to == "held" else 0)
    assert rel(out, wanted) < 2e-6


def test_the_first_half_of_the_vocabulary_is_a_smaller_vocabulary():
    """A sliced vocabulary (the first rows of the embedding and of the
    untied head): on ids of the slice the hidden states are the whole
    model's and the logits are its logits over the slice."""
    whole = dataclasses.replace(F32, vocab_size=256)
    params = seeded(whole)
    sliced = dict(params, embed={"embedding": params["embed"]["embedding"][:128]},
                  head={"rows": params["head"]["rows"][:128]})
    tokens, seg = batch()
    h_whole = ds.hidden_states(whole, params, tokens, seg)[0]
    h_slice = ds.hidden_states(F32, sliced, tokens, seg)[0]
    np.testing.assert_array_equal(np.asarray(h_whole), np.asarray(h_slice))
    np.testing.assert_allclose(np.asarray(ds.logits_of(whole, params, h_whole))[..., :128],
                               np.asarray(ds.logits_of(F32, sliced, h_slice)), rtol=1e-6, atol=1e-6)


def test_a_document_packed_behind_others_gets_the_logits_it_gets_alone():
    """Positions restart at every document and attention is masked to it:
    the 30-token document of row 0 (behind 20 tokens of another) alone in a
    sequence of its own."""
    params = seeded()
    tokens, seg = batch(rows=(0,))
    model = ds.DeepseekV2(F32)
    packed = model.apply({"params": params}, tokens, seg)[0, 20:50]
    alone = model.apply({"params": params}, tokens[:, 20:50], np.zeros((1, 30), np.int32))[0]
    assert rel(packed, alone) < 2e-6


def _logits_with_positions(positions_of):
    params = seeded()
    tokens, seg = batch(rows=(0,))
    with mock.patch.object(ds.rope, "document_positions", positions_of):
        jax.clear_caches()  # the layers are jax.checkpoint-ed and their traces cached by function
        out = ds.DeepseekV2(F32).apply({"params": params}, tokens, seg)
    jax.clear_caches()
    return out


def test_rotary_scores_depend_on_distance_alone_so_only_a_mismatch_of_positions_shows():
    """Rotary attention is invariant under a shift of all positions of a
    document: positions that run on across document boundaries give the SAME
    logits (to the rounding of larger float32 angles), so no comparison of
    outputs can see that fault; restarting keeps the angles small, and is
    what a caller of the published code passes as ``position_ids``.  What
    does show: positions that are not the token's own index in its document
    (here: every second position skipped)."""
    restarted = _logits_with_positions(ds.rope.document_positions)
    run_on = _logits_with_positions(lambda s: jnp.broadcast_to(jnp.arange(s.shape[1]), s.shape))
    assert rel(run_on, restarted) < 5e-6
    stretched = _logits_with_positions(lambda s: 2 * jnp.broadcast_to(jnp.arange(s.shape[1]), s.shape))
    assert rel(stretched, restarted) > 1e-3


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6), (jnp.bfloat16, 2e-2)], ids=["float32", "bfloat16"])
def test_attention_takes_a_value_head_narrower_than_the_query_head(dtype, tol):
    """Heads of 24 (queries, keys) and 16 (values), as latent attention's 192
    and 128: the blocked kernel (interpret mode) and the XLA blocks against a
    dense masked softmax, forward and gradients."""
    rng = np.random.default_rng(7)
    t, heads = 256, 4
    q, k = (jnp.asarray(rng.normal(size=(1, t, heads, 24)), dtype) for _ in range(2))
    v = jnp.asarray(rng.normal(size=(1, t, heads, 16)), dtype)
    seg = jnp.asarray(np.repeat([0, 1, 2], [100, 28, 128])[None], jnp.int32)
    scale = 24 ** -0.5

    def dense(q, k, v):
        scores = scale * jnp.einsum("bqhd,bshd->bhqs", q.astype(jnp.float32), k.astype(jnp.float32))
        pos = jnp.arange(t)
        mask = (pos[:, None] >= pos[None, :]) & (seg[0][:, None] == seg[0][None, :])
        return jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1),
                          v.astype(jnp.float32))

    def kernel(q, k, v):
        with mock.patch.object(attention, "BLOCK_SIZES", {name: 128 for name in attention.BLOCK_SIZES}):
            return attention._kernel_path(q, k, v, seg, scale, interpret=True)

    xla = lambda q, k, v: attention._xla_path(q, k, v, seg, scale, 64)
    g = jnp.asarray(rng.normal(size=(1, t, heads, 16)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, want_vjp = jax.vjp(dense, q, k, v)
        for path in (kernel, xla):
            out, vjp = jax.vjp(lambda *a: path(*a).astype(jnp.float32), q, k, v)
            assert out.shape == (1, t, heads, 16) and rel(out, want) < tol, path
            for a, b in zip(vjp(g), want_vjp(g)):
                assert rel(a.astype(jnp.float32), b.astype(jnp.float32)) < 3 * tol, path


# ---- the published configuration ---------------------------------------------

CONFIG_FILE = os.path.join(REPO, "benchmark", "configs", "deepseek-v2-lite-ep8.json")


def _published() -> dict:
    with open(CONFIG_FILE) as f:
        return json.load(f)


def test_the_benchmarks_configuration_holds_635_466_752_parameters():
    """The cut of ISSUE 30 by ``eval_shape``: nothing is allocated."""
    hf = _published()
    config = ds.DeepseekV2Config.from_hf(hf)
    assert (config.experts_total, config.experts_held, config.num_experts_per_tok) == (64, tuple(range(8)), 6)
    assert config.softmax_scale == pytest.approx(192 ** -0.5 * 1.2608038 ** 2, rel=1e-6) and config.rotary_scale == 1.0
    shapes = jax.eval_shape(lambda key: ds.init_params(config, key), jax.random.key(0))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    by_layer = lambda group, i: count(shapes[group].get(f"layer_{i}", {}))
    assert by_layer("attention", 0) == 13_763_072 == hf["parameters_held"]["attention"]
    assert by_layer("attention", 0) + by_layer("dense_mlp", 0) + by_layer("norms", 0) == 81_007_104
    outside = by_layer("attention", 1) + by_layer("norms", 1) + by_layer("router", 1) + by_layer("shared", 1)
    assert (outside, by_layer("experts", 1)) == (31_199_744, 69_206_016)
    assert count(shapes["embed"]) + count(shapes["head"]) + shapes["norms"]["final"].size == 52_430_848
    assert count(shapes) == 635_466_752 == hf["parameters_held"]["total"]
    assert sorted(shapes) == sorted(GROUPS)
    assert shapes["router"]["layer_3"]["gate"].shape == (2048, 64)  # the router keeps its published width
    assert shapes["experts"]["layer_3"]["gate_up"].shape == (8, 2048, 2 * 1408)
    assert not shapes["router"].get("layer_0") and list(shapes["dense_mlp"]) == ["layer_0"]


@pytest.mark.parametrize("key,value", [
    ("scoring_func", "sigmoid"), ("topk_method", "group_limited_greedy"), ("n_group", 8), ("topk_group", 3),
    ("moe_layer_freq", 2), ("norm_topk_prob", True), ("seq_aux", False), ("q_lora_rank", 1536),
    ("attention_bias", True), ("tie_word_embeddings", True), ("hidden_act", "gelu"), ("num_key_value_heads", 4),
    ("rope_scaling", None), ("rope_scaling", {"type": "linear", "factor": 4}), ("experts_held", [0, 1, 2]),
    ("experts_held", [0, 1, 2, 3, 4, 5, 6, 64]),
])
def test_from_hf_refuses_what_it_does_not_compute(key, value):
    with pytest.raises(ValueError, match=key.split("_")[0]):
        ds.DeepseekV2Config.from_hf(dict(_published(), **{key: value}))


def test_an_uncut_configuration_holds_every_expert():
    hf = {k: v for k, v in _published().items() if k not in ("experts_held", "n_routed_experts_total")}
    config = ds.DeepseekV2Config.from_hf(dict(hf, n_routed_experts=64))
    assert config.experts_total == 64 and config.experts_held == tuple(range(64))


# ---- the normal path ----------------------------------------------------------


def test_the_model_is_picked_by_model_type_or_preset(tmp_path):
    from batchai_retinanet_horovod_coco_tpu.models import granite_hybrid

    assert isinstance(build_language_model("tiny-moe"), ds.DeepseekV2)
    assert isinstance(build_language_model("tiny"), granite_hybrid.GraniteHybrid)
    assert isinstance(build_language_model(CONFIG_FILE), ds.DeepseekV2)
    assert build_language_model(_published(), dtype=jnp.float32).config.dtype == jnp.float32
    granite = os.path.join(REPO, "benchmark", "configs", "granite-4.0-h-micro-p1.json")
    assert isinstance(build_language_model(granite), granite_hybrid.GraniteHybrid)
    with pytest.raises(ValueError, match="model_type 'llama'"):
        build_language_model(dict(_published(), model_type="llama"))


def _state_and_batch():
    model = ds.DeepseekV2(ds.TINY)
    tx = make_optimizer(OptimizerConfig(optimizer="adamw", schedule="constant", warmup_steps=0))[0]
    state = create_train_state(model, tx, (1, 8), jax.random.key(0), example_dtype=LMTask.example_dtype)
    tokens, seg = batch(rows=(0, 3))
    return model, state, {"tokens": jnp.asarray(tokens), "segment_ids": jnp.asarray(seg)}


def test_the_models_scopes_reach_the_compiled_step_through_recomputation():
    """Forward, recomputed forward and backward keep the layer's scope and
    what lies beneath it; nothing of granite's or detection's is there."""
    model, state, arrays = _state_and_batch()
    compiled = make_train_step(model, (2, T), None, task=LMTask(), donate_state=False).lower(state, arrays).compile()
    table = scope_table(compiled)
    filed = {(s, d) for s, d, _ in table.values()}
    for s in (*model.scopes, "optimizer"):
        assert (s, "fwd") in filed, s
    assert {s for s, d in filed if d == "bwd"} >= {"embed", "mla", "dense_mlp", "moe", "lm_head", "loss"}
    assert not {"mamba", "attention", "mlp", "backbone", "heads"} & {s for s, _ in filed}
    for slice_ in ("mla", "moe"):
        paths = {p for t, _, p in table.values() if t == slice_}
        for name in STEP_SCOPES[slice_]:
            assert any(f"/{name}/" in p or p.endswith("/" + name) for p in paths), (slice_, name)


def test_one_step_through_the_train_step_logs_the_counters_and_every_groups_norm():
    from batchai_retinanet_horovod_coco_tpu.obs.numerics import NumericsConfig

    model, state, arrays = _state_and_batch()
    step = make_train_step(model, (2, T), None, task=LMTask(), donate_state=False,
                           numerics=NumericsConfig(enabled=True))
    new_state, metrics = step(state, arrays)
    assert {"loss", "tokens_counted", "grad_norm", "moe/aux_loss", "moe/rows_held", "moe/rows_max_expert",
            "moe/rows_min_expert", *(f"gnorm/{g}" for g in GROUPS)} <= set(metrics)
    assert int(new_state.step) == 1 and np.isfinite(float(metrics["loss"]))
    assert float(metrics["loss"]) > float(metrics["moe/aux_loss"]) > 0
    assert LMTask().run_meta(model, (2, T)) == {"attention_lowering": "xla", "moe_lowering": "xla",
                                                "moe_rows_lowering": "xla", "experts_held": 4, "experts_total": 16,
                                                **NOTHING_MORE}
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):  # the cell's model and bucket: the kernels
        meta = build_language_model(CONFIG_FILE).run_meta((2, 8192))
    assert meta == {"attention_lowering": "kernel", "attention_block_skip": "documents",
                    "attention_residuals": "kept", "moe_lowering": "kernel", "moe_rows_lowering": "kernel",
                    "experts_held": 8, "experts_total": 64, **NOTHING_MORE}

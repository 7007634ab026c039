"""Olmo-Hybrid (models/olmo_hybrid.py, ops/delta_rule.py) against the plain
float32 reference (benchmark/reference/olmo_hybrid.py: the recurrence token by
token) on seeded weights at the tiny size: one period of three gated-delta-rule
layers and a full attention layer at d = 64, 4 heads of key 8 / value 16, chunks of
8, vocabulary 128, sequences of 64 tokens with 1-5 documents."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batchai_retinanet_horovod_coco_tpu.models import lm_layers
from batchai_retinanet_horovod_coco_tpu.models import olmo_hybrid as oh
from batchai_retinanet_horovod_coco_tpu.models.language import build_language_model
from batchai_retinanet_horovod_coco_tpu.obs.numerics import NumericsConfig
from batchai_retinanet_horovod_coco_tpu.train import create_train_state
from batchai_retinanet_horovod_coco_tpu.train.optim import OptimizerConfig, decays, make_optimizer
from batchai_retinanet_horovod_coco_tpu.train.step import make_train_step
from batchai_retinanet_horovod_coco_tpu.train.task import LMTask
from benchmark.reference import olmo_hybrid as reference

T = 64
# what ``run_meta`` says of a recomputed layer's keeps where no device states a memory limit (the CPU)
NOTHING_MORE = {"layer_keeps": "attention_residuals,dsa_threshold", "mlp_gate_up_layers": 0, "mlp_gate_up_bytes": 0}
F32 = dataclasses.replace(oh.TINY, dtype=jnp.float32)
DOCS = ([20, 30, 14], [7, 57], [64], [5, 9, 21, 17, 12])  # documents per sequence
CONFIG_FILE = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark", "configs", "olmo-hybrid-7b-p1.json")


def hf_of(config: oh.OlmoHybridConfig) -> dict:
    """The published keys the reference reads, for a program configuration."""
    keys = ("vocab_size", "hidden_size", "num_attention_heads", "num_key_value_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim", "linear_allow_neg_eigval",
            "rms_norm_eps")
    return dict({k: getattr(config, k) for k in keys}, layer_types=list(config.layer_types),
                num_hidden_layers=len(config.layer_types), rope_parameters={"rope_theta": config.rope_theta})


def seeded(config=F32, seed=0):
    """Parameters with every leaf moved off its initial value (norm scales are 1
    as initialised)."""
    params = oh.init_params(config, jax.random.key(seed))
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    return jax.tree.unflatten(treedef, [x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])


def batch(rows=(0, 1, 3), vocab=128, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, vocab, (len(rows), T)).astype(np.int32)
    seg = np.stack([np.repeat(np.arange(len(DOCS[r])), DOCS[r]) for r in rows]).astype(np.int32)
    return tokens, seg


def program_loss(config, params, tokens, seg):
    return oh.OlmoHybrid(config).loss(params, tokens, seg)[0]


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def group_norm(tree):
    return float(jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(tree))))


@pytest.mark.parametrize("rope_theta", [None, 10000.0])
def test_logits_loss_and_every_gradient_leaf_equal_the_references(rope_theta):
    """As published (``rope_theta`` null: no rotation) and with a number."""
    config = dataclasses.replace(F32, rope_theta=rope_theta)
    params, (tokens, seg) = seeded(config), batch()
    logits = jax.jit(lambda p: oh.OlmoHybrid(config).apply({"params": p}, tokens, seg))(params)
    expected = jax.jit(lambda p: reference.forward(hf_of(config), p, tokens, seg))(params)
    assert float(jnp.max(jnp.abs(logits - expected))) < 2e-5 * float(jnp.max(jnp.abs(expected)))
    loss, grads = jax.jit(jax.value_and_grad(lambda p: program_loss(config, p, tokens, seg)))(params)
    ref_loss, ref_grads = jax.jit(lambda p: reference.loss_and_grads(hf_of(config), p, tokens, seg))(params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(ref_grads), strict=True):
        assert rel(g, r) < 5e-5, (jax.tree_util.keystr(path), rel(g, r))


def test_the_rotation_moves_the_logits_and_null_applies_none():
    params, (tokens, seg) = seeded(), batch()
    plain = oh.OlmoHybrid(F32).apply({"params": params}, tokens, seg)
    turned = oh.OlmoHybrid(dataclasses.replace(F32, rope_theta=10000.0)).apply({"params": params}, tokens, seg)
    assert rel(turned, plain) > 1e-3


def test_bfloat16_compute_stays_near_the_reference():
    params, (tokens, seg) = seeded(oh.TINY), batch()
    loss, grads = jax.jit(jax.value_and_grad(lambda p: program_loss(oh.TINY, p, tokens, seg)))(params)
    ref_loss, ref_grads = jax.jit(lambda p: reference.loss_and_grads(hf_of(oh.TINY), p, tokens, seg))(params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=2e-3)
    # every sublayer's output is normalised to 1 beside an embedding 0.05 wide: at d = 64 the rounding of the
    # bfloat16 residual stream is a twelfth of the rows it carries and adds 5% to the norms of the gradients that
    # pass through it (the cell at d = 3840 reads 0.1%: PERF.md section 6)
    for group in ("embed", "gdn", "attention", "mlp", "norms", "head"):
        assert group_norm(grads[group]) == pytest.approx(group_norm(ref_grads[group]), rel=8e-2), group


def test_the_reference_by_layer_and_in_blocks_equals_the_reference_direct():
    params, (tokens, seg) = seeded(), batch(rows=(0, 3))
    hf = hf_of(F32)
    loss, grads = jax.jit(lambda p: reference.loss_and_grads(hf, p, tokens, seg))(params)
    by_layer = reference.loss_and_grads_by_layer(hf, params, tokens, seg, every=8, scan_block=16, head_block=2)
    assert float(by_layer[0]) == pytest.approx(float(loss), rel=1e-6)
    for g, r in zip(jax.tree.leaves(by_layer[1]), jax.tree.leaves(grads), strict=True):
        assert float(jnp.max(jnp.abs(g - r))) <= 1e-5 * float(jnp.max(jnp.abs(r))) + 1e-9
    assert set(by_layer[2]) == {oh.ALPHA_MEAN, oh.BETA_MEAN, oh.STATE_NORM_MAX}


def test_the_steps_counters_are_the_references_own():
    """``gdn/alpha_mean``, ``gdn/beta_mean`` and ``gdn/state_norm_max`` of the
    program's loss against the reference's readings of its recurrence."""
    params, (tokens, seg) = seeded(), batch()
    _, scalars = jax.jit(lambda p: oh.OlmoHybrid(F32).loss(p, tokens, seg))(params)
    _, _, want = reference.loss_and_grads_by_layer(hf_of(F32), params, tokens, seg, every=F32.delta_rule_chunk)
    for name in (oh.ALPHA_MEAN, oh.BETA_MEAN, oh.STATE_NORM_MAX):
        assert float(scalars[name]) == pytest.approx(want[name], rel=1e-5), name
    assert 0 < want[oh.ALPHA_MEAN] < 1 and 0 < want[oh.BETA_MEAN] < 2 and want[oh.STATE_NORM_MAX] > 0


def test_one_adamw_step_through_the_train_step_equals_the_recipe_on_the_references_gradient():
    """The shared step (jit, norm, clip chain, update) with the LM task against
    AdamW's first step written out on the reference's gradient."""
    lr, wd, eps, clip = 3e-3, 0.1, 1e-12, 0.05
    tx, _ = make_optimizer(OptimizerConfig(optimizer="adamw", base_lr=lr, schedule="constant", warmup_steps=0,
                                           weight_decay=wd, adam_b2=0.95, adam_eps=eps, clip_global_norm=clip))
    model, task = oh.OlmoHybrid(F32), LMTask()
    state = create_train_state(model, tx, (1, 8), jax.random.key(3), example_dtype=task.example_dtype)
    state = state.replace(params=seeded(seed=3))
    tokens, seg = batch()
    step = make_train_step(model, tokens.shape, None, task=task, donate_state=False,
                           numerics=NumericsConfig(enabled=True))
    new_state, metrics = step(state, {"tokens": tokens, "segment_ids": seg})
    ref_loss, ref_grads = jax.jit(lambda p: reference.loss_and_grads(hf_of(F32), p, tokens, seg))(state.params)
    norm = group_norm(ref_grads)
    assert norm > clip  # the clip acts
    assert float(metrics["loss"]) == pytest.approx(float(ref_loss), rel=1e-6)
    assert float(metrics["grad_norm"]) == pytest.approx(norm, rel=1e-5)
    for group in ("embed", "gdn", "attention", "mlp", "norms", "head"):
        assert float(metrics[f"gnorm/{group}"]) == pytest.approx(group_norm(ref_grads[group]), rel=1e-4), group
    assert {oh.ALPHA_MEAN, oh.BETA_MEAN, oh.STATE_NORM_MAX} <= set(metrics)
    mask = decays(state.params)
    assert {k for k, v in mask["gdn"]["layer_0"].items() if v} == {"in_proj", "conv_w", "out_proj"}
    assert {k for k, v in mask["attention"]["layer_3"].items() if v} == {"q", "k", "v", "o"}
    assert mask["embed"]["embedding"] and mask["head"]["rows"] and not any(jax.tree.leaves(mask["norms"]))
    for (path, p0), p1, g, decayed in zip(jax.tree_util.tree_leaves_with_path(state.params),
                                          jax.tree.leaves(new_state.params), jax.tree.leaves(ref_grads),
                                          jax.tree.leaves(mask), strict=True):
        g = np.asarray(g, np.float64) * clip / norm
        expected = -lr * (g / (np.abs(g) + eps) + (wd if decayed else 0.0) * np.asarray(p0, np.float64))
        moved = np.asarray(p1, np.float64) - np.asarray(p0, np.float64)
        # float32 storage of the parameter; a sign may flip where g is ~0.  A token met only as a document's LAST
        # has a gradient of exactly zero in the recurrence; the chunked form's cumulative log-decay runs on across
        # the boundary inside a chunk and what cancels there leaves 3e-10, which this test's eps of 1e-12 turns
        # into a whole step for that one row of 128 (the cell's eps is 1e-8).
        wrong = np.abs(moved - expected) > 1e-3 * lr + 2e-7 * np.abs(np.asarray(p0))
        limit = 1e-2 if path[0].key == "embed" else 2e-3
        assert np.mean(wrong) < limit, (jax.tree_util.keystr(path), float(np.mean(wrong)))


def test_a_document_packed_with_another_gets_the_logits_it_gets_alone():
    """Every boundary is a reset: in the delta rule's state, in the convolution
    and in attention."""
    params = seeded()
    rng = np.random.default_rng(1)
    first, second = rng.integers(0, 128, 23).astype(np.int32), rng.integers(0, 128, 41).astype(np.int32)
    apply = lambda tok, seg: oh.OlmoHybrid(F32).apply({"params": params}, tok[None], seg[None])[0]
    packed = apply(np.concatenate([first, second]), np.repeat([0, 1], [23, 41]).astype(np.int32))
    for doc, logits in ((first, packed[:23]), (second, packed[23:])):
        alone = apply(doc, np.zeros(len(doc), np.int32))
        assert float(jnp.max(jnp.abs(logits - alone))) < 5e-6 * float(jnp.max(jnp.abs(alone)))


def test_the_first_eighth_of_the_vocabulary_is_a_smaller_vocabulary():
    """The cut of the benchmark's configuration: the model built with the whole
    vocabulary and with its first eighth give the same hidden states on ids drawn
    from the slice, and the slice's logits are the corresponding columns of the
    whole model's."""
    whole = dataclasses.replace(F32, vocab_size=1024)
    params = seeded(whole)
    sliced = dict(params, embed={"embedding": params["embed"]["embedding"][:128]},
                  head={"rows": params["head"]["rows"][:128]})
    tokens, seg = batch(vocab=128)
    h_whole, _ = oh.hidden_states(whole, params, tokens, seg)
    h_slice, _ = oh.hidden_states(F32, sliced, tokens, seg)
    np.testing.assert_array_equal(np.asarray(h_whole), np.asarray(h_slice))
    logits_whole = oh.logits_of(whole, params, h_whole)
    logits_slice = oh.logits_of(F32, sliced, h_slice)
    assert logits_whole.shape[-1] == 1024 and logits_slice.shape[-1] == 128
    np.testing.assert_allclose(np.asarray(logits_slice), np.asarray(logits_whole[..., :128]), rtol=1e-6, atol=1e-7)


def test_the_four_held_layers_are_the_first_four_of_a_deeper_model():
    """The other cut: the first period of a model of two periods computes what
    the model of one period computes from the same parameters."""
    deeper = dataclasses.replace(F32, layer_types=F32.layer_types * 2)
    params = seeded(deeper)
    held = {group: ({k: v for k, v in tree.items() if k in {f"layer_{i}" for i in range(4)} | {"final"}}
                    if group in ("gdn", "attention", "mlp", "norms") else tree) for group, tree in params.items()}
    assert set(held["gdn"]) == {"layer_0", "layer_1", "layer_2"} and set(held["attention"]) == {"layer_3"}
    tokens, seg = batch()
    want, _ = oh.hidden_states(F32, held, tokens, seg)

    # the deeper model's state after its fourth layer
    x = lm_layers.embed_lookup(params["embed"]["embedding"], tokens, deeper.dtype)
    for i, kind in enumerate(deeper.layer_types[:4]):
        name = f"layer_{i}"
        x, _ = oh._layer(deeper, kind, params[oh.SCOPE[kind]][name], params["mlp"][name], params["norms"][name], x, seg)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(want))
    assert rel(oh.hidden_states(deeper, params, tokens, seg)[0], want) > 1e-3  # and the second period does something


def test_the_published_configuration_builds_and_refuses_what_it_does_not_compute():
    with open(CONFIG_FILE) as f:
        hf = json.load(f)
    config = oh.OlmoHybridConfig.from_hf(hf)
    assert config.layer_types == ("linear_attention",) * 3 + ("full_attention",)
    assert (config.head_dim, config.rope_theta, config.linear_key_dim, config.linear_value_dim) == (128, None, 2880, 5760)
    shapes = jax.eval_shape(lambda k: oh.init_params(config, k), jax.random.key(0))
    count = lambda tree: sum(x.size for x in jax.tree.leaves(tree))
    held = hf["parameters_held"]
    assert count(shapes) == held["total"] == 928_862_196
    assert count(shapes["gdn"]["layer_0"]) == held["gdn_mixer"] == 88_750_332
    assert count(shapes["attention"]["layer_3"]) == held["attention_mixer"]
    assert count(shapes["mlp"]["layer_0"]) == held["mlp"]
    assert count(shapes["gdn"]["layer_0"]) + count(shapes["mlp"]["layer_0"]) + count(shapes["norms"]["layer_0"]) == held["gdn_layer"]
    assert count(shapes["embed"]) + count(shapes["head"]) + count(shapes["norms"]["final"]) == held["embedding_head_and_final_norm"]
    for key, value in (("hidden_act", "gelu"), ("attention_bias", True), ("tie_word_embeddings", True),
                       ("linear_num_key_heads", 15), ("head_dim", 64)):
        with pytest.raises(ValueError, match="does not compute"):
            oh.OlmoHybridConfig.from_hf(dict(hf, **{key: value}))
    # a number for rope_theta is read as published too
    assert oh.OlmoHybridConfig.from_hf(dict(hf, rope_parameters={"rope_theta": 500000.0})).rope_theta == 500000.0


def test_from_hf_on_the_catalog_rows_keys():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Olmo-Hybrid-7B")
    config = oh.OlmoHybridConfig.from_hf(row["config"])
    assert len(config.layer_types) == 32 and config.layer_types.count("full_attention") == 8
    assert (config.vocab_size, config.hidden_size, config.intermediate_size) == (100352, 3840, 11008)
    assert (config.linear_num_value_heads, config.linear_key_head_dim, config.linear_value_head_dim) == (30, 96, 192)


def test_the_language_models_registry_builds_it_by_preset_and_by_model_type():
    assert build_language_model("tiny-olmo").config == oh.TINY
    model = build_language_model(CONFIG_FILE, dtype=jnp.float32)
    assert isinstance(model, oh.OlmoHybrid) and model.config.dtype == jnp.float32
    assert model.scopes == ("embed", "gdn", "attention", "mlp", "lm_head", "loss")
    meta = model.run_meta((1, 8192))
    assert meta == {"attention_lowering": "xla", "delta_rule_lowering": "xla", "delta_rule_chunk": 128,
                    "conv_lowering": "xla", **NOTHING_MORE}


def test_the_lm_task_trains_on_one_device():
    from batchai_retinanet_horovod_coco_tpu.parallel import make_mesh

    with pytest.raises(ValueError, match="trains on one device"):
        make_train_step(oh.OlmoHybrid(oh.TINY), (2, T), None, mesh=make_mesh(2), task=LMTask())


def test_the_step_files_every_operation_it_can_under_a_scope_of_the_model():
    """models/olmo_hybrid.py through the shared step: the model enters ``gdn`` (with
    ``in_proj``, ``conv``, ``delta_rule``, ``gate_norm`` and ``out_proj`` beneath it),
    ``attention`` and ``mlp``, and none of the other models' scopes; forward,
    recomputed forward and backward keep the scope; every matrix product of the
    compiled step lies under one of the model's scopes or the optimizer's."""
    import re

    from batchai_retinanet_horovod_coco_tpu.train.step import STEP_SCOPES, UNSCOPED, scope_table

    model = oh.OlmoHybrid(oh.TINY)
    tx = make_optimizer(OptimizerConfig(optimizer="adamw", schedule="constant", warmup_steps=0))[0]
    state = create_train_state(model, tx, (1, 8), jax.random.key(0), example_dtype=LMTask.example_dtype)
    seg = jnp.asarray(np.repeat([[0, 1, 2], [0, 1, 1]], [20, 30, 14], axis=1), jnp.int32)
    data = {"tokens": jnp.zeros((2, 64), jnp.int32), "segment_ids": seg}
    compiled = make_train_step(model, (2, 64), None, task=LMTask(), donate_state=False).lower(state, data).compile()
    table = scope_table(compiled)
    filed = {(s, d) for s, d, _ in table.values()}
    assert set(model.scopes) <= set(STEP_SCOPES)
    for s in (*model.scopes, "optimizer"):
        assert (s, "fwd") in filed, s
    assert {s for s, d in filed if d == "bwd"} >= set(model.scopes)
    assert not {"mla", "dense_mlp", "moe", "mamba"} & {s for s, _ in filed}
    assert STEP_SCOPES["gdn"] == ("in_proj", "conv", "delta_rule", "gate_norm", "out_proj")
    paths = {p for t, _, p in table.values() if t == "gdn"}
    for name in STEP_SCOPES["gdn"]:
        assert any(f"/{name}/" in p or p.endswith("/" + name) for p in paths), name
    dots = [m.group(1) for m in re.finditer(r"^\s*(?:ROOT )?%?([\w.\-]+) = [^=]*? (?:dot|convolution)\(", compiled.as_text(), re.M)]
    work = {n: table[n] for n in dots if n in table}
    assert work and not [n for n, (s, _, _) in work.items() if s == UNSCOPED]
    assert {s for s, _, _ in work.values()} >= {"gdn", "attention", "mlp", "lm_head"}
    assert {s for s, _, _ in work.values()} <= {*model.scopes, "optimizer"}

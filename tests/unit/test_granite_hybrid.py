"""The Granite 4.0-H hybrid (models/granite_hybrid.py, ops/ssd.py) against
the plain float32 reference (benchmark/reference/granite_hybrid.py) on
seeded weights at the tiny size: one period of ten layers at d = 64, 4 mamba
heads of 16, state 16, chunk 8, vocabulary 128, sequences of 64 tokens with
1-5 documents."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batchai_retinanet_horovod_coco_tpu.models import granite_hybrid as gh
from batchai_retinanet_horovod_coco_tpu.obs.numerics import NumericsConfig
from batchai_retinanet_horovod_coco_tpu.ops import ssd
from batchai_retinanet_horovod_coco_tpu.train import create_train_state
from batchai_retinanet_horovod_coco_tpu.train.optim import OptimizerConfig, decays, make_optimizer
from batchai_retinanet_horovod_coco_tpu.train.step import make_train_step
from batchai_retinanet_horovod_coco_tpu.train.task import LMTask
from benchmark.reference import granite_hybrid as reference

T = 64
F32 = dataclasses.replace(gh.TINY, dtype=jnp.float32)
DOCS = ([20, 30, 14], [7, 57], [64], [5, 9, 21, 17, 12])  # documents per sequence


def hf_of(config: gh.GraniteHybridConfig) -> dict:
    """The published keys the reference reads, for a program configuration."""
    keys = ("vocab_size", "hidden_size", "num_attention_heads", "num_key_value_heads", "mamba_n_heads",
            "mamba_d_head", "mamba_d_state", "mamba_d_conv", "attention_multiplier", "embedding_multiplier",
            "residual_multiplier", "logits_scaling", "rms_norm_eps")
    return dict({k: getattr(config, k) for k in keys}, layer_types=list(config.layer_types),
                num_hidden_layers=len(config.layer_types))


def seeded(config=F32, seed=0):
    """Parameters with every leaf moved off its initial value (norm scales
    and D are 1, the convolution's bias 0 as initialised)."""
    params = gh.init_params(config, jax.random.key(seed))
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    return jax.tree.unflatten(treedef, [x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])


def batch(rows=(0, 1, 3), vocab=128, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, vocab, (len(rows), T)).astype(np.int32)
    seg = np.stack([np.repeat(np.arange(len(DOCS[r])), DOCS[r]) for r in rows]).astype(np.int32)
    return tokens, seg


def program_loss(config, params, tokens, seg):
    logits = gh.GraniteHybrid(config).apply({"params": params}, tokens, seg)
    return gh.next_token_loss(logits, tokens, seg)[0]


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def test_logits_loss_and_every_gradient_leaf_equal_the_references():
    params, (tokens, seg) = seeded(), batch()
    logits = jax.jit(lambda p: gh.GraniteHybrid(F32).apply({"params": p}, tokens, seg))(params)
    expected = jax.jit(lambda p: reference.forward(hf_of(F32), p, tokens, seg))(params)
    assert float(jnp.max(jnp.abs(logits - expected))) < 2e-6 * float(jnp.max(jnp.abs(expected)))
    loss, grads = jax.jit(jax.value_and_grad(lambda p: program_loss(F32, p, tokens, seg)))(params)
    ref_loss, ref_grads = jax.jit(lambda p: reference.loss_and_grads(hf_of(F32), p, tokens, seg))(params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(ref_grads), strict=True):
        assert rel(g, r) < 2e-5, (jax.tree_util.keystr(path), rel(g, r))


def test_bfloat16_compute_stays_near_the_reference():
    params, (tokens, seg) = seeded(gh.TINY), batch()
    loss, grads = jax.jit(jax.value_and_grad(lambda p: program_loss(gh.TINY, p, tokens, seg)))(params)
    ref_loss, ref_grads = jax.jit(lambda p: reference.loss_and_grads(hf_of(gh.TINY), p, tokens, seg))(params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=2e-3)
    for group in ("embed", "mamba", "attention", "mlp"):
        got, want = (jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g[group]))) for g in (grads, ref_grads))
        assert float(got) == pytest.approx(float(want), rel=3e-2), group


def test_the_reference_by_layer_and_in_blocks_equals_the_reference_direct():
    params, (tokens, seg) = seeded(), batch(rows=(0, 3))
    hf = hf_of(F32)
    loss, grads = jax.jit(lambda p: reference.loss_and_grads(hf, p, tokens, seg))(params)
    by_layer = reference.loss_and_grads_by_layer(hf, params, tokens, seg, scan_block=16, head_block=2)
    assert float(by_layer[0]) == pytest.approx(float(loss), rel=1e-6)
    for g, r in zip(jax.tree.leaves(by_layer[1]), jax.tree.leaves(grads), strict=True):
        assert float(jnp.max(jnp.abs(g - r))) <= 1e-5 * float(jnp.max(jnp.abs(r))) + 1e-9


def test_one_adamw_step_through_the_train_step_equals_the_recipe_on_the_references_gradient():
    """The shared step (jit, norm, clip chain, update) with the LM task
    against AdamW's first step written out on the reference's gradient:
    clip by the global norm, ``g / (|g| + eps)``, decoupled decay of the
    matrices only."""
    lr, wd, eps, clip = 3e-3, 0.1, 1e-12, 0.05
    tx, _ = make_optimizer(OptimizerConfig(optimizer="adamw", base_lr=lr, schedule="constant", warmup_steps=0,
                                           weight_decay=wd, adam_b2=0.95, adam_eps=eps, clip_global_norm=clip))
    model, task = gh.GraniteHybrid(F32), LMTask()
    state = create_train_state(model, tx, (1, 8), jax.random.key(3), example_dtype=task.example_dtype)
    state = state.replace(params=seeded(seed=3))
    tokens, seg = batch()
    step = make_train_step(model, tokens.shape, None, task=task, donate_state=False,
                           numerics=NumericsConfig(enabled=True))
    new_state, metrics = step(state, {"tokens": tokens, "segment_ids": seg})
    ref_loss, ref_grads = jax.jit(lambda p: reference.loss_and_grads(hf_of(F32), p, tokens, seg))(state.params)
    norm = float(jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(ref_grads))))
    assert norm > clip  # the clip acts
    assert float(metrics["loss"]) == pytest.approx(float(ref_loss), rel=1e-6)
    assert float(metrics["grad_norm"]) == pytest.approx(norm, rel=1e-5)
    for group in ("embed", "mamba", "attention", "mlp", "norms"):
        want = float(jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(ref_grads[group]))))
        assert float(metrics[f"gnorm/{group}"]) == pytest.approx(want, rel=1e-4), group
    mask = decays(state.params)
    assert {k for k, v in mask["mamba"]["layer_0"].items() if v} == {"in_proj", "conv_w", "out_proj"}
    assert mask["embed"]["embedding"] and not any(jax.tree.leaves(mask["norms"]))
    for (path, p0), p1, g, decayed in zip(jax.tree_util.tree_leaves_with_path(state.params),
                                          jax.tree.leaves(new_state.params), jax.tree.leaves(ref_grads),
                                          jax.tree.leaves(mask), strict=True):
        g = np.asarray(g, np.float64) * clip / norm
        expected = -lr * (g / (np.abs(g) + eps) + (wd if decayed else 0.0) * np.asarray(p0, np.float64))
        moved = np.asarray(p1, np.float64) - np.asarray(p0, np.float64)
        # float32 storage of the parameter; a sign may flip where g is ~0
        wrong = np.abs(moved - expected) > 1e-3 * lr + 2e-7 * np.abs(np.asarray(p0))
        assert np.mean(wrong) < 1e-3, (jax.tree_util.keystr(path), float(np.mean(wrong)))


def test_a_document_packed_with_another_gets_the_logits_it_gets_alone():
    params = seeded()
    rng = np.random.default_rng(1)
    first, second = rng.integers(0, 128, 23).astype(np.int32), rng.integers(0, 128, 41).astype(np.int32)
    apply = lambda tok, seg: gh.GraniteHybrid(F32).apply({"params": params}, tok[None], seg[None])[0]
    packed = apply(np.concatenate([first, second]), np.repeat([0, 1], [23, 41]).astype(np.int32))
    for doc, logits in ((first, packed[:23]), (second, packed[23:])):
        alone = apply(doc, np.zeros(len(doc), np.int32))
        assert float(jnp.max(jnp.abs(logits - alone))) < 2e-6 * float(jnp.max(jnp.abs(alone)))


@pytest.mark.parametrize("chunk", [8, 16, 64, 7, 24, 100])
def test_the_chunked_scan_equals_the_recurrence(chunk):
    """Chunk lengths that divide the 64 tokens and that do not, under and
    over the sequence's length, with document boundaries inside chunks, at
    chunk edges and several chunks apart."""
    heads, p, n = 4, 16, 16
    rng = np.random.default_rng(chunk)
    x = rng.normal(size=(2, T, heads, p)).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.3), (2, T, heads))).astype(np.float32)
    a = -rng.uniform(1, 16, heads).astype(np.float32)
    b, c = (rng.normal(size=(2, T, n)).astype(np.float32) for _ in range(2))
    seg = np.stack([np.repeat(np.arange(3), [8, 40, 16]), np.repeat(np.arange(5), DOCS[3])]).astype(np.int32)
    got = ssd.ssd_chunked(jnp.asarray(x), dt, a, b, c, seg, chunk)

    def recurrence(x, dt, b, c, seg):  # one sequence, token by token, in float64
        state, out = np.zeros((heads, p, n)), []
        for t in range(T):
            if t and seg[t] != seg[t - 1]:
                state = np.zeros_like(state)
            state = np.exp(dt[t] * a)[:, None, None] * state + (dt[t][:, None] * x[t])[:, :, None] * b[t]
            out.append(state @ c[t])
        return np.stack(out)

    want = np.stack([recurrence(*(v[i].astype(np.float64) for v in (x, dt, b, c)), seg[i]) for i in range(2)])
    assert np.max(np.abs(np.asarray(got) - want)) < 2e-5 * np.max(np.abs(want))


def test_the_first_eighth_of_the_vocabulary_is_a_smaller_vocabulary():
    """The cut of the benchmark's configuration: the model built with the
    whole vocabulary and with its first eighth give the same hidden states
    on ids drawn from the slice, and the slice's logits are the
    corresponding columns of the whole model's."""
    whole = dataclasses.replace(F32, vocab_size=1024)
    params = seeded(whole)
    sliced = dict(params, embed={"embedding": params["embed"]["embedding"][:128]})
    tokens, seg = batch(vocab=128)
    h_whole = gh.hidden_states(whole, params, tokens, seg)
    h_slice = gh.hidden_states(F32, sliced, tokens, seg)
    np.testing.assert_array_equal(np.asarray(h_whole), np.asarray(h_slice))
    logits_whole = gh.logits_of(whole, params, h_whole)
    logits_slice = gh.logits_of(F32, sliced, h_slice)
    assert logits_whole.shape[-1] == 1024 and logits_slice.shape[-1] == 128
    np.testing.assert_allclose(np.asarray(logits_slice), np.asarray(logits_whole[..., :128]), rtol=1e-6, atol=1e-7)


def test_the_published_configuration_builds_and_refuses_what_it_does_not_compute():
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark", "configs", "granite-4.0-h-micro-p1.json")
    with open(path) as f:
        hf = json.load(f)
    config = gh.GraniteHybridConfig.from_hf(hf)
    assert config.layer_types == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    shapes = jax.eval_shape(lambda k: gh.init_params(config, k), jax.random.key(0))
    count = lambda tree: sum(x.size for x in jax.tree.leaves(tree))
    held = hf["parameters_held"]
    assert count(shapes) == held["total"] == 772160448
    assert count(shapes["mamba"]["layer_0"]) == held["mamba_mixer"]
    assert count(shapes["attention"]["layer_5"]) == held["attention_mixer"]
    assert count(shapes["mlp"]["layer_0"]) == held["mlp"]
    for key, value in (("mamba_n_groups", 8), ("position_embedding_type", "rope"), ("num_local_experts", 4)):
        with pytest.raises(ValueError, match="does not compute"):
            gh.GraniteHybridConfig.from_hf(dict(hf, **{key: value}))


def test_the_lm_task_trains_on_one_device():
    from batchai_retinanet_horovod_coco_tpu.parallel import make_mesh

    with pytest.raises(ValueError, match="trains on one device"):
        make_train_step(gh.GraniteHybrid(gh.TINY), (2, T), None, mesh=make_mesh(2), task=LMTask())

"""What a recomputed layer keeps of its attention (models/lm_layers.py::
layer_keeps, here its answer for layers without a gated MLP: LAYER_KEEPS;
tests/unit/test_layer_keeps.py has the product): the two kernel lowerings (ops/attention.py's splash kernels,
ops/sparse_attention.py's own; interpret mode on the CPU) name their forward's
output and log-sum-exp inside their forward rule, so that under the models'
policy the forward kernel runs ONCE a layer in a gradient, and twice under a
policy that does not keep the name, with the same gradient to the last bit."""

import collections
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals  # what print_saved_residuals prints, as a list

from batchai_retinanet_horovod_coco_tpu.models import lm_layers
from batchai_retinanet_horovod_coco_tpu.models.language import build_language_model
from batchai_retinanet_horovod_coco_tpu.ops import attention
from batchai_retinanet_horovod_coco_tpu.ops import sparse_attention as sparse

T, D, HEADS, KV, SIZE, INDEX_HEADS, INDEX_SIZE = 256, 64, 4, 2, 16, 4, 8
BLOCK = 128  # of the splash kernel here: T holds two
TILES = dict(rows=128, scores=64, thresholds=dict(query_tile=32, columns=128), attention=(32, 64), probs=(32, 64))
SEG = jnp.asarray(np.r_[np.zeros(100), np.ones(156)].astype(np.int32)[None])
LAYER_KEEPS = lm_layers.policy(lm_layers.NO_PRODUCT)  # these layers hold no gated MLP
WITHOUT_RESIDUALS = jax.checkpoint_policies.save_only_these_names(sparse.THRESHOLD)  # the parent's policy


def _heads(x, w, heads):
    return (x @ w).reshape(1, T, heads, -1)


def _qkv(w, x):
    return _heads(x, w["q"], HEADS), _heads(x, w["k"], KV), _heads(x, w["v"], KV)


def _splash_layer(w, x):
    q, k, v = _qkv(w, x)
    with mock.patch.object(attention, "BLOCK_SIZES", {name: BLOCK for name in attention.BLOCK_SIZES}):
        out = attention._kernel_path(q, k, v, SEG, 0.25, interpret=True)
    return x + out.reshape(1, T, -1) @ w["o"], 0.0


def _dsa_layer(w, x):
    q, k, v = _qkv(w, x)
    u = jax.lax.stop_gradient(x)
    a = sparse.sparse_attention(q, k, v, _heads(u, w["q_idx"], INDEX_HEADS), u @ w["k_idx"], u @ w["w_idx"], SEG,
                                topk=24, scale=0.25, index_scale=0.3, how=sparse.KERNEL, interpret=True, tiles=TILES)
    return x + a.out.reshape(1, T, -1) @ w["o"], a.kl


# lowering -> (a layer, its forward kernel's name, dq's, dk/dv's)
LOWERINGS = {
    "splash": (_splash_layer, "splash_mha_fwd", "splash_mha_dq", "splash_mha_dkv"),
    "dsa": (_dsa_layer, "dsa_attention_fwd", "dsa_attention_dq", "dsa_attention_dkv"),
}


def _operands():
    rng = np.random.default_rng(0)
    n = lambda *shape: jnp.asarray(rng.normal(size=shape) * 0.1, jnp.float32)
    layer = lambda: dict(q=n(D, HEADS * SIZE), k=n(D, KV * SIZE), v=n(D, KV * SIZE), o=n(HEADS * SIZE, D),
                         q_idx=n(D, INDEX_HEADS * INDEX_SIZE), k_idx=n(D, INDEX_SIZE), w_idx=n(D, INDEX_HEADS))
    return [layer(), layer()], n(1, T, D)


def _loss(layer, policy):
    def loss(weights, x):
        kl = 0.0
        for w in weights:
            x, k = jax.checkpoint(layer, policy=policy)(w, x)
            kl = kl + k
        return jnp.sum(x * jnp.cos(x)) + 3.0 * kl

    return loss


def _kernel_calls(jaxpr, counts=None):
    """How often each Pallas kernel is called in ``jaxpr``, by name."""
    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            counts[eqn.params["name"]] += 1
        for inner in jax.core.jaxprs_in_params(eqn.params):
            _kernel_calls(inner, counts)
    return counts


@pytest.mark.parametrize("lowering", sorted(LOWERINGS))
def test_a_gradient_runs_the_forward_kernel_once_a_layer_and_gives_the_same_bits(lowering):
    layer, fwd, dq, dkv = LOWERINGS[lowering]
    operands = _operands()
    grads = {}
    for policy, forward_calls in ((LAYER_KEEPS, 2), (WITHOUT_RESIDUALS, 4)):  # of two layers
        grad = jax.grad(_loss(layer, policy), argnums=(0, 1))
        calls = _kernel_calls(jax.make_jaxpr(grad)(*operands).jaxpr)
        # by the first part of the name: the library's splash kernels' names go on (``_segmented_residuals``)
        count = lambda kernel: sum(n for name, n in calls.items() if name.startswith(kernel))
        assert (count(fwd), count(dq), count(dkv)) == (forward_calls, 2, 2), calls
        grads[forward_calls] = jax.jit(grad)(*operands)
    assert all(float(jnp.max(jnp.abs(grads[2][0][0][name]))) > 0 for name in "qkvo")
    for a, b in zip(*(jax.tree.leaves(grads[n]) for n in (2, 4))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _kept(layer, policy, w, x):
    """What one checkpointed layer saves for its backward pass beside constants
    of the trace and its weights: sorted (shape, dtype)."""
    saved = saved_residuals(lambda w, x: jnp.sum(jax.checkpoint(layer, policy=policy)(w, x)[0]), w, x)
    return sorted((tuple(aval.shape), str(aval.dtype)) for aval, why in saved
                  if not why.startswith(("from a constant", "from the argument w")))


@pytest.mark.parametrize("lowering", sorted(LOWERINGS))
def test_a_layer_keeps_its_input_the_thresholds_the_output_and_the_log_sum_exp(lowering):
    """Under the models' policy: the layer's input, the forward kernel's output
    (heads, T, size) and log-sum-exp (heads, T), for the sparse attention the
    thresholds (``tau`` and ``cut`` of each run of queries), and nothing else;
    under the parent's policy neither output nor log-sum-exp."""
    layer = LOWERINGS[lowering][0]
    weights, x = _operands()
    thresholds = [((TILES["rows"],), "int32")] * 4 if lowering == "dsa" else []
    residuals = [((HEADS, T, SIZE), "float32"), ((HEADS, T), "float32")]
    layer_input = ((1, T, D), "float32")
    assert _kept(layer, LAYER_KEEPS, weights[0], x) == sorted([layer_input, *residuals, *thresholds])
    assert _kept(layer, WITHOUT_RESIDUALS, weights[0], x) == sorted([layer_input, *thresholds])


def test_the_xla_lowerings_name_nothing():
    """On the CPU, a ragged or a short sequence: the policy keeps what no
    policy kept, the layer's input (and the thresholds)."""
    weights, x = _operands()

    def xla_layer(w, x):
        return x + attention.packed_causal_attention(*_qkv(w, x), SEG, 0.25, 64).reshape(1, T, -1) @ w["o"]

    layer = lambda w, x: (xla_layer(w, x), 0.0)
    kept = [_kept(layer, policy, weights[0], x) for policy in (LAYER_KEEPS, None)]
    assert kept[0] == kept[1] == [((1, T, D), "float32")]


CONFIGS = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark", "configs")


@pytest.mark.parametrize("config,bucket", [
    ("granite-4.0-h-micro-p1", (1, 8192)), ("deepseek-v2-lite-ep8", (2, 8192)),
    ("nemotron-3-nano-30b-ep16", (2, 8192)), ("keye-vl2-30b-a3b-ep8", (1, 16384)),
])
def test_run_meta_says_the_residuals_are_kept_where_the_kernels_run(config, bucket):
    """A cell's model at its cell's bucket: kept on a TPU, no word of it on
    the CPU or at a sequence that is no whole blocks (the xla lowerings)."""
    model = build_language_model(os.path.join(CONFIGS, f"{config}.json"))
    assert "attention_residuals" not in model.run_meta(bucket)  # this process's backend is the CPU
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        meta, ragged = model.run_meta(bucket), model.run_meta((bucket[0], bucket[1] + 192))
    assert (meta["attention_lowering"], meta["attention_residuals"]) == ("kernel", "kept")
    assert ragged["attention_lowering"] == "xla" and "attention_residuals" not in ragged

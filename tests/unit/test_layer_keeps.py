"""What a recomputed layer keeps of its MLP (models/lm_layers.py::layer_keeps):
``gated_mlp`` names its product with ``gate_up``, and where that product over
all layers fits the device beside the rest of the step the models' policy keeps
it, so that a gradient forms it ONCE a layer (twice under today's names), with
the same loss and gradients to the last bit.  The decision is one pure function
of the products' bytes, the parameters' bytes and the device's memory limit."""

import dataclasses
import functools
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals  # what print_saved_residuals prints, as a list

from batchai_retinanet_horovod_coco_tpu.models import granite_hybrid as gh
from batchai_retinanet_horovod_coco_tpu.models import lm_layers
from batchai_retinanet_horovod_coco_tpu.models import olmo_hybrid as oh
from batchai_retinanet_horovod_coco_tpu.models.language import build_language_model
from batchai_retinanet_horovod_coco_tpu.ops import attention
from batchai_retinanet_horovod_coco_tpu.ops import sparse_attention as sparse

T = 64
V5E = 16_909_336_064  # ``bytes_limit`` of a TPU v5 lite chip (PERF.md section 6, PR 40)
TODAY = (attention.RESIDUALS, sparse.THRESHOLD)
SEG = jnp.asarray(np.repeat([0, 1, 2], [20, 30, 14]).astype(np.int32)[None])
TOKENS = jnp.asarray(np.random.default_rng(0).integers(0, 128, (1, T)).astype(np.int32))
# model -> (its module, its class, a two-layer tiny configuration with one layer of each kind); float32, so
# that XLA finds no rounding to leave out of one compiled program and not of the other
TWO_LAYERS = {
    "granite": (gh, gh.GraniteHybrid,
                dataclasses.replace(gh.TINY, layer_types=(gh.MAMBA, gh.ATTENTION), dtype=jnp.float32)),
    "olmo": (oh, oh.OlmoHybrid, dataclasses.replace(oh.TINY, layer_types=(oh.LINEAR, oh.FULL), dtype=jnp.float32)),
}


@functools.lru_cache(maxsize=None)
def _params(name):
    """Seeded parameters of ``name``'s two layers, made in one program (eagerly, a model's ``init_params`` is a
    hundred small ones) and once in this file."""
    module, _, config = TWO_LAYERS[name]
    return jax.jit(functools.partial(module.init_params, config))(jax.random.key(0))


def _limit(value):
    """The device's memory limit as ``lm_layers`` reads it, for the block."""
    return mock.patch.object(lm_layers, "device_memory_limit", lambda: value)


def _dots(jaxpr, which, count=0):
    """How many ``dot_general``s in ``jaxpr`` give an array of a shape that ``which`` takes."""
    for eqn in jaxpr.eqns:
        count += eqn.primitive.name == "dot_general" and which(tuple(eqn.outvars[0].aval.shape))
        for inner in jax.core.jaxprs_in_params(eqn.params):
            count = _dots(inner, which, count)
    return count


# XLA's CPU compiler orders the float32 sums of two programs differently (its algebraic simplifier rewrites by
# context, LLVM vectorises and contracts by context): compiled as usual, the first layer's gradients under the two
# policies differ in the seventh digit, as PERF.md section 7 has it of the chip's programs.  Without the two, the
# programs do what the jaxprs say, in their order.
AS_WRITTEN = {"xla_backend_optimization_level": 0, "xla_disable_hlo_passes": "algsimp"}


@pytest.mark.parametrize("name", sorted(TWO_LAYERS))
def test_a_gradient_forms_the_gate_up_product_once_a_layer_and_gives_the_same_bits(name):
    """(a) and (b): a mixer of each kind, so two named products in the gradient's
    jaxpr under the policy that keeps the name and four under today's, every
    other product as often; loss and every gradient leaf bit-equal."""
    module, model, config = TWO_LAYERS[name]
    params = _params(name)
    product = (1, T, 2 * config.intermediate_size)
    counts, values = {}, {}
    for limit in (V5E, None):
        with _limit(limit):
            traced = jax.jit(jax.value_and_grad(lambda p: model(config).loss(p, TOKENS, SEG)[0])).trace(params)
        jaxpr = traced.jaxpr.jaxpr
        counts[limit] = (_dots(jaxpr, lambda shape: shape == product), _dots(jaxpr, lambda shape: shape != product))
        values[limit] = traced.lower().compile(compiler_options=AS_WRITTEN)(params)
    assert (counts[V5E][0], counts[None][0]) == (2, 4)
    assert counts[V5E][1] == counts[None][1] > 20
    leaves = jax.tree.leaves(values[V5E])
    assert len(leaves) > 10 and all(float(jnp.max(jnp.abs(x))) > 0 for x in leaves)
    for a, b in zip(leaves, jax.tree.leaves(values[None]), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name", sorted(TWO_LAYERS))
def test_a_layer_keeps_its_input_the_gate_up_product_and_nothing_else(name):
    """``saved_residuals`` of one checkpointed layer of each kind, beside the
    weights and the trace's constants: under the policy with ``MLP_GATE_UP`` the
    layer's input and the named product; under today's names the input alone
    (the xla lowerings of the CPU name nothing of attention's)."""
    module, _, config = TWO_LAYERS[name]
    params = _params(name)
    x = 0.1 * jax.random.normal(jax.random.key(1), (1, T, config.hidden_size), config.dtype)
    layer_input = ((1, T, config.hidden_size), str(jnp.dtype(config.dtype)))
    product = ((1, T, 2 * config.intermediate_size), str(jnp.dtype(config.dtype)))
    group = oh.SCOPE.get if name == "olmo" else str  # of a kind's mixer in the parameter tree
    for i, kind in enumerate(config.layer_types):
        p = (params[group(kind)][f"layer_{i}"], params["mlp"][f"layer_{i}"], params["norms"][f"layer_{i}"])
        for names, want in (((*TODAY, lm_layers.MLP_GATE_UP), [layer_input, product]), (TODAY, [layer_input])):
            policy = lm_layers.policy(lm_layers.Keeps(names, 0, 0))
            layer = jax.checkpoint(module._layer, static_argnums=(0, 1), policy=policy)
            out = lambda p, x, seg: jnp.sum(jax.tree.leaves(layer(config, kind, *p, x, seg))[0].astype(jnp.float32))
            saved = saved_residuals(out, p, x, SEG)
            kept = sorted((tuple(aval.shape), str(aval.dtype)) for aval, why in saved
                          if not why.startswith(("from a constant", "from the argument p", "from the argument seg")))
            assert kept == sorted(want), (kind, names, saved)


CONFIGS = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark", "configs")
# The five cells: configuration, bucket, and what ``layer_keeps`` answers at a v5e's limit
# (its ``MEASURED`` table): the layers whose product is kept and their bytes.
CELLS = {
    "granite": ("granite-4.0-h-micro-p1", (1, 8192), 10, 10 * 8192 * 16384 * 2),
    "dsv2": ("deepseek-v2-lite-ep8", (2, 8192), 6, 2 * 8192 * 2 * (10944 + 5 * 2816) * 2),
    "olmo": ("olmo-hybrid-7b-p1", (1, 8192), 0, 0),
    "nemo3": ("nemotron-3-nano-30b-ep16", (2, 8192), 0, 0),
    "keye": ("keye-vl2-30b-a3b-ep8", (1, 16384), 0, 0),
}


# ``lm_layers.param_shapes`` traces a published model's ``init_params``, a quarter of a second a call: once a
# configuration in this file
_param_shapes = functools.lru_cache(maxsize=None)(lm_layers.param_shapes)


@pytest.fixture(autouse=True)
def _shapes_once():
    with mock.patch.object(lm_layers, "param_shapes", _param_shapes):
        yield


def _meta(cell, limit):
    config, bucket, _, _ = CELLS[cell]
    with _limit(limit):
        meta = build_language_model(os.path.join(CONFIGS, f"{config}.json")).run_meta(bucket)
    return meta["layer_keeps"].split(","), meta["mlp_gate_up_layers"], meta["mlp_gate_up_bytes"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_run_meta_says_what_the_cells_layers_keep_on_a_v5e_and_on_the_cpu(cell):
    """(c) and (d): the cell's model at its bucket reports the names, the layers
    and the bytes: granite's ten products (2.68 GB) and dsv2's six (1.64 GB) fit a v5e,
    olmo's four (1.44 GB) do not, nemo3 and keye have none; with no limit (this
    process's CPU) nothing more than today's names anywhere."""
    _, _, layers, kept_bytes = CELLS[cell]
    names = [*TODAY, lm_layers.MLP_GATE_UP] if layers else list(TODAY)
    assert _meta(cell, V5E) == (names, layers, kept_bytes)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):  # the lowerings do not enter it
        assert _meta(cell, V5E) == (names, layers, kept_bytes)
    assert _meta(cell, None) == (list(TODAY), 0, 0)
    config, bucket, _, _ = CELLS[cell]
    assert "mlp_gate_up" not in build_language_model(os.path.join(CONFIGS, f"{config}.json")).run_meta(bucket)[
        "layer_keeps"]  # unpatched: the CPU states no limit


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_answer_is_monotone_in_the_limit(cell):
    """All layers or none, and once a limit keeps them every larger one does;
    what a model has to keep is kept at SOME limit."""
    kept = [_meta(cell, gib << 30)[1] for gib in (0, 4, 8, 12, 14, 16, 18, 20, 24, 32, 64, 1024)]
    assert kept == sorted(kept) and set(kept) <= {0, kept[-1]}
    assert (kept[-1] > 0) == (cell in ("granite", "dsv2", "olmo"))


GB = 10 ** 9


@pytest.mark.parametrize("products,param_bytes,layer_input_bytes,limit,layers", [
    ((), GB, GB // 30, V5E, 0),  # nothing to keep
    ((GB // 4,) * 4, GB, GB // 30, None, 0),  # no limit stated
    ((GB // 4,) * 4, GB, GB // 30, V5E, 4),  # 3.5 + 1.7 + 1 of 16.9 GB
    ((GB // 4,) * 4, 4 * GB, GB // 30, V5E, 0),  # 14 GB of state and gradients
    ((GB // 4,) * 4, GB, GB // 4, V5E, 0),  # 12.5 GB of working set
    ((4 * GB,) * 3, GB, GB // 30, V5E, 0),  # 12 GB of products
])
def test_layer_keeps_is_a_pure_function_of_bytes(products, param_bytes, layer_input_bytes, limit, layers):
    keeps = lm_layers.layer_keeps(products, param_bytes, layer_input_bytes, limit)
    assert keeps == lm_layers.layer_keeps(products, param_bytes, layer_input_bytes, limit)
    assert keeps.names == ((*TODAY, lm_layers.MLP_GATE_UP) if layers else TODAY)
    assert (keeps.gate_up_layers, keeps.gate_up_bytes) == (layers, sum(products) if layers else 0)
    assert lm_layers.NO_PRODUCT == lm_layers.Keeps(TODAY, 0, 0)
    # one policy object an answer: JAX keys a checkpointed layer's cached traces on it
    assert lm_layers.policy(keeps) is lm_layers.policy(lm_layers.layer_keeps(products, param_bytes, layer_input_bytes, limit))


def test_gated_mlp_is_the_same_function_with_the_name():
    """The name is the identity outside a policy that lists it, and the bytes
    ``keeps_of`` counts for a width are the named product's."""
    rng = np.random.default_rng(0)
    p = {"gate_up": jnp.asarray(rng.normal(size=(16, 64)), jnp.float32),
         "down": jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)}
    u = jnp.asarray(rng.normal(size=(2, 8, 16)), jnp.float32)
    gate, up = jnp.split(u @ p["gate_up"], 2, axis=-1)
    np.testing.assert_array_equal(np.asarray(lm_layers.gated_mlp(lambda x: x, p, u)),
                                  np.asarray((jax.nn.silu(gate) * up) @ p["down"]))
    named = [e for e in jax.make_jaxpr(lambda u: lm_layers.gated_mlp(lambda x: x, p, u))(u).eqns
             if e.primitive.name == "name"]
    assert [e.params["name"] for e in named] == [lm_layers.MLP_GATE_UP]
    aval = named[0].outvars[0].aval
    with _limit(V5E):
        keeps = lm_layers.keeps_of([32], p, (2, 8), 16, jnp.float32)
    assert (keeps.gate_up_layers, keeps.gate_up_bytes) == (1, aval.size * aval.dtype.itemsize)

"""ops/pallas/document_conv.py: the convolution's kernel pair (interpret mode on the
CPU) against the XLA body of ops/document_conv.py and against a token-by-token loop;
the choice between the two lowerings; where the kernels' calls sit in a step; what
``run_meta`` says."""

import dataclasses
import os
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batchai_retinanet_horovod_coco_tpu.models import granite_hybrid, lm_layers, olmo_hybrid
from batchai_retinanet_horovod_coco_tpu.models.language import build_language_model
from batchai_retinanet_horovod_coco_tpu.ops import document_conv as dc
from batchai_retinanet_horovod_coco_tpu.ops.pallas import document_conv as kernel_lib
from batchai_retinanet_horovod_coco_tpu.train.step import scope_of

T, TB, C = 512, 128, 256  # four token blocks of one lane tile, two channel blocks
BLOCKS = (TB, 128)
CONFIGS = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark", "configs")

LAYOUTS = {
    "one_document": [[T]],
    "a_boundary_in_a_blocks_first_three_tokens": [[TB + 1, TB + 1, T - 2 * TB - 2]],  # at a block's tokens 1 and 2
    "a_boundary_on_a_blocks_edge": [[TB, 2 * TB, TB]],
    "documents_of_1_2_and_3_tokens": [[TB - 2, 1, 2, 3, 1, 1, T - TB - 6]],  # across the first edge
    "padding_at_the_end": [[300, 150, -(T - 450)]],  # the last run is padding: id -1
    "two_sequences": [[200, T - 200], [TB - 1, 2, T - TB - 1]],
}


def _segments(layout):
    rows = []
    for lengths in layout:
        ids = [(-1 if n < 0 else i) for i, n in enumerate(lengths)]
        rows.append(np.repeat(ids, np.abs(lengths)).astype(np.int32))
        assert rows[-1].shape == (T,)
    return np.stack(rows)


def _operands(seed, dtype, batch, taps, bias):
    keys = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(keys[0], (batch, T, C), jnp.float32).astype(dtype)
    w = 0.5 * jax.random.normal(keys[1], (taps, C), jnp.float32)
    b = 0.3 * jax.random.normal(keys[2], (C,), jnp.float32) if bias == "vector" else 0.0  # olmo passes 0.0
    g = jax.random.normal(keys[3], (batch, T, C), jnp.float32)
    return x, w, b, g


def _kernel(x, w, b, seg):
    return dc.via_kernels(x, w, b, seg, BLOCKS, True)


def _token_loop(x, w, b, seg, g):
    """The definition, token by token in float64: ``(y, dx, dw, db)``."""
    x, w, g = (np.asarray(a, np.float64) for a in (x.astype(jnp.float32), w, g))
    taps = w.shape[0]
    pre = np.zeros_like(x) + np.asarray(b, np.float64)
    reaches = lambda s, t, j: t - j >= 0 and seg[s, t - j] == seg[s, t]
    for s in range(x.shape[0]):
        for t in range(x.shape[1]):
            for j in range(taps):
                if reaches(s, t, j):
                    pre[s, t] += w[taps - 1 - j] * x[s, t - j]
    sig = 1.0 / (1.0 + np.exp(-pre))
    dpre = g * sig * (1.0 + pre * (1.0 - sig))
    dx, dw = np.zeros_like(x), np.zeros_like(w)
    for s in range(x.shape[0]):
        for t in range(x.shape[1]):
            for j in range(taps):
                if reaches(s, t, j):
                    dx[s, t - j] += w[taps - 1 - j] * dpre[s, t]
                    dw[taps - 1 - j] += dpre[s, t] * x[s, t - j]
    return pre * sig, dx, dw, dpre.sum(axis=(0, 1))


CASES = [(layout, dtype, "vector", 4) for layout in LAYOUTS for dtype in ("bfloat16", "float32")] + [
    ("documents_of_1_2_and_3_tokens", "bfloat16", "scalar_0", 4),  # olmo's call
    ("two_sequences", "float32", "scalar_0", 4),
    ("documents_of_1_2_and_3_tokens", "bfloat16", "vector", 3),
    ("a_boundary_in_a_blocks_first_three_tokens", "float32", "vector", 2),
    ("two_sequences", "bfloat16", "vector", 9),  # taps in two sublane tiles' worth of columns
]


@pytest.mark.parametrize("layout,dtype,bias,taps", CASES, ids=["-".join(map(str, c)) for c in CASES])
def test_the_kernels_give_the_xla_bodys_and_the_token_loops_value_and_gradients(layout, dtype, bias, taps):
    seg = _segments(LAYOUTS[layout])
    x, w, b, g = _operands(3, getattr(jnp, dtype), len(seg), taps, bias)
    b_arr, seg_j = jnp.asarray(b, jnp.float32), jnp.asarray(seg)
    y_k, vjp_k = jax.vjp(lambda x, w, b: _kernel(x, w, b, seg_j), x, w, b_arr)
    y_x, vjp_x = jax.vjp(lambda x, w, b: dc.document_conv_silu(x, w, b, seg_j), x, w, b_arr)
    assert y_k.dtype == jnp.float32 and y_k.shape == x.shape
    got, xla = (y_k, *vjp_k(g)), (y_x, *vjp_x(g))
    assert got[1].dtype == x.dtype and got[2].shape == w.shape and got[3].shape == b_arr.shape
    loop = _token_loop(x, w, b, seg, g)
    scales = [float(np.max(np.abs(a))) for a in loop]
    if bias == "scalar_0":  # the scalar's gradient is the channels' added: a float32 sum of as many terms again
        loop, scales[3] = (*loop[:3], loop[3].sum()), float(np.abs(loop[3]).sum())
    # float32 sums of up to 2 x 512 terms against float64; dx rounded once to the input's dtype
    for name, a, ref_x, ref_l, scale in zip(("y", "dx", "dw", "db"), got, xla, loop, scales):
        rel = 2.0 ** -8 if (name == "dx" and dtype == "bfloat16") else 2e-5
        np.testing.assert_allclose(np.asarray(a, np.float64), ref_l, atol=rel * scale, rtol=0, err_msg=f"{name} (loop)")
        np.testing.assert_allclose(np.asarray(a, np.float64), np.asarray(ref_x, np.float64), atol=rel * scale, rtol=0,
                                   err_msg=f"{name} (xla body)")


@pytest.mark.parametrize("boundary", [TB, TB + 1, TB + 2, 2 * TB - 1, 3], ids=lambda b: f"boundary_at_{b}")
def test_a_token_of_another_document_contributes_exactly_0(boundary):
    """Whatever the document before holds, non-finite values too, the document after
    reads bit for bit what it reads after zeros; and the cotangent after the boundary
    reaches no token before it."""
    seg = jnp.asarray(_segments([[boundary, T - boundary]]))
    x, w, b, g = _operands(5, jnp.float32, 1, 4, "vector")
    before = (jnp.arange(T) < boundary)[None, :, None]
    y_clean, vjp = jax.vjp(lambda x: _kernel(x, w, b, seg), jnp.where(before, 0.0, x))
    y_dirty = _kernel(jnp.where(before, jnp.where(x > 0, jnp.inf, jnp.nan), x), w, b, seg)
    np.testing.assert_array_equal(np.asarray(y_dirty[:, boundary:]), np.asarray(y_clean[:, boundary:]))
    assert bool(jnp.all(jnp.isfinite(y_dirty[:, boundary:])))
    (dx_after,) = vjp(jnp.where(before, 0.0, g))  # only the second document's cotangent
    np.testing.assert_array_equal(np.asarray(dx_after[:, :boundary]), 0.0)
    assert float(jnp.max(jnp.abs(dx_after[:, boundary:]))) > 0


@pytest.mark.parametrize("backend,seq_len,channels,taps,want", [
    ("tpu", 8192, 4352, 4, dc.KERNEL),    # granite-h-train-pack8k
    ("tpu", 8192, 6144, 4, dc.KERNEL),    # nemo3-nano-train-pack8k
    ("tpu", 8192, 11520, 4, dc.KERNEL),   # olmo-hybrid-train-pack8k
    ("tpu", 8192, 4352, 129, dc.KERNEL),  # the longest window the neighbour's lane tile holds
    ("cpu", 8192, 4352, 4, dc.XLA),
    ("gpu", 8192, 4352, 4, dc.XLA),
    ("tpu", 8192 + 512, 4352, 4, dc.XLA),  # a ragged sequence: no whole token blocks
    ("tpu", 64, 4352, 4, dc.XLA),          # the tiny presets' sequences
    ("tpu", 8192, 4352 + 8, 4, dc.XLA),    # channels of no whole sublane tile (16 rows of bfloat16)
    ("tpu", 8192, 88, 4, dc.XLA),          # the tiny nemotron preset's channels
    ("tpu", 8192, 4352, 130, dc.XLA),      # a window longer than that
    ("tpu", 0, 4352, 4, dc.XLA),
])
def test_lowering_reads_the_backend_and_the_shapes(backend, seq_len, channels, taps, want):
    assert dc.lowering(backend, seq_len, channels, taps) == want


def test_the_seam_takes_the_xla_body_on_the_cpu(monkeypatch):
    monkeypatch.setattr(kernel_lib, "forward", lambda *a, **k: pytest.fail("the kernels on the CPU"))
    seg = jnp.asarray(_segments(LAYOUTS["two_sequences"]))
    x, w, b, _ = _operands(0, jnp.bfloat16, 2, 4, "vector")
    y = lm_layers.document_conv_silu(x, w, b, seg)  # the name the models call and the benchmark's mutations patch
    assert y.dtype == jnp.float32 and y.shape == x.shape


def _calls(text):
    return len(re.findall(r"stablehlo\.custom_call @tpu_custom_call", text))


@pytest.mark.parametrize("batch,channels,out", [(1, 4352, "float32"), (2, 6144, "float32"), (1, 11520, "float32")],
                         ids=["granite", "nemo3_two_sequences", "olmo"])
def test_kernels_lower_for_tpu_at_the_cells_shapes_with_the_committed_blocks(batch, channels, out):
    """JAX-level lowering only (what Mosaic says of it is the chip's to tell): 8192
    tokens of bfloat16, 4 taps; the forward and the backward."""
    spec = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype)

    def fn(x, w, b, seg):
        y, vjp = jax.vjp(lambda x, w, b: dc.via_kernels(x, w, b, seg), x, w, b)
        return y, vjp(y)

    text = jax.jit(fn).trace(
        spec(batch, 8192, channels), spec(4, channels, dtype=jnp.float32), spec(channels, dtype=jnp.float32),
        spec(batch, 8192, dtype=jnp.int32)).lower(lowering_platforms=("tpu",)).as_text()
    assert _calls(text) == 2
    assert channels % kernel_lib.channel_block(channels) == 0 and kernel_lib.channel_block(channels) % 16 == 0


def _grad_text(module, config, hidden):
    params = jax.eval_shape(lambda: module.init_params(config, jax.random.key(0)))
    tokens = jax.ShapeDtypeStruct((1, kernel_lib.TOKEN_BLOCK), jnp.int32)

    def objective(params, tokens, seg):  # not "loss": the jit's name would be read as the scope
        logits = module.logits_of(config, params, hidden(config, params, tokens, seg))
        return lm_layers.next_token_loss(logits, tokens, seg)[0]

    try:
        return jax.jit(jax.grad(objective)).trace(params, tokens, tokens).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    finally:
        jax.clear_caches()  # the layers' traces are cached by function; leave none with the patched choice


def _granite_text():
    config = dataclasses.replace(granite_hybrid.TINY, layer_types=("mamba", "attention", "mamba"),
                                 mamba_n_heads=7, mamba_d_head=16, mamba_d_state=8, dtype=jnp.bfloat16)  # 128 channels
    return _grad_text(granite_hybrid, config, granite_hybrid.hidden_states), "mamba"


def _olmo_text():
    config = dataclasses.replace(olmo_hybrid.TINY, dtype=jnp.bfloat16)
    assert (2 * config.linear_key_dim + config.linear_value_dim) % 128 == 0
    return _grad_text(olmo_hybrid, config, lambda *a: olmo_hybrid.hidden_states(*a)[0]), "gdn"


@pytest.mark.parametrize("step_text", [_granite_text, _olmo_text], ids=["granite_mamba_conv", "olmo_gdn_conv"])
def test_the_kernels_calls_sit_under_the_mixers_conv_scope_in_all_three_passes(monkeypatch, step_text):
    """A model's gradient with the kernels (lowered for TPU from the CPU): forward,
    recomputed forward and backward each hold one call a mixer, every one under the
    scope ``conv`` beneath the mixer's (``olmo_step.conv_ms`` reads ``gdn/conv``)."""
    monkeypatch.setattr(dc, "lowering", lambda *a: dc.KERNEL)
    text, mixer = step_text()
    names = set(re.findall(r'loc\("([^"]*document_conv_[a-z]+/pallas_call)"', text))
    # as train/step.py::scope_table files a compiled step's instructions: (slice, direction, path)
    assert {scope_of(n) for n in names} == {
        (mixer, "fwd", f"{mixer}/conv/document_conv_fwd/pallas_call"),
        (mixer, "bwd", f"{mixer}/conv/document_conv_fwd/pallas_call"),  # recomputed
        (mixer, "bwd", f"{mixer}/conv/document_conv_bwd/pallas_call"),
    }
    calls = lambda name: len(re.findall(rf"stablehlo\.custom_call @tpu_custom_call[^\n]*{name}", text))
    assert calls("document_conv_bwd") >= 1 and calls("document_conv_fwd") == 2 * calls("document_conv_bwd")


@pytest.mark.parametrize("config_file", ["granite-4.0-h-micro-p1.json", "nemotron-3-nano-30b-ep16.json",
                                         "olmo-hybrid-7b-p1.json"])
@pytest.mark.parametrize("backend,want", [("cpu", dc.XLA), ("tpu", dc.KERNEL)])
def test_run_meta_carries_conv_lowering(config_file, backend, want):
    """At the cells' sizes: the kernels on a TPU, the XLA body on the CPU."""
    model = build_language_model(os.path.join(CONFIGS, config_file))
    with mock.patch.object(jax, "default_backend", lambda: backend):
        assert model.run_meta((1, 8192))["conv_lowering"] == want
    assert model.run_meta((1, 8192 + 8))["conv_lowering"] == dc.XLA

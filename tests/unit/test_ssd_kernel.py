"""ops/pallas/ssd.py: the scan's kernel pair (interpret mode on the CPU) against
the XLA body of ops/ssd.py and against the token-by-token recurrence; the
choice between the two lowerings; where the kernels' calls sit in the step."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batchai_retinanet_horovod_coco_tpu.models import granite_hybrid
from batchai_retinanet_horovod_coco_tpu.ops import ssd
from batchai_retinanet_horovod_coco_tpu.ops.pallas import ssd as ssd_kernel
from batchai_retinanet_horovod_coco_tpu.train.step import scope_of

HEADS, HEAD, STATE, CHUNK, T = 8, 16, 128, 128, 512  # four chunks; one block of eight heads

LAYOUTS = {
    "one_document": [T],
    "a_boundary_inside_a_chunk": [200, T - 200],
    "a_boundary_on_a_chunks_edge": [CHUNK, 2 * CHUNK, CHUNK],
    "a_document_over_many_chunks": [70, 3 * CHUNK - 10, T - 60 - 3 * CHUNK],
    "a_document_of_one_token": [100, 1, 155, 1, T - 257],
    "padding_at_the_end": [300, 150, -(T - 450)],  # the last run is padding: id -1, dt 0
}


def _segments(lengths):
    ids = [(-1 if n < 0 else i) for i, n in enumerate(lengths)]
    seg = np.repeat(ids, np.abs(lengths)).astype(np.int32)
    assert seg.shape == (T,)
    return seg[None]


def _operands(seed, dtype, seg):
    keys = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(keys[0], (1, T, HEADS, HEAD), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (1, T, HEADS), jnp.float32) - 2.0)
    dt = jnp.where(seg[..., None] < 0, 0.0, dt)  # padding contributes nothing
    a = -jnp.exp(jax.random.uniform(keys[2], (HEADS,), jnp.float32, 0.0, 2.7))  # A in (-15, -1)
    b = jax.random.normal(keys[3], (1, T, STATE), jnp.float32).astype(dtype)
    c = jax.random.normal(keys[4], (1, T, STATE), jnp.float32).astype(dtype)
    g = jax.random.normal(keys[5], (1, T, HEADS, HEAD), jnp.float32)
    return (x, dt, a, b, c), g


_interpreted = functools.partial(ssd_kernel.chunked_scan, heads_per_block=HEADS, interpret=True)


def _kernel(x, dt, a, b, c, seg):
    return ssd._chunked(x, dt, a, b, c, seg, CHUNK, _interpreted)


def _xla(x, dt, a, b, c, seg):
    return ssd._chunked(x, dt, a, b, c, seg, CHUNK, None)


def _recurrence(x, dt, a, b, c, seg):
    """Token by token in float32 at the highest matmul precision, as
    benchmark/reference/granite_hybrid.py::mamba runs it."""
    x, b, c = (v[0].astype(jnp.float32) for v in (x, b, c))
    first = jnp.concatenate([jnp.ones((1,), bool), seg[0, 1:] != seg[0, :-1]])

    def token(state, inp):
        x_t, b_t, c_t, dt_t, first_t = inp
        state = jnp.where(first_t, 0.0, state)
        state = jnp.exp(dt_t * a)[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, jnp.einsum("hpn,n->hp", state, c_t, precision="highest")

    _, y = jax.lax.scan(token, jnp.zeros((HEADS, HEAD, STATE), jnp.float32), (x, b, c, dt[0], first))
    return y[None]


def _out_and_grads(fn, operands, g, seg):
    out, vjp = jax.vjp(lambda *o: fn(*o, seg), *operands)
    return out, vjp(g)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


NAMES = ("x", "dt", "A", "B", "C")


@pytest.mark.parametrize("layout", LAYOUTS)
def test_float32_kernel_is_the_xla_body_and_the_recurrence(layout):
    """The mathematics alone: masks, the state carried from chunk to chunk and
    its resets, the backward's walk from the last chunk to the first."""
    seg = jnp.asarray(_segments(LAYOUTS[layout]))
    operands, g = _operands(0, jnp.float32, seg)
    with jax.default_matmul_precision("highest"):
        out_k, grads_k = _out_and_grads(_kernel, operands, g, seg)
        out_x, grads_x = _out_and_grads(_xla, operands, g, seg)
    out_r, grads_r = _out_and_grads(_recurrence, operands, g, seg)
    assert out_k.shape == (1, T, HEADS, HEAD) and out_k.dtype == jnp.float32
    assert _rel(out_k, out_x) < 1e-6 and _rel(out_k, out_r) < 1e-5
    for name, gk, gx, gr in zip(NAMES, grads_k, grads_x, grads_r, strict=True):
        assert gk.shape == gx.shape and gk.dtype == gx.dtype, name
        assert _rel(gk, gx) < 2e-5, name
        assert _rel(gk, gr) < 1e-4, name


@pytest.mark.parametrize("layout", LAYOUTS)
def test_bfloat16_kernel_is_no_further_from_the_float32_recurrence_than_the_xla_body(layout):
    seg = jnp.asarray(_segments(LAYOUTS[layout]))
    operands, g = _operands(1, jnp.bfloat16, seg)
    out_k, grads_k = _out_and_grads(_kernel, operands, g, seg)
    out_x, grads_x = _out_and_grads(_xla, operands, g, seg)
    out_r, grads_r = _out_and_grads(_recurrence, operands, g, seg)  # float32 on the same bfloat16 values
    assert _rel(out_k, out_r) < 4e-3
    assert _rel(out_k, out_r) <= 1.05 * _rel(out_x, out_r)
    for name, gk, gx, gr in zip(NAMES, grads_k, grads_x, grads_r, strict=True):
        assert gk.dtype == gx.dtype, name  # x, B, C in bfloat16; dt and A float32
        assert _rel(gk, gr) < 1e-2, name
        assert _rel(gk, gr) <= 1.05 * _rel(gx, gr), name


def test_a_large_dt_gives_no_nan():
    """``cum_i - cum_j`` above the diagonal is then hundreds: exponentiated
    before the mask it would be inf, and inf times the mask's zero a NaN."""
    seg = jnp.asarray(_segments(LAYOUTS["a_boundary_inside_a_chunk"]))
    (x, _, a, b, c), g = _operands(2, jnp.bfloat16, seg)
    dt = jnp.full((1, T, HEADS), 40.0, jnp.float32)  # softplus of a large pre-activation; dt * A down to -600
    out, grads = _out_and_grads(_kernel, (x, dt, a, b, c), g, seg)
    out_x, _ = _out_and_grads(_xla, (x, dt, a, b, c), g, seg)
    assert bool(jnp.isfinite(out).all())
    for name, grad in zip(NAMES, grads, strict=True):
        assert bool(jnp.isfinite(grad.astype(jnp.float32)).all()), name
    assert _rel(out, out_x) < 1e-3


def test_a_document_alone_is_the_document_packed_between_two_others():
    lengths = [150, 2 * CHUNK, T - 150 - 2 * CHUNK]  # the middle one starts and ends inside chunks
    seg = jnp.asarray(_segments(lengths))
    operands, _ = _operands(3, jnp.float32, seg)
    with jax.default_matmul_precision("highest"):
        packed = _kernel(*operands, seg)
        lo, hi = lengths[0], lengths[0] + lengths[1]
        roll = lambda v: jnp.roll(v, -lo, axis=1) if v.ndim > 1 else v
        alone = _kernel(*(roll(v) for v in operands), jnp.asarray(_segments([lengths[1], T - lengths[1]])))
    # other chunk edges, so other sums: float32 rounding of values of order ten
    np.testing.assert_allclose(np.asarray(packed[:, lo:hi]), np.asarray(alone[:, :hi - lo]), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("backend,seq_len,chunk,heads,head,state,want", [
    ("cpu", 64, 8, 4, 16, 16, ssd.XLA),          # the tiny preset in the CPU tests
    ("cpu", 8192, 256, 64, 64, 128, ssd.XLA),
    ("tpu", 64, 8, 4, 16, 16, ssd.XLA),          # train.py lm-synthetic's default on a chip
    ("tpu", 8192, 256, 64, 64, 128, ssd.KERNEL),  # granite-h-train-pack8k
    ("tpu", 8192 + 128, 256, 64, 64, 128, ssd.XLA),  # not whole chunks
    ("tpu", 8192, 64, 64, 64, 128, ssd.XLA),     # a chunk is not whole lane tiles
    ("tpu", 8192, 256, 48, 64, 128, ssd.XLA),    # not whole blocks of heads
    ("tpu", 8192, 256, 64, 24, 128, ssd.XLA),    # a head is not whole sublane tiles
    ("tpu", 8192, 256, 64, 64, 16, ssd.XLA),     # a state is not whole lane tiles
    ("gpu", 8192, 256, 64, 64, 128, ssd.XLA),
])
def test_lowering_follows_backend_and_shapes(backend, seq_len, chunk, heads, head, state, want):
    assert ssd.lowering(backend, seq_len, chunk, heads, head, state) == want


def test_mixer_takes_the_xla_path_on_the_cpu(monkeypatch):
    monkeypatch.setattr(ssd_kernel, "chunked_scan", lambda *a, **k: pytest.fail("the kernels on the CPU"))
    config = granite_hybrid.TINY
    params = granite_hybrid.init_params(config, jax.random.key(0))
    tokens = jnp.zeros((1, 64), jnp.int32)
    hidden = granite_hybrid.hidden_states(config, params, tokens, jnp.zeros((1, 64), jnp.int32))
    assert hidden.shape == (1, 64, config.hidden_size)


def _calls(text):
    return len(re.findall(r"stablehlo\.custom_call @tpu_custom_call", text))


def test_kernels_lower_for_tpu_at_the_cells_shapes_with_the_committed_block():
    """JAX-level lowering only (what Mosaic says of it is the chip's to tell):
    64 heads of 64, state 128, 8192 tokens in chunks of 256; the forward that
    saves the states and the backward."""
    spec = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype)

    def fn(x, dt, a, b, c, seg):
        out, vjp = jax.vjp(lambda *o: ssd._chunked(*o, seg, 256, ssd_kernel.chunked_scan), x, dt, a, b, c)
        return vjp(out)

    text = jax.jit(fn).trace(
        spec(1, 8192, 64, 64), spec(1, 8192, 64, dtype=jnp.float32), spec(64, dtype=jnp.float32),
        spec(1, 8192, 128), spec(1, 8192, 128), spec(1, 8192, dtype=jnp.int32),
    ).lower(lowering_platforms=("tpu",)).as_text()
    assert _calls(text) == 2
    assert ssd.lowering("tpu", 8192, 256, 64, 64, 128) == ssd.KERNEL


def test_the_kernels_calls_sit_under_mamba_ssd_in_all_three_passes(monkeypatch):
    """The language model's step with the kernels (lowered for TPU from the
    CPU): forward, recomputed forward and backward each hold one call a
    mixer, every one under the scope ``mamba/ssd`` that ``lm_step.ssd_ms``
    reads."""
    monkeypatch.setattr(ssd, "lowering", lambda *a: ssd.KERNEL)
    config = dataclasses.replace(
        granite_hybrid.TINY, layer_types=("mamba", "attention", "mamba"), mamba_n_heads=ssd_kernel.HEADS_PER_BLOCK,
        mamba_d_head=16, mamba_d_state=128, mamba_chunk_size=128, dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda: granite_hybrid.init_params(config, jax.random.key(0)))
    tokens = jax.ShapeDtypeStruct((1, 256), jnp.int32)

    def objective(params, tokens, seg):  # not "loss": the jit's name would be read as the scope
        logits = granite_hybrid.logits_of(config, params, granite_hybrid.hidden_states(config, params, tokens, seg))
        return granite_hybrid.next_token_loss(logits, tokens, seg)[0]

    try:
        text = jax.jit(jax.grad(objective)).trace(params, tokens, tokens).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    finally:
        jax.clear_caches()  # the layers' traces are cached by function; leave none with the patched choice
    assert _calls(text) == 6  # two mixers x (forward, recomputed forward, backward)
    names = set(re.findall(r'loc\("([^"]*pallas_call)"', text))
    # as train/step.py::scope_table files a compiled step's instructions: (slice, direction, path)
    assert {scope_of(n) for n in names} == {
        ("mamba", "fwd", "mamba/ssd/ssd_scan_fwd/pallas_call"),
        ("mamba", "bwd", "mamba/ssd/ssd_scan_fwd/pallas_call"),  # recomputed
        ("mamba", "bwd", "mamba/ssd/ssd_scan_bwd/pallas_call"),
    }
    assert sum("checkpoint/rematted_computation/mamba/ssd/ssd_scan_fwd" in n for n in names) == 1

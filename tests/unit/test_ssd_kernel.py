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


def _operands(seed, dtype, seg, groups=None):
    """``groups`` None: B and C (1, T, STATE), one group as Granite's mixer
    passes them; a number: (1, T, groups, STATE)."""
    keys = jax.random.split(jax.random.key(seed), 6)
    bc_shape = (1, T, STATE) if groups is None else (1, T, groups, STATE)
    x = jax.random.normal(keys[0], (1, T, HEADS, HEAD), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (1, T, HEADS), jnp.float32) - 2.0)
    dt = jnp.where(seg[..., None] < 0, 0.0, dt)  # padding contributes nothing
    a = -jnp.exp(jax.random.uniform(keys[2], (HEADS,), jnp.float32, 0.0, 2.7))  # A in (-15, -1)
    b = jax.random.normal(keys[3], bc_shape, jnp.float32).astype(dtype)
    c = jax.random.normal(keys[4], bc_shape, jnp.float32).astype(dtype)
    g = jax.random.normal(keys[5], (1, T, HEADS, HEAD), jnp.float32)
    return (x, dt, a, b, c), g


_interpreted = functools.partial(ssd_kernel.chunked_scan, heads_per_block=HEADS, interpret=True)


def _kernel(x, dt, a, b, c, seg):
    return ssd._chunked(x, dt, a, b, c, seg, CHUNK, _interpreted)


def _xla(x, dt, a, b, c, seg):
    return ssd._chunked(x, dt, a, b, c, seg, CHUNK, None)


def _recurrence(x, dt, a, b, c, seg):
    """Token by token in float32 at the highest matmul precision, as
    benchmark/reference/granite_hybrid.py::mamba runs it; head ``h`` reads
    the B and C of group ``h // (heads / groups)``."""
    x, b, c = (v[0].astype(jnp.float32) for v in (x, b, c))
    if b.ndim == 3:  # (T, groups, STATE) -> a B and a C for every head
        b, c = (jnp.repeat(v, HEADS // v.shape[1], axis=1) for v in (b, c))
    else:
        b, c = (jnp.broadcast_to(v[:, None, :], (T, HEADS, STATE)) for v in (b, c))
    first = jnp.concatenate([jnp.ones((1,), bool), seg[0, 1:] != seg[0, :-1]])

    def token(state, inp):
        x_t, b_t, c_t, dt_t, first_t = inp
        state = jnp.where(first_t, 0.0, state)
        state = jnp.exp(dt_t * a)[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t, precision="highest")

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


GROUPED = {  # groups of B and C, heads in a block of the kernel
    "one_group_as_a_fourth_axis": (1, HEADS),
    "two_groups_in_one_block": (2, HEADS),
    "a_group_a_head": (8, HEADS),
    "two_blocks_inside_each_of_two_groups": (2, 2),
    "a_block_a_group": (4, 2),
}


@pytest.mark.parametrize("layout", ["a_boundary_inside_a_chunk", "a_document_over_many_chunks"])
@pytest.mark.parametrize("case", GROUPED)
def test_grouped_scan_xla_body_and_kernel_are_the_recurrence_by_group(case, layout):
    """B and C in groups of consecutive heads (Nemotron-H's mixer: 8): the XLA
    body against the token-by-token recurrence in which head h reads group
    h // (heads / groups), and the kernels (whole groups in a block, or a block
    inside a group) against the XLA body, forward and backward."""
    groups, heads_per_block = GROUPED[case]
    seg = jnp.asarray(_segments(LAYOUTS[layout]))
    operands, g = _operands(4, jnp.float32, seg, groups)
    scan = functools.partial(ssd_kernel.chunked_scan, heads_per_block=heads_per_block, interpret=True)
    with jax.default_matmul_precision("highest"):
        out_k, grads_k = _out_and_grads(lambda *o: ssd._chunked(*o, CHUNK, scan), operands, g, seg)
        out_x, grads_x = _out_and_grads(_xla, operands, g, seg)
    out_r, grads_r = _out_and_grads(_recurrence, operands, g, seg)
    assert out_x.shape == (1, T, HEADS, HEAD)
    assert _rel(out_x, out_r) < 1e-5 and _rel(out_k, out_x) < 1e-6
    for name, gk, gx, gr in zip(NAMES, grads_k, grads_x, grads_r, strict=True):
        assert gk.shape == gx.shape == gr.shape and gk.dtype == gx.dtype, name
        assert _rel(gx, gr) < 1e-4, name
        assert _rel(gk, gx) < 2e-5, name


@pytest.mark.parametrize("lowered", ["xla", "kernel"])
def test_a_head_reads_its_own_group_and_no_other(lowered):
    """Four groups of two heads: changing group 2's B and C moves heads 4 and 5 alone."""
    seg = jnp.asarray(_segments(LAYOUTS["a_boundary_inside_a_chunk"]))
    (x, dt, a, b, c), _ = _operands(5, jnp.float32, seg, 4)
    fn = _kernel if lowered == "kernel" else _xla
    other = fn(x, dt, a, b.at[:, :, 2].multiply(-1.5), c.at[:, :, 2].add(0.5), seg)
    moved = np.abs(np.asarray(fn(x, dt, a, b, c, seg) - other)).max(axis=(0, 1, 3))
    assert (moved[[4, 5]] > 1e-2).all() and (np.delete(moved, [4, 5]) == 0).all()


def test_grouped_bfloat16_kernel_is_no_further_from_the_float32_recurrence_than_the_xla_body():
    seg = jnp.asarray(_segments(LAYOUTS["a_document_over_many_chunks"]))
    operands, g = _operands(6, jnp.bfloat16, seg, 8)
    out_k, grads_k = _out_and_grads(_kernel, operands, g, seg)
    out_x, grads_x = _out_and_grads(_xla, operands, g, seg)
    out_r, grads_r = _out_and_grads(_recurrence, operands, g, seg)
    assert _rel(out_k, out_r) < 4e-3 and _rel(out_k, out_r) <= 1.05 * _rel(out_x, out_r)
    for name, gk, gx, gr in zip(NAMES, grads_k, grads_x, grads_r, strict=True):
        assert gk.dtype == gx.dtype, name
        assert _rel(gk, gr) < 1e-2 and _rel(gk, gr) <= 1.05 * _rel(gx, gr), name


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


@pytest.mark.parametrize("heads,groups,want", [
    (64, 8, ssd.KERNEL),   # nemo3-nano-train-pack8k: a block of 32 heads carries four groups
    (64, 1, ssd.KERNEL),   # granite's, said aloud
    (64, 2, ssd.KERNEL),   # a block is a group
    (64, 64, ssd.KERNEL),  # a group a head
    (64, 3, ssd.XLA),      # the heads are not whole groups
    (96, 2, ssd.XLA),      # groups of 48 heads: a block of 32 would cut one
])
def test_lowering_follows_the_groups(heads, groups, want):
    assert ssd.lowering("tpu", 8192, 256, heads, 64, 128, groups) == want
    assert ssd.lowering("cpu", 8192, 256, heads, 64, 128, groups) == ssd.XLA


def test_mixer_takes_the_xla_path_on_the_cpu(monkeypatch):
    monkeypatch.setattr(ssd_kernel, "chunked_scan", lambda *a, **k: pytest.fail("the kernels on the CPU"))
    config = granite_hybrid.TINY
    params = granite_hybrid.init_params(config, jax.random.key(0))
    tokens = jnp.zeros((1, 64), jnp.int32)
    hidden = granite_hybrid.hidden_states(config, params, tokens, jnp.zeros((1, 64), jnp.int32))
    assert hidden.shape == (1, 64, config.hidden_size)


def _calls(text):
    return len(re.findall(r"stablehlo\.custom_call @tpu_custom_call", text))


@pytest.mark.parametrize("groups", [None, 8], ids=["granite_one_group", "nemotron_eight_groups"])
def test_kernels_lower_for_tpu_at_the_cells_shapes_with_the_committed_block(groups):
    """JAX-level lowering only (what Mosaic says of it is the chip's to tell):
    64 heads of 64, state 128, 8192 tokens in chunks of 256; the forward that
    saves the states and the backward."""
    spec = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype)
    bc = spec(1, 8192, 128) if groups is None else spec(1, 8192, groups, 128)

    def fn(x, dt, a, b, c, seg):
        out, vjp = jax.vjp(lambda *o: ssd._chunked(*o, seg, 256, ssd_kernel.chunked_scan), x, dt, a, b, c)
        return vjp(out)

    text = jax.jit(fn).trace(
        spec(1, 8192, 64, 64), spec(1, 8192, 64, dtype=jnp.float32), spec(64, dtype=jnp.float32),
        bc, bc, spec(1, 8192, dtype=jnp.int32),
    ).lower(lowering_platforms=("tpu",)).as_text()
    assert _calls(text) == 2
    assert ssd.lowering("tpu", 8192, 256, 64, 64, 128, groups or 1) == ssd.KERNEL


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

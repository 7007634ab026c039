"""The gated delta rule (ops/delta_rule.py): the chunked xla lowering and the
kernel pair (ops/pallas/delta_rule.py, in Pallas's interpreter) against the
recurrence token by token (benchmark/reference/olmo_hybrid.py::
gated_delta_recurrence), values and all five gradients (q, k, v, the log-decay
and b), at chunks that do and do not divide the documents, with a reset inside a
chunk, at a chunk's first and last token, b in (1, 2], one document a sequence,
and key 96 / value 192 / 30 heads at a short length; the exact inverse of the
unit-lower-triangular system where a series would cancel; and that the kernels
lower for a TPU at the cell's sizes."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batchai_retinanet_horovod_coco_tpu.ops import delta_rule as dr
from batchai_retinanet_horovod_coco_tpu.ops.pallas import delta_rule as kernels
from benchmark.reference.olmo_hybrid import gated_delta_recurrence

INTERPRETED = functools.partial(kernels.chunked_delta_rule, interpret=True)


def inputs(t, heads, key, value, documents, seed=0, batch=1, dtype=jnp.float32):
    """q and k L2-normalised (q scaled), a decay around 0.9, b in (1, 2]; ``documents``
    the lengths of every sequence's documents."""
    r = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(r.normal(size=(batch, t, heads, key))) * key ** -0.5
    k = unit(r.normal(size=(batch, t, heads, key)))
    v = r.normal(size=(batch, t, heads, value))
    log_a = -np.exp(r.normal(size=(batch, t, heads)) - 2.0)
    b = 2.0 - r.uniform(size=(batch, t, heads))
    seg = np.stack([np.repeat(np.arange(len(d)), d) for d in documents]).astype(np.int32)
    assert seg.shape == (batch, t)
    return tuple(jnp.asarray(a, d) for a, d in zip((q, k, v, log_a, b), (dtype,) * 3 + (jnp.float32,) * 2)), jnp.asarray(seg)


def recurrence(q, k, v, log_a, b, seg):
    with jax.default_matmul_precision("highest"):
        o, sq = jax.vmap(gated_delta_recurrence)(*(a.astype(jnp.float32) for a in (q, k, v, log_a, b)), seg)
    return o, sq  # (batch, T, H, V), (batch, T, H)


def value_and_grads(fn, args, seg, weights):
    def loss(*a):
        o = fn(*a, seg)[0]
        return jnp.sum(o * weights), o
    (_, o), grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
    return o, grads


def close(got, want, tol):
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) <= tol * float(jnp.max(jnp.abs(want)))


CASES = {
    # name: (T, heads, key, value, chunk, the documents of each sequence)
    "chunks_divide_the_documents": (64, 2, 16, 32, 16, [[16, 32, 16], [64]]),
    "a_reset_inside_a_chunk": (64, 2, 16, 32, 16, [[5, 20, 39], [23, 41]]),
    "resets_at_a_chunks_first_and_last_token": (64, 2, 16, 32, 16, [[15, 1, 17, 31], [16, 15, 33]]),
    "one_document_a_sequence": (64, 3, 8, 16, 8, [[64], [64]]),
    "documents_shorter_than_a_chunk": (64, 2, 8, 16, 32, [[3, 4, 9, 2, 30, 16], [1, 1, 1, 61]]),
    "a_chunk_longer_than_the_sequence": (48, 2, 8, 16, 64, [[20, 28], [48]]),
    "a_ragged_sequence": (50, 2, 8, 16, 16, [[7, 43], [25, 25]]),
}


# the kernels take whole chunks (the xla lowering pads)
PAIRS = [(case, lowering) for case in sorted(CASES) for lowering in ("xla", "kernel")
         if lowering == "xla" or CASES[case][0] % CASES[case][4] == 0]


@pytest.mark.parametrize("case,lowering", PAIRS)
def test_the_chunked_rule_equals_the_recurrence_values_and_gradients(case, lowering):
    t, heads, key, value, chunk, documents = CASES[case]
    args, seg = inputs(t, heads, key, value, documents, batch=len(documents), seed=len(case))
    weights = jnp.asarray(np.random.default_rng(1).normal(size=(len(documents), t, heads, value)), jnp.float32)
    fn = lambda *a: dr._chunked(*a, chunk, INTERPRETED if lowering == "kernel" else None)
    o, grads = value_and_grads(fn, args, seg, weights)
    want_o, want_grads = value_and_grads(lambda *a: recurrence(*a), args, seg, weights)
    assert close(o, want_o, 2e-5)
    for name, g, w in zip("q k v log_a b".split(), grads, want_grads):
        assert close(g, w, 5e-5), name


@pytest.mark.parametrize("lowering", ["xla", "kernel"])
def test_the_published_head_sizes_at_a_short_length(lowering):
    """30 heads of key 96 and value 192, chunks of 128 (the cell's), three
    documents: neither head size is a multiple of 128 and 30 heads no block of 32."""
    args, seg = inputs(256, 30, 96, 192, [[100, 130, 26]], seed=3)
    weights = jnp.asarray(np.random.default_rng(2).normal(size=(1, 256, 30, 192)), jnp.float32)
    fn = lambda *a: dr._chunked(*a, 128, INTERPRETED if lowering == "kernel" else None)
    o, grads = value_and_grads(fn, args, seg, weights)
    want_o, want_grads = value_and_grads(lambda *a: recurrence(*a), args, seg, weights)
    assert close(o, want_o, 2e-5)
    for name, g, w in zip("q k v log_a b".split(), grads, want_grads):
        assert close(g, w, 1e-4), name


def test_the_largest_state_norm_is_the_recurrences_at_the_chunks_ends():
    args, seg = inputs(64, 2, 16, 32, [[5, 20, 39]], seed=4)
    _, sq = recurrence(*args, seg)
    for chunk in (8, 16):
        want = float(jnp.sqrt(jnp.max(sq[:, chunk - 1::chunk])))
        for kernel in (None, INTERPRETED):
            got = dr._chunked(*args, seg, chunk, kernel)[1]
            assert float(got) == pytest.approx(want, rel=1e-5)


def test_bfloat16_operands_stay_near_the_recurrence():
    args, seg = inputs(128, 4, 16, 32, [[40, 88]], seed=5, dtype=jnp.bfloat16)
    want, _ = recurrence(*args, seg)
    for kernel in (None, INTERPRETED):
        o, _ = dr._chunked(*args, seg, 32, kernel)
        assert float(jnp.linalg.norm(o - want) / jnp.linalg.norm(want)) < 1e-2


@pytest.mark.parametrize("split", [False, True])
def test_the_triangular_systems_inverse_is_exact_where_a_series_would_cancel(split):
    """Every key the same and b = 2: ``L`` is 2 below the diagonal, the inverse's
    entries are +-2, and ``L``'s 32nd power holds numbers of 10^18.  The recursion
    over block sizes never forms a power: its intermediates are inverses of
    diagonal blocks.  In three bfloat16 passes (``split``) the error is 2^-16 a
    product."""
    size = 64
    i, j = np.indices((size, size))
    lower = jnp.asarray(np.where(i > j, 2.0, 0.0), jnp.float32)
    inverse = np.asarray(kernels.unit_lower_inverse(lower, split))
    want = np.where(i == j, 1.0, np.where(i > j, 2.0 * (-1.0) ** (i - j), 0.0))
    np.testing.assert_allclose(inverse, want, atol=1e-5)
    # and a random well-conditioned one against numpy's
    r = np.random.default_rng(0)
    lower = np.tril(r.normal(size=(size, size)) * 0.3, -1).astype(np.float32)
    got = np.asarray(kernels.unit_lower_inverse(jnp.asarray(lower), split))
    np.testing.assert_allclose(got, np.linalg.inv(np.eye(size) + lower), atol=2e-4 if split else 2e-5)


def test_lowering_says_which_runs():
    cell = (8192, 128, 30, 96, 192)
    assert dr.lowering("tpu", *cell) == dr.KERNEL and dr.lowering("cpu", *cell) == dr.XLA
    assert dr.lowering("tpu", 8192 + 64, 128, 30, 96, 192) == dr.XLA  # a ragged sequence
    assert dr.lowering("tpu", 64, 8, 4, 8, 16) == dr.XLA  # the tiny preset
    assert dr.lowering("tpu", 8192, 128, 30, 100, 192) == dr.XLA  # a head that is no whole sublane tiles
    assert kernels.heads_per_block(30) == 6 and kernels.heads_per_block(4) == 4 and kernels.heads_per_block(7) == 1
    with pytest.raises(ValueError, match="power of two"):
        dr.gated_delta_rule(*inputs(24, 1, 8, 8, [[24]])[0], jnp.zeros((1, 24), jnp.int32), 12)


def test_the_kernels_lower_for_a_tpu_at_the_cells_sizes():
    """JAX-level lowering only (no libtpu): the block specs, the grid and the
    kernels' bodies trace at 30 heads of 96 / 192, chunks of 128, bfloat16."""
    shape = lambda *s, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(s, dtype)
    t, heads = 1024, 30
    args = (shape(1, t, heads, 96), shape(1, t, heads, 96), shape(1, t, heads, 192),
            shape(1, t // 128, 128, heads, dtype=jnp.float32), shape(1, t // 128, 128, heads, dtype=jnp.float32),
            shape(1, t // 128, 128, dtype=jnp.int32))
    loss = lambda *a: jnp.sum(kernels.chunked_delta_rule(*a)[0])
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 2 and kernels.FWD_NAME in text and kernels.BWD_NAME in text

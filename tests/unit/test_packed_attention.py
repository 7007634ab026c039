"""ops/attention.py: the blocked kernel (interpret mode on the CPU) against
the XLA path and against the float32 reference's dense masked softmax; the
choice between the two; the count of block pairs a batch's documents need."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batchai_retinanet_horovod_coco_tpu.models import granite_hybrid
from batchai_retinanet_horovod_coco_tpu.ops import attention
from benchmark.reference import granite_hybrid as reference

HEADS, KV_HEADS, HEAD, T = 8, 2, 64, 512
SCALE = 0.125
BLOCK = 128  # of the kernel in these tests: T holds four


def _segments(lengths):
    assert sum(lengths) == T
    return np.repeat(np.arange(len(lengths)), lengths).astype(np.int32)[None]


LAYOUTS = {
    "one_document": [T],
    "four_documents": [100, 200, 12, 200],
    "boundary_on_a_block_edge": [BLOCK, 2 * BLOCK, BLOCK],
    "all_shorter_than_a_block": [60, 100, 40, 90, 30, 110, 82],
}


def _qkv(seed, dtype=jnp.bfloat16):
    keys = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(keys[0], (1, T, HEADS, HEAD), jnp.float32)
    k = jax.random.normal(keys[1], (1, T, KV_HEADS, HEAD), jnp.float32)
    v = jax.random.normal(keys[2], (1, T, KV_HEADS, HEAD), jnp.float32)
    g = jax.random.normal(keys[3], (1, T, HEADS, HEAD), jnp.float32)
    return tuple(x.astype(dtype) for x in (q, k, v)), g


def _kernel(q, k, v, seg):
    with mock.patch.object(attention, "BLOCK_SIZES", {name: BLOCK for name in attention.BLOCK_SIZES}):
        return attention._kernel_path(q, k, v, seg, SCALE, interpret=True)


def _xla(q, k, v, seg):
    return attention._xla_path(q, k, v, seg, SCALE, 128)


def _reference(q, k, v, seg):
    """benchmark/reference/granite_hybrid.py::attention on float32 copies:
    with T == heads x head size its input can be the identity, so that the
    projection weights ARE q, k and v (and the output projection nothing)."""
    assert T == HEADS * HEAD
    hf = {"num_attention_heads": HEADS, "num_key_value_heads": KV_HEADS, "attention_multiplier": SCALE}
    eye = jnp.eye(T, dtype=jnp.float32)
    p = {"q": q[0].reshape(T, -1), "k": k[0].reshape(T, -1), "v": v[0].reshape(T, -1), "o": eye}
    with jax.default_matmul_precision("highest"):
        return reference.attention(hf, jax.tree.map(lambda x: x.astype(jnp.float32), p), eye,
                                   seg[0]).reshape(1, T, HEADS, HEAD)


def _out_and_grads(fn, qkv, g, seg):
    out, vjp = jax.vjp(lambda q, k, v: fn(q, k, v, seg).astype(jnp.float32), *qkv)
    return out, vjp(g)


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("layout", LAYOUTS)
def test_kernel_matches_xla_path_and_float32_reference(layout):
    seg = jnp.asarray(_segments(LAYOUTS[layout]))
    qkv, g = _qkv(0)
    out_k, grads_k = _out_and_grads(_kernel, qkv, g, seg)
    out_x, grads_x = _out_and_grads(_xla, qkv, g, seg)
    out_r, grads_r = _out_and_grads(_reference, qkv, g, seg)
    assert out_k.shape == (1, T, HEADS, HEAD)
    # Outputs are bfloat16 (one ulp is 2^-8 of the value); the reference is
    # float32 on the same bfloat16 inputs.
    np.testing.assert_allclose(out_k, out_r, rtol=2 ** -7, atol=2 ** -7)
    assert _rel(out_k, out_r) < 4e-3
    assert _rel(out_k, out_r) <= 1.05 * _rel(out_x, out_r)  # the kernel is no further than XLA's path
    for name, gk, gx, gr in zip("qkv", grads_k, grads_x, grads_r):
        assert gk.dtype == jnp.bfloat16
        assert _rel(gk, gr) < 1e-2, name
        assert _rel(gk, gx) < 1.5e-2, name


def test_float32_operands_give_the_reference_closely():
    """The mathematics alone: mask, grouping of heads and the online softmax."""
    seg = jnp.asarray(_segments(LAYOUTS["four_documents"]))
    qkv, g = _qkv(1, jnp.float32)
    with jax.default_matmul_precision("highest"):
        out_k, grads_k = _out_and_grads(_kernel, qkv, g, seg)
    out_r, grads_r = _out_and_grads(_reference, qkv, g, seg)
    np.testing.assert_allclose(out_k, out_r, rtol=1e-4, atol=1e-5)
    for gk, gr in zip(grads_k, grads_r):
        np.testing.assert_allclose(gk, gr, rtol=1e-3, atol=1e-4)


def test_a_document_alone_is_the_document_packed_between_two_others():
    (q, k, v), _ = _qkv(2)
    lengths = [150, 2 * BLOCK, T - 150 - 2 * BLOCK]  # the middle one crosses block edges
    packed = _kernel(q, k, v, jnp.asarray(_segments(lengths)))
    lo, hi = lengths[0], lengths[0] + lengths[1]
    # Alone: moved to the front of a sequence whose rest is another document.
    roll = lambda x: jnp.roll(x, -lo, axis=1)
    alone = _kernel(roll(q), roll(k), roll(v), jnp.asarray(_segments([lengths[1], T - lengths[1]])))
    # Other block edges, so another order of the online softmax's sums: one bfloat16 ulp.
    np.testing.assert_allclose(np.asarray(packed[:, lo:hi], np.float32),
                               np.asarray(alone[:, :hi - lo], np.float32), rtol=2 ** -7, atol=2 ** -9)


@pytest.mark.parametrize("backend,seq_len,want", [
    ("cpu", 64, attention.XLA),      # the tiny preset in the CPU tests
    ("cpu", 8192, attention.XLA),
    ("tpu", 64, attention.XLA),      # train.py lm-synthetic's default on a chip
    ("tpu", 8192 + 128, attention.XLA),
    ("tpu", 8192, attention.KERNEL),  # granite-h-train-pack8k
    ("gpu", 8192, attention.XLA),
])
def test_lowering_follows_backend_and_shape(backend, seq_len, want):
    assert attention.lowering(backend, seq_len) == want


def test_mixer_takes_the_xla_path_on_the_cpu(monkeypatch):
    monkeypatch.setattr(attention, "_kernel_path", lambda *a, **k: pytest.fail("kernel path on the CPU"))
    config = granite_hybrid.TINY
    params = granite_hybrid.init_params(config, jax.random.key(0))
    tokens = jnp.zeros((1, 64), jnp.int32)
    hidden = granite_hybrid.hidden_states(config, params, tokens, jnp.zeros((1, 64), jnp.int32))
    assert hidden.shape == (1, 64, config.hidden_size)


def _dense_counts(seg, block_q, block_kv):
    t = seg.shape[1]
    pos = np.arange(t)
    causal = pos[:, None] >= pos[None, :]
    computed = needed = 0
    for row in seg:
        same = causal & (row[:, None] == row[None, :])
        for i in range(0, t, block_q):
            for j in range(0, t, block_kv):
                computed += bool(causal[i:i + block_q, j:j + block_kv].any())
                needed += bool(same[i:i + block_q, j:j + block_kv].any())
    return computed, needed


def test_block_pair_counts_on_a_hand_made_layout():
    # 8 tokens in blocks of 2: documents of 3, 1 and 4 tokens.
    seg = np.array([[0, 0, 0, 1, 2, 2, 2, 2]])
    # Pairs under the diagonal: 4 + 3 + 2 + 1.  Needed: the diagonal's four,
    # (1, 0) (token 2 with tokens 0-1) and (3, 2) (tokens 6-7 with 4-5).
    assert attention.block_pair_counts(seg, 2, 2) == (10, 6)
    assert attention.block_pair_counts(seg, 4, 2) == (6, 4) == _dense_counts(seg, 4, 2)


@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (128, 256)])
def test_block_pair_counts_match_the_dense_mask(blocks):
    seg = np.concatenate([_segments(lengths) for lengths in LAYOUTS.values()])
    assert attention.block_pair_counts(seg, *blocks) == _dense_counts(seg, *blocks)


def test_kernel_lowers_for_tpu_at_the_cells_shapes_with_the_committed_blocks():
    """JAX-level lowering only (what Mosaic says of it is the chip's to tell):
    32 query / 8 key-value heads of 64, 8192 tokens, forward and both backward kernels."""
    spec = lambda heads: jax.ShapeDtypeStruct((1, 8192, heads, 64), jnp.bfloat16)

    def fn(q, k, v, seg):
        out, vjp = jax.vjp(lambda q, k, v: attention._kernel_path(q, k, v, seg, 0.015625), q, k, v)
        return vjp(out)

    text = jax.jit(fn).trace(spec(32), spec(8), spec(8), jax.ShapeDtypeStruct((1, 8192), jnp.int32)).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") >= 3

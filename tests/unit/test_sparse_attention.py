"""The learned sparse attention's two lowerings against each other
(ops/sparse_attention.py): the TPU kernels of ops/pallas/dsa.py in interpret mode
on the CPU, at tiles small enough that every kernel runs several steps, blocks
above the diagonal and a run that does not start at the first query, against the
``jax.numpy`` lowering; and the kernels' lowering for a TPU at the cell's sizes."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batchai_retinanet_horovod_coco_tpu.ops import sparse_attention as sparse
from batchai_retinanet_horovod_coco_tpu.ops.pallas import dsa

T, HEADS, KV, SIZE, INDEX_HEADS, INDEX_SIZE = 256, 4, 2, 16, 4, 8
TILES = dict(rows=128, scores=64, thresholds=dict(query_tile=32, columns=128), attention=(32, 64), probs=(32, 64))
SEG = np.stack([np.r_[np.zeros(100), np.ones(156)], np.zeros(256)]).astype(np.int32)


def _operands(seed=0):
    rng = np.random.default_rng(seed)
    n = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return (n(2, T, HEADS, SIZE), n(2, T, KV, SIZE), n(2, T, KV, SIZE), n(2, T, INDEX_HEADS, INDEX_SIZE),
            n(2, T, INDEX_SIZE), n(2, T, INDEX_HEADS))


def _step(how, topk, operands):
    def loss(*operands):
        a = sparse.sparse_attention(*operands, jnp.asarray(SEG), topk=topk, scale=0.25, index_scale=0.3, how=how,
                                    q_block=64, with_mask=True, interpret=True, tiles=TILES)
        return jnp.sum(a.out * jnp.cos(a.out)) + 3.0 * a.kl, a

    return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6)), has_aux=True))(*operands)


@pytest.mark.parametrize("topk", [24, 300])
def test_the_kernel_lowering_is_the_xla_lowering(topk):
    """Output, the indexer's loss, the selection, the counters and all six
    gradients (``topk`` 300 keeps every key: no query has more)."""
    operands = _operands()
    (loss_x, x), grads_x = _step(sparse.XLA, topk, operands)
    (loss_k, k), grads_k = _step(sparse.KERNEL, topk, operands)
    np.testing.assert_array_equal(np.asarray(x.mask), np.asarray(k.mask))
    assert float(x.selected) == float(k.selected) == float(np.sum(np.asarray(x.mask)))
    assert float(x.tied) == float(k.tied) and (float(x.tied) == 0) == (topk == 300)
    np.testing.assert_allclose(np.asarray(k.out), np.asarray(x.out), rtol=1e-5, atol=2e-6)
    assert float(k.kl) == pytest.approx(float(x.kl), rel=1e-5) and float(loss_k) == pytest.approx(float(loss_x), rel=1e-5)
    for got, wanted in zip(grads_k, grads_x):
        assert float(jnp.max(jnp.abs(got - wanted))) < 2e-5 * float(jnp.max(jnp.abs(wanted)))


def test_the_threshold_kernel_cuts_ties_at_the_lower_position():
    """Scores made to tie (halves, both zeros): the kernel's thresholds give
    ``lax.top_k``'s selection, for a run of queries that starts at 128 too."""
    rng = np.random.default_rng(1)
    scores = jnp.asarray(np.round(rng.normal(size=(T, T)) * 2) / 2 * rng.choice([1.0, -1.0], (T, 1)), jnp.float32)
    seg = jnp.asarray(SEG[0])
    allowed = sparse.allowed_pairs(seg[None])[0]
    for topk in (1, 16, 50, 300):
        values, index = jax.lax.top_k(jnp.where(allowed, jnp.where(scores == 0, 0.0, scores), -jnp.inf), min(topk, T))
        wanted = jnp.zeros((T, T), bool).at[jnp.arange(T)[:, None], index].set(True) & allowed
        for row0 in (0, 128):
            rows = slice(row0, T)
            found = dsa.thresholds(scores[rows], seg, topk, row0, interpret=True, query_tile=32, columns=128)
            got = sparse.selection_mask(scores[None, rows], seg[None], sparse.Thresholds(*(x[None] for x in found)), rows)
            np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(wanted[rows]))
            assert bool(jnp.any(found[2])) == (topk < 100)


def test_every_blocks_work_is_the_causal_lists_whatever_is_selected():
    """Nothing of the attention kernels' grid or of their index maps reads the
    mask: two selections, one of them empty outside the diagonal's blocks,
    lower to the same program but for the mask's values."""
    q, k, v = (x[0].transpose(1, 0, 2) for x in _operands()[:3])
    text = lambda mask: jax.jit(functools.partial(dsa.masked_attention, interpret=True, tiles=(32, 64))).lower(
        q, k, v, mask).as_text()
    causal = jnp.tril(jnp.ones((T, T), jnp.int8))
    assert text(causal) == text(jnp.eye(T, dtype=jnp.int8))


def _lowers_for_tpu(fn, *specs) -> str:
    """As tests/unit/test_chip_smoke.py lowers its kernels."""
    return jax.jit(fn).trace(*specs).lower(lowering_platforms=("tpu",)).as_text()


def test_the_kernels_lower_for_a_tpu_at_the_cells_sizes():
    """JAX-level Pallas->Mosaic lowering at T = 16 384, 32 / 4 heads of 128, an
    indexer of 16 x 64, a run of 2048 queries from 8192 on.  What Mosaic itself
    says is the cell's own run on the chip."""
    t, rows, row0 = 16384, 2048, 8192
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype)
    bf16, f32 = jnp.bfloat16, jnp.float32
    calls = [
        (lambda q, k, w: dsa.index_scores(q, k, w, 0.03125, row0),
         spec((rows, 16, 64), bf16), spec((t, 64), bf16), spec((rows, 16), f32)),
        (lambda q, k, w, d: dsa.index_scores_bwd(q, k, w, d, 0.03125, row0),
         spec((rows, 16, 64), bf16), spec((t, 64), bf16), spec((rows, 16), f32), spec((rows, t), f32)),
        (lambda s, seg: dsa.thresholds(s, seg, 2048, row0), spec((rows, t), f32), spec((t,), jnp.int32)),
        (lambda q, k, lse, m: dsa.mean_probs(q, k, lse, m, row0),
         spec((32, rows, 128), bf16), spec((4, t, 128), bf16), spec((32, rows), f32), spec((rows, t), jnp.int8)),
        (dsa.masked_attention, spec((32, t, 128), bf16), spec((4, t, 128), bf16), spec((4, t, 128), bf16),
         spec((t, t), jnp.int8)),
        (dsa.masked_attention_bwd, spec((32, t, 128), bf16), spec((4, t, 128), bf16), spec((4, t, 128), bf16),
         spec((t, t), jnp.int8), spec((32, t, 128), bf16), spec((32, t), f32), spec((32, t, 128), bf16)),
    ]
    for fn, *specs in calls:
        assert "tpu_custom_call" in _lowers_for_tpu(fn, *specs)

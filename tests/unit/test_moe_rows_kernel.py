"""ops/pallas/moe_rows.py: the row movements of the routed path as kernels
(interpret mode on the CPU) against the XLA expressions of ops/moe.py they
stand in for: values and every gradient, any share of the buffer routed
here, and nothing behind the routed rows reaching a result."""

import base64
import functools
import hashlib
import json
import os
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batchai_retinanet_horovod_coco_tpu.ops import moe
from batchai_retinanet_horovod_coco_tpu.ops.pallas import moe_rows

T, D, TILE = 96, 256, 32
# Widths that are no whole slab: 384 is 1.5 sublanes of bfloat16 (in a slab of 8: every block in the low halves) and 3
# of float32 (in 8); 1280 is 5 of bfloat16 (in 8: blocks 8 and 9 in the high halves, six behind them that do not
# exist) and 10 of float32 (in 16).
RAGGED = (384, 1280)
FIXTURE = os.path.join(os.path.dirname(__file__), "..", "fixtures", "moe_rows_lowering.json")


def _plan(case: str, k: int, seed: int = 0) -> moe.Plan:
    """A routing over 8 experts of which (0, 1, 2) are held, by case."""
    rng = np.random.default_rng(seed)
    picks = {
        "mixed": lambda: np.stack([rng.permutation(8)[:k] for _ in range(T)]),
        "every_pick_held": lambda: np.stack([rng.permutation(3)[:k] for _ in range(T)]),
        "no_pick_held": lambda: np.stack([3 + rng.permutation(5)[:k] for _ in range(T)]),
        "all_on_one_expert": lambda: np.concatenate([np.full((T, 1), 2), 3 + np.stack(
            [rng.permutation(5)[:k - 1] for _ in range(T)]).reshape(T, k - 1)], axis=1),
    }[case]()
    return moe.dispatch(jnp.asarray(picks, jnp.int32), (0, 1, 2), 8)


def _operands(plan, k, dtype, seed=1, d=D):
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.normal(size=(T, d)), dtype)
    y = jnp.asarray(rng.normal(size=(T * k, d)), dtype)
    y = jnp.where((jnp.arange(T * k) < plan.rows)[:, None], y, 0)  # as ``experts`` hands it over
    weights = jnp.asarray(rng.uniform(0.05, 1.0, size=(T, k)), jnp.float32)
    return u, y, weights


def _small_tiles():
    return mock.patch.multiple(moe, TILE_ROWS=TILE, TOKEN_TILE=TILE)


def _both(fn, *args):
    with _small_tiles():
        return fn(moe.KERNEL, *args), fn(moe.XLA, *args)


def _close(a, b, tol):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert a.shape == b.shape
    assert np.linalg.norm(a - b) <= tol * max(np.linalg.norm(b), 1e-30), (np.linalg.norm(a - b), np.linalg.norm(b))


CASES = [("mixed", 3, D), ("every_pick_held", 3, D), ("no_pick_held", 3, D), ("all_on_one_expert", 3, D), ("mixed", 1, D),
         ("mixed", 5, D), *(("mixed", 3, d) for d in RAGGED)]
# (mixed, 3): 288 rows of which about 108 are held, not a multiple of the tile; (mixed, 5): 480 rows


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6), (jnp.bfloat16, 1e-2)], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case,k,d", CASES, ids=[f"{c}-k{k}" + (f"-d{d}" if d != D else "") for c, k, d in CASES])
def test_both_kernels_are_the_xla_expressions_in_values_and_every_gradient(case, k, d, dtype, tol):
    plan = _plan(case, k)
    u, y, weights = _operands(plan, k, dtype, d=d)
    if case == "mixed":
        assert 0 < int(plan.rows) < T * k and int(plan.rows) % TILE
    rng = np.random.default_rng(2)
    d_buffer = jnp.asarray(rng.normal(size=(T * k, d)), dtype)
    d_tokens = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)

    def gathered(how, u):
        return moe.gather_rows(u, plan, how, interpret=True)

    def combined(how, y, weights):
        return moe.combine(y, plan, weights, how, interpret=True)

    xs_k, xs_x = _both(gathered, u)
    assert xs_k.dtype == xs_x.dtype == u.dtype
    np.testing.assert_array_equal(np.asarray(xs_k, np.float32), np.asarray(xs_x, np.float32))  # a copy is exact
    out_k, out_x = _both(combined, y, weights)
    assert out_k.dtype == out_x.dtype == jnp.float32
    _close(out_k, out_x, tol)
    du_k, du_x = _both(lambda how, u: jax.vjp(lambda u: gathered(how, u), u)[1](d_buffer)[0], u)
    assert du_k.dtype == u.dtype
    _close(du_k, du_x, tol)
    (dy_k, dw_k), (dy_x, dw_x) = _both(
        lambda how, y, w: jax.vjp(lambda y, w: combined(how, y, w), y, w)[1](d_tokens), y, weights)
    assert dy_k.dtype == y.dtype and dw_k.dtype == weights.dtype
    rows = int(plan.rows)
    _close(dy_k[:rows], dy_x[:rows], tol)  # behind them XLA's holds the absent pairs' rows, which ``experts`` masks
    _close(dw_k, dw_x, tol)
    held = np.asarray(plan.inverse).reshape(T, k) < rows
    assert not np.asarray(dw_k)[~held].any() and not np.asarray(dy_k, np.float32)[rows:].any()


def _poisoned_padding(pack):
    """``pack`` whose slabs hold NaN wherever a block of 128 columns does not exist: what ``pack`` wrote there is zeros."""
    def packed(x, live, *, tile, interpret=False):
        slabs = pack(x, live, tile=tile, interpret=interpret)
        sublanes, blocks = slabs.shape[1], x.shape[1] // 128
        block = np.arange(sublanes)
        if x.dtype == jnp.float32:
            nan = np.where(block < blocks, 0, 0x7FC00000)
        else:  # each half of a word by its own block: a high half is poisoned beside a low half that exists
            nan = np.where(block < blocks, 0, 0x7FC0) | np.where(sublanes + block < blocks, 0, 0x7FC00000)
        return slabs | jnp.asarray(nan, jnp.uint32)[None, :, None]

    return packed


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("d", [D, *RAGGED])
def test_a_tail_poisoned_with_nan_changes_no_value_and_no_gradient(d, dtype):
    """What lies behind the routed rows of the experts' output, and of the
    buffer's cotangent, is memory no kernel wrote: NaN there reaches nothing.
    Nor does what lies behind column ``d`` of a slab, in the blocks of 128
    columns that do not exist (every width here has some)."""
    k = 3
    plan = _plan("mixed", k)
    rows = int(plan.rows)
    u, y, weights = _operands(plan, k, dtype, d=d)
    rng = np.random.default_rng(3)
    d_buffer = jnp.asarray(rng.normal(size=(T * k, d)), dtype)
    d_tokens = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    poison = lambda a: a.at[rows:].set(jnp.nan)

    def everything(y, d_buffer):
        with _small_tiles():
            out, vjp = jax.vjp(lambda y, w: moe.combine(y, plan, w, moe.KERNEL, interpret=True), y, weights)
            du = jax.vjp(lambda u: moe.gather_rows(u, plan, moe.KERNEL, interpret=True), u)[1](d_buffer)[0]
            return (out, *vjp(d_tokens), du)

    clean = everything(y, d_buffer)
    assert moe_rows.slab_sublanes(d, dtype) * 128 * 4 > d * jnp.dtype(dtype).itemsize  # the slab is wider than its row
    # ``to_buffer`` and ``to_tokens`` look ``pack`` up when they are traced: unjitted, at every call
    with mock.patch.multiple(moe_rows, pack=_poisoned_padding(moe_rows.pack), to_buffer=moe_rows.to_buffer.__wrapped__,
                             to_tokens=moe_rows.to_tokens.__wrapped__):
        poisoned = everything(poison(y), poison(d_buffer))
    for name, a, b in zip(("combine", "dy", "dweights", "du"), clean, poisoned):
        assert np.isfinite(np.asarray(b, np.float32)).all(), name
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32), err_msg=name)


def test_combine_in_float32_is_the_pick_order_sum_bit_for_bit():
    """``((0 + w_0 y_0) + w_1 y_1) + ...`` in float32, pick by pick: equal in
    every bit to that chain with each product and sum rounded apart (a
    vector unit without a fused multiply-add: the chip's) or rounded once (a
    compiler that contracts them: the interpreter's, here); the same chain from
    the last pick down differs under either."""
    k = 5
    picks = np.stack([np.random.default_rng(t).permutation(8)[:k] for t in range(T)])
    plan = moe.dispatch(jnp.asarray(picks, jnp.int32), tuple(range(8)), 8)
    _, y, weights = _operands(plan, k, jnp.float32)
    with _small_tiles():
        out = np.asarray(moe.combine(y, plan, weights, moe.KERNEL, interpret=True))
    by_pair, w = np.asarray(y)[np.asarray(plan.inverse)].reshape(T, k, D), np.asarray(weights)

    def chain(order, contracted):
        acc = np.zeros((T, D), np.float32)
        for j in order:
            if contracted:  # the product of two float32 is exact in float64
                acc = (w[:, j, None].astype(np.float64) * by_pair[:, j] + acc).astype(np.float32)
            else:
                acc = acc + w[:, j, None] * by_pair[:, j]
        return acc

    assert any(np.array_equal(out, chain(range(k), contracted)) for contracted in (False, True))
    assert all((out != chain(range(k)[::-1], contracted)).any() for contracted in (False, True))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("live,d", [*((live, D) for live in (0, 1, 40, 64, 96)), *((40, d) for d in RAGGED)])
def test_pack_writes_the_slabs_of_the_live_rows_and_unpacks_to_the_same_bits(live, d, dtype):
    """A row's slab holds its bits (two bfloat16 columns a word: block ``c``
    of 128 columns low and block ``S + c`` high) and zeros in the blocks that
    do not exist; ``to_buffer`` of the identity unpacks what ``pack`` packed;
    rows behind the last live tile are not written."""
    x = jnp.asarray(np.random.default_rng(5).normal(size=(T, d)), dtype)
    slabs = moe_rows.pack(x, jnp.asarray(live, jnp.int32), tile=TILE, interpret=True)
    sublanes = moe_rows.slab_sublanes(d, dtype)
    assert slabs.shape == (T, sublanes, 128) and slabs.dtype == jnp.uint32
    words = np.asarray(slabs).reshape(T, sublanes * 128)[:live]
    bits = np.asarray(x.astype(jnp.float32))[:live].view(np.uint32)
    halves = 1 if dtype == jnp.float32 else 2
    padded = np.zeros((live, halves * sublanes * 128), np.uint32)  # the blocks that do not exist hold zeros
    padded[:, :d] = bits if dtype == jnp.float32 else bits >> 16
    assert padded.shape[1] > d
    np.testing.assert_array_equal(
        words, padded if dtype == jnp.float32 else padded[:, :sublanes * 128] | (padded[:, sublanes * 128:] << 16))
    same = moe_rows.to_buffer(x, jnp.arange(T, dtype=jnp.int32), jnp.asarray(live, jnp.int32), dtype, tile=TILE,
                              interpret=True)
    np.testing.assert_array_equal(np.asarray(same, np.float32)[:live], np.asarray(x, np.float32)[:live])
    assert not np.asarray(same, np.float32)[live:].any()


def test_a_row_that_is_not_whole_lanes_is_refused_and_a_slab_is_whole_tiles_of_eight_sublanes():
    with pytest.raises(ValueError, match="whole lanes"):
        moe_rows.slab_sublanes(200, jnp.float32)
    with pytest.raises(ValueError, match="whole lanes"):
        moe_rows.slab_sublanes(2688 + 64, jnp.bfloat16)
    with pytest.raises(ValueError, match="whole lanes"):
        moe_rows.slab_sublanes(256, jnp.int8)
    with pytest.raises(ValueError, match="bfloat16"):
        moe_rows.slab_sublanes(256, jnp.float16)
    sublanes = {d: (moe_rows.slab_sublanes(d, jnp.bfloat16), moe_rows.slab_sublanes(d, jnp.float32))
                for d in (128, 384, 1024, 1280, 2048, 2688, 4096)}
    assert sublanes == {128: (8, 8), 384: (8, 8), 1024: (8, 8), 1280: (8, 16), 2048: (8, 16),  # 2048, 4096: as before PR 33
                        2688: (16, 24), 4096: (16, 32)}


@functools.lru_cache  # two tests read the text at 2048 columns
def _lowered_for_tpu(d: int) -> str:
    """JAX-level lowering only (what Mosaic says of it is the chip's to tell):
    16 384 tokens of ``d`` columns, 6 picks, forward and backward of both movements:
    ``pack`` + ``to_buffer``, ``pack`` + ``to_tokens``, and their transposes."""
    tokens, k = 16384, 6
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype)
    plan = moe.Plan(spec((tokens * k,), jnp.int32), spec((tokens * k,), jnp.int32), spec((8,), jnp.int32),
                    spec((), jnp.int32))

    def fn(u, y, weights, plan):
        xs, back = jax.vjp(lambda u: moe.gather_rows(u, plan, moe.KERNEL), u)
        out, back_c = jax.vjp(lambda y, w: moe.combine(y, plan, w, moe.KERNEL), y, weights)
        return back(xs), back_c(out)

    return jax.jit(fn).trace(spec((tokens, d), jnp.bfloat16), spec((tokens * k, d), jnp.bfloat16),
                             spec((tokens, k), jnp.float32), plan).lower(lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("d", [2048, 2688], ids=["dsv2", "nemotron"])
def test_the_kernels_lower_for_tpu_at_the_cells_shapes_under_names_the_products_reader_skips(d):
    text = _lowered_for_tpu(d)
    assert len(re.findall(r"stablehlo\.custom_call @tpu_custom_call", text)) == 8
    names = set(re.findall(r'kernel_name = "([^"]+)"', text))
    assert names == {"moe_rows_pack", "moe_rows_to_buffer", "moe_rows_to_tokens"}
    assert not any(re.match(r"^t?gmm(\.\d+)?$", n) for n in names)  # benchmark/harness/moe_lm_trace.py::GMM_PATTERN


def _without_locations(text: str) -> str:
    """``text`` with every Mosaic kernel's body, which is MLIR bytecode that
    holds the file, line and column of each operation, printed without them: a
    comment added to moe_rows.py, or a checkout elsewhere, changes nothing."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def body(match):
        context = mlir.make_ir_context()
        context.allow_unregistered_dialects = True
        with context:
            return ir.Module.parse(base64.b64decode(match.group(1))).operation.get_asm(enable_debug_info=False)

    text, bodies = re.subn(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body, text)
    assert bodies == 8
    return text


def test_dsv2s_kernels_lower_to_the_text_recorded_before_pr_33():
    """A slab may be wider than its row since PR 33, by Python branches on
    static shapes: at 2048 columns, where it is not, the three kernels and what
    surrounds them are the text PR 32's tree (68bb241) lowered to, recorded
    there before moe_rows.py was touched (this file's ``_lowered_for_tpu(2048)``
    and ``_without_locations`` with that tree on the path).  A later PR that
    means to change dsv2's kernels records it again the same way and says so."""
    with open(FIXTURE) as f:
        recorded = json.load(f)
    assert hashlib.sha256(_without_locations(_lowered_for_tpu(2048)).encode()).hexdigest() == recorded["d=2048"]

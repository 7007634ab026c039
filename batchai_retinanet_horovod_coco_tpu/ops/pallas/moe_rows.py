"""The row movements of ops/moe.py's routed path as Pallas TPU kernels whose
work follows ``rows``, the rows really routed to this chip, read on the
device as megablox reads its group sizes:

    to the buffer   out[r] = scale[r] * src[token[r]]                   for r < rows, zeros behind
    to the tokens   out[t] = sum over j of w[t, j] * buf[inverse[t, j]]   over the picks with inverse < rows

``to_buffer`` is ``dispatch`` forward (no scale) and ``combine`` backward
(``src`` the output's cotangent, ``scale`` the pair's weight; the same pass
holds the tile of ``y`` and so gives the weight's gradient ``<y[r],
src[token[r]]>`` as one float per row).  ``to_tokens`` is ``combine`` forward
and ``dispatch`` backward (``w`` = 1).  A row is fetched from HBM by one
asynchronous copy; a tile's copies are all in flight before the first is
waited for; a tile of the buffer or a pair that is not routed here starts
none.  The weighing and adding happen on the tile in VMEM, in float32, in
pick order: no ``(tokens, k, d)`` array exists in HBM.

**Slabs.**  Mosaic slices an HBM array only at whole (8, 128) tiles of its
last two dimensions, so a single row of a ``(n, d)`` array cannot be copied.
Rows are therefore gathered from a *slab* copy ``(n, S, 128)`` uint32 in which
a row is ``S`` whole sublanes and contiguous, ``S`` the row's words rounded UP
to whole tiles of eight sublanes: a 32-bit row as it is (block ``c`` of 128
columns on sublane ``c``), a 16-bit row with block ``c`` in the low and block
``S + c`` in the high half of a word (unpacking bfloat16 is then a shift or a
mask, and both halves stay on their lanes).  **A slab may be wider than its
row**: 2048 columns are 8 sublanes of bfloat16 and 16 of float32 and fill
their slab, Nemotron-H's 2688 are 10.5 and 21 and lie in slabs of 16 (blocks
0-15 low, 16-20 high, the eleven behind them do not exist) and 24.  ``pack``
writes zeros where a block does not exist, the other kernels neither store
such a block nor add it to the weight's gradient: what lies behind column
``d`` of a slab reaches no result.  All of that is Python on static shapes; a
width that fills its slabs traces to what it traced to before slabs could be
wider (a test holds the lowered text at 2048 columns).  A width need only be
whole lanes; a narrow row pays for its padding (1024 columns of bfloat16 are
4 sublanes in a slab of 8: ``pack`` writes, and a copy moves, twice the
bytes; not measured).  ``pack`` writes the slabs of the first ``live`` rows
and touches nothing behind them.  The way back to rows by columns is a
strided load: sublane ``c`` of every slab of a tile.

**Walking the held pairs alone.**  ``to_tokens`` sorts the pairs of each tile
of tokens, held ones first and in their order (one XLA sort of (tiles, tile x
k) keys with rows, tokens and weights riding along), and hands the kernel the
count: its three loops (start the copies, wait, add) run over the held pairs
and nothing else.  A token's held pairs follow each other in pick order, so
its sum rides in registers; tokens with no held pair keep the zeros the tile
started from.

**Behind the routed rows.**  ``to_buffer`` writes zeros there without reading
anything; ``pack`` and ``to_tokens`` never read there (so what ``pack`` leaves
behind ``live`` is whatever memory held).  A tile of the buffer that straddles
``rows`` is computed whole and its surplus rows are replaced by zeros with a
select, never multiplied by zero: a NaN behind the routed rows reaches no result.

MEASURED (v5e-1, PR 31; 16 384 tokens of 2048 bfloat16, k 6, so 98 304 rows; ms
a call with the packing each needs, at 12.5 / 18 / 50 / 100% of the buffer routed
here; XLA's gathers they stand in for take 2.0 / 7.6 / 7.1 / 17.6 at any share):
  to_buffer            0.91 / 0.93 / 1.11 / 1.37     (tile 256: the same within 0.02)
  to_buffer, weighted  1.52 / 1.65 / 2.40 / 3.61     (float32 rows of 8 KB, the tile of y, a float a row)
  to_tokens            0.78-0.91 / 0.94-1.04 / 1.98 / 3.59   (tiles of 64, 128, 256 tokens within 0.04)
  those at eight rows a trip of ``_each``; with no unrolling to_buffer 0.97-1.88 and
  to_tokens 1.0-5.55; before the pairs were sorted (the scalar core visiting all 98 304,
  the vector unit all k slots) to_tokens 2.3-3.9 whatever was held.
  One expert layer forward + backward, ms: XLA's row path 41.1 / 42.7 / 51.7 / 65.8;
  eight rows a trip 18.27 / 20.40 / 32.39 / 51.50; FOUR, as committed, 18.28 / 20.49 /
  32.84 / 52.36 in the same call.  Four and not eight because the step is traced and
  lowered twice a run and every unrolled row is traced again: on the chip's host the
  two lowerings take 4.14 + 1.57 s with XLA's row path, 5.70 + 2.22 with no
  unrolling, 6.43 + 2.52 at four, 7.07 + 3.02 at eight (it is ``setup_s``).  The
  three entry points are jitted for the same reason: traced once a process.
MEASURED (v5e-1, PR 33; 2688 bfloat16 columns, slabs of 16 and 24 sublanes, beside 2048
in the same call; ms a call with its packing, the host's clock over ten calls, at 6 /
18 / 50 / 100% of the buffer routed here; XLA's expression at any share behind it):
  to_buffer            1.34 / 1.59 / 2.24 / 3.18   (2048: 1.00 / 1.17 / 1.63 / 2.35)   2.50
  to_tokens, w = 1     0.81 / 1.25 / 2.62 / 4.77   (2048: 0.57 / 0.91 / 2.03 / 3.80)   9.0
  to_tokens            0.95 / 1.46 / 2.65 / 4.83   (2048: 0.68 / 0.98 / 2.05 / 3.80)   9.5
  to_buffer, weighted  1.92 / 2.27 / 3.22 / 4.67   (2048: 1.55 / 1.83 / 2.67 / 3.94)   22.3
  1.18-1.49 x a call at 2048 for 1.31 x the columns in slabs of 2 x and 1.5 x the words.

The names of the compiled kernels (``moe_rows_pack``, ``moe_rows_to_buffer``,
``moe_rows_to_tokens``) are what a trace shows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# Rows per trip of a kernel's loops over rows (Mosaic unrolls a loop whole or not at all).
_UNROLL = 4
_HIGH_HALF = 0xFFFF0000


def slab_sublanes(d: int, dtype) -> int:
    """Sublanes ``S`` of one row's slab: the row's words in whole tiles of
    eight sublanes (the blocks of 128 columns behind column ``d`` do not
    exist); raises where a row is not whole lanes of 16- or 32-bit columns."""
    itemsize = jnp.dtype(dtype).itemsize
    if itemsize not in (2, 4) or d % LANES:
        raise ValueError(f"a row of {d} x {jnp.dtype(dtype).name} is not whole lanes of 16- or 32-bit columns")
    if itemsize == 2 and jnp.dtype(dtype) != jnp.bfloat16:
        raise ValueError("the only 16-bit rows are bfloat16")
    return pl.cdiv(d * itemsize, 4 * LANES * 8) * 8


def _bits(x):
    return pltpu.bitcast(x.astype(jnp.float32), jnp.uint32)


def _unpacked(words, dtype):
    """The float32 values that slab words hold: one array of a 32-bit dtype,
    of bfloat16 the low halves (the first S blocks of 128 columns) and the high halves."""
    if jnp.dtype(dtype).itemsize == 4:
        return (pltpu.bitcast(words, jnp.float32),)
    return pltpu.bitcast(words << 16, jnp.float32), pltpu.bitcast(words & jnp.uint32(_HIGH_HALF), jnp.float32)


def _last_live(live_ref, tile: int):
    """The last tile that holds a live row (0 where none does): steps behind it
    ask for that block again, which moves nothing."""
    return jnp.maximum((live_ref[0] + tile - 1) // tile - 1, 0)


_COMPILER_PARAMS = pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=64 * 1024 * 1024)


# ---- rows by columns -> slabs ---------------------------------------------


def _pack_kernel(live_ref, x_ref, out_ref, *, tile, sublanes):
    d = x_ref.shape[1]

    @pl.when(pl.program_id(0) * tile < live_ref[0])
    def _():
        for c in range(sublanes):
            if c * LANES >= d:  # a block that does not exist, and in 16 bits the one above it
                words = jnp.zeros((tile, LANES), jnp.uint32)
            elif x_ref.dtype.itemsize == 4:
                words = pltpu.bitcast(x_ref[:, c * LANES:(c + 1) * LANES], jnp.uint32)
            else:
                words = _bits(x_ref[:, c * LANES:(c + 1) * LANES]) >> 16
                if (sublanes + c) * LANES < d:
                    high = _bits(x_ref[:, (sublanes + c) * LANES:(sublanes + c + 1) * LANES]) & jnp.uint32(_HIGH_HALF)
                    words = high | words
            out_ref[pl.ds(c, tile, stride=sublanes), :] = words


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def pack(x, live, *, tile: int, interpret: bool = False):
    """``x`` (n, d) -> its slabs (n, S, 128) uint32 for the rows before
    ``live`` (a traced int32 scalar, or None for all); what lies behind the
    last live tile is not written."""
    n, d = x.shape
    sublanes = slab_sublanes(d, x.dtype)
    live = jnp.full((1,), n, jnp.int32) if live is None else jnp.reshape(live, (1,)).astype(jnp.int32)
    out = pl.pallas_call(
        functools.partial(_pack_kernel, tile=tile, sublanes=sublanes),
        out_shape=jax.ShapeDtypeStruct((n * sublanes, LANES), jnp.uint32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n // tile,),
            in_specs=[pl.BlockSpec((tile, d), lambda i, live: (jnp.minimum(i, _last_live(live, tile)), 0))],
            out_specs=pl.BlockSpec((tile * sublanes, LANES), lambda i, live: (jnp.minimum(i, _last_live(live, tile)), 0))),
        compiler_params=_COMPILER_PARAMS, interpret=interpret, name="moe_rows_pack",
    )(live, x)
    return out.reshape(n, sublanes, LANES)


# ---- to the buffer ----------------------------------------------------------


def _each(count, body, carry=0):
    """``carry = body(q, carry)`` for q in [0, count), ``_UNROLL`` to a trip so
    that the trips' scalar loads, copies and vector work overlap, the rest one by one."""
    def trip(t, carry):
        for u in range(_UNROLL):
            carry = body(t * _UNROLL + u, carry)
        return carry

    whole = count // _UNROLL
    carry = jax.lax.fori_loop(0, whole, trip, carry)
    if isinstance(count, int) and count % _UNROLL == 0:
        return carry
    return jax.lax.fori_loop(whole * _UNROLL, count, body, carry)


def _row_copy(src, row, scratch, slot, sublanes, sem):
    return pltpu.make_async_copy(src.at[row], scratch.at[pl.ds(pl.multiple_of(slot * sublanes, sublanes), sublanes)], sem)


def _to_buffer_kernel(rows_ref, token_ref, src, *rest, tile, sublanes, src_dtype, weighted):
    if weighted:
        scale_ref, y_ref, out_ref, dscale_ref, scratch, sem = rest
    else:
        out_ref, scratch, sem = rest
    start = pl.program_id(0) * tile
    rows = rows_ref[0]

    @pl.when(start < rows)
    def _():
        def issue(q, carry):
            _row_copy(src, token_ref[0, 0, q], scratch, q, sublanes, sem).start()
            return carry

        def wait(q, carry):
            _row_copy(src, 0, scratch, 0, sublanes, sem).wait()
            return carry

        _each(tile, issue)
        _each(tile, wait)
        live = start + jax.lax.broadcasted_iota(jnp.int32, (tile, LANES), 0) < rows
        if weighted:
            # (1, tile) on lanes -> the same numbers down the sublanes, on every lane
            scale = jnp.broadcast_to(scale_ref[0], (LANES, tile)).T
            partial = jnp.zeros((tile, LANES), jnp.float32)
        d = out_ref.shape[1]
        for c in range(min(sublanes, d // LANES)):  # sublane c of every slab: block c and, of bfloat16, block S + c
            for half, value in enumerate(_unpacked(scratch[pl.ds(c, tile, stride=sublanes), :], src_dtype)):
                first = (half * sublanes + c) * LANES
                if first >= d:  # a block that does not exist
                    continue
                if weighted:
                    partial = partial + y_ref[:, first:first + LANES].astype(jnp.float32) * value
                    value = scale * value
                out_ref[:, first:first + LANES] = jnp.where(live, value, 0.0).astype(out_ref.dtype)
        if weighted:
            by_row = jnp.sum(jnp.where(live, partial, 0.0).T, axis=0, keepdims=True)  # (1, tile)
            dscale_ref[0] = by_row

    @pl.when(start >= rows)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)
        if weighted:
            dscale_ref[...] = jnp.zeros_like(dscale_ref)


@functools.partial(jax.jit, static_argnames=("out_dtype", "tile", "interpret"))
def to_buffer(src, token, rows, out_dtype, *, tile: int, scale=None, y=None, interpret: bool = False):
    """``src`` (n, d), ``token`` (R,) int32 (the row of ``src`` that row ``r``
    of the buffer takes), ``rows`` () int32 -> (R, d) in ``out_dtype``:
    ``src[token[r]]`` for ``r < rows``, zeros behind.  With ``scale`` (R,)
    float32 and ``y`` (R, d): ``scale[r] * src[token[r]]`` rounded once, and
    beside it ``sum_d y[r, d] * src[token[r], d]`` (R,) float32, zeros behind."""
    (n, d), total = src.shape, token.shape[0]
    weighted = scale is not None
    sublanes = slab_sublanes(d, src.dtype)
    slabs = pack(src, None, tile=min(tile, n), interpret=interpret)
    tiles = total // tile
    by_tile = lambda i, rows: (i, 0, 0)
    live_tile = lambda i, rows: (jnp.minimum(i, _last_live(rows, tile)), 0)
    in_specs = [pl.BlockSpec((1, 1, tile), by_tile, memory_space=pltpu.SMEM), pl.BlockSpec(memory_space=pl.ANY)]
    operands = [token.reshape(tiles, 1, tile), slabs]
    out_shape = [jax.ShapeDtypeStruct((total, d), out_dtype)]
    out_specs = [pl.BlockSpec((tile, d), lambda i, rows: (i, 0))]
    if weighted:
        in_specs += [pl.BlockSpec((1, 1, tile), by_tile), pl.BlockSpec((tile, d), live_tile)]
        operands += [scale.astype(jnp.float32).reshape(tiles, 1, tile), y]
        out_shape.append(jax.ShapeDtypeStruct((tiles, 1, tile), jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, tile), by_tile))
    out = pl.pallas_call(
        functools.partial(_to_buffer_kernel, tile=tile, sublanes=sublanes, src_dtype=src.dtype, weighted=weighted),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(tiles,), in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((tile * sublanes, LANES), jnp.uint32), pltpu.SemaphoreType.DMA(())]),
        compiler_params=_COMPILER_PARAMS, interpret=interpret, name="moe_rows_to_buffer",
    )(jnp.reshape(rows, (1,)).astype(jnp.int32), *operands)
    return (out[0], out[1].reshape(total)) if weighted else out[0]


# ---- to the tokens ------------------------------------------------------------


def _to_tokens_kernel(held_ref, row_ref, token_ref, weight_ref, buf, out_ref, scratch, acc, sem, *, tile, sublanes,
                      buf_dtype):
    held = held_ref[pl.program_id(0)]
    wide = acc.shape[0] // tile  # sublanes of a token's float32 row: column c x 128 + lane on sublane c

    def issue(q, carry):
        _row_copy(buf, row_ref[0, 0, q], scratch, q, sublanes, sem).start()
        return carry

    def wait(q, carry):
        _row_copy(buf, 0, scratch, 0, sublanes, sem).wait()
        return carry

    _each(held, issue)
    acc[...] = jnp.zeros_like(acc)
    _each(held, wait)

    def add(q, carry):
        """The held pairs of a token follow each other in pick order: its sum
        so far rides in registers and is stored after every pair, the last
        store holding the whole sum; nothing is read back from ``acc``."""
        last, sums = carry
        token, weight = token_ref[0, 0, q], weight_ref[0, 0, q]
        rows = _unpacked(scratch[pl.ds(pl.multiple_of(q * sublanes, sublanes), sublanes), :], buf_dtype)
        sums = tuple(jnp.where(token == last, total, 0.0) + weight * row for total, row in zip(sums, rows))
        first = pl.multiple_of(token * wide, wide)
        for i, total in enumerate(sums):
            acc[pl.ds(first + i * total.shape[0], total.shape[0]), :] = total
        return token, sums

    zero = jnp.zeros((sublanes, LANES), jnp.float32)
    _each(held, add, (jnp.int32(-1), (zero,) * (wide // sublanes)))
    for c in range(out_ref.shape[1] // LANES):  # the blocks that exist
        out_ref[:, c * LANES:(c + 1) * LANES] = acc[pl.ds(c, tile, stride=wide), :].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "tile", "buffer_tile", "interpret"))
def to_tokens(buf, inverse, weights, rows, out_dtype, *, tile: int, buffer_tile: int, interpret: bool = False):
    """``buf`` (R, d), ``inverse`` (n x k,) int32 (the row of pair ``t k + j``),
    ``weights`` (n, k) float32 -> (n, d) in ``out_dtype``: for every token its
    picks' rows BEFORE ``rows``, weighed and added in float32 in pick order,
    rounded once.  The pairs of a tile of tokens are sorted, held ones first
    and in their order, so the kernel walks the held pairs alone."""
    (total, d), (n, k) = buf.shape, weights.shape
    sublanes = slab_sublanes(d, buf.dtype)
    wide = sublanes * 4 // buf.dtype.itemsize  # sublanes of a token's float32 sum: a slab's halves on end
    slabs = pack(buf, rows, tile=buffer_tile, interpret=interpret)
    tiles, pairs = n // tile, tile * k
    by_tile = inverse.reshape(tiles, pairs)
    absent = (by_tile >= rows).astype(jnp.int32)
    token = jax.lax.broadcasted_iota(jnp.int32, (tiles, pairs), 1) // k  # within the tile
    _, row, token, weight = jax.lax.sort((absent, by_tile, token, weights.astype(jnp.float32).reshape(tiles, pairs)),
                                         dimension=1, is_stable=True, num_keys=1)
    held = pairs - jnp.sum(absent, axis=1, dtype=jnp.int32)
    in_smem = pl.BlockSpec((1, 1, pairs), lambda i, held: (i, 0, 0), memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_to_tokens_kernel, tile=tile, sublanes=sublanes, buf_dtype=buf.dtype),
        out_shape=jax.ShapeDtypeStruct((n, d), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(tiles,),
            in_specs=[in_smem, in_smem, in_smem, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, d), lambda i, held: (i, 0)),
            scratch_shapes=[pltpu.VMEM((pairs * sublanes, LANES), jnp.uint32),
                            pltpu.VMEM((tile * wide, LANES), jnp.float32), pltpu.SemaphoreType.DMA(())]),
        compiler_params=_COMPILER_PARAMS, interpret=interpret, name="moe_rows_to_tokens",
    )(held, row.reshape(tiles, 1, pairs), token.reshape(tiles, 1, pairs), weight.reshape(tiles, 1, pairs), slabs)

"""The chunked state-space scan of a Mamba-2 mixer, with state reset at
document boundaries.

The recurrence, per head (``X_t`` of size P, ``B_t``/``C_t`` of size N,
``a_t = exp(dt_t * A)``; the heads share ``B`` and ``C`` in ``G`` groups of
consecutive heads, head ``h`` reading group ``h // (H / G)``; Granite 4.0-H
has one group, Nemotron-H eight):

    S_t = a_t * S_{t-1} + dt_t * X_t (x) B_t,   S = 0 at a document's first token
    Y_t = S_t C_t

computed chunk by chunk (the "state-space duality" form of Dao & Gu 2024,
arXiv:2405.21060 section 6): inside a chunk the contribution of token j to
token i is ``(C_i . B_j) * exp(cum_i - cum_j) * dt_j * X_j``, a masked
matrix product; across chunks each chunk's end state is carried on by the
product of the chunks' decays.  Documents are contiguous runs of one
segment id, so "no reset between j and i" is ``seg_j == seg_i`` and every
reset is a mask: nothing is ever divided by a decay or exponentiated above
zero.

Precision: decays, their cumulative sums, the masks and the carried state
are float32; matrix-multiplication operands are rounded to ``x``'s dtype
and accumulate in float32.

One algorithm, two lowerings; ``lowering`` says which runs, from the backend
and the shapes alone:

- ``xla``: the body below, batched matmuls and fused elementwise work.  The
  (chunks, heads, chunk, chunk) masks, decays and weights and every chunk's
  state go through HBM, and the (tokens, heads x head size) operands change
  layout between the three einsums.
- ``kernel``: ops/pallas/ssd.py, a forward and a backward Pallas TPU kernel
  that take over after the cumulative sums: a block of heads' chunks run in
  order with the state in VMEM (the product across chunks becomes that
  order; float32 as here), and decays and weights never leave VMEM.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from batchai_retinanet_horovod_coco_tpu.ops.pallas import ssd as ssd_kernel

KERNEL, XLA = "kernel", "xla"
_NEG_INF = -jnp.inf


def lowering(backend: str, seq_len: int, chunk: int, heads: int, head_dim: int, state: int, groups: int = 1) -> str:
    """``kernel`` where the scan's kernels can run: a TPU backend, a sequence
    of whole chunks, and whole tiles (chunks and states of whole lane tiles,
    heads of whole sublane tiles, whole blocks of heads, each holding whole
    groups or lying inside one); ``xla`` everywhere else (the CPU, the tiny
    presets' chunks of 8, a ragged sequence)."""
    whole_tiles = (chunk % 128 == 0 and state % 128 == 0 and head_dim % 16 == 0
                   and heads % ssd_kernel.HEADS_PER_BLOCK == 0
                   and ssd_kernel.groups_per_block(heads, groups, ssd_kernel.HEADS_PER_BLOCK) is not None)
    return KERNEL if backend == "tpu" and seq_len > 0 and seq_len % chunk == 0 and whole_tiles else XLA


def ssd_chunked(
    x: jnp.ndarray,
    dt: jnp.ndarray,
    a_log_decay: jnp.ndarray,
    b: jnp.ndarray,
    c: jnp.ndarray,
    segment_ids: jnp.ndarray,
    chunk: int,
) -> jnp.ndarray:
    """``Y`` of the recurrence above (without the ``D`` skip).

    x: (batch, T, H, P); dt: (batch, T, H) float32, after softplus;
    a_log_decay: (H,) float32, ``A = -exp(A_log)``; b, c: (batch, T, N), one
    group, or (batch, T, G, N);
    segment_ids: (batch, T) int, constant over a document and different in
    neighbouring documents; chunk: tokens per chunk, any positive number
    (T is padded up to a multiple with tokens of no document).
    Returns (batch, T, H, P) float32.
    """
    _, t, heads, p = x.shape
    groups = 1 if b.ndim == 3 else b.shape[2]
    kernel = lowering(jax.default_backend(), t, chunk, heads, p, b.shape[-1], groups) == KERNEL
    return _chunked(x, dt, a_log_decay, b, c, segment_ids, chunk, ssd_kernel.chunked_scan if kernel else None)


def _chunked(x, dt, a_log_decay, b, c, segment_ids, chunk, scan_kernel):
    batch, t, heads, p = x.shape
    n = b.shape[-1]
    if b.ndim == 4 and scan_kernel is None:
        # XLA: each group's heads are a scan of their own over that group's B and C.
        groups = b.shape[2]
        by_group = lambda a: a.reshape(*a.shape[:2], groups, heads // groups, *a.shape[3:])
        y = jax.vmap(lambda x, dt, a, b, c: _chunked(x, dt, a, b, c, segment_ids, chunk, None),
                     in_axes=(2, 2, 0, 2, 2), out_axes=2)(
            by_group(x), by_group(dt), a_log_decay.reshape(groups, -1), b, c)
        return y.reshape(batch, t, heads, p)
    pad = -t % chunk
    if pad:
        # Padding: a document of its own that contributes nothing (dt = 0).
        widths = lambda a: [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)
        x, dt, b, c = (jnp.pad(a, widths(a)) for a in (x, dt, b, c))
        segment_ids = jnp.pad(segment_ids, [(0, 0), (0, pad)], constant_values=-1)
    nc = (t + pad) // chunk
    dtype = x.dtype
    x = x.reshape(batch, nc, chunk, heads, p)
    dt = dt.astype(jnp.float32).reshape(batch, nc, chunk, heads)
    b = b.reshape(batch, nc, chunk, *b.shape[2:])  # (..., n), or (..., groups, n) for the kernels
    c = c.reshape(batch, nc, chunk, *c.shape[2:])
    seg = segment_ids.reshape(batch, nc, chunk)

    log_a = dt * a_log_decay.astype(jnp.float32)  # (b, c, l, h), <= 0
    cum = jnp.cumsum(log_a, axis=2)  # inclusive: log of a_1 ... a_l
    if scan_kernel is not None:  # everything below, in one kernel (ops/pallas/ssd.py)
        return scan_kernel(x, dt, cum, b, c, seg).reshape(batch, nc * chunk, heads, p)[:, :t]
    xdt = (x.astype(jnp.float32) * dt[..., None]).astype(dtype)  # dt_j X_j

    # Inside a chunk: i >= j and the same document.
    idx = jnp.arange(chunk)
    within = (idx[:, None] >= idx[None, :]) & (seg[:, :, :, None] == seg[:, :, None, :])  # (b,c,i,j)
    cum_h = jnp.moveaxis(cum, 3, 2)  # (b, c, h, l)
    decay = jnp.exp(jnp.where(within[:, :, None], cum_h[..., :, None] - cum_h[..., None, :], _NEG_INF))
    cb = jnp.einsum("bcin,bcjn->bcij", c, b, preferred_element_type=jnp.float32)
    weights = (cb[:, :, None] * decay).astype(dtype)  # (b, c, h, i, j)
    y = jnp.einsum("bchij,bcjhp->bcihp", weights, xdt, preferred_element_type=jnp.float32)

    # Each chunk's end state from its own tokens of the chunk's last document.
    seg_last = seg[:, :, -1]  # (b, c)
    to_end = jnp.exp(jnp.where((seg == seg_last[..., None])[..., None],
                               cum[:, :, -1:, :] - cum, _NEG_INF))  # (b, c, l, h)
    xdt_end = (xdt.astype(jnp.float32) * to_end[..., None]).astype(dtype)
    states = jnp.einsum("bcjhp,bcjn->bchpn", xdt_end, b, preferred_element_type=jnp.float32)

    # Across chunks: the state at the end of chunk m is the sum over c <= m
    # of chunk c's own end state, decayed by the chunks between, where no
    # document ended between (the last tokens share a document).
    total = jnp.cumsum(cum[:, :, -1, :], axis=1)  # (b, c, h)
    ci = jnp.arange(nc)
    carried = (ci[:, None] >= ci[None, :]) & (seg_last[:, :, None] == seg_last[:, None, :])  # (b, m, c)
    total_h = jnp.moveaxis(total, 2, 1)  # (b, h, c)
    across = jnp.exp(jnp.where(carried[:, None], total_h[..., :, None] - total_h[..., None, :], _NEG_INF))
    ends = jnp.einsum("bhmc,bchpn->bmhpn", across, states, precision=lax.Precision.HIGHEST)
    # What chunk m starts from is where chunk m - 1 ended; it reaches token
    # i of chunk m where i is still in that document.
    start = jnp.pad(ends[:, :-1], [(0, 0), (1, 0), (0, 0), (0, 0), (0, 0)])
    seg_before = jnp.pad(seg_last[:, :-1], [(0, 0), (1, 0)], constant_values=-2)
    reach = jnp.exp(jnp.where((seg == seg_before[..., None])[..., None], cum, _NEG_INF))  # (b, c, l, h)
    from_start = jnp.einsum("bcin,bchpn->bcihp", c, start.astype(dtype), preferred_element_type=jnp.float32)
    y = y + from_start * reach[..., None]
    return y.reshape(batch, nc * chunk, heads, p)[:, :t]

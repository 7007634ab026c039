"""A routed expert layer for a chip that holds a SHARE of the experts:

    F(u) = sum over e in top_k(u) and in held of  w_e(u) * E_e(u)
    E_e(u) = W_down,e (silu(W_gate,e u) * W_up,e u)       (``experts``: DeepSeek-V2's)
          or W_down,e relu(W_up,e u)^2                    (``experts_relu2``: Nemotron-H's)

A router scores every token over ALL the experts of the model (it is whole
on every chip) and picks ``k`` of them: ``route`` is a softmax whose ``k``
largest scores are the weights as they are (``route_renormalised``: divided
by their sum, Qwen3-MoE's ``norm_topk_prob``); ``route_sigmoid`` scores by a
sigmoid, picks the ``k`` largest of score + a selection bias, and weighs by
the picked scores (without the bias) normalised to sum to a scale.
``dispatch`` sorts the (token, pick) pairs whose expert is held here by
expert, into a buffer of static size; ``experts`` / ``experts_relu2`` run the
MLP as grouped matrix products whose group sizes are data; ``combine`` weighs
each row by its weight and adds it to its token.  What an absent expert would add is left out: that partial sum
is the layer's result on this chip (expert parallelism without its exchange;
nothing here stands in for the other chips).

**Dropless under static shapes.**  The buffer holds the worst case, every
token sending all ``k`` picks here (tokens x k rows), so no routing can
overflow it and no pair is ever dropped.  The pairs of held experts come
first, sorted by expert; the rest (absent experts) lie behind them and are
never computed: the products' work follows ``sum(group_sizes)``, the rows
really routed here.

Two lowerings of the grouped products, chosen by ``lowering`` from the
backend and the shapes alone:

- ``kernel``: the grouped matmul jax ships
  (``jax.experimental.pallas.ops.tpu.megablox``): the grid runs over the row
  tiles that hold a routed row (a dynamic grid bound), a tile on a group
  boundary is visited once per group and stored under a mask.  Its two
  kernels (``gmm``, and ``tgmm`` for the weights' gradient) are wired into a
  ``custom_vjp`` HERE and not through the shipped ``ops.gmm``, because that
  one gives all three products one tiling and ``tgmm`` at the forward's tiles
  wants 16.8 MB of the 16 MB of scoped VMEM at these widths.
- ``xla``: ``jax.lax.ragged_dot`` (the CPU tier, and shapes that are not
  whole tiles).

An axis on lanes is tiled by its largest divisor of whole lanes up to 1408; an
expert's width that is NOT whole lanes (Nemotron-H's 1856 = 14.5 x 128) is one
tile, the whole width (a block may be the whole axis whatever its size; the
compiler pads the last lanes in VMEM, nothing is padded in HBM or in the
parameters).

Rows behind the routed ones are not written by the kernel (whatever was in
memory stays there), so both lowerings put zeros there, on the way in and
on the way out: the masks' transposes keep that memory out of every
gradient too.

Two lowerings of the row movements around the products too (``gather_rows``,
``combine`` and their backward passes), chosen by ``rows_lowering``, which
follows ``lowering`` and asks for whole token tiles besides (a row of whole
lanes is all the kernels need: a slab may be wider than its row):

- ``kernel``: ops/pallas/moe_rows.py.  ``to_buffer`` (``gather_rows``, and
  ``combine``'s backward with the pair's weight as scale and the weight's
  gradient as one float a row) and ``to_tokens`` (``combine``, and
  ``gather_rows``' backward with weight 1) fetch a row from HBM by one
  asynchronous copy each and weigh and add on the tile in VMEM.  Their work is
  bounded by ``plan.rows``, read on the device, and by nothing else: a tile
  of the buffer behind the routed rows starts no copy (``to_buffer`` writes
  zeros there without reading anything), a pair that is not routed here is
  never visited (``to_tokens`` walks the held pairs alone, so what lies
  behind the routed rows of its input is never read: no mask is needed, and
  a NaN there reaches nothing).  Every routing up to every pick held
  (tokens x k rows) is computed; there is no capacity and no fallback.
- ``xla``: the gathers below, over the whole buffer whatever is routed (the
  CPU tier, and shapes that are not whole tiles), and a float32 ``(tokens, k,
  d)`` intermediate beside ``combine``'s.

Precision: the router is float32 throughout (matmul at ``highest``: top-k is
discontinuous, and a score rounded to bfloat16 picks other experts); the
experts' operands are the caller's dtype (bfloat16), accumulated in float32
and rounded once per product; ``combine`` weighs and adds in float32.

Gathers, not scatters: sorting is a permutation, so ``dispatch``'s backward
and ``combine``'s forward gather by the inverse permutation and add the
``k`` rows of a token, where a scatter-add would serialise on a TPU.  The
row kernels keep that shape (each output row is written once, by the tile
that owns it), and a permutation of SCALARS (the weights into buffer order,
their gradients back) is a sort by the permutation, not a gather.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from batchai_retinanet_horovod_coco_tpu.ops.pallas import moe_rows

KERNEL, XLA = "kernel", "xla"

# Rows per tile of the grouped products and of the row kernels: the
# granularity at which work follows routing (a group's last tile is partly empty).
TILE_ROWS = 512
# Tokens per tile of ``moe_rows.to_tokens`` (its scratch holds k rows a token).
TOKEN_TILE = 128


class Routing(NamedTuple):
    scores: jax.Array  # (tokens, experts) float32, of all experts (``route``: softmax; ``route_sigmoid``: sigmoid)
    picks: jax.Array  # (tokens, k) int32 expert ids, largest first
    weights: jax.Array  # (tokens, k) float32: what ``combine`` weighs the picks by
    counts: jax.Array  # (experts,) int32 picks of every expert, held or not


class Plan(NamedTuple):
    order: jax.Array  # (tokens x k,) pair index by row: held pairs first, by expert
    inverse: jax.Array  # (tokens x k,) row by pair index
    group_sizes: jax.Array  # (held,) int32 rows of every held expert
    rows: jax.Array  # () int32 their sum: the rows routed here


# The widest expert whose width may be ONE tile of the grouped products (a width that is not whole lanes).
WHOLE_WIDTH_MOST = 2048


def lowering(backend: str, rows: int, d_model: int, d_expert: int) -> str:
    """``kernel`` on a TPU where the buffer is whole row tiles, the model's
    width whole lanes and the expert's width whole lanes or one tile (whole
    packed sublanes of 16, at most ``WHOLE_WIDTH_MOST``); ``xla`` everywhere else."""
    expert_tiles = d_expert % 128 == 0 or (d_expert % 16 == 0 and 128 < d_expert <= WHOLE_WIDTH_MOST)
    whole = rows > 0 and rows % TILE_ROWS == 0 and d_model % 128 == 0 and expert_tiles
    return KERNEL if backend == "tpu" and whole else XLA


def route(u, w_gate, k: int) -> Routing:
    """``u`` (tokens, d) in any dtype, ``w_gate`` (d, experts) float32."""
    logits = jnp.dot(u.astype(jnp.float32), w_gate.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, axis=-1)
    weights, picks = jax.lax.top_k(scores, k)
    counts = jnp.sum(jax.nn.one_hot(picks, scores.shape[-1], dtype=jnp.int32), axis=(0, 1))
    return Routing(scores, picks.astype(jnp.int32), weights, counts)


def route_renormalised(u, w_gate, k: int) -> Routing:
    """``route`` with the picked scores divided by their sum (``norm_topk_prob``):
    a token's ``k`` weights add up to 1."""
    routing = route(u, w_gate, k)
    return routing._replace(weights=routing.weights / jnp.sum(routing.weights, axis=-1, keepdims=True))


def route_sigmoid(u, w_gate, k: int, bias, scale: float) -> Routing:
    """``u`` (tokens, d), ``w_gate`` (d, experts) float32, ``bias`` (experts,)
    float32.  Scores are sigmoids; the picks are the ``k`` largest of score +
    ``bias`` (the bias moves the choice and nothing else: no gradient reaches
    it); the weights are the picked SCORES over their sum, times ``scale``."""
    logits = jnp.dot(u.astype(jnp.float32), w_gate.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, picks = jax.lax.top_k(scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), k)
    picked = jnp.take_along_axis(scores, picks, axis=-1)
    weights = scale * picked / jnp.sum(picked, axis=-1, keepdims=True)
    counts = jnp.sum(jax.nn.one_hot(picks, scores.shape[-1], dtype=jnp.int32), axis=(0, 1))
    return Routing(scores, picks.astype(jnp.int32), weights, counts)


def global_balance_loss(counts, mean_scores, tokens: int):
    """Qwen3-MoE's ``load_balancing_loss_func`` without its coefficient, over
    the tokens of ALL the step's expert layers taken together (the published
    code concatenates the layers' router logits before it takes its means):
    ``experts x sum_i f_i P_i`` with ``f_i`` the picks of expert i a token and
    ``P_i`` the mean score of expert i, both over layers and tokens.
    ``counts`` (layers, experts) picks, ``mean_scores`` (layers, experts)
    float32 means over a layer's ``tokens`` tokens.  The gradient flows
    through ``P`` alone: ``f`` is a count."""
    f = jax.lax.stop_gradient(jnp.mean(counts.astype(jnp.float32), axis=0) / tokens)
    return counts.shape[-1] * jnp.sum(f * jnp.mean(mean_scores, axis=0))


def sequence_balance_loss(scores, picks, k: int):
    """DeepSeek-V2's sequence-wise balance loss without its coefficient:
    mean over sequences of ``sum_i f_i P_i`` with ``f_i`` = (picks of expert
    i in the sequence) x experts / (k T) and ``P_i`` the sequence's mean
    score of expert i.  ``scores`` (batch, T, experts), ``picks`` (batch, T,
    k).  The gradient flows through ``P`` alone: ``f`` is a count."""
    t, experts = scores.shape[1], scores.shape[2]
    picked = jnp.sum(jax.nn.one_hot(picks, experts, dtype=jnp.float32), axis=(1, 2))  # (batch, experts)
    f = jax.lax.stop_gradient(picked * (experts / (k * t)))
    return jnp.mean(jnp.sum(f * jnp.mean(scores, axis=1), axis=-1))


def dispatch(picks, held: tuple[int, ...], experts: int) -> Plan:
    """Sort the (token, pick) pairs by the LOCAL index of their expert; an
    absent expert's pairs get the index ``len(held)`` and so lie last."""
    local_of = np.full((experts,), len(held), np.int32)
    local_of[list(held)] = np.arange(len(held))
    local = jnp.asarray(local_of)[picks.reshape(-1)]
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    group_sizes = jnp.sum(local[:, None] == jnp.arange(len(held), dtype=jnp.int32), axis=0, dtype=jnp.int32)
    return Plan(order, inverse, group_sizes, jnp.sum(group_sizes))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rows_of(k, u, order, inverse):
    """``u[order // k]``: row ``r`` of the buffer is the token of pair ``order[r]``."""
    return u[order // k]


def _rows_of_fwd(k, u, order, inverse):
    return _rows_of(k, u, order, inverse), inverse


def _rows_of_bwd(k, inverse, dy):
    by_pair = dy[inverse].reshape(-1, k, dy.shape[-1])
    return jnp.sum(by_pair.astype(jnp.float32), axis=1).astype(dy.dtype), None, None


_rows_of.defvjp(_rows_of_fwd, _rows_of_bwd)


@jax.custom_vjp
def _pairs_of(y, order, inverse):
    """``y[inverse]``: the buffer's rows back in (token, pick) order."""
    return y[inverse]


def _pairs_of_fwd(y, order, inverse):
    return y[inverse], order


def _pairs_of_bwd(order, dy):
    return dy[order], None, None


_pairs_of.defvjp(_pairs_of_fwd, _pairs_of_bwd)


def gather_rows(u, plan: Plan, how: str = XLA, interpret: bool = False):
    """(tokens, d) -> the buffer (tokens x k, d): routed rows first, zeros behind."""
    if how == KERNEL:
        return _kernel_rows_of(interpret, u, plan)
    routed = jnp.arange(plan.order.shape[0]) < plan.rows
    return jnp.where(routed[:, None], _rows_of(plan.order.shape[0] // u.shape[0], u, plan.order, plan.inverse), 0)


def combine(y, plan: Plan, weights, how: str = XLA, interpret: bool = False):
    """The buffer's rows weighed by their scores and added to their tokens:
    (tokens x k, d), (tokens, k) -> (tokens, d) float32."""
    if how == KERNEL:
        return _kernel_combine(interpret, y, plan, weights)
    by_pair = _pairs_of(y, plan.order, plan.inverse).reshape(*weights.shape, y.shape[-1])
    return jnp.sum(weights[..., None] * by_pair.astype(jnp.float32), axis=1)


def _gated_silu(h):
    gate, up = jnp.split(h, 2, axis=-1)
    return jax.nn.silu(gate) * up


def _relu2(h):
    return jnp.square(jax.nn.relu(h))


def experts(xs, gate_up, down, plan: Plan, how: str, interpret: bool = False, activation=_gated_silu):
    """The gated MLP of every held expert on its rows of the buffer.
    ``xs`` (rows, d), ``gate_up`` (held, d, 2 x width), ``down`` (held,
    width, d), all in the operand dtype -> (rows, d), zeros behind the routed
    rows."""
    product = functools.partial(_kernel_product, interpret=interpret) if how == KERNEL else _xla_product
    y = product(activation(product(xs, gate_up, plan.group_sizes)), down, plan.group_sizes)
    routed = jnp.arange(xs.shape[0]) < plan.rows
    return jnp.where(routed[:, None], y, 0)


def experts_relu2(xs, up, down, plan: Plan, how: str, interpret: bool = False):
    """``experts`` for the two-matrix expert ``W_down relu(W_up u)^2``:
    ``up`` (held, d, width), ``down`` (held, width, d)."""
    return experts(xs, up, down, plan, how, interpret, _relu2)


def _xla_product(lhs, rhs, group_sizes):
    return jax.lax.ragged_dot(lhs, rhs, group_sizes, preferred_element_type=jnp.float32).astype(lhs.dtype)


# ---- the kernel lowering ------------------------------------------------


def _lane_tile(n: int, most: int = 1408) -> int:
    """A tile of an axis that lies on lanes: its largest divisor of whole
    lanes up to ``most`` (1408 = 11 x 128 for DeepSeek-V2-Lite's routed width
    and its double, 1024 for 2048, 896 = 7 x 128 for 2688), or the whole axis
    where it is not whole lanes (``lowering`` admits one such width)."""
    if n % 128:
        return n
    return max(t for t in range(128, min(n, most) + 1, 128) if n % t == 0)


def _weight_gradient_tiles(k: int, n: int) -> tuple[int, int]:
    """``tgmm``'s (k, n) tile holds a float32 accumulator and two output
    buffers: the narrower of the two lane tiles is cut to 512 at most (1408 x
    512 x 4 B = 2.9 MB; 1408 x 1024 does not fit the 16 MB of scoped VMEM)."""
    tk, tn = _lane_tile(k), _lane_tile(n)
    return (tk, _lane_tile(n, 512)) if tk >= tn else (_lane_tile(k, 512), tn)


def _product_tiles(k: int, n: int) -> tuple[int, int]:
    """``gmm``'s (k, n) tiles: the two lane tiles; beside a whole width that
    is not whole lanes the other is cut as ``tgmm``'s is (512 rows x 896 x
    1856 wants 16.6 MB of the 16 MB of scoped VMEM; x 384 x 1856 11.5)."""
    return _weight_gradient_tiles(k, n) if k % 128 or n % 128 else (_lane_tile(k), _lane_tile(n))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _kernel_product(lhs, rhs, group_sizes, interpret=False):
    """``lhs`` (rows, k) x ``rhs`` (groups, k, n) -> (rows, n) in ``lhs``'s dtype."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    k, n = rhs.shape[1:]
    return gmm(lhs, rhs, group_sizes, lhs.dtype, (TILE_ROWS, *_product_tiles(k, n)), interpret=interpret)


def _kernel_product_fwd(lhs, rhs, group_sizes, interpret):
    return _kernel_product(lhs, rhs, group_sizes, interpret), (lhs, rhs, group_sizes)


def _kernel_product_bwd(interpret, res, dy):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    lhs, rhs, group_sizes = res
    k, n = rhs.shape[1:]
    d_lhs = gmm(dy, rhs, group_sizes, lhs.dtype, (TILE_ROWS, *_product_tiles(n, k)), transpose_rhs=True,
                interpret=interpret)
    d_rhs = tgmm(lhs.swapaxes(0, 1), dy, group_sizes, rhs.dtype, (TILE_ROWS, *_weight_gradient_tiles(k, n)),
                 num_actual_groups=rhs.shape[0], interpret=interpret)
    return d_lhs, d_rhs, None


_kernel_product.defvjp(_kernel_product_fwd, _kernel_product_bwd)


def _permuted(values, by):
    """``out[by[i]] = values[i]`` for a permutation ``by``: a sort by it, where
    XLA's gather of as many scalars (``values[inverse of by]``) takes ten times as long."""
    return jax.lax.sort_key_val(by, values)[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _kernel_rows_of(interpret, u, plan: Plan):
    """``gather_rows`` by ``moe_rows.to_buffer``: the rows routed here and zeros behind them."""
    k = plan.order.shape[0] // u.shape[0]
    return moe_rows.to_buffer(u, plan.order // k, plan.rows, u.dtype, tile=TILE_ROWS, interpret=interpret)


def _kernel_rows_of_fwd(interpret, u, plan):
    return _kernel_rows_of(interpret, u, plan), (plan, u.shape[0])


def _kernel_rows_of_bwd(interpret, res, dy):
    """A token's cotangent is the sum of its routed picks' rows, in float32,
    rounded once; what lies behind the routed rows of ``dy`` is not read."""
    plan, tokens = res
    ones = jnp.ones((tokens, plan.order.shape[0] // tokens), jnp.float32)
    du = moe_rows.to_tokens(dy, plan.inverse, ones, plan.rows, dy.dtype, tile=TOKEN_TILE, buffer_tile=TILE_ROWS,
                            interpret=interpret)
    return du, None


_kernel_rows_of.defvjp(_kernel_rows_of_fwd, _kernel_rows_of_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _kernel_combine(interpret, y, plan: Plan, weights):
    """``combine`` by ``moe_rows.to_tokens``; what lies behind the routed rows of ``y`` is not read."""
    return moe_rows.to_tokens(y, plan.inverse, weights, plan.rows, jnp.float32, tile=TOKEN_TILE, buffer_tile=TILE_ROWS,
                              interpret=interpret)


def _kernel_combine_fwd(interpret, y, plan, weights):
    return _kernel_combine(interpret, y, plan, weights), (y, plan, weights)


def _kernel_combine_bwd(interpret, res, dout):
    """One pass over the routed rows: ``dy[r] = w[r] dout[token[r]]`` rounded
    once, and the weight's gradient ``<y[r], dout[token[r]]>`` as one float a
    row (zeros behind the routed rows: a pair that is not routed here gets
    none), which goes back to pair order as a permutation of scalars."""
    y, plan, weights = res
    k = weights.shape[-1]
    dy, by_row = moe_rows.to_buffer(dout, plan.order // k, plan.rows, y.dtype, tile=TILE_ROWS,
                                    scale=_permuted(weights.reshape(-1), plan.inverse), y=y, interpret=interpret)
    return dy, None, _permuted(by_row, plan.order).reshape(weights.shape).astype(weights.dtype)


_kernel_combine.defvjp(_kernel_combine_fwd, _kernel_combine_bwd)


def _rows_follow(how: str, tokens: int, d_model: int) -> str:
    whole = tokens % TOKEN_TILE == 0 and d_model % moe_rows.LANES == 0
    return KERNEL if how == KERNEL and whole else XLA


def rows_lowering(backend: str, tokens: int, k: int, d_model: int, d_expert: int) -> str:
    """What ``gather_rows`` and ``combine`` take: ``kernel`` where the grouped
    products take it (``lowering``, which asks for rows of whole lanes: all
    that ops/pallas/moe_rows.py asks of a width) and the tokens are whole
    tiles of ``TOKEN_TILE``; ``xla`` everywhere else."""
    return _rows_follow(lowering(backend, tokens * k, d_model, d_expert), tokens, d_model)


def expert_layer(u, w_gate, gate_up, down, held: tuple[int, ...], k: int, how: str, router=None, mlp=None):
    """The whole layer on ``u`` (batch, T, d): route, dispatch, experts,
    combine, each under its named scope.  ``router(flat, w_gate, k)`` is
    ``route`` (None) or ``route_sigmoid`` with its bias and scale bound;
    ``mlp`` is ``experts`` (None) or ``experts_relu2`` (``gate_up`` then the
    up matrix alone).  The defaults are looked up when the layer is traced.
    Returns the held experts' part of the result (batch, T, d) float32, the
    routing, and the plan."""
    batch, t, d = u.shape
    flat = u.reshape(batch * t, d)
    rows_how = _rows_follow(how, batch * t, d)
    with jax.named_scope("router"):
        routing = (router or route)(flat, w_gate, k)
    with jax.named_scope("dispatch"):
        plan = dispatch(routing.picks, held, w_gate.shape[-1])
        xs = gather_rows(flat, plan, rows_how)
    with jax.named_scope("experts"):
        y = (mlp or experts)(xs, gate_up, down, plan, how)
    with jax.named_scope("combine"):
        out = combine(y, plan, routing.weights, rows_how)
    return out.reshape(batch, t, d), routing, plan

"""ops/moe.py: the routed expert layer of a chip that holds a share of the
experts, against a dense loop over every token (``for e in held: y += s_e
picked_e E_e(u)``); its two lowerings against each other (the grouped kernel
and the row kernels in interpret mode); no token dropped under any imbalance;
and ops/rope.py."""

import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batchai_retinanet_horovod_coco_tpu.ops import moe, rope

T, K, D, W, E = 96, 3, 128, 128, 16
TILE = 32  # of the kernels in these tests: the buffer of 288 rows holds nine, the 96 tokens three


def _weights(seed, held, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.normal(size=(T, D)), dtype)
    w_gate = jnp.asarray(rng.normal(size=(D, E)) * 0.3, jnp.float32)
    gate_up = jnp.asarray(rng.normal(size=(len(held), D, 2 * W)) * 0.1, dtype)
    down = jnp.asarray(rng.normal(size=(len(held), W, D)) * 0.1, dtype)
    return u, w_gate, gate_up, down


def _layer(u, w_gate, gate_up, down, held, how=moe.XLA, interpret=False, rows_how=moe.XLA):
    r = moe.route(u, w_gate, K)
    plan = moe.dispatch(r.picks, held, E)
    y = moe.experts(moe.gather_rows(u, plan, rows_how, interpret), gate_up, down, plan, how, interpret=interpret)
    return moe.combine(y, plan, r.weights, rows_how, interpret), r, plan


def _small_tiles():
    return mock.patch.multiple(moe, TILE_ROWS=TILE, TOKEN_TILE=TILE)


def _dense(u, w_gate, gate_up, down, held):
    """Every held expert on every token, times the score where picked."""
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.softmax(u.astype(jnp.float32) @ w_gate, axis=-1)
        picks = jnp.argsort(-scores, axis=-1)[:, :K]
        picked = jnp.sum(jax.nn.one_hot(picks, E), axis=1)
        y = 0.0
        for j, e in enumerate(held):
            gate, up = jnp.split(u @ gate_up[j], 2, axis=-1)
            y = y + (scores[:, e] * picked[:, e])[:, None] * ((jax.nn.silu(gate) * up) @ down[j])
        return y


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def test_route_scores_every_expert_and_picks_the_k_largest():
    u, w_gate, _, _ = _weights(0, (0,))
    r = moe.route(u, w_gate, K)
    np.testing.assert_allclose(np.sum(r.scores, axis=-1), 1.0, rtol=1e-5)
    order = np.argsort(-np.asarray(r.scores), axis=-1)[:, :K]
    np.testing.assert_array_equal(np.asarray(r.picks), order)
    np.testing.assert_array_equal(np.asarray(r.weights), np.take_along_axis(np.asarray(r.scores), order, axis=-1))
    assert r.counts.shape == (E,) and int(r.counts.sum()) == T * K  # of ALL experts, held or not
    np.testing.assert_array_equal(np.asarray(r.counts), np.bincount(order.reshape(-1), minlength=E))


@pytest.mark.parametrize("rows_how", [moe.XLA, moe.KERNEL], ids=["rows_xla", "rows_kernel"])
@pytest.mark.parametrize("held", [(0, 1, 2, 3), (1, 5, 6, 9), (15,), tuple(range(E))], ids=str)
def test_the_layer_matches_a_dense_loop_over_every_token_and_its_gradients(held, rows_how):
    """With the row kernels (interpret mode) the router's gradient comes
    through ``to_buffer``'s float per row, the input's through ``to_tokens``."""
    args = _weights(1, held)
    layer = lambda *a: _layer(*a, held, interpret=True, rows_how=rows_how)
    with _small_tiles():
        out, r, plan = layer(*args)
        grads = jax.grad(lambda *a: jnp.sum(jnp.square(layer(*a)[0])), argnums=(0, 1, 2, 3))(*args)
    assert _rel(out, _dense(*args, held)) < 2e-6
    assert int(plan.rows) == int(sum(r.counts[e] for e in held)) == int(plan.group_sizes.sum())
    np.testing.assert_array_equal(np.asarray(plan.group_sizes), np.asarray(r.counts)[list(held)])
    wanted = jax.grad(lambda u, g, gu, dn: jnp.sum(jnp.square(_dense(u, g, gu, dn, held))), argnums=(0, 1, 2, 3))(*args)
    for name, a, b in zip(("input", "router", "gate_up", "down"), grads, wanted):
        assert _rel(a, b) < 1e-5, name


def test_the_buffer_sorts_held_pairs_first_by_expert_and_zeroes_the_rest():
    held = (1, 5, 6, 9)
    u, w_gate, _, _ = _weights(2, held)
    r = moe.route(u, w_gate, K)
    plan = moe.dispatch(r.picks, held, E)
    rows, flat = int(plan.rows), np.asarray(r.picks).reshape(-1)
    order = np.asarray(plan.order)
    assert order.shape == (T * K,) and sorted(order) == list(range(T * K))  # a permutation: nothing dropped
    np.testing.assert_array_equal(np.asarray(plan.inverse)[order], np.arange(T * K))
    sorted_experts = flat[order[:rows]]
    assert set(sorted_experts) <= set(held) and list(sorted_experts) == sorted(sorted_experts)
    assert not set(flat[order[rows:]]) & set(held)
    xs = np.asarray(moe.gather_rows(u, plan))
    np.testing.assert_array_equal(xs[:rows], np.asarray(u)[order[:rows] // K])
    assert not xs[rows:].any()


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6), (jnp.bfloat16, 1e-2)], ids=["float32", "bfloat16"])
def test_the_grouped_kernel_and_the_xla_lowering_agree(dtype, tol):
    """megablox's gmm / tgmm (interpret mode) under this module's custom_vjp
    against ``ragged_dot``: the output and the gradients of the input and of
    both weights."""
    held = (1, 5, 6, 9)
    args = _weights(3, held, dtype)

    def loss(how, interpret):
        return lambda u, gu, dn: jnp.sum(jnp.square(_layer(u, args[1], gu, dn, held, how, interpret)[0]))

    with mock.patch.object(moe, "TILE_ROWS", TILE):
        out_k = _layer(*args, held, moe.KERNEL, True)[0]
        out_x = _layer(*args, held, moe.XLA)[0]
        grads_k = jax.grad(loss(moe.KERNEL, True), argnums=(0, 1, 2))(args[0], args[2], args[3])
        grads_x = jax.grad(loss(moe.XLA, False), argnums=(0, 1, 2))(args[0], args[2], args[3])
    assert _rel(out_k, out_x) < tol
    for name, a, b in zip(("input", "gate_up", "down"), grads_k, grads_x):
        assert _rel(a, b) < tol, name


# ---- the two-matrix relu^2 expert, the sigmoid router, a width that is not whole lanes -----

SCALE = 2.5
RAGGED = 208  # 13 x 16: whole packed sublanes, 1.625 lane tiles (Nemotron-H's 1856 is 14.5)


def _relu2_weights(seed, held, width, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.normal(size=(T, D)), dtype)
    w_gate = jnp.asarray(rng.normal(size=(D, E)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(E,)) * 0.2, jnp.float32)
    up = jnp.asarray(rng.normal(size=(len(held), D, width)) * 0.1, dtype)
    down = jnp.asarray(rng.normal(size=(len(held), width, D)) * 0.1, dtype)
    return u, w_gate, bias, up, down


def _relu2_layer(u, w_gate, bias, up, down, held, how=moe.XLA, interpret=False):
    r = moe.route_sigmoid(u, w_gate, K, bias, SCALE)
    plan = moe.dispatch(r.picks, held, E)
    y = moe.experts_relu2(moe.gather_rows(u, plan), up, down, plan, how, interpret=interpret)
    return moe.combine(y, plan, r.weights), r, plan


def _relu2_dense(u, w_gate, bias, up, down, held):
    """Every held expert on every token; the weight of a picked expert is its
    sigmoid over the sum of the picked sigmoids, times the scale."""
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(u.astype(jnp.float32) @ w_gate)
        picks = jnp.argsort(-(scores + bias), axis=-1)[:, :K]
        picked = jnp.sum(jax.nn.one_hot(picks, E), axis=1)
        weights = SCALE * scores * picked / jnp.sum(scores * picked, axis=-1, keepdims=True)
        y = 0.0
        for j, e in enumerate(held):
            y = y + weights[:, e, None] * (jnp.square(jnp.maximum(u @ up[j], 0.0)) @ down[j])
        return y


def test_the_sigmoid_routers_bias_moves_picks_and_never_weights():
    u, w_gate, bias, _, _ = _relu2_weights(7, (0,), W)
    r = moe.route_sigmoid(u, w_gate, K, bias, SCALE)
    scores = np.asarray(jax.nn.sigmoid(jnp.dot(u, w_gate, precision="highest")))
    np.testing.assert_allclose(np.asarray(r.scores), scores, rtol=1e-6)
    order = np.argsort(-(scores + np.asarray(bias)), axis=-1)[:, :K]
    np.testing.assert_array_equal(np.asarray(r.picks), order)
    picked = np.take_along_axis(scores, order, axis=-1)
    np.testing.assert_allclose(np.asarray(r.weights), SCALE * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(r.weights).sum(-1), SCALE, rtol=1e-6)  # norm_topk_prob x the scale
    # without the bias other experts are picked ...
    plain = moe.route_sigmoid(u, w_gate, K, jnp.zeros((E,)), SCALE)
    assert (np.sort(np.asarray(plain.picks), -1) != np.sort(order, -1)).any()
    # ... a bias that is the same for every expert changes nothing at all ...
    shifted = moe.route_sigmoid(u, w_gate, K, jnp.full((E,), 3.0), SCALE)
    np.testing.assert_array_equal(np.asarray(shifted.picks), np.asarray(plain.picks))
    np.testing.assert_array_equal(np.asarray(shifted.weights), np.asarray(plain.weights))
    # ... and no gradient reaches it
    g = jax.grad(lambda b: jnp.sum(jnp.square(moe.route_sigmoid(u, w_gate, K, b, SCALE).weights)))(bias)
    assert not np.asarray(g).any()
    np.testing.assert_array_equal(np.asarray(r.counts), np.bincount(order.reshape(-1), minlength=E))


@pytest.mark.parametrize("width", [W, RAGGED], ids=["whole_lanes", "ragged_width"])
@pytest.mark.parametrize("held", [(0, 1, 2, 3), (1, 5, 6, 9), tuple(range(E))], ids=str)
def test_the_relu2_layer_matches_a_dense_loop_and_its_gradients(held, width):
    args = _relu2_weights(8, held, width)
    out, r, plan = _relu2_layer(*args, held)
    assert _rel(out, _relu2_dense(*args, held)) < 2e-6
    np.testing.assert_array_equal(np.asarray(plan.group_sizes), np.asarray(r.counts)[list(held)])
    got = jax.grad(lambda u, g, b, up, dn: jnp.sum(jnp.square(_relu2_layer(u, g, b, up, dn, held)[0])),
                   argnums=(0, 1, 3, 4))(*args)
    wanted = jax.grad(lambda u, g, b, up, dn: jnp.sum(jnp.square(_relu2_dense(u, g, b, up, dn, held))),
                      argnums=(0, 1, 3, 4))(*args)
    for name, a, b in zip(("input", "router", "up", "down"), got, wanted):
        assert _rel(a, b) < 1e-5, name


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6), (jnp.bfloat16, 1e-2)], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("expert", ["gated_silu", "relu2"])
def test_the_grouped_kernel_at_a_width_that_is_not_whole_lanes_is_ragged_dot(expert, dtype, tol):
    """The expert's width is ONE tile of gmm / tgmm (interpret mode), the
    whole axis, as the kernel lowering takes Nemotron-H's 1856: the output and
    the gradients of the input and of both weights against ``ragged_dot``."""
    held = (1, 5, 6, 9)
    u, w_gate, bias, up, down = _relu2_weights(9, held, RAGGED, dtype)
    if expert == "gated_silu":
        up = jnp.concatenate([up, up[..., ::-1]], axis=-1)  # (held, D, 2 x RAGGED)
    mlp = moe.experts if expert == "gated_silu" else moe.experts_relu2
    r = moe.route_sigmoid(u, w_gate, K, bias, SCALE)
    plan = moe.dispatch(r.picks, held, E)
    assert moe._product_tiles(D, up.shape[-1]) == (D, up.shape[-1]) and moe._product_tiles(RAGGED, D) == (RAGGED, D)

    def run(how, interpret):
        f = lambda u, up, down: moe.combine(
            mlp(moe.gather_rows(u, plan), up, down, plan, how, interpret=interpret), plan, r.weights)
        return f(u, up, down), jax.grad(lambda *a: jnp.sum(jnp.square(f(*a))), argnums=(0, 1, 2))(u, up, down)

    with mock.patch.object(moe, "TILE_ROWS", TILE):
        out_k, grads_k = run(moe.KERNEL, True)
    out_x, grads_x = run(moe.XLA, False)
    assert _rel(out_k, out_x) < tol
    for name, a, b in zip(("input", "up", "down"), grads_k, grads_x):
        assert a.shape == b.shape and _rel(a, b) < tol, name


@pytest.mark.parametrize("k,n,tiles,gradient_tiles", [
    (2048, 2816, (1024, 1408), (512, 1408)),  # DeepSeek-V2-Lite's gate and up: as PR 30 measured them
    (1408, 2048, (1408, 1024), (1408, 512)),
    (2688, 1856, (384, 1856), (384, 1856)),   # Nemotron-H's up: the width whole, the other cut to fit VMEM
    (1856, 2688, (1856, 384), (1856, 384)),
    (2688, 3712, (896, 128), (896, 128)),     # 3712 = 29 x 128: prime in lane tiles (the shared expert is no grouped product)
])
def test_the_products_tiles_divide_their_axes(k, n, tiles, gradient_tiles):
    assert moe._product_tiles(k, n) == tiles and moe._weight_gradient_tiles(k, n) == gradient_tiles
    assert all(axis % tile == 0 for axis, tile in zip((k, n), tiles))


def _forced_router(to: tuple[int, ...]):
    """A router whose K largest scores are ``to``'s experts for EVERY token:
    a bias through a constant input column."""
    w_gate = np.zeros((D, E), np.float32)
    w_gate[0, list(to)] = 50.0 + np.arange(len(to))
    return jnp.asarray(w_gate)


@pytest.mark.parametrize("how,rows_how", [(moe.XLA, moe.XLA), (moe.KERNEL, moe.XLA), (moe.KERNEL, moe.KERNEL)],
                         ids=["xla", "kernel", "kernel_and_row_kernels"])
@pytest.mark.parametrize("case", ["every_pick_held", "no_pick_held", "all_on_one_expert"])
def test_no_token_is_dropped_under_imbalance(case, how, rows_how):
    """The buffer holds the worst case: every token sending all its picks
    here (tokens x k rows on ONE chip, three times an average share), all of
    them to few experts, or none at all.  The row kernels' bound is the rows
    routed here and nothing else: every row of the buffer when every pick is held."""
    held = (2, 3, 4, 5)
    u, _, gate_up, down = _weights(4, held)
    u = u.at[:, 0].set(1.0)
    to = {"every_pick_held": (2, 3, 4), "no_pick_held": (7, 8, 9), "all_on_one_expert": (5, 11, 12)}[case]
    w_gate = _forced_router(to)
    with _small_tiles():
        out, r, plan = _layer(u, w_gate, gate_up, down, held, how, interpret=True, rows_how=rows_how)
    assert set(np.asarray(r.picks).reshape(-1)) == set(to)
    assert int(plan.rows) == T * len(set(to) & set(held))
    if case == "all_on_one_expert":
        assert int(plan.group_sizes.max()) == T  # one expert has every token, 16 times its average load
    assert _rel(out, _dense(u, w_gate, gate_up, down, held)) < 2e-6 or case == "no_pick_held"
    if case == "no_pick_held":
        assert not np.asarray(out).any()
    assert np.isfinite(np.asarray(out)).all()


def test_the_balance_loss_is_the_sum_of_load_times_mean_score_per_sequence():
    rng = np.random.default_rng(5)
    scores = jax.nn.softmax(jnp.asarray(rng.normal(size=(2, 10, 4)), jnp.float32), axis=-1)
    picks = jnp.asarray(np.argsort(-np.asarray(scores), axis=-1)[..., :2], jnp.int32)
    by_hand = 0.0
    for b in range(2):
        load = np.bincount(np.asarray(picks[b]).reshape(-1), minlength=4) * 4 / (2 * 10)
        by_hand += float(np.sum(load * np.asarray(scores[b]).mean(axis=0))) / 2
    assert float(moe.sequence_balance_loss(scores, picks, 2)) == pytest.approx(by_hand, rel=1e-6)
    # perfectly even routing with uniform scores reads 1
    even = jnp.full((1, 4, 4), 0.25)
    assert float(moe.sequence_balance_loss(even, jnp.asarray([[[0, 1], [2, 3], [0, 1], [2, 3]]]), 2)) == pytest.approx(1.0)
    # the load is a count: the gradient flows through the scores' mean alone
    g = jax.grad(lambda s: moe.sequence_balance_loss(s, picks, 2))(scores)
    load0 = np.bincount(np.asarray(picks[0]).reshape(-1), minlength=4) * 4 / 20
    np.testing.assert_allclose(np.asarray(g[0, 3]), load0 / 10 / 2, rtol=1e-5)


@pytest.mark.parametrize("backend,tokens,k,d,w,expected,rows_expected", [
    ("tpu", 16384, 6, 2048, 1408, moe.KERNEL, moe.KERNEL),  # the cell's: 2 x 8192 tokens x 6 picks = 98 304 rows
    ("cpu", 16384, 6, 2048, 1408, moe.XLA, moe.XLA),
    ("tpu", 64, 6, 64, 32, moe.XLA, moe.XLA),  # the tiny preset: not whole tiles
    ("tpu", 98304 + 256, 1, 2048, 1408, moe.XLA, moe.XLA),
    ("tpu", 16384, 6, 2048, 1400, moe.XLA, moe.XLA),
    # Nemotron-H: 1856 = 14.5 lane tiles is one tile of the products; a bfloat16 row of 2688 is 10.5 sublanes in a slab of 16
    ("tpu", 16384, 6, 2688, 1856, moe.KERNEL, moe.KERNEL),
    ("cpu", 16384, 6, 2688, 1856, moe.XLA, moe.XLA),
    ("tpu", 16384, 6, 2688, 4160, moe.XLA, moe.XLA),  # too wide to be one tile
    # the row kernels follow the products and ask for whole tiles of tokens besides
    ("tpu", 16384, 6, 4096, 1408, moe.KERNEL, moe.KERNEL),
    ("tpu", 16384, 6, 1024, 1408, moe.KERNEL, moe.KERNEL),  # a bfloat16 row of 1024 is 4 sublanes in a slab of 8
    ("tpu", 64, 8, 2048, 1408, moe.KERNEL, moe.XLA),  # 512 rows are a tile of the buffer, 64 tokens no tile of tokens
])
def test_lowering_picks_the_kernel_on_a_tpu_at_whole_tiles(backend, tokens, k, d, w, expected, rows_expected):
    assert moe.lowering(backend, tokens * k, d, w) == expected
    assert moe.rows_lowering(backend, tokens, k, d, w) == rows_expected


def test_run_meta_says_which_lowering_the_row_movements_take():
    """``moe_rows_lowering`` beside ``moe_lowering``: the kernels at the
    published widths on a TPU (2 sequences of 8192), XLA on the CPU and at the
    tiny preset's shapes on any backend."""
    from batchai_retinanet_horovod_coco_tpu.models import deepseek_v2

    path = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark", "configs", "deepseek-v2-lite-ep8.json")
    with open(path) as f:
        published = deepseek_v2.DeepseekV2(deepseek_v2.DeepseekV2Config.from_hf(json.load(f)))
    tiny = deepseek_v2.DeepseekV2(deepseek_v2.TINY)
    assert published.run_meta((2, 8192))["moe_rows_lowering"] == moe.XLA  # this process's backend is the CPU
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        meta = published.run_meta((2, 8192))
        assert (meta["moe_lowering"], meta["moe_rows_lowering"]) == (moe.KERNEL, moe.KERNEL)
        assert tiny.run_meta((2, 64))["moe_rows_lowering"] == moe.XLA


# ---- ops/rope.py -------------------------------------------------------------


def test_yarn_frequencies_and_mscale_as_written_out_by_hand():
    """DeepSeek-V2-Lite's rope_scaling: factor 40, original 4096, beta 32 / 1,
    64 rotary dimensions, theta 10000.  The correction dimensions are
    64 ln(4096 / (32 x 2 pi)) / (2 ln 10000) = 10.47 -> 10 and
    64 ln(4096 / (2 pi)) / (2 ln 10000) = 22.51 -> 23: pairs up to 10 keep
    10000^(-2i/64), pairs from 23 on are divided by 40, between them the
    blend (i - 10) / 13."""
    f = np.asarray(rope.yarn_inv_freq(64, 10000.0, 40.0, 4096, 32.0, 1.0), np.float64)
    plain = lambda i: 10000.0 ** (-2 * i / 64)
    assert f.shape == (32,)
    np.testing.assert_allclose(f[:11], [plain(i) for i in range(11)], rtol=1e-6)
    np.testing.assert_allclose(f[23:], [plain(i) / 40 for i in range(23, 32)], rtol=1e-6)
    # pair 11: 10^-1.375 = 0.0421697 x (12/13 + 1/(13 x 40)) = 0.0390069; pair 16: 0.01 x (7/13 + 6/520) = 0.0055
    assert f[11] == pytest.approx(0.0390069, rel=1e-5) and f[16] == pytest.approx(0.0055, rel=1e-5)
    assert rope.yarn_mscale(40.0, 0.707) == pytest.approx(1.2608038, rel=1e-7)  # 0.1 x 0.707 x ln 40 + 1
    assert rope.yarn_mscale(1.0, 0.707) == 1.0


def test_positions_restart_at_every_document():
    seg = jnp.asarray([[0, 0, 0, 1, 1, 2, 2, 2, 2], [0, 1, 1, 1, 1, 1, 2, 3, 3]], jnp.int32)
    np.testing.assert_array_equal(np.asarray(rope.document_positions(seg)),
                                  [[0, 1, 2, 0, 1, 0, 1, 2, 3], [0, 0, 1, 2, 3, 4, 0, 0, 1]])


def test_a_rotated_query_times_a_rotated_key_depends_on_their_distance_alone():
    rng = np.random.default_rng(6)
    inv_freq = rope.yarn_inv_freq(8, 10000.0, 40.0, 16, 32.0, 1.0)
    q = jnp.asarray(rng.normal(size=(1, 1, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 1, 8)), jnp.float32)
    dot = lambda i, j: float(jnp.sum(rope.apply_rotary(q, jnp.asarray([[i]]), inv_freq)
                                     * rope.apply_rotary(k, jnp.asarray([[j]]), inv_freq)))
    assert dot(7, 3) == pytest.approx(dot(104, 100), rel=1e-4)
    assert dot(0, 0) == pytest.approx(float(jnp.sum(q * k)), rel=1e-6)
    assert abs(dot(7, 3) - dot(7, 4)) > 1e-3
    # pairs (2i, 2i + 1) turn together: (1, 0, ...) by a quarter turn of pair 0 becomes (0, ..., 1 at the second half's 0)
    e0 = jnp.zeros((1, 1, 8)).at[0, 0, 0].set(1.0)
    turned = rope.apply_rotary(e0, jnp.asarray([[1]]), jnp.asarray([np.pi / 2, 0, 0, 0], jnp.float32))
    np.testing.assert_allclose(np.asarray(turned)[0, 0], [0, 0, 0, 0, 1, 0, 0, 0], atol=1e-6)

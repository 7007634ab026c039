"""ops/attention.py: the blocked kernel (interpret mode on the CPU) against
the XLA path and against the float32 reference's dense masked softmax; the
choice between the two; the count of block pairs a batch's documents need,
and the kernel's block lists that follow them."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batchai_retinanet_horovod_coco_tpu.data.tokens import PackedTokensConfig, packed_token_batches
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


def _qkv(seed, dtype=jnp.bfloat16, batch=1):
    keys = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(keys[0], (batch, T, HEADS, HEAD), jnp.float32)
    k = jax.random.normal(keys[1], (batch, T, KV_HEADS, HEAD), jnp.float32)
    v = jax.random.normal(keys[2], (batch, T, KV_HEADS, HEAD), jnp.float32)
    g = jax.random.normal(keys[3], (batch, T, HEADS, HEAD), jnp.float32)
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


def _dense_counts(seg, block_q, block_kv, window=None):
    t = seg.shape[1]
    pos = np.arange(t)
    causal = pos[:, None] >= pos[None, :]
    near = causal if window is None else causal & (pos[:, None] - pos[None, :] < window)
    computed = needed = 0
    for row in seg:
        same = near & (row[:, None] == row[None, :])
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


@pytest.mark.parametrize("sequences", [1, 2])
@pytest.mark.parametrize("heads,kv_heads,head,value_head", [
    (32, 8, 64, 64),      # granite-h-train-pack8k
    (16, 16, 192, 128),   # dsv2-lite-train-pack8k: latent attention's narrower value head
    (32, 2, 128, 128),    # nemo3-nano-train-pack8k
])
def test_kernel_lowers_for_tpu_at_the_cells_shapes_with_the_committed_blocks(heads, kv_heads, head, value_head,
                                                                             sequences):
    """JAX-level lowering only (what Mosaic says of it is the chip's to tell):
    8192 tokens, forward and both backward kernels once a sequence, their
    block lists computed from the segment ids inside the program."""
    spec = lambda h, d: jax.ShapeDtypeStruct((sequences, 8192, h, d), jnp.bfloat16)

    def fn(q, k, v, g, seg):
        out, vjp = jax.vjp(lambda q, k, v: attention._kernel_path(q, k, v, seg, 0.015625), q, k, v)
        return out, vjp(g)

    text = jax.jit(fn).trace(spec(heads, head), spec(kv_heads, head), spec(kv_heads, value_head), spec(heads, value_head),
                             jax.ShapeDtypeStruct((sequences, 8192), jnp.int32)).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") >= 3


# ---- the block lists follow the documents ----------------------------------

PACKINGS = {**{name: [lengths] for name, lengths in LAYOUTS.items()},
            "a_document_over_three_blocks": [[100, 250, 162]],
            "two_sequences_packed_differently": [[T], [60, 100, 40, 90, 30, 110, 82]],
            "two_sequences_with_documents": [[100, 250, 162], [BLOCK, 2 * BLOCK, BLOCK]]}


def _static_lists():
    """The same kernel with the causal mask's static lists, as the library built them."""
    return mock.patch.object(attention, "_follow_documents", lambda info, *_: info)


@pytest.mark.parametrize("packing", PACKINGS)
def test_document_block_lists_give_the_static_lists_arrays_bit_for_bit(packing):
    seg = jnp.asarray(np.concatenate([_segments(lengths) for lengths in PACKINGS[packing]]))
    qkv, g = _qkv(3, batch=seg.shape[0])
    followed = _out_and_grads(_kernel, qkv, g, seg)
    with _static_lists():
        static = _out_and_grads(_kernel, qkv, g, seg)
    for name, a, b in zip(("out", "dq", "dk", "dv"), jax.tree.leaves(followed), jax.tree.leaves(static)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32), err_msg=name)


def _lists_of(seg, heads=2):
    """(name, is_dkv, blocks, static info, followed info) of the three kernels at the committed blocks,
    for a batch's ``seg`` (sequences, T)."""
    kernel = attention._causal_kernel(*seg.shape, heads, tuple(attention.BLOCK_SIZES.items()), False)
    followed = attention._document_block_lists(kernel, jnp.asarray(seg.reshape(-1)), heads)
    b = attention.BLOCK_SIZES
    return [("forward", False, (b["block_q"], b["block_kv"]), kernel.fwd_mask_info, followed.fwd_mask_info),
            ("dq", False, (b["block_q_dq"], b["block_kv_dq"]), kernel.dq_mask_info, followed.dq_mask_info),
            ("dkv", True, (b["block_q_dkv"], b["block_kv_dkv"]), kernel.dkv_mask_info, followed.dkv_mask_info)]


def _cell_packings(seed):
    source = packed_token_batches(PackedTokensConfig(vocab_size=64, seq_len=8192, batch_size=2, seed=seed))
    return next(source).segment_ids


@pytest.mark.parametrize("seed", [0, 1, 7, 905418237, 1732050807])
def test_lists_run_the_pairs_block_pair_counts_needs_at_the_cells_sequence_length(seed):
    both = _cell_packings(seed)
    for seg in (both, both[:1]):  # two sequences a step (dsv2, nemo3) and one (granite)
        for name, is_dkv, blocks, static, followed in _lists_of(seg):
            computed, needed = attention.block_pair_counts(seg, *blocks)
            if seed == 0:  # the oracle's own oracle, at this length too
                assert _dense_counts(seg, *blocks) == (computed, needed)
            mask, static_mask = np.asarray(followed.block_mask), np.asarray(static.block_mask)
            # the library shrank each grid to one sequence's width: no step for a pair of two sequences
            assert static_mask.shape[1 if is_dkv else 2] == 8192 // blocks[is_dkv]
            assert mask.shape == (2 if is_dkv else 1, *static_mask.shape[1:]), name  # dk/dv: a list a head
            assert (mask == mask[:1]).all(), name
            assert int((static_mask > 0).sum()) == computed, name
            assert int((mask[0] > 0).sum()) == needed, name
            np.testing.assert_array_equal(mask[0], np.where(mask[0] > 0, static_mask[0], 0))  # 1 and 2 keep their meaning


@pytest.mark.parametrize("seed", [0, 905418237])
def test_a_step_left_out_names_the_block_of_the_next_step_that_runs(seed):
    for name, is_dkv, _, static, followed in _lists_of(_cell_packings(seed)):
        mask, nxt = np.asarray(followed.block_mask), np.asarray(followed.data_next)
        own = np.broadcast_to(np.asarray(static.data_next), nxt.shape)
        heads, n_i, n_j = mask.shape
        # the grid's order: forward and dq (head, query block, key block), where one list serves every head and
        # after a head's last step comes the next head's first; dk/dv (key block, head, query block)
        steps = ([(h, i, j) for j in range(n_j) for h in range(heads) for i in range(n_i)] if is_dkv else
                 [(h, i, j) for h in range(heads) for i in range(n_i) for j in range(n_j)])
        running = [s for s in steps if mask[s] > 0]
        assert running and len(running) < len(steps), name
        for at, step in enumerate(steps):
            later = next((s for s in steps[at:] if mask[s] > 0), running[0])
            assert nxt[step] == own[later], (name, step, later)


def test_run_share_is_one_for_one_document_and_the_hosts_count_otherwise():
    one = np.zeros((2, 8192), np.int32)
    assert float(attention.block_pairs_run_share(jnp.asarray(one), 1024, 1024)) == 1.0
    for seed in (0, 905418237):
        seg = _cell_packings(seed)
        for blocks in ((1024, 1024), (512, 512), (1024, 512)):
            computed, needed = attention.block_pair_counts(seg, *blocks)
            assert 0.3 < needed / computed < 0.9
            np.testing.assert_allclose(float(attention.block_pairs_run_share(jnp.asarray(seg), *blocks)),
                                       needed / computed, rtol=1e-6)


def test_the_counter_and_run_meta_speak_of_skipping_on_the_kernel_path_alone():
    seg = jnp.asarray(_cell_packings(0))
    assert attention.step_counters(seg) == {}  # the CPU: the xla path skips nothing by block
    assert attention.run_meta("cpu", 8192) == {"attention_lowering": "xla"}
    assert attention.run_meta("tpu", 8192) == {"attention_lowering": "kernel", "attention_block_skip": "documents",
                                               "attention_residuals": "kept"}
    assert attention.run_meta("tpu", 8192 + 64) == {"attention_lowering": "xla"}  # a ragged sequence keeps none
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        (name, share), = attention.step_counters(seg).items()
        assert attention.step_counters(seg[:, :64]) == {}  # a short sequence: the xla path
    b = attention.BLOCK_SIZES
    computed, needed = attention.block_pair_counts(np.asarray(seg), b["block_q"], b["block_kv"])
    assert name == "attn/block_pairs_run_share"
    np.testing.assert_allclose(float(share), needed / computed, rtol=1e-6)


# ---- a window: the second rule, chosen per call (ISSUE 46) -------------------

# windows of these tests: an edge INSIDE a block (100) and ON one (BLOCK); in "four_documents" a document is
# equal to the first (100), shorter than both (12) and longer (200); "one_document" is several times either
WINDOWS = {"edge_inside_a_block": 100, "edge_on_a_block": BLOCK}


def _window_blocks():
    blocks = {name: BLOCK for name in attention.BLOCK_SIZES}
    return mock.patch.multiple(attention, BLOCK_SIZES=blocks)


def _kernel_w(window):
    def fn(q, k, v, seg):
        with _window_blocks():
            return attention._kernel_path(q, k, v, seg, SCALE, interpret=True, window=window)
    return fn


def _xla_w(window):
    return lambda q, k, v, seg: attention._xla_path(q, k, v, seg, SCALE, 128, window)


def _written_out(window):
    """The mask written out as a comparison of positions and document ids, a dense float32 softmax."""
    def fn(q, k, v, seg):
        pos, ids = np.arange(T), np.asarray(seg[0])
        mask = (pos[:, None] >= pos[None, :]) & (ids[:, None] == ids[None, :])
        if window is not None:
            mask &= pos[:, None] - pos[None, :] < window
        q, k, v = (x[0].astype(jnp.float32) for x in (q, k, v))
        k, v = jnp.repeat(k, HEADS // KV_HEADS, axis=1), jnp.repeat(v, HEADS // KV_HEADS, axis=1)
        with jax.default_matmul_precision("highest"):
            scores = jnp.where(mask[None], SCALE * jnp.einsum("qhd,shd->hqs", q, k), -jnp.inf)
            return jnp.einsum("hqs,shd->qhd", jax.nn.softmax(scores, axis=-1), v)[None]
    return fn


def test_the_mask_written_out_without_a_window_is_the_reference():
    seg = jnp.asarray(_segments(LAYOUTS["four_documents"]))
    (q, k, v), _ = _qkv(4)
    np.testing.assert_allclose(_written_out(None)(q, k, v, seg), _reference(q, k, v, seg), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("layout", ["one_document", "four_documents", "boundary_on_a_block_edge"])
def test_window_in_both_lowerings_matches_the_mask_written_out(layout, window):
    w = WINDOWS[window]
    seg = jnp.asarray(_segments(LAYOUTS[layout]))
    qkv, g = _qkv(5)
    out_k, grads_k = _out_and_grads(_kernel_w(w), qkv, g, seg)
    out_x, grads_x = _out_and_grads(_xla_w(w), qkv, g, seg)
    out_r, grads_r = _out_and_grads(_written_out(w), qkv, g, seg)
    full = _written_out(None)(*qkv, seg)
    assert _rel(out_r, full) > 0.1  # the window is felt: these layouts have documents longer than it
    for out in (out_k, out_x):
        np.testing.assert_allclose(out, out_r, rtol=2 ** -7, atol=2 ** -7)
        assert _rel(out, out_r) < 4e-3
    for name, gk, gx, gr in zip("qkv", grads_k, grads_x, grads_r):
        assert _rel(gk, gr) < 1e-2 and _rel(gx, gr) < 1e-2, name
    # off by one either way is another function, further away than the rounding
    for other in (w - 1, w + 1):
        assert _rel(_written_out(other)(*qkv, seg), out_r) > 4 * _rel(out_k, out_r)


def test_window_in_float32_gives_the_mask_written_out_closely():
    seg = jnp.asarray(_segments(LAYOUTS["four_documents"]))
    qkv, g = _qkv(6, jnp.float32)
    out_r, grads_r = _out_and_grads(_written_out(100), qkv, g, seg)
    with jax.default_matmul_precision("highest"):
        for fn in (_kernel_w(100), _xla_w(100)):
            out, grads = _out_and_grads(fn, qkv, g, seg)
            np.testing.assert_allclose(out, out_r, rtol=1e-4, atol=1e-5)
            for a, b in zip(grads, grads_r):
                np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)


def test_a_window_no_shorter_than_the_sequence_is_no_window_bit_for_bit():
    seg = jnp.asarray(_segments(LAYOUTS["four_documents"]))
    qkv, g = _qkv(7)
    for with_window, without in ((_kernel_w(T), _kernel_w(None)), (_xla_w(T), _xla_w(None))):
        for a, b in zip(jax.tree.leaves(_out_and_grads(with_window, qkv, g, seg)),
                        jax.tree.leaves(_out_and_grads(without, qkv, g, seg))):
            np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_without_a_window_the_kernel_and_its_lists_are_the_parents():
    """``window=None`` asks for the kernel object the parent built (the cache's key
    as the parent's call gave it, plus ``None``), whose lists are the causal mask's;
    a window's are another object with fewer pairs."""
    blocks = tuple(attention.BLOCK_SIZES.items())
    parent = attention._causal_kernel(1, 8192, 2, blocks, False)
    ours = attention._causal_kernel(1, 8192, 2, blocks, False, None)
    windowed = attention._causal_kernel(1, 8192, 2, blocks, False, 2048)  # at the same blocks, to compare the lists
    assert ours is attention._causal_kernel(1, 8192, 2, blocks, False, None) and windowed is not ours
    for name in ("fwd_mask_info", "dq_mask_info", "dkv_mask_info"):
        a, b, c = getattr(parent, name), getattr(ours, name), getattr(windowed, name)
        for x, y in zip(a, b):
            assert (x is None and y is None) or np.array_equal(np.asarray(x), np.asarray(y)), name
        assert int((np.asarray(c.block_mask) > 0).sum()) < int((np.asarray(a.block_mask) > 0).sum()), name
    assert parent.kwargs["mask_function"] is not None and windowed.kwargs["mask_function"] is not None


def test_window_lists_run_the_pairs_the_count_needs_at_the_cells_sequence_length():
    """At 16 384 tokens, one document (the Trinity cell's layout) and packed
    documents: the window kernel's static lists hold the window's pairs and no
    more, and followed through the documents they run what the count says."""
    one = np.zeros((1, 16384), np.int32)
    packed = np.concatenate([_cell_packings(0)[0], _cell_packings(1)[0]])[None]
    b = attention.BLOCK_SIZES
    kernel = attention._causal_kernel(1, 16384, 2, tuple(b.items()), False, 2048)
    for seg in (one, packed):
        followed = attention._document_block_lists(kernel, jnp.asarray(seg.reshape(-1)), 2)
        for name, blocks in (("fwd", (b["block_q"], b["block_kv"])), ("dq", (b["block_q_dq"], b["block_kv_dq"])),
                             ("dkv", (b["block_q_dkv"], b["block_kv_dkv"]))):
            static = np.asarray(getattr(kernel, f"{name}_mask_info").block_mask)
            mask = np.asarray(getattr(followed, f"{name}_mask_info").block_mask)
            assert int((static > 0).sum()) == _dense_counts(one, *blocks, 2048)[1], name
            assert int((mask[0] > 0).sum()) == _dense_counts(seg, *blocks, 2048)[1], name
    n = 16384 // b["block_q"]  # a window of 2048 keys reaches 2048 / block + 1 key blocks a query block
    reach = 2048 // b["block_kv"] + 1
    assert _dense_counts(one, b["block_q"], b["block_kv"], 2048) == (
        n * (n + 1) // 2, sum(min(i + 1, reach) for i in range(n)))


def test_both_run_share_counters_and_run_meta_with_a_window():
    one, packed = jnp.zeros((1, 16384), jnp.int32), jnp.asarray(_cell_packings(0))
    assert attention.step_counters(one, 2048, 2) == {}  # the CPU
    assert attention.run_meta("cpu", 16384, 2048) == {"attention_lowering": "xla", "attention_window": 2048}
    assert attention.run_meta("tpu", 16384, 2048) == {
        "attention_lowering": "kernel", "attention_block_skip": "documents", "attention_residuals": "kept",
        "attention_window": 2048}
    b = w = attention.BLOCK_SIZES  # window layers run the full layers' blocks
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        for seg in (one, packed):
            counters = attention.step_counters(seg, 2048, 2)
            assert set(counters) == {"attn/block_pairs_run_share", "attn/window_block_pairs_run_share"}
            assert set(attention.step_counters(seg)) == {"attn/block_pairs_run_share"}  # a model without a window
            causal, run = attention.block_pair_counts(np.asarray(seg), b["block_q"], b["block_kv"])
            np.testing.assert_allclose(float(counters["attn/block_pairs_run_share"]), run / causal, rtol=1e-6)
            causal, run = _dense_counts(np.asarray(seg), w["block_q"], w["block_kv"], 2048)
            np.testing.assert_allclose(float(counters["attn/window_block_pairs_run_share"]), run / causal, rtol=1e-6)
    assert float(attention.block_pairs_run_share(one, 1024, 1024)) == 1.0
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):  # one document: the window's 45 of the 136 causal pairs
        np.testing.assert_allclose(float(attention.step_counters(one, 2048, 2)[attention.WINDOW_RUN_SHARE]), 45 / 136, rtol=1e-6)


def test_window_kernel_lowers_for_tpu_at_the_trinity_cells_shape():
    """32 / 4 heads of 128, one sequence of 16 384 tokens, a window of 2048 keys,
    at the committed blocks of a window layer: JAX-level lowering only."""
    spec = lambda h: jax.ShapeDtypeStruct((1, 16384, h, 128), jnp.bfloat16)

    def fn(q, k, v, g, seg):
        out, vjp = jax.vjp(lambda q, k, v: attention._kernel_path(q, k, v, seg, 128 ** -0.5, window=2048), q, k, v)
        return out, vjp(g)

    text = jax.jit(fn).trace(spec(32), spec(4), spec(4), spec(32), jax.ShapeDtypeStruct((1, 16384), jnp.int32)).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") >= 3


# ---- the head-major entry (PR 48): what ``_kernel_path`` is now ----------------------------------------------------


@pytest.mark.parametrize("window", [None, 100, BLOCK], ids=["no_window", "window_100", "window_of_a_block"])
@pytest.mark.parametrize("batch", [1, 2])
def test_kernel_path_is_the_head_major_entry_fed_the_scaled_and_transposed_operands(batch, window):
    """Output and the three gradients, bit for bit: ``_kernel_path`` is the scale, one transpose an array,
    ``head_major_attention`` and one transpose back."""
    seg = jnp.asarray(np.concatenate([_segments(LAYOUTS["four_documents"]), _segments(LAYOUTS["one_document"])])[:batch])
    qkv, g = _qkv(11, batch=batch)

    def by_hand(q, k, v, seg):
        q = (q.astype(jnp.float32) * SCALE).astype(q.dtype)
        laid = lambda x: x.transpose(2, 0, 1, 3).reshape(x.shape[2], batch * T, x.shape[3])
        with _window_blocks():
            out = attention.head_major_attention(seg, HEADS, True, window)(laid(q), laid(k), laid(v))
        assert out.shape == (HEADS, batch * T, HEAD)
        return out.reshape(HEADS, batch, T, HEAD).transpose(1, 2, 0, 3)

    for a, b in zip(jax.tree.leaves(_out_and_grads(_kernel_w(window), qkv, g, seg)),
                    jax.tree.leaves(_out_and_grads(by_hand, qkv, g, seg))):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def _kernel_path_of_pr_47(q, k, v, segment_ids, scale, interpret=False, window=None):
    """A literal copy of ``ops/attention.py::_kernel_path`` as PR 48 found it."""
    from jax.experimental.pallas.ops.tpu import splash_attention as splash

    batch, t, heads, _ = q.shape
    kernel = attention._causal_kernel(batch, t, heads, tuple(attention.BLOCK_SIZES.items()), interpret, window)
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    end_to_end = lambda x: x.transpose(2, 0, 1, 3).reshape(x.shape[2], batch * t, x.shape[3])
    seg = segment_ids.reshape(batch * t)
    out = attention._document_block_lists(kernel, seg, heads)(
        end_to_end(q), end_to_end(k), end_to_end(v), splash.SegmentIds(q=seg, kv=seg))
    return out.reshape(heads, batch, t, -1).transpose(1, 2, 0, 3)


@pytest.mark.parametrize("window", [None, 2048], ids=["no_window", "window_2048"])
def test_packed_causal_attention_lowers_to_the_operations_of_the_kernel_path_pr_48_found(window):
    """At granite's shape (32 / 8 heads of 64, one sequence of 8192 tokens), output and gradients lowered for
    the TPU platform, every Mosaic body printed without its source locations: the same text, so the same
    operations in the same order, as the function this PR split in two."""
    from test_accepted_steps_lowering import _without_locations

    spec = lambda h: jax.ShapeDtypeStruct((1, 8192, h, 64), jnp.bfloat16)

    def lowered(attend):
        def fn(q, k, v, g, seg):
            out, vjp = jax.vjp(lambda q, k, v: attend(q, k, v, seg), q, k, v)
            return out, vjp(g)

        text = jax.jit(fn).trace(spec(32), spec(8), spec(8), spec(32), jax.ShapeDtypeStruct((1, 8192), jnp.int32)).lower(
            lowering_platforms=("tpu",)).as_text()
        return _without_locations(text)

    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        ours, kernels = lowered(lambda q, k, v, seg: attention.packed_causal_attention(q, k, v, seg, 0.125, 1024, window=window))
    theirs, _ = lowered(lambda q, k, v, seg: _kernel_path_of_pr_47(q, k, v, seg, 0.125, window=window))
    assert kernels == 3 and ours == theirs

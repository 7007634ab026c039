"""ops/attention_edges.py: the two passes between an attention layer's projections and its
attention kernels (ops/pallas/attention_edges.py, interpret mode on the CPU) against the lines the
models write (``lm_layers.rms_norm`` -> ``rope.apply_rotary_halves`` -> the scale -> the transpose;
back: the transpose -> float32 x sigmoid), values and every gradient; against the same lines in
float32; what the backward pass holds; which path a layer takes; what ``run_meta`` says."""

import contextlib
import dataclasses
import functools
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batchai_retinanet_horovod_coco_tpu.models import afmoe, lm_layers
from batchai_retinanet_horovod_coco_tpu.models.language import build_language_model
from batchai_retinanet_horovod_coco_tpu.ops import attention, rope
from batchai_retinanet_horovod_coco_tpu.ops import attention_edges as edges
from batchai_retinanet_horovod_coco_tpu.ops.pallas import attention_edges as kernel_lib

BATCH, T, SIZE = 2, 256, 128  # two sequences laid end to end for the kernels: 512 tokens, four token blocks
BLOCKS = (128, 8)
EPS, THETA = 1e-5, 10000.0
HEADS = {"q_32_heads": 32, "k_4_heads": 4}
CONFIG_FILE = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark", "configs", "trinity-mini-ep8.json")


def _angles():
    """Two documents a sequence, the positions beginning again in each."""
    seg = jnp.asarray(np.stack([np.repeat([0, 1], [100, T - 100]), np.repeat([0, 1], [T - 30, 30])]).astype(np.int32))
    return rope.document_positions(seg).astype(jnp.float32)[..., None] * rope.plain_inv_freq(SIZE, THETA)


def _in_operands(heads, seed=0):
    keys = jax.random.split(jax.random.key(seed), 3)
    x = (2.0 * jax.random.normal(keys[0], (BATCH, T, heads * SIZE), jnp.float32)).astype(jnp.bfloat16)
    w = 1.0 + 0.2 * jax.random.normal(keys[1], (SIZE,), jnp.float32)
    dy = jax.random.normal(keys[2], (heads, BATCH * T, SIZE), jnp.float32).astype(jnp.bfloat16)
    return x, w, dy


def _written_in(x, w, angles, scale, dtype=None):
    """The way in as models/afmoe.py::_attention and ops/attention.py::_kernel_path write it; ``dtype``
    float32: the same lines on a float32 copy of the product."""
    batch, t, _ = x.shape
    q = x.reshape(batch, t, -1, SIZE).astype(dtype or x.dtype)
    q = lm_layers.rms_norm(q, w, EPS)
    if angles is not None:
        q = rope.apply_rotary_halves(q, angles)
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    return q.transpose(2, 0, 1, 3).reshape(q.shape[2], batch * t, SIZE)


def _written_out(o, g, dtype=None):
    batch, t, _ = g.shape
    out = o.reshape(o.shape[0], batch, t, SIZE).transpose(1, 2, 0, 3).reshape(batch, t, -1)
    return (out.astype(jnp.float32) * jax.nn.sigmoid(g.astype(jnp.float32))).astype(dtype or g.dtype)


def _value_and_grads(fn, operands, dy):
    y, vjp = jax.vjp(fn, *operands)
    return (y, *vjp(dy.astype(y.dtype)))


def _worst(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))


@pytest.mark.parametrize("rotated", [True, False], ids=["rotated", "not_rotated"])
@pytest.mark.parametrize("scale", [SIZE ** -0.5, 1.0], ids=["softmax_scale", "scale_1"])
@pytest.mark.parametrize("which", HEADS)
def test_the_way_in_is_the_written_lines_and_no_further_from_float32_than_they_are(which, scale, rotated):
    """Value, the product's gradient and the norm scale's: within a bfloat16 rounding or two of the written
    lines (which round three times), and against the written lines in float32 never worse than they."""
    x, w, dy = _in_operands(HEADS[which])
    angles = _angles() if rotated else None
    ours = _value_and_grads(lambda x, w: edges.heads_in(x, w, angles, EPS, scale, BLOCKS, True), (x, w), dy)
    written = _value_and_grads(lambda x, w: _written_in(x, w, angles, scale), (x, w), dy)
    exact = _value_and_grads(lambda x, w: _written_in(x, w, angles, scale, jnp.float32), (x, w), dy)
    assert ours[0].shape == (HEADS[which], BATCH * T, SIZE) and ours[0].dtype == ours[1].dtype == x.dtype
    assert ours[2].shape == w.shape and ours[2].dtype == w.dtype
    for name, a, b, c in zip(("y", "dx", "dw"), ours, written, exact):
        size = float(jnp.max(jnp.abs(c)))
        assert _worst(a, b) <= 0.02 * size, name  # three bfloat16 roundings of the written lines
        # one rounding: the last bit of bfloat16 (where the written lines round nothing on the way, scale 1 and no
        # rotation, they ARE the float32 lines rounded, and a float32 product ordered otherwise moves that bit)
        assert _worst(a, c) <= max(_worst(b, c), 0.008 * size), name
        assert np.linalg.norm(np.asarray(a, np.float32) - np.asarray(c, np.float32)) <= 1.001 * np.linalg.norm(
            np.asarray(b, np.float32) - np.asarray(c, np.float32)) + 1e-3 * np.linalg.norm(np.asarray(c, np.float32)), name


@pytest.mark.parametrize("heads", [32, 4])
def test_the_way_out_is_the_written_lines(heads):
    keys = jax.random.split(jax.random.key(1), 3)
    o = jax.random.normal(keys[0], (heads, BATCH * T, SIZE), jnp.float32).astype(jnp.bfloat16)
    g = (2.0 * jax.random.normal(keys[1], (BATCH, T, heads * SIZE), jnp.float32)).astype(jnp.bfloat16)
    dy = jax.random.normal(keys[2], (BATCH, T, heads * SIZE), jnp.float32).astype(jnp.bfloat16)
    ours = _value_and_grads(lambda o, g: edges.heads_out(o, g, BLOCKS, True), (o, g), dy)
    written = _value_and_grads(_written_out, (o, g), dy)
    exact = _value_and_grads(lambda o, g: _written_out(o, g, jnp.float32), (o, g), dy)
    np.testing.assert_array_equal(np.asarray(ours[0], np.float32), np.asarray(written[0], np.float32))
    for name, a, b, c in zip(("do", "dg"), ours[1:], written[1:], exact[1:]):
        assert a.shape == b.shape and a.dtype == b.dtype == jnp.bfloat16, name
        assert _worst(a, b) <= 0.008 * float(jnp.max(jnp.abs(c))), name  # the last bit, where a product was ordered otherwise
        assert _worst(a, c) <= 1.01 * _worst(b, c), name


def test_the_layout_alone_is_a_transpose():
    x = jnp.arange(BATCH * T * 4 * SIZE, dtype=jnp.float32).reshape(BATCH, T, 4 * SIZE)
    want = x.reshape(BATCH, T, 4, SIZE).transpose(2, 0, 1, 3).reshape(4, BATCH * T, SIZE)
    np.testing.assert_array_equal(np.asarray(edges.head_major(x, 4)), np.asarray(want))


def test_the_rotation_by_one_roll_is_apply_rotary_halves():
    x = jax.random.normal(jax.random.key(2), (BATCH * T, SIZE), jnp.float32)
    cos, sin = edges.rotation_tables(_angles())
    want = rope.apply_rotary_halves(x.reshape(BATCH, T, SIZE), _angles()).reshape(BATCH * T, SIZE)
    np.testing.assert_array_equal(np.asarray(x * cos + jnp.roll(x, SIZE // 2, axis=-1) * sin), np.asarray(want))


# ---- what the backward pass holds ------------------------------------------------------------------------------


def _large_float32_moves(jaxpr, least: int, found=None):
    """The ``concatenate``, ``pad`` and ``transpose`` of ``jaxpr`` (and of every jaxpr inside it but the kernels'
    own bodies, which live in VMEM) with a float32 result of ``least`` elements or more."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("concatenate", "pad", "transpose"):
            found += [(eqn.primitive.name, v.aval.shape) for v in eqn.outvars
                      if v.aval.dtype == jnp.float32 and v.aval.size >= least]
        if eqn.primitive.name != "pallas_call":
            for inner in jax.core.jaxprs_in_params(eqn.params):
                _large_float32_moves(inner, least, found)
    return found


@pytest.mark.parametrize("rotated", [True, False], ids=["rotated", "not_rotated"])
def test_the_backward_pass_moves_no_q_sized_float32_array(rotated):
    """At the cell's shape (traced, nothing run): the gradient of both ways through the passes holds no
    ``concatenate``, ``pad`` or ``transpose`` of a float32 array as large as q; the written lines' holds
    the rotation's (the checker sees them; what XLA makes of the written lines' ``astype`` and bfloat16 ``transpose``, the
    float32 copies of PERF.md section 6, PR 48, no jaxpr shows)."""
    tokens, heads = 16384, 32
    x = jax.ShapeDtypeStruct((1, tokens, heads * SIZE), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((SIZE,), jnp.float32)
    angles = jax.ShapeDtypeStruct((1, tokens, SIZE // 2), jnp.float32) if rotated else None

    def through(way_in, way_out):
        def loss(x, g, w, angles):
            return jnp.sum(way_out(way_in(x, w, angles), g).astype(jnp.float32))
        return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, w, angles).jaxpr

    q_sized = tokens * heads * SIZE
    ours = through(lambda x, w, a: edges.heads_in(x, w, a, EPS, SIZE ** -0.5), edges.heads_out)
    assert _large_float32_moves(ours, q_sized) == []
    written = _large_float32_moves(through(lambda x, w, a: _written_in(x, w, a, SIZE ** -0.5), _written_out), q_sized)  # the rotation's halves joined, each way
    assert written == ([("concatenate", (1, tokens, heads, SIZE))] * 2 if rotated else []), written


@pytest.mark.parametrize("heads", [32, 4])
@pytest.mark.parametrize("rotated", [True, False], ids=["rotated", "not_rotated"])
def test_the_kernels_lower_for_tpu_at_the_cells_shape_with_the_committed_blocks(heads, rotated):
    """16 384 tokens of 32 / 4 heads of 128, both ways and their gradients: JAX-level lowering only."""
    x = jax.ShapeDtypeStruct((1, 16384, heads * SIZE), jnp.bfloat16)
    angles = jax.ShapeDtypeStruct((1, 16384, SIZE // 2), jnp.float32) if rotated else None

    def fn(x, g, w, angles):
        y, vjp = jax.vjp(lambda x, g, w: edges.heads_out(edges.heads_in(x, w, angles, EPS, 0.5), g), x, g, w)
        return y, vjp(g)

    text = jax.jit(fn).trace(x, x, jax.ShapeDtypeStruct((SIZE,), jnp.float32), angles).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 4 and all(
        name in text for name in ("heads_in_fwd", "heads_in_bwd", "heads_out_fwd", "heads_out_bwd"))


# ---- which path a layer takes ------------------------------------------------------------------------------------


@pytest.mark.parametrize("backend,seq_len,head_dim,want", [
    ("tpu", 16384, 128, edges.KERNEL), ("tpu", 8192, 256, edges.KERNEL), ("tpu", 1024, 128, edges.KERNEL),
    ("cpu", 16384, 128, edges.XLA),  # the CPU
    ("tpu", 16384, 16, edges.XLA), ("tpu", 16384, 64, edges.XLA), ("tpu", 16384, 192, edges.XLA),  # no whole lane tiles
    ("tpu", 16384 + 512, 128, edges.XLA), ("tpu", 1000, 128, edges.XLA), ("tpu", 0, 128, edges.XLA),  # no whole blocks
])
def test_lowering_follows_the_backend_the_attention_lowering_and_the_heads_size(backend, seq_len, head_dim, want):
    assert edges.lowering(backend, seq_len, head_dim) == want
    if want == edges.KERNEL:
        assert attention.lowering(backend, seq_len) == attention.KERNEL and seq_len % kernel_lib.TOKEN_BLOCK == 0


def test_run_meta_says_which_edges_the_step_takes():
    """The published model at its cell's bucket: the passes on a TPU, the written lines on the CPU and at a ragged
    sequence; the tiny preset (heads of 16) the written lines everywhere."""
    published = build_language_model(CONFIG_FILE)
    assert published.run_meta((1, 16384))["attention_edges"] == "xla"  # this process's backend is the CPU
    assert afmoe.Afmoe(afmoe.TINY).run_meta((2, 64))["attention_edges"] == "xla"
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        meta = published.run_meta((1, 16384))
        assert (meta["attention_lowering"], meta["attention_edges"]) == ("kernel", "kernel")
        assert published.run_meta((1, 16384 + 192))["attention_edges"] == "xla"
        assert afmoe.Afmoe(afmoe.TINY).run_meta((1, 16384))["attention_edges"] == "xla"


# ---- the model's layer on either path -----------------------------------------------------------------------------

WIDE = dataclasses.replace(afmoe.TINY, head_dim=SIZE, sliding_window=100, num_attention_heads=4, num_key_value_heads=2)
SEG = jnp.asarray(np.stack([np.repeat([0, 1], [100, T - 100]), np.zeros(T)]).astype(np.int32))


def _layer_operands(config, seed=3):
    p = afmoe.init_params(config, jax.random.key(seed))["attention"]["layer_0"]
    keys = jax.random.split(jax.random.key(seed + 1), 3)
    p = dict(p, q_norm=1.0 + 0.2 * jax.random.normal(keys[0], (SIZE,)), k_norm=1.0 + 0.2 * jax.random.normal(keys[1], (SIZE,)))
    p = {k: (8.0 * v if v.ndim == 2 else v) for k, v in p.items()}  # scores and gates that are not all alike
    return p, jax.random.normal(keys[2], (BATCH, T, config.hidden_size), jnp.float32).astype(config.dtype)


def _attend(config, kind, p, u):
    out, vjp = jax.vjp(lambda p, u: afmoe._attention(config, kind, p, u, SEG, rope.document_positions(SEG)), p, u)
    return out, vjp(jnp.ones_like(out))


@contextlib.contextmanager
def _on_a_tpu_in_the_interpreter():
    """The backend says ``tpu``; every kernel runs in Pallas's interpreter at blocks T holds two of."""
    attend = attention.head_major_attention
    with contextlib.ExitStack() as stack:
        for target, name, value in (
                (jax, "default_backend", lambda: "tpu"),
                (attention, "BLOCK_SIZES", {name: 128 for name in attention.BLOCK_SIZES}),
                (kernel_lib, "TOKEN_BLOCK", 128),
                (attention, "head_major_attention", lambda seg, heads, interpret=True, window=None: attend(seg, heads, True, window)),
                (edges, "heads_in", functools.partial(edges.heads_in, interpret=True)),
                (edges, "heads_out", functools.partial(edges.heads_out, interpret=True))):
            stack.enter_context(mock.patch.object(target, name, value))
        yield


@pytest.mark.parametrize("kind", [afmoe.SLIDING, afmoe.FULL])
def test_a_layer_through_the_passes_is_the_layer_through_the_written_lines(kind):
    """``afmoe._attention`` with 4 / 2 heads of 128 where the kernels run (the passes and the splash kernels in the
    interpreter) against the same call on the CPU (the written lines, the xla attention): the output and the
    gradients of every weight and of the input, to what bfloat16 leaves of either."""
    p, u = _layer_operands(WIDE)
    want = _attend(WIDE, kind, p, u)
    with _on_a_tpu_in_the_interpreter():
        assert afmoe._edges(WIDE, T) == edges.KERNEL
        jaxpr = jax.make_jaxpr(lambda p, u: _attend(WIDE, kind, p, u))(p, u)
        got = _attend(WIDE, kind, p, u)
    text = str(jaxpr)
    assert all(name in text for name in ("heads_in_fwd", "heads_in_bwd", "heads_out_fwd", "heads_out_bwd", "splash_mha_fwd"))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a.shape == b.shape and np.linalg.norm(a - b) <= 0.02 * np.linalg.norm(b), jax.tree_util.keystr(path)


@pytest.mark.parametrize("why,config,t", [("heads_of_16", afmoe.TINY, T), ("a_ragged_sequence", WIDE, T - 56)])
def test_where_the_passes_cannot_run_a_layer_takes_the_written_lines(why, config, t, monkeypatch):
    """On a TPU too: heads that are no whole lane tiles, a sequence that is no whole blocks.  The written lines
    call ``lm_layers.rms_norm`` on four dimensions, ``rope.apply_rotary_halves``, ``packed_causal_attention``
    and ``jax.nn.sigmoid`` BY NAME (the benchmark's mutations patch them there)."""
    called = []

    def spy(module, name, note):
        real = getattr(module, name)

        def wrapper(*args, **kwargs):
            called.append(note(*args, **kwargs))
            return real(*args, **kwargs)

        monkeypatch.setattr(module, name, wrapper)

    spy(lm_layers, "rms_norm", lambda x, w, eps: f"rms_norm{x.ndim}")
    spy(rope, "apply_rotary_halves", lambda x, angles: "rotary")
    spy(attention, "packed_causal_attention", lambda q, k, v, seg, scale, block, window=None: f"packed{window}")
    spy(jax.nn, "sigmoid", lambda x: f"sigmoid{x.ndim}")
    seg = SEG[:, :t]
    p = afmoe.init_params(config, jax.random.key(0))["attention"]["layer_0"]
    u = jnp.ones((BATCH, t, config.hidden_size), config.dtype)
    with _on_a_tpu_in_the_interpreter():
        assert afmoe._edges(config, t) == edges.XLA, why
        jax.eval_shape(lambda p, u: afmoe._attention(config, afmoe.SLIDING, p, u, seg, rope.document_positions(seg)), p, u)
    assert called == ["rms_norm4", "rms_norm4", "rotary", "rotary", f"packed{config.sliding_window}", "sigmoid3"]

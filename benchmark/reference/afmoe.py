"""A plain reference of Arcee's Trinity family (``model_type`` ``afmoe``:
grouped-query attention layers of two kinds, sliding-window with rotary
positions and full without any, a sigmoid gate on attention's output, a norm on
every sublayer's input and output, leading dense MLPs, then sigmoid-routed
experts with a shared one, untied head) for a chip that holds a share of the
routed experts: forward pass, loss, gradients, in ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``.

Written from the published ``config.json``'s keys (a plain dict, ``hf``) and,
where the config has no key, the family's public modelling code (transformers
``modeling_afmoe.py``: ``AfmoeAttention``, ``AfmoeTokenChoiceRouter``,
``AfmoeMoE``, ``AfmoeDecoderLayer``) AS THIS REPOSITORY'S ISSUE 46 STATES IT -
there is no network here, so each such point is marked [assumed] and listed in
the benchmark's configuration file as "not checked against the hub" -,
independently of the program's ``models/afmoe.py``, ``ops/attention.py``,
``ops/moe.py`` and ``ops/rope.py``: it shares their parameter tree and nothing
else.  No kernels, no sorting, no buffers: the mask is a comparison of
positions and segment ids written out; an expert is applied to EVERY token and
multiplied by the token's weight for it where the token picked it, else by zero;
positions are counted, not scanned; the rotation is a complex product.

``held`` is the list of the expert ids whose weights ``params["experts"]``
holds, in that order.  What the other experts would add is left out, here as in
the program (the chip's share of an expert-parallel layer); ``held`` = all of
them is the uncut layer.

The points the config does not settle, each at its line below:

- [norms] four RMSNorms a layer: on attention's input and OUTPUT, on the MLP's
  input and OUTPUT (the output ones inside the residual branch).
- [qk-norm] RMSNorm over the channels of each head of q and of k, one scale
  vector each, before any rotation.
- [nope] a ``full_attention`` layer rotates nothing; a ``sliding_attention``
  layer rotates q and k by the plain ``rope_theta`` frequencies, channel ``j``
  with ``j + head_dim / 2``.
- [window] a query sees ``sliding_window`` keys, itself among them (the
  library's convention): ``0 <= t - s < sliding_window``.
- [gate] ``W_o (heads * sigmoid(W_g u))``.
- [bias] ``expert_bias`` is a buffer of zeros unless ``hf`` carries rows of it;
  it is added for the choice alone.  The published 1e-20 in ``route_norm``'s
  sum is left out (it is below float32's resolution of a sum of sigmoids).
- [packed] one sequence holds several documents: attention is masked to the
  query's own document and a token's rotary position is its index inside it.
- [share] only the experts in ``held`` are computed.
- [top-k] the k largest by a descending sort.
- [float32] everything is float32.
- ``q_block``, ``head_block``, ``logits_block``, ``loss_and_grads_by_layer``:
  only where asked, so that 16 384 tokens fit: attention for that many queries
  and heads at a time against all keys, each block recomputed in the backward
  pass; the head's logits for that many tokens at a time; the chain rule written
  out per layer.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = "highest"
SLIDING, FULL = "sliding_attention", "full_attention"


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def mlp(p, u):
    gate, up = jnp.split(u @ p["gate_up"], 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ p["down"]


def document_positions(seg):
    """[packed] a token's position inside its document: the earlier tokens
    that bear its id."""
    t = seg.shape[0]
    earlier = jnp.arange(t)[None, :] < jnp.arange(t)[:, None]
    return jnp.sum((seg[:, None] == seg[None, :]) & earlier, axis=1)


def rotate(x, positions, theta: float):
    """Channels ``(j, j + dim / 2)`` of the last axis are one complex number,
    turned by ``position x theta^(-2j / dim)``.  x: (T, heads, dim); the layout
    is kept."""
    dim = x.shape[-1]
    freq = jnp.asarray([float(theta) ** (-2.0 * j / dim) for j in range(dim // 2)], jnp.float32)
    angle = (positions.astype(jnp.float32)[:, None] * freq)[:, None, :]
    z = lax.complex(x[..., : dim // 2], x[..., dim // 2:]) * lax.complex(jnp.cos(angle), jnp.sin(angle))
    return jnp.concatenate([jnp.real(z), jnp.imag(z)], axis=-1)


# ---- layers -----------------------------------------------------------------


def attention(hf, kind, p, u, seg, q_block=None, head_block=None):
    """``AfmoeAttention`` of one kind of layer.  u: (T, d), seg: (T,)."""
    t = u.shape[0]
    heads, kv, size, eps = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"], hf["rms_norm_eps"]
    q = rms_norm((u @ p["q"]).reshape(t, heads, size), p["q_norm"], eps)  # [qk-norm]
    k = rms_norm((u @ p["k"]).reshape(t, kv, size), p["k_norm"], eps)
    v = (u @ p["v"]).reshape(t, kv, size)
    if kind == SLIDING:  # [nope]
        positions = document_positions(seg)
        q, k = rotate(q, positions, hf["rope_theta"]), rotate(k, positions, hf["rope_theta"])
    group = heads // kv
    q, k, v = q.transpose(1, 0, 2), jnp.repeat(k, group, axis=1).transpose(1, 0, 2), jnp.repeat(
        v, group, axis=1).transpose(1, 0, 2)
    hb, qb = head_block or heads, q_block or t
    pos = jnp.arange(t)

    def rows(args):
        q_rows, pos_q, seg_q = args  # (heads, qb, size), (qb,), (qb,)
        mask = (pos_q[:, None] >= pos[None, :]) & (seg_q[:, None] == seg[None, :])  # [packed]
        if kind == SLIDING:
            mask = mask & (pos_q[:, None] - pos[None, :] < hf["sliding_window"])  # [window]

        def some_heads(qkv):
            qh, kh, vh = qkv
            scores = size ** -0.5 * jnp.einsum("hqd,hsd->hqs", qh, kh)
            return jnp.einsum("hqs,hsd->hqd", jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1), vh)

        blocks = [a.reshape(heads // hb, hb, *a.shape[1:]) for a in (q_rows, k, v)]
        return lax.map(jax.checkpoint(some_heads), tuple(blocks)).reshape(heads, qb, size)

    o = lax.map(rows, (q.reshape(heads, t // qb, qb, size).transpose(1, 0, 2, 3), pos.reshape(t // qb, qb),
                       seg.reshape(t // qb, qb)))
    o = o.transpose(0, 2, 1, 3).reshape(t, heads * size)
    return (o * jax.nn.sigmoid(u @ p["gate"])) @ p["o"]  # [gate]


def expert_bias(hf, index: int):
    rows = hf.get("expert_bias")
    total = hf.get("num_experts_total", hf["num_experts"])
    return jnp.zeros((total,), jnp.float32) if not rows else jnp.asarray(rows[index], jnp.float32)  # [bias]


def gate(hf, router, u, bias):
    """``AfmoeTokenChoiceRouter``: ``(weights (T, experts), picks (T, k))``: the
    picked sigmoids over their sum times ``route_scale`` where the token picked
    the expert, zero elsewhere; the picks by score + bias."""
    scores = jax.nn.sigmoid(u @ router["gate"])
    picks = jnp.argsort(-(scores + bias), axis=-1)[:, : hf["num_experts_per_tok"]]  # [top-k] [bias]
    picked = jnp.sum(jax.nn.one_hot(picks, scores.shape[-1], dtype=scores.dtype), axis=1)
    weights = scores * picked / jnp.sum(scores * picked, axis=-1, keepdims=True)  # route_norm, over all the picks
    return hf["route_scale"] * weights, picks


def moe(hf, router, experts, shared, u, held, bias):
    """``AfmoeMoE``: -> (F(u), the picks)."""
    weights, picks = gate(hf, router, u, bias)
    y = mlp(shared, u)  # one MLP of num_shared_experts x the routed width
    for j, e in enumerate(held):  # [share]
        y = y + weights[:, e, None] * mlp(jax.tree.map(lambda w: w[j], experts), u)
    return y, picks


def is_dense(hf, i: int) -> bool:
    return i < hf["num_dense_layers"]


def layer(hf, i, attn_p, mlp_p, norms, x, seg, held, q_block=None, head_block=None):
    """Decoder layer ``i`` on one sequence: x (T, d) -> (y, picks); no picks
    but an expert layer's."""
    eps, seg = hf["rms_norm_eps"], jnp.asarray(seg)
    a = attention(hf, hf["layer_types"][i], attn_p, rms_norm(x, norms["attention_in"], eps), seg, q_block, head_block)
    h = x + rms_norm(a, norms["attention_out"], eps)  # [norms]
    u = rms_norm(h, norms["mlp_in"], eps)
    if is_dense(hf, i):
        return h + rms_norm(mlp(mlp_p, u), norms["mlp_out"], eps), jnp.zeros((0,), jnp.int32)
    f, picks = moe(hf, *mlp_p, u, held, expert_bias(hf, i - hf["num_dense_layers"]))
    return h + rms_norm(f, norms["mlp_out"], eps), picks


def _layer_params(params, hf, i):
    name = f"layer_{i}"
    mlp_p = params["dense_mlp"][name] if is_dense(hf, i) else (
        params["router"][name], params["experts"][name], params["shared"][name])
    return params["attention"][name], mlp_p, params["norms"][name]


def embed(hf, table, tokens):
    rows = table[tokens]
    return math.sqrt(hf["hidden_size"]) * rows if hf.get("mup_enabled", True) else rows


def head_loss(hf, params, x, tokens, seg, logits_block=None):
    """(sum of next-token cross-entropies over counted positions, logits); the
    logits of ``logits_block`` tokens at a time, and then no logits are returned."""
    x = rms_norm(x, params["norms"]["final"], hf["rms_norm_eps"])
    rows = params["head"]["rows"]
    counted = jnp.concatenate([seg[1:] == seg[:-1], jnp.zeros((1,), bool)])
    targets = jnp.concatenate([tokens[1:], tokens[:1]])  # the last position is never counted

    def nll_of(args):
        xs, tg = args
        logits = xs @ rows.T
        return -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), tg[:, None], axis=-1)[:, 0], logits

    if logits_block is None:
        nll, logits = nll_of((x, targets))
    else:
        n = x.shape[0] // logits_block
        nll = lax.map(jax.checkpoint(lambda a: nll_of(a)[0]),
                      (x.reshape(n, logits_block, -1), targets.reshape(n, logits_block))).reshape(-1)
        logits = None
    return jnp.sum(jnp.where(counted, nll, 0.0)), logits


def _sequence(hf, params, tok, sg, held, blocks):
    x = embed(hf, params["embed"]["embedding"], tok)
    for i in range(hf["num_hidden_layers"]):
        x, _ = layer(hf, i, *_layer_params(params, hf, i), x, sg, held, blocks.get("q_block"), blocks.get("head_block"))
    return x


def forward(hf, params, tokens, seg, held, **blocks):
    """Logits (batch, T, vocabulary held) of a batch of packed sequences."""
    with jax.default_matmul_precision(HIGHEST):
        return jnp.stack([head_loss(hf, params, _sequence(hf, params, t, s, held, blocks), t, s)[1]
                          for t, s in zip(tokens, seg)])


def _count(seg):
    return jnp.maximum(jnp.sum(seg[:, 1:] == seg[:, :-1]), 1).astype(jnp.float32)


def loss(hf, params, tokens, seg, held, **blocks):
    """The mean cross-entropy of the next token over the batch's positions
    whose next token lies in the same document."""
    with jax.default_matmul_precision(HIGHEST):
        total = 0.0
        for tok, sg in zip(tokens, seg):
            x = _sequence(hf, params, tok, sg, held, blocks)
            total = total + head_loss(hf, params, x, tok, sg, blocks.get("logits_block"))[0]
        return total / _count(seg)


def loss_and_grads(hf, params, tokens, seg, held, **blocks):
    """-> (loss, gradients of the loss)."""
    return jax.value_and_grad(lambda p: loss(hf, p, tokens, seg, held, **blocks))(params)


def loss_and_grads_by_layer(hf, params, tokens, seg, held, **blocks):
    """``loss_and_grads`` with the chain rule written out per layer (one
    layer's float32 activations alive at a time), and each expert layer's
    picks: -> (loss, gradients, picks (batch, expert layers, T, k))."""
    held = tuple(held)
    q_block, head_block, logits_block = (blocks.get(k) for k in ("q_block", "head_block", "logits_block"))
    with jax.default_matmul_precision(HIGHEST):
        count = _count(seg)
        fns = {}

        def layer_fn(i):
            """One jitted forward and one backward a (denseness, kind of attention, bias row)."""
            dense = is_dense(hf, i)
            key = (dense, hf["layer_types"][i], None if dense or not hf.get("expert_bias") else i)
            if key not in fns:
                fwd = jax.jit(lambda ap, mp, np_, x, sg: layer(hf, i, ap, mp, np_, x, sg, held, q_block, head_block))
                bwd = jax.jit(lambda ap, mp, np_, x, sg, dy: jax.vjp(
                    lambda ap, mp, np_, x: layer(hf, i, ap, mp, np_, x, sg, held, q_block, head_block)[0],
                    ap, mp, np_, x)[1](dy))
                fns[key] = (fwd, bwd)
            return fns[key]

        head = jax.jit(jax.value_and_grad(
            lambda h, w, x, tok, sg: head_loss(hf, {"head": {"rows": h}, "norms": {"final": w}}, x, tok, sg,
                                               logits_block)[0] / count, argnums=(0, 1, 2)))
        embed_bwd = jax.jit(lambda e, tok, dx: jax.vjp(lambda e: embed(hf, e, tok), e)[1](dx)[0])
        add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))

        grads = jax.tree.map(jnp.zeros_like, params)
        total, picks = 0.0, []
        e, h, w = params["embed"]["embedding"], params["head"]["rows"], params["norms"]["final"]
        for tok, sg in zip(tokens, seg):
            xs, picks_here = [embed(hf, e, tok)], []
            for i in range(hf["num_hidden_layers"]):
                y, pk = layer_fn(i)[0](*_layer_params(params, hf, i), xs[-1], sg)
                xs.append(y)
                if not is_dense(hf, i):
                    picks_here.append(pk)
            picks.append(jnp.stack(picks_here))
            part, (dh, dw, dx) = head(h, w, xs.pop(), tok, sg)
            total = total + part
            grads["head"]["rows"] = grads["head"]["rows"] + dh
            grads["norms"]["final"] = grads["norms"]["final"] + dw
            for i in reversed(range(hf["num_hidden_layers"])):
                name = f"layer_{i}"
                dap, dmp, dnp, dx = layer_fn(i)[1](*_layer_params(params, hf, i), xs.pop(), sg, dx)
                grads["attention"][name] = add(grads["attention"][name], dap)
                grads["norms"][name] = add(grads["norms"][name], dnp)
                if is_dense(hf, i):
                    grads["dense_mlp"][name] = add(grads["dense_mlp"][name], dmp)
                else:
                    for group, d in zip(("router", "experts", "shared"), dmp):
                        grads[group][name] = add(grads[group][name], d)
            grads["embed"]["embedding"] = grads["embed"]["embedding"] + embed_bwd(e, tok, dx)
        return total, grads, jnp.stack(picks)

"""A plain reference of Olmo-Hybrid (Gated DeltaNet linear-attention mixers,
three of every four, beside full multi-head attention; gated MLPs; every
sublayer's OUTPUT normalised; untied head): forward pass, loss and gradients in
``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``.

Written from the published ``config.json``'s keys (passed as a plain dict,
``hf``) and the gated delta rule's RECURRENCE (Yang, Kautz, Hatamizadeh,
arXiv:2412.06464, eq. 10: ``S_t = a_t S_{t-1} (I - b_t k_t k_t^T) + b_t v_t k_t^T``),
independently of the program's ``models/olmo_hybrid.py`` and ``ops/delta_rule.py``:
it shares their parameter tree and nothing else.  The recurrence runs TOKEN BY
TOKEN in a ``lax.scan`` (no chunks, no triangular system, no kernels), attention
is a dense masked softmax over all positions, the convolution gathers its taps,
nothing is rounded below float32 and, by default, nothing is recomputed.

Departures from the description, each only where asked for by an argument:

- ``scan_block``: the token-by-token scan is cut into blocks of that many tokens
  and each block is recomputed in the backward pass (``jax.checkpoint``); the same
  recurrence in the same order, but the backward pass keeps one state per block
  and not one per token (8192 states of 30 x 192 x 96 float32 are 18 GB).
- ``head_block``: attention is computed for that many heads at a time
  (``lax.map``), each block recomputed in the backward pass; the same dense masked
  softmax per head (all 30 heads at once are 8 GB of scores at 8192 tokens).
- ``loss_and_grads_by_layer``: the chain rule written out layer by layer (every
  layer's input kept, each layer's forward repeated inside its own ``jax.vjp``),
  so that one layer's float32 activations are alive at a time.  ``loss_and_grads``
  is ``jax.value_and_grad`` of ``loss``; a test holds the two equal.

``counters`` are the reference's own readings of what the program logs as
``gdn/alpha_mean``, ``gdn/beta_mean`` and ``gdn/state_norm_max``: the last is the
largest Frobenius norm of a head's state after every ``every``-th token (the
program reads its states where its chunks end; the recurrence has no chunks, so
it is told which tokens to look at and nothing else).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = "highest"
LINEAR, FULL = "linear_attention", "full_attention"
GROUP = {LINEAR: "gdn", FULL: "attention"}
L2_EPS = 1e-6


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def mlp(p, u):
    gate, up = jnp.split(u @ p["gate_up"], 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ p["down"]


def attention(hf, p, u, seg, head_block=None):
    """u: (T, d), seg: (T,).  Dense: every query scores every position, and the
    mask keeps the past of its own document."""
    t, d = u.shape
    heads, kv_heads = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = d // heads
    eps = hf["rms_norm_eps"]
    q = rms_norm(u @ p["q"], p["q_norm"], eps).reshape(t, heads, hd).transpose(1, 0, 2)
    k = rms_norm(u @ p["k"], p["k_norm"], eps).reshape(t, kv_heads, hd).transpose(1, 0, 2)
    v = (u @ p["v"]).reshape(t, kv_heads, hd).transpose(1, 0, 2)
    theta = (hf.get("rope_parameters") or {}).get("rope_theta")
    pos = jnp.arange(t)
    if theta is not None:
        first = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
        in_doc = pos - lax.cummax(jnp.where(first, pos, 0))  # the position inside the document
        angles = in_doc[:, None] * (1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))[None, :]
        cos, sin = jnp.cos(angles), jnp.sin(angles)  # (T, hd / 2); elements (2 i, 2 i + 1) turn together

        def turn(x):
            a, b = x[..., 0::2], x[..., 1::2]
            return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)

        q, k = turn(q), turn(k)
    k, v = (jnp.repeat(a, heads // kv_heads, axis=0) for a in (k, v))
    mask = (pos[:, None] >= pos[None, :]) & (seg[:, None] == seg[None, :])

    def some_heads(qkv):
        qh, kh, vh = qkv
        scores = hd ** -0.5 * jnp.einsum("hqd,hsd->hqs", qh, kh)
        return jnp.einsum("hqs,hsd->hqd", jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1), vh)

    if head_block is None:
        out = some_heads((q, k, v))
    else:
        blocks = [a.reshape(heads // head_block, head_block, t, hd) for a in (q, k, v)]
        out = lax.map(jax.checkpoint(some_heads), tuple(blocks)).reshape(heads, t, hd)
    return out.transpose(1, 0, 2).reshape(t, d) @ p["o"]


def gated_delta_recurrence(q, k, v, log_a, b, seg, scan_block=None):
    """The recurrence, token by token: q, k (T, H, K), v (T, H, V), log_a, b (T, H),
    seg (T,) -> ``o`` (T, H, V) and the squared Frobenius norm of every head's
    state after every token, (T, H)."""
    t, heads, kd = q.shape
    first = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])  # a document's first token

    def token(state, inp):
        q_t, k_t, v_t, la_t, b_t, first_t = inp
        state = jnp.where(first_t, 0.0, state)
        state = jnp.exp(la_t)[:, None, None] * state  # a_t S_{t-1}
        read = jnp.einsum("hvk,hk->hv", state, k_t)  # what the decayed state returns for this key
        state = state + (b_t[:, None] * (v_t - read))[:, :, None] * k_t[:, None, :]
        return state, (jnp.einsum("hvk,hk->hv", state, q_t), jnp.sum(state * state, axis=(1, 2)))

    inputs = (q, k, v, log_a, b, first)
    state0 = jnp.zeros((heads, v.shape[-1], kd), jnp.float32)
    if scan_block is None:
        _, (o, sq) = lax.scan(token, state0, inputs)
        return o, sq
    if t % scan_block:
        raise ValueError(f"scan_block {scan_block} does not divide {t} tokens")
    blocks = jax.tree.map(lambda x: x.reshape(t // scan_block, scan_block, *x.shape[1:]), inputs)
    _, (o, sq) = lax.scan(jax.checkpoint(lambda s, blk: lax.scan(token, s, blk)), state0, blocks)
    return o.reshape(t, heads, -1), sq.reshape(t, heads)


def gated_delta_net(hf, p, u, seg, scan_block=None):
    """u: (T, d), seg: (T,) -> (the mixer's output (T, d), (a (T, H), b (T, H),
    the states' squared norms (T, H)))."""
    t = u.shape[0]
    heads, kh, vh = hf["linear_num_value_heads"], hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    width = hf["linear_conv_kernel_dim"]
    kd, vd = heads * kh, heads * vh
    qkv, z, da, db = jnp.split(u @ p["in_proj"], [2 * kd + vd, 2 * kd + 2 * vd, 2 * kd + 2 * vd + heads], axis=-1)
    # Depthwise causal convolution: tap j reads the token (width - 1 - j) back, if there is one and it is of
    # the same document.
    back = jnp.arange(t)[:, None] - (width - 1 - jnp.arange(width))[None, :]  # (T, width)
    source = jnp.clip(back, 0)
    taps = jnp.where(((back >= 0) & (seg[source] == seg[:, None]))[..., None], qkv[source], 0.0)
    qkv = jax.nn.silu(jnp.einsum("tkc,kc->tc", taps, p["conv_w"]))
    q, k, v = (x.reshape(t, heads, -1) for x in jnp.split(qkv, [kd, 2 * kd], axis=-1))
    unit = lambda x: x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)
    q, k = unit(q) * kh ** -0.5, unit(k)
    b = (2.0 if hf["linear_allow_neg_eigval"] else 1.0) * jax.nn.sigmoid(db)
    log_a = -jnp.exp(p["A_log"]) * jax.nn.softplus(da + p["dt_bias"])
    o, sq = gated_delta_recurrence(q, k, v, log_a, b, seg, scan_block)
    o = rms_norm(o, p["norm_w"], hf["rms_norm_eps"]) * jax.nn.silu(z.reshape(t, heads, vh))
    return o.reshape(t, vd) @ p["out_proj"], (jnp.exp(log_a), b, sq)


def layer_and_stats(hf, kind, mixer_p, mlp_p, norms, x, seg, scan_block=None, head_block=None):
    """One decoder layer on one sequence: x (T, d) -> ((T, d), the delta rule's
    (a, b, squared state norms) or nothing)."""
    eps = hf["rms_norm_eps"]
    seg = jnp.asarray(seg)
    if kind == LINEAR:
        mixed, stats = gated_delta_net(hf, mixer_p, x, seg, scan_block)
    else:
        mixed, stats = attention(hf, mixer_p, x, seg, head_block), None
    h = x + rms_norm(mixed, norms["mixer"], eps)
    return h + rms_norm(mlp(mlp_p, h), norms["mlp"], eps), stats


def layer(hf, kind, mixer_p, mlp_p, norms, x, seg, **blocks):
    return layer_and_stats(hf, kind, mixer_p, mlp_p, norms, x, seg, **blocks)[0]


def embed(params, tokens):
    return params["embed"]["embedding"][tokens]


def head_loss(hf, params, x, tokens, seg):
    """(sum of next-token cross-entropies over counted positions, logits) from
    the last layer's output of one sequence."""
    logits = rms_norm(x, params["norms"]["final"], hf["rms_norm_eps"]) @ params["head"]["rows"].T
    counted = seg[1:] == seg[:-1]
    logp = jax.nn.log_softmax(logits[:-1], axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(counted, nll, 0.0)), logits


def _layers(hf):
    return list(enumerate(hf["layer_types"][: hf["num_hidden_layers"]]))


def _layer_params(params, i, kind):
    name = f"layer_{i}"
    return params[GROUP[kind]][name], params["mlp"][name], params["norms"][name]


def forward(hf, params, tokens, seg, **blocks):
    """Logits (batch, T, vocabulary) of a batch of packed sequences."""
    def one(tok, sg):
        x = embed(params, tok)
        for i, kind in _layers(hf):
            x = layer(hf, kind, *_layer_params(params, i, kind), x, sg, **blocks)
        return head_loss(hf, params, x, tok, sg)[1]

    with jax.default_matmul_precision(HIGHEST):
        return jnp.stack([one(t, s) for t, s in zip(tokens, seg)])


def _count(seg):
    return jnp.maximum(jnp.sum(seg[:, 1:] == seg[:, :-1]), 1).astype(jnp.float32)


def loss(hf, params, tokens, seg, **blocks):
    """Mean cross-entropy of the next token over the positions of the batch
    whose next token lies in the same document."""
    with jax.default_matmul_precision(HIGHEST):
        total = 0.0
        for tok, sg in zip(tokens, seg):
            x = embed(params, tok)
            for i, kind in _layers(hf):
                x = layer(hf, kind, *_layer_params(params, i, kind), x, sg, **blocks)
            total = total + head_loss(hf, params, x, tok, sg)[0]
        return total / _count(seg)


def loss_and_grads(hf, params, tokens, seg, **blocks):
    return jax.value_and_grad(lambda p: loss(hf, p, tokens, seg, **blocks))(params)


def counters_of(stats: list, every: int) -> dict:
    """The reference's ``gdn/*`` readings from the linear-attention layers'
    ``(a, b, squared state norms)`` of every sequence."""
    a, b, sq = (jnp.stack(x) for x in zip(*stats))
    return {"gdn/alpha_mean": float(jnp.mean(a)), "gdn/beta_mean": float(jnp.mean(b)),
            "gdn/state_norm_max": float(jnp.sqrt(jnp.max(sq[:, every - 1::every])))}


def loss_and_grads_by_layer(hf, params, tokens, seg, every: int = 1, **blocks):
    """``(loss, gradients, counters)``: ``loss_and_grads`` with the chain rule
    written out per layer (one jitted program per kind of layer, run once forward
    with the inputs kept and once as ``jax.vjp`` in reverse) and ``counters_of``
    the forward pass's delta rules, their states read after every ``every``-th
    token."""
    with jax.default_matmul_precision(HIGHEST):
        count = _count(seg)
        kinds = set(hf["layer_types"][: hf["num_hidden_layers"]])
        layer_fn = {kind: jax.jit(lambda mp, lp, np_, x, sg, kind=kind: layer_and_stats(
            hf, kind, mp, lp, np_, x, sg, **blocks)) for kind in kinds}
        layer_bwd = {kind: jax.jit(lambda mp, lp, np_, x, sg, dy, kind=kind: jax.vjp(
            lambda mp, lp, np_, x: layer(hf, kind, mp, lp, np_, x, sg, **blocks), mp, lp, np_, x)[1](dy))
            for kind in kinds}
        head = jax.jit(jax.value_and_grad(
            lambda h, w, x, tok, sg: head_loss(hf, {"head": {"rows": h}, "norms": {"final": w}}, x, tok, sg)[0]
            / count, argnums=(0, 1, 2)))
        embed_bwd = jax.jit(lambda e, tok, dx: jax.vjp(lambda e: e[tok], e)[1](dx)[0])
        add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))

        grads = jax.tree.map(jnp.zeros_like, params)
        total, stats = 0.0, []
        e, h, w = params["embed"]["embedding"], params["head"]["rows"], params["norms"]["final"]
        for tok, sg in zip(tokens, seg):
            xs = [embed(params, tok)]
            for i, kind in _layers(hf):
                y, s = layer_fn[kind](*_layer_params(params, i, kind), xs[-1], sg)
                xs.append(y)
                if s is not None:
                    stats.append(s)
            part, (dh, dw, dx) = head(h, w, xs.pop(), tok, sg)
            total = total + part
            grads["head"]["rows"] = grads["head"]["rows"] + dh
            grads["norms"]["final"] = grads["norms"]["final"] + dw
            for i, kind in reversed(_layers(hf)):
                name = f"layer_{i}"
                dmp, dlp, dnp, dx = layer_bwd[kind](*_layer_params(params, i, kind), xs.pop(), sg, dx)
                grads[GROUP[kind]][name] = add(grads[GROUP[kind]][name], dmp)
                grads["mlp"][name] = add(grads["mlp"][name], dlp)
                grads["norms"][name] = add(grads["norms"][name], dnp)
            grads["embed"]["embedding"] = grads["embed"]["embedding"] + embed_bwd(e, tok, dx)
        return total, grads, (counters_of(stats, every) if stats else {})

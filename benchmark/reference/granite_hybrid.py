"""A plain reference of Granite 4.0-H (Mamba-2 mixers, one grouped-query
attention layer per period, gated MLPs, tied head): forward pass, loss and
gradients in ``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``.

Written from the published ``config.json``'s keys (passed as a plain dict,
``hf``) and the Mamba-2 paper's recurrence (Dao & Gu 2024, arXiv:2405.21060,
eq. 1 with scalar-times-identity ``A``), independently of the program's
``models/granite_hybrid.py`` and ``ops/ssd.py``: it shares their parameter
tree and nothing else.  The state-space recurrence runs TOKEN BY TOKEN in a
``lax.scan`` (no chunks), attention is a dense masked softmax over all
positions, the convolution gathers its four taps, nothing is rounded below
float32 and, by default, nothing is recomputed.

Departures from the description, each only where asked for by an argument:

- ``scan_block``: the token-by-token scan is cut into blocks of that many
  tokens and each block is recomputed in the backward pass
  (``jax.checkpoint``); the same recurrence in the same order, but the
  backward pass keeps one state per block and not one per token (8192 states
  of 64 x 64 x 128 float32 are 17 GB).
- ``head_block``: attention is computed for that many query heads at a time
  (``lax.map``), each block recomputed in the backward pass; the same dense
  masked softmax per head (all heads at once are 8.6 GB of scores at 8192
  tokens).
- ``loss_and_grads_by_layer``: the chain rule written out layer by layer
  (every layer's input kept, each layer's forward repeated inside its own
  ``jax.vjp``), so that one layer's float32 activations are alive at a time.
  ``loss_and_grads`` is ``jax.value_and_grad`` of ``loss``; a test holds the
  two equal.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = "highest"


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def mlp(p, u):
    gate, up = jnp.split(u @ p["gate_up"], 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ p["down"]


def attention(hf, p, u, seg, head_block=None):
    """u: (T, d), seg: (T,).  Dense: every query scores every position, and
    the mask keeps the past of its own document."""
    t, d = u.shape
    heads, kv_heads = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = d // heads
    q = (u @ p["q"]).reshape(t, heads, hd).transpose(1, 0, 2)
    k = (u @ p["k"]).reshape(t, kv_heads, hd).transpose(1, 0, 2)
    v = (u @ p["v"]).reshape(t, kv_heads, hd).transpose(1, 0, 2)
    # query head i reads key/value head i // (heads / kv_heads)
    k, v = (jnp.repeat(a, heads // kv_heads, axis=0) for a in (k, v))
    pos = jnp.arange(t)
    mask = (pos[:, None] >= pos[None, :]) & (seg[:, None] == seg[None, :])

    def some_heads(qkv):
        qh, kh, vh = qkv
        scores = hf["attention_multiplier"] * jnp.einsum("hqd,hsd->hqs", qh, kh)
        return jnp.einsum("hqs,hsd->hqd", jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1), vh)

    if head_block is None:
        out = some_heads((q, k, v))
    else:
        blocks = [a.reshape(heads // head_block, head_block, t, hd) for a in (q, k, v)]
        out = lax.map(jax.checkpoint(some_heads), tuple(blocks)).reshape(heads, t, hd)
    return out.transpose(1, 0, 2).reshape(t, d) @ p["o"]


def mamba(hf, p, u, seg, scan_block=None):
    """u: (T, d), seg: (T,).  The recurrence token by token."""
    t = u.shape[0]
    heads, hd, n, width = hf["mamba_n_heads"], hf["mamba_d_head"], hf["mamba_d_state"], hf["mamba_d_conv"]
    inner = heads * hd
    z, xbc, dt = jnp.split(u @ p["in_proj"], [inner, 2 * inner + 2 * n], axis=-1)
    # Depthwise causal convolution: tap k reads the token (width - 1 - k)
    # back, if there is one and it is of the same document.
    back = jnp.arange(t)[:, None] - (width - 1 - jnp.arange(width))[None, :]  # (T, width)
    source = jnp.clip(back, 0)
    taps = jnp.where(((back >= 0) & (seg[source] == seg[:, None]))[..., None], xbc[source], 0.0)
    xbc = jax.nn.silu(jnp.einsum("tkc,kc->tc", taps, p["conv_w"]) + p["conv_b"])
    x, b, c = jnp.split(xbc, [inner, inner + n], axis=-1)
    x = x.reshape(t, heads, hd)
    dt = jax.nn.softplus(dt + p["dt_bias"])  # (T, heads)
    a = -jnp.exp(p["A_log"])
    first = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])  # a document's first token

    def token(state, inp):
        x_t, b_t, c_t, dt_t, first_t = inp
        state = jnp.where(first_t, 0.0, state)
        state = jnp.exp(dt_t * a)[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, jnp.einsum("hpn,n->hp", state, c_t)

    inputs = (x, b, c, dt, first)
    state0 = jnp.zeros((heads, hd, n), jnp.float32)
    if scan_block is None:
        _, y = lax.scan(token, state0, inputs)
    else:
        if t % scan_block:
            raise ValueError(f"scan_block {scan_block} does not divide {t} tokens")
        blocks = jax.tree.map(lambda v: v.reshape(t // scan_block, scan_block, *v.shape[1:]), inputs)
        _, y = lax.scan(jax.checkpoint(lambda s, blk: lax.scan(token, s, blk)), state0, blocks)
        y = y.reshape(t, heads, hd)
    y = y + p["D"][:, None] * x
    y = rms_norm(y.reshape(t, inner) * jax.nn.silu(z), p["norm_w"], hf["rms_norm_eps"])
    return y @ p["out_proj"]


def layer(hf, kind, mixer_p, mlp_p, norms, x, seg, scan_block=None, head_block=None):
    """One decoder layer on one sequence: x (T, d) -> (T, d)."""
    r, eps = hf["residual_multiplier"], hf["rms_norm_eps"]
    seg = jnp.asarray(seg)
    u = rms_norm(x, norms["mixer"], eps)
    mixed = (mamba(hf, mixer_p, u, seg, scan_block) if kind == "mamba"
             else attention(hf, mixer_p, u, seg, head_block))
    h = x + r * mixed
    return h + r * mlp(mlp_p, rms_norm(h, norms["mlp"], eps))


def embed(hf, params, tokens):
    return hf["embedding_multiplier"] * params["embed"]["embedding"][tokens]


def head_loss(hf, params, x, tokens, seg):
    """(sum of next-token cross-entropies over counted positions, logits)
    from the last layer's output of one sequence."""
    logits = rms_norm(x, params["norms"]["final"], hf["rms_norm_eps"]) @ params["embed"]["embedding"].T
    logits = logits / hf["logits_scaling"]
    counted = seg[1:] == seg[:-1]
    logp = jax.nn.log_softmax(logits[:-1], axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(counted, nll, 0.0)), logits


def _layers(hf):
    return list(enumerate(hf["layer_types"][: hf["num_hidden_layers"]]))


def _layer_params(params, i, kind):
    name = f"layer_{i}"
    return params[kind][name], params["mlp"][name], params["norms"][name]


def forward(hf, params, tokens, seg, **blocks):
    """Logits (batch, T, vocabulary) of a batch of packed sequences."""
    def one(tok, sg):
        x = embed(hf, params, tok)
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
            x = embed(hf, params, tok)
            for i, kind in _layers(hf):
                x = layer(hf, kind, *_layer_params(params, i, kind), x, sg, **blocks)
            total = total + head_loss(hf, params, x, tok, sg)[0]
        return total / _count(seg)


def loss_and_grads(hf, params, tokens, seg, **blocks):
    return jax.value_and_grad(lambda p: loss(hf, p, tokens, seg, **blocks))(params)


def loss_and_grads_by_layer(hf, params, tokens, seg, **blocks):
    """``loss_and_grads`` with the chain rule written out per layer: one
    jitted program per kind of layer, run once forward (inputs kept) and once
    as ``jax.vjp`` in reverse."""
    with jax.default_matmul_precision(HIGHEST):
        count = _count(seg)
        layer_fn = {kind: jax.jit(lambda mp, lp, np_, x, sg, kind=kind: layer(hf, kind, mp, lp, np_, x, sg, **blocks))
                    for kind in set(hf["layer_types"])}
        layer_bwd = {kind: jax.jit(lambda mp, lp, np_, x, sg, dy, fn=fn: jax.vjp(
            lambda mp, lp, np_, x: fn(mp, lp, np_, x, sg), mp, lp, np_, x)[1](dy))
            for kind, fn in layer_fn.items()}
        head = jax.jit(jax.value_and_grad(
            lambda e, w, x, tok, sg: head_loss(hf, {"embed": {"embedding": e}, "norms": {"final": w}}, x, tok, sg)[0]
            / count, argnums=(0, 1, 2)))
        embed_bwd = jax.jit(lambda e, tok, dx: jax.vjp(
            lambda e: hf["embedding_multiplier"] * e[tok], e)[1](dx)[0])
        add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))

        grads = jax.tree.map(jnp.zeros_like, params)
        total = 0.0
        e, w = params["embed"]["embedding"], params["norms"]["final"]
        for tok, sg in zip(tokens, seg):
            xs = [embed(hf, params, tok)]
            for i, kind in _layers(hf):
                xs.append(layer_fn[kind](*_layer_params(params, i, kind), xs[-1], sg))
            part, (de, dw, dx) = head(e, w, xs.pop(), tok, sg)
            total = total + part
            grads["embed"]["embedding"] = grads["embed"]["embedding"] + de
            grads["norms"]["final"] = grads["norms"]["final"] + dw
            for i, kind in reversed(_layers(hf)):
                name = f"layer_{i}"
                dmp, dlp, dnp, dx = layer_bwd[kind](*_layer_params(params, i, kind), xs.pop(), sg, dx)
                grads[kind][name] = add(grads[kind][name], dmp)
                grads["mlp"][name] = add(grads["mlp"][name], dlp)
                grads["norms"][name] = add(grads["norms"][name], dnp)
            grads["embed"]["embedding"] = grads["embed"]["embedding"] + embed_bwd(e, tok, dx)
        return total, grads

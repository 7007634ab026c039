"""A plain reference of Nemotron-H (every layer ONE mixer: a Mamba-2 scan
with grouped B and C, squared-ReLU routed and shared experts behind a sigmoid
router, or grouped-query attention; untied head) for a chip that holds a share
of the routed experts: forward pass, loss and gradients in ``jax.numpy``,
float32, ``jax.default_matmul_precision("highest")``.

Written from the published ``config.json``'s keys (a plain dict, ``hf``), the
Mamba-2 paper's recurrence (Dao & Gu 2024, arXiv:2405.21060, eq. 1 with
scalar-times-identity ``A``) and the family's published modelling code as the
issue that asked for this model sets its equations out
(``NemotronHMamba2Mixer``, ``MambaRMSNormGated`` with ``group_size = inner /
n_groups``, ``NemotronHTopkRouter``, ``NemotronHMOE``, ``NemotronHAttention``),
independently of the program's ``models/nemotron_h.py`` and ``ops/``: it
imports nothing of the package and shares its parameter tree and nothing
else.  The recurrence runs TOKEN BY TOKEN in a ``lax.scan`` (no chunks), every
head reading the B and C of its group; attention is a dense masked softmax
over all positions; an expert is applied to EVERY token and multiplied by the
token's weight for it where the token picked it, else by zero; the head is
whole (16 384 x 8192 float32 logits are 0.5 GB a sequence).

``held`` is the list of the expert ids whose weights ``params["experts"]``
holds, in that order.  What the other experts would add is left out, here as
in the program; ``held`` = all of them is the uncut layer.

Departures from the published code, each at its line below:

- [packed] one sequence holds several documents: attention is masked to the
  query's own document, the scan's state is zero at a document's first token
  and the convolution does not reach into the previous document.
- [share] only the experts in ``held`` are computed; the weights are
  normalised over ALL the picks, held or not.
- [rotary] the attention layer applies NO positional encoding; ``ROTARY_KEY``
  true in ``hf`` applies plain rotary by the position inside the document
  (the other reading of the row, unchecked against the hub).
- [bias] ``e_score_correction_bias`` is ``hf["router_bias"]`` (a row an expert
  layer), zeros where absent; it moves the picks and never the weights.
- [top-k] the k largest by a descending sort.
- [float32] the published code computes in bfloat16 with float32 router
  scores; here everything is float32.
- ``scan_block``, ``head_block``, ``loss_and_grads_by_layer``: as the granite
  reference's, only where asked: the scan cut into blocks that are recomputed
  in the backward pass, attention for that many heads at a time, the chain
  rule written out per layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = "highest"
ROTARY_KEY = "attention_rotary"
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def mlp(p, u):
    """``W_down relu(W_up u)^2``: no gate, no bias."""
    return jnp.square(jnp.maximum(u @ p["up"], 0.0)) @ p["down"]


def document_positions(seg):
    t = seg.shape[0]
    earlier = jnp.arange(t)[None, :] < jnp.arange(t)[:, None]
    return jnp.sum((seg[:, None] == seg[None, :]) & earlier, axis=1)


def rotate(x, positions, theta):
    """Plain rotary: pair (2i, 2i + 1) of the last axis turned by ``position x
    theta^(-2i/dim)``.  x: (T, heads, dim)."""
    dim = x.shape[-1]
    angle = positions.astype(jnp.float32)[:, None, None] / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    z = lax.complex(x[..., 0::2], x[..., 1::2]) * lax.complex(jnp.cos(angle), jnp.sin(angle))
    return jnp.concatenate([jnp.real(z), jnp.imag(z)], axis=-1)


def attention(hf, p, u, seg, head_block=None):
    """u: (T, d), seg: (T,).  Dense: every query scores every position, and
    the mask keeps the past of its own document."""
    t = u.shape[0]
    heads, kv_heads, hd = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    q = (u @ p["q"]).reshape(t, heads, hd)
    k = (u @ p["k"]).reshape(t, kv_heads, hd)
    v = (u @ p["v"]).reshape(t, kv_heads, hd)
    if hf.get(ROTARY_KEY, False):  # [rotary]
        positions = document_positions(seg)
        q, k = rotate(q, positions, float(hf["rope_theta"])), rotate(k, positions, float(hf["rope_theta"]))
    # query head i reads key/value head i // (heads / kv_heads)
    q, k, v = q.transpose(1, 0, 2), *(jnp.repeat(a.transpose(1, 0, 2), heads // kv_heads, axis=0) for a in (k, v))
    pos = jnp.arange(t)
    mask = (pos[:, None] >= pos[None, :]) & (seg[:, None] == seg[None, :])  # [packed]

    def some_heads(qkv):
        qh, kh, vh = qkv
        scores = hd ** -0.5 * jnp.einsum("hqd,hsd->hqs", qh, kh)
        return jnp.einsum("hqs,hsd->hqd", jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1), vh)

    if head_block is None:
        out = some_heads((q, k, v))
    else:
        blocks = [a.reshape(heads // head_block, head_block, t, hd) for a in (q, k, v)]
        out = lax.map(jax.checkpoint(some_heads), tuple(blocks)).reshape(heads, t, hd)
    return out.transpose(1, 0, 2).reshape(t, heads * hd) @ p["o"]


def mamba(hf, p, u, seg, scan_block=None):
    """u: (T, d), seg: (T,).  The recurrence token by token; head ``h`` reads
    the B and C of group ``h // (heads / n_groups)``."""
    t = u.shape[0]
    heads, hd, n, groups = hf["mamba_num_heads"], hf["mamba_head_dim"], hf["ssm_state_size"], hf["n_groups"]
    width, inner = hf["conv_kernel"], heads * hd  # NOT expand x hidden_size
    z, xbc, dt = jnp.split(u @ p["in_proj"], [inner, 2 * inner + 2 * groups * n], axis=-1)
    # Depthwise causal convolution: tap k reads the token (width - 1 - k)
    # back, if there is one and it is of the same document [packed].
    back = jnp.arange(t)[:, None] - (width - 1 - jnp.arange(width))[None, :]  # (T, width)
    source = jnp.clip(back, 0)
    taps = jnp.where(((back >= 0) & (seg[source] == seg[:, None]))[..., None], xbc[source], 0.0)
    xbc = jax.nn.silu(jnp.einsum("tkc,kc->tc", taps, p["conv_w"]) + p["conv_b"])
    x, b, c = jnp.split(xbc, [inner, inner + groups * n], axis=-1)
    x = x.reshape(t, heads, hd)
    b, c = (jnp.repeat(a.reshape(t, groups, n), heads // groups, axis=1) for a in (b, c))  # (T, heads, n)
    dt = jax.nn.softplus(dt + p["dt_bias"])  # (T, heads); no clamp
    a = -jnp.exp(p["A_log"])
    first = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])  # a document's first token [packed]

    def token(state, inp):
        x_t, b_t, c_t, dt_t, first_t = inp
        state = jnp.where(first_t, 0.0, state)
        state = jnp.exp(dt_t * a)[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

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
    y = (y + p["D"][:, None] * x).reshape(t, inner) * jax.nn.silu(z)
    # MambaRMSNormGated, group_size = inner / n_groups: each group's channels normed on their own
    y = rms_norm(y.reshape(t, groups, inner // groups), p["norm_w"].reshape(groups, -1), hf["layer_norm_epsilon"])
    return y.reshape(t, inner) @ p["out_proj"]


def gate(hf, router, u, bias):
    """``NemotronHTopkRouter``: ``(weights (T, experts), picks (T, k))``: the
    picked sigmoids over their sum times the scaling factor where the token
    picked the expert, zero elsewhere; the picks by score + bias."""
    scores = jax.nn.sigmoid(u @ router["gate"])
    picks = jnp.argsort(-(scores + bias), axis=-1)[:, : hf["num_experts_per_tok"]]  # [top-k] [bias]
    picked = jnp.sum(jax.nn.one_hot(picks, scores.shape[-1], dtype=scores.dtype), axis=1)
    weights = scores * picked / jnp.sum(scores * picked, axis=-1, keepdims=True)  # norm_topk_prob, over all picks
    return hf["routed_scaling_factor"] * weights, picks


def moe(hf, router, experts, shared, u, held, bias):
    """``NemotronHMOE``: -> (F(u), the picks)."""
    weights, picks = gate(hf, router, u, bias)
    y = mlp(shared, u)
    for j, e in enumerate(held):  # [share]
        y = y + weights[:, e, None] * mlp(jax.tree.map(lambda w: w[j], experts), u)
    return y, picks


def router_bias(hf, index: int):
    rows = hf.get("router_bias")
    total = hf.get("n_routed_experts_total", hf["n_routed_experts"])
    return jnp.zeros((total,), jnp.float32) if not rows else jnp.asarray(rows[index], jnp.float32)


def layer(hf, kind, index, p, norm, x, seg, held, scan_block=None, head_block=None):
    """One layer on one sequence: x (T, d) -> (x + Mixer(RMSNorm(x)), picks);
    ``index`` says which bias an expert layer's router reads; no picks but
    an expert layer's."""
    seg = jnp.asarray(seg)
    u = rms_norm(x, norm, hf["layer_norm_epsilon"])
    if kind == MAMBA:
        return x + mamba(hf, p, u, seg, scan_block), jnp.zeros((0,), jnp.int32)
    if kind == ATTENTION:
        return x + attention(hf, p, u, seg, head_block), jnp.zeros((0,), jnp.int32)
    f, picks = moe(hf, *p, u, held, router_bias(hf, index))
    return x + f, picks


def _layers(hf):
    """(layer, kind, which of the router's biases it reads: how many expert
    layers lie before it; 0 where no layer reads one)."""
    pattern = hf["hybrid_override_pattern"][: hf["num_hidden_layers"]]
    biased = bool(hf.get("router_bias"))
    return [(i, kind, pattern[:i].count(EXPERTS) if biased and kind == EXPERTS else 0) for i, kind in enumerate(pattern)]


def _layer_params(params, i, kind):
    name = f"layer_{i}"
    if kind == EXPERTS:
        return (params["router"][name], params["experts"][name], params["shared"][name]), params["norms"][name]
    return params["mamba" if kind == MAMBA else "attention"][name], params["norms"][name]


def head_loss(hf, params, x, tokens, seg):
    """(sum of next-token cross-entropies over counted positions, logits)."""
    logits = rms_norm(x, params["norms"]["final"], hf["layer_norm_epsilon"]) @ params["head"]["rows"].T
    counted = seg[1:] == seg[:-1]
    logp = jax.nn.log_softmax(logits[:-1], axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(counted, nll, 0.0)), logits


def _sequence(hf, params, tok, sg, held, blocks):
    x = params["embed"]["embedding"][tok]
    for i, kind, index in _layers(hf):
        x, _ = layer(hf, kind, index, *_layer_params(params, i, kind), x, sg, held, **blocks)
    return x


def forward(hf, params, tokens, seg, held, **blocks):
    """Logits (batch, T, vocabulary held) of a batch of packed sequences."""
    with jax.default_matmul_precision(HIGHEST):
        return jnp.stack([head_loss(hf, params, _sequence(hf, params, t, s, held, blocks), t, s)[1]
                          for t, s in zip(tokens, seg)])


def _count(seg):
    return jnp.maximum(jnp.sum(seg[:, 1:] == seg[:, :-1]), 1).astype(jnp.float32)


def loss(hf, params, tokens, seg, held, **blocks):
    """Mean cross-entropy of the next token over the positions of the batch
    whose next token lies in the same document.  No balance loss."""
    with jax.default_matmul_precision(HIGHEST):
        total = 0.0
        for tok, sg in zip(tokens, seg):
            total = total + head_loss(hf, params, _sequence(hf, params, tok, sg, held, blocks), tok, sg)[0]
        return total / _count(seg)


def loss_and_grads(hf, params, tokens, seg, held, **blocks):
    return jax.value_and_grad(lambda p: loss(hf, p, tokens, seg, held, **blocks))(params)


def loss_and_grads_by_layer(hf, params, tokens, seg, held, **blocks):
    """``loss_and_grads`` with the chain rule written out per layer (one
    layer's float32 activations alive at a time), and each expert layer's
    picks: -> (loss, gradients, picks (batch, expert layers, T, k))."""
    held = tuple(held)
    with jax.default_matmul_precision(HIGHEST):
        count = _count(seg)
        layer_fn = {(kind, index): jax.jit(lambda p, w, x, sg, kind=kind, index=index: layer(
            hf, kind, index, p, w, x, sg, held, **blocks)) for _, kind, index in _layers(hf)}
        layer_bwd = {key: jax.jit(lambda p, w, x, sg, dy, fn=fn: jax.vjp(
            lambda p, w, x: fn(p, w, x, sg)[0], p, w, x)[1](dy)) for key, fn in layer_fn.items()}
        head = jax.jit(jax.value_and_grad(
            lambda h, w, x, tok, sg: head_loss(hf, {"head": {"rows": h}, "norms": {"final": w}}, x, tok, sg)[0]
            / count, argnums=(0, 1, 2)))
        embed_bwd = jax.jit(lambda e, tok, dx: jax.vjp(lambda e: e[tok], e)[1](dx)[0])
        add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))

        grads = jax.tree.map(jnp.zeros_like, params)
        total, picks = 0.0, []
        e, h, w = params["embed"]["embedding"], params["head"]["rows"], params["norms"]["final"]
        for tok, sg in zip(tokens, seg):
            xs, picks_here = [e[tok]], []
            for i, kind, index in _layers(hf):
                y, pk = layer_fn[kind, index](*_layer_params(params, i, kind), xs[-1], sg)
                xs.append(y)
                if kind == EXPERTS:
                    picks_here.append(pk)
            picks.append(jnp.stack(picks_here))
            part, (dh, dw, dx) = head(h, w, xs.pop(), tok, sg)
            total = total + part
            grads["head"]["rows"] = grads["head"]["rows"] + dh
            grads["norms"]["final"] = grads["norms"]["final"] + dw
            for i, kind, index in reversed(_layers(hf)):
                name = f"layer_{i}"
                dp, dnorm, dx = layer_bwd[kind, index](*_layer_params(params, i, kind), xs.pop(), sg, dx)
                grads["norms"][name] = grads["norms"][name] + dnorm
                if kind == EXPERTS:
                    for group, d in zip(("router", "experts", "shared"), dp):
                        grads[group][name] = add(grads[group][name], d)
                else:
                    group = "mamba" if kind == MAMBA else "attention"
                    grads[group][name] = add(grads[group][name], dp)
            grads["embed"]["embedding"] = grads["embed"]["embedding"] + embed_bwd(e, tok, dx)
        return total, grads, jnp.stack(picks)

"""A plain reference of DeepSeek-V2 (latent attention, a leading dense layer,
routed and shared experts, untied head) for a chip that holds a share of the
routed experts: forward pass, loss with the balance term, gradients, in
``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``.

Written from the published ``config.json``'s keys (a plain dict, ``hf``) and
the modelling code published beside it (``modeling_deepseek.py``:
``DeepseekV2Attention``, ``MoEGate``, ``DeepseekV2MoE``,
``DeepseekV2YarnRotaryEmbedding``), independently of the program's
``models/deepseek_v2.py``, ``ops/moe.py``, ``ops/rope.py`` and
``ops/attention.py``: it shares their parameter tree and nothing else.  No
kernels, no sorting, no buffers: attention is a dense masked softmax over all
positions; an expert is applied to EVERY token and multiplied by the token's
score for it where the token picked it, else by zero; positions are counted,
not scanned; the rotation is a complex product.

``held`` is the list of the expert ids whose weights ``params["experts"]``
holds, in that order.  What the other experts would add is left out, here as
in the program (the chip's share of an expert-parallel layer); ``held`` =
all of them is the uncut layer.

Departures from the published code, each at its line below:

- [packed] one sequence holds several documents: attention is masked to the
  query's own document and a token's rotary position is its index inside its
  document (the published code takes ``position_ids`` and an attention mask
  from the caller; these are the ones a packed batch needs).
- [share] only the experts in ``held`` are computed.
- [top-k] the k largest by a descending sort (``torch.topk(sorted=False)``
  returns the same set; the order within it multiplies nothing).
- [float32] the published code computes in the checkpoint's bfloat16 with
  the router's scores and the softmax in float32; here everything is float32.
- ``head_block``, ``loss_and_grads_by_layer``: as the granite reference's,
  only where asked: attention for that many heads at a time, each block
  recomputed in the backward pass; the chain rule written out per layer.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = "highest"
AUX_LOSS_ALPHA = 0.001  # where ``hf`` has no ``aux_loss_alpha`` (the catalog's copy dropped it)


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def mlp(p, u):
    gate, up = jnp.split(u @ p["gate_up"], 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ p["down"]


# ---- rotary positions -------------------------------------------------------


def yarn_frequencies(hf) -> np.ndarray:
    """``DeepseekV2YarnRotaryEmbedding._set_cos_sin_cache``'s ``inv_freq``, one
    pair at a time in float64."""
    s, dim, base = hf["rope_scaling"], hf["qk_rope_head_dim"], float(hf["rope_theta"])
    original = s["original_max_position_embeddings"]

    def correction_dim(rotations):  # yarn_find_correction_dim
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(s["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(s["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i in range(dim // 2):
        extrapolated = 1.0 / base ** (2 * i / dim)
        interpolated = extrapolated / s["factor"]
        keep = 1.0 - min(max((i - low) / (high - low), 0.0), 1.0)  # 1 - yarn_linear_ramp_mask
        out.append(interpolated * (1.0 - keep) + extrapolated * keep)
    return np.asarray(out)


def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def document_positions(seg):
    """[packed] a token's position inside its document: the earlier tokens
    that bear its id."""
    t = seg.shape[0]
    earlier = jnp.arange(t)[None, :] < jnp.arange(t)[:, None]
    return jnp.sum((seg[:, None] == seg[None, :]) & earlier, axis=1)


def rotate(x, positions, hf):
    """``apply_rotary_pos_emb``: elements (2i, 2i + 1) of the last axis are one
    complex number, turned by ``position x frequency_i`` (and scaled by
    ``mscale / mscale_all_dim``'s ratio, 1 as published).  x: (T, ..., dim);
    the result holds the real parts then the imaginary parts, which is the
    published layout; only products of two such vectors are used."""
    s = hf["rope_scaling"]
    ratio = yarn_get_mscale(s["factor"], s["mscale"]) / yarn_get_mscale(s["factor"], s["mscale_all_dim"])
    angle = positions.astype(jnp.float32)[:, None] * jnp.asarray(yarn_frequencies(hf), jnp.float32)
    angle = angle.reshape(angle.shape[0], *([1] * (x.ndim - 2)), angle.shape[-1])
    z = lax.complex(x[..., 0::2], x[..., 1::2]) * lax.complex(ratio * jnp.cos(angle), ratio * jnp.sin(angle))
    return jnp.concatenate([jnp.real(z), jnp.imag(z)], axis=-1)


# ---- layers -----------------------------------------------------------------


def attention(hf, p, u, seg, head_block=None):
    """``DeepseekV2Attention`` with ``q_lora_rank`` null.  u: (T, d), seg: (T,)."""
    t = u.shape[0]
    heads, nope, pe, vd = hf["num_attention_heads"], hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"]
    rank = hf["kv_lora_rank"]
    q = (u @ p["q"]).reshape(t, heads, nope + pe)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    compressed = u @ p["kv_a"]  # kv_a_proj_with_mqa
    c, k_pe = compressed[:, :rank], compressed[:, rank:]
    kv = (rms_norm(c, p["kv_a_norm"], hf["rms_norm_eps"]) @ p["kv_b"]).reshape(t, heads, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    positions = document_positions(seg)  # [packed]
    q_pe = rotate(q_pe, positions, hf)
    k_pe = rotate(k_pe, positions, hf)  # one rotary key, shared by every head
    q = jnp.concatenate([q_nope, q_pe], axis=-1).transpose(1, 0, 2)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[:, None, :], (t, heads, pe))], axis=-1).transpose(1, 0, 2)
    v = v.transpose(1, 0, 2)
    s = hf["rope_scaling"]
    m = yarn_get_mscale(s["factor"], s["mscale_all_dim"])
    scale = (nope + pe) ** -0.5 * m * m
    pos = jnp.arange(t)
    mask = (pos[:, None] >= pos[None, :]) & (seg[:, None] == seg[None, :])  # [packed]

    def some_heads(qkv):
        qh, kh, vh = qkv
        scores = scale * jnp.einsum("hqd,hsd->hqs", qh, kh)
        return jnp.einsum("hqs,hsd->hqd", jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1), vh)

    if head_block is None:
        out = some_heads((q, k, v))
    else:
        blocks = [a.reshape(heads // head_block, head_block, t, a.shape[-1]) for a in (q, k, v)]
        out = lax.map(jax.checkpoint(some_heads), tuple(blocks)).reshape(heads, t, vd)
    return out.transpose(1, 0, 2).reshape(t, heads * vd) @ p["o"]


def gate(hf, router, u):
    """``MoEGate``: ``(scores, picked, picks)`` - the softmax over all experts
    (T, experts), 1.0 where the token picked the expert, the k ids."""
    scores = jax.nn.softmax(u @ router["gate"], axis=-1)
    picks = jnp.argsort(-scores, axis=-1)[:, : hf["num_experts_per_tok"]]  # [top-k]
    picked = jnp.sum(jax.nn.one_hot(picks, scores.shape[-1], dtype=scores.dtype), axis=1)
    return scores, picked, picks


def balance(hf, scores, picked):
    """``MoEGate``'s ``seq_aux`` branch for one sequence, without alpha:
    ``sum_i ce_i mean_t(scores_i)``, ``ce_i`` = picks of i / (T k / experts).
    The counts carry no gradient (a ``scatter_add_`` of ones)."""
    t, experts = scores.shape
    ce = lax.stop_gradient(jnp.sum(picked, axis=0)) / (t * hf["num_experts_per_tok"] / experts)
    return jnp.sum(ce * jnp.mean(scores, axis=0))


def moe(hf, router, experts, shared, u, held):
    """``DeepseekV2MoE``: -> (F(u), this sequence's balance term, the picks)."""
    scores, picked, picks = gate(hf, router, u)
    weights = hf["routed_scaling_factor"] * scores * picked  # norm_topk_prob false: the scores as they are
    y = mlp(shared, u)  # shared_experts: one MLP of n_shared_experts x the routed width
    for j, e in enumerate(held):  # [share]
        y = y + weights[:, e, None] * mlp(jax.tree.map(lambda w: w[j], experts), u)
    return y, balance(hf, scores, picked), picks


def is_dense(hf, i: int) -> bool:
    return i < hf["first_k_dense_replace"]


def layer(hf, dense, attn_p, mlp_p, norms, x, seg, held, head_block=None):
    """One decoder layer on one sequence: x (T, d) -> (y, balance, picks);
    the last two 0 and nothing for a dense layer."""
    eps = hf["rms_norm_eps"]
    seg = jnp.asarray(seg)
    h = x + attention(hf, attn_p, rms_norm(x, norms["attention"], eps), seg, head_block)
    u = rms_norm(h, norms["mlp"], eps)
    if dense:
        return h + mlp(mlp_p, u), jnp.zeros((), jnp.float32), jnp.zeros((0,), jnp.int32)
    f, bal, picks = moe(hf, *mlp_p, u, held)
    return h + f, bal, picks


def _layer_params(params, hf, i):
    name = f"layer_{i}"
    mlp_p = params["dense_mlp"][name] if is_dense(hf, i) else (
        params["router"][name], params["experts"][name], params["shared"][name])
    return params["attention"][name], mlp_p, params["norms"][name]


def head_loss(hf, params, x, tokens, seg):
    """(sum of next-token cross-entropies over counted positions, logits)."""
    logits = rms_norm(x, params["norms"]["final"], hf["rms_norm_eps"]) @ params["head"]["rows"].T
    counted = seg[1:] == seg[:-1]
    logp = jax.nn.log_softmax(logits[:-1], axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(counted, nll, 0.0)), logits


def _sequence(hf, params, tok, sg, held, head_block):
    """-> (last hidden state, the layers' balance terms summed)."""
    x, bal = params["embed"]["embedding"][tok], 0.0
    for i in range(hf["num_hidden_layers"]):
        x, b, _ = layer(hf, is_dense(hf, i), *_layer_params(params, hf, i), x, sg, held, head_block)
        bal = bal + b
    return x, bal


def forward(hf, params, tokens, seg, held, head_block=None):
    """Logits (batch, T, vocabulary held) of a batch of packed sequences."""
    with jax.default_matmul_precision(HIGHEST):
        return jnp.stack([head_loss(hf, params, _sequence(hf, params, t, s, held, head_block)[0], t, s)[1]
                          for t, s in zip(tokens, seg)])


def _count(seg):
    return jnp.maximum(jnp.sum(seg[:, 1:] == seg[:, :-1]), 1).astype(jnp.float32)


def loss(hf, params, tokens, seg, held, head_block=None):
    """``(loss, auxiliary)``: the mean cross-entropy of the next token over
    the batch's positions whose next token lies in the same document, plus
    ``auxiliary`` = alpha x the mean over sequences of the layers' balance
    terms."""
    alpha = hf.get("aux_loss_alpha", AUX_LOSS_ALPHA)
    with jax.default_matmul_precision(HIGHEST):
        total, bal = 0.0, 0.0
        for tok, sg in zip(tokens, seg):
            x, b = _sequence(hf, params, tok, sg, held, head_block)
            total, bal = total + head_loss(hf, params, x, tok, sg)[0], bal + b
        aux = alpha * bal / len(tokens)
        return total / _count(seg) + aux, aux


def loss_and_grads(hf, params, tokens, seg, held, head_block=None):
    """-> ((loss, auxiliary), gradients of the loss)."""
    return jax.value_and_grad(lambda p: loss(hf, p, tokens, seg, held, head_block), has_aux=True)(params)


def loss_and_grads_by_layer(hf, params, tokens, seg, held, head_block=None):
    """``loss_and_grads`` with the chain rule written out per layer (one
    layer's float32 activations alive at a time), and each expert layer's
    picks: -> ((loss, auxiliary), gradients, picks (batch, expert layers, T, k))."""
    alpha = hf.get("aux_loss_alpha", AUX_LOSS_ALPHA)
    held = tuple(held)
    with jax.default_matmul_precision(HIGHEST):
        count, batch = _count(seg), len(tokens)
        layer_fn = {dense: jax.jit(lambda ap, mp, np_, x, sg, dense=dense: layer(
            hf, dense, ap, mp, np_, x, sg, held, head_block)) for dense in (True, False)}
        # the layer's two differentiable results: its output and its balance term
        layer_bwd = {dense: jax.jit(lambda ap, mp, np_, x, sg, dy, dbal, fn=fn: jax.vjp(
            lambda ap, mp, np_, x: fn(ap, mp, np_, x, sg)[:2], ap, mp, np_, x)[1]((dy, dbal)))
            for dense, fn in layer_fn.items()}
        head = jax.jit(jax.value_and_grad(
            lambda h, w, x, tok, sg: head_loss(hf, {"head": {"rows": h}, "norms": {"final": w}}, x, tok, sg)[0]
            / count, argnums=(0, 1, 2)))
        embed_bwd = jax.jit(lambda e, tok, dx: jax.vjp(lambda e: e[tok], e)[1](dx)[0])
        add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))

        grads = jax.tree.map(jnp.zeros_like, params)
        total, bal, picks = 0.0, 0.0, []
        e, h, w = params["embed"]["embedding"], params["head"]["rows"], params["norms"]["final"]
        for tok, sg in zip(tokens, seg):
            xs, picks_here = [e[tok]], []
            for i in range(hf["num_hidden_layers"]):
                y, b, pk = layer_fn[is_dense(hf, i)](*_layer_params(params, hf, i), xs[-1], sg)
                xs.append(y)
                bal = bal + b
                if not is_dense(hf, i):
                    picks_here.append(pk)
            picks.append(jnp.stack(picks_here))
            part, (dh, dw, dx) = head(h, w, xs.pop(), tok, sg)
            total = total + part
            grads["head"]["rows"] = grads["head"]["rows"] + dh
            grads["norms"]["final"] = grads["norms"]["final"] + dw
            for i in reversed(range(hf["num_hidden_layers"])):
                name, dense = f"layer_{i}", is_dense(hf, i)
                dap, dmp, dnp, dx = layer_bwd[dense](*_layer_params(params, hf, i), xs.pop(), sg, dx,
                                                     jnp.asarray(alpha / batch, jnp.float32))
                grads["attention"][name] = add(grads["attention"][name], dap)
                grads["norms"][name] = add(grads["norms"][name], dnp)
                if dense:
                    grads["dense_mlp"][name] = add(grads["dense_mlp"][name], dmp)
                else:
                    for group, d in zip(("router", "experts", "shared"), dmp):
                        grads[group][name] = add(grads[group][name], d)
            grads["embed"]["embedding"] = grads["embed"]["embedding"] + embed_bwd(e, tok, dx)
        aux = alpha * bal / batch
        return (total + aux, aux), grads, jnp.stack(picks)

"""A plain reference of Keye-VL-2.0's language model (grouped-query attention
over the keys a learned indexer selects, routed experts without a shared one,
untied head) for a chip that holds a share of the routed experts: forward pass,
loss with the balance term and the indexer's loss, gradients, in ``jax.numpy``,
float32, ``jax.default_matmul_precision("highest")``.

Written from the published ``config.json``'s keys (a plain dict, ``hf``) and
ISSUE 38's equations (the published modelling code is not in the catalog: the
body is Qwen3-MoE's - per-head q/k RMSNorm, softmax router with renormalised
top-k weights, ``load_balancing_loss_func`` - the positions Qwen2-VL's rotary
embedding in sections, the indexer DeepSeek-V3.2-Exp's), independently of the
program's ``models/keye_vl2.py``, ``ops/sparse_attention.py``, ``ops/moe.py`` and
``ops/rope.py``: it shares their parameter tree and nothing else.  No kernels,
no bisection, no buffers: the selection is ``lax.top_k`` of the masked scores;
attention is a dense softmax over all positions under the selection's mask; an
expert is applied to EVERY token and multiplied by the token's weight for it
where the token picked it, else by zero; positions are counted, not scanned; the
rotation is a complex product.

``held`` is the list of the expert ids whose weights ``params["experts"]``
holds, in that order.  What the other experts would add is left out, here as in
the program (the chip's share of an expert-parallel layer); ``held`` = all of
them is the uncut layer.

``selection``, where given, is a list (one entry a layer) of (batch, T, T) bool:
the keys every query attends to, INSTEAD of the reference's own choice.  Top-k
is discontinuous, so a program that scores in bfloat16 picks other keys than
this float32 reference wherever two scores lie closer than its rounding; the
benchmark's check hands the program's selection in so that the outputs, the
losses and the gradients are compared on the same keys, and reads from
``selection_report`` how far the program's choice is from the reference's own.

Departures from the published description, each at its line below:

- [packed] one sequence holds several documents: attention and the selection
  stay inside the query's own document, and a text token's three rotary ids are
  its index inside its document.
- [share] only the experts in ``held`` are computed.
- [top-k] the router's k largest by a descending sort (the same set as
  ``torch.topk``); the indexer's by ``lax.top_k``, whose ties go to the lower
  position.
- [zero] ``-0.0`` (a negative weight times a ReLU's zero) and ``0.0`` are one
  score: a sort that tells them apart would break a tie by the sign of a zero.
- [float32] everything is float32.
- ``head_block``, ``q_block``, ``score_block``, ``loss_and_grads_by_layer``: as the
  DeepSeek-V2 reference's, only where asked: attention for that many heads and
  queries at a time, the index scores for that many queries at a time, each
  block recomputed in the backward pass; the chain rule written out per layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = "highest"
ROUTER_AUX_LOSS_COEF = 0.001  # where ``hf`` has no ``router_aux_loss_coef`` (the catalog's copy has none)
INDEXER_LOSS_COEF = 1.0  # where ``hf`` has no ``indexer_loss_coef``


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def layer_norm(x, scale, bias, eps):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred * lax.rsqrt(jnp.mean(centred * centred, axis=-1, keepdims=True) + eps) * scale + bias


def mlp(p, u):
    gate, up = jnp.split(u @ p["gate_up"], 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ p["down"]


# ---- rotary positions -------------------------------------------------------


def document_positions(seg):
    """[packed] a token's position inside its document: the earlier tokens
    that bear its id."""
    t = seg.shape[0]
    earlier = jnp.arange(t)[None, :] < jnp.arange(t)[:, None]
    return jnp.sum((seg[:, None] == seg[None, :]) & earlier, axis=1)


def rotate(x, ids, theta: float, sections):
    """The rotary embedding in sections: ``x`` (T, ..., dim), ``ids`` (3, T) the
    temporal, height and width id.  Pair ``i`` is elements ``(i, i + dim / 2)``,
    one complex number turned by ``id x theta^(-2i / dim)``, the id that of the
    pair's section: the first ``sections[0]`` pairs the temporal, the next the
    height, the last the width."""
    half = x.shape[-1] // 2
    assert sum(sections) == half
    which = np.repeat(np.arange(len(sections)), sections)  # pair -> its section's id
    frequency = jnp.asarray(1.0 / float(theta) ** (np.arange(half) / half), jnp.float32)
    angle = ids.astype(jnp.float32)[which].T * frequency  # (T, pairs)
    angle = angle.reshape(angle.shape[0], *([1] * (x.ndim - 2)), half)
    z = lax.complex(x[..., :half], x[..., half:]) * lax.complex(jnp.cos(angle), jnp.sin(angle))
    return jnp.concatenate([jnp.real(z), jnp.imag(z)], axis=-1)


# ---- the indexer and its selection --------------------------------------------


def index_scores(hf, p, h, ids, score_block=None, with_bound=False):
    """``I`` (T, T) of every pair, from ``h`` (T, d) (which carries no gradient
    where the caller says so); ``with_bound`` also what no score's magnitude can
    pass, as its two factors: ``scale x sum_j |w[t, j]| |qI[t, j]|`` (T,) and
    ``|kI[s]|`` (T,) - the size of the products a score is summed from, which is
    what a rounding of their operands is relative to."""
    sa = hf["sa_config"]
    heads, size, t = sa["indexer_num_heads"], sa["indexer_head_dim"], h.shape[0]
    sections = [s * size // hf["head_dim"] for s in hf["rope_scaling"]["mrope_section"]]
    q = rotate((h @ p["q"]).reshape(t, heads, size), ids, hf["rope_theta"], sections)
    k = rotate(layer_norm(h @ p["k"], p["k_norm_scale"], p["k_norm_bias"], hf["rms_norm_eps"]), ids,
               hf["rope_theta"], sections)
    w = h @ p["w"]

    def rows(args):
        q_blk, w_blk = args
        return size ** -0.5 * heads ** -0.5 * jnp.einsum("qh,qhs->qs", w_blk, jax.nn.relu(
            jnp.einsum("qhd,sd->qhs", q_blk, k)))

    if score_block is None:
        scores = rows((q, w))
    else:
        n = t // score_block
        scores = lax.map(jax.checkpoint(rows), (q.reshape(n, score_block, heads, size),
                                                w.reshape(n, score_block, heads))).reshape(t, t)
    if not with_bound:
        return scores
    by_query = size ** -0.5 * heads ** -0.5 * jnp.sum(jnp.abs(w) * jnp.linalg.norm(q, axis=-1), axis=-1)
    return scores, (by_query, jnp.linalg.norm(k, axis=-1))


def allowed_pairs(seg):
    pos = jnp.arange(seg.shape[0])
    return (pos[:, None] >= pos[None, :]) & (seg[:, None] == seg[None, :])  # [packed]


def select(scores, seg, topk: int, score_block=None):
    """``(S, tau)``: S (T, T) bool, the ``topk`` allowed keys of every query with
    the largest scores (all of them where there are fewer; [top-k] ties to the
    lower position), and the smallest selected score of every query."""
    t = scores.shape[0]
    k = min(topk, t)
    scores = jnp.where(scores == 0, 0.0, scores)  # [zero]

    def rows(args):
        scores_blk, allowed_blk = args
        values, index = lax.top_k(jnp.where(allowed_blk, scores_blk, -jnp.inf), k)
        chosen = jnp.zeros(scores_blk.shape, bool).at[jnp.arange(scores_blk.shape[0])[:, None], index].set(True)
        chosen = chosen & allowed_blk
        return chosen, jnp.min(jnp.where(jnp.isfinite(values), values, jnp.inf), axis=-1)

    allowed = allowed_pairs(seg)
    if score_block is None:
        return rows((scores, allowed))
    n = t // score_block
    chosen, tau = lax.map(rows, (scores.reshape(n, score_block, t), allowed.reshape(n, score_block, t)))
    return chosen.reshape(t, t), tau.reshape(t)


def selection_report(scores, bound, seg, given, topk: int, score_block=None) -> dict:
    """How far ``given`` (T, T) bool is from the reference's own selection on
    ``scores``: ``differ`` the pairs in one and not in the other, ``own`` the
    pairs of the reference's, ``distance_max`` the largest distance of a
    differing pair's score from its query's threshold (the smallest selected
    score), in units of the pair's ``bound`` (``index_scores``' two factors: the
    size of the products the score is summed from), and ``deviations_max`` the
    same in standard deviations of that query's allowed scores."""
    own, tau = select(scores, seg, topk, score_block)
    allowed = allowed_pairs(seg)  # as ``select`` forms it
    n = jnp.maximum(jnp.sum(allowed, axis=-1), 1)
    mean = jnp.sum(jnp.where(allowed, scores, 0.0), axis=-1) / n
    spread = jnp.sqrt(jnp.sum(jnp.where(allowed, jnp.square(scores - mean[:, None]), 0.0), axis=-1) / n)
    differs = own != given
    distance = jnp.where(differs, jnp.abs(scores - tau[:, None]), 0.0)
    return {"differ": jnp.sum(differs), "own": jnp.sum(own), "given": jnp.sum(given),
            "outside_allowed": jnp.sum(given & ~allowed),
            "distance_max": jnp.max(distance / jnp.maximum(bound[0][:, None] * bound[1][None, :], 1e-30)),
            "deviations_max": jnp.max(distance / jnp.maximum(spread, 1e-30)[:, None])}


# ---- layers -----------------------------------------------------------------


def selected_attention(hf, q, k, v, chosen, head_block=None, q_block=None):
    """``(o (T, heads, size), P (T, T))``: the softmax over every query's chosen
    keys, dense under the mask, and the mean over the query heads of its
    probabilities."""
    t, heads, size = q.shape
    group = heads // k.shape[1]
    q, k, v = q.transpose(1, 0, 2), jnp.repeat(k, group, axis=1).transpose(1, 0, 2), jnp.repeat(
        v, group, axis=1).transpose(1, 0, 2)
    hb, qb = head_block or heads, q_block or t

    def rows(args):
        q_rows, chosen_rows = args  # (heads, qb, size), (qb, T)

        def some_heads(qkv):
            qh, kh, vh = qkv
            scores = size ** -0.5 * jnp.einsum("hqd,hsd->hqs", qh, kh)
            probs = jax.nn.softmax(jnp.where(chosen_rows, scores, -jnp.inf), axis=-1)
            return jnp.einsum("hqs,hsd->hqd", probs, vh), jnp.sum(probs, axis=0)

        blocks = [a.reshape(heads // hb, hb, *a.shape[1:]) for a in (q_rows, k, v)]
        o, p = lax.map(jax.checkpoint(some_heads), tuple(blocks))
        return o.reshape(heads, qb, size), jnp.sum(p, axis=0) / heads

    o, p = lax.map(rows, (q.reshape(heads, t // qb, qb, size).transpose(1, 0, 2, 3), chosen.reshape(t // qb, qb, t)))
    return o.transpose(0, 2, 1, 3).reshape(t, heads, size), p.reshape(t, t)


def indexer_kl(scores, chosen, target):
    """``mean_t KL(target[t, .] || softmax over chosen of scores[t, .])``."""
    log_q = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf), axis=-1)
    counted = chosen & (target > 0)
    terms = jnp.where(counted, target * (jnp.log(jnp.where(counted, target, 1.0)) - jnp.where(counted, log_q, 0.0)), 0.0)
    return jnp.sum(terms) / scores.shape[0]


def attention(hf, p, indexer, h, seg, ids, given=None, blocks=None):
    """-> (``W_o o`` (T, d), ``L_I``, the keys attended to (T, T) bool, the report
    on ``given`` or nothing)."""
    blocks = blocks or {}
    t = h.shape[0]
    heads, kv, size, eps = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"], hf["rms_norm_eps"]
    sections, topk = hf["rope_scaling"]["mrope_section"], hf["sa_config"]["topk"]
    q = rotate(rms_norm((h @ p["q"]).reshape(t, heads, size), p["q_norm"], eps), ids, hf["rope_theta"], sections)
    k = rotate(rms_norm((h @ p["k"]).reshape(t, kv, size), p["k_norm"], eps), ids, hf["rope_theta"], sections)
    v = (h @ p["v"]).reshape(t, kv, size)
    scores, bound = index_scores(hf, indexer, lax.stop_gradient(h), ids, blocks.get("score_block"), with_bound=True)
    if given is None:
        chosen, report = select(lax.stop_gradient(scores), seg, topk, blocks.get("score_block"))[0], None
    else:
        chosen = given
        report = selection_report(lax.stop_gradient(scores), lax.stop_gradient(bound), seg, given, topk,
                                  blocks.get("score_block"))
    o, probs = selected_attention(hf, q, k, v, chosen, blocks.get("head_block"), blocks.get("q_block"))
    kl = indexer_kl(scores, chosen, lax.stop_gradient(probs))
    return o.reshape(t, heads * size) @ p["o"], kl, chosen, report


def gate(hf, router, u):
    """``(scores, weights, picks)``: the softmax over all experts (T, experts),
    every expert's weight for every token (the picked scores over their sum,
    zero where not picked), the k ids."""
    scores = jax.nn.softmax(u @ router["gate"], axis=-1)
    picks = jnp.argsort(-scores, axis=-1)[:, : hf["num_experts_per_tok"]]  # [top-k]
    picked = jnp.sum(jax.nn.one_hot(picks, scores.shape[-1], dtype=scores.dtype), axis=1)
    weights = scores * picked
    return scores, weights / jnp.sum(weights, axis=-1, keepdims=True), picks  # norm_topk_prob


def moe(hf, router, experts, u, held):
    """-> (F(u), this sequence's mean score of every expert, the picks)."""
    scores, weights, picks = gate(hf, router, u)
    y = jnp.zeros_like(u)
    for j, e in enumerate(held):  # [share]
        y = y + weights[:, e, None] * mlp(jax.tree.map(lambda w: w[j], experts), u)
    return y, jnp.mean(scores, axis=0), picks


def layer(hf, attn_p, indexer_p, router_p, experts_p, norms, x, seg, ids, held, given=None, blocks=None):
    """One decoder layer on one sequence: x (T, d) -> (y, ``L_I``, every expert's
    mean score, (picks, the keys attended to, the report on ``given``))."""
    eps = hf["rms_norm_eps"]
    a, kl, chosen, report = attention(hf, attn_p, indexer_p, rms_norm(x, norms["attention"], eps), seg, ids, given,
                                      blocks)
    h = x + a
    f, mean_scores, picks = moe(hf, router_p, experts_p, rms_norm(h, norms["mlp"], eps), held)
    return h + f, kl, mean_scores, (picks, chosen, report)


def _layer_params(params, i):
    name = f"layer_{i}"
    return (params["attention"][name], params["indexer"][name], params["router"][name], params["experts"][name],
            params["norms"][name])


def head_loss(hf, params, x, tokens, seg):
    """(sum of next-token cross-entropies over counted positions, logits)."""
    logits = rms_norm(x, params["norms"]["final"], hf["rms_norm_eps"]) @ params["head"]["rows"].T
    counted = seg[1:] == seg[:-1]
    logp = jax.nn.log_softmax(logits[:-1], axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(counted, nll, 0.0)), logits


def text_ids(seg):
    """[packed] text: the temporal, height and width id are the position in the document."""
    return jnp.broadcast_to(document_positions(jnp.asarray(seg)), (3, len(seg)))


def balance(hf, counts, mean_scores):
    """Qwen3-MoE's ``load_balancing_loss_func`` without its coefficient, for
    router logits concatenated over the layers and the batch: ``counts``
    (layers x sequences, experts) the picks of every expert, ``mean_scores``
    alike its mean score, over the ``tokens`` tokens of one layer's sequence.
    ``experts x sum_i f_i P_i``, ``f_i`` the picks of i a token; the counts carry
    no gradient."""
    experts = mean_scores.shape[-1]
    return experts * jnp.sum(lax.stop_gradient(jnp.mean(counts, axis=0)) * jnp.mean(mean_scores, axis=0))


def _picked(hf, picks):
    """(T, k) picks -> every expert's picks a token (experts,)."""
    experts = hf.get("num_experts_total", hf["num_experts"])
    return jnp.sum(jax.nn.one_hot(picks, experts, dtype=jnp.float32), axis=(0, 1)) / picks.shape[0]


def _sequence(hf, params, tok, sg, ids, held, given, blocks):
    """-> (last hidden state, the layers' ``L_I`` summed, by layer every
    expert's picks a token and mean score, the keys attended to by layer)."""
    x, kl, counts, means, chosen = params["embed"]["embedding"][tok], 0.0, [], [], []
    for i in range(hf["num_hidden_layers"]):
        x, kl_i, mean_scores, (picks, s, _) = layer(hf, *_layer_params(params, i), x, sg, ids, held,
                                                    None if given is None else given[i], blocks)
        kl = kl + kl_i
        counts.append(_picked(hf, picks))
        means.append(mean_scores)
        chosen.append(s)
    return x, kl, counts, means, chosen


def forward(hf, params, tokens, seg, held, position_ids=None, selection=None, **blocks):
    """Logits (batch, T, vocabulary held) of a batch of packed sequences and
    the keys every query attended to (layers, batch, T, T)."""
    with jax.default_matmul_precision(HIGHEST):
        logits, chosen = [], []
        for b, (t, s) in enumerate(zip(tokens, seg)):
            ids = text_ids(s) if position_ids is None else position_ids[:, b]
            given = None if selection is None else [m[b] for m in selection]
            x, _, _, _, ch = _sequence(hf, params, t, jnp.asarray(s), ids, held, given, blocks)
            logits.append(head_loss(hf, params, x, t, s)[1])
            chosen.append(jnp.stack(ch))
        return jnp.stack(logits), jnp.stack(chosen, axis=1)


def _count(seg):
    return jnp.maximum(jnp.sum(seg[:, 1:] == seg[:, :-1]), 1).astype(jnp.float32)


def _coefficients(hf):
    return hf.get("router_aux_loss_coef", ROUTER_AUX_LOSS_COEF), hf.get("indexer_loss_coef", INDEXER_LOSS_COEF)


def loss(hf, params, tokens, seg, held, position_ids=None, selection=None, **blocks):
    """``(loss, (balance term, indexer term))``: the mean cross-entropy of the
    next token over the batch's positions whose next token lies in the same
    document, plus the two auxiliary terms with their coefficients (the
    indexer's is the mean over the batch's queries, summed over the layers)."""
    alpha, beta = _coefficients(hf)
    with jax.default_matmul_precision(HIGHEST):
        total, kl, counts, means = 0.0, 0.0, [], []
        for b, (tok, sg) in enumerate(zip(tokens, seg)):
            ids = text_ids(sg) if position_ids is None else position_ids[:, b]
            given = None if selection is None else [m[b] for m in selection]
            x, kl_b, c, m, _ = _sequence(hf, params, tok, jnp.asarray(sg), ids, held, given, blocks)
            total, kl = total + head_loss(hf, params, x, tok, sg)[0], kl + kl_b
            counts += c
            means += m
        aux = alpha * balance(hf, jnp.stack(counts), jnp.stack(means))
        kl = beta * kl / len(tokens)
        return total / _count(seg) + aux + kl, (aux, kl)


def loss_and_grads(hf, params, tokens, seg, held, position_ids=None, selection=None, **blocks):
    """-> ((loss, (balance term, indexer term)), gradients of the loss)."""
    return jax.value_and_grad(lambda p: loss(hf, p, tokens, seg, held, position_ids, selection, **blocks),
                              has_aux=True)(params)


def loss_and_grads_by_layer(hf, params, tokens, seg, held, selection=None, **blocks):
    """``loss_and_grads`` for text with the chain rule written out per layer (one
    layer's float32 activations alive at a time), each layer's picks, and where
    ``selection`` is given the report on it: -> ((loss, (balance term, indexer
    term)), gradients, picks (batch, layers, T, k), reports (a list by sequence
    of a list by layer of ``selection_report``'s dict, or nothing))."""
    alpha, beta = _coefficients(hf)
    held, layers, batch = tuple(held), hf["num_hidden_layers"], len(tokens)
    experts = hf.get("num_experts_total", hf["num_experts"])
    with jax.default_matmul_precision(HIGHEST):
        count = _count(seg)
        layer_fn = jax.jit(lambda ap, ip, rp, ep, np_, x, sg, ids, given: layer(
            hf, ap, ip, rp, ep, np_, x, sg, ids, held, given, blocks))
        # the layer's three differentiable results: its output, its L_I and its mean scores
        layer_bwd = jax.jit(lambda ap, ip, rp, ep, np_, x, sg, ids, given, dy, dkl, dmean: jax.vjp(
            lambda ap, ip, rp, ep, np_, x: layer(hf, ap, ip, rp, ep, np_, x, sg, ids, held, given, blocks)[:3],
            ap, ip, rp, ep, np_, x)[1]((dy, dkl, dmean)))
        head = jax.jit(jax.value_and_grad(
            lambda h, w, x, tok, sg: head_loss(hf, {"head": {"rows": h}, "norms": {"final": w}}, x, tok, sg)[0]
            / count, argnums=(0, 1, 2)))
        embed_bwd = jax.jit(lambda e, tok, dx: jax.vjp(lambda e: e[tok], e)[1](dx)[0])
        add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))

        # the balance term spans layers and sequences: every sequence forward first, for the picks' counts
        e, h, w = params["embed"]["embedding"], params["head"]["rows"], params["norms"]["final"]
        inputs, picks, reports, counts, means, kl = [], [], [], [], [], 0.0
        for b, (tok, sg) in enumerate(zip(tokens, seg)):
            sg = jnp.asarray(sg)
            ids, xs, picks_b, reports_b = text_ids(sg), [e[tok]], [], []
            for i in range(layers):
                given = None if selection is None else selection[i][b]
                y, kl_i, mean_scores, (pk, _, report) = layer_fn(*_layer_params(params, i), xs[-1], sg, ids, given)
                xs.append(y)
                kl = kl + kl_i
                picks_b.append(pk)
                reports_b.append(report)
                counts.append(_picked(hf, pk))
                means.append(mean_scores)
            inputs.append(xs)
            picks.append(jnp.stack(picks_b))
            reports.append(reports_b)
        aux = alpha * balance(hf, jnp.stack(counts), jnp.stack(means))
        d_mean = alpha * experts * jnp.mean(jnp.stack(counts), axis=0) / (layers * batch)

        grads = jax.tree.map(jnp.zeros_like, params)
        total = 0.0
        for b, (tok, sg) in enumerate(zip(tokens, seg)):
            sg = jnp.asarray(sg)
            ids, xs = text_ids(sg), inputs[b]
            part, (dh, dw, dx) = head(h, w, xs.pop(), tok, sg)
            total = total + part
            grads["head"]["rows"] = grads["head"]["rows"] + dh
            grads["norms"]["final"] = grads["norms"]["final"] + dw
            for i in reversed(range(layers)):
                name = f"layer_{i}"
                given = None if selection is None else selection[i][b]
                dap, dip, drp, dep, dnp, dx = layer_bwd(*_layer_params(params, i), xs.pop(), sg, ids, given, dx,
                                                        jnp.asarray(beta / batch, jnp.float32), d_mean)
                for group, d in (("attention", dap), ("indexer", dip), ("router", drp), ("experts", dep),
                                 ("norms", dnp)):
                    grads[group][name] = add(grads[group][name], d)
            grads["embed"]["embedding"] = grads["embed"]["embedding"] + embed_bwd(e, tok, dx)
        kl = beta * kl / batch
        return (total + aux + kl, (aux, kl)), grads, jnp.stack(picks), (None if selection is None else reports)

"""``lm_layers.next_token_loss`` (PR 47) names the target's logit by comparison
with the vocabulary's index: its value, the positions it counts and its gradient
are those of the plain form written here (``log_softmax`` over ``logits[:, :-1]``
and ``take_along_axis``, float32), and the gradient's program holds no gather and
no scatter, which is what the gain on the chip rests on
(``lm_layers.py::LOSS_MEASURED``); the one cut of a logits-sized array is the
static ``[:, :-1]`` and its transpose, a pad."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batchai_retinanet_horovod_coco_tpu.models import lm_layers


def _reference(logits, tokens, segment_ids):
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    counted = (segment_ids[:, 1:] == segment_ids[:, :-1]).astype(jnp.float32)
    n = jnp.sum(counted)
    return jnp.sum(nll * counted) / jnp.maximum(n, 1.0), n


# name: (vocabulary, the documents' lengths of each sequence, in order; ids restart at 0 in every sequence).  Four of
# one shape (37 tokens: no multiple of 8; 53: no multiple of 128), so that they share their compilations.
PACKINGS = {
    "boundary_before_the_last_position": (53, [[20, 16, 1], [36, 1]]),
    "one_document_first_and_last_ids_equal": (53, [[37], [37]]),
    "two_sequences_apart": (53, [[5, 30, 2], [1, 36]]),
    "a_boundary_at_every_position": (53, [[1] * 37, [1] * 37]),
    "whole_tiles": (128, [[9, 7]]),
}


def _case(name):
    vocabulary, lengths = PACKINGS[name]
    seg = np.stack([np.repeat(np.arange(len(row)), row) for row in lengths]).astype(np.int32)
    key = jax.random.fold_in(jax.random.key(47), sorted(PACKINGS).index(name))
    logits = 4.0 * jax.random.normal(key, (*seg.shape, vocabulary), jnp.float32)
    tokens = jax.random.randint(jax.random.fold_in(key, 1), seg.shape, 0, vocabulary, jnp.int32)
    return logits, tokens, jnp.asarray(seg)


@pytest.mark.parametrize("name", sorted(PACKINGS))
def test_value_count_and_gradient_are_the_plain_forms(name):
    logits, tokens, seg = _case(name)
    (loss, n), grad = jax.jit(jax.value_and_grad(lm_layers.next_token_loss, has_aux=True))(logits, tokens, seg)
    (want, want_n), want_grad = jax.jit(jax.value_and_grad(_reference, has_aux=True))(logits, tokens, seg)
    assert float(n) == float(want_n) == float(np.sum(np.asarray(seg)[:, 1:] == np.asarray(seg)[:, :-1]))
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(want_grad), rtol=0, atol=1e-6)
    assert not np.any(np.asarray(grad)[:, -1])  # the last position has no target: exactly 0, as the plain form's
    if name == "a_boundary_at_every_position":
        assert float(n) == 0.0 and float(loss) == 0.0 and not np.any(np.asarray(grad))


def test_the_first_token_is_nobodys_target():
    """One document, so the first and the last segment ids are equal: moving the FIRST token (what a form over
    all T positions would hand the last one as its target) changes nothing."""
    logits, tokens, seg = _case("one_document_first_and_last_ids_equal")
    other = tokens.at[:, 0].set((tokens[:, 0] + 1) % logits.shape[-1])
    loss = jax.jit(lm_layers.next_token_loss)
    a, b = loss(logits, tokens, seg), loss(logits, other, seg)
    assert float(a[0]) == float(b[0]) and float(a[1]) == float(b[1]) == 2 * (tokens.shape[1] - 1)


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def test_the_gradient_holds_no_gather_and_no_scatter_and_cuts_the_logits_once():
    logits, tokens, seg = _case("two_sequences_apart")
    jaxpr = jax.make_jaxpr(jax.grad(lambda x: lm_layers.next_token_loss(x, tokens, seg)[0]))(logits)
    eqns = list(_equations(jaxpr.jaxpr))
    names = [eqn.primitive.name for eqn in eqns]
    assert "reduce_sum" in names and "exp" in names  # the walk sees inside the nested calls
    assert not [n for n in names if "gather" in n or "scatter" in n or n.startswith("dynamic")], names
    cuts = [eqn.primitive.name for eqn in eqns
            if eqn.primitive.name in ("slice", "pad", "concatenate") and eqn.invars[0].aval.ndim == 3]
    assert cuts == ["slice", "pad"], cuts  # ``logits[:, :-1]`` forward, zeros for the last position backward

"""What the language models share (models/granite_hybrid.py,
models/deepseek_v2.py, models/nemotron_h.py, models/keye_vl2.py,
models/olmo_hybrid.py, models/afmoe.py): RMSNorm, the matmul with a weight, the gated SiLU MLP
and the squared-ReLU MLP, a Mamba-2 mixer's depthwise convolution, the
embedding lookup, the head, the next-token loss, and what a layer recomputed in
the backward pass keeps (``layer_keeps``: the attention kernels' residuals, the
sparse attention's thresholds and, where the device has the room, the gated
MLPs' products with ``gate_up``, named ``MLP_GATE_UP``).

A matmul's operands are rounded by the CALLER's ``cast`` (its module's
``_operand`` bound to its config): the benchmark's precision controls patch
that one function of a model's module and nothing here.  Norms and the loss
reduce in float32.  The loss names the target's logit by comparison with the
vocabulary's index, not by a gather (PR 47; ``LOSS_MEASURED`` beside
``next_token_loss`` says what the gather's scatter-add cost on the chip, and what
running over all T positions with the last one masked read there).
"""

from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from batchai_retinanet_horovod_coco_tpu.ops import attention, document_conv
from batchai_retinanet_horovod_coco_tpu.ops import sparse_attention as sparse

# What a model's ``jax.checkpoint(_layer)`` keeps of a layer beside its input: whatever carries one of ``names``
# (``policy`` below); of these ``MLP_GATE_UP`` is carried by ``gate_up_layers`` products of ``gate_up_bytes`` in all.
Keeps = collections.namedtuple("Keeps", "names gate_up_layers gate_up_bytes")

MLP_GATE_UP = "mlp_gate_up"  # ``gated_mlp``'s product with ``gate_up``, (batch, T, 2 x intermediate size)

# ``layer_keeps``' memory model, in bytes of the device: a step holds STATE_AND_GRADIENTS x the parameters' bytes
# (float32 parameters, Adam's two moments, and the gradients, most of them products of bfloat16 operands that XLA holds
# unconverted: half the parameters' bytes) and WORKING_SET x one layer's input (the float32 logits and what is live
# while one layer is recomputed and differentiated: both grow with tokens x width); what is kept besides has to leave
# MARGIN of the limit free.
#
# MEASURED (``memory_analysis()`` of the five cells' train steps compiled for a described v5e, ``jax.default_backend``
# patched to say ``tpu``, before any chip call; PR 44; GB = 1e9 bytes; the limit a v5e states is 16.909):
#   cell's model                   granite      dsv2        olmo        nemo3       keye
#   parameters P                   3.089        2.542       3.715       2.668       2.637
#   arguments (= 3 P)              9.266        7.626       11.147      8.004       7.910
#   temporaries, today's names     3.000        4.589       4.621       5.847       6.091
#     less P / 2, over one layer's
#     input (tokens x d x 2 bytes) 43.4         49.4        43.9        51.2        71.1    <- WORKING_SET 50
#   + code = the step              12.471       12.581      15.809      14.106      14.716
#   the model's estimate of it     12.488       12.252      16.149      13.742      12.584
#   named products: layers, bytes  10, 2.684    6, 1.640    4, 1.443    none        none
#   temporaries, products kept     5.382        5.740       4.500 (!)   -           -
#   + code = the step              14.842       13.728      15.687      -           -
#   the estimate + the products    15.172       13.892      17.592      -           -
#   of the limit                   89.7%        82.2%       104.0%      -           -
#   ``layer_keeps`` at 94%         KEEPS        KEEPS       nothing more
# (keye's working set, the index scores of 16 384 keys a query and the widest vocabulary slice, reads 71 inputs and
# enters no decision: it has no such product.)
# (PR 46, trinity, the same way: P 2.822, arguments 8.466, temporaries 5.818 = 65.7 layer inputs over P / 2, the step
# 14.586 where the model says 13.232; five products of 0.671 in all: temporaries 6.646, the step 15.409 = 91.1% of the
# limit where the model says 13.903 = 82.2%: KEEPS, and it fits, but a 16 384-token step's working set is 66-71 inputs,
# not 50, and here the products cost MORE than their bytes, 0.829.)
# (PR 47, the six steps the same way, parent 38762b9 -> ``next_token_loss`` by comparison; temporaries in GB:
# granite 5.382 -> 5.123   dsv2 5.740 -> 5.740   olmo 4.621 -> 4.621   nemo3 5.847 -> 5.847   keye 6.091 -> 6.091
# trinity 7.325 -> 6.349 (PR 46's builder read 6.646 of its tree; mine, the numerics plane on as the cells run it,
# reads 7.325 of the same files: one script for both sides here).  The head's gradient was the step's peak in
# granite and trinity alone; the constants below stay as they were.)
# What is kept costs less than its bytes (granite 2.38 of 2.68 GB, dsv2 1.15 of 1.64) because it takes the room of
# temporaries that died earlier (PR 39 found the same), and in olmo's step the compiler's count FALLS by 0.12 GB: by
# that count olmo's products would fit at 92.8%.  The model cannot see that (it would take a compilation to find out,
# and a step that does not fit fails to compile), so it answers from what the step needs before any reuse.
STATE_AND_GRADIENTS, WORKING_SET, MARGIN = 3.5, 50, 0.06


def param_shapes(init_params, config):
    """The shapes of ``init_params(config, key)``'s tree, nothing computed: a ``run_meta(bucket)`` has no
    parameters, and asks ``keeps_of`` what the traced step asks with them."""
    return jax.eval_shape(functools.partial(init_params, config), jax.random.key(0))


def device_memory_limit() -> int | None:
    """The bytes a program may take of the first local device; ``None`` where the backend does not say
    (the CPU)."""
    return (jax.local_devices()[0].memory_stats() or {}).get("bytes_limit")


def layer_keeps(products, param_bytes: int, layer_input_bytes: int, memory_limit: int | None) -> Keeps:
    """What every recomputed layer of a step keeps: the attention kernels' residuals and the sparse attention's
    thresholds (a few hundred MB a step against a second run of a forward kernel a layer; the xla lowerings carry
    neither name), and ``MLP_GATE_UP`` where the gated MLPs' named products fit the device beside the rest of the
    step.  Selective recomputation under a memory budget, decided from what the program sees when it is traced and
    from nothing else: ``products`` the bytes of each such product (one a layer that calls ``gated_mlp``),
    ``param_bytes`` the parameter tree's, ``layer_input_bytes`` one layer's input's (batch x T x d in the
    activations' dtype), ``memory_limit`` the device's.  All products or none; none where the device states no limit
    (the CPU: its programs are what they were)."""
    names, kept = (attention.RESIDUALS, sparse.THRESHOLD), sum(products)
    if not kept or memory_limit is None:
        return Keeps(names, 0, 0)
    step = STATE_AND_GRADIENTS * param_bytes + WORKING_SET * layer_input_bytes
    if step + kept > (1 - MARGIN) * memory_limit:
        return Keeps(names, 0, 0)
    return Keeps((*names, MLP_GATE_UP), len(products), kept)


NO_PRODUCT = layer_keeps((), 0, 0, None)  # of a model without a gated MLP: there is nothing to decide


def keeps_of(widths, params, bucket, hidden_size: int, dtype) -> Keeps:
    """``layer_keeps`` for a step over ``bucket`` (sequences, tokens) on this process's first device: ``widths``
    the intermediate size of every gated MLP the step runs (a product is tokens x 2 x that, in ``dtype``),
    ``params`` the parameter tree (arrays, tracers or shapes), ``hidden_size`` and ``dtype`` the activations'."""
    tokens, itemsize = bucket[0] * bucket[1], jnp.dtype(dtype).itemsize
    param_bytes = sum(math.prod(x.shape) * x.dtype.itemsize for x in jax.tree.leaves(params))
    return layer_keeps(tuple(tokens * 2 * w * itemsize for w in widths), param_bytes, tokens * hidden_size * itemsize,
                       device_memory_limit())


@functools.lru_cache(maxsize=None)
def policy(keeps: Keeps):
    """The ``policy`` of a model's ``jax.checkpoint(_layer)``: ONE object for one answer.  JAX keys its caches of
    a checkpointed layer's traces on the policy's identity, so a fresh closure a trace would make a step built a
    second time in a process (the benchmark's measured call) trace every layer anew: 3.9 s for 1.1 in granite's
    cell (my chip run, PR 44)."""
    return jax.checkpoint_policies.save_only_these_names(*keeps.names)


def run_meta(keeps: Keeps) -> dict:
    """What a model's ``run_meta`` says of what its recomputed layers keep."""
    return {"layer_keeps": ",".join(keeps.names), "mlp_gate_up_layers": keeps.gate_up_layers,
            "mlp_gate_up_bytes": keeps.gate_up_bytes}


def rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def matmul(cast, x, w):
    return jnp.dot(cast(x), cast(w))


def gated_mlp(cast, p, u):
    """``W_down (silu(W_g u) * W_u u)`` with ``p = {gate_up, down}``; the product with ``gate_up`` carries
    the checkpoint name ``MLP_GATE_UP``."""
    gate, up = jnp.split(checkpoint_name(matmul(cast, u, p["gate_up"]), MLP_GATE_UP), 2, axis=-1)
    return matmul(cast, jax.nn.silu(gate) * up, p["down"])


def relu2_mlp(cast, p, u):
    """``W_down relu(W_up u)^2`` with ``p = {up, down}``: no gate."""
    return matmul(cast, jnp.square(jax.nn.relu(matmul(cast, u, p["up"]))), p["down"])


# The mixers' depthwise convolution, ``document_conv_silu(x, w, b, segment_ids)`` -> float32: the models look
# it up HERE at call time (the benchmark's mutations patch this name); ops/document_conv.py says which
# lowering runs.
document_conv_silu = document_conv.document_conv_silu


def embed_lookup(table, tokens, dtype, multiplier=None):
    rows = table[tokens]
    return (rows if multiplier is None else multiplier * rows).astype(dtype)


def head_logits(cast, x, table):
    """float32 logits of ``x`` (batch, T, d) over ``table``'s rows (vocabulary, d)."""
    return jnp.einsum("btd,vd->btv", cast(x), cast(table), preferred_element_type=jnp.float32)


# LOSS_MEASURED (PR 47).  Why ``next_token_loss`` is written as it is.  Until PR 47 it read the target's logit with
# ``jnp.take_along_axis(logits[:, :-1], targets)``.  The gradient of that gather is a scatter-add of T - 1 numbers into
# zeros of the logits' size, and XLA for this chip runs it on a FLAT array: with ONE sequence a step it keeps the
# scatter (with two it rewrites it itself), and where the vocabulary slice is no whole lane tile of 128 (trinity's
# 25 024, keye's 18 992) it also fills ``f32[V x (T - 1)]`` with zeros, copies the float32 softmax term into it with a
# ``while`` of ``dynamic-update-slice`` steps, scatters, converts the flat array to bfloat16 and copies it back to
# three dimensions with a second ``while``, and only then do the head's two gradient products read it: in trinity's
# cell ``while.201`` over ``f32[1,25024,16383]`` (with ``dynamic-update-slice.168 f32[409968192]`` in its body) 13.78 ms
# a step and 35.7 ms of ``unscoped`` in all (my traced pair, PR 47).  A comparison with the vocabulary's index and a
# sum name the same number, and their gradient is a select: softmax minus one-hot, ONE fusion that writes the bfloat16
# gradient the two products read.
# TWO forms were compiled for a described v5e at the six cells' real steps and run on the chip:
#   (A) over all T positions, the last masked (``jnp.roll`` for the targets): no slice of the logits either; XLA then
#       puts the row maximum into the logits' product's epilogue and softmax-minus-one-hot into the PROLOGUE of both
#       gradient products, and the float32 logits are the step's one logits-sized array;
#   (B) the comparison over ``logits[:, :-1]``: what is below.  The bfloat16 gradient is written once and read twice.
# The compiler's readings (``memory_analysis()``, ``cost_analysis()``, ``as_text()``; no timings), parent 38762b9 ->
# A / B; "results" counts the entry computation's instructions whose RESULT has the logits' size (flat or not, either
# dtype), "loops" the ``while`` / ``scatter`` over such arrays:
#   cell's step (sequences, T, V / 8)  results     loops           temporaries GB          bytes accessed GB
#   trinity (1, 16384, 25024)          10 -> 1 / 2  2 / 1 -> none  7.325 -> 6.349 / 6.349  396.6 -> 386.6 / 389.0
#   keye    (1, 16384, 18992)          12 -> 1 / 2  2 / 1 -> none  6.091 -> 6.091 / 6.091  589.8 -> 582.0 / 583.9
#   nemo3   (2,  8192, 16384)           2 -> 1 / 2  none           5.847 -> 5.847 / 5.847  365.5 -> 364.7 / 365.4
#   dsv2    (2,  8192, 12800)           2 -> 1 / 2  none           5.740 -> 5.740 / 5.740  399.0 -> 398.5 / 398.9
#   granite (1,  8192, 12544)           6 -> 1 / 2  0 / 1 -> none  5.382 -> 4.568 / 5.123  144.5 -> 140.8 / 142.3
#   olmo    (1,  8192, 12544)           6 -> 1 / 2  0 / 1 -> none  4.621 -> 4.621 / 4.621  132.4 -> 129.6 / 130.2
# ON THE CHIP (my chip runs, PR 47; ``train_img_per_s_chip`` of pairs of one seed, ``train_step.device_ms`` and the
# slices of traced runs, ms a step):
#   trinity  parent 1.5378 / 1.5413 -> A 1.6309 / 1.6337 (+6.05%, +6.00%); parent 1.5398 / 1.5383 / 1.5328 -> B 1.6275
#            / 1.6233 / 1.6236 (+5.70%, +5.53%, +5.93%).  Traced (parent -> A; B at another seed): device 667.36 ->
#            629.65; 624.77.  ``unscoped`` 56.88 -> 21.15; 21.12: the loops, scatter, convert and fills were 35.7.
#            ``lm_head`` 32.76 -> 35.17; 32.76: A's gradient products 10.23 + 9.08 -> 12.26 + 9.31 under their prologue.
#            ``loss`` 7.16 -> 2.17; 7.88: B's gradient fusion 3.54, the sums 2.17, the row maximum's pass 2.17.
#   nemo3    parent 4.0273 -> A 4.0013 (-0.65%: every log window 3.4 ms a step SLOWER; A's dx product with the
#            prologue, ``fusion.602``, 11.95 ms where the parent's reads 7.83); parent 4.0308 -> B 4.0310 (the parent's
#            step but for the gather: the logits' product with the row maximum in its epilogue 9.16, the gradient's
#            fusion 2.30).
#   keye     parent 0.71617 -> B 0.73166 (+2.16%); A not paired (traced device 1353.48 at one seed, B 1376.16 at
#            another).
#   traced alone (the ledger's PR 46 parent lines, other seeds, in brackets): granite A 376.71, B 376.32 [379.8]; olmo
#            A 551.42, B 550.17 [554.2]; dsv2 A 516.31, B 515.56 (its step follows the seed).
#   ``memory_peak_bytes``, parent -> A / B: trinity 15 224 413 184 -> 14 949 998 080 / 14 950 328 832; granite
#            14 339 112 960 -> 13 798 158 336 / 14 209 447 936; nemo3, dsv2, olmo and keye within 2 MB of the parent's.
# B is kept: over all T positions XLA folds softmax-minus-one-hot into BOTH gradient products, which then compute every
# exponent twice and run slower than the one fusion they absorbed wherever the step holds two sequences (nemo3 -0.65%,
# bound 1%), for about 0.4% more than B in trinity (a step 612.6 against 615.4 ms over the untraced log windows).
# What the ragged T - 1 still costs B: a pass for the row maximum in the one-sequence cells (``slice_reduce_fusion``,
# 2.17 ms in trinity, 1.65 keye, 0.55 granite and olmo).
# ``tests/unit/test_next_token_loss.py`` holds the value, the count and the gradient to the plain form, and the
# gradient's jaxpr to no gather and no scatter.


def next_token_loss(logits, tokens, segment_ids):
    """Mean cross-entropy of the next token over positions whose next token
    lies in the same document; also the number of such positions.

    The target's logit is named by COMPARISON with the vocabulary's index, not by ``take_along_axis``: the
    gradient of a gather is a scatter-add (``LOSS_MEASURED`` above says what that cost on the chip, and why the
    positions are still ``[:, :-1]`` and not all T with the last masked)."""
    targets = tokens[:, 1:]
    counted = (segment_ids[:, 1:] == segment_ids[:, :-1]).astype(jnp.float32)
    logits = logits[:, :-1].astype(jnp.float32)
    hit = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2) == targets[..., None]
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
    n = jnp.sum(counted)
    return jnp.sum(nll * counted) / jnp.maximum(n, 1.0), n

"""ResNet backbone (v1.5 bottleneck) exposing C3, C4, C5 feature maps.

Parity target: keras-retinanet's ResNet-50 backbone (SURVEY.md M2,
``models/resnet.py`` + the keras-resnet dependency), which feeds C3..C5 into
the FPN and freezes BatchNorm during detection fine-tuning.

TPU-first design:
- NHWC layout (XLA:TPU's native conv layout), bfloat16 activations with
  float32 params by default — convs hit the MXU in bf16.
- Norm is pluggable:
  * ``"gn"`` (default): GroupNorm(32) — batch-size independent, no mutable
    state, the right choice for from-scratch training in an air-gapped env
    (SURVEY.md §7.3 hard part 5);
  * ``"bn"``: BatchNorm with running stats (mutable ``batch_stats``);
  * ``"frozen_bn"``: running-stats-only BatchNorm (never updates), matching
    the reference's frozen-BN fine-tuning recipe when pretrained weights are
    supplied.
- Strided 3x3 in the bottleneck's middle conv (v1.5), symmetric torch-style
  padding (k//2 each side) so imported torchvision weights see the exact
  sampling grid they were trained with; spatial dims still follow
  ceil(H/stride) — consistent with ops.anchors.feature_shape.
"""

from __future__ import annotations

from collections.abc import Callable, Sequence
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

ModuleDef = Any


class StemConv(nn.Module):
    """The 7x7/2 stem conv, optionally computed space-to-depth.

    The plain stem is the worst op on the MXU: a 3-input-channel conv runs
    the 128-wide systolic array at ~2% occupancy (profiled 7.5 TFLOP/s vs
    ~180 for the heads' 256-channel convs).  ``space_to_depth`` is the
    MLPerf-ResNet reformulation: fold each 2x2 pixel block into channels
    (3 → 12) and convolve 4x4/1 with an exactly-equivalent reshaped kernel —
    identical math, 4x the contraction depth, one H-fold transpose of the
    (B, H, W, 3) tensor (the W fold is layout-free; see the fold comment).

    The parameter keeps the canonical ``(7, 7, C, 64)`` layout either way, so
    checkpoints and the torch-weight importer (models/import_weights.py) are
    mode-independent; the kernel reshape is 9k elements and folds into XLA's
    constant/weight preprocessing.

    ``block=4`` folds 4x4 tiles (48-channel contraction, both MXU sides well
    fed) and emits each block's two stride-2 outputs as channels, unfolded
    depth-to-space after.  MEASURED (v5e-1, flagship b8 train step): 140.9 ms
    vs 135.1 ms for ``block=2`` — the zero-padded kernel does 2.9x the MACs
    and the (B, H/4, W/4, 256) output shuffle is extra bandwidth, which
    together outweigh the packing gain.  Kept as an exact, tested
    reformulation in case future hardware shifts the tradeoff; ``block=2``
    stays the default.
    """

    features: int = 64
    space_to_depth: bool = False
    # Fold size when space_to_depth: 2 folds 2x2 pixel blocks (12-channel
    # contraction), 4 folds 4x4 blocks (48 channels, both MXU sides well fed
    # — measured numbers in the class docstring) and emits both stride-2
    # outputs of each block as channels, unfolded depth-to-space after.
    block: int = 2
    dtype: jnp.dtype = jnp.bfloat16

    # When True (and the h2w4 lowering applies), return the conv output in
    # its native packed layout (B, H/2, W/4, (u, f)) — u = the two stride-2
    # W outputs per block, u-MAJOR — instead of unfolding to
    # (B, H/2, W/2, f).  The unfold is a lane retile (128 -> 64) that XLA
    # pays as ~4 copies fwd+bwd (~5 ms/step profiled); the ResNet wiring
    # instead runs norm/relu packed and lets the maxpool consume the packed
    # layout directly (maxpool_packed_w).
    packed_output: bool = False

    def _h2w4(self, x: jnp.ndarray, kernel: jnp.ndarray) -> jnp.ndarray:
        """block=2 stem computed as an H-fold-2 / W-fold-4 block conv.

        Same math as the 2x2 fold (one zero-led kernel regather), but the
        conv runs at (4, 3, 8c, 128) instead of (4, 4, 4c, 64): 24 input
        channels / 128 output channels fill the MXU far better than 12/64,
        which outweighs the 1.5x MAC redundancy of the wider zero-padded
        taps.  MEASURED (v5e-1, flagship shapes, fwd+bwd in isolation):
        4.4 ms vs 9.1 ms for the 2x2 form — and unlike the 4x4 fold
        (measured end-to-end negative, class docstring) BOTH W-side
        reshapes stay free: the W input fold because W-slots are
        channel-major, and the W output unfold because the two stride-2
        outputs of each block are emitted u-MAJOR ahead of the feature
        channels.  Only the H fold moves data (the same single transpose
        the 2x2 form pays).

        Derivation (torch geometry, per dim: out[o] = Σ_t w[t]·x[2o+t-3]):
        H: x row 2j+t-3 = 2(j+β)+r → t = 2β+r+3, β ∈ {-2..1} → 4 taps,
        pad (2, 1).  W: with o = 2J+u (u ∈ {0,1} emitted as channels) and
        x col 4(J+β)+r → t = 4β+r-2u+3, β ∈ {-1..1} → 3 taps, pad (1, 1).
        Invalid t gathers a zero row (index 7 of the zero-padded kernel).
        """
        b, h, w, c_in = x.shape
        f = self.features
        x = x.reshape(b, h // 2, 2, w, c_in)
        x = x.transpose(0, 1, 3, 2, 4)  # the one real data movement
        x = x.reshape(b, h // 2, w // 4, 8 * c_in)  # (p_w, p_h, c): free
        dy = jnp.arange(4)
        rh = jnp.arange(2)
        t_h = 2 * (dy[:, None] - 2) + rh[None, :] + 3  # (dy, rh)
        dx = jnp.arange(3)
        rw = jnp.arange(4)
        u = jnp.arange(2)
        t_w = (
            4 * (dx[:, None, None] - 1)
            + rw[None, :, None]
            - 2 * u[None, None, :]
            + 3
        )  # (dx, rw, u)
        t_h = jnp.where((t_h >= 0) & (t_h <= 6), t_h, 7)
        t_w = jnp.where((t_w >= 0) & (t_w <= 6), t_w, 7)
        kp = jnp.pad(kernel, ((0, 1), (0, 1), (0, 0), (0, 0)))  # (8, 8, c, f)
        kg = kp[
            t_h[:, :, None, None, None], t_w[None, None, :, :, :]
        ]  # (dy, rh, dx, rw, u, c, f)
        kg = kg.transpose(0, 2, 3, 1, 5, 4, 6)  # (dy, dx, rw, rh, c, u, f)
        k2 = kg.reshape(4, 3, 8 * c_in, 2 * f)
        y = lax.conv_general_dilated(
            x,
            k2.astype(self.dtype),
            window_strides=(1, 1),
            padding=((2, 1), (1, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )  # (b, h/2, w/4, (u, f))
        if self.packed_output:
            return y
        return y.reshape(b, h // 2, w // 2, f)  # W unfold (lane retile)

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = x.astype(self.dtype)
        c_in = x.shape[-1]
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (7, 7, c_in, self.features),
            jnp.float32,
        )
        dn = ("NHWC", "HWIO", "NHWC")
        if not self.space_to_depth:
            # Symmetric (3, 3) padding — torchvision's conv1 geometry, so
            # imported pretrained weights see the exact sampling grid they
            # were trained with (XLA's SAME rule pads (2, 3) on even dims,
            # shifting every output half a tap).  Output stays ceil(d/2)
            # for every input parity.
            return lax.conv_general_dilated(
                x,
                kernel.astype(self.dtype),
                window_strides=(2, 2),
                padding=((3, 3), (3, 3)),
                dimension_numbers=dn,
            )

        b, h, w, _ = x.shape
        if h % self.block or w % self.block:
            raise ValueError(
                f"space_to_depth({self.block}) stem needs H, W divisible by "
                f"{self.block}; got {(h, w)}"
            )
        if self.block == 2 and w % 4 == 0:
            return self._h2w4(x, kernel)
        if self.packed_output:
            raise ValueError(
                "packed_output requires the h2w4 lowering "
                f"(block=2 and W % 4 == 0; got block={self.block}, W={w})"
            )
        # Input: fold block x block pixel tiles into channels.  Channel order
        # is (p_w, p_h, c) — W-slot MAJOR — because that order makes the W
        # fold a FREE reshape: only the H fold needs a real transpose.  The
        # naive (p_h, p_w, c) reshape/transpose/reshape lowered to ~3.7 ms of
        # minor-dim layout copies per b8 step (HLO copy.245/246/248, round-3
        # profile); a strided-slice+concat form measured worse still
        # (138.4 vs 131.8 ms/step).  Kernel folds below use the same order.
        s = self.block
        x = x.reshape(b, h // s, s, w, c_in)
        x = x.transpose(0, 1, 3, 2, 4)  # the one real data movement
        x = x.reshape(b, h // s, w // s, s * s * c_in)  # W fold: free
        if s == 2:
            # Kernel: pad 7→8 taps (LEADING zero), split each spatial dim
            # into (block, within-block) and fold within-block into input
            # channels in the SAME (p_w, p_h, c) order as the input fold.
            # With the torch geometry out[j] = Σ_t x[2j+t-3]·w[t]; writing
            # the x index as 2(j+β)+r gives tap u = 2β+r+4 into the zero-led
            # 8-kernel — a 4-tap block conv over β ∈ {-2..1} → padding (2, 1).
            k = jnp.pad(kernel, ((1, 0), (1, 0), (0, 0), (0, 0)))
            k = k.reshape(4, 2, 4, 2, c_in, self.features)
            k = k.transpose(0, 2, 3, 1, 4, 5).reshape(
                4, 4, 4 * c_in, self.features
            )
            return lax.conv_general_dilated(
                x,
                k.astype(self.dtype),
                window_strides=(1, 1),
                padding=((2, 1), (2, 1)),
                dimension_numbers=dn,
            )
        if s != 4:
            raise ValueError(f"space_to_depth block must be 2 or 4, got {s}")
        # 4x4 fold: each block carries TWO stride-2 outputs per spatial dim,
        # emitted as extra output channels and unfolded depth-to-space below.
        # With the torch (3, 3) padding the stride-2 conv is
        # out[i] = Σ_t w[t]·x[2i+t-3] (t = 0..6); writing i = 2j+u
        # (u ∈ {0,1} within block j) and x-index = 4(j+β)+r (β block tap,
        # r ∈ 0..3 within block) gives
        #   t = 4β + r - 2u + 3,
        # a 3-tap block conv (β ∈ {-1,0,1}, padding (1,1)) whose folded
        # kernel gathers the original tap t where valid and zero elsewhere.
        beta = jnp.arange(3) - 1  # block taps
        r = jnp.arange(4)
        u = jnp.arange(2)
        t = (4 * beta[:, None, None] + r[None, :, None]
             - 2 * u[None, None, :] + 3)  # (β, r, u)
        valid = (t >= 0) & (t <= 6)
        t = jnp.where(valid, t, 7)  # 7 = the zero-padded tap
        kp = jnp.pad(kernel, ((0, 1), (0, 1), (0, 0), (0, 0)))  # (8,8,c,f)
        # Gather → (βh, rh, uh, βw, rw, uw, c, f), then order in-channels as
        # (rw, rh, c) [matching the input fold] and out-channels as
        # (uh, uw, f) [matching the depth-to-space unfold].
        k = kp[t[:, :, :, None, None, None], t[None, None, None, :, :, :]]
        k = k.transpose(0, 3, 4, 1, 6, 2, 5, 7).reshape(
            3, 3, 16 * c_in, 4 * self.features
        )
        y = lax.conv_general_dilated(
            x,
            k.astype(self.dtype),
            window_strides=(1, 1),
            padding=((1, 1), (1, 1)),
            dimension_numbers=dn,
        )
        # Depth-to-space: (B, h/4, w/4, (uh, uw, f)) → (B, h/2, w/2, f).
        bh, bw = h // 4, w // 4
        y = y.reshape(b, bh, bw, 2, 2, self.features)
        y = y.transpose(0, 1, 3, 2, 4, 5).reshape(
            b, 2 * bh, 2 * bw, self.features
        )
        return y


# --- Width-packing: run narrow-channel stages with W-pairs folded into
# channels ------------------------------------------------------------------
#
# Stage2's C=64 contractions under-fill the v5e MXU's 128 lanes on BOTH
# matmul sides (profiled ~30 TFLOP/s vs ~188 for the 256-channel heads —
# PARITY.md attribution table; the single worst slice of the step at
# ~23 ms).  Folding each pair of adjacent W positions into channels makes
# every stage2 tensor 128-channel and every conv a 128x128-block
# contraction: kernels become block-structured (1x1 -> block-diagonal over
# the two W slots; 3x3 -> a 3-tap conv over packed columns whose taps
# gather the original taps, half the blocks structurally zero).  The
# hardware does 2x the MACs (the zero blocks) at ~4x the lane occupancy.
# MEASURED NEGATIVE end-to-end on v5e at the flagship bucket (58.3 vs
# 60.7 imgs/s, b8): profiling shows stage2 is mostly HBM-bandwidth-bound
# (~513 GB/s on 11.9 GB/step), so the extra MACs outweigh the occupancy
# win; only its three fwd 3x3 convs (~2.5 ms at 48 TF/s) are lane-bound.
# Kept OFF by default as an exact, tested reformulation (PARITY.md r3).
# Math is IDENTICAL: same sums, reordered; params keep their canonical
# shapes, so checkpoints/imports are layout-independent.
#
# Packed channel order is (c, u) — logical channel MAJOR, w-slot minor — so
# GroupNorm's contiguous channel groups stay contiguous after packing and
# per-channel affines broadcast with a plain reshape.


def _pack_w(x: jnp.ndarray) -> jnp.ndarray:
    """(B, H, W, C) → (B, H, W/2, 2C), packed channel index = c*2 + u."""
    b, h, w, c = x.shape
    return (
        x.reshape(b, h, w // 2, 2, c)
        .transpose(0, 1, 2, 4, 3)
        .reshape(b, h, w // 2, 2 * c)
    )


def _unpack_w(x: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`_pack_w`."""
    b, h, wp, c2 = x.shape
    c = c2 // 2
    return (
        x.reshape(b, h, wp, c, 2).transpose(0, 1, 2, 4, 3).reshape(b, h, 2 * wp, c)
    )


def _pack_kernel_1x1(k: jnp.ndarray) -> jnp.ndarray:
    """(1, 1, ci, co) → (1, 1, 2ci, 2co) block-diagonal over the w slot."""
    cin, cout = k.shape[2], k.shape[3]
    eye = jnp.eye(2, dtype=k.dtype)
    kp = k[:, :, :, None, :, None] * eye[None, None, None, :, None, :]
    return kp.reshape(1, 1, 2 * cin, 2 * cout)


def _pack_kernel_3x3(k: jnp.ndarray) -> jnp.ndarray:
    """(3, 3, ci, co) → (3, 3, 2ci, 2co) packed-column taps.

    Output w index 2j+u reads input 2j+u+dw = 2(j+β)+r, so packed tap β
    carries original tap dw = 2β + r - u where that lands in {-1, 0, 1}
    and zero elsewhere (gathered via a zero-padded 4th tap).
    """
    cin, cout = k.shape[2], k.shape[3]
    beta = jnp.arange(3) - 1
    r = jnp.arange(2)
    u = jnp.arange(2)
    t = 2 * beta[:, None, None] + r[None, :, None] - u[None, None, :] + 1
    tw = jnp.where((t >= 0) & (t <= 2), t, 3)  # (β, r, u); 3 = zero tap
    kpad = jnp.pad(k, ((0, 0), (0, 1), (0, 0), (0, 0)))  # (3, 4, ci, co)
    kp = kpad[:, tw]  # (dh, β, r, u, ci, co)
    kp = kp.transpose(0, 1, 4, 2, 5, 3)  # (dh, β, ci, r, co, u)
    return kp.reshape(3, 3, 2 * cin, 2 * cout)


class PackedConv(nn.Module):
    """Stride-1 conv on the width-packed layout; canonical param shape.

    Declares ``kernel`` as the logical (k, k, cin, cout) — identical tree
    to ``nn.Conv`` — and runs the packed-block equivalent; the kernel
    repack is a few-KB gather XLA folds into weight preprocessing.
    """

    features: int
    kernel_size: int  # 1 or 3
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cin = x.shape[-1] // 2
        k = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (self.kernel_size, self.kernel_size, cin, self.features),
            jnp.float32,
        )
        if self.kernel_size == 1:
            kp, pad = _pack_kernel_1x1(k), (0, 0)
        elif self.kernel_size == 3:
            kp, pad = _pack_kernel_3x3(k), (1, 1)
        else:
            raise ValueError(f"PackedConv supports k in (1, 3), got {self.kernel_size}")
        return lax.conv_general_dilated(
            x,
            kp.astype(self.dtype),
            window_strides=(1, 1),
            padding=(pad, pad),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )


class PackedGroupNorm(nn.Module):
    """GroupNorm(32) on the packed layout, exact w.r.t. the unpacked op.

    Stats for a logical-channel group must pool BOTH w slots of its
    channels.  ``slot_major`` selects the packing order: False = (c, u)
    channel-major (the pack_width stage layout), True = (u, c) slot-major
    (the h2w4 packed stem layout) — same math, different unpack reshape.
    Params are the logical (C,) scale/bias — same tree as ``nn.GroupNorm``.
    """

    num_groups: int = 32
    epsilon: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16
    slot_major: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        b, h, wp, c2 = x.shape
        c = c2 // 2
        scale = self.param("scale", nn.initializers.ones_init(), (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros_init(), (c,), jnp.float32)
        g = self.num_groups
        xf = x.astype(jnp.float32)
        if self.slot_major:
            xf = xf.reshape(b, h, wp, 2, g, c // g)
            pool_axes, aff = (1, 2, 3, 5), (1, 1, 1, 1, g, c // g)
        else:
            xf = xf.reshape(b, h, wp, g, c // g, 2)
            pool_axes, aff = (1, 2, 4, 5), (1, 1, 1, g, c // g, 1)
        mean = xf.mean(axis=pool_axes, keepdims=True)
        # use_fast_variance formula, as flax GroupNorm computes it.
        var = (xf * xf).mean(axis=pool_axes, keepdims=True) - mean * mean
        y = (xf - mean) * lax.rsqrt(var + self.epsilon)
        y = y * scale.reshape(aff) + bias.reshape(aff)
        return y.reshape(b, h, wp, c2).astype(self.dtype)


class PackedBatchNorm(nn.Module):
    """BatchNorm on the packed layout; same variable tree as ``nn.BatchNorm``.

    Batch statistics pool over (B, H, Wp, slot) — exactly the unpacked
    (B, H, W) reduction.  ``use_running_average`` covers both frozen_bn
    (always) and plain bn at eval; train-mode bn updates the running stats
    with the same 0.9 momentum as the unpacked layer.  ``slot_major`` as
    in :class:`PackedGroupNorm`.
    """

    use_running_average: bool
    momentum: float = 0.9
    epsilon: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16
    slot_major: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        b, h, wp, c2 = x.shape
        c = c2 // 2
        scale = self.param("scale", nn.initializers.ones_init(), (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros_init(), (c,), jnp.float32)
        ra_mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros((c,), jnp.float32)
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda: jnp.ones((c,), jnp.float32)
        )
        xf = x.astype(jnp.float32)
        if self.slot_major:
            xf = xf.reshape(b, h, wp, 2, c)
            pool_axes, chan = (0, 1, 2, 3), slice(None)
        else:
            xf = xf.reshape(b, h, wp, c, 2)
            pool_axes, chan = (0, 1, 2, 4), (slice(None), None)
        if self.use_running_average:
            mean, var = ra_mean.value, ra_var.value
        else:
            mean = xf.mean(axis=pool_axes)
            var = (xf * xf).mean(axis=pool_axes) - mean * mean
            if not self.is_initializing():
                ra_mean.value = (
                    self.momentum * ra_mean.value + (1 - self.momentum) * mean
                )
                ra_var.value = (
                    self.momentum * ra_var.value + (1 - self.momentum) * var
                )
        y = (xf - mean[chan]) * lax.rsqrt(var[chan] + self.epsilon)
        y = y * scale[chan] + bias[chan]
        return y.reshape(b, h, wp, c2).astype(self.dtype)


# --- Packed-stem maxpool ----------------------------------------------------
#
# The h2w4 stem emits (B, H/2, W/4, (u, f)) with the W slot u MAJOR (that is
# what makes its kernel fold free); PackedGroupNorm/PackedBatchNorm handle
# that order via slot_major=True, and maxpool_packed_w consumes the packed
# layout directly so the 128->64 lane retile of an explicit unfold never
# happens.


def maxpool_packed_w(x: jnp.ndarray) -> jnp.ndarray:
    """3x3/s2 maxpool with (1, 1) -inf padding, consuming the u-major
    packed stem layout and emitting the UNPACKED pooled tensor.

    H first: a native 3x1/s2 reduce_window on the packed tensor (its VJP
    is the efficient 1-D select_and_scatter).  Then W on the QUARTER-SIZE
    result: logical cols w = 2J + u, and pooled col o reads w in
    {2o-1, 2o, 2o+1} = (J=o-1, u=1), (J=o, u=0), (J=o, u=1) — two channel
    halves plus one shifted slice (lax.pad with a negative edge), pure
    lane ops.  Forward matches
    ``nn.max_pool(x_unfolded, (3, 3), (2, 2), ((1, 1), (1, 1)))`` exactly
    (pinned by a unit test).

    Backward is plain autodiff: first-max rows along H, JAX's half/half
    tie split along W — a deliberate, documented subgradient divergence
    from the 2-D select_and_scatter's row-major first-max (ties only;
    both are valid, deterministic, and identical across shards).  The
    exact-routing custom VJP was measured SLOWER either way it was
    decomposed (W-first: ~4 ms/step of select traffic at full height);
    this H-first form measured 6.2 ms vs 6.6 for the unpacked
    nn.max_pool fwd+bwd in isolation at the flagship bucket.
    """
    y = lax.reduce_window(
        x,
        -jnp.inf,
        lax.max,
        (1, 3, 1, 1),
        (1, 2, 1, 1),
        ((0, 0), (1, 1), (0, 0), (0, 0)),
    )  # (b, h/2 -> h/4 rows, w4, (u, f))
    f = y.shape[-1] // 2
    u0 = y[..., :f]
    u1 = y[..., f:]
    # Shift right one block: u1_left[J] = u1[J-1], -inf into the new col.
    u1_left = lax.pad(
        u1,
        jnp.asarray(-jnp.inf, y.dtype),
        ((0, 0, 0), (0, 0, 0), (1, -1, 0), (0, 0, 0)),
    )
    return jnp.maximum(jnp.maximum(u1_left, u0), u1)


class NormFactory:
    """Builds the configured norm layer; see module docstring for options."""

    def __init__(self, kind: str, dtype: jnp.dtype):
        if kind not in ("gn", "bn", "frozen_bn"):
            raise ValueError(f"unknown norm kind: {kind!r}")
        self.kind = kind
        self.dtype = dtype

    def __call__(self, name: str, train: bool) -> Callable:
        if self.kind == "gn":
            return nn.GroupNorm(
                num_groups=32, dtype=self.dtype, name=name, param_dtype=jnp.float32
            )
        use_running = (self.kind == "frozen_bn") or (not train)
        return nn.BatchNorm(
            use_running_average=use_running,
            momentum=0.9,
            dtype=self.dtype,
            param_dtype=jnp.float32,
            name=name,
        )

    def packed(self, name: str, train: bool, slot_major: bool = False) -> Callable:
        """The same norm, applied on a width-packed layout (same params)."""
        if self.kind == "gn":
            return PackedGroupNorm(
                dtype=self.dtype, slot_major=slot_major, name=name
            )
        use_running = (self.kind == "frozen_bn") or (not train)
        return PackedBatchNorm(
            use_running_average=use_running,
            dtype=self.dtype,
            slot_major=slot_major,
            name=name,
        )


class BottleneckBlock(nn.Module):
    """1x1 → 3x3(stride) → 1x1(x4) with projection shortcut on shape change."""

    filters: int
    stride: int
    norm: NormFactory
    dtype: jnp.dtype = jnp.bfloat16
    packed: bool = False  # width-packed layout (stride must be 1)

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False) -> jnp.ndarray:
        # Symmetric (k//2) padding, torchvision's geometry: identical to
        # SAME for stride 1, but for stride 2 on even dims SAME pads (0, 1)
        # — a one-pixel grid shift that would misalign imported pretrained
        # features.  Output sizes are ceil(d/s) either way.
        if self.packed:
            if self.stride != 1:
                raise ValueError("packed bottleneck blocks require stride 1")
            conv = lambda f, k, s, name: PackedConv(  # noqa: E731
                features=f, kernel_size=k, dtype=self.dtype, name=name
            )
            norm_for = lambda name: self.norm.packed(name, train)  # noqa: E731
        else:
            conv = lambda f, k, s, name: nn.Conv(  # noqa: E731
                f,
                (k, k),
                strides=(s, s),
                padding=((k // 2, k // 2), (k // 2, k // 2)),
                use_bias=False,
                dtype=self.dtype,
                param_dtype=jnp.float32,
                name=name,
            )
            norm_for = lambda name: self.norm(name, train)  # noqa: E731
        residual = x
        y = conv(self.filters, 1, 1, "conv1")(x)
        y = norm_for("norm1")(y)
        y = nn.relu(y)
        y = conv(self.filters, 3, self.stride, "conv2")(y)
        y = norm_for("norm2")(y)
        y = nn.relu(y)
        y = conv(self.filters * 4, 1, 1, "conv3")(y)
        y = norm_for("norm3")(y)
        if residual.shape != y.shape:
            residual = conv(self.filters * 4, 1, self.stride, "proj")(x)
            residual = norm_for("proj_norm")(residual)
        return nn.relu(y + residual)


class ResNet(nn.Module):
    """ResNet exposing {"c3", "c4", "c5"} (strides 8/16/32)."""

    stage_sizes: Sequence[int]
    norm_kind: str = "gn"
    dtype: jnp.dtype = jnp.bfloat16
    stem: str = "conv"  # "conv" | "space_to_depth" | "space_to_depth4"
    # Run stage2 (the C=64 stage — PARITY.md's worst MXU slice) with W-pairs
    # packed into channels; math-identical, same param tree (see the
    # width-packing block above).  Needs stage2 width (ceil(W_img/4)) even.
    pack_width: bool = False
    # Stem downsample: "max" is the canonical 3x3/2 maxpool.  "avg" swaps in
    # an avg pool of the same geometry — a DIAGNOSTIC configuration whose
    # gradient is linear and therefore tie-free: maxpool backward routes
    # each window's cotangent to its first max, and which element wins a
    # tie is partition-dependent under GSPMD spatial sharding
    # (tests/distributed/test_spatial_train.py uses this knob to prove the
    # spatial step's gradient divergence lives ENTIRELY in the pool).
    stem_pool: str = "max"  # "max" | "avg"

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False) -> dict[str, jnp.ndarray]:
        if self.stem not in ("conv", "space_to_depth", "space_to_depth4"):
            raise ValueError(f"unknown stem: {self.stem!r}")
        if self.stem_pool not in ("max", "avg"):
            raise ValueError(f"unknown stem_pool: {self.stem_pool!r}")
        if self.stem_pool == "avg" and self.stem != "conv":
            raise ValueError(
                "stem_pool='avg' (the tie-free diagnostic pool) is only "
                "supported with stem='conv' — the packed stem layouts bake "
                "in the maxpool (maxpool_packed_w)"
            )
        norm = NormFactory(self.norm_kind, self.dtype)
        x = x.astype(self.dtype)
        # The h2w4 stem lowering keeps its output packed (B, H/2, W/4,
        # (u, f)) and norm/relu/maxpool consume that layout: unfolding
        # first costs a 128->64 lane retile XLA pays as ~4 full copies
        # fwd+bwd (~5 ms/step profiled at the flagship bucket).
        packed_stem = (
            self.stem == "space_to_depth"
            and x.shape[1] % 2 == 0
            and x.shape[2] % 4 == 0
        )
        # named_scope "stem", "stage2".."stage5": the backbone's slices in a
        # device trace read by scope (train/step.py::STEP_SCOPES).
        with jax.named_scope("stem"):
            x = StemConv(
                features=64,
                space_to_depth=self.stem != "conv",
                block=4 if self.stem == "space_to_depth4" else 2,
                dtype=self.dtype,
                packed_output=packed_stem,
                name="stem_conv",
            )(x)
            if packed_stem:
                x = norm.packed("stem_norm", train, slot_major=True)(x)
                x = nn.relu(x)
                x = maxpool_packed_w(x)
            else:
                x = norm("stem_norm", train)(x)
                x = nn.relu(x)
                if self.stem_pool == "avg":
                    # Tie-free diagnostic downsample (see stem_pool field doc).
                    x = nn.avg_pool(
                        x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1))
                    )
                else:
                    # Symmetric (1, 1) padding (torch geometry; SAME would pad
                    # (0, 1) on even dims).  -inf pad so padding never wins the
                    # max.
                    x = nn.max_pool(
                        x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1))
                    )

        features: dict[str, jnp.ndarray] = {}
        filters = 64
        for stage, num_blocks in enumerate(self.stage_sizes):
            stride = 1 if stage == 0 else 2
            packed = self.pack_width and stage == 0  # all-stride-1 C=64 stage
            if packed:
                if x.shape[2] % 2:
                    raise ValueError(
                        f"pack_width needs an even stage2 width; got "
                        f"{x.shape[2]} (make W divisible by 8)"
                    )
                x = _pack_w(x)
            with jax.named_scope(f"stage{stage + 2}"):
                for block in range(num_blocks):
                    x = BottleneckBlock(
                        filters=filters,
                        stride=stride if block == 0 else 1,
                        norm=norm,
                        dtype=self.dtype,
                        packed=packed,
                        name=f"stage{stage + 2}_block{block}",
                    )(x, train=train)
            if packed:
                x = _unpack_w(x)
            if stage >= 1:  # C3 at stride 8, C4 at 16, C5 at 32
                features[f"c{stage + 2}"] = x
            filters *= 2
        return features


def resnet50(
    norm_kind: str = "gn",
    dtype: jnp.dtype = jnp.bfloat16,
    stem: str = "conv",
    pack_width: bool = False,
) -> ResNet:
    return ResNet(
        stage_sizes=(3, 4, 6, 3),
        norm_kind=norm_kind,
        dtype=dtype,
        stem=stem,
        pack_width=pack_width,
    )

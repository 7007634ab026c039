"""Bucketed, error-feedback gradient compression (the comm/ data plane).

The wire scheme per bucket is the EQuARX-style two-phase decomposition
``parallel/quantize.py`` proved out (reduce in exact f32, compress only
the phase that can take it), upgraded three ways (ISSUE 13):

1. **Bucketing** — leaves are packed per schedule stage into flat
   buckets of ``CommConfig.bucket_mb`` so many small leaves share ONE
   collective and one scale vector.  The old per-leaf
   ``_MIN_QUANTIZE_SIZE`` blind spot (biases/norm scales skipped
   per-leaf, paying exact bytes AND per-leaf collective latency) is
   subsumed: small leaves ride inside full buckets; only a bucket whose
   TOTAL payload is under ``min_bucket_bytes`` stays exact.
2. **Error feedback** — device ``i`` owns the reduced shard it
   quantizes, so it also owns the rounding error it introduced:
   ``residual = shard - dequant(quant(shard))`` is carried in
   ``TrainState.comm_state`` (a flat ``(n * chunk,)`` array per bucket,
   sharded over the data axis exactly like ZeRO optimizer state — same
   padding-is-zeros invariant, same ``reshard_flat_leaf`` elasticity)
   and added back before the next quantize.  The telescoping identity
   ``sum(applied) + residual_T == sum(exact)`` makes the scheme
   unbiased-in-expectation instead of one-step-biased.
3. **Health** — every reduce returns the local EF residual and the
   count of saturated (|q| == 127) elements, which the train step turns
   into the ``ef_residual_norm`` / ``ef_saturation`` /
   ``comm_compressed_bytes`` metrics (obs gauges + the always-armed
   ``ef_residual_spike`` SLO rule).

Two collective layouts share the per-bucket quantizer:

- ``reduce_tree`` — the DP path: per bucket, ``psum_scatter`` in f32
  (summation precision untouched), EF add-back, per-block int8/bf16
  quantize of the reduced shard, compressed ``all_gather``.  Every
  device dequantizes the same gathered bytes, so the update stays
  bitwise replicated.  Handed a ``parallel.mesh.CommTopology`` the
  tree becomes HIERARCHICAL (ISSUE 16): exact f32 reduce-scatter
  within each ICI slice, then the quantized exchange ONLY on the
  cross-slice DCN hop (reduce-scatter exact, gather compressed, EF
  residual keyed per hop — ``"<stage>.<index>@dcn"``), then an exact
  intra-slice all-gather.  Compression pays exactly where bandwidth is
  scarce; the ICI hops carry zero quantized bytes.  When both hops
  share one mode (or the topology is a single slice) the hierarchy
  degenerates and callers compile the FLAT tree — byte-identical HLO,
  pinned by tests.
- ``zero_gather_updates`` — the ZeRO path: the gradient reduce-scatter
  stays exact per-leaf (it feeds the sharded optimizer), and
  compression moves to the OTHER half of the traffic, the
  param-all-gather: each device quantizes its optimizer UPDATE shard
  (with per-leaf EF residuals in the ZeRO flat layout), gathers int8,
  and every device applies the identical dequantized update to its
  replicated params.  Gathering the *update* instead of the params is
  what lifts the old "quantizing the gather would bias the model"
  exclusivity: an update is a gradient-like increment, exactly what EF
  makes unbiased.

Non-finite gradients must SURFACE, not launder: a non-finite block
poisons its gathered scale to NaN (the ``parallel/quantize.py``
contract), so the loop's finite-check aborts exactly as on the exact
path.

House rules: everything here is jit-pure (pure jnp + named-axis
collectives, no clocks/IO); the collectives are unconditional — the
collective-safety lint rule knows these wrapper names (``reduce_tree``,
``zero_gather_updates``, ``bucketed_pmean``,
``reduce_bucket_hierarchical``) as collective call sites.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from batchai_retinanet_horovod_coco_tpu.comm.config import (
    CommConfig,
    stage_of,
)
from batchai_retinanet_horovod_coco_tpu.parallel.mesh import DATA_AXIS
from batchai_retinanet_horovod_coco_tpu.parallel.zero import _pad_flat


# ---------------------------------------------------------------------------
# The plan: a deterministic, n-independent bucketing of a gradient tree
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BucketLeaf:
    path: str  # jax.tree_util.keystr of the full-tree path
    offset: int  # element offset within the bucket's logical flat
    size: int
    shape: tuple
    dtype: str


@dataclasses.dataclass(frozen=True)
class Bucket:
    stage: str
    index: int
    mode: str  # "exact" | "int8" | "bf16"
    leaves: tuple  # of BucketLeaf
    size: int  # total logical elements

    @property
    def key(self) -> str:
        return f"{self.stage}.{self.index}"


@dataclasses.dataclass(frozen=True)
class CommPlan:
    buckets: tuple  # of Bucket, stage-major in backward-completion order
    config: CommConfig

    def stage_buckets(self, stage: str) -> tuple:
        return tuple(b for b in self.buckets if b.stage == stage)

    @property
    def stages(self) -> tuple:
        seen = []
        for b in self.buckets:
            if b.stage not in seen:
                seen.append(b.stage)
        return tuple(seen)

    # ---- static wire accounting (per-device bytes sent, ring model) ----

    def _chunk(self, size: int, n: int) -> int:
        return -(-size // n)

    def _blocks(self, size: int, n: int) -> int:
        return -(-self._chunk(size, n) // self.config.block)

    def exact_bytes(self, n: int) -> int:
        """Per-device ring bytes of the uncompressed schedule: one f32
        all-reduce (reduce-scatter + all-gather) per bucket."""
        f = (n - 1) / max(n, 1)
        return int(sum(2 * f * 4 * b.size for b in self.buckets))

    def compressed_bytes(self, n: int) -> int:
        """Per-device ring bytes under this plan: exact f32
        reduce-scatter + compressed gather (int8 payload + one f32
        scale per block; bf16 payload; exact buckets unchanged)."""
        f = (n - 1) / max(n, 1)
        total = 0.0
        for b in self.buckets:
            rs = f * 4 * b.size
            if b.mode == "int8":
                gather = f * (b.size + 4 * n * self._blocks(b.size, n))
            elif b.mode == "bf16":
                gather = f * 2 * b.size
            else:
                gather = f * 4 * b.size
            total += rs + gather
        return int(total)

    def quant_elems(
        self, n: int, zero: bool = False, topology=None
    ) -> int:
        """Per-device INT8-quantized elements (the saturation
        denominator).  bf16 buckets are excluded — they can never
        saturate (no clip boundary), and counting them would dilute the
        gauge under mixed stage_modes.

        DP layout: one padded chunk per bucket.  ZeRO layout
        (``zero=True``): the quantized local vector is the concat of
        PER-LEAF padded chunks, which is larger whenever leaf sizes
        don't divide ``n`` — the denominator must match or the
        ``ef_saturation`` gauge over-reports on ZeRO runs.
        Hierarchical layout (``topology``): the quantized shard is the
        DCN-hop chunk (double-padded: first to the slice, then across
        slices)."""
        total = 0
        for b in self.buckets:
            if b.mode != "int8":
                continue
            if zero:
                total += sum(self._chunk(l.size, n) for l in b.leaves)
            elif topology is not None:
                total += self._hier_chunk(b.size, topology)
            else:
                total += self._chunk(b.size, n)
        return total

    # ---- per-hop accounting (the hierarchical tree, ISSUE 16) ----

    def _hier_chunk(self, size: int, topology) -> int:
        """Final per-device chunk of the hierarchical tree: the bucket
        pads to the slice count first (ICI tile), then that tile pads
        across slices (DCN tile)."""
        return self._chunk(
            self._chunk(size, topology.slice_size), topology.num_slices
        )

    def _hop_bucket_bytes(self, mode: str, size: int, topology) -> dict:
        """Per-device ring bytes of ONE bucket through the hierarchical
        tree, split by fabric.  The tree is: ICI reduce-scatter (f32),
        DCN reduce-scatter (f32) + gather (``mode``), ICI all-gather
        (f32).  ``mode == "exact"`` is also the model of a flat
        all-reduce routed hierarchically — the reference the DCN ratio
        is stated against."""
        S, L = topology.num_slices, topology.slice_size
        fi = (L - 1) / max(L, 1)
        fd = (S - 1) / max(S, 1)
        tile = size / max(L, 1)  # the per-slice ICI tile the DCN hop moves
        ici = fi * 4 * size * 2  # reduce-scatter + all-gather, both f32
        dcn_rs = fd * 4 * tile
        if mode == "int8":
            chunk = self._hier_chunk(size, topology)
            blocks = -(-chunk // self.config.block)
            dcn_gather = fd * (tile + 4 * S * blocks)
        elif mode == "bf16":
            dcn_gather = fd * 2 * tile
        else:
            dcn_gather = fd * 4 * tile
        return {"ici": ici, "dcn": dcn_rs + dcn_gather}

    def hop_bytes(self, topology) -> dict:
        """Per-device ring bytes under this plan's modes, split per
        fabric hop: ``{"ici": ..., "dcn": ...}``.  Exact buckets route
        hierarchically too (same tree, f32 gather) so the split is
        comparable across modes."""
        out = {"ici": 0.0, "dcn": 0.0}
        for b in self.buckets:
            bb = self._hop_bucket_bytes(b.mode, b.size, topology)
            out["ici"] += bb["ici"]
            out["dcn"] += bb["dcn"]
        return {k: int(v) for k, v in out.items()}

    def hop_bytes_exact(self, topology) -> dict:
        """Per-device ring bytes of the all-exact hierarchical tree —
        the denominator of the per-hop compression ratio."""
        out = {"ici": 0.0, "dcn": 0.0}
        for b in self.buckets:
            bb = self._hop_bucket_bytes("exact", b.size, topology)
            out["ici"] += bb["ici"]
            out["dcn"] += bb["dcn"]
        return {k: int(v) for k, v in out.items()}

    def hop_quant_bytes(self, topology) -> dict:
        """Per-device QUANTIZED payload bytes per hop.  The ICI hops
        are exact f32 by construction, so ``"ici"`` is identically 0."""
        S = topology.num_slices
        fd = (S - 1) / max(S, 1)
        dcn = 0.0
        for b in self.buckets:
            chunk = self._hier_chunk(b.size, topology)
            if b.mode == "int8":
                blocks = -(-chunk // self.config.block)
                dcn += fd * S * (chunk + 4 * blocks)
            elif b.mode == "bf16":
                dcn += fd * S * 2 * chunk
        return {"ici": 0, "dcn": int(dcn)}


def _flatten_float_leaves(tree: Any) -> list:
    """(keystr path, top-level key, leaf) for float leaves, flatten order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        dtype = getattr(leaf, "dtype", None) or np.asarray(leaf).dtype
        if not jnp.issubdtype(dtype, jnp.floating):
            continue
        top = ""
        if path and hasattr(path[0], "key"):
            top = str(path[0].key)
        out.append((jax.tree_util.keystr(path), top, leaf))
    return out


def plan_buckets(
    tree: Any, config: CommConfig, topology=None
) -> CommPlan:
    """Deterministic bucketing of a gradient/update tree.

    Leaves group by schedule stage (``stage_of`` on the top-level key),
    keep tree-flatten order within a stage, and pack greedily into
    buckets of at most ``bucket_mb``.  The assignment depends only on
    the tree structure and the config — NOT on the mesh size — so EF
    state saved at world N reshards to world M with the bucket
    composition unchanged (the checkpoint-elasticity requirement).
    Non-float leaves are excluded (they take the exact per-leaf path).

    With an ENGAGED hierarchical ``topology``
    (``config.hierarchical_with``): bucket capacity comes from
    ``dcn_bucket_mb`` (sized for the slow hop) and the bucket mode is
    the stage's DCN mode — the only hop that compresses.  The slice
    count does not influence composition, so the plan stays
    world-size-independent within one policy.
    """
    hier = config.hierarchical_with(topology)
    by_stage: dict[str, list] = {}
    for path, top, leaf in _flatten_float_leaves(tree):
        by_stage.setdefault(stage_of(top), []).append((path, leaf))
    buckets: list[Bucket] = []
    # Backward-completion order: heads first, backbone last (STAGES
    # reversed) — the order overlap issues collectives in.
    stage_order = [s for s in ("heads", "fpn", "backbone") if s in by_stage]
    cap = config.dcn_bucket_elems if hier else config.bucket_elems
    for stage in stage_order:
        pending: list[BucketLeaf] = []
        total = 0
        index = 0

        def flush():
            nonlocal pending, total, index
            if not pending:
                return
            mode = config.mode_for_stage(
                stage, config.effective_dcn_mode if hier else None
            )
            if mode == "none":
                # "none" (overlap-without-compression, or a per-stage
                # opt-out) means EXACT wire format — it must never fall
                # through to the quantizer.
                mode = "exact"
            if total * 4 < config.min_bucket_bytes:
                mode = "exact"  # wire saving is noise below this
            buckets.append(
                Bucket(
                    stage=stage, index=index, mode=mode,
                    leaves=tuple(pending), size=total,
                )
            )
            pending, total = [], 0
            index += 1

        for path, leaf in by_stage[stage]:
            size = int(np.prod(np.shape(leaf))) if np.shape(leaf) else 1
            if total and total + size > cap:
                flush()
            pending.append(
                BucketLeaf(
                    path=path, offset=total, size=size,
                    shape=tuple(int(d) for d in np.shape(leaf)),
                    dtype=str(np.dtype(getattr(leaf, "dtype", np.float32))),
                )
            )
            total += size
        flush()
    return CommPlan(buckets=tuple(buckets), config=config)


# ---------------------------------------------------------------------------
# EF state: init / partition specs (the opt_state-adjacent comm state)
# ---------------------------------------------------------------------------


def _padded_total(size: int, n: int) -> int:
    return n * (-(-size // n))


def bucket_state_key(bucket: Bucket, topology=None) -> str:
    """EF-state key of a bucket: ``"<stage>.<index>"`` on the flat
    tree, ``"<stage>.<index>@dcn"`` on the hierarchical tree — the
    residual lives on the hop that quantizes, and keying it per hop
    keeps a policy flip (flat <-> hierarchical) an explicit layout
    change (checkpoint ``ef_reset``) instead of a silent misread."""
    return bucket.key if topology is None else f"{bucket.key}@dcn"


def init_comm_state(
    params: Any,
    config: CommConfig,
    n: int,
    zero: bool = False,
    topology=None,
) -> dict:
    """Host-side zero EF state for ``params`` under ``config`` at world
    ``n``.  DP layout (``zero=False``): one flat ``(n * chunk,)`` f32
    residual per compressed bucket, keyed ``"<stage>.<index>"``.  ZeRO
    layout (``zero=True``): one flat residual per LEAF in the exact
    ZeRO storage layout (``(n * ceil(size/n),)``), keyed by the leaf's
    tree path — bucket composition then never constrains resharding.
    Hierarchical layout (an engaged ``topology``): one flat
    ``(n * hier_chunk,)`` residual per compressed bucket, keyed
    ``"<stage>.<index>@dcn"`` — thanks to the interleaved mesh
    convention (``parallel.mesh.CommTopology``) the array is in global
    bucket order with zero padding, so ``reshard_flat_leaf`` elasticity
    holds across world-size changes exactly like the flat layout.
    Empty dict when the policy carries no state."""
    if zero:
        topology = None  # the ZeRO update gather stays flat (ISSUE 16)
    hier = config.hierarchical_with(topology)
    if not hier:
        config = config.flat_equivalent(topology)
        topology = None
    if not config.needs_state:
        return {}
    plan = plan_buckets(params, config, topology)
    out: dict[str, np.ndarray] = {}
    for bucket in plan.buckets:
        if bucket.mode == "exact":
            continue
        if zero:
            for leaf in bucket.leaves:
                out[leaf.path] = np.zeros(
                    (_padded_total(leaf.size, n),), np.float32
                )
        elif topology is not None:
            chunk = plan._hier_chunk(bucket.size, topology)
            out[bucket_state_key(bucket, topology)] = np.zeros(
                (n * chunk,), np.float32
            )
        else:
            out[bucket.key] = np.zeros(
                (_padded_total(bucket.size, n),), np.float32
            )
    return out


def state_partition_specs(comm_state: Any) -> Any:
    """PartitionSpec tree for comm state: every residual is a flat array
    sharded on the data axis (device ``i`` owns the residual of the
    shard it quantizes); mirrors ``zero.opt_state_partition_specs``."""
    from jax.sharding import PartitionSpec as P

    return jax.tree.map(
        lambda l: P(DATA_AXIS) if getattr(l, "ndim", 0) >= 1 else P(),
        comm_state,
    )


# ---------------------------------------------------------------------------
# The per-bucket quantizer (shared by both collective layouts)
# ---------------------------------------------------------------------------


def _quantize_shard(
    shard: jnp.ndarray, mode: str, block: int
) -> tuple[Any, jnp.ndarray, jnp.ndarray]:
    """Quantize one reduced local shard; returns (payload, dequantized
    local shard, saturated-element count).  ``payload`` is what crosses
    the wire (int8 blocks + f32 scales, or a bf16 array)."""
    m = shard.shape[0]
    if mode == "bf16":
        q = shard.astype(jnp.bfloat16)
        deq = q.astype(jnp.float32)
        return q, deq, jnp.zeros((), jnp.float32)
    blocks = -(-m // block)
    sb = jnp.pad(shard, (0, blocks * block - m)).reshape(blocks, block)
    amax = jnp.max(jnp.abs(sb), axis=1)
    # Non-finite blocks poison their scale: the dequantized values go
    # NaN and the loop's finite-check aborts (never launder Inf into
    # finite int8 garbage — parallel/quantize.py's contract).
    scale = jnp.where(
        jnp.isfinite(amax), jnp.maximum(amax, 1e-30) / 127.0, jnp.nan
    )
    q = jnp.clip(jnp.round(sb / scale[:, None]), -127.0, 127.0).astype(
        jnp.int8
    )
    deq = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)[:m]
    sat = jnp.sum((jnp.abs(q) >= 127).astype(jnp.float32))
    return (q, scale), deq, sat


def _dequantize_gathered(payload_all, mode: str, m: int, n: int):
    """All-gathered payload → the full ``(n * m,)`` f32 flat."""
    if mode == "bf16":
        return payload_all.astype(jnp.float32).reshape(-1)
    q_all, s_all = payload_all
    blocks_block = q_all.shape[1] * q_all.shape[2]
    return (
        (q_all.astype(jnp.float32) * s_all[..., None])
        .reshape(n, blocks_block)[:, :m]
        .reshape(-1)
    )


def _reduce_bucket_flat(
    flat: jnp.ndarray,
    res: jnp.ndarray | None,
    bucket: Bucket,
    config: CommConfig,
    axis_name: str,
    n: int,
):
    """One bucket's compressed pmean (call inside shard_map).

    ``flat`` is the local (pre-reduce) logical concat of the bucket's
    leaves; ``res`` the local EF residual slice or None.  Returns
    (reduced full flat (size,), new local residual | None, sat count).
    """
    size = bucket.size
    if bucket.mode == "exact":
        return lax.pmean(flat, axis_name), res, jnp.zeros((), jnp.float32)
    padded = _pad_flat(flat, n)
    # Phase 1: exact f32 reduction — each device owns 1/n of the sum.
    shard = lax.psum_scatter(padded, axis_name, tiled=True) / n
    if res is not None:
        shard = shard + res  # EF add-back: last step's dropped rounding
    payload, deq_local, sat = _quantize_shard(
        shard, bucket.mode, config.block
    )
    new_res = (shard - deq_local) if res is not None else None
    # Phase 2: compressed gather — every device dequantizes the same
    # bytes, so the result stays bitwise replicated.
    if bucket.mode == "bf16":
        gathered = lax.all_gather(payload, axis_name)
    else:
        gathered = (
            lax.all_gather(payload[0], axis_name),
            lax.all_gather(payload[1], axis_name),
        )
    out = _dequantize_gathered(gathered, bucket.mode, shard.shape[0], n)
    return out[:size], new_res, sat


def reduce_bucket_hierarchical(
    flat: jnp.ndarray,
    res: jnp.ndarray | None,
    bucket: Bucket,
    config: CommConfig,
    axis_name: str,
    topology,
):
    """One bucket's pmean through the two-fabric hierarchical tree
    (call inside shard_map; ISSUE 16).

    Five phases, compression ONLY on the slow hop:

    1. ICI reduce-scatter (exact f32, grouped per slice): intra-slice
       rank ``r`` owns tile ``r`` of the slice-local sum;
    2. DCN reduce-scatter (exact f32, grouped per rank): slice ``s``
       owns tile ``s`` of the GLOBAL sum — with the interleaved mesh
       convention that tile is exactly ``[d * chunk, (d+1) * chunk)``
       of the bucket flat for mesh position ``d``;
    3. EF add-back + quantize of the owned chunk (``bucket.mode``);
    4. DCN all-gather of the quantized payload: every device in the
       rank group dequantizes the same bytes — the reconstructed ICI
       tile is bitwise identical across slices;
    5. ICI all-gather (exact f32) of the tiles back to the full bucket.

    Returns (reduced full flat ``(size,)``, new local DCN-hop residual
    or None, saturated-element count)."""
    size = bucket.size
    if bucket.mode == "exact":
        return lax.pmean(flat, axis_name), res, jnp.zeros((), jnp.float32)
    S, L = topology.num_slices, topology.slice_size
    n = topology.num_devices
    ici_groups = topology.ici_groups()
    dcn_groups = topology.dcn_groups()
    padded = _pad_flat(flat, L)
    tile = lax.psum_scatter(
        padded, axis_name, tiled=True, axis_index_groups=ici_groups
    )
    tile_padded = _pad_flat(tile, S)
    shard = (
        lax.psum_scatter(
            tile_padded, axis_name, tiled=True, axis_index_groups=dcn_groups
        )
        / n
    )
    if res is not None:
        shard = shard + res  # EF add-back: last step's dropped rounding
    payload, deq_local, sat = _quantize_shard(
        shard, bucket.mode, config.block
    )
    new_res = (shard - deq_local) if res is not None else None
    if bucket.mode == "bf16":
        gathered = lax.all_gather(
            payload, axis_name, axis_index_groups=dcn_groups
        )
    else:
        gathered = (
            lax.all_gather(
                payload[0], axis_name, axis_index_groups=dcn_groups
            ),
            lax.all_gather(
                payload[1], axis_name, axis_index_groups=dcn_groups
            ),
        )
    tile_out = _dequantize_gathered(
        gathered, bucket.mode, shard.shape[0], S
    )[: tile.shape[0]]
    full = lax.all_gather(
        tile_out, axis_name, tiled=True, axis_index_groups=ici_groups
    )
    return full[:size], new_res, sat


# ---------------------------------------------------------------------------
# DP path: reduce_tree (the bucketed, EF'd pmean)
# ---------------------------------------------------------------------------


def _leaf_map(tree: Any) -> tuple[dict, Any]:
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): l for p, l in flat}, (flat, treedef)


def _rebuild(tree: Any, out_map: Mapping[str, Any]) -> Any:
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    leaves = [
        out_map.get(jax.tree_util.keystr(p), l) for p, l in flat
    ]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def reduce_leaves(
    leaf_map: Mapping[str, jnp.ndarray],
    res_map: Mapping[str, jnp.ndarray],
    buckets,
    config: CommConfig,
    axis_name: str,
    n: int,
    topology=None,
):
    """Reduce the leaves of ``buckets`` (a leaf-path → local-grad map);
    the shared engine under ``reduce_tree`` and the overlap taps.
    ``topology`` non-None selects the hierarchical tree (callers pass
    it ONLY when the hierarchy actually engages — the flat fallback
    must stay byte-identical HLO).
    Returns (reduced leaf map, new residual map, saturation count)."""
    out: dict[str, jnp.ndarray] = {}
    new_res: dict[str, jnp.ndarray] = {}
    sat_total = jnp.zeros((), jnp.float32)
    for bucket in buckets:
        parts = []
        for leaf in bucket.leaves:
            g = leaf_map[leaf.path]
            parts.append(g.astype(jnp.float32).reshape(-1))
        flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        key = bucket_state_key(bucket, topology)
        res = res_map.get(key) if bucket.mode != "exact" else None
        if topology is not None:
            reduced, res_out, sat = reduce_bucket_hierarchical(
                flat, res, bucket, config, axis_name, topology
            )
        else:
            reduced, res_out, sat = _reduce_bucket_flat(
                flat, res, bucket, config, axis_name, n
            )
        sat_total = sat_total + sat
        if res_out is not None:
            new_res[key] = res_out
        for leaf in bucket.leaves:
            piece = lax.dynamic_slice(
                reduced, (leaf.offset,), (leaf.size,)
            )
            out[leaf.path] = piece.reshape(leaf.shape).astype(
                leaf_map[leaf.path].dtype
            )
    return out, new_res, sat_total


def reduce_tree(
    grads: Any,
    comm_state: Mapping[str, jnp.ndarray],
    plan: CommPlan,
    config: CommConfig,
    axis_name: str = DATA_AXIS,
    n: int = 1,
    topology=None,
):
    """Bucketed compressed pmean of a whole gradient tree (the fused,
    overlap-off path; call inside shard_map).  Non-float leaves take
    the exact per-leaf pmean.  ``topology`` non-None selects the
    hierarchical tree (see ``reduce_bucket_hierarchical``); callers
    resolve the flat fallback BEFORE tracing.  Returns (reduced tree,
    new comm state, local saturation count)."""
    leaf_map, _ = _leaf_map(grads)
    planned = {l.path for b in plan.buckets for l in b.leaves}
    out_map, new_res, sat = reduce_leaves(
        leaf_map, comm_state, plan.buckets, config, axis_name, n, topology
    )
    for path, leaf in leaf_map.items():
        if path not in planned:
            out_map[path] = lax.pmean(leaf, axis_name)
    # Preserve the comm-state STRUCTURE exactly (a key a bucket did not
    # update — e.g. EF off for that bucket — passes through unchanged),
    # so the step can replace state.comm_state wholesale.
    new_res = {k: new_res.get(k, v) for k, v in comm_state.items()}
    return _rebuild(grads, out_map), new_res, sat


def bucketed_pmean(grads: Any, axis_name: str, n: int, config=None):
    """Stateless (no-EF) bucketed compressed pmean.  Builds the plan at
    trace time from the tree itself."""
    config = config or CommConfig(compress="int8", error_feedback=False)
    plan = plan_buckets(grads, config)
    reduced, _, _ = reduce_tree(grads, {}, plan, config, axis_name, n)
    return reduced


# ---------------------------------------------------------------------------
# ZeRO path: compressed update gather
# ---------------------------------------------------------------------------


def zero_gather_updates(
    updates: Any,
    params: Any,
    comm_state: Mapping[str, jnp.ndarray],
    plan: CommPlan,
    config: CommConfig,
    axis_name: str = DATA_AXIS,
    n: int = 1,
):
    """Replace ZeRO's f32 param all-gather with a compressed UPDATE
    gather (call inside shard_map).

    ``updates`` is the optax update tree in local ZeRO shards (one
    ``(chunk_leaf,)`` slice per leaf, ``parallel/zero.sharded_update``
    layout); ``params`` the replicated full params.  Per bucket: concat
    the member leaves' update shards, EF add-back from the per-leaf
    residual slices, quantize, all-gather, and apply the identical
    dequantized full update to the replicated params.  Exact buckets
    gather in f32 (bitwise ZeRO-classic for that bucket).  Returns
    (new_params, new comm state, saturation count).
    """
    upd_map, _ = _leaf_map(updates)
    param_map, _ = _leaf_map(params)
    new_params_map: dict[str, jnp.ndarray] = {}
    new_res: dict[str, jnp.ndarray] = {}
    sat_total = jnp.zeros((), jnp.float32)
    planned = {l.path for b in plan.buckets for l in b.leaves}
    for bucket in plan.buckets:
        shards = [
            upd_map[l.path].astype(jnp.float32).reshape(-1)
            for l in bucket.leaves
        ]
        chunks = [s.shape[0] for s in shards]
        flat = shards[0] if len(shards) == 1 else jnp.concatenate(shards)
        # EF engages iff the caller's state carries EVERY member leaf's
        # residual (the step.py contract) — a stateless caller (the
        # deprecated alias, or a policy flip before init_comm_state)
        # degrades to no-EF quantization instead of a trace-time error.
        use_ef = (
            bucket.mode != "exact"
            and config.needs_state
            and all(l.path in comm_state for l in bucket.leaves)
        )
        res = None
        if use_ef:
            res_parts = [comm_state[l.path] for l in bucket.leaves]
            res = (
                res_parts[0]
                if len(res_parts) == 1
                else jnp.concatenate(res_parts)
            )
        if bucket.mode == "exact":
            gathered = lax.all_gather(flat, axis_name)  # (n, L) f32
            sat = jnp.zeros((), jnp.float32)
        else:
            if res is not None:
                flat = flat + res
            payload, deq_local, sat = _quantize_shard(
                flat, bucket.mode, config.block
            )
            if res is not None:
                res_out = flat - deq_local
                off = 0
                for leaf, c in zip(bucket.leaves, chunks):
                    new_res[leaf.path] = lax.dynamic_slice(
                        res_out, (off,), (c,)
                    )
                    off += c
            if bucket.mode == "bf16":
                gathered = lax.all_gather(payload, axis_name).astype(
                    jnp.float32
                )
            else:
                q_all = lax.all_gather(payload[0], axis_name)
                s_all = lax.all_gather(payload[1], axis_name)
                gathered = (
                    q_all.astype(jnp.float32) * s_all[..., None]
                ).reshape(n, -1)[:, : flat.shape[0]]
        sat_total = sat_total + sat
        # Reassemble each leaf's full update from its column range of
        # the gathered (n, L) matrix: full = interleave of device
        # shards in logical order (the ZeRO flat layout).
        off = 0
        for leaf, c in zip(bucket.leaves, chunks):
            cols = lax.dynamic_slice(
                gathered, (0, off), (n, c)
            ).reshape(n * c)[: leaf.size]
            p = param_map[leaf.path]
            new_params_map[leaf.path] = (
                p + cols.reshape(leaf.shape).astype(p.dtype)
            )
            off += c
    # Leaves outside the plan (non-float — none in practice) gather f32.
    for path, p in param_map.items():
        if path not in planned:
            shard = upd_map[path]
            full = lax.all_gather(shard, axis_name, tiled=True)
            new_params_map[path] = p + full[: p.size].reshape(p.shape).astype(
                p.dtype
            )
    # Structure-preserving state replacement (see reduce_tree).
    new_res = {k: new_res.get(k, v) for k, v in comm_state.items()}
    return _rebuild(params, new_params_map), new_res, sat_total


# ---------------------------------------------------------------------------
# In-step health metrics (the obs wiring)
# ---------------------------------------------------------------------------


def comm_metrics(
    plan: CommPlan,
    new_comm_state: Mapping[str, jnp.ndarray],
    sat_local: jnp.ndarray,
    axis_name: str,
    n: int,
    zero: bool = False,
    topology=None,
) -> dict[str, jnp.ndarray]:
    """EF health metrics for the step's metrics dict (call inside
    shard_map, after the reduce): global residual norm, global scale
    saturation fraction, and the plan's static bytes-on-wire.
    ``zero`` selects the ZeRO layout's saturation denominator.

    Hierarchical runs (``topology``) split the static accounting per
    hop — ``comm_ici_bytes`` / ``comm_dcn_bytes`` — and label the
    residual norm with its hop (``ef_residual_norm_dcn``; all
    hierarchical residuals live on the DCN hop) so a DCN-only blow-up
    is attributable (the per-hop ``ef_residual_spike`` SLO rule).  The
    hop-agnostic keys stay for dashboard continuity."""
    if topology is not None:
        hop = plan.hop_bytes(topology)
        out: dict[str, jnp.ndarray] = {
            "comm_compressed_bytes": jnp.asarray(
                float(hop["ici"] + hop["dcn"]), jnp.float32
            ),
            "comm_ici_bytes": jnp.asarray(float(hop["ici"]), jnp.float32),
            "comm_dcn_bytes": jnp.asarray(float(hop["dcn"]), jnp.float32),
        }
    else:
        out = {
            "comm_compressed_bytes": jnp.asarray(
                float(plan.compressed_bytes(n)), jnp.float32
            ),
        }
    denom = float(
        max(1, n * plan.quant_elems(n, zero=zero, topology=topology))
    )
    out["ef_saturation"] = lax.psum(sat_local, axis_name) / denom
    if new_comm_state:
        sq = sum(
            jnp.sum(jnp.square(r)) for r in new_comm_state.values()
        )
        out["ef_residual_norm"] = jnp.sqrt(lax.psum(sq, axis_name))
        if topology is not None:
            out["ef_residual_norm_dcn"] = out["ef_residual_norm"]
    return out

"""Comm/compute overlap: issue each stage's collective INSIDE backward.

With the fused path (comm/compress.reduce_tree) every gradient collective
runs after the whole backward pass has finished.  XLA can hide some of
that behind compute, but the schedule is one monolithic block at the end
of the step.  This module restructures WHERE the collectives appear in
the autodiff graph instead: each schedule stage's parameters pass through
an identity "tap" whose custom VJP performs that stage's bucketed
compressed reduce on the cotangents — so the heads' all-reduce is
emitted (and can be scheduled by XLA) the moment the heads' gradients
exist, while the backbone's backward is still running.  Backward-
completion order is heads → fpn → backbone (the reverse of forward), so
the deepest stage's (largest) collective is the only one that cannot
overlap with anything.

Staging is ``jax.remat``-safe by construction: ``jax.custom_vjp`` is the
one AD primitive remat treats as opaque-and-replayable, so a remat'd
forward re-runs the identity tap (free) and the collective still fires
exactly once, in the backward.

State threading through a custom VJP (which cannot return side
outputs) uses the cotangent channel itself:

- the EF residual enters as a PRIMAL input whose "gradient" IS the new
  residual (the bwd returns it as that input's cotangent), so
  ``jax.grad(..., argnums=(params, residuals, token))`` hands the step
  the post-quantization EF state with no side channel;
- a zero scalar "token" input's cotangent carries the stage's
  saturated-element count the same way.

The quantization math is byte-for-byte the shared
``compress.reduce_leaves`` — overlap-on and overlap-off produce the
same values (pinned by tests/unit/test_comm.py), only the schedule
differs.
"""

from __future__ import annotations

from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
from jax import lax

from batchai_retinanet_horovod_coco_tpu.comm.compress import (
    CommPlan,
    bucket_state_key,
    reduce_leaves,
)
from batchai_retinanet_horovod_coco_tpu.comm.config import (
    CommConfig,
    stage_of,
)


def group_tree(params: Any, plan: CommPlan) -> dict[str, Any]:
    """Split a params tree into per-stage subtrees ({stage: {top: sub}}).

    Every top-level key lands in exactly one stage (non-Mapping trees
    collapse into a single "heads" group), so the union of the groups
    is the whole tree and ``merge_groups`` is the exact inverse."""
    if not isinstance(params, Mapping):
        return {"heads": {"__root__": params}}
    groups: dict[str, dict] = {}
    for key in params:
        groups.setdefault(stage_of(key), {})[key] = params[key]
    return groups


def merge_groups(params: Any, groups: Mapping[str, Any]) -> Any:
    """Inverse of ``group_tree`` (same leaf objects, original shape)."""
    if not isinstance(params, Mapping):
        return groups["heads"]["__root__"]
    merged = {}
    for sub in groups.values():
        merged.update(sub)
    return {k: merged[k] for k in params}


def _stage_leaf_map(sub: Any, raw_root: bool) -> dict[str, Any]:
    """Leaf-path → leaf map whose paths match the FULL-tree plan paths
    (compress.py's keyed flatten, minus the ``__root__`` wrapper)."""
    from batchai_retinanet_horovod_coco_tpu.comm.compress import _leaf_map

    leaf_map, _ = _leaf_map(sub["__root__"] if raw_root else sub)
    return leaf_map


def _rebuild_stage(sub: Any, raw_root: bool, out_map: Mapping[str, Any]):
    from batchai_retinanet_horovod_coco_tpu.comm.compress import _rebuild

    rebuilt = _rebuild(sub["__root__"] if raw_root else sub, out_map)
    return {"__root__": rebuilt} if raw_root else rebuilt


def make_stage_tap(
    stage: str,
    plan: CommPlan,
    config: CommConfig,
    axis_name: str,
    n: int,
    raw_root: bool,
    topology=None,
) -> Callable:
    """Identity on a stage's params whose VJP reduces the cotangents.

    ``tap(params_sub, res_sub, token) -> params_sub``; under ``grad``
    the cotangents are (reduced grads, new EF residuals, saturation
    count) — see the module docstring's cotangent-channel contract.
    ``topology`` non-None stages the HIERARCHICAL reduce (exact ICI,
    compressed DCN) instead of the flat one — same shared engine
    (``reduce_leaves``), so overlap-on/off parity holds per hop too."""
    buckets = plan.stage_buckets(stage)
    bucket_paths = {l.path for b in buckets for l in b.leaves}

    @jax.custom_vjp
    def tap(params_sub, res_sub, token):
        del res_sub, token
        return params_sub

    def fwd(params_sub, res_sub, token):
        del token
        return params_sub, res_sub

    @jax.named_scope("grad_allreduce")  # train/step.py::STEP_SCOPES
    def bwd(res_sub, ct):
        leaf_map = _stage_leaf_map(ct, raw_root)
        out_map, new_res, sat = reduce_leaves(
            leaf_map, res_sub, buckets, config, axis_name, n, topology
        )
        # Non-bucketed leaves of this stage (non-float) reduce exact.
        for path, leaf in leaf_map.items():
            if path not in bucket_paths:
                out_map[path] = lax.pmean(leaf, axis_name)
        reduced = _rebuild_stage(ct, raw_root, out_map)
        # The residual cotangent must mirror res_sub's structure
        # exactly (exact buckets carry no state and pass through).
        res_out = {k: new_res.get(k, v) for k, v in res_sub.items()}
        return reduced, res_out, sat

    tap.defvjp(fwd, bwd)
    return tap


def make_overlap_grad_fn(
    plan: CommPlan, config: CommConfig, axis_name: str, n: int,
    topology=None,
) -> Callable:
    """Build ``grad_fn(loss_of_params, params, comm_state)`` returning
    ``((loss, aux), reduced_grads, new_comm_state, sat_count)`` with the
    per-stage collectives staged inside the backward pass.  With
    ``topology`` each stage's collective is the hierarchical tree and
    the EF residuals use the per-hop keys (``bucket_state_key``)."""
    def grad_fn(loss_of_params, params, comm_state):
        raw_root = not isinstance(params, Mapping)
        groups = group_tree(params, plan)
        taps = {
            s: make_stage_tap(
                s, plan, config, axis_name, n, raw_root, topology
            )
            for s in groups
        }
        res_groups = {
            s: {
                bucket_state_key(b, topology): comm_state[
                    bucket_state_key(b, topology)
                ]
                for b in plan.stage_buckets(s)
                if bucket_state_key(b, topology) in comm_state
            }
            for s in groups
        }
        tokens = {s: jnp.zeros((), jnp.float32) for s in groups}

        def wrapped(groups_in, res_in, tokens_in):
            tapped = {
                s: taps[s](groups_in[s], res_in[s], tokens_in[s])
                for s in groups_in
            }
            return loss_of_params(merge_groups(params, tapped))

        (loss, aux), (g_groups, g_res, g_tok) = jax.value_and_grad(
            wrapped, argnums=(0, 1, 2), has_aux=True
        )(groups, res_groups, tokens)
        grads = merge_groups(params, g_groups)
        new_comm = {
            k: v for s in g_res for k, v in g_res[s].items()
        }
        sat = sum(g_tok.values(), jnp.zeros((), jnp.float32))
        return (loss, aux), grads, new_comm, sat

    return grad_fn

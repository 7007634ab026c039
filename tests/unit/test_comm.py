"""comm/ subsystem (ISSUE 13): bucketed EF compression on the 8-dev mesh.

The claims, in dependency order:

1. plan/bytes — bucketing is deterministic, n-independent, packs small
   leaves, and the int8 plan's bytes-on-wire is <= 0.65x exact;
2. bucketed int8 pmean == exact pmean within the derived per-block
   tolerance (quantization AFTER the exact f32 reduce);
3. error feedback telescopes: a constant gradient is BIT-exact after
   the residual is applied on step 2 (controlled values on the exact
   float grid);
4. EF state survives the PR-10 checkpoint round-trip at a DIFFERENT
   world size (reshard like opt_state), and a policy/layout mismatch
   resets it to zero with one structured ef_reset event instead of
   refusing the restore;
5. overlap-on == overlap-off (same quantizer, different schedule);
6. ZeRO + compression parity vs the gathered exact reference (the
   lifted exclusivity);
7. the collective-safety lint rule bites on an unguarded comm/
   collective wrapper under a rank conditional;
8. with compression off the compiled train step is byte-identical
   (lowered-HLO text + metric key-set) to the comm-free step;
9. the ef_residual_spike SLO rule fires exactly once on an injected
   saturation spike, and the CLI flags map onto one CommConfig.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from batchai_retinanet_horovod_coco_tpu.comm import (
    CommConfig,
    init_comm_state,
    plan_buckets,
    reduce_tree,
    state_partition_specs,
)
from batchai_retinanet_horovod_coco_tpu.parallel import (
    init_sharded_opt_state,
    make_mesh,
)
from batchai_retinanet_horovod_coco_tpu.parallel.mesh import DATA_AXIS
from jax import shard_map
from batchai_retinanet_horovod_coco_tpu.train import make_train_step
from batchai_retinanet_horovod_coco_tpu.train.state import TrainState

N = 8
HW = (64, 64)


def make_batch(batch=8):
    rng = np.random.default_rng(3)
    return {
        "images": jnp.asarray(
            rng.normal(0, 1, (batch, *HW, 3)).astype(np.float32)
        ),
        "gt_boxes": jnp.asarray(
            np.tile(
                np.array([[8.0, 8.0, 40.0, 40.0]], np.float32),
                (batch, 1, 1),
            )
        ),
        "gt_labels": jnp.ones((batch, 1), jnp.int32),
        "gt_mask": jnp.ones((batch, 1), bool),
    }


def _with_comm_state(state, config, zero=False):
    return state.replace(
        comm_state={
            k: jnp.asarray(v)
            for k, v in init_comm_state(
                state.params, config, N, zero=zero
            ).items()
        }
    )


def _reduce_on_mesh(tree, config, comm_state=None, steps=1):
    """Run ``reduce_tree`` ``steps`` times on per-device data; returns
    (reduced, exact pmean, final comm state).  ``tree`` leaves carry a
    leading (N,) device axis; the same values feed every step."""
    mesh = make_mesh(N)
    plan = plan_buckets(jax.tree.map(lambda a: a[0], tree), config)
    comm_state = comm_state or {
        k: jnp.asarray(v)
        for k, v in init_comm_state(
            jax.tree.map(lambda a: a[0], tree), config, N
        ).items()
    }
    res_spec = state_partition_specs(comm_state)

    @jax.jit
    @lambda f: shard_map(
        f,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), res_spec),
        out_specs=(P(), P(), res_spec),
        check_vma=False,
    )
    def run(x, res):
        per_dev = jax.tree.map(lambda a: a[0], x)
        out = None
        for _ in range(steps):
            out, res, _sat = reduce_tree(
                per_dev, res, plan, config, DATA_AXIS, N
            )
        exact = jax.tree.map(lambda a: lax.pmean(a, DATA_AXIS), per_dev)
        return out, exact, res

    return run(tree, comm_state)


# ---------------------------------------------------------------------------
# 1. plan / bytes
# ---------------------------------------------------------------------------


class TestPlan:
    def test_small_leaves_ride_inside_buckets(self):
        """The old per-leaf _MIN_QUANTIZE_SIZE blind spot is gone: tiny
        leaves pack into the same bucket as large ones and quantize."""
        tree = {
            "backbone": {
                "w": np.zeros((64, 513), np.float32),
                "bias": np.zeros((7,), np.float32),  # old path: skipped
            }
        }
        plan = plan_buckets(tree, CommConfig(compress="int8"))
        assert len(plan.buckets) == 1
        bucket = plan.buckets[0]
        assert bucket.mode == "int8"
        assert {l.path for l in bucket.leaves} == {
            "['backbone']['bias']", "['backbone']['w']",
        }

    def test_undersized_bucket_stays_exact(self):
        tree = {"head": {"b": np.zeros((128,), np.float32)}}
        plan = plan_buckets(tree, CommConfig(compress="int8"))
        assert [b.mode for b in plan.buckets] == ["exact"]

    def test_bucket_assignment_is_world_size_independent(self):
        """EF checkpoints reshard across world sizes, so the bucket
        composition must not depend on n (only chunk shapes do)."""
        tree = {
            "backbone": {"w": np.zeros((40000,), np.float32)},
            "fpn": {"w": np.zeros((20000,), np.float32)},
        }
        cfg = CommConfig(compress="int8")
        plan = plan_buckets(tree, cfg)
        keys_by_n = {
            n: sorted(init_comm_state(tree, cfg, n)) for n in (2, 4, 8)
        }
        assert keys_by_n[2] == keys_by_n[4] == keys_by_n[8]
        assert [
            (b.key, tuple(l.path for l in b.leaves)) for b in plan.buckets
        ] == [
            (b.key, tuple(l.path for l in b.leaves))
            for b in plan_buckets(tree, cfg).buckets
        ]

    def test_int8_bytes_ratio_clears_the_claim(self, tiny_model_and_state):
        _, state = tiny_model_and_state
        plan = plan_buckets(state.params, CommConfig(compress="int8"))
        ratio = plan.compressed_bytes(N) / plan.exact_bytes(N)
        assert ratio <= 0.65, f"bytes ratio {ratio:.3f} > 0.65"

    def test_stage_mode_override(self):
        tree = {
            "backbone": {"w": np.zeros((40000,), np.float32)},
            "cls_head": {"w": np.zeros((40000,), np.float32)},
        }
        plan = plan_buckets(
            tree,
            CommConfig(compress="int8", stage_modes=(("heads", "bf16"),)),
        )
        modes = {b.stage: b.mode for b in plan.buckets}
        assert modes == {"backbone": "int8", "heads": "bf16"}

    def test_mode_none_means_exact_never_int8(self):
        """Overlap-without-compression (and a per-stage "none" opt-out)
        must keep the EXACT wire format — "none" falling through to the
        quantizer would silently quantize gradients the config promised
        to leave alone (review-round finding)."""
        tree = {"backbone": {"w": np.zeros((40000,), np.float32)}}
        overlap_only = plan_buckets(
            tree, CommConfig(compress="none", overlap=True)
        )
        assert [b.mode for b in overlap_only.buckets] == ["exact"]
        assert overlap_only.compressed_bytes(N) == overlap_only.exact_bytes(N)
        opt_out = plan_buckets(
            {"backbone": {"w": np.zeros((40000,), np.float32)},
             "cls_head": {"w": np.zeros((40000,), np.float32)}},
            CommConfig(compress="int8", stage_modes=(("heads", "none"),)),
        )
        assert {b.stage: b.mode for b in opt_out.buckets} == {
            "backbone": "int8", "heads": "exact",
        }

    def test_zero_quant_elems_uses_per_leaf_chunks(self):
        """The ZeRO saturation denominator counts the concat of PER-LEAF
        padded chunks (what zero_gather_updates actually quantizes), not
        the bucket-level chunk — sizes indivisible by n differ."""
        tree = {
            "backbone": {
                "a": np.zeros((10001,), np.float32),
                "b": np.zeros((10003,), np.float32),
            }
        }
        plan = plan_buckets(tree, CommConfig(compress="int8"))
        dp = plan.quant_elems(8)
        zero = plan.quant_elems(8, zero=True)
        assert dp == -(-20004 // 8)
        assert zero == -(-10001 // 8) + -(-10003 // 8)
        assert zero > dp


# ---------------------------------------------------------------------------
# 2. bucketed int8 pmean vs exact (the derived bound)
# ---------------------------------------------------------------------------


class TestBucketedPmean:
    def test_matches_exact_within_bound(self):
        rng = np.random.default_rng(0)
        tree = {
            "backbone": {
                "w": jnp.asarray(
                    rng.normal(0, 0.1, (N, 64, 513)).astype(np.float32)
                ),
                "bias": jnp.asarray(
                    rng.normal(0, 0.1, (N, 33)).astype(np.float32)
                ),
            }
        }
        q, exact, _ = _reduce_on_mesh(tree, CommConfig(compress="int8"))
        for key in ("w", "bias"):
            e = np.asarray(exact["backbone"][key])
            a = np.asarray(q["backbone"][key])
            # Derived tolerance: one symmetric rounding of the ALREADY
            # reduced value, <= max|block| / 254 per element; the global
            # max bounds every block max.
            bound = np.abs(np.asarray(exact["backbone"]["w"])).max() / 254.0
            np.testing.assert_allclose(a, e, atol=float(bound) + 1e-7)

    def test_outlier_blast_radius_is_one_block(self):
        cfg = CommConfig(compress="int8")
        rng = np.random.default_rng(5)
        shard_len = 8 * cfg.block
        big = rng.normal(0, 1e-3, (N, N * shard_len)).astype(np.float32)
        for s in range(N):
            big[:, s * shard_len] = 1e3  # one outlier per device shard
        q, exact, _ = _reduce_on_mesh({"w": jnp.asarray(big)}, cfg)
        q_np, e_np = np.asarray(q["w"]), np.asarray(exact["w"])
        mask = np.ones_like(e_np, dtype=bool)
        for s in range(N):
            mask[s * shard_len : s * shard_len + cfg.block] = False
        rel = np.abs(q_np[mask] - e_np[mask]) / np.maximum(
            np.abs(e_np[mask]), 1e-12
        )
        assert np.median(rel) < 0.05
        assert np.count_nonzero(q_np[mask]) > 0.95 * mask.sum()

    def test_non_finite_gradients_surface_as_nan(self):
        rng = np.random.default_rng(2)
        big = rng.normal(0, 0.1, (N, 16, 1024)).astype(np.float32)
        big[3, 5, 100] = np.inf
        q, _, _ = _reduce_on_mesh(
            {"w": jnp.asarray(big)}, CommConfig(compress="int8")
        )
        assert not np.isfinite(np.asarray(q["w"])).all()

    def test_bf16_mode_reduces(self):
        rng = np.random.default_rng(7)
        big = rng.normal(0, 0.1, (N, 9000)).astype(np.float32)
        q, exact, _ = _reduce_on_mesh(
            {"w": jnp.asarray(big)}, CommConfig(compress="bf16")
        )
        e = np.asarray(exact["w"])
        np.testing.assert_allclose(
            np.asarray(q["w"]), e, atol=np.abs(e).max() / 128.0
        )


# ---------------------------------------------------------------------------
# 3. error feedback: constant gradient bit-exact after step 2
# ---------------------------------------------------------------------------


def test_error_feedback_constant_gradient_bit_exact_on_step_2():
    """Controlled values on the exact float grid: every block carries a
    127.0 pin (scale = 1.0 exactly) and 0.5 elsewhere.  Step 1 rounds
    0.5 -> 0 (half-to-even) and banks the 0.5 residual; step 2 sees
    0.5 + 0.5 = 1.0, which quantizes exactly — so the CUMULATIVE applied
    gradient equals the exact sum bit-for-bit and the residual returns
    to zero.  The telescoping identity, on values where every float op
    is exact."""
    cfg = CommConfig(compress="int8")
    size = 8192  # one int8 bucket (32 KB), chunk 1024 = 2 blocks/device
    v = np.full((size,), 0.5, np.float32)
    v[:: cfg.block] = 127.0  # a scale pin in every block of every shard
    tree = {"w": jnp.asarray(np.tile(v, (N, 1)))}

    mesh = make_mesh(N)
    plan = plan_buckets({"w": v}, cfg)
    cs = {
        k: jnp.asarray(val)
        for k, val in init_comm_state({"w": v}, cfg, N).items()
    }
    res_spec = state_partition_specs(cs)

    @jax.jit
    @lambda f: shard_map(
        f,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), res_spec),
        out_specs=(P(), P(), res_spec),
        check_vma=False,
    )
    def two_steps(x, res):
        per_dev = jax.tree.map(lambda a: a[0], x)
        out1, res, _ = reduce_tree(per_dev, res, plan, cfg, DATA_AXIS, N)
        out2, res, _ = reduce_tree(per_dev, res, plan, cfg, DATA_AXIS, N)
        return out1, out2, res

    out1, out2, res = two_steps(tree, cs)
    applied = np.asarray(out1["w"]) + np.asarray(out2["w"])
    np.testing.assert_array_equal(applied, 2.0 * v)  # BIT-exact
    np.testing.assert_array_equal(  # residual telescoped back to zero
        np.asarray(res["heads.0"]), np.zeros((res["heads.0"].size,), np.float32)
    )
    # And step 1 alone is NOT exact (the residual was real).
    assert not np.array_equal(np.asarray(out1["w"]), v)


# ---------------------------------------------------------------------------
# 4. checkpoint round-trip: reshard like opt_state + the ef_reset path
# ---------------------------------------------------------------------------


class _SinkSpy:
    def __init__(self):
        self.events = []

    def event(self, kind, **fields):
        self.events.append((kind, fields))


def _tiny_state(comm_state):
    params = {"w": np.arange(6, dtype=np.float32)}
    tx = optax.sgd(1e-2)
    return TrainState(
        step=np.zeros((), np.int32),
        params=params,
        batch_stats={},
        opt_state=tx.init(params),
        tx=tx,
        comm_state=comm_state,
    )


class TestCheckpointElasticity:
    def test_ef_state_reshards_across_world_sizes(self, tmp_path):
        from batchai_retinanet_horovod_coco_tpu.utils.checkpoint import (
            CheckpointManager,
        )

        # Logical EF content: 100 elements + world-8 zero padding.
        logical = np.arange(1, 101, dtype=np.float32) / 7.0
        world8 = np.zeros((8 * 13,), np.float32)  # 8 * ceil(100/8) = 104
        world8[:100] = logical
        saved_state = _tiny_state({"backbone.0": world8})
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        assert mgr.save(saved_state, step=5, force=True)

        # Restore into a WORLD-4 template: 4 * ceil(100/4) = 100 (the
        # padding truncates — legal iff all-zero, the ZeRO invariant).
        template = _tiny_state({"backbone.0": np.zeros((100,), np.float32)})
        restored = CheckpointManager(str(tmp_path)).restore(template)
        np.testing.assert_array_equal(
            restored.comm_state["backbone.0"], logical
        )
        # And back up to a WORLD-16 template (zero-pad).
        t16 = _tiny_state({"backbone.0": np.zeros((16 * 7,), np.float32)})
        r16 = CheckpointManager(str(tmp_path)).restore(t16)
        np.testing.assert_array_equal(
            r16.comm_state["backbone.0"][:100], logical
        )
        np.testing.assert_array_equal(
            r16.comm_state["backbone.0"][100:], 0.0
        )

    def test_missing_ef_state_zeroes_with_one_ef_reset_event(
        self, tmp_path, capsys
    ):
        from batchai_retinanet_horovod_coco_tpu.utils.checkpoint import (
            CheckpointManager,
        )

        # Uncompressed checkpoint (no comm leaves) ...
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        assert mgr.save(_tiny_state(()), step=3, force=True)
        # ... restored into a run WITH compression: zeros + ONE event,
        # never a refusal.
        sink = _SinkSpy()
        template = _tiny_state({"backbone.0": np.ones((24,), np.float32)})
        restored = CheckpointManager(str(tmp_path), sink=sink).restore(
            template
        )
        np.testing.assert_array_equal(
            restored.comm_state["backbone.0"], np.zeros((24,), np.float32)
        )
        resets = [e for e in sink.events if e[0] == "ef_reset"]
        assert len(resets) == 1
        err = capsys.readouterr().err
        assert sum(1 for l in err.splitlines() if '"ef_reset"' in l) == 1

    def test_dropped_ef_state_is_tolerated(self, tmp_path):
        """Compressed checkpoint restored WITHOUT compression: the comm
        leaves are dropped (with the same ef_reset record), and the
        params/optimizer restore is untouched."""
        from batchai_retinanet_horovod_coco_tpu.utils.checkpoint import (
            CheckpointManager,
        )

        mgr = CheckpointManager(str(tmp_path), async_save=False)
        assert mgr.save(
            _tiny_state({"backbone.0": np.ones((24,), np.float32)}),
            step=3, force=True,
        )
        restored = CheckpointManager(str(tmp_path)).restore(_tiny_state(()))
        assert restored.comm_state == ()
        np.testing.assert_array_equal(
            restored.params["w"], np.arange(6, dtype=np.float32)
        )

    def test_bucket_layout_change_zeroes_instead_of_refusing(
        self, tmp_path
    ):
        """A comm key that survives a bucket-layout change but SHRINKS
        (real residual content would be dropped) zeroes with one
        ef_reset instead of refusing the restore — EF residuals are
        advisory state; only params/optimizer mismatches refuse
        (review-round finding)."""
        from batchai_retinanet_horovod_coco_tpu.utils.checkpoint import (
            CheckpointManager,
        )

        full = np.ones((24,), np.float32)  # no zero tail at all
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        assert mgr.save(_tiny_state({"backbone.0": full}), step=1, force=True)
        sink = _SinkSpy()
        template = _tiny_state({"backbone.0": np.zeros((12,), np.float32)})
        restored = CheckpointManager(str(tmp_path), sink=sink).restore(
            template
        )
        np.testing.assert_array_equal(
            restored.comm_state["backbone.0"], np.zeros((12,), np.float32)
        )
        assert [e[0] for e in sink.events] == ["ef_reset"]
        # The params restore is untouched by the comm degrade.
        np.testing.assert_array_equal(
            restored.params["w"], np.arange(6, dtype=np.float32)
        )


def test_overlap_only_reduce_is_bitwise_exact():
    """--comm-overlap without --comm-compress: the reduce must be the
    exact pmean values (only the schedule moves)."""
    rng = np.random.default_rng(11)
    tree = {
        "backbone": {
            "w": jnp.asarray(rng.normal(0, 0.1, (N, 40000)).astype(np.float32))
        }
    }
    q, exact, _ = _reduce_on_mesh(
        tree, CommConfig(compress="none", overlap=True)
    )
    np.testing.assert_array_equal(
        np.asarray(q["backbone"]["w"]), np.asarray(exact["backbone"]["w"])
    )


def test_zero_gather_tolerates_missing_ef_state():
    """ZeRO + an EF-enabled policy with NO initialized comm state (the
    deprecated alias's default TrainState.comm_state == ()) must degrade
    to stateless quantization, not crash with a KeyError at trace time
    (review-round finding — the deleted quantized×ZeRO exclusivity
    guard's replacement contract)."""
    from batchai_retinanet_horovod_coco_tpu.comm import zero_gather_updates
    from batchai_retinanet_horovod_coco_tpu.parallel.zero import (
        _local_shard,
    )

    cfg = CommConfig(compress="int8")  # error_feedback=True by default
    assert cfg.needs_state
    rng = np.random.default_rng(13)
    params = {
        "backbone": {
            "w": jnp.asarray(rng.normal(0, 0.1, (40000,)).astype(np.float32))
        }
    }
    updates_full = jax.tree.map(lambda p: -0.01 * jnp.ones_like(p), params)
    plan = plan_buckets(params, cfg)
    mesh = make_mesh(N)

    @jax.jit
    @lambda f: shard_map(
        f, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        check_vma=False,
    )
    def run(p, upd_full):
        idx = jax.lax.axis_index(DATA_AXIS)
        upd = jax.tree.map(lambda u: _local_shard(u, N, idx), upd_full)
        new_p, new_res, _sat = zero_gather_updates(
            upd, p, {}, plan, cfg, DATA_AXIS, N
        )
        assert new_res == {}  # stateless degrade, structure preserved
        return new_p, jnp.zeros(())

    new_p, _ = run(params, updates_full)
    expect = params["backbone"]["w"] - 0.01
    np.testing.assert_allclose(
        np.asarray(new_p["backbone"]["w"]), np.asarray(expect), atol=1e-3
    )


# ---------------------------------------------------------------------------
# 5/6/8. full train-step flavors (fixture model, one batch)
# ---------------------------------------------------------------------------


class TestTrainStepFlavors:
    def test_overlap_matches_fused_and_single_device(
        self, tiny_model_and_state
    ):
        model, state = tiny_model_and_state
        batch = make_batch()
        mesh = make_mesh(N)
        cfg_fused = CommConfig(compress="int8")
        cfg_overlap = CommConfig(compress="int8", overlap=True)

        single = make_train_step(model, HW, 3, mesh=None, donate_state=False)
        s_new, s_metrics = single(state, batch)

        fused_state = _with_comm_state(state, cfg_fused)
        fused = make_train_step(
            model, HW, 3, mesh=mesh, comm=cfg_fused, donate_state=False
        )
        f_new, f_metrics = fused(fused_state, batch)

        over_state = _with_comm_state(state, cfg_overlap)
        over = make_train_step(
            model, HW, 3, mesh=mesh, comm=cfg_overlap, donate_state=False
        )
        o_new, o_metrics = over(over_state, batch)

        # (5) overlap == fused: same quantizer, different schedule.
        np.testing.assert_allclose(
            float(o_metrics["loss"]), float(f_metrics["loss"]), rtol=1e-6
        )
        for a, b in zip(
            jax.tree.leaves(o_new.params), jax.tree.leaves(f_new.params)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-6
            )
        for k in o_new.comm_state:
            np.testing.assert_allclose(
                np.asarray(o_new.comm_state[k]),
                np.asarray(f_new.comm_state[k]),
                atol=1e-7,
            )
        # Compressed step tracks the exact single-device update within
        # the one-rounding bound.
        np.testing.assert_allclose(
            float(f_metrics["loss"]), float(s_metrics["loss"]), rtol=1e-5
        )
        for a, b in zip(
            jax.tree.leaves(f_new.params), jax.tree.leaves(s_new.params)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-3
            )
        # EF health metrics present and sane.
        for m in (f_metrics, o_metrics):
            assert float(m["ef_residual_norm"]) > 0
            assert 0.0 <= float(m["ef_saturation"]) <= 1.0
            assert float(m["comm_compressed_bytes"]) > 0

    def test_zero_plus_compression_matches_gathered_reference(
        self, tiny_model_and_state
    ):
        model, state = tiny_model_and_state
        batch = make_batch()
        mesh = make_mesh(N)
        cfg = CommConfig(compress="int8")

        single = make_train_step(model, HW, 3, mesh=None, donate_state=False)
        s_new, s_metrics = single(state, batch)

        zstate = state.replace(
            opt_state=init_sharded_opt_state(state.tx, state.params, mesh)
        )
        zstate = _with_comm_state(zstate, cfg, zero=True)
        zstep = make_train_step(
            model, HW, 3, mesh=mesh, shard_weight_update=True, comm=cfg,
            donate_state=False,
        )
        z_new, z_metrics = zstep(zstate, batch)
        np.testing.assert_allclose(
            float(z_metrics["loss"]), float(s_metrics["loss"]), rtol=1e-5
        )
        for a, b in zip(
            jax.tree.leaves(z_new.params), jax.tree.leaves(s_new.params)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-3
            )
        assert float(z_metrics["ef_residual_norm"]) >= 0
        assert float(z_metrics["comm_compressed_bytes"]) > 0
        # The params must stay bitwise REPLICATED (every device applied
        # the identical dequantized update).
        for leaf in jax.tree.leaves(z_new.params):
            assert bool(
                jnp.all(jnp.isfinite(jnp.asarray(leaf)))
            )

    def test_compression_off_is_byte_identical(self, tiny_model_and_state):
        """The acceptance gate: comm=None and comm=CommConfig("none")
        lower to the SAME HLO text, and the metric key-set is the
        pre-ISSUE-13 vocabulary (the PR-9 numerics-gate technique)."""
        model, state = tiny_model_and_state
        batch = make_batch()
        mesh = make_mesh(N)
        base = make_train_step(model, HW, 3, mesh=mesh, donate_state=False)
        off = make_train_step(
            model, HW, 3, mesh=mesh, comm=CommConfig(compress="none"),
            donate_state=False,
        )
        text_a = base.lower(state, batch).as_text()
        text_b = off.lower(state, batch).as_text()
        assert text_a == text_b
        new_state, metrics = base(state, batch)
        assert set(metrics) == {
            "loss", "cls_loss", "box_loss", "num_pos", "grad_norm",
            "param_norm",
        }


# ---------------------------------------------------------------------------
# 7. lint: rank-guarded comm collective
# ---------------------------------------------------------------------------


def test_lint_bites_on_rank_guarded_comm_collective():
    from tests.unit.test_lint import run_rule

    result = run_rule(
        """
        import jax

        from batchai_retinanet_horovod_coco_tpu.comm import compress

        def step(grads, comm_state, plan, cfg):
            if jax.process_index() == 0:
                grads, comm_state, _ = compress.reduce_tree(
                    grads, comm_state, plan, cfg, "data", 8
                )
            return grads
        """,
        "collective-safety",
    )
    assert len(result.findings) == 1
    assert "reduce_tree" in result.findings[0].message

    clean = run_rule(
        """
        from batchai_retinanet_horovod_coco_tpu.comm import compress

        def step(grads, comm_state, plan, cfg):
            return compress.reduce_tree(
                grads, comm_state, plan, cfg, "data", 8
            )
        """,
        "collective-safety",
    )
    assert clean.findings == []


# ---------------------------------------------------------------------------
# 9. SLO rule + CLI mapping
# ---------------------------------------------------------------------------


def test_ef_residual_spike_fires_exactly_once():
    from batchai_retinanet_horovod_coco_tpu.obs import slo, telemetry

    telemetry.enable()  # Gauge.set is gated on the global enable
    try:
        registry = telemetry.Registry()
        gauge = registry.gauge("train_ef_residual", "test")
        monitor = slo.SloMonitor(
            registry, [slo.ef_residual_spike(factor=10.0)],
            poll_interval=999,
        )
        # Healthy baseline (min_baseline samples) ...
        for i in range(6):
            gauge.set(1.0 + 0.01 * i)
            assert monitor.check_once(now=float(i)) == []
        # ... injected saturation spike: fires EXACTLY once and stays
        # latched through the sustained breach.
        gauge.set(100.0)
        fired = monitor.check_once(now=10.0)
        assert [v["rule"] for v in fired] == ["ef_residual_spike"]
        assert monitor.check_once(now=11.0) == []
        assert monitor.check_once(now=12.0) == []
    finally:
        telemetry.disable()


def test_ef_rule_silent_without_compression_gauge():
    from batchai_retinanet_horovod_coco_tpu.obs import slo
    from batchai_retinanet_horovod_coco_tpu.obs.telemetry import Registry

    monitor = slo.SloMonitor(
        Registry(), [slo.ef_residual_spike()], poll_interval=999
    )
    for i in range(10):
        assert monitor.check_once(now=float(i)) == []


class TestCliMapping:
    def _args(self, **kw):
        import argparse

        defaults = dict(
            comm_compress="none", comm_overlap=False, comm_bucket_mb=4.0,
            comm_no_error_feedback=False,
        )
        defaults.update(kw)
        return argparse.Namespace(**defaults)

    def test_none_maps_to_no_config(self):
        from batchai_retinanet_horovod_coco_tpu.utils.cli import (
            make_comm_config,
        )

        assert make_comm_config(self._args()) is None

    def test_flags_map_to_config(self):
        from batchai_retinanet_horovod_coco_tpu.utils.cli import (
            make_comm_config,
        )

        cfg = make_comm_config(
            self._args(comm_compress="int8", comm_overlap=True,
                       comm_bucket_mb=2.0)
        )
        assert cfg == CommConfig(
            compress="int8", overlap=True, bucket_mb=2.0
        )


def test_record_comm_feeds_gauges_and_counter():
    from batchai_retinanet_horovod_coco_tpu.obs import telemetry

    telemetry.reset()
    telemetry.enable()
    try:
        telemetry.record_comm(
            ef_residual=0.25, ef_saturation=0.01,
            compressed_bytes=1000.0, steps=20,
        )
        snap = telemetry.default().snapshot()
        assert snap["train_ef_residual"] == 0.25
        assert snap["train_ef_saturation"] == 0.01
        assert snap["train_comm_compressed_bytes_total"] == 20000.0
        # Disabled: the record site is a single bool check, no mutation.
        telemetry.reset()
        telemetry.record_comm(ef_residual=9.9, compressed_bytes=1.0)
        assert "train_ef_residual" not in telemetry.default().snapshot()
    finally:
        telemetry.reset()

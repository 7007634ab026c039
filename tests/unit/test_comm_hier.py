"""Topology-aware hierarchical collectives (ISSUE 16), on the 8-dev mesh.

The claims, in dependency order:

1. topology — the interleaved grouping is exactly the documented
   convention at 2x4 AND 4x2, and ``derive_topology`` resolves the
   override chain (arg > env > device slice_index) with clear
   divisibility errors;
2. config — the per-hop fields validate (unknown stage names list the
   valid stages, errors name the ``CommConfig.`` path, ICI-compression
   mismatch is rejected), and the engage/degenerate logic
   (``hierarchical_with`` / ``flat_equivalent``) resolves every
   degenerate case to the flat tree BEFORE tracing;
3. degenerate == flat, byte-identical: equal hop modes and the
   single-slice topology lower to the SAME HLO text as the flat tree /
   the comm-free step (the pinned contract);
4. the engaged hierarchical reduce matches the exact pmean within the
   one-rounding bound (compression only on the DCN hop), and the
   per-hop EF residual telescopes bit-exactly on constant gradients;
5. per-hop EF state lives under ``"<bucket>@dcn"`` keys in GLOBAL
   bucket order (the interleaved-mesh invariant) and reshards across
   world sizes 8 -> 4 -> 16 through the PR-10 checkpoint machinery;
6. wire accounting — the DCN hop's bytes under int8 are <= 0.65x the
   all-exact hierarchical tree, the ICI hops carry ZERO quantized
   bytes, and the split reaches the step metrics / telemetry counters /
   the per-hop ``ef_residual_spike_dcn`` SLO rule;
7. the collective-safety lint rule bites on a rank-guarded
   ``reduce_bucket_hierarchical`` call;
8. the CLI maps ``--comm-ici-mode`` / ``--comm-dcn-mode`` /
   ``--comm-dcn-bucket-mb`` onto the config (and a hop-only policy
   still produces a config).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
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
    CommTopology,
    derive_topology,
    make_mesh,
)
from batchai_retinanet_horovod_coco_tpu.parallel.mesh import (
    COMM_SLICES_ENV,
    DATA_AXIS,
)
from jax import shard_map
from batchai_retinanet_horovod_coco_tpu.train import make_train_step

N = 8
HW = (64, 64)
T24 = CommTopology(num_slices=2, slice_size=4)
T42 = CommTopology(num_slices=4, slice_size=2)


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


def _hier_reduce_on_mesh(tree, config, topology, steps=1):
    """Run the HIERARCHICAL ``reduce_tree`` ``steps`` times on per-device
    data; returns (reduced, exact pmean, final comm state).  ``tree``
    leaves carry a leading (N,) device axis."""
    assert config.hierarchical_with(topology)
    mesh = make_mesh(N, topology=topology)
    per_dev_tree = jax.tree.map(lambda a: a[0], tree)
    plan = plan_buckets(per_dev_tree, config, topology)
    comm_state = {
        k: jnp.asarray(v)
        for k, v in init_comm_state(
            per_dev_tree, config, N, topology=topology
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
                per_dev, res, plan, config, DATA_AXIS, N, topology
            )
        exact = jax.tree.map(lambda a: lax.pmean(a, DATA_AXIS), per_dev)
        return out, exact, res

    return run(tree, comm_state)


# ---------------------------------------------------------------------------
# 1. topology: grouping convention + derivation
# ---------------------------------------------------------------------------


class TestTopology:
    def test_2x4_grouping_is_the_interleaved_convention(self):
        """Position d: slice d % S, intra-slice rank d // S."""
        assert T24.num_devices == 8
        assert T24.ici_groups() == [[0, 2, 4, 6], [1, 3, 5, 7]]
        assert T24.dcn_groups() == [[0, 1], [2, 3], [4, 5], [6, 7]]

    def test_4x2_grouping(self):
        assert T42.ici_groups() == [[0, 4], [1, 5], [2, 6], [3, 7]]
        assert T42.dcn_groups() == [[0, 1, 2, 3], [4, 5, 6, 7]]

    def test_groups_partition_the_mesh(self):
        for topo in (T24, T42):
            for groups in (topo.ici_groups(), topo.dcn_groups()):
                flat = sorted(d for g in groups for d in g)
                assert flat == list(range(topo.num_devices))

    def test_derive_explicit_arg_wins(self, monkeypatch):
        monkeypatch.setenv(COMM_SLICES_ENV, "4")
        topo = derive_topology(8, 2)
        assert (topo.num_slices, topo.slice_size) == (2, 4)

    def test_derive_env_override(self, monkeypatch):
        monkeypatch.setenv(COMM_SLICES_ENV, "2")
        topo = derive_topology(8)
        assert (topo.num_slices, topo.slice_size) == (2, 4)

    def test_derive_flat_without_slice_info(self, monkeypatch):
        """Virtual CPU devices carry no slice_index: flat unless told."""
        monkeypatch.delenv(COMM_SLICES_ENV, raising=False)
        assert derive_topology(8) is None

    def test_derive_rejects_indivisible(self):
        with pytest.raises(ValueError, match="do not divide"):
            derive_topology(8, 3)
        with pytest.raises(ValueError, match=">= 1"):
            derive_topology(8, 0)

    def test_derive_rejects_garbage_env(self, monkeypatch):
        monkeypatch.setenv(COMM_SLICES_ENV, "two")
        with pytest.raises(ValueError, match=COMM_SLICES_ENV):
            derive_topology(8)

    def test_make_mesh_accepts_topology_and_checks_size(self):
        mesh = make_mesh(N, topology=T24)
        assert mesh.size == N  # CPU devices: order passes through
        with pytest.raises(ValueError, match="topology is 2x2"):
            make_mesh(N, topology=CommTopology(2, 2))


# ---------------------------------------------------------------------------
# 2. config: per-hop validation + engage/degenerate resolution
# ---------------------------------------------------------------------------


class TestConfig:
    def test_unknown_stage_name_lists_valid_stages(self):
        with pytest.raises(ValueError) as e:
            CommConfig(compress="int8", stage_modes=(("bakbone", "int8"),))
        msg = str(e.value)
        assert "bakbone" in msg
        assert "backbone" in msg and "fpn" in msg and "heads" in msg

    def test_bucket_mb_error_names_the_config_path(self):
        with pytest.raises(ValueError, match=r"CommConfig\.bucket_mb"):
            CommConfig(compress="int8", bucket_mb=0)
        with pytest.raises(ValueError, match=r"CommConfig\.dcn_bucket_mb"):
            CommConfig(compress="int8", dcn_bucket_mb=-1.0)

    def test_hop_mode_vocabulary(self):
        with pytest.raises(ValueError, match=r"CommConfig\.dcn_mode"):
            CommConfig(dcn_mode="int4")
        with pytest.raises(ValueError, match=r"CommConfig\.ici_mode"):
            CommConfig(ici_mode="fp8")

    def test_compressed_ici_with_different_dcn_is_rejected(self):
        with pytest.raises(ValueError, match="fast \\(ICI\\) hop"):
            CommConfig(compress="none", ici_mode="int8", dcn_mode="bf16")
        # Equal modes are legal — that's just the flat tree.
        cfg = CommConfig(compress="none", ici_mode="int8", dcn_mode="int8")
        assert not cfg.hierarchical_with(T24)

    def test_defaults_engage_only_on_multi_slice(self):
        cfg = CommConfig(compress="int8")  # ici none, dcn inherits int8
        assert cfg.effective_ici_mode == "none"
        assert cfg.effective_dcn_mode == "int8"
        assert cfg.hierarchical_with(T24)
        assert not cfg.hierarchical_with(None)
        assert not cfg.hierarchical_with(CommTopology(1, 8))

    def test_flat_equivalent_resolution(self):
        cfg = CommConfig(
            compress="int8", stage_modes=(("heads", "bf16"),)
        )
        # No topology: unchanged (legacy path).
        assert cfg.flat_equivalent(None) is cfg
        # Single slice: the whole world is the fast wire — exact.
        single = cfg.flat_equivalent(CommTopology(1, 8))
        assert single.compress == "none"
        assert single.stage_modes == ()
        assert not single.enabled
        # Equal modes at multi-slice: flat at the shared mode, pinned
        # on BOTH hops so the result is a fixed point — re-resolving it
        # against any topology never re-engages the hierarchy.
        eq = CommConfig(compress="none", ici_mode="bf16", dcn_mode="bf16")
        flat = eq.flat_equivalent(T24)
        assert flat.compress == "bf16"
        assert (flat.ici_mode, flat.dcn_mode) == ("bf16", "bf16")
        assert not flat.hierarchical_with(T24)
        assert flat.flat_equivalent(T24) == flat

    def test_hop_only_policy_counts_as_enabled_and_stateful(self):
        cfg = CommConfig(compress="none", dcn_mode="int8")
        assert cfg.enabled and cfg.needs_state
        assert cfg.hierarchical_with(T24)

    def test_hier_state_keys_and_shapes(self):
        tree = {"backbone": {"w": np.zeros((35000,), np.float32)}}
        cfg = CommConfig(compress="int8")
        state = init_comm_state(tree, cfg, N, topology=T24)
        # hier_chunk = ceil(ceil(35000/4)/2) = 4375, keyed per hop.
        assert set(state) == {"backbone.0@dcn"}
        assert state["backbone.0@dcn"].shape == (8 * 4375,)
        # ZeRO ignores the topology: per-leaf flat keys, no @dcn.
        zstate = init_comm_state(tree, cfg, N, zero=True, topology=T24)
        assert set(zstate) == {"['backbone']['w']"}
        # Degenerate topologies fall back to the flat bucket keys.
        flat = init_comm_state(tree, cfg, N)
        single = init_comm_state(
            tree, cfg, N, topology=CommTopology(1, 8)
        )
        assert set(flat) == {"backbone.0"}
        assert single == {}  # single slice + default ici "none": exact

    def test_plan_composition_is_slice_count_independent(self):
        """Same policy at 2x4 and 4x2: identical bucket composition
        (only chunk shapes differ) — the reshard prerequisite."""
        tree = {
            "backbone": {"w": np.zeros((40000,), np.float32)},
            "fpn": {"w": np.zeros((20000,), np.float32)},
        }
        cfg = CommConfig(compress="int8")
        key = lambda plan: [
            (b.key, b.mode, tuple(l.path for l in b.leaves))
            for b in plan.buckets
        ]
        assert key(plan_buckets(tree, cfg, T24)) == key(
            plan_buckets(tree, cfg, T42)
        )


# ---------------------------------------------------------------------------
# 3. degenerate == flat, byte-identical HLO
# ---------------------------------------------------------------------------


class TestDegenerateHlo:
    def test_equal_hop_modes_lower_to_the_flat_tree(
        self, tiny_model_and_state
    ):
        """ici == dcn == int8 at a 2-slice topology IS the flat int8
        tree: same HLO text, no grouped collectives."""
        model, state = tiny_model_and_state
        batch = make_batch()
        mesh = make_mesh(N)
        cfg_flat = CommConfig(compress="int8")
        cfg_eq = CommConfig(
            compress="int8", ici_mode="int8", dcn_mode="int8"
        )
        cs = {
            k: jnp.asarray(v)
            for k, v in init_comm_state(state.params, cfg_flat, N).items()
        }
        state = state.replace(comm_state=cs)
        flat = make_train_step(
            model, HW, 3, mesh=mesh, comm=cfg_flat, donate_state=False
        )
        eq = make_train_step(
            model, HW, 3, mesh=mesh, comm=cfg_eq, topology=T24,
            donate_state=False,
        )
        assert (
            flat.lower(state, batch).as_text()
            == eq.lower(state, batch).as_text()
        )

    def test_single_slice_topology_is_byte_identical_to_comm_off(
        self, tiny_model_and_state
    ):
        """A single-slice topology has no DCN hop; with the default
        ici_mode="none" the whole policy degenerates to the comm-free
        step — pinned at the HLO text."""
        model, state = tiny_model_and_state
        batch = make_batch()
        mesh = make_mesh(N)
        base = make_train_step(model, HW, 3, mesh=mesh, donate_state=False)
        degen = make_train_step(
            model, HW, 3, mesh=mesh, comm=CommConfig(compress="int8"),
            topology=CommTopology(1, 8), donate_state=False,
        )
        assert (
            base.lower(state, batch).as_text()
            == degen.lower(state, batch).as_text()
        )

    def test_topology_mesh_size_mismatch_is_rejected(
        self, tiny_model_and_state
    ):
        model, _ = tiny_model_and_state
        with pytest.raises(ValueError, match="mesh"):
            make_train_step(
                model, HW, 3, mesh=make_mesh(N),
                comm=CommConfig(compress="int8"),
                topology=CommTopology(2, 2), donate_state=False,
            )


# ---------------------------------------------------------------------------
# 4. engaged hierarchy: parity + per-hop EF telescoping
# ---------------------------------------------------------------------------


class TestHierarchicalReduce:
    @pytest.mark.parametrize("topo", [T24, T42], ids=["2x4", "4x2"])
    def test_matches_exact_within_bound(self, topo):
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
        cfg = CommConfig(compress="int8")
        q, exact, res = _hier_reduce_on_mesh(tree, cfg, topo)
        bound = np.abs(np.asarray(exact["backbone"]["w"])).max() / 254.0
        for key in ("w", "bias"):
            np.testing.assert_allclose(
                np.asarray(q["backbone"][key]),
                np.asarray(exact["backbone"][key]),
                atol=float(bound) + 1e-7,
            )
        assert set(res) == {"backbone.0@dcn"}

    def test_non_finite_gradients_surface_as_nan(self):
        rng = np.random.default_rng(2)
        big = rng.normal(0, 0.1, (N, 16, 1024)).astype(np.float32)
        big[3, 5, 100] = np.inf
        q, _, _ = _hier_reduce_on_mesh(
            {"w": jnp.asarray(big)}, CommConfig(compress="int8"), T24
        )
        assert not np.isfinite(np.asarray(q["w"])).all()

    def test_per_hop_ef_telescopes_bit_exact_on_step_2(self):
        """The flat EF telescoping claim, through the 5-phase tree: a
        constant gradient on the exact float grid is BIT-exact after the
        DCN-hop residual is applied on step 2, and the residual returns
        to zero."""
        cfg = CommConfig(compress="int8")
        size = 8192  # hier_chunk at 2x4 = 1024 = 2 blocks, pin-aligned
        v = np.full((size,), 0.5, np.float32)
        v[:: cfg.block] = 127.0
        tree = {"w": jnp.asarray(np.tile(v, (N, 1)))}

        mesh = make_mesh(N, topology=T24)
        plan = plan_buckets({"w": v}, cfg, T24)
        cs = {
            k: jnp.asarray(val)
            for k, val in init_comm_state(
                {"w": v}, cfg, N, topology=T24
            ).items()
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
            out1, res, _ = reduce_tree(
                per_dev, res, plan, cfg, DATA_AXIS, N, T24
            )
            out2, res, _ = reduce_tree(
                per_dev, res, plan, cfg, DATA_AXIS, N, T24
            )
            return out1, out2, res

        out1, out2, res = two_steps(tree, cs)
        applied = np.asarray(out1["w"]) + np.asarray(out2["w"])
        np.testing.assert_array_equal(applied, 2.0 * v)  # BIT-exact
        np.testing.assert_array_equal(
            np.asarray(res["heads.0@dcn"]),
            np.zeros((res["heads.0@dcn"].size,), np.float32),
        )
        assert not np.array_equal(np.asarray(out1["w"]), v)

    def test_hier_train_step_tracks_single_device(
        self, tiny_model_and_state
    ):
        """Full integration: the hierarchical step at 2x4 stays within
        the one-rounding bound of the exact single-device update and
        emits the per-hop metric vocabulary."""
        model, state = tiny_model_and_state
        batch = make_batch()
        cfg = CommConfig(compress="int8")
        mesh = make_mesh(N, topology=T24)

        single = make_train_step(model, HW, 3, mesh=None, donate_state=False)
        s_new, s_metrics = single(state, batch)

        hstate = state.replace(
            comm_state={
                k: jnp.asarray(v)
                for k, v in init_comm_state(
                    state.params, cfg, N, topology=T24
                ).items()
            }
        )
        assert all(k.endswith("@dcn") for k in hstate.comm_state)
        hier = make_train_step(
            model, HW, 3, mesh=mesh, comm=cfg, topology=T24,
            donate_state=False,
        )
        h_new, h_metrics = hier(hstate, batch)

        np.testing.assert_allclose(
            float(h_metrics["loss"]), float(s_metrics["loss"]), rtol=1e-5
        )
        for a, b in zip(
            jax.tree.leaves(h_new.params), jax.tree.leaves(s_new.params)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-3
            )
        # Per-hop metric vocabulary: the step emits the plan's static
        # split (each leg f32-rounded independently, so compare against
        # the plan, not ici + dcn re-summed in f64).
        plan = plan_buckets(state.params, cfg, T24)
        hop = plan.hop_bytes(T24)
        assert hop["ici"] > 0 and hop["dcn"] > 0
        assert float(h_metrics["comm_ici_bytes"]) == np.float32(hop["ici"])
        assert float(h_metrics["comm_dcn_bytes"]) == np.float32(hop["dcn"])
        assert float(h_metrics["comm_compressed_bytes"]) == np.float32(
            hop["ici"] + hop["dcn"]
        )
        assert float(h_metrics["ef_residual_norm_dcn"]) == float(
            h_metrics["ef_residual_norm"]
        )
        assert 0.0 <= float(h_metrics["ef_saturation"]) <= 1.0


# ---------------------------------------------------------------------------
# 5. checkpoint elasticity of the per-hop EF state
# ---------------------------------------------------------------------------


def test_dcn_residuals_reshard_8_to_4_to_16(tmp_path):
    """The ``@dcn`` keys ride the same reshard_flat_leaf machinery as
    flat EF / ZeRO state: logical prefix + zero padding, truncate down,
    zero-pad up — the interleaved-mesh invariant made checkpointable."""
    import optax

    from batchai_retinanet_horovod_coco_tpu.train.state import TrainState
    from batchai_retinanet_horovod_coco_tpu.utils.checkpoint import (
        CheckpointManager,
    )

    def tiny_state(comm_state):
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

    logical = np.arange(1, 101, dtype=np.float32) / 7.0
    world8 = np.zeros((8 * 13,), np.float32)  # 8 * ceil(100/8)
    world8[:100] = logical
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    assert mgr.save(
        tiny_state({"backbone.0@dcn": world8}), step=5, force=True
    )

    t4 = tiny_state({"backbone.0@dcn": np.zeros((100,), np.float32)})
    r4 = CheckpointManager(str(tmp_path)).restore(t4)
    np.testing.assert_array_equal(r4.comm_state["backbone.0@dcn"], logical)

    t16 = tiny_state({"backbone.0@dcn": np.zeros((16 * 7,), np.float32)})
    r16 = CheckpointManager(str(tmp_path)).restore(t16)
    np.testing.assert_array_equal(
        r16.comm_state["backbone.0@dcn"][:100], logical
    )
    np.testing.assert_array_equal(r16.comm_state["backbone.0@dcn"][100:], 0.0)


# ---------------------------------------------------------------------------
# 6. per-hop wire accounting + telemetry + SLO
# ---------------------------------------------------------------------------


class TestPerHopAccounting:
    def test_dcn_ratio_clears_the_claim_and_ici_stays_exact(
        self, tiny_model_and_state
    ):
        _, state = tiny_model_and_state
        cfg = CommConfig(compress="int8")
        plan = plan_buckets(state.params, cfg, T24)
        hop = plan.hop_bytes(T24)
        exact = plan.hop_bytes_exact(T24)
        ratio = hop["dcn"] / exact["dcn"]
        assert ratio <= 0.65, f"DCN bytes ratio {ratio:.3f} > 0.65"
        # The ICI hops are untouched by the policy ...
        assert hop["ici"] == exact["ici"]
        # ... and carry ZERO quantized bytes, by construction.
        quant = plan.hop_quant_bytes(T24)
        assert quant["ici"] == 0
        assert quant["dcn"] > 0

    def test_record_comm_feeds_the_per_hop_counters(self):
        from batchai_retinanet_horovod_coco_tpu.obs import telemetry

        telemetry.reset()
        telemetry.enable()
        try:
            telemetry.record_comm(
                ef_residual=0.5, compressed_bytes=300.0,
                ici_bytes=200.0, dcn_bytes=100.0, ef_residual_dcn=0.5,
                steps=10,
            )
            snap = telemetry.default().snapshot()
            assert snap["train_comm_ici_bytes_total"] == 2000.0
            assert snap["train_comm_dcn_bytes_total"] == 1000.0
            assert snap["train_ef_residual_dcn"] == 0.5
            # Disabled: one bool check, no mutation.
            telemetry.reset()
            telemetry.record_comm(ici_bytes=1.0, dcn_bytes=1.0)
            assert (
                "train_comm_dcn_bytes_total"
                not in telemetry.default().snapshot()
            )
        finally:
            telemetry.reset()

    def test_per_hop_slo_rule_watches_the_dcn_gauge(self):
        from batchai_retinanet_horovod_coco_tpu.obs import slo, telemetry

        rule = slo.ef_residual_spike(hop="dcn")
        assert rule.name == "ef_residual_spike_dcn"
        assert rule.metric == "train_ef_residual_dcn"
        telemetry.enable()
        try:
            registry = telemetry.Registry()
            gauge = registry.gauge("train_ef_residual_dcn", "test")
            monitor = slo.SloMonitor(
                registry, [slo.ef_residual_spike(factor=10.0, hop="dcn")],
                poll_interval=999,
            )
            for i in range(6):
                gauge.set(1.0 + 0.01 * i)
                assert monitor.check_once(now=float(i)) == []
            gauge.set(100.0)
            fired = monitor.check_once(now=10.0)
            assert [v["rule"] for v in fired] == ["ef_residual_spike_dcn"]
            assert monitor.check_once(now=11.0) == []
        finally:
            telemetry.disable()

    def test_per_hop_rule_silent_on_flat_runs(self):
        from batchai_retinanet_horovod_coco_tpu.obs import slo
        from batchai_retinanet_horovod_coco_tpu.obs.telemetry import (
            Registry,
        )

        registry = Registry()
        registry.gauge("train_ef_residual", "flat gauge").set
        monitor = slo.SloMonitor(
            registry, [slo.ef_residual_spike(hop="dcn")], poll_interval=999
        )
        for i in range(10):
            assert monitor.check_once(now=float(i)) == []


# ---------------------------------------------------------------------------
# 7. lint: rank-guarded hierarchical wrapper
# ---------------------------------------------------------------------------


def test_lint_bites_on_rank_guarded_hierarchical_reduce():
    from tests.unit.test_lint import run_rule

    result = run_rule(
        """
        import jax

        from batchai_retinanet_horovod_coco_tpu.comm import compress

        def step(flat, res, bucket, cfg, topo):
            if jax.process_index() == 0:
                flat, res, _ = compress.reduce_bucket_hierarchical(
                    flat, res, bucket, cfg, "data", topo
                )
            return flat
        """,
        "collective-safety",
    )
    assert len(result.findings) == 1
    assert "reduce_bucket_hierarchical" in result.findings[0].message


# ---------------------------------------------------------------------------
# 8. CLI mapping
# ---------------------------------------------------------------------------


class TestCliMapping:
    def _args(self, **kw):
        import argparse

        defaults = dict(
            comm_compress="none", comm_overlap=False, comm_bucket_mb=4.0,
            comm_no_error_feedback=False,
            comm_ici_mode=None, comm_dcn_mode=None, comm_dcn_bucket_mb=None,
            comm_slices=None,
        )
        defaults.update(kw)
        return argparse.Namespace(**defaults)

    def test_all_off_maps_to_no_config(self):
        from batchai_retinanet_horovod_coco_tpu.utils.cli import (
            make_comm_config,
        )

        assert make_comm_config(self._args()) is None

    def test_hop_flags_map_to_config(self):
        from batchai_retinanet_horovod_coco_tpu.utils.cli import (
            make_comm_config,
        )

        cfg = make_comm_config(
            self._args(
                comm_compress="int8", comm_dcn_mode="bf16",
                comm_dcn_bucket_mb=8.0,
            )
        )
        assert cfg.dcn_mode == "bf16"
        assert cfg.dcn_bucket_mb == 8.0
        assert cfg.effective_ici_mode == "none"

    def test_hop_only_policy_still_produces_a_config(self):
        from batchai_retinanet_horovod_coco_tpu.utils.cli import (
            make_comm_config,
        )

        cfg = make_comm_config(self._args(comm_dcn_mode="int8"))
        assert cfg is not None
        assert cfg.compress == "none" and cfg.dcn_mode == "int8"
        assert cfg.hierarchical_with(T24)

    def test_comm_flags_parse(self):
        import argparse

        from batchai_retinanet_horovod_coco_tpu.utils.cli import (
            add_comm_flags,
        )

        parser = argparse.ArgumentParser()
        add_comm_flags(parser)
        args = parser.parse_args(
            ["--comm-slices", "2", "--comm-dcn-mode", "int8",
             "--comm-dcn-bucket-mb", "8"]
        )
        assert args.comm_slices == 2
        assert args.comm_dcn_mode == "int8"
        assert args.comm_dcn_bucket_mb == 8.0

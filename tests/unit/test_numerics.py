"""Numerics flight recorder tests (ISSUE 10, obs/numerics.py).

The checklist, pinned:

- the in-step summary's metrics exist, are finite on a healthy step, and
  the update ratio matches the hand-computed ||new − old|| / ||new||;
- the disabled path is structurally free: the numerics-off step's
  metrics dict carries NO summary keys (same keys as pre-ISSUE-10);
- the pre-clip grad_norm metric equals a reference value_and_grad
  global norm, and ``clip_by_global_norm_precomputed`` is equivalent to
  ``optax.clip_by_global_norm`` with and without the precomputed norm;
- injected-NaN provenance: the abort lands ONE NUMERICS_DUMP.json
  naming the first non-finite layer + the batch source ids, without any
  rerun;
- the cadence boundary: a NaN appearing BETWEEN finite-checks is caught
  at the NEXT cadence step — never silently trained past it;
- pre-save gate and cadence check share the abort path (a poisoned
  state writes the dump AND never reaches disk);
- the cross-replica agreement probe: controlled per-device values give
  the exact min/max ratio; a mesh train step reports it;
- the built-in SLO rules: nonfinite fires EXACTLY ONCE and immediately,
  grad-norm-spike uses the regression baseline;
- ``debug.py nans`` is a thin driver over load_dump/format_dump.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from batchai_retinanet_horovod_coco_tpu.data.pipeline import Batch
from batchai_retinanet_horovod_coco_tpu.models import (
    RetinaNetConfig,
    build_retinanet,
)
from batchai_retinanet_horovod_coco_tpu.obs import numerics, telemetry, trace
from batchai_retinanet_horovod_coco_tpu.obs.numerics import NumericsConfig
from batchai_retinanet_horovod_coco_tpu.train import create_train_state
from batchai_retinanet_horovod_coco_tpu.train.loop import (
    LoopConfig,
    run_training,
)
from batchai_retinanet_horovod_coco_tpu.train.step import make_train_step

HW = (64, 64)
NUM_CLASSES = 3
BATCH = 4


@pytest.fixture(autouse=True)
def _clean_obs_state():
    telemetry.reset()
    trace.reset()
    yield
    telemetry.reset()
    trace.reset()


def tiny_model():
    return build_retinanet(
        RetinaNetConfig(
            num_classes=NUM_CLASSES, backbone="resnet_test",
            fpn_channels=16, head_width=16, head_depth=1,
            dtype=jnp.float32,
        )
    )


def fresh_state(model, seed=0, lr=1e-3):
    return create_train_state(
        model, optax.sgd(lr, momentum=0.9), (1, *HW, 3),
        jax.random.key(seed),
    )


def make_batch(rng_seed=0, nan=False):
    rng = np.random.default_rng(rng_seed)
    images = rng.normal(0, 1, (BATCH, *HW, 3)).astype(np.float32)
    if nan:
        images[0, 0, 0, 0] = np.nan
    return {
        "images": jnp.asarray(images),
        "gt_boxes": jnp.asarray(
            np.tile(np.array([[8.0, 8.0, 40.0, 40.0]], np.float32),
                    (BATCH, 1, 1))
        ),
        "gt_labels": jnp.ones((BATCH, 1), jnp.int32),
        "gt_mask": jnp.ones((BATCH, 1), bool),
    }


def batch_stream(nan_at_step=None, seed=0):
    rng = np.random.default_rng(seed)
    i = 0
    while True:
        i += 1
        images = rng.normal(0, 1, (BATCH, *HW, 3)).astype(np.float32)
        if nan_at_step is not None and i == nan_at_step:
            images[0, 0, 0, 0] = np.nan
        yield Batch(
            images=images,
            gt_boxes=np.tile(
                np.array([[8.0, 8.0, 40.0, 40.0]], np.float32),
                (BATCH, 1, 1),
            ),
            gt_labels=np.ones((BATCH, 1), np.int32),
            gt_mask=np.ones((BATCH, 1), bool),
            image_ids=np.arange(BATCH, dtype=np.int64) + i * 100,
            scales=np.ones((BATCH,), np.float32),
            valid=np.ones((BATCH,), bool),
        )


class TestInStepSummary:
    def test_summary_keys_present_and_update_ratio_exact(self):
        model = tiny_model()
        state = fresh_state(model)
        step = make_train_step(
            model, HW, NUM_CLASSES, donate_state=False,
            numerics=NumericsConfig(enabled=True),
        )
        new_state, metrics = step(state, make_batch())
        for key in ("grad_norm", "update_ratio", "nonfinite_grads"):
            assert key in metrics
        groups = {k for k in metrics if k.startswith("gnorm/")}
        assert groups == {
            "gnorm/backbone", "gnorm/fpn", "gnorm/cls_head",
            "gnorm/box_head",
        }
        assert float(metrics["nonfinite_grads"]) == 0.0
        # Hand-computed ratio from the actual param trees.
        diff_sq = sum(
            float(jnp.sum(jnp.square(n - o)))
            for n, o in zip(
                jax.tree.leaves(new_state.params),
                jax.tree.leaves(state.params),
            )
        )
        expected = np.sqrt(diff_sq) / float(metrics["param_norm"])
        assert float(metrics["update_ratio"]) == pytest.approx(
            expected, rel=1e-4
        )

    def test_disabled_path_adds_no_keys(self):
        """The pre-ISSUE-10 metric vocabulary is unchanged with numerics
        off — the gate is compile-time, not a runtime branch."""
        model = tiny_model()
        step = make_train_step(model, HW, NUM_CLASSES, donate_state=False)
        _, metrics = step(fresh_state(model), make_batch())
        assert set(metrics) == {
            "loss", "cls_loss", "box_loss", "num_pos", "grad_norm",
            "param_norm",
        }

    def test_grad_norm_matches_reference(self):
        """The recorded pre-clip norm equals an independent global_norm
        of the raw gradients (the clip shares it, never recomputes)."""
        from batchai_retinanet_horovod_coco_tpu.train.step import (
            _forward_and_loss,
        )
        from batchai_retinanet_horovod_coco_tpu import losses as losses_lib
        from batchai_retinanet_horovod_coco_tpu.ops import (
            anchors as anchors_lib,
            matching as matching_lib,
        )

        model = tiny_model()
        state = fresh_state(model)
        batch = make_batch()
        step = make_train_step(
            model, HW, NUM_CLASSES, donate_state=False,
            numerics=NumericsConfig(enabled=True),
        )
        _, metrics = step(state, batch)
        anchors = jnp.asarray(
            anchors_lib.anchors_for_image_shape(
                HW, anchors_lib.AnchorConfig()
            )
        )
        _, grads = jax.value_and_grad(
            lambda p: _forward_and_loss(
                model, state, p, batch["images"], batch["gt_boxes"],
                batch["gt_labels"], batch["gt_mask"], anchors,
                losses_lib.LossConfig(pallas_focal=False),
                matching_lib.MatchingConfig(fused_pallas=False),
                train=True,
            )[0],
            has_aux=False,
        )(state.params)
        assert float(metrics["grad_norm"]) == pytest.approx(
            float(optax.global_norm(grads)), rel=1e-5
        )

    def test_nonfinite_count_detects_poison(self):
        model = tiny_model()
        step = make_train_step(
            model, HW, NUM_CLASSES, donate_state=False,
            numerics=NumericsConfig(enabled=True),
        )
        _, metrics = step(fresh_state(model), make_batch(nan=True))
        assert float(metrics["nonfinite_grads"]) > 0
        assert not np.isfinite(float(metrics["loss"]))


class TestPrecomputedClip:
    def test_equivalent_to_optax_clip(self):
        from batchai_retinanet_horovod_coco_tpu.train.optim import (
            clip_by_global_norm_precomputed,
        )

        grads = {"w": jnp.array([3.0, 4.0]), "b": jnp.zeros(2)}  # norm 5
        for max_norm in (1.0, 10.0):  # clipping engaged / not engaged
            ref, _ = optax.clip_by_global_norm(max_norm).update(
                grads, optax.EmptyState()
            )
            mine = clip_by_global_norm_precomputed(max_norm)
            got_implicit, _ = mine.update(grads, optax.EmptyState())
            got_explicit, _ = mine.update(
                grads, optax.EmptyState(),
                grad_norm=optax.global_norm(grads),
            )
            for got in (got_implicit, got_explicit):
                jax.tree.map(
                    np.testing.assert_allclose, got, ref
                )

    def test_make_optimizer_chain_consumes_grad_norm(self):
        """The unmasked production chain (clip + sgd + plateau) forwards
        grad_norm and clips by the SUPPLIED value (the proof it consumes
        the precomputed one, not a recomputation)."""
        from batchai_retinanet_horovod_coco_tpu.train.optim import (
            OptimizerConfig,
            make_optimizer,
        )

        cfg = OptimizerConfig(
            optimizer="sgd", schedule="plateau", warmup_steps=0,
            total_steps=10, clip_global_norm=1.0,
            momentum=0.0, weight_decay=0.0,
        )
        tx, _ = make_optimizer(cfg)
        params = {"head": jnp.array([3.0, 4.0])}
        opt_state = tx.init(params)
        grads = {"head": jnp.array([3.0, 4.0])}  # true norm 5
        updates, _ = tx.update(
            grads, opt_state, params,
            value=jnp.asarray(1.0), grad_norm=jnp.asarray(10.0),  # a lie
        )
        got = np.abs(np.asarray(updates["head"]))
        lr = cfg.base_lr * cfg.global_batch_size / 256.0
        np.testing.assert_allclose(  # scaled by 1/10, not 1/5
            got, np.array([0.3, 0.4]) * lr, rtol=1e-5
        )

    def test_freeze_masked_chain_ignores_full_tree_norm(self):
        """Review-round regression pin: under --freeze-backbone the clip
        inside multi_transform sees only the trained SUBTREE, so the
        step's full-tree grad_norm must be IGNORED — forwarding it would
        clip trained params by a norm inflated with frozen-backbone
        gradients (a silent effective-LR collapse)."""
        from batchai_retinanet_horovod_coco_tpu.train.optim import (
            OptimizerConfig,
            make_optimizer,
        )

        cfg = OptimizerConfig(
            optimizer="sgd", warmup_steps=0, total_steps=10,
            freeze_backbone=True, clip_global_norm=1.0,
            momentum=0.0, weight_decay=0.0, schedule="constant",
        )
        tx, _ = make_optimizer(cfg)
        params = {
            "backbone": jnp.full((4,), 100.0), "head": jnp.array([0.1, 0.12])
        }
        opt_state = tx.init(params)
        # Huge frozen gradient, tiny trained one: the full-tree norm is
        # ~200 while the trained subtree's is ~0.16 (below the clip).
        grads = {
            "backbone": jnp.full((4,), 100.0),
            "head": jnp.array([0.1, 0.12]),
        }
        full_norm = optax.global_norm(grads)
        updates, _ = tx.update(
            grads, opt_state, params, grad_norm=full_norm
        )
        np.testing.assert_allclose(np.asarray(updates["backbone"]), 0.0)
        # Reference: the stock optax clip over the trained subtree only
        # (no clipping engages at norm 0.16 < 1.0) — the pre-ISSUE-10
        # semantics the freeze path must keep.
        lr = cfg.base_lr * cfg.global_batch_size / 256.0
        np.testing.assert_allclose(
            np.abs(np.asarray(updates["head"])),
            np.array([0.1, 0.12]) * lr,
            rtol=1e-5,
        )


class TestProvenance:
    def test_injected_nan_writes_dump_with_layer_and_ids(self, tmp_path):
        model = tiny_model()
        with pytest.raises(FloatingPointError, match="provenance dump"):
            run_training(
                model, fresh_state(model), batch_stream(nan_at_step=2),
                NUM_CLASSES,
                LoopConfig(
                    total_steps=4, log_every=1, numerics=True,
                    numerics_dump_dir=str(tmp_path), rng_seed=7,
                ),
            )
        dump = json.loads(
            (tmp_path / "NUMERICS_DUMP.json").read_text()
        )
        assert dump["step"] == 2
        assert dump["tripped"]["metric"] == "loss"
        # NaN images poison everything downstream: the first non-finite
        # layer in forward order is in the backbone (the stem).
        assert "backbone" in str(dump["first_nonfinite"])
        # Step 2's batch fed the trip (ids are 100*step + i).
        assert dump["batch_image_ids"] == [200, 201, 202, 203]
        assert dump["rng_seed"] == 7
        assert dump["forward"]["nonfinite_layers"] > 0

    def test_cadence_boundary_catches_at_next_check(self, monkeypatch):
        """A NaN appearing BETWEEN checks (step 2; cadence 4) trains
        through AT MOST until the next cadence step, where it aborts —
        never silently past it (the recorded ISSUE-10 satellite)."""
        from batchai_retinanet_horovod_coco_tpu.train import loop as loop_mod

        monkeypatch.setattr(loop_mod, "_FINITE_CHECK_EVERY", 4)
        model = tiny_model()
        with pytest.raises(
            FloatingPointError, match="at or before step 4"
        ):
            run_training(
                model, fresh_state(model), batch_stream(nan_at_step=2),
                NUM_CLASSES,
                LoopConfig(total_steps=50, log_every=0),
            )

    def test_pre_save_gate_dumps_and_never_checkpoints(self, tmp_path):
        """Both the ISSUE-10 satellite pins in one scenario: the
        pre-save check goes through the SAME abort path (dump written)
        and the poisoned state never reaches disk."""
        from batchai_retinanet_horovod_coco_tpu.utils.checkpoint import (
            latest_step,
        )

        model = tiny_model()
        state = create_train_state(
            model, optax.sgd(float("inf")), (1, *HW, 3), jax.random.key(0)
        )
        ckpt_dir = str(tmp_path / "ckpt")
        dump_dir = str(tmp_path / "obs")
        with pytest.raises(FloatingPointError):
            run_training(
                model, state, batch_stream(), NUM_CLASSES,
                LoopConfig(
                    total_steps=10, log_every=0, checkpoint_every=1,
                    checkpoint_dir=ckpt_dir, numerics_dump_dir=dump_dir,
                ),
            )
        assert latest_step(ckpt_dir) is None
        dump = json.loads(
            open(os.path.join(dump_dir, "NUMERICS_DUMP.json")).read()
        )
        # LR=inf poisons the params via the update: param_norm trips.
        assert dump["tripped"]["metric"] == "param_norm"
        assert dump["params"]["nonfinite_total"] > 0

    def test_forward_provenance_clean_and_poisoned(self):
        model = tiny_model()
        state = fresh_state(model)
        variables = {"params": state.params}
        clean = numerics.forward_provenance(
            model, variables, make_batch()["images"]
        )
        assert clean["nonfinite_layers"] == 0
        assert clean["first_nonfinite_layer"] is None
        poisoned = numerics.forward_provenance(
            model, variables, make_batch(nan=True)["images"]
        )
        assert poisoned["nonfinite_layers"] > 0
        assert "backbone" in poisoned["first_nonfinite_layer"]

    def test_first_nonfinite_scalar_root_cause_order(self):
        hit = numerics.first_nonfinite_scalar(
            {"loss": float("nan"), "cls_loss": float("nan"), "lr": 0.1}
        )
        assert hit[0] == "cls_loss"  # more specific than the total
        assert numerics.first_nonfinite_scalar({"loss": 1.0}) is None

    def test_dump_format_and_debug_cli(self, tmp_path, capsys):
        import sys

        dump = {
            "step": 7,
            "tripped": {"metric": "loss", "value": float("nan")},
            "first_nonfinite": "['backbone']['stem_conv']",
            "batch_image_ids": [1, 2],
            "rng_seed": 0,
            "metrics": {"loss": float("nan"), "num_pos": 3.0},
            "params": {
                "nonfinite_total": 5,
                "entries": {
                    "['backbone']['stem_conv']['kernel']": {
                        "size": 10, "nonfinite": 5, "nan": 5, "inf": 0,
                    }
                },
            },
        }
        text = numerics.format_dump(dump)
        assert "step 7" in text
        assert "stem_conv" in text
        assert "batch image ids: 1, 2" in text
        path = tmp_path / "NUMERICS_DUMP.json"
        numerics.write_dump(dump, str(tmp_path))
        assert path.exists()
        repo_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        if repo_root not in sys.path:
            sys.path.insert(0, repo_root)
        from debug import main as debug_main

        out = debug_main(["nans", str(path)])
        assert out[0]["step"] == 7
        assert "stem_conv" in capsys.readouterr().out


class TestReplicaAgreement:
    def test_controlled_values_exact_ratio(self):
        from functools import partial

        from jax.sharding import PartitionSpec as P

        from batchai_retinanet_horovod_coco_tpu.parallel import make_mesh
        from batchai_retinanet_horovod_coco_tpu.parallel.mesh import (
            DATA_AXIS,
        )
        from jax import shard_map

        mesh = make_mesh(8)
        norms = jnp.array([1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 4.0])

        @partial(
            shard_map, mesh=mesh, in_specs=(P(DATA_AXIS),),
            out_specs=P(DATA_AXIS), check_vma=False,
        )
        def probe(n):
            return jnp.reshape(
                numerics.replica_agreement(n[0], DATA_AXIS), (1,)
            )

        out = np.asarray(probe(norms))
        np.testing.assert_allclose(out, 0.25, rtol=1e-6)

    def test_mesh_train_step_reports_agreement(self):
        from batchai_retinanet_horovod_coco_tpu.parallel import make_mesh

        model = tiny_model()
        state = fresh_state(model)
        rng = np.random.default_rng(0)
        b8 = {
            "images": jnp.asarray(
                rng.normal(0, 1, (8, *HW, 3)).astype(np.float32)
            ),
            "gt_boxes": jnp.asarray(
                np.tile(
                    np.array([[8.0, 8.0, 40.0, 40.0]], np.float32),
                    (8, 1, 1),
                )
            ),
            "gt_labels": jnp.ones((8, 1), jnp.int32),
            "gt_mask": jnp.ones((8, 1), bool),
        }
        step = make_train_step(
            model, HW, NUM_CLASSES, mesh=make_mesh(8), donate_state=False,
            numerics=NumericsConfig(enabled=True),
        )
        _, metrics = step(state, b8)
        agreement = float(metrics["replica_agreement"])
        assert 0.0 < agreement <= 1.0


class TestSloRules:
    def test_nonfinite_fires_exactly_once_and_immediately(self):
        from batchai_retinanet_horovod_coco_tpu.obs import slo
        from batchai_retinanet_horovod_coco_tpu.obs.telemetry import (
            Registry,
        )

        reg = Registry()
        monitor = slo.SloMonitor(reg, [slo.nonfinite_rule()])
        telemetry.enable()
        counter = reg.counter("train_nonfinite_total", "")
        assert monitor.check_once(now=0.0) == []  # healthy: no metric yet
        counter.inc(3.0)
        fired = monitor.check_once(now=1.0)
        assert [v["rule"] for v in fired] == ["train-nonfinite"]
        # Latched: the (monotonic) counter keeps the breach alive, so no
        # second fire over the rest of the run.
        assert monitor.check_once(now=2.0) == []
        assert monitor.check_once(now=100.0) == []

    def test_record_nonfinite_trip_feeds_the_rule(self):
        from batchai_retinanet_horovod_coco_tpu.obs import slo

        telemetry.enable()
        telemetry.record_nonfinite_trip("loss")
        monitor = slo.SloMonitor(telemetry.default(), [slo.nonfinite_rule()])
        fired = monitor.check_once(now=0.0)
        assert len(fired) == 1 and fired[0]["rule"] == "train-nonfinite"

    def test_grad_norm_spike_regression_mode(self):
        from batchai_retinanet_horovod_coco_tpu.obs import slo
        from batchai_retinanet_horovod_coco_tpu.obs.telemetry import (
            Registry,
        )

        reg = Registry()
        telemetry.enable()
        gauge = reg.gauge("train_grad_norm", "")
        rule = slo.grad_norm_spike(factor=10.0, window=8)
        monitor = slo.SloMonitor(reg, [rule])
        for i in range(6):  # build the healthy baseline (median ~2)
            gauge.set(2.0 + 0.01 * i)
            assert monitor.check_once(now=float(i)) == []
        gauge.set(50.0)  # 25x the median
        fired = monitor.check_once(now=10.0)
        assert [v["rule"] for v in fired] == ["grad-norm-spike"]

    def test_record_numerics_sets_gauges_and_counts(self):
        telemetry.enable()
        telemetry.record_numerics(
            grad_norm=2.5, update_ratio=1e-3, nonfinite=0.0,
            replica_agreement=0.9,
        )
        snap = telemetry.default().snapshot()
        assert snap["train_grad_norm"] == 2.5
        assert snap["train_update_ratio"] == 1e-3
        assert snap["train_replica_agreement"] == 0.9
        assert "train_nonfinite_total" not in snap  # zero = no incident
        telemetry.record_numerics(nonfinite=4.0)
        assert (
            telemetry.default().snapshot()["train_nonfinite_total"] == 4.0
        )

    def test_record_sites_noop_while_disabled(self):
        telemetry.record_numerics(grad_norm=1.0, nonfinite=9.0)
        telemetry.record_nonfinite_trip("loss")
        assert telemetry.default().snapshot().get("train_grad_norm") is None
        assert (
            telemetry.default().snapshot().get("train_nonfinite_total")
            is None
        )


class TestAnalyzerNumerics:
    def _events_file(self, tmp_path, records):
        path = tmp_path / "metrics.jsonl"
        lines = [json.dumps({"event": "run_header", "run_id": "abc"})]
        lines += [json.dumps(r) for r in records]
        path.write_text("\n".join(lines) + "\n")
        return str(path)

    def test_numerics_section_and_divergence_rank_one(self, tmp_path):
        from batchai_retinanet_horovod_coco_tpu.obs.analyze import (
            analyze_events,
            validate_report,
        )

        events = [
            {"ph": "X", "name": "step", "ts": 0, "dur": 1000, "pid": 1,
             "tid": 1},
            {"ph": "i", "name": "numerics_trip", "ts": 900,
             "args": {"metric": "loss", "step": 3}},
            {"ph": "i", "name": "slo_violation", "ts": 950,
             "args": {"rule": "train-nonfinite",
                      "metric": "train_nonfinite_total", "value": 1.0,
                      "threshold": 0.0, "sustained_s": 0.0}},
        ]
        records = [
            {"event": "numerics", "step": 2, "grad_norm": 2.0,
             "update_ratio": 1e-3, "nonfinite_grads": 0.0},
            {"event": "numerics", "step": 3, "grad_norm": 7.0,
             "update_ratio": 2e-3, "nonfinite_grads": 5.0},
            {"event": "numerics_trip", "metric": "loss", "step": 3,
             "value": float("nan")},
        ]
        dump_path = tmp_path / "NUMERICS_DUMP.json"
        dump_path.write_text(json.dumps({
            "step": 3,
            "first_nonfinite": "['backbone']['stem_conv']",
            "tripped": {"metric": "loss", "value": None},
        }))
        report = analyze_events(
            events,
            events_path=self._events_file(tmp_path, records),
            dump_path=str(dump_path),
        )
        assert validate_report(report) == []
        num = report["numerics"]
        assert num["available"]
        assert num["records"] == 2
        assert num["grad_norm"]["max"] == 7.0
        assert num["nonfinite_total"] == 5.0
        assert num["trips"]["count"] == 1
        assert num["dump"]["first_nonfinite"] == (
            "['backbone']['stem_conv']"
        )
        # The divergence verdict outranks the slo:* verdict AND the
        # inferred device_step bottleneck.
        names = [b["name"] for b in report["bottlenecks"]]
        assert names[0] == "numerics:divergence"
        assert any(n.startswith("slo:") for n in names[1:])
        assert report["bottlenecks"][0]["rank"] == 1

    def test_healthy_run_has_no_divergence_verdict(self, tmp_path):
        from batchai_retinanet_horovod_coco_tpu.obs.analyze import (
            analyze_events,
        )

        events = [
            {"ph": "X", "name": "step", "ts": 0, "dur": 1000, "pid": 1,
             "tid": 1},
        ]
        records = [
            {"event": "numerics", "step": 2, "grad_norm": 2.0,
             "update_ratio": 1e-3, "nonfinite_grads": 0.0},
        ]
        report = analyze_events(
            events, events_path=self._events_file(tmp_path, records)
        )
        assert report["numerics"]["available"]
        assert report["numerics"]["trips"]["count"] == 0
        assert not any(
            b["name"].startswith("numerics:")
            for b in report["bottlenecks"]
        )


class TestTreeHelpers:
    def test_tree_report_localizes_first_leaf(self):
        tree = {
            "backbone": {"w": jnp.array([1.0, float("nan")])},
            "fpn": {"w": jnp.array([float("inf"), 2.0])},
        }
        rep = numerics.tree_report(tree)
        assert rep["nonfinite_total"] == 2
        assert "backbone" in rep["first_nonfinite"]
        entry = rep["entries"][rep["first_nonfinite"]]
        assert entry["nan"] == 1 and entry["inf"] == 0

    def test_tree_all_finite(self):
        assert numerics.tree_all_finite({"a": jnp.ones(3)})
        assert not numerics.tree_all_finite(
            {"a": jnp.array([1.0, float("nan")])}
        )

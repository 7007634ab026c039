"""Training-loop tests: stepping, logging, checkpoint-resume mid-run.

The resume test is the §5.3 fault-recovery story: kill a run after N steps,
restart from the latest checkpoint, and the loop continues from there.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from batchai_retinanet_horovod_coco_tpu.data.pipeline import Batch
from batchai_retinanet_horovod_coco_tpu.models import RetinaNetConfig, build_retinanet
from batchai_retinanet_horovod_coco_tpu.parallel import make_mesh
from batchai_retinanet_horovod_coco_tpu.train import create_train_state
from batchai_retinanet_horovod_coco_tpu.train.loop import LoopConfig, run_training
from batchai_retinanet_horovod_coco_tpu.obs.events import EventSink

HW = (64, 64)
NUM_CLASSES = 3
BATCH = 8


def tiny_model():
    return build_retinanet(
        RetinaNetConfig(
            num_classes=NUM_CLASSES, backbone="resnet_test", fpn_channels=16,
            head_width=16, head_depth=1, dtype=jnp.float32,
        )
    )


def fresh_state(model, seed=0):
    return create_train_state(
        model, optax.sgd(1e-3, momentum=0.9), (1, *HW, 3), jax.random.key(seed)
    )


def batch_stream(seed=0):
    # One fixed batch repeated forever: keeps the resume-parity test exact
    # (step k sees the same data in the resumed and uninterrupted runs).
    rng = np.random.default_rng(seed)
    images = rng.normal(0, 1, (BATCH, *HW, 3)).astype(np.float32)
    gt_boxes = np.tile(
        np.array([[8.0, 8.0, 40.0, 40.0]], np.float32), (BATCH, 1, 1)
    )
    while True:
        yield Batch(
            images=images,
            gt_boxes=gt_boxes,
            gt_labels=np.ones((BATCH, 1), np.int32),
            gt_mask=np.ones((BATCH, 1), bool),
            image_ids=np.arange(BATCH, dtype=np.int64),
            scales=np.ones((BATCH,), np.float32),
            valid=np.ones((BATCH,), bool),
        )


class TestRunTraining:
    def test_steps_and_jsonl_logging(self, tmp_path):
        model = tiny_model()
        logger = EventSink(str(tmp_path), stdout=False)
        state = run_training(
            model, fresh_state(model), batch_stream(), NUM_CLASSES,
            LoopConfig(total_steps=4, log_every=2), logger=logger,
        )
        logger.close()
        assert int(state.step) == 4
        lines = [
            json.loads(l)
            for l in (tmp_path / "metrics.jsonl").read_text().splitlines()
        ]
        # The sink opens with a run_header record (ISSUE 3: run delimiter
        # for append-mode files) and may emit structured events (compile);
        # the step-metric records keep their historical shape.
        assert lines[0]["event"] == "run_header" and "run_id" in lines[0]
        metric_lines = [l for l in lines if "step" in l and "event" not in l]
        assert [l["step"] for l in metric_lines] == [2, 4]
        assert all(np.isfinite(l["train/loss"]) for l in metric_lines)
        assert all("train/images_per_sec" in l for l in metric_lines)

    def test_mesh_loop_runs(self):
        model = tiny_model()
        state = run_training(
            model, fresh_state(model), batch_stream(), NUM_CLASSES,
            LoopConfig(total_steps=2, log_every=10), mesh=make_mesh(8),
        )
        assert int(state.step) == 2

    def test_eval_hook_called(self):
        calls = []

        def eval_fn(state):
            calls.append(int(state.step))
            return {"mAP": 0.0}

        model = tiny_model()
        run_training(
            model, fresh_state(model), batch_stream(), NUM_CLASSES,
            LoopConfig(total_steps=4, log_every=10, eval_every=2),
            eval_fn=eval_fn,
        )
        assert calls == [2, 4]  # mid-run + final (final not duplicated)

    def test_checkpoint_resume_continues(self, tmp_path):
        model = tiny_model()
        ckpt_dir = str(tmp_path / "ckpt")
        cfg = dict(log_every=100, checkpoint_every=1, checkpoint_dir=ckpt_dir)

        # Run 1: 3 steps, then "crash".
        s1 = run_training(
            model, fresh_state(model), batch_stream(), NUM_CLASSES,
            LoopConfig(total_steps=3, **cfg),
        )
        # Run 2: fresh state, resumes at 3, continues to 5.
        template = fresh_state(model, seed=99)
        s2 = run_training(
            model, template, batch_stream(), NUM_CLASSES,
            LoopConfig(total_steps=5, **cfg),
        )
        assert int(s2.step) == 5
        # The template's buffers were released as the restored leaves
        # arrived: a state that fills the chip is never held twice.
        assert all(x.is_deleted() for x in jax.tree.leaves(template))

        # Bitwise parity: an uninterrupted 5-step run from the same init and
        # the same stream yields the resumed run's params exactly (the data
        # stream here is stateless per step, so resume sees the same batches).
        s_full = run_training(
            model, fresh_state(model), batch_stream(), NUM_CLASSES,
            LoopConfig(total_steps=5, log_every=100),
        )
        jax.tree.map(
            np.testing.assert_array_equal, s2.params, s_full.params
        )


def test_non_finite_loss_aborts_with_step_number():
    """SURVEY.md §5.2 numerical sanitizer: LR=inf poisons the params in the
    first update; the post-update param_norm sentinel catches it AT step 1
    (the step-2 loss would be the first pre-update witness) and the loop
    aborts instead of training garbage."""
    model = tiny_model()
    state = create_train_state(
        model, optax.sgd(float("inf")), (1, *HW, 3), jax.random.key(0)
    )
    with pytest.raises(FloatingPointError, match="before step 1"):
        run_training(
            model,
            state,
            batch_stream(),
            NUM_CLASSES,
            LoopConfig(total_steps=3, log_every=1),
        )


def test_non_finite_abort_fires_early_with_log_every_zero(monkeypatch):
    """log_every=0 must NOT defer the sanitizer to the final step: the loop
    checks every _FINITE_CHECK_EVERY steps regardless (shrunk here so the
    test stays cheap)."""
    from batchai_retinanet_horovod_coco_tpu.train import loop as loop_mod

    monkeypatch.setattr(loop_mod, "_FINITE_CHECK_EVERY", 2)
    model = tiny_model()
    state = create_train_state(
        model, optax.sgd(float("inf")), (1, *HW, 3), jax.random.key(0)
    )
    with pytest.raises(FloatingPointError, match="before step 2"):
        run_training(
            model,
            state,
            batch_stream(),
            NUM_CLASSES,
            LoopConfig(total_steps=50, log_every=0),
        )  # step 1 has no check (1 % 2 != 0, no save); step 2 aborts


def test_non_finite_state_never_checkpointed(tmp_path):
    """The abort runs BEFORE each checkpoint save and checks the
    POST-update param_norm, so a state poisoned by this very step's update
    never reaches disk — auto-resume can only ever see finite params
    (ADVICE r2; the pre-update loss alone would have let step 1's poisoned
    snapshot through)."""
    from batchai_retinanet_horovod_coco_tpu.utils.checkpoint import latest_step

    model = tiny_model()
    state = create_train_state(
        model, optax.sgd(float("inf")), (1, *HW, 3), jax.random.key(0)
    )
    ckpt_dir = str(tmp_path / "ckpt")
    with pytest.raises(FloatingPointError):
        run_training(
            model,
            state,
            batch_stream(),
            NUM_CLASSES,
            LoopConfig(
                total_steps=10,
                log_every=0,
                checkpoint_every=1,
                checkpoint_dir=ckpt_dir,
            ),
        )
    # Step 1's update already poisoned the params; its param_norm sentinel
    # must have aborted before ANY snapshot landed.
    assert latest_step(ckpt_dir) is None


def test_debug_nans_flag_parses():
    import os
    import sys

    repo_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    if repo_root not in sys.path:
        sys.path.insert(0, repo_root)
    from train import parse_args

    args = parse_args(["synthetic", "--debug-nans"])
    assert args.debug_nans is True
    assert parse_args(["synthetic"]).debug_nans is False


class _RaisingLowerStep:
    """Step wrapper whose AOT ``lower`` raises — a stand-in for a genuine
    compile failure (bad sharding spec, OOM during compilation, ...)."""

    def lower(self, state, device_arrays):
        raise RuntimeError("compile exploded")

    def __call__(self, state, device_arrays):  # pragma: no cover
        raise AssertionError("step must not be dispatched")


def test_compile_barrier_propagates_compile_failure(monkeypatch):
    """A real compile error must RAISE out of _compile_barrier, not degrade
    to a warning: swallowing it defeats the barrier (healthy peers would
    time out in the step's collectives while this process dies later with
    a confusing secondary error).  Only the no-AOT-surface / no-client
    cases skip (ADVICE r3, VERDICT r3 weak #5)."""
    from batchai_retinanet_horovod_coco_tpu.train import loop as loop_mod

    monkeypatch.setattr(loop_mod.jax, "process_count", lambda: 2)
    with pytest.raises(RuntimeError, match="compile exploded"):
        loop_mod._compile_barrier(_RaisingLowerStep(), None, None, (64, 64))


def test_compile_barrier_skips_without_aot_surface(monkeypatch):
    """A plain callable without ``lower`` (no AOT surface) skips silently."""
    from batchai_retinanet_horovod_coco_tpu.train import loop as loop_mod

    monkeypatch.setattr(loop_mod.jax, "process_count", lambda: 2)
    loop_mod._compile_barrier(lambda s, d: (s, {}), None, None, (64, 64))


def test_compile_barrier_noop_single_process():
    """Single-process runs never touch the AOT surface or the client."""
    from batchai_retinanet_horovod_coco_tpu.train import loop as loop_mod

    assert jax.process_count() == 1
    loop_mod._compile_barrier(_RaisingLowerStep(), None, None, (64, 64))


def test_mixed_bucket_stream_compiles_per_shape():
    """The multiscale pipeline emits MULTIPLE (H, W) buckets in one run;
    the loop must compile one step per bucket and keep training across
    alternating shapes (SURVEY.md §7.3 hard part 1).  No prior test
    streamed more than one bucket through run_training."""
    model = tiny_model()
    state = fresh_state(model)

    shapes = [(64, 64), (64, 96)]

    def stream():
        rng = np.random.default_rng(0)
        i = 0
        while True:
            h, w = shapes[i % len(shapes)]
            i += 1
            yield Batch(
                images=rng.normal(0, 1, (2, h, w, 3)).astype(np.float32),
                gt_boxes=np.tile(
                    np.array([[8.0, 8.0, 40.0, 40.0]], np.float32), (2, 1, 1)
                ),
                gt_labels=np.ones((2, 1), np.int32),
                gt_mask=np.ones((2, 1), bool),
                image_ids=np.arange(2, dtype=np.int64),
                scales=np.ones((2,), np.float32),
                valid=np.ones((2,), bool),
            )

    class CapturingLogger:
        def __init__(self):
            self.records = []

        def log(self, step, metrics, prefix="train"):
            self.records.append((step, prefix, dict(metrics)))

    logger = CapturingLogger()
    out = run_training(
        model, state, stream(), NUM_CLASSES,
        LoopConfig(total_steps=4, log_every=1), logger=logger,
    )
    assert int(out.step) == 4
    # Both buckets trained (each shape ran twice) and stayed finite.
    train_recs = [r for r in logger.records if r[1] == "train"]
    assert len(train_recs) == 4
    assert all(np.isfinite(float(r[2]["loss"])) for r in train_recs)


class TestProfilerAnnotations:
    def test_profile_dir_trace_holds_the_programs_spans(self, tmp_path):
        """``--profile-dir``'s session (no Python tracing, the HLO proto left
        on) records the loop's own spans as ``rn.*`` annotations on the host
        plane, with the obs ring never enabled."""
        import glob

        from jax.profiler import ProfileData

        from batchai_retinanet_horovod_coco_tpu.obs import trace
        from batchai_retinanet_horovod_coco_tpu.train import loop

        trace.reset()
        trace.install_annotation_factory(jax.profiler.TraceAnnotation)
        options = loop._profile_options()
        assert (options.host_tracer_level, options.python_tracer_level) == (1, 0)
        assert options.enable_hlo_proto is True
        model = tiny_model()
        run_training(
            model, fresh_state(model), batch_stream(), NUM_CLASSES,
            LoopConfig(total_steps=5, log_every=5, profile_dir=str(tmp_path),
                       profile_start_step=2, profile_steps=3),
        )
        assert not trace.enabled()
        (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
        names = [
            e.name
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines
            for e in line.events
            if e.name.startswith("rn.")
        ]
        # steps 2..4 are inside the session; the prefetch thread runs ahead
        assert names.count("rn.step") == 3 and names.count("rn.data_wait") >= 2
        assert "rn.device-prefetch" in names
        assert not [n for n in names if n.startswith("rn.rn.")]

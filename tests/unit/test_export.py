"""Inference-artifact export/reload (the convert_model.py equivalent).

The contract (SURVEY.md M3): a converted artifact must reproduce the live
detection path — forward, decode, clip, on-device NMS — without the training
code, like the reference's inference ``.h5``.  Round-trip equality against
``make_detect_fn`` is the oracle.
"""

import numpy as np
import pytest

from batchai_retinanet_horovod_coco_tpu.evaluate.detect import (
    DetectConfig,
    make_detect_fn,
)
from batchai_retinanet_horovod_coco_tpu.evaluate.export import (
    export_model,
    load_model,
)

CONFIG = DetectConfig(pre_nms_size=64, max_detections=10)


def test_roundtrip_matches_live_detection(tiny_model_and_state, tmp_path):
    model, state = tiny_model_and_state
    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, (2, 64, 64, 3), dtype=np.uint8)

    manifest_path = export_model(
        state, model, str(tmp_path / "exp"), buckets=((64, 64),),
        batch_size=2, config=CONFIG, class_names=["a", "b", "c"],
        label_to_cat_id={0: 1, 1: 2, 2: 3},
    )
    assert manifest_path.endswith("manifest.json")

    loaded = load_model(str(tmp_path / "exp"))
    assert loaded.buckets() == [(2, 64, 64)]
    assert loaded.manifest["class_names"] == ["a", "b", "c"]

    got = loaded(images)
    want = make_detect_fn(model, (64, 64), CONFIG)(state, images)
    for g, w, name in zip(got, want, ("boxes", "scores", "labels", "valid")):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w), err_msg=name
        )


def test_export_multiple_batch_sizes(tiny_model_and_state, tmp_path):
    """One artifact per (bucket, batch size); the manifest records the
    inference resize rule for manifest-driven serve routing (ISSUE 4)."""
    model, state = tiny_model_and_state
    export_model(
        state, model, str(tmp_path / "exp"), buckets=((64, 64),),
        batch_size=(1, 2), config=CONFIG,
        image_min_side=64, image_max_side=64,
    )
    loaded = load_model(str(tmp_path / "exp"))
    assert loaded.buckets() == [(1, 64, 64), (2, 64, 64)]
    assert loaded.bucket_shapes() == [(64, 64)]
    assert loaded.batch_sizes((64, 64)) == [1, 2]
    assert loaded.manifest["image_min_side"] == 64
    assert loaded.manifest["image_max_side"] == 64
    # both programs run; warmup touches every one
    loaded.warmup()
    for b in (1, 2):
        out = loaded(np.zeros((b, 64, 64, 3), dtype=np.uint8))
        assert np.asarray(out[0]).shape[0] == b


_NO_IMPORT_LOADER = """
import json, os, sys
import numpy as np
from jax import export as jax_export

export_dir, in_npz, out_npz = sys.argv[1:4]
with open(os.path.join(export_dir, "manifest.json")) as f:
    manifest = json.load(f)
entry = manifest["artifacts"][0]
with open(os.path.join(export_dir, entry["file"]), "rb") as f:
    fn = jax_export.deserialize(f.read()).call
images = np.load(in_npz)["images"]
boxes, scores, labels, valid = fn(images)
np.savez(out_npz, boxes=np.asarray(boxes), scores=np.asarray(scores),
         labels=np.asarray(labels), valid=np.asarray(valid))
banned = sorted(m for m in sys.modules if "batchai_retinanet" in m)
assert not banned, f"model code leaked into the loader: {banned}"
print("loaded_without_model_code")
"""


def test_artifact_runs_with_no_model_code_imports(
    tiny_model_and_state, tmp_path
):
    """ISSUE 4 satellite: a ``detector_<H>x<W>_b<B>.stablehlo`` artifact
    is consumable by a process that imports ONLY jax + numpy — no model
    code, no package import — and its detections are bit-identical to the
    live ``make_detect_fn`` path."""
    import subprocess
    import sys

    model, state = tiny_model_and_state
    export_model(
        state, model, str(tmp_path / "exp"), buckets=((64, 64),),
        batch_size=2, config=CONFIG,
    )
    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, (2, 64, 64, 3), dtype=np.uint8)
    np.savez(tmp_path / "in.npz", images=images)
    r = subprocess.run(
        [sys.executable, "-c", _NO_IMPORT_LOADER, str(tmp_path / "exp"),
         str(tmp_path / "in.npz"), str(tmp_path / "out.npz")],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "loaded_without_model_code" in r.stdout

    got = np.load(tmp_path / "out.npz")
    want = make_detect_fn(model, (64, 64), CONFIG)(state, images)
    for name, w in zip(("boxes", "scores", "labels", "valid"), want):
        np.testing.assert_array_equal(got[name], np.asarray(w), err_msg=name)


def test_unknown_shape_rejected(tiny_model_and_state, tmp_path):
    model, state = tiny_model_and_state
    export_model(
        state, model, str(tmp_path / "exp"), buckets=((64, 64),),
        batch_size=2, config=CONFIG,
    )
    loaded = load_model(str(tmp_path / "exp"))
    with pytest.raises(ValueError, match="no exported program"):
        loaded(np.zeros((1, 64, 64, 3), dtype=np.uint8))


@pytest.mark.parametrize("written_by", ["this tree", "PR 44 and before"])
def test_manifest_loads_with_or_without_the_schedule_key(
    tiny_model_and_state, tmp_path, written_by
):
    """Until PR 45 a manifest carried ``schedule``: where the registry of
    kernel parameters had its values from.  Nothing ever read the key; an
    export of that age must still serve."""
    import json

    from batchai_retinanet_horovod_coco_tpu.serve import DetectEngine

    model, state = tiny_model_and_state
    path = export_model(
        state, model, str(tmp_path / "exp"), buckets=((64, 64),),
        batch_size=2, config=CONFIG,
    )
    with open(path) as f:
        manifest = json.load(f)
    assert "schedule" not in manifest
    assert manifest["detect_config"] == {
        "score_threshold": 0.05, "iou_threshold": 0.5, "pre_nms_size": 64,
        "max_detections": 10, "nms_impl": "xla", "nms_block_k": 256,
    }
    if written_by != "this tree":
        manifest["schedule"] = {
            "device_kind": "cpu", "found": True,
            "source": "artifacts/schedules/cpu.json",
        }
        with open(path, "w") as f:
            json.dump(manifest, f, indent=2)
    engine = DetectEngine.from_export(str(tmp_path / "exp"))
    assert engine.buckets == ((64, 64),)
    assert engine.batch_sizes((64, 64)) == [2]
    images = np.zeros((2, 64, 64, 3), dtype=np.uint8)
    got = engine.fetch(engine.dispatch((64, 64), images))
    want = make_detect_fn(model, (64, 64), CONFIG)(state, images)
    np.testing.assert_array_equal(got.valid, np.asarray(want.valid))
    np.testing.assert_array_equal(got.scores, np.asarray(want.scores))


def test_convert_model_cli_roundtrip_to_server(tmp_path):
    """ISSUE 4 satellite: checkpoint → ``convert_model.py`` (with bucket /
    batch-size / platform flags) → export dir → serve engine answers a
    request.  Fast-tier: the checkpoint is written directly (no training
    run; the slow CLI test covers train.py in the loop)."""
    import os
    import sys

    import jax
    import jax.numpy as jnp
    import optax

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    import convert_model
    from batchai_retinanet_horovod_coco_tpu.models import (
        RetinaNetConfig,
        build_retinanet,
    )
    from batchai_retinanet_horovod_coco_tpu.serve import (
        DetectEngine,
        DetectionServer,
        ServeConfig,
    )
    from batchai_retinanet_horovod_coco_tpu.train import create_train_state
    from batchai_retinanet_horovod_coco_tpu.utils.checkpoint import (
        CheckpointManager,
    )

    # Exactly the model convert_model.py rebuilds from these flags.
    model = build_retinanet(
        RetinaNetConfig(
            num_classes=3, backbone="resnet_test", norm_kind="gn",
            dtype=jnp.float32,
        )
    )
    state = create_train_state(
        model, optax.sgd(0.01), (1, 64, 64, 3), jax.random.key(0)
    )
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    mgr.save(state, step=0, force=True)
    mgr.wait()
    mgr.close()

    manifest = convert_model.main(
        ["--snapshot-path", str(tmp_path / "ckpt"),
         "--output", str(tmp_path / "exp"),
         "--num-classes", "3", "--backbone", "resnet_test", "--f32",
         "--buckets", "64x64", "--batch-sizes", "1,2",
         "--image-min-side", "64", "--image-max-side", "64",
         "--score-threshold", "0.001", "--platform", "cpu"]
    )
    assert manifest.endswith("manifest.json")

    engine = DetectEngine.from_export(str(tmp_path / "exp"))
    assert engine.buckets == ((64, 64),)
    assert engine.batch_sizes((64, 64)) == [1, 2]
    rng = np.random.default_rng(0)
    with DetectionServer(
        engine, ServeConfig(max_delay_ms=5, preprocess_workers=1)
    ) as srv:
        dets = srv.submit(
            rng.integers(0, 256, (70, 60, 3), dtype=np.uint8)
        ).result(timeout=120)
    assert isinstance(dets, list)
    for d in dets:
        assert set(d) == {"category_id", "bbox", "score"}


@pytest.mark.slow
def test_convert_model_cli(tiny_model_and_state, tmp_path, monkeypatch):
    """End-to-end: train 1 step with snapshots, convert, reload, run."""
    import os
    import sys

    # repo root, derived from this file's own path
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    import convert_model
    from train import main as train_main

    train_main(
        ["synthetic",
         "--synthetic-root", str(tmp_path / "data"),
         "--synthetic-images", "4", "--synthetic-size", "64",
         "--image-min-side", "64", "--image-max-side", "64",
         "--backbone", "resnet_test", "--f32",
         "--batch-size", "2", "--num-devices", "1",
         "--max-gt", "8", "--workers", "2", "--steps", "1",
         "--snapshot-path", str(tmp_path / "ckpt"),
         "--checkpoint-every", "1"]
    )
    manifest = convert_model.main(
        ["--snapshot-path", str(tmp_path / "ckpt"),
         "--output", str(tmp_path / "exp"),
         "--num-classes", "3", "--backbone", "resnet_test", "--f32",
         "--image-min-side", "64", "--image-max-side", "64",
         "--batch-size", "2"]
    )
    loaded = load_model(str(tmp_path / "exp"))
    boxes, scores, labels, valid = loaded(
        np.zeros((2, 64, 64, 3), dtype=np.uint8)
    )
    assert np.asarray(boxes).shape[0] == 2
    assert np.asarray(valid).dtype == bool

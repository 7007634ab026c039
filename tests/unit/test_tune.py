"""tune/ subsystem (ISSUE 6): registry round-trip, loud fallback, search.

Three contracts under test:

1. **Registry** (tune/schedule.py): schema validation names EVERY problem;
   save → load → lookup round-trips; partial artifacts deep-merge over the
   built-in defaults; an unknown/invalid device falls back to the defaults
   with ONE structured ``schedule_fallback`` stderr event per process —
   never a crash; lookups are cached (the zero-request-time-recompile
   guarantee) yet isolated per registry dir.
2. **Consumers**: ``resolve_detect_config`` (evaluate/detect.py) and
   ``resolve_kernel_schedule`` (train/step.py) fill exactly the None
   fields from the registry, and explicit values always win.
3. **Search** (tune/search.py + CLI): a CPU smoke run produces a
   schema-valid artifact that the consumers actually resolve from, with
   pallas candidates recorded as skipped (no Mosaic) and the winner drawn
   from exact-semantics trials only.
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(
    0,
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
)

from batchai_retinanet_horovod_coco_tpu.tune import (  # noqa: E402
    DEFAULT_SCHEDULE,
    ScheduleError,
    load_schedule,
    lookup,
    provenance,
    save_schedule,
    schedule_path,
    serve_batch_sizes_for,
    validate_schedule,
)
from batchai_retinanet_horovod_coco_tpu.tune import (  # noqa: E402
    schedule as schedule_lib,
)


@pytest.fixture(autouse=True)
def clean_registry_state():
    """Process-global lookup cache + once-per-reason warning dedupe must
    not leak between tests."""
    schedule_lib._cache.clear()
    schedule_lib._warned.clear()
    yield
    schedule_lib._cache.clear()
    schedule_lib._warned.clear()


def _doc(device_kind="TPU v5 lite", **entries):
    return {
        "format": schedule_lib.FORMAT,
        "device_kind": device_kind,
        "entries": entries,
    }


class TestSchema:
    def test_round_trip(self, tmp_path):
        doc = _doc(
            nms={"impl": "pallas", "block_k": 512, "pre_nms_size": 1000},
            focal={"impl": "pallas", "fwd_tile_a": 16384, "bwd_tile_a": 2048},
        )
        path = save_schedule(doc, str(tmp_path))
        assert path == schedule_path("TPU v5 lite", str(tmp_path))
        assert os.path.basename(path) == "tpu_v5_lite.json"
        assert load_schedule(path)["entries"] == doc["entries"]

    def test_every_problem_named_not_just_the_first(self):
        bad = _doc(
            nms={"impl": "cuda", "block_k": 100},
            focal={"fwd_tile_a": -8},
            bogus_op={"x": 1},
        )
        bad["format"] = "wrong.format"
        with pytest.raises(ScheduleError) as exc:
            validate_schedule(bad)
        msg = str(exc.value)
        for fragment in (
            "format:", "bogus_op", "nms.impl", "block_k", "fwd_tile_a"
        ):
            assert fragment in msg, (fragment, msg)

    def test_tiles_must_be_lane_multiples(self):
        with pytest.raises(ScheduleError, match="multiple of 128"):
            validate_schedule(_doc(matching={"tile_a": 1000}))

    def test_batch_tables_validated(self):
        with pytest.raises(ScheduleError, match="not HxW"):
            validate_schedule(_doc(eval={"batch": {"big": 8}}))
        with pytest.raises(ScheduleError, match="non-empty list"):
            validate_schedule(
                _doc(serve={"batch_sizes": {"800x1344": 8}})
            )

    def test_save_refuses_invalid(self, tmp_path):
        with pytest.raises(ScheduleError):
            save_schedule(_doc(nms={"impl": "nope"}), str(tmp_path))
        assert not os.listdir(tmp_path)


class TestLookupFallback:
    def test_unknown_device_falls_back_with_one_structured_event(
        self, tmp_path, capsys
    ):
        merged = lookup("never-tuned-chip", str(tmp_path))
        assert merged == DEFAULT_SCHEDULE
        merged2 = lookup("never-tuned-chip", str(tmp_path))
        assert merged2 == DEFAULT_SCHEDULE
        err_lines = [
            l for l in capsys.readouterr().err.splitlines() if l.strip()
        ]
        events = [json.loads(l) for l in err_lines]
        events = [e for e in events if e.get("event") == "schedule_fallback"]
        assert len(events) == 1, "exactly ONE event per (device, reason)"
        assert events[0]["device_kind"] == "never-tuned-chip"
        assert events[0]["reason"] == "no_schedule_artifact"
        assert events[0]["using"] == "built-in defaults"

    def test_invalid_artifact_falls_back_loudly_never_crashes(
        self, tmp_path, capsys
    ):
        path = schedule_path("brokenchip", str(tmp_path))
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write('{"format": "wrong", "entries": 3}')
        merged = lookup("brokenchip", str(tmp_path))
        assert merged == DEFAULT_SCHEDULE
        events = [
            json.loads(l)
            for l in capsys.readouterr().err.splitlines()
            if l.strip()
        ]
        assert events[0]["reason"] == "invalid_schedule_artifact"
        # Strict readers DO crash on the same artifact (CI wants that).
        with pytest.raises(ScheduleError):
            load_schedule(path)

    def test_partial_artifact_merges_over_defaults(self, tmp_path):
        save_schedule(_doc(nms={"impl": "pallas"}), str(tmp_path))
        merged = lookup("TPU v5 lite", str(tmp_path))
        assert merged["nms"]["impl"] == "pallas"
        # Unsearched keys keep the hand-picked defaults.
        assert merged["nms"]["block_k"] == DEFAULT_SCHEDULE["nms"]["block_k"]
        assert merged["focal"] == DEFAULT_SCHEDULE["focal"]

    def test_lookup_cached_and_isolated_per_root(self, tmp_path):
        a, b = tmp_path / "a", tmp_path / "b"
        save_schedule(_doc(nms={"block_k": 512}), str(a))
        save_schedule(_doc(nms={"block_k": 128}), str(b))
        assert lookup("TPU v5 lite", str(a))["nms"]["block_k"] == 512
        assert lookup("TPU v5 lite", str(b))["nms"]["block_k"] == 128
        # Mutating a returned dict must not poison the cache.
        got = lookup("TPU v5 lite", str(a))
        got["nms"]["block_k"] = 999
        assert lookup("TPU v5 lite", str(a))["nms"]["block_k"] == 512

    def test_batch_table_helpers(self, tmp_path):
        save_schedule(
            _doc(
                eval={"batch": {"800x1344": 16}},
                serve={"batch_sizes": {"800x1344": [1, 16]}},
            ),
            str(tmp_path),
        )
        kind, root = "TPU v5 lite", str(tmp_path)
        assert serve_batch_sizes_for((800, 1344), (8,), kind, root) == (1, 16)
        assert serve_batch_sizes_for((1344, 800), (8,), kind, root) == (8,)

    def test_provenance(self, tmp_path):
        p = provenance("TPU v5 lite", str(tmp_path))
        assert p == {
            "device_kind": "TPU v5 lite", "source": "defaults", "found": False
        }
        save_schedule(_doc(nms={"impl": "xla"}), str(tmp_path))
        p = provenance("TPU v5 lite", str(tmp_path))
        assert p["found"] and p["source"].endswith("tpu_v5_lite.json")


class TestConsumers:
    @pytest.fixture()
    def registry(self, tmp_path, monkeypatch):
        """A committed-winner registry for THIS process's device kind,
        installed via the env override every consumer honors."""
        import jax

        kind = jax.devices()[0].device_kind
        save_schedule(
            _doc(
                device_kind=kind,
                nms={"impl": "pallas", "block_k": 512, "pre_nms_size": 512},
                focal={"impl": "xla", "fwd_tile_a": 16384, "bwd_tile_a": 2048},
                matching={"impl": "pallas", "tile_a": 4096},
            ),
            str(tmp_path),
        )
        monkeypatch.setenv("RETINANET_SCHEDULE_DIR", str(tmp_path))
        schedule_lib._cache.clear()
        yield kind
        schedule_lib._cache.clear()

    def test_resolve_detect_config_fills_none_fields(self, registry):
        from batchai_retinanet_horovod_coco_tpu.evaluate.detect import (
            DetectConfig,
            resolve_detect_config,
        )

        resolved = resolve_detect_config(DetectConfig())
        assert resolved.nms_impl == "pallas"
        assert resolved.nms_block_k == 512
        assert resolved.pre_nms_size == 512
        # Semantics knobs not owned by the schedule are untouched.
        assert resolved.score_threshold == DetectConfig.score_threshold

    def test_explicit_fields_always_win(self, registry):
        from batchai_retinanet_horovod_coco_tpu.evaluate.detect import (
            DetectConfig,
            resolve_detect_config,
        )

        pinned = resolve_detect_config(
            DetectConfig(nms_impl="xla", pre_nms_size=1000, nms_block_k=128)
        )
        assert pinned.nms_impl == "xla"
        assert pinned.pre_nms_size == 1000
        assert pinned.nms_block_k == 128

    def test_typod_impl_raises_even_when_fully_pinned(self, registry):
        """A fully concrete config must not dodge impl validation via the
        early return — 'Pallas' silently running XLA would let an export
        manifest record a kernel that never ran."""
        from batchai_retinanet_horovod_coco_tpu.evaluate.detect import (
            DetectConfig,
            resolve_detect_config,
        )

        with pytest.raises(ValueError, match="nms_impl"):
            resolve_detect_config(
                DetectConfig(
                    nms_impl="Pallas", pre_nms_size=1000, nms_block_k=128
                )
            )

    def test_resolve_kernel_schedule_train_side(self, registry):
        from batchai_retinanet_horovod_coco_tpu import losses as losses_lib
        from batchai_retinanet_horovod_coco_tpu.ops import (
            matching as matching_lib,
        )
        from batchai_retinanet_horovod_coco_tpu.train.step import (
            resolve_kernel_schedule,
        )

        loss, match = resolve_kernel_schedule(
            losses_lib.LossConfig(), matching_lib.MatchingConfig()
        )
        assert loss.pallas_focal is False  # registry says impl: xla
        assert loss.focal_fwd_tile_a == 16384
        assert loss.focal_bwd_tile_a == 2048
        assert match.fused_pallas is True
        assert match.pallas_tile_a == 4096
        # Explicit values survive resolution untouched.
        loss2, match2 = resolve_kernel_schedule(
            losses_lib.LossConfig(pallas_focal=True, focal_fwd_tile_a=4096),
            matching_lib.MatchingConfig(fused_pallas=False),
        )
        assert loss2.pallas_focal is True
        assert loss2.focal_fwd_tile_a == 4096
        assert match2.fused_pallas is False

    def test_unknown_device_resolution_is_todays_defaults(
        self, tmp_path, monkeypatch
    ):
        """The no-artifact path every consumer ships with: resolution must
        reproduce the pre-ISSUE-6 hand-picked values exactly."""
        from batchai_retinanet_horovod_coco_tpu.evaluate.detect import (
            DetectConfig,
            resolve_detect_config,
        )

        monkeypatch.setenv("RETINANET_SCHEDULE_DIR", str(tmp_path / "none"))
        schedule_lib._cache.clear()
        resolved = resolve_detect_config(DetectConfig())
        assert resolved.nms_impl == "xla"
        assert resolved.pre_nms_size == 1000
        assert resolved.nms_block_k == 256


class TestSearch:
    def test_failed_candidate_is_recorded_not_fatal(self):
        from batchai_retinanet_horovod_coco_tpu.tune import search

        def build(params):
            if params.get("block_k") == 128:
                raise ValueError("XLA compile error: tile too fat")
            return lambda: np.zeros(())

        t_ok = search.run_trial(
            "nms", {"impl": "xla", "pre_nms_size": 1000}, build, steps=2
        )
        t_bad = search.run_trial(
            "nms", {"impl": "xla", "block_k": 128, "pre_nms_size": 1000},
            build, steps=2,
        )
        assert t_ok.status == "ok" and t_ok.ms_per_call is not None
        assert t_bad.status == "failed"
        assert "tile too fat" in t_bad.error

    def test_cpu_smoke_produces_consumable_artifact(
        self, tmp_path, monkeypatch, capsys
    ):
        """The acceptance bar: a CPU tuner run emits a schema-valid
        artifact that detect-side resolution consumes, with a stable
        (cached) resolution — the zero-request-time-recompile property."""
        import jax

        from batchai_retinanet_horovod_coco_tpu.tune.__main__ import main

        rc = main([
            "--smoke", "--ops", "nms", "--hw", "128x128", "--batch", "1",
            "--steps", "2", "--out-root", str(tmp_path),
        ])
        assert rc == 0
        kind = jax.devices()[0].device_kind
        path = schedule_path(kind, str(tmp_path))
        assert os.path.exists(path)
        doc = load_schedule(path)  # schema-valid by construction
        assert doc["entries"]["nms"]["impl"] == "xla"  # no Mosaic on CPU
        skipped = [t for t in doc["trials"] if t["status"] == "skipped"]
        assert skipped, "pallas candidates must be RECORDED as skipped"
        assert all("Mosaic" in t["error"] for t in skipped)
        ok = [t for t in doc["trials"] if t["status"] == "ok"]
        assert ok and all(t["ms_per_call"] > 0 for t in ok)

        # Consumable: detect resolution picks the winner up...
        from batchai_retinanet_horovod_coco_tpu.evaluate.detect import (
            DetectConfig,
            resolve_detect_config,
        )

        monkeypatch.setenv("RETINANET_SCHEDULE_DIR", str(tmp_path))
        schedule_lib._cache.clear()
        r1 = resolve_detect_config(DetectConfig())
        assert r1.pre_nms_size == doc["entries"]["nms"]["pre_nms_size"]
        # ...and resolution is STABLE for the process lifetime: same
        # concrete config on every call → the AOT table compiled at serve
        # startup keeps matching → no request-time recompiles.
        assert resolve_detect_config(DetectConfig()) == r1

    def test_winner_never_comes_from_approx_semantics(self, monkeypatch):
        """pre_nms_size trials are measured (opt-in) but the harness must
        not auto-promote a semantics-changing winner."""
        from batchai_retinanet_horovod_coco_tpu.tune import search

        def fake_builder(batch, hw):
            def build(params):
                # Make the semantics-approx candidate measurably "fastest".
                return lambda: np.zeros(())
            return build

        monkeypatch.setitem(search._BUILDERS, "nms", fake_builder)
        winner, trials = search.search_op(
            "nms",
            steps=2,
            candidates=[
                {"impl": "xla", "pre_nms_size": 1000},
                {"impl": "xla", "pre_nms_size": 512},
            ],
        )
        assert winner["pre_nms_size"] == 1000
        approx = [t for t in trials if t.semantics == "approx"]
        assert len(approx) == 1 and approx[0].status == "ok"

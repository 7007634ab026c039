"""Smoke tests for the auxiliary CLIs (evaluate.py / debug.py, SURVEY.md M12)."""

import os
import sys

import pytest

# repo root, derived from this file's own path
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


@pytest.mark.slow
class TestDebugCli:
    def test_synthetic_report_and_vis(self, tmp_path):
        import debug

        report = debug.main(
            [
                "synthetic",
                "--synthetic-root", str(tmp_path / "data"),
                "--synthetic-images", "3",
                "--synthetic-size", "128",
                "--limit", "3",
                "--output-dir", str(tmp_path / "vis"),
            ]
        )
        assert len(report) == 3
        # Every synthetic image has gt and the matcher must find positives
        # (force_match_for_gt semantics — a gt with no anchor is a data bug).
        assert all(r["positive"] > 0 for r in report)
        assert all(
            r["positive"] + r["negative"] + r["ignored"] == r["anchors"]
            for r in report
        )
        vis = list((tmp_path / "vis").glob("*.jpg"))
        assert len(vis) == 3


class TestEvaluateCli:
    """evaluate.main's metric formatting — fast, no model in the loop."""

    def _run(self, monkeypatch, capsys, metrics):
        import evaluate
        import train

        seen_argv = {}

        def fake_train_main(argv):
            seen_argv["argv"] = argv
            return metrics

        monkeypatch.setattr(train, "main", fake_train_main)
        out = evaluate.main(["synthetic"])
        assert out is metrics
        assert seen_argv["argv"][-1] == "--eval-only"
        return capsys.readouterr().out.strip().splitlines()

    def test_coco_metrics_print_without_voc_keys(self, monkeypatch, capsys):
        # Regression: COCO keys ('AP') used to hit the voc sort key's
        # rsplit('_')[1] and raise IndexError on every run.
        lines = self._run(
            monkeypatch, capsys, {"AP": 0.5, "AP50": 0.7, "loss": 1.0}
        )
        assert lines == ["AP: 0.5000", "AP50: 0.7000"]

    def test_voc_metrics_numeric_order(self, monkeypatch, capsys):
        lines = self._run(
            monkeypatch,
            capsys,
            {"AP": 0.5, "voc_AP_10": 0.2, "voc_AP_2": 0.1, "voc_mAP": 0.6},
        )
        assert lines == [
            "AP: 0.5000",
            "voc_mAP: 0.6000",
            "voc_AP_2: 0.1000",
            "voc_AP_10: 0.2000",
        ]


class TestBucketsCli:
    """debug.py buckets: exact bucket shares from annotation metadata only."""

    def _write_annotations(self, path, dims):
        import json

        blob = {
            "categories": [{"id": 1, "name": "thing"}],
            "images": [
                {"id": i, "file_name": f"{i}.jpg", "width": w, "height": h}
                for i, (w, h) in enumerate(dims)
            ],
            "annotations": [
                {
                    "id": i,
                    "image_id": i,
                    "category_id": 1,
                    "bbox": [1, 1, 10, 10],
                    "area": 100,
                    "iscrowd": 0,
                }
                for i in range(len(dims))
            ],
        }
        with open(path, "w") as f:
            json.dump(blob, f)

    def test_shares(self, tmp_path):
        import debug

        # 2 landscape (640x480 -> 800x1067 -> 800x1344 bucket), 1 portrait
        # (480x640 -> 1067x800 -> 1344x800), 1 near-square landscape
        # (500x500 -> 800x800 -> fits 800x1344, the smallest-area bucket).
        ann = tmp_path / "instances.json"
        self._write_annotations(
            ann, [(640, 480), (640, 480), (480, 640), (500, 500)]
        )
        (out,) = debug.main(["buckets", str(ann)])
        shares = out["shares"]
        assert shares["800x1344"]["count"] == 3
        assert shares["1344x800"]["count"] == 1
        assert "1088x1088" not in shares
        assert abs(shares["800x1344"]["share"] - 0.75) < 1e-9

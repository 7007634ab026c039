"""What ``test_benchmark_keye_cell.py::test_what_the_benchmark_had_is_still_there_word_for_word``
(PR 38) held, but for its pin of ``keye-vl2-train-doc16k`` as the LAST cell of
four ``workloads`` lists: PR 40 appended ``olmo-hybrid-train-pack8k`` after it, so
the accepted test is expected to fail, strictly (``tests/conftest.py``), until a
``benchmark`` issue (ROADMAP S0c) finds the cell by name.  Every other assert of
its body is kept alive here, one test a list."""

import os

import pytest

import benchmark_tiny_tree as tiny

CELL = "keye-vl2-train-doc16k"
BEFORE = ["r50-train-b8", "r50-train-dp4", "r50-train-b8-portrait", "granite-h-train-pack8k",
          "dsv2-lite-train-pack8k", "nemo3-nano-train-pack8k"]


def _by_name(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, (name, len(found))
    return found[0]


@pytest.fixture(scope="module")
def bench():
    return tiny._load(os.path.join(tiny.REPO, "BENCHMARK.json"))


def test_every_accepted_cell_and_configuration_is_found_by_name(bench):
    for name in (*BEFORE, CELL):
        _by_name(bench["workloads"], name)
    for name in ("retinanet-r50-fpn-800", "granite-4.0-h-micro-p1", "deepseek-v2-lite-ep8", "nemotron-3-nano-30b-ep16",
                 "keye-vl2-30b-a3b-ep8"):
        _by_name(bench["configs"], name)


@pytest.mark.parametrize("kind,name", [("end_to_end", "train_img_per_s_chip"), ("per_layer", "train_loop.data_wait_ms"),
                                       ("per_layer", "train_step.device_ms"), ("per_layer", "train_step.mfu_pct")])
def test_the_accepted_lists_keep_their_cells_in_their_order_with_keyes_behind_nemo3s(bench, kind, name):
    cells = _by_name(bench[kind], name)["workloads"]
    at = cells.index(CELL)
    assert cells[at - 1] == "nemo3-nano-train-pack8k" and len(set(cells)) == len(cells)
    assert [c for c in cells[:at]] == [c for c in BEFORE if c in cells]


def test_the_bounds_and_the_run_are_what_they_were(bench):
    assert (bench["run_seconds"], _by_name(bench["end_to_end"], "train_img_per_s_chip")["bound"],
            _by_name(bench["end_to_end"], "setup_s")["bound"]) == (10, 0.01, 0.1)

"""What ``test_program_trace.py``'s two tests of the portrait cell assert,
less "it is the last cell": ``conftest.py`` expects those two to fail since
PR 26 appended a cell after it, and these hold everything else of their
bodies, and that the accepted lists only grew at the end."""

import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ACCEPTED = ["setup.compiles_in_window", "train_loop.data_wait_ms", "train_step.device_ms", "train_step.mfu_pct",
            "assign_fused.kernel_ms", "assign_fused_roofline"]
R50 = ["r50-train-b8", "r50-train-dp4", "r50-train-b8-portrait"]


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_portrait_cell_is_one_chip_of_the_flagship_and_the_last_of_its_cells(bench):
    assert [w["name"] for w in bench["workloads"][:3]] == R50
    cell = bench["workloads"][2]
    assert cell == dict(cell, config="retinanet-r50-fpn-800", traffic="train-loop-b8-portrait", chips=1)
    rate = next(m for m in bench["end_to_end"] if m["name"] == "train_img_per_s_chip")
    assert rate["workloads"][:3] == R50 and rate["bound"] == 0.01


def test_the_portrait_cell_lists_what_the_flagship_cell_lists(bench):
    listed = {c: [m["name"] for m in bench["per_layer"] if "workloads" not in m or c in m["workloads"]]
              for c in ("r50-train-b8", "r50-train-b8-portrait")}
    assert listed["r50-train-b8-portrait"] == listed["r50-train-b8"] == ACCEPTED
    assert [m["name"] for m in bench["per_layer"][:6]] == ACCEPTED


@pytest.mark.parametrize("index", range(1, 6))
def test_an_accepted_list_keeps_its_cells_in_front(bench, index):
    """Whatever later PRs append, the flagship's cells stay first, in the
    accepted order, with portrait the last of them."""
    cells = bench["per_layer"][index]["workloads"]
    flagship = [c for c in cells if c in R50]
    assert cells[:len(flagship)] == flagship == [c for c in R50 if c in flagship] and flagship[-1] == R50[-1]

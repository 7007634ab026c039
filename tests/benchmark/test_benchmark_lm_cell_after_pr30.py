"""What ``test_benchmark_lm_cell.py::test_the_cell_and_its_configuration_as_the_issue_set_them``
asserts, less "granite's entries are the last of their lists": since PR 30
appended a cell, a configuration and six metrics after them,
``tests/conftest.py`` expects that test to fail, and this holds everything
else of its body, with every entry found by name, and that what PR 30 added
came after what was there."""

import os

import benchmark_tiny_tree as tiny

CELL = "granite-h-train-pack8k"
CONFIG = "granite-4.0-h-micro-p1"
SLICES = ["lm_step.mamba_ms", "lm_step.ssd_ms", "lm_step.attention_ms", "lm_step.mlp_ms"]


def _bench():
    return tiny._load(os.path.join(tiny.REPO, "BENCHMARK.json"))


def _named(entries, name):
    (found,) = [e for e in entries if e["name"] == name]
    return found


def test_the_cell_and_its_configuration_as_issue_26_set_them():
    bench = _bench()
    cell, entry = _named(bench["workloads"], CELL), _named(bench["configs"], CONFIG)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "lm-train-pack8k", 1)
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    cfg = tiny._load(os.path.join(tiny.REPO, entry["file"]))
    assert cfg["source"] == entry["source"] and cfg["reduced"] == entry["reduced"]
    assert (cfg["num_hidden_layers"], cfg["vocab_size"], cfg["hidden_size"]) == (10, 100352 // 8, 2048)
    assert cfg["layer_types"][:10].count("mamba") == 9 and cfg["layer_types"][5] == "attention"
    t = tiny._load(os.path.join(tiny.REPO, "benchmark", "traffic", cell["traffic"] + ".json"))
    assert (t["seq_len"], t["per_chip_batch"], t["pool_batches"], t["log_every"]) == (8192, 1, 4, 4)
    assert (t["lr"], t["adam_b2"], t["weight_decay"], t["clip_global_norm"]) == (3e-4, 0.95, 0.1, 1.0)
    for e in (entry, cell):
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"] and "\t" not in e["why"], e["name"]
    assert len(entry["source"]) <= 200


def test_the_cell_reads_the_accepted_metrics_and_its_four_slices_and_nothing_of_a_later_cell():
    bench = _bench()
    listed = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])]
    assert listed == ["setup.compiles_in_window", "train_loop.data_wait_ms", "train_step.device_ms",
                      "train_step.mfu_pct"] + SLICES
    for name in listed:
        assert os.path.exists(os.path.join(tiny.REPO, "benchmark", "layer_metrics", name + ".py")), name
    assert [m["name"] for m in bench["end_to_end"] if CELL in m.get("workloads", [CELL])] == [
        "train_img_per_s_chip", "setup_s"]
    for m in (_named(bench["per_layer"], name) for name in SLICES):
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (m["workloads"], m["moves"], m["layer"]) == ([CELL], "train_img_per_s_chip", "train step")


def test_later_entries_came_after_the_accepted_ones():
    """The order PR 26 left is a prefix of every list: what a later PR adds is
    appended, which is what the benchmark check takes as no change."""
    bench = _bench()
    assert [w["name"] for w in bench["workloads"]][:4] == ["r50-train-b8", "r50-train-dp4", "r50-train-b8-portrait", CELL]
    assert [c["name"] for c in bench["configs"]][:2] == ["retinanet-r50-fpn-800", CONFIG]
    assert [m["name"] for m in bench["per_layer"]][6:10] == SLICES
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells = m.get("workloads", [])
        if CELL in cells:
            assert all(c.startswith("r50-") for c in cells[:cells.index(CELL)]), m["name"]

"""The language-model train kind end to end on the CPU at the tiny size
(one period of ten layers at d = 64), from a throw-away checkout whose
``BENCHMARK.json`` is the repo's with tiny configurations, mixes and cells
added beside the cell's own: untraced, traced, the control, and the form of
the entries PR 26 appended to ``BENCHMARK.json``."""

import os
import shutil

import pytest

import benchmark_tiny_tree as tiny

CELL = "granite-h-train-pack8k"
CONFIG = "granite-4.0-h-micro-p1"
TINY_MODEL = dict(num_hidden_layers=10, vocab_size=128, hidden_size=64, intermediate_size=128,
                  shared_intermediate_size=128, num_attention_heads=4, num_key_value_heads=2,
                  mamba_n_heads=4, mamba_d_head=16, mamba_expand=1, mamba_d_state=16, mamba_chunk_size=8,
                  attention_multiplier=0.25)
TINY_TRAFFIC = dict(seq_len=64, per_chip_batch=2, doc_len_median=16, doc_len_min=4, warm_steps=4, adam_eps=1e-12,
                    trace_steps=3, loss_rise_tol=1.0, reference_blocks={"scan_block": 16, "head_block": 2})


# the cell through ``harness/lm_control.py`` instead of ``run.py``
CONTROL_LAUNCHER = tiny.LAUNCHER.replace("from benchmark import run\nsys.exit(run.main(",
                                         "from benchmark.harness import lm_control\nsys.exit(lm_control.main(")
assert CONTROL_LAUNCHER != tiny.LAUNCHER


def build(root: str, launcher: str = tiny.LAUNCHER) -> str:
    """``benchmark/`` copied, then a tiny configuration, its mixes (the
    cell's own tolerances; one computes in float32) and their cells added
    beside, listed wherever the cell is."""
    shutil.copytree(os.path.join(tiny.REPO, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = os.path.join(root, "benchmark")
    bench = tiny._load(os.path.join(tiny.REPO, "BENCHMARK.json"))
    cfg = tiny._load(os.path.join(b, "configs", "granite-4.0-h-micro-p1.json"))
    traffic = dict(tiny._load(os.path.join(b, "traffic", "lm-train-pack8k.json")), **TINY_TRAFFIC)
    for name, cfg_extra in {"lm-tiny": {}, "lm-tiny-f32": {"compute_dtype": "float32"}}.items():
        tiny._dump(dict(cfg, **TINY_MODEL, name=name, **cfg_extra), os.path.join(b, "configs", name + ".json"))
        tiny._dump(traffic, os.path.join(b, "traffic", name + ".json"))
        bench["configs"].append({"name": name, "source": cfg["source"], "file": f"benchmark/configs/{name}.json",
                                 "reduced": [], "why": "throw-away"})
        bench["workloads"].append({"name": name, "config": name, "traffic": name, "chips": 1, "why": "throw-away"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if CELL in m.get("workloads", ()):
                m["workloads"].append(name)
    tiny._dump(bench, os.path.join(root, "BENCHMARK.json"))
    with open(os.path.join(root, "launch.py"), "w") as f:
        f.write(launcher.format(repo=tiny.REPO))
    return root


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return build(str(tmp_path_factory.mktemp("bench_lm")))


def test_lm_cell_end_to_end(tree):
    rc, line, out = tiny.run_cell(tree, "lm-tiny", trace=0)
    assert rc == 0 and line is not None, out[-3000:]
    assert set(line) == tiny.KEYS, line
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    assert set(line["metrics"]) == {"train_img_per_s_chip", "setup_s"}
    facts = tiny.facts(out)
    assert facts["tokens_per_s_chip"] == pytest.approx(64 * line["metrics"]["train_img_per_s_chip"]["value"])
    assert facts["compiles_in_window"] == 0
    first = facts["first_step"]
    assert set(first) == {"loss", "grad_norm", "gnorm/embed", "gnorm/mamba", "gnorm/attention", "gnorm/mlp",
                          "gnorm/norms", "update", "seconds"}
    assert 0.9 < first["update"]["moved_min"] <= first["update"]["moved_max"] < 1.1
    assert sum(facts["setup_parts_s"].values()) == pytest.approx(facts["setup_s"], abs=1e-6)


def test_lm_cell_in_float32_agrees_closely_with_the_reference(tree):
    """The same program computing in float32: what is left is the order of
    the sums (chunks against token by token, blocks against dense)."""
    rc, line, out = tiny.run_cell(tree, "lm-tiny-f32", trace=0)
    assert rc == 0 and line is not None and line["correct"] is True, out[-3000:]
    first = tiny.facts(out)["first_step"]
    for name in ("loss", "grad_norm", "gnorm/embed", "gnorm/mamba", "gnorm/attention", "gnorm/mlp", "gnorm/norms"):
        assert first[name]["rel"] < 1e-4, (name, first[name])
    assert first["update"]["sign_agreement_min"] > 0.995 and first["update"]["decay_error_max"] < 1e-3


def test_the_control_is_not_correct(tmp_path):
    """The nearest precision below the one the configuration states
    (``harness/lm_control.py``: fp8 matmul operands, the program wrapped
    from outside) fails one of the cell's own limits."""
    rc, line, out = tiny.run_cell(build(str(tmp_path / "tree"), CONTROL_LAUNCHER), "lm-tiny", trace=0)
    assert rc == 0 and line is not None, out[-3000:]
    assert tiny.said(out, "benchmark: CONTROL:") and line["correct"] is False
    problems = tiny.said(out, "benchmark: NOT CORRECT:")
    assert problems and all(p.startswith("first step's") for p in problems), problems


def test_traced_lm_cell_reads_what_a_cpu_gives(tree):
    rc, line, out = tiny.run_cell(tree, "lm-tiny", trace=1)
    assert rc == 0 and line is not None, out[-3000:]
    assert {"train_step.mfu_pct", "train_loop.data_wait_ms", "setup.compiles_in_window"} <= set(line["metrics"])
    # the device-trace readers find no device plane on a CPU, and say so
    named = {p.split()[2] for p in tiny.said(out, "benchmark: NOT CORRECT:") if p.startswith("per-layer metric")}
    assert named == {"train_step.device_ms", "lm_step.mamba_ms", "lm_step.ssd_ms", "lm_step.attention_ms",
                     "lm_step.mlp_ms"}, named


def test_the_cell_and_its_configuration_as_the_issue_set_them():
    bench = tiny._load(os.path.join(tiny.REPO, "BENCHMARK.json"))
    cell, entry = bench["workloads"][-1], bench["configs"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (CELL, CONFIG, "lm-train-pack8k", 1)
    assert entry["name"] == CONFIG and entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    cfg = tiny._load(os.path.join(tiny.REPO, entry["file"]))
    assert cfg["source"] == entry["source"] and cfg["reduced"] == entry["reduced"]
    assert (cfg["num_hidden_layers"], cfg["vocab_size"], cfg["hidden_size"]) == (10, 100352 // 8, 2048)
    assert cfg["layer_types"][:10].count("mamba") == 9 and cfg["layer_types"][5] == "attention"
    t = tiny._load(os.path.join(tiny.REPO, "benchmark", "traffic", cell["traffic"] + ".json"))
    assert (t["seq_len"], t["per_chip_batch"], t["pool_batches"], t["log_every"]) == (8192, 1, 4, 4)
    assert (t["lr"], t["adam_b2"], t["weight_decay"], t["clip_global_norm"]) == (3e-4, 0.95, 0.1, 1.0)
    listed = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])]
    # the accepted train-step metrics read on this cell too; only the four slices are new
    assert listed == ["setup.compiles_in_window", "train_loop.data_wait_ms", "train_step.device_ms",
                      "train_step.mfu_pct", "lm_step.mamba_ms", "lm_step.ssd_ms", "lm_step.attention_ms",
                      "lm_step.mlp_ms"]
    for name in listed:
        assert os.path.exists(os.path.join(tiny.REPO, "benchmark", "layer_metrics", name + ".py")), name
    assert [m["name"] for m in bench["end_to_end"] if CELL in m.get("workloads", [CELL])] == [
        "train_img_per_s_chip", "setup_s"]
    # the forms BENCHMARK.json's entries must have
    for e in (entry, cell):
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"] and "\t" not in e["why"], e["name"]
    assert len(entry["source"]) <= 200
    new = bench["per_layer"][-4:]
    assert all(set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"} and m["workloads"] == [CELL]
               and m["moves"] == "train_img_per_s_chip" and m["layer"] == "train step" for m in new)


"""The hybrid mixture-of-experts train kind end to end on the CPU at the tiny
size (the pattern ``MEM*E`` at d = 64, 2 groups, 2 of 8 experts held of width
24), from a throw-away checkout whose ``BENCHMARK.json`` is the repo's with
tiny configurations, mixes and cells added beside the cell's own: untraced,
in float32, traced, the control; and the form of the entries PR 32 added to
``BENCHMARK.json`` and the numbers of its cut, every entry FOUND BY NAME and
never by its position in a list."""

import os
import shutil

import pytest

import benchmark_tiny_tree as tiny

CELL = "nemo3-nano-train-pack8k"
CONFIG = "nemotron-3-nano-30b-ep16"
MIX = "lm-hybrid-moe-train-pack8k-b2"
TINY_MODEL = dict(num_hidden_layers=5, hybrid_override_pattern="MEM*E", vocab_size=128, hidden_size=64,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=16, mamba_num_heads=4, mamba_head_dim=16,
                  n_groups=2, ssm_state_size=16, moe_intermediate_size=24, moe_shared_expert_intermediate_size=48,
                  n_routed_experts=2, n_routed_experts_total=8, experts_held=[0, 1], num_experts_per_tok=3,
                  mamba_chunk_size=8, attention_q_block=32)
TINY_TRAFFIC = dict(seq_len=64, per_chip_batch=2, doc_len_median=16, doc_len_min=4, warm_steps=4, window_steps=8,
                    adam_eps=1e-12, trace_steps=3, loss_rise_tol=1.0, reference_blocks={"head_block": 2, "scan_block": 16})
NEW_METRICS = ["nemo_step.mamba_ms", "nemo_step.ssd_ms", "nemo_step.attention_ms", "nemo_step.router_ms",
               "nemo_step.experts_ms", "nemo_step.shared_ms", "nemo_gmm_roofline", "nemo_ssd_roofline"]

# the cell through ``harness/nemotron_control.py`` instead of ``run.py``
CONTROL_LAUNCHER = tiny.LAUNCHER.replace("from benchmark import run\nsys.exit(run.main(",
                                         "from benchmark.harness import nemotron_control\nsys.exit(nemotron_control.main(")
assert CONTROL_LAUNCHER != tiny.LAUNCHER


def build(root: str, launcher: str = tiny.LAUNCHER) -> str:
    """``benchmark/`` copied, then a tiny configuration, its mixes (the
    cell's own tolerances; one computes in float32) and their cells added
    beside, listed wherever the cell is."""
    shutil.copytree(os.path.join(tiny.REPO, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = os.path.join(root, "benchmark")
    bench = tiny._load(os.path.join(tiny.REPO, "BENCHMARK.json"))
    cfg = tiny._load(os.path.join(b, "configs", CONFIG + ".json"))
    traffic = dict(tiny._load(os.path.join(b, "traffic", MIX + ".json")), **TINY_TRAFFIC)
    for name, cfg_extra in {"nemo-tiny": {}, "nemo-tiny-f32": {"compute_dtype": "float32"}}.items():
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
    return build(str(tmp_path_factory.mktemp("bench_nemo")))


FIRST_STEP = {"loss", "grad_norm", "gnorm/embed", "gnorm/mamba", "gnorm/attention", "gnorm/router", "gnorm/experts",
              "gnorm/shared", "gnorm/norms", "gnorm/head", "rows", "picks_differ", "update", "seconds"}


def test_nemotron_cell_end_to_end(tree):
    rc, line, out = tiny.run_cell(tree, "nemo-tiny", trace=0)
    assert rc == 0 and line is not None, out[-3000:]
    assert set(line) == tiny.KEYS, line
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    assert set(line["metrics"]) == {"train_img_per_s_chip", "setup_s"}
    facts = tiny.facts(out)
    assert facts["tokens_per_s_chip"] == pytest.approx(64 * line["metrics"]["train_img_per_s_chip"]["value"])
    assert facts["compiles_in_window"] == 0 and facts["steps"] == 8 and facts["ssd_chunk"] == 8
    first = facts["first_step"]
    assert set(first) == FIRST_STEP
    assert len(first["picks_differ"]["by_layer"]) == 2 and 0 <= first["picks_differ"]["max"] < 0.2
    assert 0.9 < first["update"]["moved_min"] <= first["update"]["moved_max"] < 1.1
    # the routing counters reach the facts: 2 x 64 tokens x 3 picks x 2 expert layers, a quarter of the experts held
    assert 0 <= facts["moe_rows_min_expert"] <= facts["moe_rows_max_expert"] <= 128
    assert 0.05 < facts["moe_rows_held_share_of_picks"] < 0.9 and facts["moe_buffer_rows"] == 384
    by_step = facts["moe_rows_held_share_of_picks_by_step"]
    assert [s for s, _ in by_step] == [8, 12, 16] and all(0 < share < 1 for _, share in by_step)
    # two products an expert, not three
    assert facts["model_flops_per_step"]["routed_experts"] == pytest.approx(
        3 * 2 * 2 * 64 * 24 * facts["moe_rows_held_per_step"])
    assert sum(facts["setup_parts_s"].values()) == pytest.approx(facts["setup_s"], abs=1e-6)


def test_nemotron_cell_in_float32_agrees_closely_with_the_reference(tree):
    """The same program computing in float32: what is left is the order of
    the sums (chunks against tokens, sorted rows against every token), and no
    token picks another expert."""
    rc, line, out = tiny.run_cell(tree, "nemo-tiny-f32", trace=0)
    assert rc == 0 and line is not None and line["correct"] is True, out[-3000:]
    first = tiny.facts(out)["first_step"]
    for name in FIRST_STEP - {"rows", "picks_differ", "update", "seconds"}:
        assert first[name]["rel"] < 1e-4, (name, first[name])
    assert first["picks_differ"]["max"] == 0.0 and {v["rel"] for v in first["rows"].values()} == {0.0}
    assert first["update"]["sign_agreement_min"] > 0.995 and first["update"]["decay_error_max"] < 1e-3


def test_the_control_is_not_correct(tmp_path):
    """The nearest precision below the one the configuration states
    (``harness/nemotron_control.py``: fp8 matmul operands, the program wrapped
    from outside) fails one of the cell's own limits."""
    rc, line, out = tiny.run_cell(build(str(tmp_path / "tree"), CONTROL_LAUNCHER), "nemo-tiny", trace=0)
    assert rc == 0 and line is not None, out[-3000:]
    assert tiny.said(out, "benchmark: CONTROL:") and line["correct"] is False
    problems = tiny.said(out, "benchmark: NOT CORRECT:")
    assert problems and all(p.startswith("first step's") for p in problems), problems


def test_traced_nemotron_cell_reads_what_a_cpu_gives(tree):
    rc, line, out = tiny.run_cell(tree, "nemo-tiny", trace=1)
    assert rc == 0 and line is not None, out[-3000:]
    assert {"train_step.mfu_pct", "train_loop.data_wait_ms", "setup.compiles_in_window"} <= set(line["metrics"])
    # the device-trace readers find no device plane on a CPU, and say so
    named = {p.split()[2] for p in tiny.said(out, "benchmark: NOT CORRECT:") if p.startswith("per-layer metric")}
    assert named == {"train_step.device_ms", *NEW_METRICS}, named


def _by_name(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, (name, len(found))
    return found[0]


def test_the_cell_and_its_configuration_as_the_issue_set_them():
    bench = tiny._load(os.path.join(tiny.REPO, "BENCHMARK.json"))
    cell, entry = _by_name(bench["workloads"], CELL), _by_name(bench["configs"], CONFIG)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json"
    cfg = tiny._load(os.path.join(tiny.REPO, entry["file"]))
    assert cfg["source"] == entry["source"] and cfg["reduced"] == entry["reduced"] and cfg["name"] == CONFIG
    # the cut: depth (the first nine layers as published), experts held, vocabulary
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (9, 8, 131072 // 8)
    assert cfg["hybrid_override_pattern"] == "MEMEM*EME" == cfg["published"]["hybrid_override_pattern"][:9]
    assert [cfg["hybrid_override_pattern"].count(k) for k in "ME*"] == [4, 4, 1]
    assert [cfg["published"]["hybrid_override_pattern"].count(k) for k in "ME*"] == [23, 23, 6]
    assert cfg["published"]["num_hidden_layers"] == 52 and cfg["published"]["n_routed_experts"] == 128
    assert cfg["published"]["vocab_size"] == 131072 and "16 chips" in cfg["deployment"]
    assert cfg["n_routed_experts_total"] == 128 and cfg["experts_held"] == list(range(8))
    # every width as published
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]) == (2688, 32, 2, 128)
    assert (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]) == (
        64, 64, 8, 128, 4)
    assert (cfg["moe_intermediate_size"], cfg["moe_shared_expert_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["routed_scaling_factor"], cfg["n_shared_experts"]) == (1856, 3712, 6, 2.5, 1)
    assert cfg["parameters_held"]["total"] == 666_962_944 == (
        4 * cfg["parameters_held"]["mamba_layer"] + cfg["parameters_held"]["attention_layer"]
        + 4 * cfg["parameters_held"]["expert_layer"] + cfg["parameters_held"]["embedding_head_and_final_norm"])
    assert {"weights", "e_score_correction_bias", "attention_positions", "dt_clamp", "rescale_prenorm_residual",
            "chunk_size", "recomputation"} <= set(cfg["assumed"])
    t = tiny._load(os.path.join(tiny.REPO, "benchmark", "traffic", cell["traffic"] + ".json"))
    assert t["kind"] == "lm_hybrid_moe_train_loop"
    assert os.path.exists(os.path.join(tiny.REPO, "benchmark", "kinds", t["kind"] + ".py"))
    # the mix is dsv2's, so that the language-model cells differ by model and not by traffic
    theirs = tiny._load(os.path.join(tiny.REPO, "benchmark", "traffic", "lm-moe-train-pack8k-b2.json"))
    same = ("seq_len", "per_chip_batch", "doc_len_median", "doc_len_sigma", "doc_len_min", "pool_batches",
            "device_prefetch", "log_every", "lr", "adam_b2", "adam_eps", "weight_decay", "clip_global_norm",
            "warm_steps", "window_steps", "trace_steps", "trace_steady_runs", "loss_rise_tol", "step_program_pattern")
    assert {k: t[k] for k in same} == {k: theirs[k] for k in same}
    assert (t["seq_len"], t["per_chip_batch"], t["warm_steps"], t["window_steps"]) == (8192, 2, 3, 12)
    # every limit of the comparison is written with its two readings
    limits = set(t["tolerances"]) - {"why"}
    assert limits == {"loss_rel", "grad_norm_rel", "rows_held_rel", "rows_expert_rel", "picks_differ_max",
                      "update_moved", "update_held_share", "update_sign_agreement", "update_decay_error"}
    assert limits | {"readings"} <= set(t["tolerances"]["why"])
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert listed == {"setup.compiles_in_window", "train_loop.data_wait_ms", "train_step.device_ms",
                      "train_step.mfu_pct", *NEW_METRICS}
    for name in listed:
        assert os.path.exists(os.path.join(tiny.REPO, "benchmark", "layer_metrics", name + ".py")), name
    assert {m["name"] for m in bench["end_to_end"] if CELL in m.get("workloads", [CELL])} == {
        "train_img_per_s_chip", "setup_s"}
    # the forms BENCHMARK.json's entries must have
    for e in (entry, cell):
        assert set(e) == ({"name", "source", "file", "reduced", "why"} if e is entry else
                          {"name", "config", "traffic", "chips", "why"})
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"] and "\t" not in e["why"], e["name"]
    for name in NEW_METRICS:
        m = _by_name(bench["per_layer"], name)
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["workloads"] == [CELL] and m["moves"] == "train_img_per_s_chip" and m["layer"] == "train step"
        assert m["source"] == "device_trace" and (m["unit"], m["better"]) == (
            ("%", "higher") if name.endswith("_roofline") else ("ms/step", "lower"))
    # one cell of four chips, as before; four configurations, six cells
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == ["r50-train-dp4"]
    assert (len(bench["configs"]), len(bench["workloads"])) == (4, 6)


def test_what_the_benchmark_had_is_still_there_word_for_word():
    """PR 32 appends: every accepted entry is found by name with the keys it
    had, and the accepted ``workloads`` lists keep their cells in their order
    with this cell behind them."""
    bench = tiny._load(os.path.join(tiny.REPO, "BENCHMARK.json"))
    for name in ("r50-train-b8", "r50-train-dp4", "r50-train-b8-portrait", "granite-h-train-pack8k",
                 "dsv2-lite-train-pack8k"):
        _by_name(bench["workloads"], name)
    for name in ("retinanet-r50-fpn-800", "granite-4.0-h-micro-p1", "deepseek-v2-lite-ep8"):
        _by_name(bench["configs"], name)
    for entries, name in ((bench["end_to_end"], "train_img_per_s_chip"), (bench["per_layer"], "train_loop.data_wait_ms"),
                          (bench["per_layer"], "train_step.device_ms"), (bench["per_layer"], "train_step.mfu_pct")):
        cells = _by_name(entries, name)["workloads"]
        assert cells[-2:] == ["dsv2-lite-train-pack8k", CELL] and len(set(cells)) == len(cells), name
    assert (bench["run_seconds"], _by_name(bench["end_to_end"], "train_img_per_s_chip")["bound"],
            _by_name(bench["end_to_end"], "setup_s")["bound"]) == (10, 0.01, 0.1)


@pytest.mark.parametrize("tokens,rows", [(16384, 24576.0), (128, 40.0)])
def test_the_flop_and_byte_counts_by_hand(tokens, rows):
    """``harness/nemotron_flops.py`` at the published widths: the issue's
    forward FLOPs a step (16 384 tokens, uniform routing: 4 layers x 6144
    rows), and the two kernels' costs."""
    from benchmark.harness import nemotron_flops as nf

    cfg = tiny._load(os.path.join(tiny.REPO, "benchmark", "configs", CONFIG + ".json"))
    f = nf.forward_flops_per_step(cfg, tokens, 1000.0, rows)
    assert f["mamba_matmuls"] == 2.0 * tokens * 4 * (2688 * 10304 + 4096 * 2688)
    assert f["shared_experts"] == 2.0 * tokens * 4 * 2 * 2688 * 3712 and f["router"] == 2.0 * tokens * 4 * 2688 * 128
    assert f["routed_experts"] == 2.0 * rows * 2 * 2688 * 1856  # two products an expert
    assert f["attention_matmuls"] == 2.0 * tokens * (2 * 2688 * 4096 + 2 * 2688 * 256)
    assert f["attention_pairs"] == 2.0 * 1000.0 * 2 * 4096 and f["lm_head"] == 2.0 * tokens * 16384 * 2688
    assert f["ssd"] == 5.0 * tokens * 4 * 64 * 64 * 128
    assert f["total"] == pytest.approx(sum(v for k, v in f.items() if k != "total"))
    if tokens == 16384:  # ISSUE 32: 5.1, 2.6, 0.49, 1.4, 0.8 T
        assert [round(f[k] / 1e12, 2) for k in ("mamba_matmuls", "shared_experts", "routed_experts", "lm_head",
                                                "attention_matmuls")] == [5.07, 2.62, 0.49, 1.44, 0.77]
    train = nf.train_flops_per_step(cfg, tokens, 1000.0, rows)
    assert train["total"] == pytest.approx(3 * f["total"])
    gmm = nf.gmm_cost_per_step(cfg, rows)
    assert gmm["ops"] == 4 * f["routed_experts"]
    weights = 4 * 8 * 2 * 2688 * 1856 * 2  # four layers' held experts, bfloat16
    assert gmm["bytes"] == pytest.approx(4 * weights + 4 * 2 * rows * (2688 + 1856) * 2)
    ssd = nf.ssd_cost_per_step(cfg, tokens, 256)
    # a head and chunk forward: C B^T shared by the 8 heads of a group, weights x values, the state in and out
    assert ssd["ops"] == 4 * 4.0 * (tokens / 256) * 64 * 2 * 256 * (256 * 128 / 8 + 256 * 64 + 2 * 128 * 64)
    x, scalars, bc, states = tokens * 4096 * 2, 2 * tokens * 64 * 4, 2 * tokens * 8 * 128 * 2, tokens / 256 * 4096 * 128 * 4
    assert ssd["bytes"] == 4 * (2 * (2 * x + scalars + bc) + states + (3 * x + 2 * scalars + 2 * bc + states))
    assert 1.0 < (ssd["bytes"] / 819e9) / (ssd["ops"] / 197e12) < 1.3  # the two bounds lie side by side; bytes the larger

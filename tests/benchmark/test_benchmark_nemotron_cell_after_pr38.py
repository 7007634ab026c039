"""What ``test_benchmark_nemotron_cell.py``'s two tests of ``BENCHMARK.json``'s
entries assert, less "four configurations, six cells" and "nemo3's cell is the last
name of four ``workloads`` lists": since PR 38 appended a configuration, a cell and
nine metrics after them, ``tests/conftest.py`` expects those two tests to fail, and
this holds everything else of their bodies, with every entry found by name, and
that what PR 38 added came after what was there."""

import os

import benchmark_tiny_tree as tiny

from test_benchmark_nemotron_cell import CELL, CONFIG, MIX, NEW_METRICS, _by_name


def test_the_cell_and_its_configuration_as_issue_32_set_them():
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
    # one cell of four chips, as before; the four configurations and six cells of PR 32 first, in their order
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == ["r50-train-dp4"]
    assert [c["name"] for c in bench["configs"]][:4] == [
        "retinanet-r50-fpn-800", "granite-4.0-h-micro-p1", "deepseek-v2-lite-ep8", CONFIG]
    assert [w["name"] for w in bench["workloads"]][:6] == [
        "r50-train-b8", "r50-train-dp4", "r50-train-b8-portrait", "granite-h-train-pack8k", "dsv2-lite-train-pack8k", CELL]


def test_what_the_benchmark_had_at_pr_32_is_still_there_word_for_word():
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
        at = cells.index(CELL)  # what later PRs appended lies behind it
        assert cells[at - 1:at + 1] == ["dsv2-lite-train-pack8k", CELL] and len(set(cells)) == len(cells), name
    assert (bench["run_seconds"], _by_name(bench["end_to_end"], "train_img_per_s_chip")["bound"],
            _by_name(bench["end_to_end"], "setup_s")["bound"]) == (10, 0.01, 0.1)

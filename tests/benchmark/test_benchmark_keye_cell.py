"""The learned-sparse-attention mixture-of-experts train kind end to end on the
CPU at the tiny size (three layers at d = 64, a query keeps 24 of a 64-token
document's keys, 4 of 16 experts held of width 32), from a throw-away checkout
whose ``BENCHMARK.json`` is the repo's with tiny configurations, mixes and cells
added beside the cell's own: untraced, in float32, traced, the two controls; and
the form of the entries PR 38 added to ``BENCHMARK.json`` and the numbers of its
cut, every entry FOUND BY NAME and never by its position in a list."""

import os
import shutil

import numpy as np
import pytest

import benchmark_tiny_tree as tiny

CELL = "keye-vl2-train-doc16k"
CONFIG = "keye-vl2-30b-a3b-ep8"
MIX = "lm-dsa-moe-train-doc16k-b1"
TINY_MODEL = dict(num_hidden_layers=3, vocab_size=128, hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                  head_dim=16, moe_intermediate_size=32, num_experts=4, num_experts_total=16, experts_held=[0, 1, 2, 3],
                  num_experts_per_tok=3, attention_q_block=32,
                  rope_scaling={"mrope_section": [2, 2, 4], "rope_type": "default", "type": "default"},
                  sa_config={"indexer_head_dim": 8, "indexer_num_heads": 4, "indexer_num_kv_heads": 1,
                             "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 24})
TINY_TRAFFIC = dict(seq_len=64, per_chip_batch=2, doc_len_min=64, warm_steps=4, window_steps=8, adam_eps=1e-12,
                    trace_steps=3, loss_rise_tol=1.0,
                    reference_blocks={"head_block": 2, "q_block": 32, "score_block": 16})
# The cell's own limits but one: heads of 8 have fewer products to average a rounding over than heads of 64, so at
# the tiny size a differing key lies 0.0026 of its products' size from the threshold where the cell reads 0.0010
# (limit 0.0025), and the scores' control 0.017 where the cell's reads 0.0065: the tiny size's limit lies between.
TINY_TOLERANCES = {"selection_distance_first": 0.006}
NEW_METRICS = ["keye_step.attention_ms", "keye_step.indexer_ms", "keye_step.select_ms", "keye_step.attention_core_ms",
               "keye_step.router_ms", "keye_step.experts_ms", "keye_gmm_roofline", "keye_attn_core_roofline",
               "keye_indexer_roofline"]


def control_launcher(control: str) -> str:
    """The cell through ``harness/keye_control.py`` instead of ``run.py``."""
    out = tiny.LAUNCHER.replace(
        "from benchmark import run\nsys.exit(run.main(sys.argv[1:]))",
        f"from benchmark.harness import keye_control\nsys.exit(keye_control.main(['--control', '{control}'] + sys.argv[1:]))")
    assert out != tiny.LAUNCHER
    return out


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
    traffic["tolerances"] = dict(traffic["tolerances"], **TINY_TOLERANCES)
    for name, cfg_extra in {"keye-tiny": {}, "keye-tiny-f32": {"compute_dtype": "float32"}}.items():
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
    return build(str(tmp_path_factory.mktemp("bench_keye")))


FIRST_STEP = {"loss", "aux_loss", "kl_loss", "grad_norm", "gnorm/embed", "gnorm/attention", "gnorm/indexer",
              "gnorm/router", "gnorm/experts", "gnorm/norms", "gnorm/head", "rows", "picks_differ", "selection",
              "threshold_ties", "update", "seconds"}


def test_keye_cell_end_to_end(tree):
    rc, line, out = tiny.run_cell(tree, "keye-tiny", trace=0)
    assert rc == 0 and line is not None, out[-3000:]
    assert set(line) == tiny.KEYS, line
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    assert set(line["metrics"]) == {"train_img_per_s_chip", "setup_s"}
    facts = tiny.facts(out)
    assert facts["tokens_per_s_chip"] == pytest.approx(64 * line["metrics"]["train_img_per_s_chip"]["value"])
    assert facts["compiles_in_window"] == 0 and facts["steps"] == 8 and facts["documents_per_sequence"] == 1.0
    first = facts["first_step"]
    assert set(first) == FIRST_STEP
    assert len(first["picks_differ"]["by_layer"]) == 3 and 0 <= first["picks_differ"]["max"] < 0.2
    s = first["selection"]
    assert len(s["differ_share_by_layer"]) == 3 and 0 <= s["differ_share_max"] < 0.2 and s["outside_allowed"] == 0
    # the selection is exact: 2 sequences x 3 layers x sum over 64 positions of min(p + 1, 24) pairs
    pairs = 2 * 3 * (sum(range(1, 25)) + 40 * 24)
    assert s["pairs"]["program"] == s["pairs"]["reference_f32"] == pairs
    assert s["selected_share"]["rel"] < 1e-6 and s["selected_share"]["layout"] == pytest.approx(pairs / 6 / (64 * 65 / 2))
    assert 0.9 < first["update"]["moved_min"] <= first["update"]["moved_max"] < 1.1
    # the pairs a step needs reach the facts: selected for the main attention, causal for the index scores
    assert facts["dsa_selected_pairs_per_step"] == pairs / 3 and facts["dsa_causal_pairs_per_step"] == 2 * 64 * 65 / 2
    assert all(share == pytest.approx(pairs / 6 / (64 * 65 / 2), rel=1e-6) for share in facts["dsa_selected_share_logged"])
    # the routing counters reach the facts: 2 x 64 tokens x 3 picks x 3 layers, a quarter of the experts held
    assert 0 <= facts["moe_rows_min_expert"] <= facts["moe_rows_max_expert"] <= 128
    assert 0.05 < facts["moe_rows_held_share_of_picks"] < 0.9 and facts["moe_buffer_rows"] == 384
    by_step = facts["moe_rows_held_share_of_picks_by_step"]
    assert [s for s, _ in by_step] == [8, 12, 16] and all(0 < share < 1 for _, share in by_step)
    assert facts["model_flops_per_step"]["routed_experts"] == pytest.approx(
        3 * 2 * 3 * 64 * 32 * facts["moe_rows_held_per_step"])
    assert sum(facts["setup_parts_s"].values()) == pytest.approx(facts["setup_s"], abs=1e-6)


def test_keye_cell_in_float32_agrees_closely_with_the_reference(tree):
    """The same program computing in float32: what is left is the order of the
    sums, no token picks another expert and no query another key."""
    rc, line, out = tiny.run_cell(tree, "keye-tiny-f32", trace=0)
    assert rc == 0 and line is not None and line["correct"] is True, out[-3000:]
    first = tiny.facts(out)["first_step"]
    for name in FIRST_STEP - {"rows", "picks_differ", "selection", "threshold_ties", "update", "seconds"}:
        assert first[name]["rel"] < 1e-4, (name, first[name])
    assert first["picks_differ"]["max"] == 0.0 and {v["rel"] for v in first["rows"].values()} == {0.0}
    assert first["selection"]["differ_share_max"] == 0.0 and first["selection"]["distance_max"] == 0.0
    assert first["update"]["sign_agreement_min"] > 0.995 and first["update"]["decay_error_max"] < 1e-3


@pytest.mark.parametrize("control", ["operands", "scores"])
def test_the_control_is_not_correct(tmp_path, control):
    """The nearest precision below the one the configuration states
    (``harness/keye_control.py``: fp8 operands of the weight matmuls, or of the
    index scores alone; the program wrapped from outside) fails one of the
    cell's own limits."""
    rc, line, out = tiny.run_cell(build(str(tmp_path / "tree"), control_launcher(control)), "keye-tiny", trace=0)
    assert rc == 0 and line is not None, out[-3000:]
    assert tiny.said(out, f"benchmark: CONTROL {control}:") and line["correct"] is False
    problems = tiny.said(out, "benchmark: NOT CORRECT:")
    assert problems and all(p.startswith("first step's") for p in problems), problems
    if control == "scores":  # the selection moves and nothing else does
        assert all("selection" in p for p in problems), problems


def test_traced_keye_cell_reads_what_a_cpu_gives(tree):
    rc, line, out = tiny.run_cell(tree, "keye-tiny", trace=1)
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
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json"
    cfg = tiny._load(os.path.join(tiny.REPO, entry["file"]))
    assert cfg["source"] == entry["source"] and cfg["reduced"] == entry["reduced"] and cfg["name"] == CONFIG
    # the cut: depth, experts held, vocabulary
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (6, 16, 151936 // 8)
    assert cfg["published"]["num_hidden_layers"] == 48 and cfg["published"]["num_experts"] == 128
    assert cfg["published"]["vocab_size"] == 151936 and "8 chips" in cfg["deployment"]
    assert cfg["num_experts_total"] == 128 and cfg["experts_held"] == list(range(16))
    # every width as published
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]) == (2048, 32, 4, 128)
    assert (cfg["moe_intermediate_size"], cfg["num_experts_per_tok"], cfg["norm_topk_prob"]) == (768, 8, True)
    assert cfg["sa_config"] == {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                                "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048}
    assert cfg["rope_theta"] == 10_000_000 and cfg["rope_scaling"]["mrope_section"] == [16, 24, 24]
    held = cfg["parameters_held"]
    assert held["layer"] == 96_899_456 == (held["attention"] + held["indexer"] + held["router"]
                                            + held["routed_experts_of_a_layer"] + held["norms_of_a_layer"])
    assert held["total"] == 659_190_016 == 6 * held["layer"] + held["embedding_head_and_final_norm"]
    assert {"weights", "topk_counts_tokens", "q_k_norm", "indexer", "indexer_loss", "router_aux_loss_coef",
            "experts_held", "rotary_layout", "zero_scores", "recomputation"} <= set(cfg["assumed"])
    t = tiny._load(os.path.join(tiny.REPO, "benchmark", "traffic", cell["traffic"] + ".json"))
    assert t["kind"] == "lm_dsa_moe_train_loop"
    assert os.path.exists(os.path.join(tiny.REPO, "benchmark", "kinds", t["kind"] + ".py"))
    # the optimizer, the pool and the window are dsv2's number for number; the layout is this cell's own
    theirs = tiny._load(os.path.join(tiny.REPO, "benchmark", "traffic", "lm-moe-train-pack8k-b2.json"))
    same = ("pool_batches", "device_prefetch", "log_every", "lr", "adam_b2", "adam_eps", "weight_decay",
            "clip_global_norm", "warm_steps", "window_steps", "trace_steps", "trace_steady_runs", "loss_rise_tol",
            "step_program_pattern")
    assert {k: t[k] for k in same} == {k: theirs[k] for k in same}
    assert (t["seq_len"], t["per_chip_batch"], t["doc_len_min"], t["warm_steps"], t["window_steps"]) == (16384, 1, 16384, 3, 12)
    assert t["seq_len"] * t["per_chip_batch"] == theirs["seq_len"] * theirs["per_chip_batch"]  # the same tokens a step
    # every limit of the comparison is written with its two readings
    limits = set(t["tolerances"]) - {"why"}
    assert limits == {"loss_rel", "aux_loss_rel", "kl_loss_rel", "grad_norm_rel", "rows_held_rel", "rows_expert_rel",
                      "picks_differ_max", "selection_differ_first", "selection_distance_first", "selection_differ_max",
                      "selection_distance_max", "selected_share_rel",
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
    # one cell of four chips, as before
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == ["r50-train-dp4"]


def test_every_catalog_number_is_in_the_file_or_in_reduced():
    """The configuration file holds every number of the catalog row's ``config``
    under the same key; what differs is listed in ``reduced``."""
    import json

    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Keye-VL-2.0-30B-A3B")
    cfg = tiny._load(os.path.join(tiny.REPO, "benchmark", "configs", CONFIG + ".json"))
    assert cfg["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cfg.get(k, "absent") != v}
    assert differs == set(cfg["reduced"]), differs


def test_what_the_benchmark_had_is_still_there_word_for_word():
    """PR 38 appends: every accepted entry is found by name with the keys it
    had, and the accepted ``workloads`` lists keep their cells in their order
    with this cell behind them."""
    bench = tiny._load(os.path.join(tiny.REPO, "BENCHMARK.json"))
    for name in ("r50-train-b8", "r50-train-dp4", "r50-train-b8-portrait", "granite-h-train-pack8k",
                 "dsv2-lite-train-pack8k", "nemo3-nano-train-pack8k"):
        _by_name(bench["workloads"], name)
    for name in ("retinanet-r50-fpn-800", "granite-4.0-h-micro-p1", "deepseek-v2-lite-ep8", "nemotron-3-nano-30b-ep16"):
        _by_name(bench["configs"], name)
    for entries, name in ((bench["end_to_end"], "train_img_per_s_chip"), (bench["per_layer"], "train_loop.data_wait_ms"),
                          (bench["per_layer"], "train_step.device_ms"), (bench["per_layer"], "train_step.mfu_pct")):
        cells = _by_name(entries, name)["workloads"]
        assert cells[-2:] == ["nemo3-nano-train-pack8k", CELL] and len(set(cells)) == len(cells), name
    assert (bench["run_seconds"], _by_name(bench["end_to_end"], "train_img_per_s_chip")["bound"],
            _by_name(bench["end_to_end"], "setup_s")["bound"]) == (10, 0.01, 0.1)


@pytest.mark.parametrize("seed", [0, 1, 7, 905418237, 2100415840, 2**31 + 12345])
def test_the_layout_is_one_document_a_sequence_for_every_seed(seed):
    """The traffic file fixes the layout: whatever ``--seed``, every sequence of
    the pool is one document, so nothing of attention's work follows the seed;
    the token ids do follow it."""
    import itertools

    from batchai_retinanet_horovod_coco_tpu.data.tokens import PackedTokensConfig, packed_token_batches

    t = tiny._load(os.path.join(tiny.REPO, "benchmark", "traffic", MIX + ".json"))
    make = lambda s: list(itertools.islice(packed_token_batches(PackedTokensConfig(
        vocab_size=18992, seq_len=t["seq_len"], batch_size=t["per_chip_batch"], doc_len_median=t["doc_len_median"],
        doc_len_sigma=t["doc_len_sigma"], doc_len_min=t["doc_len_min"], seed=s)), t["pool_batches"]))
    pool = make(seed)
    assert all(b.segment_ids.shape == (1, 16384) and not b.segment_ids.any() for b in pool)
    assert not np.array_equal(pool[0].tokens, make(seed + 1)[0].tokens)


@pytest.mark.parametrize("tokens,rows", [(16384, 6 * 8192.0), (128, 40.0)])
def test_the_flop_and_byte_counts_by_hand(tokens, rows):
    """``harness/keye_flops.py`` at the published widths: the issue's forward
    FLOPs a token and layer (dense products 52 M, index scores 17 M, selected
    attention 33.5 M at T = 16 384), and the three parts' costs."""
    from benchmark.harness import keye_flops as kf

    cfg = tiny._load(os.path.join(tiny.REPO, "benchmark", "configs", CONFIG + ".json"))
    seg = np.zeros((1, tokens), np.int32)
    selected = kf.selected_pairs([seg], 2048)
    causal = tokens * (tokens + 1) / 2
    assert selected == (2048 * 2049 / 2 + (tokens - 2048) * 2048 if tokens > 2048 else causal)
    f = kf.forward_flops_per_step(cfg, tokens, selected, causal, rows)
    assert f["attention_matmuls"] == 2.0 * tokens * 6 * (2 * 2048 * 4096 + 2 * 2048 * 512)
    assert f["indexer_matmuls"] == 2.0 * tokens * 6 * 2048 * (1024 + 64 + 16)
    assert f["attention_pairs"] == 2.0 * selected * 6 * 32 * 256 and f["index_scores"] == 2.0 * causal * 6 * 16 * 64
    assert f["router"] == 2.0 * tokens * 6 * 2048 * 128 and f["routed_experts"] == 2.0 * rows * 3 * 2048 * 768
    assert f["lm_head"] == 2.0 * tokens * 18992 * 2048
    assert f["total"] == pytest.approx(sum(v for k, v in f.items() if k != "total"))
    if tokens == 16384:  # ISSUE 38, per token and layer: projections 38, an expert's 9.4, the indexer's 4.5; 17; 33.5 (31 after the first 2048)
        per = lambda key: round(f[key] / tokens / 6 / 1e6, 1)
        assert [per(k) for k in ("attention_matmuls", "indexer_matmuls", "index_scores", "attention_pairs")] == [
            37.7, 4.5, 16.8, 31.5]
        assert round(f["routed_experts"] / rows / 1e6, 1) == 9.4
    train = kf.train_flops_per_step(cfg, tokens, selected, causal, rows)
    assert train["total"] == pytest.approx(3 * f["total"])
    gmm = kf.gmm_cost_per_step(cfg, rows)
    assert gmm["ops"] == 4 * f["routed_experts"]
    weights = 6 * 16 * 3 * 2048 * 768 * 2  # six layers' held experts, bfloat16
    assert gmm["bytes"] == pytest.approx(4 * weights + 4 * rows * (2048 + 2 * 768 + 768 + 2048) * 2)
    core = kf.attention_core_cost_per_step(cfg, tokens, selected)
    assert core["ops"] == 4 * f["attention_pairs"] and core["bytes"] == 4 * 6 * tokens * (2 * 4096 + 2 * 512) * 2
    indexer = kf.indexer_cost_per_step(cfg, tokens, causal)
    assert indexer["ops"] == 4 * (f["indexer_matmuls"] + f["index_scores"])
    if tokens == 16384:  # the operations bound applies to both (the scores once a pass are most of the indexer's bytes)
        assert core["ops"] / 197e12 > core["bytes"] / 819e9 and indexer["ops"] / 197e12 > indexer["bytes"] / 819e9


def test_the_pairs_of_a_packed_layout():
    """``selected_pairs`` restarts with every document, as the selection does."""
    from benchmark.harness import keye_flops as kf

    seg = np.array([[0] * 5 + [1] * 3, [0] * 8])
    assert kf.selected_pairs([seg], 4) == ((1 + 2 + 3 + 4 + 4) + (1 + 2 + 3) + (1 + 2 + 3 + 4 * 5)) / 2
    assert kf.selected_pairs([seg], 100) == ((15 + 6) + 36) / 2

"""The mixture-of-experts train kind end to end on the CPU at the tiny size
(a dense and two expert layers at d = 64, 4 of 16 experts held), from a
throw-away checkout whose ``BENCHMARK.json`` is the repo's with tiny
configurations, mixes and cells added beside the cell's own: untraced, in
float32, traced, the control; and the form of the entries PR 30 added to
``BENCHMARK.json`` and the numbers of its cut, every entry FOUND BY NAME and
never by its position in a list."""

import os
import shutil

import pytest

import benchmark_tiny_tree as tiny

CELL = "dsv2-lite-train-pack8k"
CONFIG = "deepseek-v2-lite-ep8"
MIX = "lm-moe-train-pack8k-b2"
TINY_MODEL = dict(num_hidden_layers=3, vocab_size=128, hidden_size=64, intermediate_size=160, moe_intermediate_size=32,
                  num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=4, n_routed_experts_total=16,
                  experts_held=[0, 1, 2, 3], num_experts_per_tok=3,
                  rope_scaling=dict(beta_fast=32, beta_slow=1, factor=40, mscale=0.707, mscale_all_dim=0.707,
                                    original_max_position_embeddings=16, type="yarn"))
TINY_TRAFFIC = dict(seq_len=64, per_chip_batch=2, doc_len_median=16, doc_len_min=4, warm_steps=4, window_steps=8,
                    adam_eps=1e-12, trace_steps=3, loss_rise_tol=1.0, reference_blocks={"head_block": 2})
NEW_METRICS = ["moe_step.mla_ms", "moe_step.router_ms", "moe_step.experts_ms", "moe_step.shared_ms", "moe_gmm_roofline",
               "moe_step.mla_core_ms"]

# the cell through ``harness/moe_lm_control.py`` instead of ``run.py``
CONTROL_LAUNCHER = tiny.LAUNCHER.replace("from benchmark import run\nsys.exit(run.main(",
                                         "from benchmark.harness import moe_lm_control\nsys.exit(moe_lm_control.main(")
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
    for name, cfg_extra in {"moe-tiny": {}, "moe-tiny-f32": {"compute_dtype": "float32"}}.items():
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
    return build(str(tmp_path_factory.mktemp("bench_moe")))


FIRST_STEP = {"loss", "aux_loss", "grad_norm", "gnorm/embed", "gnorm/attention", "gnorm/dense_mlp", "gnorm/router",
              "gnorm/experts", "gnorm/shared", "gnorm/norms", "gnorm/head", "rows", "picks_differ", "update", "seconds"}


def test_moe_cell_end_to_end(tree):
    rc, line, out = tiny.run_cell(tree, "moe-tiny", trace=0)
    assert rc == 0 and line is not None, out[-3000:]
    assert set(line) == tiny.KEYS, line
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    assert set(line["metrics"]) == {"train_img_per_s_chip", "setup_s"}
    facts = tiny.facts(out)
    assert facts["tokens_per_s_chip"] == pytest.approx(64 * line["metrics"]["train_img_per_s_chip"]["value"])
    assert facts["compiles_in_window"] == 0
    first = facts["first_step"]
    assert set(first) == FIRST_STEP
    assert len(first["picks_differ"]["by_layer"]) == 2 and 0 <= first["picks_differ"]["max"] < 0.2
    assert 0.9 < first["update"]["moved_min"] <= first["update"]["moved_max"] < 1.1
    # the routing counters reach the facts: 2 x 64 tokens x 3 picks x 2 expert layers, a quarter of the experts held
    assert 0 < facts["moe_rows_min_expert"] <= facts["moe_rows_max_expert"] <= 128
    assert 0.1 < facts["moe_rows_held_share_of_picks"] < 0.9 and facts["moe_buffer_rows"] == 384
    assert facts["model_flops_per_step"]["routed_experts"] == pytest.approx(
        3 * 2 * 3 * 64 * 32 * facts["moe_rows_held_per_step"])
    assert sum(facts["setup_parts_s"].values()) == pytest.approx(facts["setup_s"], abs=1e-6)


def test_moe_cell_in_float32_agrees_closely_with_the_reference(tree):
    """The same program computing in float32: what is left is the order of
    the sums (blocks against dense, sorted rows against every token), and no
    token picks another expert."""
    rc, line, out = tiny.run_cell(tree, "moe-tiny-f32", trace=0)
    assert rc == 0 and line is not None and line["correct"] is True, out[-3000:]
    first = tiny.facts(out)["first_step"]
    for name in FIRST_STEP - {"rows", "picks_differ", "update", "seconds"}:
        assert first[name]["rel"] < 1e-4, (name, first[name])
    assert first["picks_differ"]["max"] == 0.0 and {v["rel"] for v in first["rows"].values()} == {0.0}
    assert first["update"]["sign_agreement_min"] > 0.995 and first["update"]["decay_error_max"] < 1e-3


def test_the_control_is_not_correct(tmp_path):
    """The nearest precision below the one the configuration states
    (``harness/moe_lm_control.py``: fp8 matmul operands, the program wrapped
    from outside) fails one of the cell's own limits."""
    rc, line, out = tiny.run_cell(build(str(tmp_path / "tree"), CONTROL_LAUNCHER), "moe-tiny", trace=0)
    assert rc == 0 and line is not None, out[-3000:]
    assert tiny.said(out, "benchmark: CONTROL:") and line["correct"] is False
    problems = tiny.said(out, "benchmark: NOT CORRECT:")
    assert problems and all(p.startswith("first step's") for p in problems), problems


def test_traced_moe_cell_reads_what_a_cpu_gives(tree):
    rc, line, out = tiny.run_cell(tree, "moe-tiny", trace=1)
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
    assert entry["source"] == "https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json"
    cfg = tiny._load(os.path.join(tiny.REPO, entry["file"]))
    assert cfg["source"] == entry["source"] and cfg["reduced"] == entry["reduced"]
    # the cut: depth, experts held, vocabulary; beside them what was published and the deployment
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (6, 8, 102400 // 8)
    assert cfg["published"]["num_hidden_layers"] == 27 and cfg["published"]["n_routed_experts"] == 64
    assert cfg["published"]["vocab_size"] == 102400 and "8 chips" in cfg["deployment"]
    assert cfg["n_routed_experts_total"] == 64 and cfg["experts_held"] == list(range(8))
    # every width as published
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"]) == (2048, 16, 128, 64, 128, 512)
    assert (cfg["moe_intermediate_size"], cfg["n_shared_experts"], cfg["intermediate_size"],
            cfg["num_experts_per_tok"], cfg["first_k_dense_replace"]) == (1408, 2, 10944, 6, 1)
    assert cfg["rope_scaling"] == dict(beta_fast=32, beta_slow=1, factor=40, mscale=0.707, mscale_all_dim=0.707,
                                       original_max_position_embeddings=4096, type="yarn")
    assert {"weights", "aux_loss_alpha", "recomputation"} <= set(cfg["assumed"])
    t = tiny._load(os.path.join(tiny.REPO, "benchmark", "traffic", cell["traffic"] + ".json"))
    assert t["kind"] == "lm_moe_train_loop"
    assert (t["seq_len"], t["per_chip_batch"], t["pool_batches"], t["log_every"], t["device_prefetch"]) == (8192, 2, 4, 4, 2)
    assert (t["doc_len_median"], t["doc_len_sigma"], t["doc_len_min"]) == (512, 1.3, 16)
    assert (t["lr"], t["adam_b2"], t["adam_eps"], t["weight_decay"], t["clip_global_norm"]) == (3e-4, 0.95, 1e-8, 0.1, 1.0)
    # every limit of the comparison is written with its two readings
    limits = set(t["tolerances"]) - {"why"}
    assert limits == {"loss_rel", "aux_loss_rel", "grad_norm_rel", "rows_held_rel", "rows_expert_rel",
                      "picks_differ_max", "update_moved",
                      "update_held_share", "update_sign_agreement", "update_decay_error"}
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
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"] and "\t" not in e["why"], e["name"]
    for name in NEW_METRICS:
        m = _by_name(bench["per_layer"], name)
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["workloads"] == [CELL] and m["moves"] == "train_img_per_s_chip" and m["layer"] == "train step"
        assert m["source"] == "device_trace" and (m["unit"], m["better"]) == (
            ("%", "higher") if name.endswith("_roofline") else ("ms/step", "lower"))
    # one cell of four chips, as before
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == ["r50-train-dp4"]


def test_the_configuration_file_holds_every_number_of_the_catalog_row():
    """Every key of the catalog's copy of the published config.json at the
    top level with the published value, but the three ``reduced``."""
    published = dict(
        attention_bias=False, first_k_dense_replace=1, hidden_act="silu", hidden_size=2048, intermediate_size=10944,
        kv_lora_rank=512, max_position_embeddings=163840, model_type="deepseek_v2", moe_intermediate_size=1408,
        moe_layer_freq=1, n_group=1, n_routed_experts=64, n_shared_experts=2, norm_topk_prob=False,
        num_attention_heads=16, num_experts_per_tok=6, num_hidden_layers=27, num_key_value_heads=16, q_lora_rank=None,
        qk_nope_head_dim=128, qk_rope_head_dim=64, rms_norm_eps=1e-06, rope_theta=10000, routed_scaling_factor=1,
        scoring_func="softmax", seq_aux=True, tie_word_embeddings=False, topk_group=1, topk_method="greedy",
        v_head_dim=128, vocab_size=102400)
    cfg = tiny._load(os.path.join(tiny.REPO, "benchmark", "configs", CONFIG + ".json"))
    differs = sorted(k for k, v in published.items() if k not in cfg or cfg[k] != v)
    assert differs == sorted(cfg["reduced"]), differs

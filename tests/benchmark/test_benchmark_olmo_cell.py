"""The linear-attention train kind end to end on the CPU at the tiny size (one
period of three gated-delta-rule layers and a full attention layer at d = 64, 4
heads of key 8 / value 16, chunks of 8), from a throw-away checkout whose
``BENCHMARK.json`` is the repo's with tiny configurations, mixes and cells added
beside the cell's own: untraced, in float32, traced, the two controls; and the form
of the entries PR 40 added to ``BENCHMARK.json`` and the numbers of its cut, every
entry FOUND BY NAME and never by its position in a list."""

import os
import shutil

import numpy as np
import pytest

import benchmark_tiny_tree as tiny

CELL = "olmo-hybrid-train-pack8k"
CONFIG = "olmo-hybrid-7b-p1"
MIX = "lm-linear-train-pack8k-fixed"
TINY_MODEL = dict(num_hidden_layers=4, vocab_size=128, hidden_size=64, intermediate_size=128, num_attention_heads=4,
                  num_key_value_heads=4, linear_num_key_heads=4, linear_num_value_heads=4, linear_key_head_dim=8,
                  linear_value_head_dim=16, attention_q_block=32, delta_rule_chunk=8)
TINY_TRAFFIC = dict(seq_len=64, per_chip_batch=2, doc_len_median=16, doc_len_min=4, warm_steps=4, adam_eps=1e-12,
                    trace_steps=3, loss_rise_tol=1.0, reference_blocks={"scan_block": 16, "head_block": 2})
# The cell's own limits but one: the loss of a vocabulary of 128 is ln 128 plus a little, and bfloat16 moves it by
# 4e-5 of itself where the cell's 12 544 rows read under 1e-5.
TINY_TOLERANCES = {"loss_rel": 2e-4}
NEW_METRICS = ["olmo_step.gdn_ms", "olmo_step.delta_rule_ms", "olmo_step.conv_ms", "olmo_step.attention_ms",
               "olmo_step.mlp_ms", "olmo_delta_rule_roofline"]
FIRST_STEP = {"loss", "grad_norm", "gnorm/embed", "gnorm/gdn", "gnorm/attention", "gnorm/mlp", "gnorm/norms",
              "gnorm/head", "gdn/alpha_mean", "gdn/beta_mean", "gdn/state_norm_max", "update", "seconds"}


def control_launcher(control: str) -> str:
    """The cell through ``harness/olmo_control.py`` instead of ``run.py``."""
    out = tiny.LAUNCHER.replace(
        "from benchmark import run\nsys.exit(run.main(sys.argv[1:]))",
        f"from benchmark.harness import olmo_control\nsys.exit(olmo_control.main(['--control', '{control}'] + sys.argv[1:]))")
    assert out != tiny.LAUNCHER
    return out


def build(root: str, launcher: str = tiny.LAUNCHER) -> str:
    """``benchmark/`` copied, then a tiny configuration, its mixes (the cell's
    own tolerances; one computes in float32) and their cells added beside, listed
    wherever the cell is."""
    shutil.copytree(os.path.join(tiny.REPO, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = os.path.join(root, "benchmark")
    bench = tiny._load(os.path.join(tiny.REPO, "BENCHMARK.json"))
    cfg = tiny._load(os.path.join(b, "configs", CONFIG + ".json"))
    traffic = dict(tiny._load(os.path.join(b, "traffic", MIX + ".json")), **TINY_TRAFFIC)
    traffic["tolerances"] = dict(traffic["tolerances"], **TINY_TOLERANCES)
    for name, cfg_extra in {"olmo-tiny": {}, "olmo-tiny-f32": {"compute_dtype": "float32"}}.items():
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
    return build(str(tmp_path_factory.mktemp("bench_olmo")))


def test_olmo_cell_end_to_end(tree):
    rc, line, out = tiny.run_cell(tree, "olmo-tiny", trace=0)
    assert rc == 0 and line is not None, out[-3000:]
    assert set(line) == tiny.KEYS, line
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    assert set(line["metrics"]) == {"train_img_per_s_chip", "setup_s"}
    facts = tiny.facts(out)
    assert facts["tokens_per_s_chip"] == pytest.approx(64 * line["metrics"]["train_img_per_s_chip"]["value"])
    assert facts["compiles_in_window"] == 0 and facts["steps"] >= 4
    # run_meta and the delta rule's counters reach the facts
    assert (facts["delta_rule_lowering"], facts["delta_rule_chunk"], facts["attention_lowering"]) == ("xla", 8, "xla")
    assert all(0.5 < a < 1.0 for a in facts["gdn_alpha_mean_logged"]) and facts["gdn_alpha_mean_logged"]
    assert all(0.9 < b < 1.1 for b in facts["gdn_beta_mean_logged"])
    assert all(0.0 < n < 10.0 for n in facts["gdn_state_norm_max_logged"])
    first = facts["first_step"]
    assert set(first) == FIRST_STEP
    assert 0.9 < first["update"]["moved_min"] <= first["update"]["moved_max"] < 1.1
    f = facts["model_flops_per_sequence"]
    assert f["delta_rule"] == 3 * 3 * 2.0 * 64 * 4 * (2 * 8 * 8 + 2 * 8 * 16 + 3 * 8 * 16)
    assert sum(facts["setup_parts_s"].values()) == pytest.approx(facts["setup_s"], abs=1e-6)


def test_olmo_cell_in_float32_agrees_closely_with_the_reference(tree):
    """The same program computing in float32: what is left is the order of the
    sums (the chunked form against the recurrence)."""
    rc, line, out = tiny.run_cell(tree, "olmo-tiny-f32", trace=0)
    assert rc == 0 and line is not None and line["correct"] is True, out[-3000:]
    first = tiny.facts(out)["first_step"]
    for name in FIRST_STEP - {"update", "seconds"}:
        assert first[name]["rel"] < 1e-4, (name, first[name])
    assert first["update"]["sign_agreement_min"] > 0.995 and first["update"]["decay_error_max"] < 1e-3


@pytest.mark.parametrize("control", ["operands", "state"])
def test_the_control_is_not_correct(tmp_path, control):
    """The nearest precision below the one the configuration states
    (``harness/olmo_control.py``: fp8 operands of the weight matmuls, or the delta
    rule's keys, values and state alone; the program wrapped from outside) fails
    one of the cell's own limits."""
    rc, line, out = tiny.run_cell(build(str(tmp_path / "tree"), control_launcher(control)), "olmo-tiny", trace=0)
    assert rc == 0 and line is not None, out[-3000:]
    assert tiny.said(out, f"benchmark: CONTROL {control}:") and line["correct"] is False
    problems = tiny.said(out, "benchmark: NOT CORRECT:")
    assert problems and all(p.startswith("first step's") for p in problems), problems


def test_traced_olmo_cell_reads_what_a_cpu_gives(tree):
    rc, line, out = tiny.run_cell(tree, "olmo-tiny", trace=1)
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
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert entry["source"] == "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
    cfg = tiny._load(os.path.join(tiny.REPO, entry["file"]))
    assert cfg["source"] == entry["source"] and cfg["reduced"] == entry["reduced"] and cfg["name"] == CONFIG
    # the cut: depth (the first whole period; layer_types kept whole) and vocabulary
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (4, 100352 // 8)
    assert cfg["published"]["num_hidden_layers"] == 32 and cfg["published"]["vocab_size"] == 100352
    assert len(cfg["layer_types"]) == 32 and cfg["layer_types"][:4] == ["linear_attention"] * 3 + ["full_attention"]
    assert "eight pipeline stages" in cfg["deployment"] and "first eighth" in cfg["deployment"]
    # every width as published
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]) == (
        3840, 11008, 30, 30)
    assert (cfg["linear_num_key_heads"], cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"], cfg["linear_allow_neg_eigval"]) == (
        30, 30, 96, 192, 4, True)
    assert cfg["rope_parameters"] == {"rope_theta": None} and cfg["tie_word_embeddings"] is False
    held = cfg["parameters_held"]
    assert held["gdn_mixer"] == 88_750_332 == (2 * 3840 * 2880 + 3 * 3840 * 5760 + 2 * 3840 * 30 + 4 * 11520 + 60 + 192)
    assert held["mlp"] == 126_812_160 and held["gdn_layer"] == held["gdn_mixer"] + held["mlp"] + 2 * 3840
    assert held["attention_layer"] == 185_809_920 == 4 * 3840 ** 2 + 2 * 3840 + held["mlp"] + 2 * 3840
    assert held["one_period"] == 832_520_436 == 3 * held["gdn_layer"] + held["attention_layer"]
    assert held["total"] == 928_862_196 == held["one_period"] + 2 * 12544 * 3840 + 3840
    assert {"norm_placement", "qk_norm", "head_dim", "rotation", "linear_attention", "delta_rule_chunk", "weights", "A_log",
            "dt_bias", "layer_types_at_4_layers", "recomputation", "unchecked"} <= set(cfg["assumed"])
    assert "unchecked against the hub" in cfg["assumed"]["unchecked"]
    t = tiny._load(os.path.join(tiny.REPO, "benchmark", "traffic", cell["traffic"] + ".json"))
    assert t["kind"] == "lm_linear_train_loop"
    assert os.path.exists(os.path.join(tiny.REPO, "benchmark", "kinds", t["kind"] + ".py"))
    # granite's mix number for number, but for the kind, its description, the fixed layout, the limits and the
    # reference's blocks
    theirs = tiny._load(os.path.join(tiny.REPO, "benchmark", "traffic", "lm-train-pack8k.json"))
    own = {"kind", "what", "layout_seed", "layout", "tolerances", "reference_blocks"}
    assert set(t) - set(theirs) == {"layout_seed", "layout"} and set(theirs) <= set(t)
    assert {k: t[k] for k in set(theirs) - own} == {k: theirs[k] for k in set(theirs) - own}
    assert t["layout_seed"] == 20261001 and (t["seq_len"], t["per_chip_batch"], t["warm_steps"]) == (8192, 1, 6)
    # every limit of the comparison is written with its two readings
    limits = set(t["tolerances"]) - {"why"}
    assert limits == {"loss_rel", "grad_norm_rel", "alpha_mean_rel", "beta_mean_rel", "state_norm_max_rel",
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
        row = next(r for r in map(json.loads, f) if r["name"] == "Olmo-Hybrid-7B")
    cfg = tiny._load(os.path.join(tiny.REPO, "benchmark", "configs", CONFIG + ".json"))
    assert cfg["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cfg.get(k, "absent") != v}
    assert differs == set(cfg["reduced"]), differs


def test_what_the_benchmark_had_is_still_there_word_for_word():
    """PR 40 appends: every accepted entry is found by name with the keys it
    had, and the accepted ``workloads`` lists keep their cells in their order with
    this cell somewhere behind them."""
    bench = tiny._load(os.path.join(tiny.REPO, "BENCHMARK.json"))
    accepted = ["r50-train-b8", "r50-train-dp4", "r50-train-b8-portrait", "granite-h-train-pack8k",
                "dsv2-lite-train-pack8k", "nemo3-nano-train-pack8k", "keye-vl2-train-doc16k"]
    for name in accepted:
        _by_name(bench["workloads"], name)
    for name in ("retinanet-r50-fpn-800", "granite-4.0-h-micro-p1", "deepseek-v2-lite-ep8", "nemotron-3-nano-30b-ep16",
                 "keye-vl2-30b-a3b-ep8"):
        _by_name(bench["configs"], name)
    for entries, name in ((bench["end_to_end"], "train_img_per_s_chip"), (bench["per_layer"], "train_loop.data_wait_ms"),
                          (bench["per_layer"], "train_step.device_ms"), (bench["per_layer"], "train_step.mfu_pct")):
        cells = _by_name(entries, name)["workloads"]
        before = [c for c in accepted if c in cells]
        assert cells[:len(before)] == before and CELL in cells[len(before):] and len(set(cells)) == len(cells), name
    assert (bench["run_seconds"], _by_name(bench["end_to_end"], "train_img_per_s_chip")["bound"],
            _by_name(bench["end_to_end"], "setup_s")["bound"]) == (10, 0.01, 0.1)


def _pool(seed):
    from benchmark.kinds import lm_linear_train_loop as kind

    t = tiny._load(os.path.join(tiny.REPO, "benchmark", "traffic", MIX + ".json"))
    return t, kind.packed_pool({"vocab_size": 12544}, t, seed)


@pytest.mark.parametrize("seed", [0, 1, 7, 905418237, 2100415840, 2**31 + 12345])
def test_the_layout_does_not_move_with_the_seed(seed):
    """The traffic file fixes the layout: whatever ``--seed``, the four pooled
    batches' ``segment_ids`` are the ones the file records, so nothing of the delta
    rule's resets or of attention's blocks follows the seed; the token ids do."""
    from batchai_retinanet_horovod_coco_tpu.ops import attention

    t, pool = _pool(seed)
    _, other = _pool(seed + 1)
    assert all(np.array_equal(a.segment_ids, b.segment_ids) for a, b in zip(pool, other))
    assert not np.array_equal(pool[0].tokens, other[0].tokens)
    assert all(b.segment_ids.shape == (1, 8192) and b.tokens.max() < 12544 for b in pool)
    layout = t["layout"]
    assert [int(b.segment_ids.max() + 1) for b in pool] == layout["documents_by_batch"]
    assert np.mean(layout["documents_by_batch"]) == layout["documents_per_sequence"]
    shares = [run / causal for causal, run in (attention.block_pair_counts(b.segment_ids, 1024, 1024) for b in pool)]
    assert shares == pytest.approx(layout["attn/block_pairs_run_share"]["by_batch"])
    assert np.mean(shares) == pytest.approx(layout["attn/block_pairs_run_share"]["mean"])


def test_the_flop_and_byte_counts_by_hand():
    """``harness/olmo_flops.py`` at the published widths: the issue's TFLOP a step
    (the five projections 13.1, the MLPs 24.9, the full layer's four 2.9, the head
    2.4) and the delta rule's minimal chunked operations and bytes."""
    from benchmark.harness import lm_flops
    from benchmark.harness import olmo_flops as of

    cfg = tiny._load(os.path.join(tiny.REPO, "benchmark", "configs", CONFIG + ".json"))
    t, pool = _pool(0)
    pairs = lm_flops.attention_pairs([b.segment_ids for b in pool])
    assert pairs == t["layout"]["same_document_causal_pairs_per_sequence"]
    f = of.forward_flops_per_sequence(cfg, 8192, pairs, 128)
    assert f["gdn_matmuls"] == 2.0 * 8192 * 3 * (3840 * (2 * 2880 + 2 * 5760 + 60) + 5760 * 3840)
    assert f["delta_rule"] == 3 * 2.0 * 8192 * 30 * (2 * 128 * 96 + 2 * 128 * 192 + 3 * 96 * 192)
    assert f["attention_matmuls"] == 2.0 * 8192 * 4 * 3840 ** 2 and f["attention_pairs"] == 2.0 * pairs * 2 * 3840
    assert f["mlp"] == 2.0 * 8192 * 4 * 3 * 3840 * 11008 and f["lm_head"] == 2.0 * 8192 * 12544 * 3840
    assert f["total"] == pytest.approx(sum(v for k, v in f.items() if k != "total"))
    train = of.train_flops_per_sequence(cfg, 8192, pairs, 128)
    assert train["total"] == pytest.approx(3 * f["total"])
    tera = lambda key: round(train[key] / 1e12, 1)
    assert [tera(k) for k in ("gdn_matmuls", "mlp", "attention_matmuls", "lm_head", "delta_rule")] == [
        13.1, 24.9, 2.9, 2.4, 0.6]
    cost = of.delta_rule_cost_per_step(cfg, 8192, 128)
    assert cost["ops"] == 3 * f["delta_rule"]  # forward and two gradients a product, no recomputation
    qkv, out, scalars = 8192 * 30 * (2 * 96 + 192) * 2, 8192 * 30 * 192 * 2, 2 * 8192 * 30 * 4
    states = 64 * 30 * 96 * 192 * 4
    assert cost["bytes"] == 3 * ((qkv + scalars + out + states) + (qkv + scalars + out + states) + (qkv + scalars))
    # the bytes bound applies at these shapes
    assert cost["bytes"] / 819e9 > cost["ops"] / 197e12

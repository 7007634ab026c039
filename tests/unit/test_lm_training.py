"""The language-model task on the normal path: the packed source
(data/tokens.py), ``run_training`` with ``LMTask`` (logs, spans,
``compiled_step``, checkpoints and resume as for detection), and
``train.py lm-synthetic``."""

import itertools
import json
import os
import re
import sys

import jax
import numpy as np
import pytest

from batchai_retinanet_horovod_coco_tpu.data.tokens import PackedTokensConfig, packed_token_batches
from batchai_retinanet_horovod_coco_tpu.models import granite_hybrid
from batchai_retinanet_horovod_coco_tpu.obs import trace
from batchai_retinanet_horovod_coco_tpu.train import create_train_state, loop
from batchai_retinanet_horovod_coco_tpu.train.optim import OptimizerConfig, make_optimizer
from batchai_retinanet_horovod_coco_tpu.train.step import scope_table
from batchai_retinanet_horovod_coco_tpu.train.task import LMTask

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, _REPO_ROOT)

SOURCE = PackedTokensConfig(vocab_size=128, seq_len=64, batch_size=2, doc_len_median=16, doc_len_min=4, seed=7)


def test_the_packed_source_is_seeded_packs_without_padding_and_cuts_at_the_boundary():
    a = list(itertools.islice(packed_token_batches(SOURCE), 6))
    b = list(itertools.islice(packed_token_batches(SOURCE), 6))
    other = next(packed_token_batches(PackedTokensConfig(**{**SOURCE.__dict__, "seed": 2**31 + 11})))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.tokens, y.tokens)
        np.testing.assert_array_equal(x.segment_ids, y.segment_ids)
    assert not np.array_equal(a[0].tokens, other.tokens)
    lengths = []
    for i, batch in enumerate(a):
        assert batch.tokens.shape == batch.segment_ids.shape == (2, 64)
        assert batch.tokens.dtype == batch.segment_ids.dtype == np.int32
        assert 0 <= batch.tokens.min() and batch.tokens.max() < 128
        np.testing.assert_array_equal(batch.sequence_ids, [2 * i, 2 * i + 1])
        for row in batch.segment_ids:
            steps = np.diff(row)
            assert row[0] == 0 and set(steps) <= {0, 1}  # documents numbered from 0, contiguous: no padding
            lengths += list(np.diff(np.flatnonzero(np.concatenate([[1], steps, [1]]))))
    # only a document cut at a sequence's end (or continued after one) is shorter than the minimum
    assert 4 <= np.median(lengths) <= 32 and max(lengths) <= 64


def test_documents_are_clipped_to_the_sequence_and_heavy_tailed():
    cfg = PackedTokensConfig(vocab_size=12544, seq_len=8192, doc_len_median=512, doc_len_min=16, seed=3)
    rows = [b.segment_ids[0] for b in itertools.islice(packed_token_batches(cfg), 40)]
    lengths = np.concatenate([np.diff(np.flatnonzero(np.concatenate([[1], np.diff(r), [1]]))) for r in rows])
    assert 300 < np.median(lengths) < 800 and lengths.max() > 4096 and lengths.min() >= 1
    assert 4 < np.mean([r.max() + 1 for r in rows]) < 14  # documents per sequence


class _Sink:
    def __init__(self):
        self.rows = []

    def log(self, step, scalars, prefix=None):
        self.rows.append((step, dict(scalars)))


def _state(model):
    tx, _ = make_optimizer(OptimizerConfig(optimizer="adamw", base_lr=1e-2, schedule="constant", warmup_steps=0,
                                           weight_decay=0.1, adam_b2=0.95, clip_global_norm=1.0))
    return create_train_state(model, tx, (1, 8), jax.random.key(0), example_dtype=LMTask.example_dtype)


def test_run_training_with_the_lm_task_logs_checkpoints_resumes_and_spans(tmp_path):
    model, task = granite_hybrid.GraniteHybrid(granite_hybrid.TINY), LMTask()
    trace.reset()
    trace.configure(str(tmp_path / "obs"), process_label="t")
    try:
        sink = _Sink()
        config = loop.LoopConfig(total_steps=4, log_every=2, checkpoint_every=2, checkpoint_dir=str(tmp_path / "ckpt"),
                                 numerics=True)
        state = loop.run_training(model, _state(model), packed_token_batches(SOURCE), None, config,
                                  task=task, logger=sink)
        names = {e["name"] for e in trace.snapshot_events()}
    finally:
        trace.reset()
    assert int(state.step) == 4 and [s for s, _ in sink.rows] == [2, 4]
    scalars = sink.rows[-1][1]
    assert {"loss", "grad_norm", "param_norm", "tokens_counted", "images_per_sec", "data_wait_ms",
            "gnorm/embed", "gnorm/mamba", "gnorm/attention", "gnorm/mlp", "update_ratio"} <= set(scalars)
    assert 0 < scalars["tokens_counted"] <= 2 * 63 and np.isfinite(scalars["loss"])
    # the loop's spans, the prefetch thread's and the packed source's own
    assert {"data_wait", "step", "metrics_fetch", "compile_train_step", "device-prefetch", "pack_assemble"} <= names
    # the step the loop ran, by the task's bucket (sequences, tokens)
    compiled = loop.compiled_step((2, 64))
    assert {"mamba", "attention", "mlp", "lm_head", "optimizer"} <= {s for s, _, _ in scope_table(compiled).values()}
    with pytest.raises(LookupError):
        loop.compiled_step((64, 64))
    # resume from the checkpoint of step 4 and go on to 6
    resumed = loop.run_training(model, _state(model), packed_token_batches(SOURCE), None,
                                loop.LoopConfig(total_steps=6, log_every=0, checkpoint_every=2,
                                                checkpoint_dir=str(tmp_path / "ckpt")), task=task)
    assert int(resumed.step) == 6
    assert not np.array_equal(np.asarray(resumed.params["mlp"]["layer_0"]["down"]),
                              np.asarray(_state(model).params["mlp"]["layer_0"]["down"]))


def test_the_loss_on_one_batch_falls_under_training():
    model = granite_hybrid.GraniteHybrid(granite_hybrid.TINY)
    batch = next(packed_token_batches(SOURCE))
    sink = _Sink()
    loop.run_training(model, _state(model), itertools.repeat(batch), None,
                      loop.LoopConfig(total_steps=30, log_every=10), task=LMTask(), logger=sink)
    losses = [s["loss"] for _, s in sink.rows]
    assert losses[-1] < losses[0] - 0.05, losses


def test_the_lm_task_on_a_mesh_is_refused_by_the_loop():
    from batchai_retinanet_horovod_coco_tpu.parallel import make_mesh

    model = granite_hybrid.GraniteHybrid(granite_hybrid.TINY)
    with pytest.raises(ValueError, match="trains on one device"):
        loop.run_training(model, _state(model), packed_token_batches(SOURCE), None,
                          loop.LoopConfig(total_steps=1, log_every=0), mesh=make_mesh(2), task=LMTask())


def test_train_py_lm_synthetic_trains_the_tiny_preset_and_resumes(tmp_path, capsys):
    from train import main

    common = ["lm-synthetic", "--platform", "cpu", "--log-every", "2", "--snapshot-path", str(tmp_path / "ckpt"),
              "--checkpoint-every", "2"]
    assert main(common + ["--steps", "4", "--log-dir", str(tmp_path / "logs")]) == {"final_step": 4.0}
    with open(tmp_path / "logs" / "metrics.jsonl") as f:
        rows = [json.loads(line) for line in f]
    assert any("loss" in json.dumps(r) for r in rows)
    assert main(common + ["--steps", "6"]) == {"final_step": 6.0}
    out = capsys.readouterr().out
    assert "10 layers (9 mamba), d=64" in out and "resumed from step 4" in out
    with pytest.raises(SystemExit, match="one chip"):
        main(["lm-synthetic", "--num-devices", "4"])


def test_train_py_lm_synthetic_trains_the_tiny_moe_preset_and_logs_its_counters(tmp_path, capsys):
    from train import main

    args = ["lm-synthetic", "--platform", "cpu", "--model", "tiny-moe", "--log-every", "2", "--steps", "4",
            "--log-dir", str(tmp_path / "logs")]
    assert main(args) == {"final_step": 4.0}
    out = capsys.readouterr().out
    assert "deepseek v2, 3 layers (1 dense), 4 of 16 experts held, 3 a token, d=64" in out
    with open(tmp_path / "logs" / "metrics.jsonl") as f:
        text = f.read()
    for name in ("moe/rows_held", "moe/rows_max_expert", "moe/rows_min_expert", "moe/aux_loss", "tokens_counted"):
        assert name in text, name


def test_train_py_lm_synthetic_trains_the_tiny_nemotron_preset_and_logs_its_counters(tmp_path, capsys):
    from train import main

    args = ["lm-synthetic", "--platform", "cpu", "--model", "tiny-nemotron", "--log-every", "2", "--steps", "4",
            "--log-dir", str(tmp_path / "logs")]
    assert main(args) == {"final_step": 4.0}
    out = capsys.readouterr().out
    assert "nemotron-h, 5 layers (2 mamba, 2 moe, 1 attention), 2 of 8 experts held, 3 a token, d=64" in out
    with open(tmp_path / "logs" / "metrics.jsonl") as f:
        text = f.read()
    for name in ("moe/rows_held", "moe/rows_max_expert", "moe/rows_min_expert", "tokens_counted"):
        assert name in text, name
    assert "moe/aux_loss" not in text


def test_a_few_steps_of_run_training_lower_the_tiny_nemotrons_loss_and_log_its_scalars(tmp_path):
    from batchai_retinanet_horovod_coco_tpu.models import nemotron_h

    model = nemotron_h.NemotronH(nemotron_h.TINY)
    batch = next(packed_token_batches(SOURCE))
    trace.reset()
    trace.configure(str(tmp_path / "obs"), process_label="t")
    try:
        sink = _Sink()
        state = loop.run_training(model, _state(model), itertools.repeat(batch), None,
                                  loop.LoopConfig(total_steps=30, log_every=10, numerics=True), task=LMTask(), logger=sink)
        events = trace.snapshot_events()
    finally:
        trace.reset()
    assert int(state.step) == 30
    losses = [s["loss"] for _, s in sink.rows]
    assert losses[-1] < losses[0] - 0.05, losses
    scalars = sink.rows[-1][1]
    assert {"loss", "tokens_counted", "moe/rows_held", "moe/rows_max_expert", "moe/rows_min_expert", "grad_norm",
            *(f"gnorm/{g}" for g in ("embed", "mamba", "attention", "router", "experts", "shared", "norms", "head"))
            } <= set(scalars)
    meta = [e for e in events if e["name"] == "run_meta"]
    assert meta and {"attention_lowering": "xla", "ssd_lowering": "xla", "ssd_groups": 2, "moe_lowering": "xla",
                     "moe_rows_lowering": "xla", "experts_held": 2, "experts_total": 8}.items() <= meta[-1]["args"].items()
    (built,) = [e for e in events if e["name"] == "compile_train_step"]
    assert built["args"]["bucket"] == "2x64"
    compiled = loop.compiled_step((2, 64))
    assert {"mamba", "attention", "moe", "lm_head", "optimizer"} <= {s for s, _, _ in scope_table(compiled).values()}


@pytest.mark.parametrize("model_type,says", [("deepseek_v2", "deepseek v2, 3 layers"),
                                             ("granitemoehybrid", "granite hybrid, 10 layers (9 mamba)"),
                                             ("nemotron_h", "nemotron-h, 5 layers (2 mamba, 2 moe, 1 attention)")])
def test_train_py_lm_synthetic_picks_the_model_by_model_type(tmp_path, capsys, model_type, says):
    """A ``--model <config.json>`` at toy widths: the benchmark's file of
    that model_type with the CPU tests' sizes."""
    import sys

    from train import main

    sys.path.insert(0, os.path.join(_REPO_ROOT, "tests", "benchmark"))
    from test_benchmark_lm_cell import TINY_MODEL as granite_tiny
    from test_benchmark_moe_cell import TINY_MODEL as moe_tiny
    from test_benchmark_nemotron_cell import TINY_MODEL as nemotron_tiny

    name, tiny = {"deepseek_v2": ("deepseek-v2-lite-ep8", moe_tiny),
                  "granitemoehybrid": ("granite-4.0-h-micro-p1", granite_tiny),
                  "nemotron_h": ("nemotron-3-nano-30b-ep16", nemotron_tiny)}[model_type]
    with open(os.path.join(_REPO_ROOT, "benchmark", "configs", name + ".json")) as f:
        config = dict(json.load(f), **tiny)
    assert config["model_type"] == model_type
    path = tmp_path / "config.json"
    path.write_text(json.dumps(config))
    assert main(["lm-synthetic", "--platform", "cpu", "--model", str(path), "--steps", "2", "--log-every", "1"]) == {
        "final_step": 2.0}
    assert says in capsys.readouterr().out
    path.write_text(json.dumps(dict(config, model_type="llama")))
    with pytest.raises(ValueError, match="model_type 'llama'"):
        main(["lm-synthetic", "--platform", "cpu", "--model", str(path), "--steps", "1"])


def test_run_training_records_the_moe_models_lowerings_in_run_meta(tmp_path):
    from batchai_retinanet_horovod_coco_tpu.models import deepseek_v2

    model = deepseek_v2.DeepseekV2(deepseek_v2.TINY)
    trace.reset()
    trace.configure(str(tmp_path / "obs"), process_label="t")
    try:
        sink = _Sink()
        state = loop.run_training(model, _state(model), packed_token_batches(SOURCE), None,
                                  loop.LoopConfig(total_steps=2, log_every=1, numerics=True), task=LMTask(), logger=sink)
        meta = [e for e in trace.snapshot_events() if e["name"] == "run_meta"]
    finally:
        trace.reset()
    assert int(state.step) == 2
    assert meta and {"attention_lowering": "xla", "moe_lowering": "xla", "experts_held": 4,
                     "experts_total": 16}.items() <= meta[-1]["args"].items()
    scalars = sink.rows[-1][1]
    assert {"moe/rows_held", "moe/aux_loss", "gnorm/router", "gnorm/experts", "gnorm/shared", "gnorm/head"} <= set(scalars)
    compiled = loop.compiled_step((2, 64))
    assert {"mla", "moe", "dense_mlp", "lm_head", "optimizer"} <= {s for s, _, _ in scope_table(compiled).values()}


def test_train_py_help_names_the_lm_subcommand(capsys):
    from train import build_parser

    with pytest.raises(SystemExit):
        build_parser().parse_args(["--help"])
    text = capsys.readouterr().out
    assert "lm-synthetic" in text and "single-chip" in text and "tiny" in text
    with pytest.raises(SystemExit):
        build_parser().parse_args(["lm-synthetic", "--help"])
    from batchai_retinanet_horovod_coco_tpu.models.language import BY_TYPE, PRESETS

    text = re.sub(r"-\n\s*", "-", capsys.readouterr().out)  # argparse breaks a line after a hyphen
    text = " ".join(text.split())
    # built from models/language.py: a sixth model edits no help text
    assert "--model" in text and "benchmark/configs/" in text
    assert all(name in text for name in (*PRESETS, *BY_TYPE))

"""The language-model task on the normal path: the packed source
(data/tokens.py), ``run_training`` with ``LMTask`` (logs, spans,
``compiled_step``, checkpoints and resume as for detection), and
``train.py lm-synthetic``."""

import itertools
import json
import os
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


def test_train_py_help_names_the_lm_subcommand(capsys):
    from train import build_parser

    with pytest.raises(SystemExit):
        build_parser().parse_args(["--help"])
    text = capsys.readouterr().out
    assert "lm-synthetic" in text and "single-chip" in text and "tiny" in text
    with pytest.raises(SystemExit):
        build_parser().parse_args(["lm-synthetic", "--help"])
    assert "--model" in capsys.readouterr().out

"""The train kind end to end on the CPU at ``resnet_test`` / 64x96, on one
device and on four virtual ones, from a throw-away checkout whose cells,
configuration, traffic mixes and per-layer metric are ADDED files; and
``run.py``'s refusal of a CPU and of a checkout without the program."""

import os
import shutil
import subprocess
import sys

import pytest

import benchmark_tiny_tree as tiny


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.build(str(tmp_path_factory.mktemp("bench_train")))


@pytest.mark.parametrize("workload,devices", [("tiny-train", 1), ("tiny-dp4", 4)])
def test_train_cell_end_to_end(tree, workload, devices):
    rc, line, out = tiny.run_cell(tree, workload, trace=0, devices=devices)
    assert rc == 0 and line is not None, out[-3000:]
    assert set(line) == tiny.KEYS, line
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    assert set(line["metrics"]) == {"train_img_per_s_chip", "setup_s"}
    assert line["metrics"]["train_img_per_s_chip"]["unit"] == "img/s/chip"
    assert line["metrics"]["train_img_per_s_chip"]["value"] > 0
    assert line["device"]["count"] == devices and line["device"]["platform"] == "cpu"
    facts = tiny.facts(out)
    # Step 1 against the float32 reference: the tiny model computes in
    # float32 itself, so forward, backward and update agree closely.
    first = facts["first_step"]
    assert first["loss"]["rel"] < 1e-4 and first["grad_norm"]["rel"] < 1e-4, first
    assert first["update"]["rel_diff"] < 1e-2, first  # float32 storage of the parameters
    assert facts.get("replica_checksums_equal", True) is True
    # Where the time went: the parts of set-up sum to it, and every log
    # window of the measured call is there with its time.
    assert sum(facts["setup_parts_s"].values()) == pytest.approx(facts["setup_s"], abs=1e-6)
    windows, tail = facts["log_windows"], facts["after_last_log"]
    assert windows[-1][0] + tail["steps"] == 5 + line["attempted"]
    assert windows[-1][1] + tail["ms"] / 1e3 == pytest.approx(facts["window_s"], abs=1e-6)
    assert all(ms > 0 for _step, _t, ms in windows)


def test_a_wrong_update_is_not_correct(tree):
    """The same cell with twice the rate declared to the reference as the
    optimizer is given: what the backward and the optimizer did is seen."""
    rc, line, out = tiny.run_cell(tree, "tiny-wrong-rate", trace=0)
    assert rc == 0 and line is not None, out[-3000:]
    assert line["correct"] is False
    problems = tiny.said(out, "benchmark: NOT CORRECT:")
    assert len(problems) == 1 and problems[0].startswith("first step's update"), problems
    assert tiny.facts(out)["first_step"]["update"]["rel_diff"] == pytest.approx(0.5, abs=0.01)


def test_run_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(tiny.REPO, "benchmark", "run.py"), "--workload", "r50-train-b8",
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        env=env, cwd=tiny.REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert not any(l.startswith("{") for l in proc.stdout.splitlines())  # no result line


def test_run_fails_without_the_program(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    ``paths``: no result, exit code not 0."""
    shutil.copytree(os.path.join(tiny.REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(tiny.REPO, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"), "--workload", "r50-train-b8",
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        env=dict(env, JAX_PLATFORMS="cpu"), cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert not any(l.startswith("{") for l in proc.stdout.splitlines())

"""chip_smoke.py, the compile-cache helper and the Pallas lowering
tripwire — everything about the chip bring-up that a CPU can check.

(a) without a TPU the smoke exits non-zero in seconds, says what it found
    and runs no phase;
(b) its phase functions pass at resnet_test/64x64 with the kernels in
    interpret mode (and the one check that needs the TPU backend — the
    fused matching call in the lowered step — FAILS here, which is what
    shows it is not vacuous);
(c) the cache helper leaves a cache placed from outside alone and
    otherwise uses exactly ``<checkout>/.jax_cache``;
(d) every Pallas kernel that is selectable lowers for TPU at its flagship
    shape — the check that catches an unlowerable kernel from a sandbox
    with no chip, in about a second each.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import chip_smoke

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

TINY = chip_smoke.SmokeSize(
    backbone="resnet_test",
    norm="frozen_bn",
    f32=True,
    min_side=64,
    max_side=64,
    bucket=(64, 64),
    per_chip_batch=2,
    max_gt=100,
    steps=3,
    platform="cpu",
    serve_images=3,
)


def _run(code_or_argv, env_extra, timeout=120):
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")
    }
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env.update(env_extra)
    argv = (
        [sys.executable, "-c", code_or_argv]
        if isinstance(code_or_argv, str)
        else [sys.executable, *code_or_argv]
    )
    return subprocess.run(
        argv, env=env, cwd=REPO, capture_output=True, text=True,
        timeout=timeout,
    )


class TestNoTpu:
    def test_exits_nonzero_names_the_platform_and_runs_no_phase(self):
        for argv in (["chip_smoke.py"], ["chip_smoke.py", "--chips", "4"]):
            proc = _run(argv, {})
            assert proc.returncode not in (0, None)
            assert "needs a TPU" in proc.stderr
            assert "platform='cpu'" in proc.stderr
            assert "phase" not in proc.stdout
            assert '"ok"' not in proc.stdout

    def test_no_switch_reaches_the_phases_on_cpu(self):
        """main() takes one option, and it is not a platform."""
        with pytest.raises(SystemExit):
            chip_smoke.main(["--platform", "cpu"])
        assert chip_smoke.FLAGSHIP.platform == "tpu"
        assert not chip_smoke.FLAGSHIP.interpret


class TestPhasesOnCpu:
    def test_kernels_agree_in_interpret_mode(self):
        done = chip_smoke.phase_kernels(TINY)
        for family in ("matching G=8", "matching G=100", "nms", "focal"):
            assert any(tag.startswith(family) for tag in done), done

    def test_train_export_serve(self, tmp_path):
        import dataclasses

        work = str(tmp_path)
        trained = chip_smoke.phase_train(TINY, work)
        assert sorted(trained["losses"]) == [1, 2, 3]
        assert "AP" in trained["eval"]
        # The phase checks what the entry point logged, not that it
        # returned: the same logs fail a run that wanted one more step.
        with pytest.raises(chip_smoke.SmokeFailure, match="steps logged"):
            chip_smoke.check_train_log(
                dataclasses.replace(TINY, steps=TINY.steps + 1), "train",
                os.path.join(work, "logs_train"), trained["snapshot"],
            )
        export_dir = chip_smoke.phase_export(TINY, work, trained["snapshot"])
        stats = chip_smoke.phase_serve(TINY, work, export_dir)
        assert stats["completed"] == TINY.serve_images
        assert stats["failed"] == 0

    def test_step_program_check_has_teeth(self):
        """On the CPU backend the step takes the XLA matching path, and the
        check that main() runs on the chip says so."""
        with pytest.raises(chip_smoke.SmokeFailure, match="tpu_custom_call"):
            chip_smoke.phase_step_program(TINY)

    def test_multichip_placement_on_the_virtual_mesh(self, tmp_path):
        chip_smoke.phase_multichip(TINY, str(tmp_path), 4)

    def test_placement_check_catches_everything_on_one_device(self):
        import numpy as np

        from batchai_retinanet_horovod_coco_tpu.train.state import TrainState

        state = TrainState(
            step=jnp.zeros((), jnp.int32),
            params={"w": jax.device_put(np.ones((4, 4), np.float32))},
            batch_stats={}, opt_state=(), tx=None,
        )
        with pytest.raises(chip_smoke.SmokeFailure, match="batch shards"):
            chip_smoke.check_placement(
                {"batch_devices": [0], "state": state}, 4
            )
        with pytest.raises(chip_smoke.SmokeFailure, match="1 device"):
            chip_smoke.check_placement(
                {"batch_devices": [0, 1, 2, 3], "state": state}, 4
            )


_CACHE_PROBE = """
import json, os
import jax
from batchai_retinanet_horovod_coco_tpu.utils import backend
before = jax.config.jax_compilation_cache_dir
first = backend.enable_compile_cache()
second = backend.enable_compile_cache("/somewhere/else")
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
jax.jit(lambda x: x * 2 + 1)(jax.numpy.arange(7.0)).block_until_ready()
print(json.dumps({
    "before": before, "first": first, "second": second,
    "config": jax.config.jax_compilation_cache_dir,
    "default": backend.DEFAULT_CACHE_DIR,
}))
"""


class TestCompileCachePlacement:
    def _probe(self, env_extra):
        import json

        proc = _run(_CACHE_PROBE, env_extra)
        assert proc.returncode == 0, proc.stderr[-2000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    def test_placed_from_outside_is_left_alone(self, tmp_path):
        outside = str(tmp_path / "outside")
        default = os.path.join(REPO, ".jax_cache")
        had = set(os.listdir(default)) if os.path.isdir(default) else None
        got = self._probe({"JAX_COMPILATION_CACHE_DIR": outside})
        # JAX read the variable itself; no code path set another.
        assert got["before"] == got["first"] == got["second"] == outside
        assert got["config"] == outside
        assert os.listdir(outside), "the compiled program was not cached"
        now = set(os.listdir(default)) if os.path.isdir(default) else None
        assert now == had, "something was written under <checkout>/.jax_cache"

    def test_default_is_the_fixed_checkout_path(self):
        got = self._probe({})
        fixed = os.path.join(REPO, ".jax_cache")
        assert got["before"] is None
        assert got["default"] == fixed
        # First placement wins for the life of the process.
        assert got["first"] == got["second"] == got["config"] == fixed


def _spec(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _lowers_for_tpu(fn, *specs) -> str:
    text = jax.jit(fn).trace(*specs).lower(
        lowering_platforms=("tpu",)
    ).as_text()
    assert "tpu_custom_call" in text
    return text


class TestPallasKernelsLowerForTpu:
    """JAX-level Pallas→Mosaic lowering at the flagship shapes (B=8,
    A=201600 anchors of the 800x1344 bucket).  What Mosaic itself says is
    chip_smoke.py's ``kernels`` phase."""

    B, A, K = 8, 201600, 80

    @pytest.mark.parametrize("num_gt", [8, 100])
    def test_matching(self, num_gt):
        from batchai_retinanet_horovod_coco_tpu.ops.pallas import matching

        b, a = self.B, self.A
        _lowers_for_tpu(
            lambda an, bx, lb, mk: matching.assign_fused(
                an, bx, lb, mk, planar=True
            ),
            _spec((a, 4), jnp.float32), _spec((b, num_gt, 4), jnp.float32),
            _spec((b, num_gt), jnp.int32), _spec((b, num_gt), jnp.bool_),
        )

    def test_nms_every_block_the_smoke_checks(self):
        from batchai_retinanet_horovod_coco_tpu.ops.pallas import nms

        b, k = self.B, 1000
        for block_k in chip_smoke.NMS_BLOCKS:
            _lowers_for_tpu(
                lambda bx, sc, cl, bk=block_k: nms.nms_keep_mask(
                    bx, sc, cl, 0.5, block_k=bk
                ),
                _spec((b, k, 4), jnp.float32), _spec((b, k), jnp.float32),
                _spec((b, k), jnp.int32),
            )

    def test_focal_forward_and_backward(self):
        from batchai_retinanet_horovod_coco_tpu.ops.pallas import focal

        b, a, k = self.B, self.A, self.K
        text = _lowers_for_tpu(
            lambda x, lb, st: jax.value_and_grad(
                lambda z: focal.focal_loss_per_image_sums(z, lb, st).sum()
            )(x),
            _spec((b, a, k), jnp.float32), _spec((b, a), jnp.int32),
            _spec((b, a), jnp.int32),
        )
        assert text.count("tpu_custom_call") >= 2  # forward and backward

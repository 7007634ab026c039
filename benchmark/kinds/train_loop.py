"""Traffic kind ``train_loop``: seeded batches through ``train/loop.py::run_training``.

One warm call (compiles or loads the step, runs a few steps, gives the step
rate) and one measured call.  The window opens when the measured call's
first log window has been fetched - the step function it rebuilt is loaded
and the device is in step - and closes when the last step's state is ready.
Two calls, because ``run_training`` takes its number of steps when it is
called and hands the state back only when it returns: a call that is to last
``--seconds`` needs the rate first (PERF.md, Open questions).

``correct`` holds step 1 to a plain float32 reference kept here: the loss,
the gradient's norm and the optimizer's update, so the forward, the backward
and the optimizer are all seen.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.harness import flops, model as model_lib, scenes
from benchmark.harness.runctx import Run, now


class _Sink:
    """The loop's logger: keeps (step, time, scalars) and calls a hook."""

    def __init__(self, on_log=None):
        self.rows, self.on_log = [], on_log

    def log(self, step, scalars, prefix=None):
        if prefix is not None:  # the loop logs its eval hook's (empty) result too
            return
        self.rows.append((int(step), now(), {k: float(v) for k, v in scalars.items()}))
        if self.on_log is not None:
            self.on_log(int(step))


class Driver:
    # The program's obs/trace.py spans stay off: with them on, run_training
    # lowers the step once more for its FLOP count and the step then misses
    # the compile cache (a second 90 s compile, my chip run, PR 22).  The
    # loop's own log carries data_wait.

    def __init__(self, run: Run):
        self.run, self.t = run, run.traffic

    # ---- set-up ----------------------------------------------------------

    def setup(self) -> None:
        from batchai_retinanet_horovod_coco_tpu.data.pipeline import Batch
        from batchai_retinanet_horovod_coco_tpu.parallel.mesh import make_mesh
        from batchai_retinanet_horovod_coco_tpu.train.optim import OptimizerConfig, make_optimizer

        run, t = self.run, self.t
        chips = run.chips
        t0 = now()
        self.hw = tuple(t["bucket_hw"])
        self.global_batch = t["per_chip_batch"] * chips
        self.model = model_lib.build_model(run.config)
        self.tx, _ = make_optimizer(OptimizerConfig(
            schedule="constant", warmup_steps=0, base_lr=t["base_lr"],
            global_batch_size=self.global_batch, world_size=chips,
            momentum=t["momentum"], weight_decay=t["weight_decay"],
            clip_global_norm=t["clip_global_norm"],
        ))
        self.mesh = make_mesh(chips) if chips > 1 else None
        self.make_state = model_lib.state_maker(self.model, self.tx)
        self.state = self.make_state(run.seed)
        t1 = now()
        n = self.global_batch
        self.pool = [
            Batch(images=b["images"], gt_boxes=b["gt_boxes"], gt_labels=b["gt_labels"],
                  gt_mask=b["gt_mask"], image_ids=np.arange(i * n, (i + 1) * n, dtype=np.int64),
                  scales=np.ones(n, np.float32), valid=np.ones(n, bool))
            for i, b in enumerate(scenes.labelled_batches(
                run.seed, t["pool_batches"], n, self.hw, t["max_gt"],
                count_range=tuple(t["boxes_per_image"]), size_range=tuple(t["box_side_px"]),
                num_classes=run.config["model"]["num_classes"]))
        ]
        self.served = 0  # batches handed to the loop, over both calls
        self.params_after_first = None  # host copy, taken by the warm call's eval hook
        self.setup_detail = {"model_optimizer_state_s": t1 - t0, "host_batches_s": now() - t1}

    def _batches(self):
        annotate = self.run.tracer.annotate
        while True:
            with annotate("bench.next_batch"):
                batch = self.pool[self.served % len(self.pool)]
                self.served += 1
            yield batch

    def _train(self, total_steps: int, log_every: int, sink: _Sink, **hooks):
        from batchai_retinanet_horovod_coco_tpu.train.loop import LoopConfig, run_training

        self.served = int(self.state.step)  # step k always sees pool[(k-1) % len]
        self.state = run_training(
            self.model, self.state, self._batches(), self.run.config["model"]["num_classes"],
            LoopConfig(total_steps=total_steps, log_every=log_every, checkpoint_every=0,
                       resume=False, device_prefetch=self.t["device_prefetch"],
                       eval_every=1 if hooks else 0),
            mesh=self.mesh, logger=sink, **hooks,
        )

    def _keep_first_update(self, state) -> dict:
        """The warm call's eval hook (after every warm step): a host copy of
        the parameters as step 1 left them, for ``check``."""
        import jax

        if self.params_after_first is None and int(state.step) == 1:
            self.params_after_first = jax.device_get(state.params)
        return {}

    def warm(self) -> None:
        w = self.t["warm_steps"]
        self.warm_sink = _Sink()
        t0 = now()
        self._train(w, 1, self.warm_sink, eval_fn=self._keep_first_update)
        rows = self.warm_sink.rows
        # The first steps hold the compile (or the load); the rest the rate.
        k = max(1, len(rows) // 2)
        self.step_s = (rows[-1][1] - rows[-k - 1][1]) / k if len(rows) > k else 0.1
        self.setup_detail.update(warm_until_first_step_s=rows[0][1] - t0,
                                 warm_other_steps_s=rows[-1][1] - rows[0][1])

    # ---- the window ------------------------------------------------------

    def measure(self) -> dict:
        import jax

        t, tracer = self.t, self.run.tracer
        log_every = t["log_every"]
        start = int(self.state.step)
        open_step = (start // log_every + 1) * log_every
        steps = max(log_every, int(round(self.run.seconds / self.step_s)))
        total = open_step + steps
        # The traced run starts the profiler at its last periodic log and runs
        # trace_steps more: few, because stopping the profiler and reading the
        # trace cost seconds per traced step and chip (a traced run of 18-24
        # steps took 155 s on one chip and 371 s on four, my chip runs, PR 22),
        # and with no scalar fetch among them.  It stops after the window closed.
        trace_from = None
        if tracer.enabled:
            trace_from = (total // log_every) * log_every
            total = trace_from + t["trace_steps"]
            steps = total - open_step

        def on_log(step: int) -> None:
            if step == open_step:
                self.run.open_window()
            if step == trace_from:
                tracer.start()

        self.sink = _Sink(on_log)
        self._train(total, log_every, self.sink)
        jax.block_until_ready(self.state.params)
        t_close = now()
        tracer.stop()
        t_open = self.run.t_open
        window = t_close - t_open
        images = steps * self.global_batch
        rate = e2e_rate = images / window / self.run.chips
        model_flops = flops.train_flops_per_image(self.run.config["flops_model"], self.hw)
        in_window = [r for r in self.sink.rows if r[0] > open_step]
        # The traced run's rate comes from the log windows before the
        # profiler starts: tracing slows the host.
        clean = [r for r in self.sink.rows if open_step <= r[0] <= trace_from] if tracer.enabled else []
        if len(clean) >= 2:
            rate = (clean[-1][0] - clean[0][0]) * self.global_batch / (clean[-1][1] - clean[0][1]) / self.run.chips
        # Every log window of the measured call, so that a run the host
        # stalled shows where: [last step, seconds since the window opened,
        # ms per step]; then what lay between the last log and the close.
        edges = [(r[0], r[1]) for r in self.sink.rows if r[0] >= open_step]
        log_windows = [[s1, t1 - t_open, (t1 - t0) / (s1 - s0) * 1e3]
                       for (s0, t0), (s1, t1) in zip(edges, edges[1:])]
        self.facts = {
            "t_window_open": t_open, "window_s": window, "steps": steps,
            "steps_per_s": steps / window,
            "images_per_s_chip": e2e_rate,
            "model_flops_per_s_chip": rate * model_flops,
            "log_windows": log_windows,
            "after_last_log": {"steps": total - edges[-1][0], "ms": (t_close - edges[-1][1]) * 1e3},
            "setup_detail": self.setup_detail,
            "data_wait_ms": [r[2]["data_wait_ms"] for r in in_window if "data_wait_ms" in r[2]],
            "module_pattern": t["step_program_pattern"],
            "trace_steady_runs": t["trace_steady_runs"],
            # The trace names the Mosaic custom call after the jitted function
            # that holds it ("jvp_jit_assign_fused__.1"): the pattern is data.
            "assign_pattern": t["assign_kernel_pattern"],
            "assign_cost": flops.assign_fused_cost(
                t["per_chip_batch"],
                flops.forward_macs(self.run.config["flops_model"], self.hw)["anchors"],
                t["max_gt"]),
        }
        return {
            "attempted": steps, "failed": 0,
            "end_to_end": {"train_img_per_s_chip": e2e_rate},
        }

    # ---- correct ---------------------------------------------------------

    def check(self) -> list[str]:
        t, problems = self.t, []
        rows = self.warm_sink.rows + self.sink.rows
        losses = [(s, r["loss"]) for s, _, r in rows]
        if not all(math.isfinite(l) for _, l in losses):
            problems.append("a logged loss is not finite")
        # The pool repeats: the last step's loss against the loss the warm
        # call logged the first time it met the same batch.
        last_step, last = losses[-1]
        same = next(l for s, l in losses if (s - 1) % len(self.pool) == (last_step - 1) % len(self.pool))
        # From seeded weights under frozen batch-norm the loss moves by under
        # 0.1% in a window (5.4468 -> 5.4475 over 179 steps, my chip run, PR
        # 22), so "it fell" cannot be asked; that it did not rise can.  What
        # the step learns is held to the reference below.
        if not last <= same * (1.0 + t["loss_rise_tol"]):
            problems.append(f"loss on one batch rose: first {same:.4f}, step {last_step} {last:.4f}")

        # Step 1 against float32: bf16 activations against float32 at the
        # highest matmul precision.  The loss sees the forward, the
        # gradient's norm the backward, the update the optimizer; int8
        # heads, a dropped term or a skipped update fail.
        first = rows[0][2]
        ref = self._reference_first_step()
        update = _update_report(ref["params"], self.params_after_first, ref["grads"], ref["grad_norm"],
                                lr=t["lr_per_image"] * self.global_batch, weight_decay=t["weight_decay"],
                                clip=t["clip_global_norm"])
        report = {
            "loss": _against(first["loss"], ref["loss"]),
            "grad_norm": _against(first["grad_norm"], ref["grad_norm"]),
            "update": update,
        }
        self.facts["first_step"] = report
        for name, rel, tol in (("loss", report["loss"]["rel"], t["first_loss_rel_tol"]),
                               ("grad_norm", report["grad_norm"]["rel"], t["grad_norm_rel_tol"]),
                               ("update", update["rel_diff"], t["update_rel_tol"])):
            if not rel <= tol:
                problems.append(f"first step's {name} against the float32 reference: {report[name]}, tolerance {tol}")
        if self.mesh is not None:
            problems += self._replicas_differ()
        return problems

    def _reference_program(self):
        """``(params, state, images, gt_boxes, gt_labels, gt_mask) -> (loss,
        gradient)``: float32 activations, highest matmul precision, the jnp
        assignment and the flat loss path (not the step's Pallas call and NHWC
        path), ``jax.value_and_grad`` of that."""
        import jax
        import jax.numpy as jnp

        from batchai_retinanet_horovod_coco_tpu import losses as losses_lib
        from batchai_retinanet_horovod_coco_tpu.data.pipeline import normalize_images
        from batchai_retinanet_horovod_coco_tpu.ops import anchors as anchors_lib
        from batchai_retinanet_horovod_coco_tpu.ops import matching as matching_lib
        from batchai_retinanet_horovod_coco_tpu.train.state import model_variables

        cfg = dict(self.run.config, model=dict(self.run.config["model"], dtype="float32"))
        model32 = model_lib.build_model(cfg)
        anchors = anchors_lib.anchors_for_image_shape(self.hw, model32.config.anchor)

        def loss_of(params, state, images, gt_boxes, gt_labels, gt_mask):
            with jax.default_matmul_precision("highest"):
                variables = dict(model_variables(state), params=params)
                kw = {"mutable": ["batch_stats"]} if state.batch_stats else {}
                out = model32.apply(variables, normalize_images(images), train=True, **kw)
                out = out[0] if kw else out
                targets = matching_lib.anchor_targets_compact_batched(
                    jnp.asarray(anchors), gt_boxes, gt_labels, gt_mask,
                    matching_lib.MatchingConfig(fused_pallas=False))
                return losses_lib.total_loss_compact(
                    out["cls_logits"], out["box_deltas"], targets.matched_labels,
                    targets.box_targets, targets.state,
                    losses_lib.LossConfig(pallas_focal=False))["loss"]

        return jax.jit(jax.value_and_grad(loss_of))

    def _reference_first_step(self) -> dict:
        """Step 1 again on the same seed and batch by ``_reference_program``.
        The loss is a mean of per-image losses (each normalized by its own
        positives), so the batch's loss and gradient are the means over
        microbatches, which keeps float32 activations inside the chip's
        memory."""
        import jax
        import jax.numpy as jnp

        loss_and_grad = self._reference_program()
        add = jax.jit(lambda acc, g: jax.tree.map(jnp.add, acc, g), donate_argnums=0)
        state0 = self.make_state(self.run.seed)
        b, m = self.pool[0], self.t["reference_microbatch"]
        if self.global_batch % m:
            raise ValueError("reference_microbatch must divide the global batch")
        losses, grads = [], None
        for i in range(0, self.global_batch, m):
            loss, g = loss_and_grad(state0.params, state0, b.images[i:i + m], b.gt_boxes[i:i + m],
                                    b.gt_labels[i:i + m], b.gt_mask[i:i + m])
            losses.append(float(loss))
            grads = g if grads is None else add(grads, g)
        k = len(losses)
        grads = [np.asarray(g, np.float64) / k for g in jax.tree.leaves(jax.device_get(grads))]
        return {
            "loss": float(np.mean(losses)),
            "grads": grads,
            "grad_norm": math.sqrt(sum(float(np.sum(g * g)) for g in grads)),
            "params": jax.device_get(state0.params),
        }

    def _replicas_differ(self) -> list[str]:
        """Parameters bit-identical across devices after the window."""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def checksum(leaves):
            return jnp.stack([
                jnp.sum(jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32).astype(jnp.uint32))
                for x in leaves
            ])

        leaves = jax.tree.leaves(self.state.params)
        sums = []
        for d in range(self.run.chips):
            sums.append(np.asarray(checksum([l.addressable_shards[d].data for l in leaves])))
        bad = [i for i in range(1, len(sums)) if not np.array_equal(sums[0], sums[i])]
        self.facts["replica_checksums_equal"] = not bad
        return [f"parameters differ between device 0 and device(s) {bad}"] if bad else []


def _against(program: float, reference: float) -> dict:
    return {"program": program, "reference_f32": reference,
            "rel": abs(program - reference) / abs(reference)}


def _update_report(params0, params1, grads: list, grad_norm: float, *, lr: float,
                   weight_decay: float, clip: float) -> dict:
    """The program's first update (parameters after step 1 less parameters
    before) against the recipe the traffic file declares, written out plainly
    on the reference gradient: clip by global norm, add the decayed weights,
    SGD with momentum, whose first step is -lr x that."""
    import jax

    if params1 is None:
        return {"rel_diff": float("inf"), "why": "the loop never handed out the state after step 1"}
    scale = clip / max(grad_norm, clip)
    diff = ref = got = 0.0
    for p0, p1, g in zip(jax.tree.leaves(params0), jax.tree.leaves(params1), grads, strict=True):
        p0 = np.asarray(p0, np.float64)
        expected = -lr * (g * scale + weight_decay * p0)
        moved = np.asarray(p1, np.float64) - p0
        diff += float(np.sum((moved - expected) ** 2))
        ref += float(np.sum(expected ** 2))
        got += float(np.sum(moved ** 2))
    return {"norm_program": math.sqrt(got), "norm_reference": math.sqrt(ref),
            "rel_diff": math.sqrt(diff / ref) if ref else float("inf")}

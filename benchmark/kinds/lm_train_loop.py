"""Traffic kind ``lm_train_loop``: seeded packed token sequences through
``train/loop.py::run_training`` with the language-model task.

Built like ``train_loop.py``: one warm call (compiles or loads the step,
runs a few steps, gives the step rate) and one measured call; the window
opens when the measured call's first log window has been fetched and closes
when the last step's state is ready.  One training example (one packed
sequence) is what ``train_img_per_s_chip`` counts; ``facts`` carries tokens
per second beside it.

``correct``: every logged loss finite and not risen when the pool comes
round; nothing compiled in the window (``run.py``); and step 1 of the timed
path against the plain float32 reference kept under ``benchmark/reference/``:
the loss, the gradient's norm for each of embedding, mamba mixers, attention,
MLPs and norm scales (the step's own ``gnorm/*`` scalars, obs/numerics.py:
both calls run with ``LoopConfig(numerics=True)``, which ``train.py`` leaves
off; PERF.md section 5 has what that costs a step), and the update
(``first_update_report``).  The reference's gradients do not fit beside
Adam's slots, so the measured state is dropped first and the reference runs
layer by layer (``loss_and_grads_by_layer``).
"""

from __future__ import annotations

import itertools
import math

import numpy as np

from benchmark.harness import lm_flops
from benchmark.harness.runctx import Run, now
from benchmark.reference import granite_hybrid as reference

GROUPS = ("embed", "mamba", "attention", "mlp", "norms")
HELD_FLOOR = 0.5  # of the rate: a leaf whose predicted first step is smaller is not held to ``moved``


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


def build(config: dict, traffic: dict):
    """``(model, task, tx)`` of the program for a configuration file (the
    published ``config.json``'s keys at its top level) and a traffic file."""
    import jax.numpy as jnp

    from batchai_retinanet_horovod_coco_tpu.models.granite_hybrid import GraniteHybrid, GraniteHybridConfig
    from batchai_retinanet_horovod_coco_tpu.train.optim import OptimizerConfig, make_optimizer
    from batchai_retinanet_horovod_coco_tpu.train.task import LMTask

    model = GraniteHybrid(GraniteHybridConfig.from_hf(config, dtype=getattr(jnp, config["compute_dtype"])))
    tx, _ = make_optimizer(OptimizerConfig(
        optimizer="adamw", schedule="constant", warmup_steps=0, base_lr=traffic["lr"], world_size=1,
        adam_b2=traffic["adam_b2"], adam_eps=traffic["adam_eps"],
        weight_decay=traffic["weight_decay"], clip_global_norm=traffic["clip_global_norm"]))
    return model, LMTask(), tx


class Driver:
    def __init__(self, run: Run):
        self.run, self.t = run, run.traffic

    # ---- set-up ----------------------------------------------------------

    def setup(self) -> None:
        from batchai_retinanet_horovod_coco_tpu.data.tokens import PackedTokensConfig, packed_token_batches

        run, t = self.run, self.t
        if run.chips != 1:
            raise SystemExit("benchmark: the lm task trains on one chip")
        t0 = now()
        self.bytes_limit = (run.devices[0].memory_stats() or {}).get("bytes_limit")  # before anything else
        self.model, self.task, self.tx = build(run.config, t)
        self.state = self._initial_state()
        t1 = now()
        self.pool = list(itertools.islice(packed_token_batches(PackedTokensConfig(
            vocab_size=run.config["vocab_size"], seq_len=t["seq_len"], batch_size=t["per_chip_batch"],
            doc_len_median=t["doc_len_median"], doc_len_sigma=t["doc_len_sigma"],
            doc_len_min=t["doc_len_min"], seed=run.seed)), t["pool_batches"]))
        self.served = 0
        self.params_after_first = None  # host copy, taken by the warm call's eval hook
        self.setup_detail = {"model_optimizer_state_s": t1 - t0, "host_batches_s": now() - t1}

    def _initial_state(self, init_opt_state: bool = True):
        """As ``train.py lm-synthetic`` makes it: ``create_train_state`` in one
        jitted call.  ``check`` asks again for the parameters alone."""
        import jax

        from batchai_retinanet_horovod_coco_tpu.train import create_train_state

        shape = (self.t["per_chip_batch"], self.t["seq_len"])
        return jax.jit(lambda key: create_train_state(
            self.model, self.tx, shape, key, init_opt_state=init_opt_state,
            example_dtype=self.task.example_dtype))(jax.random.key(self.run.seed))

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
            self.model, self.state, self._batches(), None,
            LoopConfig(total_steps=total_steps, log_every=log_every, checkpoint_every=0,
                       resume=False, device_prefetch=self.t["device_prefetch"],
                       eval_every=1 if hooks else 0,
                       numerics=True),  # the per-group gradient norms the check reads
            task=self.task, logger=sink, **hooks,
        )

    def _keep_first_update(self, state) -> dict:
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
        k = max(1, len(rows) // 2)  # the first steps hold the compile (or the load)
        self.step_s = (rows[-1][1] - rows[-k - 1][1]) / k if len(rows) > k else 1.0
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
        trace_from = None
        if tracer.enabled:  # as train_loop.py: the profiler starts at the last periodic log
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
        per_step = t["per_chip_batch"]
        rate = e2e_rate = steps * per_step / window
        clean = [r for r in self.sink.rows if open_step <= r[0] <= trace_from] if tracer.enabled else []
        if len(clean) >= 2:  # tracing slows the host: the rate before the profiler starts
            rate = (clean[-1][0] - clean[0][0]) * per_step / (clean[-1][1] - clean[0][1])
        edges = [(r[0], r[1]) for r in self.sink.rows if r[0] >= open_step]
        in_window = [r for r in self.sink.rows if r[0] > open_step]
        flops = lm_flops.train_flops_per_sequence(
            self.run.config, t["seq_len"], lm_flops.attention_pairs([b.segment_ids for b in self.pool]))
        self.facts = {
            "t_window_open": t_open, "window_s": window, "steps": steps,
            "steps_per_s": steps / window,
            "sequences_per_s_chip": e2e_rate,
            "tokens_per_s_chip": e2e_rate * t["seq_len"],
            "model_flops_per_sequence": flops,
            "model_flops_per_s_chip": rate * flops["total"],
            "documents_per_sequence": float(np.mean([b.segment_ids.max(axis=1) + 1 for b in self.pool])),
            "log_windows": [[s1, t1 - t_open, (t1 - t0) / (s1 - s0) * 1e3]
                            for (s0, t0), (s1, t1) in zip(edges, edges[1:])],
            "after_last_log": {"steps": total - edges[-1][0], "ms": (t_close - edges[-1][1]) * 1e3},
            "setup_detail": self.setup_detail,
            "data_wait_ms": [r[2]["data_wait_ms"] for r in in_window if "data_wait_ms" in r[2]],
            "module_pattern": t["step_program_pattern"],
            "trace_steady_runs": t["trace_steady_runs"],
            "bytes_limit": self.bytes_limit,
        }
        return {"attempted": steps, "failed": 0, "end_to_end": {"train_img_per_s_chip": e2e_rate}}

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
        if not last <= same * (1.0 + t["loss_rise_tol"]):
            problems.append(f"loss on one batch rose: first {same:.4f}, step {last_step} {last:.4f}")

        self.state = None  # the reference needs the room Adam's slots took
        report = first_step_report(
            self.run.config, t, self.warm_sink.rows[0][2], self.params_after_first,
            self._initial_state(init_opt_state=False).params, self.pool[0], t["reference_blocks"])
        self.facts["first_step"] = report
        problems += first_step_problems(report, t["tolerances"])
        return problems


# ---- step 1 against the reference ------------------------------------------


def first_step_report(config: dict, traffic: dict, logged: dict, params_after, params_before, batch,
                      blocks: dict | None = None) -> dict:
    """Step 1 of the program (its logged scalars, its parameters after the
    step) against the float32 reference on the same parameters and batch;
    ``seconds`` says where the comparison's time went."""
    import jax
    import jax.numpy as jnp

    t0 = now()
    ref_loss, ref_grads = reference.loss_and_grads_by_layer(
        config, params_before, batch.tokens, batch.segment_ids, **(blocks or {}))
    ref_loss = float(ref_loss)
    t1 = now()
    square = jax.jit(lambda x: jnp.sum(jnp.square(x)))
    group_sq = {g: sum(float(square(x)) for x in jax.tree.leaves(ref_grads[g])) for g in ref_grads}
    ref_norm = math.sqrt(sum(group_sq.values()))
    report = {"loss": _against(logged["loss"], ref_loss),
              "grad_norm": _against(logged["grad_norm"], ref_norm)}
    for g in GROUPS:
        report[f"gnorm/{g}"] = _against(logged[f"gnorm/{g}"], math.sqrt(group_sq[g]))
    t2 = now()
    report["update"] = first_update_report(
        params_before, params_after, ref_grads, ref_norm, lr=traffic["lr"], eps=traffic["adam_eps"],
        weight_decay=traffic["weight_decay"], clip=traffic["clip_global_norm"])
    report["seconds"] = {"reference": t1 - t0, "norms": t2 - t1, "update": now() - t2}
    return report


def _against(program: float, reference_value: float) -> dict:
    return {"program": program, "reference_f32": reference_value,
            "rel": abs(program - reference_value) / abs(reference_value)}


def first_update_report(params0, params1, ref_grads, ref_norm: float, *, lr: float, eps: float,
                        weight_decay: float, clip: float) -> dict:
    """What AdamW's FIRST update can show, leaf by leaf.  With both moments
    zero the step is ``-lr * (g / (|g| + eps) + weight_decay * p)`` on the
    clipped gradient: close to ``-lr * sign(g)`` whatever the gradient's
    size, so the gradient is held by its norms and the update by

    - ``moved``: the root mean square of each leaf's step over that of the
      step the REFERENCE gradient predicts (0 skipped, 2 at a doubled rate),
      smallest and largest over the leaves whose predicted step is at least
      ``HELD_FLOOR`` of the rate (under it the gradient is near ``eps`` and
      float32 storage swallows the step: Mamba's ``A_log`` and ``dt_bias``);
      ``held_share`` is the share of the parameters in the leaves so held;
    - ``sign_agreement``: of the elements whose reference gradient is above
      ``eps``, the share that moved against its sign, smallest over the
      groups (0.5 for a step that ignores the gradient);
    - ``decay``: in each leaf, the coefficient of ``p`` in what is left of
      ``step / -lr`` after the reference's Adam term, over the elements
      whose sign agrees and whose gradient is above ``100 eps``:
      ``weight_decay`` where the leaf decays (two or more dimensions), 0
      where it does not; the largest error over the leaves.
    """
    import jax
    import jax.numpy as jnp

    if params1 is None:
        return {"why": "the loop never handed out the state after step 1", "moved_min": 0.0, "moved_max": 0.0,
                "held_share": 0.0, "sign_agreement_min": 0.0, "decay_error_max": float("inf")}
    scale = clip / max(ref_norm, clip)

    @jax.jit
    def leaf_sums(p0, p1, g, decay):
        """On the device, in float32: one pass over a leaf."""
        step = (p1 - p0) / -lr
        g = g * scale
        adam = g / (jnp.abs(g) + eps)
        agrees = jnp.sign(step) == jnp.sign(g)
        big = jnp.abs(g) > eps
        clear = agrees & (jnp.abs(g) >= 100 * eps)
        return {"step_sq": jnp.sum(step * step), "predicted_sq": jnp.sum(jnp.square(adam + decay * p0)),
                "agrees": jnp.sum(agrees & big), "big": jnp.sum(big),
                "pp": jnp.sum(jnp.where(clear, p0 * p0, 0.0)),
                "rp": jnp.sum(jnp.where(clear, (step - adam) * p0, 0.0))}

    moved, agreement, decay_error, under_floor = {}, {}, {}, []
    held = total = 0
    flat0 = jax.tree_util.tree_leaves_with_path(params0)
    for (path, p0), p1, g in zip(flat0, jax.tree.leaves(params1), jax.tree.leaves(ref_grads), strict=True):
        name = jax.tree_util.keystr(path)
        decay = weight_decay if p0.ndim >= 2 else 0.0
        sums = {k: float(v) for k, v in leaf_sums(p0, jnp.asarray(p1), g, decay).items()}
        predicted = math.sqrt(sums["predicted_sq"] / p0.size)
        total += p0.size
        if predicted >= HELD_FLOOR:
            moved[name] = math.sqrt(sums["step_sq"] / p0.size) / predicted
            held += p0.size
        else:
            under_floor.append(name)
        group = agreement.setdefault(path[0].key, [0.0, 0.0])
        group[0] += sums["agrees"]
        group[1] += sums["big"]
        if sums["pp"] > 0:
            decay_error[name] = abs(sums["rp"] / sums["pp"] - decay)
    worst = max(decay_error, key=decay_error.get, default=None)
    shares = {k: a / n for k, (a, n) in agreement.items() if n}
    return {
        "moved_min": min(moved.values(), default=0.0), "moved_max": max(moved.values(), default=0.0),
        "held_share": held / total, "leaves_under_floor": under_floor,
        "sign_agreement": shares, "sign_agreement_min": min(shares.values(), default=0.0),
        "decay_error_max": decay_error.get(worst, float("inf")), "decay_error_at": worst,
    }


def first_step_problems(report: dict, tol: dict) -> list[str]:
    """The report against the traffic file's ``tolerances`` (each with its
    reason there)."""
    problems = []
    for name in ("loss", "grad_norm", *(f"gnorm/{g}" for g in GROUPS)):
        limit = tol["loss_rel"] if name == "loss" else tol["grad_norm_rel"]
        if not report[name]["rel"] <= limit:
            problems.append(f"first step's {name} against the float32 reference: {report[name]}, tolerance {limit}")
    u = report["update"]
    lo, hi = tol["update_moved"]
    if not (lo <= u["moved_min"] and u["moved_max"] <= hi):
        problems.append(f"first step's update: a leaf moved {u['moved_min']:.4f} or {u['moved_max']:.4f} "
                        f"of what the reference predicts, outside [{lo}, {hi}]")
    if not u["held_share"] >= tol["update_held_share"]:
        problems.append(f"first step's update: only {u['held_share']:.4f} of the parameters are in leaves whose "
                        f"predicted step is large enough to hold, under {tol['update_held_share']}")
    if not u["sign_agreement_min"] >= tol["update_sign_agreement"]:
        problems.append(f"first step's update: {u['sign_agreement_min']:.4f} of a group's elements moved against "
                        f"the reference gradient, under {tol['update_sign_agreement']}")
    if not u["decay_error_max"] <= tol["update_decay_error"]:
        problems.append(f"first step's update: weight decay off by {u['decay_error_max']:.4f} at "
                        f"{u.get('decay_error_at')}, over {tol['update_decay_error']}")
    return problems

"""Traffic kind ``lm_hybrid_moe_train_loop``: ``lm_moe_train_loop`` for a
hybrid of state-space, attention and expert layers of which this chip holds a
share of the experts (``models/nemotron_h.py``): seeded packed token sequences
through ``train/loop.py::run_training`` with the language-model task.

The driver is ``lm_moe_train_loop.Driver`` (and through it
``lm_train_loop.Driver``): its ``setup`` (``build`` picks the model by
``model_type``), state, pool cycling, warm call and ``_train`` are REUSED BY
IMPORT, as are ``_Sink``, ``_against``, ``first_update_report``,
``picks_differ`` and ``rows_by_held_expert``.  What those two modules bind to
their own model is written again here and nothing else:

- ``measure``: the accepted one reads ``first_k_dense_replace`` and the
  ``moe/aux_loss`` counter, which this model has not, and counts
  DeepSeek-V2's FLOPs; this one counts Nemotron-H's
  (``harness/nemotron_flops.py``) and adds the scan's chunk for
  ``nemo_ssd_roofline``.  The window's logic is the same, line for line;
- ``check``'s call of the reference (``benchmark/reference/nemotron_h.py``:
  no auxiliary loss, ``scan_block`` beside ``head_block``);
- ``GROUPS`` and ``first_step_problems`` (the accepted ones read their own
  module's groups and an ``aux_loss`` entry).

A ``benchmark`` issue makes model, FLOP count, reference, groups and counters
arguments of ONE kind (ROADMAP S0c); it is not started here.

``correct``, as the dsv2 cell's: every logged loss finite and not risen when
the pool comes round; nothing compiled in the window (``run.py``); step 1 of
the timed path at the timed sizes against the float32 reference on the same
seed and batch: the loss, the gradient's norm whole and for each of embed,
mamba, attention, router, experts, shared, norms, head, the update, the
timed step's three row counters against the counts of the reference's picks,
and per expert layer the share of tokens whose picks differ from the
reference's (top-6 of 128 is discontinuous; the program's forward is run once
more for its picks, ``NemotronH.picks``).

THE WINDOW IS A FIXED SET OF STEPS (``window_steps`` of the traffic file), for
the dsv2 cell's reason: only the held experts add to the output here, so
training on the pool's few batches pulls the router toward them.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.harness import lm_flops, nemotron_flops
from benchmark.harness.runctx import now
from benchmark.kinds import lm_moe_train_loop as moe_base
from benchmark.kinds import lm_train_loop as base
from benchmark.reference import nemotron_h as reference

GROUPS = ("embed", "mamba", "attention", "router", "experts", "shared", "norms", "head")
COUNTERS = ("moe/rows_held", "moe/rows_max_expert", "moe/rows_min_expert")


class Driver(moe_base.Driver):
    def setup(self) -> None:
        try:
            super().setup()
        except ValueError as e:  # a program from before this model: fail at once, and say why
            raise SystemExit(f"benchmark: this program cannot build model_type {self.run.config['model_type']!r}: {e}")

    # ---- the window ------------------------------------------------------

    def measure(self) -> dict:
        import jax

        t, tracer = self.t, self.run.tracer
        log_every = t["log_every"]
        start = int(self.state.step)
        open_step = (start // log_every + 1) * log_every
        steps = t["window_steps"]  # the same steps, so the same routing, for every program (the module's note)
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

        self.sink = base._Sink(on_log)
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
        counters = {c: [r[2][c] for r in in_window if c in r[2]] for c in COUNTERS}
        rows_held = float(np.mean(counters["moe/rows_held"]))
        config, tokens = self.run.config, per_step * t["seq_len"]
        picks = tokens * config["num_experts_per_tok"] * nemotron_flops.expert_layers(config)
        pairs = per_step * lm_flops.attention_pairs([b.segment_ids for b in self.pool])
        flops = nemotron_flops.train_flops_per_step(config, tokens, pairs, rows_held)
        self.facts = {
            "t_window_open": t_open, "window_s": window, "steps": steps,
            "steps_per_s": steps / window,
            "sequences_per_s_chip": e2e_rate,
            "tokens_per_s_chip": e2e_rate * t["seq_len"],
            "model_flops_per_step": flops,
            "model_flops_per_s_chip": rate / per_step * flops["total"],
            "documents_per_sequence": float(np.mean([b.segment_ids.max(axis=1) + 1 for b in self.pool])),
            # the routing counters of the window's logged steps: rows routed to the held experts (summed over
            # the expert layers), and the fullest and emptiest held expert of any layer
            "moe_rows_held_per_step": rows_held,
            "moe_rows_held_share_of_picks": rows_held / picks,
            "moe_rows_held_share_of_picks_by_step": [[r[0], r[2]["moe/rows_held"] / picks]
                                                     for r in self.sink.rows if "moe/rows_held" in r[2]],
            "moe_rows_max_expert": max(counters["moe/rows_max_expert"]),
            "moe_rows_min_expert": min(counters["moe/rows_min_expert"]),
            "moe_buffer_rows": tokens * config["num_experts_per_tok"],
            # (step, rows) of every fetched step, and the step after which the profiler started: the grouped
            # products' roofline reader needs the rows of the traced steps themselves (routing moves)
            "moe_rows_logged": [[r[0], r[2]["moe/rows_held"]] for r in self.sink.rows if "moe/rows_held" in r[2]],
            "trace_from": trace_from,
            "ssd_chunk": self.model.config.mamba_chunk_size,  # the scan's own chunk: nemo_ssd_roofline counts by it
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
        import jax

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
        params = self._initial_state(init_opt_state=False).params
        batch = self.pool[0]
        picks = np.asarray(jax.jit(self.model.picks)(params, batch.tokens, batch.segment_ids))
        report = first_step_report(self.run.config, t, self.warm_sink.rows[0][2], self.params_after_first,
                                   params, batch, picks, t["reference_blocks"])
        self.facts["first_step"] = report
        problems += first_step_problems(report, t["tolerances"])
        return problems


# ---- step 1 against the reference ------------------------------------------


def first_step_report(config: dict, traffic: dict, logged: dict, params_after, params_before, batch, picks,
                      blocks: dict | None = None) -> dict:
    """Step 1 of the program (its logged scalars, its parameters after the
    step, its picks) against the float32 reference on the same parameters and
    batch; ``seconds`` says where the comparison's time went."""
    import jax
    import jax.numpy as jnp

    t0 = now()
    ref_loss, ref_grads, ref_picks = reference.loss_and_grads_by_layer(
        config, params_before, batch.tokens, batch.segment_ids, config["experts_held"], **(blocks or {}))
    ref_loss = float(ref_loss)
    t1 = now()
    square = jax.jit(lambda x: jnp.sum(jnp.square(x)))
    group_sq = {g: sum(float(square(x)) for x in jax.tree.leaves(ref_grads[g])) for g in ref_grads}
    ref_norm = math.sqrt(sum(group_sq.values()))
    report = {"loss": base._against(logged["loss"], ref_loss),
              "grad_norm": base._against(logged["grad_norm"], ref_norm)}
    for g in GROUPS:
        report[f"gnorm/{g}"] = base._against(logged[f"gnorm/{g}"], math.sqrt(group_sq[g]))
    differ = moe_base.picks_differ(picks, ref_picks)
    report["picks_differ"] = {"by_layer": differ, "max": max(differ)}
    # the timed step's own routing: its counters against the counts of the reference's picks
    ref_rows = moe_base.rows_by_held_expert(ref_picks, config["experts_held"])
    report["rows"] = {name: {"program": logged[f"moe/rows_{name}"], "reference_f32": float(count),
                             "rel": abs(logged[f"moe/rows_{name}"] - count) / max(float(count), 1.0)}
                      for name, count in (("held", ref_rows.sum()), ("max_expert", ref_rows.max()),
                                          ("min_expert", ref_rows.min()))}
    t2 = now()
    report["update"] = base.first_update_report(
        params_before, params_after, ref_grads, ref_norm, lr=traffic["lr"], eps=traffic["adam_eps"],
        weight_decay=traffic["weight_decay"], clip=traffic["clip_global_norm"])
    report["seconds"] = {"reference": t1 - t0, "norms": t2 - t1, "update": now() - t2}
    return report


def first_step_problems(report: dict, tol: dict) -> list[str]:
    """The report against the traffic file's ``tolerances`` (each with its
    reason there)."""
    problems = []
    limits = {"loss": tol["loss_rel"], "grad_norm": tol["grad_norm_rel"],
              **{f"gnorm/{g}": tol["grad_norm_rel"] for g in GROUPS}}
    for name, limit in limits.items():
        if not report[name]["rel"] <= limit:
            problems.append(f"first step's {name} against the float32 reference: {report[name]}, tolerance {limit}")
    for name, limit in (("held", tol["rows_held_rel"]), ("max_expert", tol["rows_expert_rel"]),
                        ("min_expert", tol["rows_expert_rel"])):
        if not report["rows"][name]["rel"] <= limit:
            problems.append(f"first step's rows routed here ({name}) by the timed step's counter against the "
                            f"reference's picks: {report['rows'][name]}, tolerance {limit}")
    if not report["picks_differ"]["max"] <= tol["picks_differ_max"]:
        problems.append(f"first step's picks: in a layer {report['picks_differ']['max']:.5f} of the tokens pick other "
                        f"experts than the float32 reference ({report['picks_differ']['by_layer']}), "
                        f"over {tol['picks_differ_max']}")
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

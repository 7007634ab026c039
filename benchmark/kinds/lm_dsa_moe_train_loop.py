"""Traffic kind ``lm_dsa_moe_train_loop``: ``lm_moe_train_loop`` for a
mixture-of-experts language model whose attention runs over the keys a learned
indexer selects (``models/keye_vl2.py``), of which this chip holds a share of the
experts: seeded token sequences through ``train/loop.py::run_training`` with the
language-model task.

The driver is ``lm_moe_train_loop.Driver`` (and through it
``lm_train_loop.Driver``): its ``setup`` (``build`` picks the model by
``model_type``), state, pool cycling, warm call and ``_train`` are REUSED BY
IMPORT, as are ``_Sink``, ``_against``, ``first_update_report``, ``picks_differ``
and ``rows_by_held_expert``.  What those modules bind to their own model is
written again here and nothing else:

- ``measure``: the accepted one reads ``first_k_dense_replace`` and counts
  DeepSeek-V2's FLOPs; this one counts this model's (``harness/keye_flops.py``:
  the SELECTED pairs for the main attention, the causal pairs for the index
  scores) and carries the selection's counters.  The window's logic is the same,
  line for line;
- ``check``'s call of the reference (``benchmark/reference/keye_vl2.py``, which is
  handed the program's selection) and what it reads of the selection;
- ``GROUPS`` and ``first_step_problems``.

A ``benchmark`` issue makes model, FLOP count, reference, groups and counters
arguments of ONE kind (ROADMAP S0c); it is not started here.

``correct``, as the dsv2 cell's: every logged loss finite and not risen when the
pool comes round; nothing compiled in the window (``run.py``); step 1 of the
timed path at the timed sizes against the float32 reference on the same seed and
batch: the loss, the balance loss, the indexer's loss, the gradient's norm whole
and for each of embed, attention, indexer, router, experts, norms, head, the
update, the timed step's three row counters and the share of tokens whose picks
differ.  TOP-K IS DISCONTINUOUS TWICE HERE.  For the keys a query selects, the
program's forward is run once more for its selection (``KeyeVL2.
picks_and_selection``) and THE REFERENCE COMPUTES LOSS AND GRADIENTS ON THAT
SELECTION, so that those limits stay as tight as dsv2's; beside them the check
holds, per layer, the share of (query, key) selections in which the program and
the reference's own ``lax.top_k`` of its own float32 scores differ, the largest
distance of a differing key's reference score from that query's threshold (as a
share of the size of the products that score is summed from, which is what a
rounding of their operands is relative to) - both for every layer and, under
limits ten times tighter, for the FIRST layer alone, whose input no earlier
top-k (of experts or of keys) has moved: there the two differ by the indexer's
own rounding and nothing else, and that is where a lower precision of the index
scores shows -, that no selected key lies outside
the causal past of the query's document, and that the TIMED step's own counter
of selected pairs is the layout's count to the last pair (the selection is
exact: ``sum_t min(p_t + 1, topk)`` for every layer, whatever the scores).

THE WINDOW IS A FIXED SET OF STEPS (``window_steps`` of the traffic file), for
the dsv2 cell's reason.  THE LAYOUT IS FIXED BY THE TRAFFIC FILE (``doc_len_min``
= ``seq_len``: every sequence one document), so no part of the step's work
follows ``--seed``.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.harness import keye_flops, lm_flops
from benchmark.harness.runctx import now
from benchmark.kinds import lm_moe_train_loop as moe_base
from benchmark.kinds import lm_train_loop as base
from benchmark.reference import keye_vl2 as reference

GROUPS = ("embed", "attention", "indexer", "router", "experts", "norms", "head")
COUNTERS = ("moe/rows_held", "moe/rows_max_expert", "moe/rows_min_expert", "moe/aux_loss", "dsa/kl_loss",
            "dsa/selected_share", "dsa/threshold_ties")


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
        picks = tokens * config["num_experts_per_tok"] * config["num_hidden_layers"]
        segments = [b.segment_ids for b in self.pool]
        causal = per_step * lm_flops.attention_pairs(segments)
        selected = per_step * keye_flops.selected_pairs(segments, config["sa_config"]["topk"])
        flops = keye_flops.train_flops_per_step(config, tokens, selected, causal, rows_held)
        self.facts = {
            "t_window_open": t_open, "window_s": window, "steps": steps,
            "steps_per_s": steps / window,
            "sequences_per_s_chip": e2e_rate,
            "tokens_per_s_chip": e2e_rate * t["seq_len"],
            "model_flops_per_step": flops,
            "model_flops_per_s_chip": rate / per_step * flops["total"],
            "documents_per_sequence": float(np.mean([b.segment_ids.max(axis=1) + 1 for b in self.pool])),
            # the pairs of one step: those the main attention needs (selected) and those the indexer scores (causal)
            "dsa_selected_pairs_per_step": selected, "dsa_causal_pairs_per_step": causal,
            "dsa_selected_share_logged": counters["dsa/selected_share"],
            "dsa_kl_loss_logged": counters["dsa/kl_loss"],
            "dsa_threshold_ties_logged": counters["dsa/threshold_ties"],
            # the routing counters of the window's logged steps: rows routed to the held experts (summed over
            # the layers), and the fullest and emptiest held expert of any layer
            "moe_rows_held_per_step": rows_held,
            "moe_rows_held_share_of_picks": rows_held / picks,
            "moe_rows_held_share_of_picks_by_step": [[r[0], r[2]["moe/rows_held"] / picks]
                                                     for r in self.sink.rows if "moe/rows_held" in r[2]],
            "moe_rows_max_expert": max(counters["moe/rows_max_expert"]),
            "moe_rows_min_expert": min(counters["moe/rows_min_expert"]),
            "moe_aux_loss_last": counters["moe/aux_loss"][-1],
            "moe_buffer_rows": tokens * config["num_experts_per_tok"],
            # (step, rows) of every fetched step, and the step after which the profiler started: the grouped
            # products' roofline reader needs the rows of the traced steps themselves (routing moves)
            "moe_rows_logged": [[r[0], r[2]["moe/rows_held"]] for r in self.sink.rows if "moe/rows_held" in r[2]],
            "trace_from": trace_from,
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
        picks, selection = jax.jit(self.model.picks_and_selection)(params, batch.tokens, batch.segment_ids)
        report = first_step_report(self.run.config, t, self.warm_sink.rows[0][2], self.params_after_first,
                                   params, batch, np.asarray(picks), selection, t["reference_blocks"])
        self.facts["first_step"] = report
        problems += first_step_problems(report, t["tolerances"])
        return problems


# ---- step 1 against the reference ------------------------------------------


def selection_summary(reports, logged_share: float, batch, topk: int) -> dict:
    """What the reference said of the program's selection (``reports``: by
    sequence, by layer, ``reference.selection_report``'s dict) and what the timed
    step's own counter says of it."""
    layers = len(reports[0])
    by_layer = lambda key, how: [how([float(seq[i][key]) for seq in reports]) for i in range(layers)]
    differ, own = by_layer("differ", sum), by_layer("own", sum)
    # a pair that differs is counted once on either side: over the two selections' pairs
    share = [d / (2.0 * o) for d, o in zip(differ, own)]
    distance, deviations = by_layer("distance_max", max), by_layer("deviations_max", max)
    segments = np.asarray(batch.segment_ids)
    expected = keye_flops.selected_pairs([segments], topk) / lm_flops.attention_pairs([segments])
    return {"differ_share_by_layer": share, "differ_share_max": max(share), "differ_share_first": share[0],
            "distance_by_layer": distance, "distance_max": max(distance), "distance_first": distance[0],
            "deviations_by_layer": deviations,
            "outside_allowed": sum(by_layer("outside_allowed", sum)),
            "pairs": {"program": sum(by_layer("given", sum)), "reference_f32": sum(own)},
            "selected_share": {"program": logged_share, "layout": expected,
                               "rel": abs(logged_share - expected) / expected}}


def first_step_report(config: dict, traffic: dict, logged: dict, params_after, params_before, batch, picks, selection,
                      blocks: dict | None = None) -> dict:
    """Step 1 of the program (its logged scalars, its parameters after the
    step, its picks and its selection) against the float32 reference on the
    same parameters, batch AND SELECTION; ``seconds`` says where the
    comparison's time went."""
    import jax
    import jax.numpy as jnp

    t0 = now()
    (ref_loss, (ref_aux, ref_kl)), ref_grads, ref_picks, reports = reference.loss_and_grads_by_layer(
        config, params_before, batch.tokens, batch.segment_ids, config["experts_held"], selection=list(selection),
        **(blocks or {}))
    ref_loss, ref_aux, ref_kl = float(ref_loss), float(ref_aux), float(ref_kl)
    t1 = now()
    square = jax.jit(lambda x: jnp.sum(jnp.square(x)))
    group_sq = {g: sum(float(square(x)) for x in jax.tree.leaves(ref_grads[g])) for g in ref_grads}
    ref_norm = math.sqrt(sum(group_sq.values()))
    report = {"loss": base._against(logged["loss"], ref_loss),
              "aux_loss": base._against(logged["moe/aux_loss"], ref_aux),
              "kl_loss": base._against(logged["dsa/kl_loss"], ref_kl),
              "grad_norm": base._against(logged["grad_norm"], ref_norm)}
    for g in GROUPS:
        report[f"gnorm/{g}"] = base._against(logged[f"gnorm/{g}"], math.sqrt(group_sq[g]))
    differ = moe_base.picks_differ(picks, ref_picks)
    report["picks_differ"] = {"by_layer": differ, "max": max(differ)}
    report["selection"] = selection_summary(reports, logged["dsa/selected_share"], batch, config["sa_config"]["topk"])
    report["threshold_ties"] = logged["dsa/threshold_ties"]
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
    limits = {"loss": tol["loss_rel"], "aux_loss": tol["aux_loss_rel"], "kl_loss": tol["kl_loss_rel"],
              "grad_norm": tol["grad_norm_rel"], **{f"gnorm/{g}": tol["grad_norm_rel"] for g in GROUPS}}
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
    s = report["selection"]
    for name, limit, what in (("differ_share_first", tol["selection_differ_first"], "of the (query, key) selections differ"),
                              ("distance_first", tol["selection_distance_first"], "of the size of its products lies a "
                               "differing key's reference score from its query's threshold")):
        if not s[name] <= limit:
            problems.append(f"first step's selection in the FIRST layer, whose input no earlier top-k has moved: "
                            f"{s[name]:.5f} {what}, over {limit}")
    if not s["differ_share_max"] <= tol["selection_differ_max"]:
        problems.append(f"first step's selection: in a layer {s['differ_share_max']:.5f} of the (query, key) selections "
                        f"differ from the float32 reference's own ({s['differ_share_by_layer']}), over "
                        f"{tol['selection_differ_max']}")
    if not s["distance_max"] <= tol["selection_distance_max"]:
        problems.append(f"first step's selection: a differing key's reference score lies {s['distance_max']:.5f} of the "
                        f"size of its products from its query's threshold ({s['distance_by_layer']}), over "
                        f"{tol['selection_distance_max']}")
    if s["outside_allowed"]:
        problems.append(f"first step's selection: {s['outside_allowed']} selected keys lie outside the causal past of "
                        "their query's document")
    if not s["selected_share"]["rel"] <= tol["selected_share_rel"]:
        problems.append(f"first step's selected pairs by the timed step's counter against the layout's count: "
                        f"{s['selected_share']}, tolerance {tol['selected_share_rel']}")
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

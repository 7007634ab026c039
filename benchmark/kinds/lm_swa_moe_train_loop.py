"""Traffic kind ``lm_swa_moe_train_loop``: ``lm_moe_train_loop`` for a
mixture-of-experts language model whose attention layers are of two kinds,
sliding-window and full (``models/afmoe.py``), of which this chip holds a share
of the experts: seeded token sequences through ``train/loop.py::run_training``
with the language-model task.

The driver is ``lm_moe_train_loop.Driver`` (and through it
``lm_train_loop.Driver``): its ``setup`` (``build`` picks the model by
``model_type``), state, pool cycling, warm call and ``_train`` are REUSED BY
IMPORT, as are ``_Sink``, ``_against``, ``first_update_report``,
``picks_differ`` and ``rows_by_held_expert``.  What those modules bind to
their own model is written again here and nothing else:

- ``measure``: the accepted ones count DeepSeek-V2's and Nemotron-H's FLOPs;
  this one counts this model's (``harness/afmoe_flops.py``: the VISIBLE pairs,
  ``sum_t min(p_t + 1, sliding_window)`` in a sliding layer) and carries the
  two run-share counters.  The window's logic is the same, line for line;
- ``check``'s call of the reference (``benchmark/reference/afmoe.py``) and the
  run shares against the layout's own count;
- ``GROUPS`` and ``first_step_problems``.

A ``benchmark`` issue makes model, FLOP count, reference, groups and counters
arguments of ONE kind (ROADMAP S0c); it is not started here.

``correct``, as the dsv2 cell's: every logged loss finite and not risen when
the pool comes round; nothing compiled in the window (``run.py``); step 1 of
the timed path at the timed sizes against the float32 reference on the same
seed and batch: the loss, the gradient's norm whole and for each of embed,
attention, dense_mlp, router, experts, shared, norms, head, the update, the
timed step's three row counters against the counts of the reference's picks,
per expert layer the share of tokens whose picks differ from the reference's
(top-8 of 128 is discontinuous; the program's forward is run once more for its
picks, ``Afmoe.picks``), and - where the attention kernels run - BOTH of the
timed step's run-share counters (``attn/block_pairs_run_share`` of the full
layers, ``attn/window_block_pairs_run_share`` of the sliding ones) against a
count of the layout's block pairs that hold a visible pair, made here from
positions and document ids block by block.  The sliding layers' counter is
counted IN THE FORWARD LIST OF THE KERNEL OBJECT those layers call
(``ops/attention.py::_window_run_share``), so a window that is masked and not
skipped (a list that holds every causal block) reads 1.0 where the count says
0.33; the full layers' is the accepted count of the step's documents.

THE WINDOW IS A FIXED SET OF STEPS (``window_steps`` of the traffic file), for
the dsv2 cell's reason.  THE LAYOUT IS FIXED BY THE TRAFFIC FILE (``doc_len_min``
= ``seq_len``: every sequence one document), so no part of the step's work
follows ``--seed``.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.harness import afmoe_flops
from benchmark.harness.runctx import now
from benchmark.kinds import lm_moe_train_loop as moe_base
from benchmark.kinds import lm_train_loop as base
from benchmark.reference import afmoe as reference

GROUPS = ("embed", "attention", "dense_mlp", "router", "experts", "shared", "norms", "head")
COUNTERS = ("moe/rows_held", "moe/rows_max_expert", "moe/rows_min_expert")
RUN_SHARES = {"full": "attn/block_pairs_run_share", "window": "attn/window_block_pairs_run_share"}


class Driver(moe_base.Driver):
    def setup(self) -> None:
        from batchai_retinanet_horovod_coco_tpu.models import language

        model_type = self.run.config["model_type"]
        if model_type not in language.BY_TYPE:  # a program from before this model: fail at once, and say why
            raise SystemExit(f"benchmark: this program cannot build model_type {model_type!r}: "
                             f"it trains {sorted(language.BY_TYPE)}")
        super().setup()

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
        counters = {c: [r[2][c] for r in in_window if c in r[2]] for c in (*COUNTERS, *RUN_SHARES.values())}
        rows_held = float(np.mean(counters["moe/rows_held"]))
        config, tokens = self.run.config, per_step * t["seq_len"]
        picks = tokens * config["num_experts_per_tok"] * afmoe_flops.expert_layers(config)
        segments = [b.segment_ids for b in self.pool]
        window_pairs = per_step * afmoe_flops.visible_pairs(segments, config["sliding_window"])
        full_pairs = per_step * afmoe_flops.visible_pairs(segments, None)
        flops = afmoe_flops.train_flops_per_step(config, tokens, window_pairs, full_pairs, rows_held)
        self.facts = {
            "t_window_open": t_open, "window_s": window, "steps": steps,
            "steps_per_s": steps / window,
            "sequences_per_s_chip": e2e_rate,
            "tokens_per_s_chip": e2e_rate * t["seq_len"],
            "model_flops_per_step": flops,
            "model_flops_per_s_chip": rate / per_step * flops["total"],
            "documents_per_sequence": float(np.mean([b.segment_ids.max(axis=1) + 1 for b in self.pool])),
            # the lowerings of attention (with the sliding layers' window), of the grouped products and of the rows
            # around them, what the recomputed layers keep, the experts held
            **self.model.run_meta((per_step, t["seq_len"])),
            # the visible (query, key) pairs of one step in ONE layer of each kind: what the two attention rooflines count
            "attention_window_pairs_per_step": window_pairs, "attention_full_pairs_per_step": full_pairs,
            # the share of the causal block pairs the two kinds of layer's forward kernels ran, as the steps logged it
            "attention_run_share_logged": {kind: counters[name] for kind, name in RUN_SHARES.items()},
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
        report["run_shares"] = run_shares_report(self.run.config, self.warm_sink.rows[0][2], batch.segment_ids,
                                                 kernel_blocks(self.model, batch.segment_ids.shape))
        self.facts["first_step"] = report
        problems += first_step_problems(report, t["tolerances"])
        return problems


# ---- step 1 against the reference ------------------------------------------


def kernel_blocks(model, bucket) -> dict | None:
    """``{"full": (query block, key block), "window": ...}`` of the program's
    forward kernels where its step's attention runs as kernels (its
    ``run_meta``), else nothing: the xla lowering skips nothing by block and
    logs no share."""
    from batchai_retinanet_horovod_coco_tpu.ops import attention

    if model.run_meta(tuple(bucket)).get("attention_lowering") != attention.KERNEL:
        return None
    blocks = (attention.BLOCK_SIZES["block_q"], attention.BLOCK_SIZES["block_kv"])
    return {"full": blocks, "window": blocks}


def block_pairs_with_a_visible_pair(segment_ids, block_q: int, block_kv: int, window: int | None) -> tuple[int, int]:
    """For a batch's ``segment_ids`` (batch, T), from positions and ids alone,
    one block pair at a time: (the pairs that hold a query and a key ``s <= t``,
    those of them that hold such a pair of ONE document no further apart than
    ``window``)."""
    seg = np.asarray(segment_ids)
    t = seg.shape[1]
    causal = visible = 0
    for row in seg:
        for q0 in range(0, t, block_q):
            pos_q = np.arange(q0, q0 + block_q)[:, None]
            for k0 in range(0, min(q0 + block_q, t), block_kv):
                pos_k = np.arange(k0, k0 + block_kv)[None, :]
                seen = (pos_k <= pos_q) & (row[q0:q0 + block_q, None] == row[None, k0:k0 + block_kv])
                if window is not None:
                    seen &= pos_q - pos_k < window
                causal += 1
                visible += bool(seen.any())
    return causal, visible


def run_shares_report(config: dict, logged: dict, segment_ids, blocks: dict | None) -> dict:
    """The timed step's two run-share counters against the layout's count;
    where the program's attention is no kernel, that it logged none."""
    if blocks is None:
        return {"kernel": False, "logged": sorted(set(RUN_SHARES.values()) & set(logged))}
    out = {"kernel": True}
    for kind, name in RUN_SHARES.items():
        causal, visible = block_pairs_with_a_visible_pair(
            segment_ids, *blocks[kind], config["sliding_window"] if kind == "window" else None)
        out[kind] = {"blocks": list(blocks[kind]), "causal": causal, "visible": visible,
                     **(base._against(logged[name], visible / causal) if name in logged else
                        {"program": None, "reference_f32": visible / causal, "rel": float("inf")})}
    return out


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
    shares = report.get("run_shares")
    if shares is not None and not shares["kernel"] and shares["logged"]:
        problems.append(f"the step's attention is no kernel and yet logged {shares['logged']}")
    for kind in RUN_SHARES if shares is not None and shares["kernel"] else ():
        if not shares[kind]["rel"] <= tol["run_share_rel"]:
            problems.append(f"first step's {RUN_SHARES[kind]} against the layout's count of block pairs that hold a "
                            f"visible pair: {shares[kind]}, tolerance {tol['run_share_rel']}")
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

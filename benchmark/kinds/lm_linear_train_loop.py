"""Traffic kind ``lm_linear_train_loop``: ``lm_train_loop`` for a hybrid of
gated-delta-rule linear-attention layers and full attention
(``models/olmo_hybrid.py``): seeded packed token sequences through
``train/loop.py::run_training`` with the language-model task, THE PACKING FIXED BY
THE TRAFFIC FILE.

The driver is ``lm_train_loop.Driver``: its state, pool cycling, warm call and
``_train`` are REUSED BY IMPORT, as are ``_Sink``, ``_against``,
``first_update_report`` and ``HELD_FLOOR``, and ``lm_moe_train_loop.build`` (the
model by ``model_type``).  What that module binds to its own
model is written again here and nothing else:

- ``setup``: the accepted one builds granite; this one builds the model the
  configuration's ``model_type`` names (``models/language.py``), fails AT ONCE where
  the program cannot (a program from before this model), and hands the traffic
  file's ``layout_seed`` to the packed source: the documents' lengths come from a
  generator of their own, so ``segment_ids`` of the pooled batches are the same for
  every ``--seed``, which moves token ids and weights alone.  (PR 35 made
  attention's work follow the packing, and a packing drawn from ``--seed`` moved a
  cell's rate by more than its bound between seeds: PRs 35, 37 and 39 in the ledger.)
- ``measure``: granite's closed loop of ``--seconds``, line for line, with this
  model's FLOPs (``harness/olmo_flops.py``), its ``run_meta`` and the delta rule's
  counters in ``facts``;
- ``check``'s call of the reference (``benchmark/reference/olmo_hybrid.py``: the
  recurrence token by token) and what it reads of the delta rule's counters;
- ``GROUPS`` and ``first_step_problems``.

A ``benchmark`` issue makes model, FLOP count, reference, groups and counters
arguments of ONE kind (ROADMAP S0c); it is not started here.

``correct``, as granite's cell: every logged loss finite and not risen when the
pool comes round; nothing compiled in the window (``run.py``); step 1 of the timed
path at the timed sizes against the float32 reference on the same seed and batch:
the loss, the gradient's norm whole and for each of embed, gdn, attention, mlp,
norms, head, the update (``first_update_report``), and the timed step's three
``gdn/*`` counters against the reference's own (the mean decay, the mean writing
strength, the largest Frobenius norm of a head's state at a chunk's end).
"""

from __future__ import annotations

import itertools
import math

import numpy as np

from benchmark.harness import lm_flops, olmo_flops
from benchmark.harness.runctx import now
from benchmark.kinds import lm_moe_train_loop as moe_base
from benchmark.kinds import lm_train_loop as base
from benchmark.reference import olmo_hybrid as reference

GROUPS = ("embed", "gdn", "attention", "mlp", "norms", "head")
COUNTERS = ("gdn/alpha_mean", "gdn/beta_mean", "gdn/state_norm_max")
RUN_SHARE = "attn/block_pairs_run_share"


build = moe_base.build  # the model its ``model_type`` names (``models/language.py``), AdamW as the traffic file has it


def packed_pool(config: dict, traffic: dict, seed: int) -> list:
    """The cell's pool of host batches: ``traffic['layout_seed']`` draws the
    documents, ``seed`` the token ids."""
    from batchai_retinanet_horovod_coco_tpu.data.tokens import PackedTokensConfig, packed_token_batches

    t = traffic
    return list(itertools.islice(packed_token_batches(PackedTokensConfig(
        vocab_size=config["vocab_size"], seq_len=t["seq_len"], batch_size=t["per_chip_batch"],
        doc_len_median=t["doc_len_median"], doc_len_sigma=t["doc_len_sigma"],
        doc_len_min=t["doc_len_min"], seed=seed, layout_seed=t["layout_seed"])), t["pool_batches"]))


class Driver(base.Driver):
    # ---- set-up ----------------------------------------------------------

    def setup(self) -> None:
        run, t = self.run, self.t
        if run.chips != 1:
            raise SystemExit("benchmark: the lm task trains on one chip")
        t0 = now()
        self.bytes_limit = (run.devices[0].memory_stats() or {}).get("bytes_limit")  # before anything else
        try:
            self.model, self.task, self.tx = build(run.config, t)
        except ValueError as e:  # a program from before this model: fail at once, and say why
            raise SystemExit(f"benchmark: this program cannot build model_type {run.config['model_type']!r}: {e}")
        self.state = self._initial_state()
        t1 = now()
        self.pool = packed_pool(run.config, t, run.seed)
        self.served = 0
        self.params_after_first = None  # host copy, taken by the warm call's eval hook
        self.setup_detail = {"model_optimizer_state_s": t1 - t0, "host_batches_s": now() - t1}

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
        meta = self.model.run_meta((per_step, t["seq_len"]))
        segments = [b.segment_ids for b in self.pool]
        flops = olmo_flops.train_flops_per_sequence(
            self.run.config, t["seq_len"], lm_flops.attention_pairs(segments), meta["delta_rule_chunk"])
        self.facts = {
            "t_window_open": t_open, "window_s": window, "steps": steps,
            "steps_per_s": steps / window,
            "sequences_per_s_chip": e2e_rate,
            "tokens_per_s_chip": e2e_rate * t["seq_len"],
            "model_flops_per_sequence": flops,
            "model_flops_per_s_chip": rate * flops["total"],
            "documents_per_sequence": float(np.mean([b.segment_ids.max(axis=1) + 1 for b in self.pool])),
            "documents_by_batch": [int(b.segment_ids.max(axis=1).sum() + len(b.segment_ids)) for b in self.pool],
            **meta,  # the lowerings of attention and of the delta rule, and the delta rule's chunk
            # the counters of the window's logged steps
            **{c.replace("/", "_") + "_logged": [r[2][c] for r in in_window if c in r[2]] for c in (*COUNTERS, RUN_SHARE)},
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
            self._initial_state(init_opt_state=False).params, self.pool[0], self.facts["delta_rule_chunk"],
            t["reference_blocks"])
        self.facts["first_step"] = report
        problems += first_step_problems(report, t["tolerances"])
        return problems


# ---- step 1 against the reference ------------------------------------------


def first_step_report(config: dict, traffic: dict, logged: dict, params_after, params_before, batch, chunk: int,
                      blocks: dict | None = None) -> dict:
    """Step 1 of the program (its logged scalars, its parameters after the step)
    against the float32 reference on the same parameters and batch; the
    reference reads its states after every ``chunk``-th token, where the program's
    chunks end; ``seconds`` says where the comparison's time went."""
    import jax
    import jax.numpy as jnp

    t0 = now()
    ref_loss, ref_grads, ref_counters = reference.loss_and_grads_by_layer(
        config, params_before, batch.tokens, batch.segment_ids, every=chunk, **(blocks or {}))
    ref_loss = float(ref_loss)
    t1 = now()
    square = jax.jit(lambda x: jnp.sum(jnp.square(x)))
    group_sq = {g: sum(float(square(x)) for x in jax.tree.leaves(ref_grads[g])) for g in ref_grads}
    ref_norm = math.sqrt(sum(group_sq.values()))
    report = {"loss": base._against(logged["loss"], ref_loss),
              "grad_norm": base._against(logged["grad_norm"], ref_norm)}
    for g in GROUPS:
        report[f"gnorm/{g}"] = base._against(logged[f"gnorm/{g}"], math.sqrt(group_sq[g]))
    for c in COUNTERS:
        report[c] = base._against(logged[c], ref_counters[c])
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
              **{f"gnorm/{g}": tol["grad_norm_rel"] for g in GROUPS},
              "gdn/alpha_mean": tol["alpha_mean_rel"], "gdn/beta_mean": tol["beta_mean_rel"],
              "gdn/state_norm_max": tol["state_norm_max_rel"]}
    for name, limit in limits.items():
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

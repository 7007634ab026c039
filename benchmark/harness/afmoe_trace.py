"""The ``afmoe`` (Trinity) step's device time by named scope, for the
``trinity_step.*`` readers and the three ``trinity_*_roofline`` readers:
``harness/moe_lm_trace.py``'s join with this model's scopes.  Against a program
that has no such step, scopes, kernels or counter every function here returns
nothing and raises nothing."""

from __future__ import annotations

from benchmark.harness import moe_lm_trace, program_trace

EXPECTED = {"attention", "dense_mlp", "moe", "lm_head", "optimizer"}
BENEATH = ("window_core", "full_core")  # scopes only this model's attention enters


def slices(ctx) -> dict | None:
    """``program_trace.join``'s result for this run (once per run), or nothing."""
    if not hasattr(ctx, "_program_slices"):
        try:
            from batchai_retinanet_horovod_coco_tpu.train import loop, step

            table, levels = step.scope_table(loop.compiled_step()), step.STEP_SCOPES
        except (ImportError, AttributeError, LookupError) as e:
            program_trace.say(f"no compiled step with scopes: {e!r}")
            table = None
        mine = table is not None and EXPECTED <= {t[0] for t in table.values()} and any(
            t[0] == "attention" and set(BENEATH) & set(t[2].split("/")) for t in table.values())
        if not mine:
            ctx._program_slices = None
        else:
            program_trace.slices(ctx, table, levels)
    return ctx._program_slices


def slice_ms(ctx, name: str, beneath: tuple[str, ...] | None = None) -> float | None:
    """ms per step in slice ``name`` (median over the steady runs), or in the
    scopes ``beneath`` it (forward, recomputed forward and backward, mean over
    the runs)."""
    s = slices(ctx)
    if s is None:
        return None
    if beneath is None:
        return s["ms"].get(name, 0.0)
    return sum(sum(s["by_scope"].get(name, {}).get(b, {}).values()) for b in beneath)


def gmm_ms_and_rows(ctx) -> tuple[float, float] | None:
    """As ``moe_lm_trace.gmm_ms_and_rows``: the grouped products' time and the
    rows of the very steps it is read in; nothing unless this is the step of
    this model (its scopes)."""
    return moe_lm_trace.gmm_ms_and_rows(ctx) if slices(ctx) is not None else None


def attention_roofline_pct(ctx, scope: str, pairs_fact: str, kind: str) -> float | None:
    """The share of its roofline of the attention layers of one ``kind``: the
    least time for the visible pairs of one step (``facts[pairs_fact]`` a layer)
    over the device time of ``attention/<scope>``."""
    from benchmark.harness import afmoe_flops, flops

    ms = slice_ms(ctx, "attention", (scope,)) if ctx.peaks is not None else None
    pairs = ctx.facts.get(pairs_fact)
    if not ms or not pairs:
        return None
    traffic, config = ctx.run.traffic, ctx.run.config
    layers = config["layer_types"][: config["num_hidden_layers"]].count(kind)
    cost = afmoe_flops.attention_cost_per_step(config, traffic["per_chip_batch"] * traffic["seq_len"], pairs, layers)
    return 100.0 * flops.roofline_share(cost, ms / 1e3, ctx.peaks)["share"]

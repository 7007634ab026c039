"""Schedule-search CLI: ``python -m batchai_retinanet_horovod_coco_tpu.tune``.

Measures candidates for the requested ops on THIS process's device,
composes the winners + full trial log into a schema-valid artifact, and
saves it to the per-device registry
(``artifacts/schedules/<device_kind>.json``) — consumers pick it up on
their next process start (RUNBOOK "Autotuning schedules").  ``--dry-run``
prints without writing; ``--smoke`` is the CPU-sized run of
``make tune-smoke``.

The search runs in this one process on whatever device JAX finds and
names it in the artifact; it starts no other process that would want the
same chip.
"""

from __future__ import annotations

import argparse
import json
import sys


def _ops_from_report(path: str) -> tuple[list[str], bool]:
    """PERF_REPORT.json (obs/analyze) → (op families, search batch axis).

    The perf doctor's top-3 bottleneck verdict names the ``tune/``
    problems to attack (``tune_ops`` per entry: nms/focal/matching/
    batch); this is the loop-closing consumer — ``--from-report`` turns a
    run's own attribution into the next search instead of a hand-picked
    --ops list.  Ops come back deduplicated in rank order; ``batch``
    maps onto the --batch-axis search rather than an op family.
    Raises SystemExit on an unreadable report or an empty verdict (an
    explicit "nothing tunable" beats silently searching everything).
    """
    ops: list[str] = []
    names: list[str] = []
    batch_axis = False
    try:
        with open(path) as f:
            report = json.load(f)
        # TypeError/AttributeError cover structurally-wrong JSON (a
        # top-level array, string entries): every malformation gets the
        # same friendly SystemExit, never a raw traceback.
        for b in report["bottlenecks"]:
            names.append(str(b.get("name")))
            for op in b.get("tune_ops") or []:
                if op == "batch":
                    batch_axis = True
                elif op not in ops:
                    ops.append(op)
    except (OSError, ValueError, KeyError, TypeError, AttributeError) as e:
        raise SystemExit(f"--from-report: cannot read {path!r}: {e}")
    if not ops and not batch_axis:
        raise SystemExit(
            f"--from-report: {path!r} names no tunable ops in its top-3 "
            f"verdict ({names}) — nothing for the tuner to attack; run "
            "the search explicitly with --ops"
        )
    return ops, batch_axis


def _parse_hw(text: str) -> tuple[int, int]:
    try:
        h, w = text.lower().split("x")
        return int(h), int(w)
    except ValueError:
        raise SystemExit(f"--hw: not an HxW shape: {text!r}") from None


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m batchai_retinanet_horovod_coco_tpu.tune",
        description="measured schedule search → per-device registry artifact",
    )
    ap.add_argument(
        "--ops", default=None,
        help="comma list of op families to search (default "
             "nms,focal,matching, or the --from-report verdict)",
    )
    ap.add_argument(
        "--batch-axis", action="store_true",
        help="also search per-bucket batch sizes (eval/serve tables)",
    )
    ap.add_argument(
        "--from-report", default=None, metavar="PERF_REPORT.json",
        help="derive the search from a perf-doctor report's top-3 "
             "bottleneck verdict (obs/analyze): the union of its "
             "tune_ops in rank order; a 'batch' op enables --batch-axis. "
             "An explicit --ops overrides",
    )
    ap.add_argument("--hw", default=None, metavar="HxW",
                    help="bucket to measure at (default: flagship 800x1344)")
    ap.add_argument("--batch", type=int, default=None,
                    help="batch size for op trials (default 8)")
    ap.add_argument("--steps", type=int, default=None,
                    help="timed calls per trial, split into two windows")
    ap.add_argument(
        "--include-semantic", action="store_true",
        help="also measure non-default pre_nms_size values (recorded as "
             "semantics-approx trials; never auto-promoted to winner)",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="CPU-sized smoke: tiny bucket/steps — proves the search "
             "end-to-end and commits an xla-winner artifact",
    )
    ap.add_argument("--device-kind", default=None,
                    help="override the artifact's device_kind (tests)")
    ap.add_argument("--out-root", default=None,
                    help="registry dir (default artifacts/schedules/)")
    ap.add_argument("--dry-run", action="store_true",
                    help="print the artifact instead of writing it")
    ap.add_argument("--trace", "--obs-trace", action="store_true",
                    dest="trace",
                    help="record tune_search/tune_trial spans to a "
                         "Perfetto-loadable trace in --obs-dir")
    ap.add_argument("--obs-dir", default="artifacts/obs",
                    help="where --trace writes its artifacts")
    args = ap.parse_args(argv)

    if args.from_report is not None and args.ops is None:
        report_ops, report_batch_axis = _ops_from_report(args.from_report)
        args.ops = ",".join(report_ops)
        args.batch_axis = args.batch_axis or report_batch_axis
        print(
            f"# tune: --from-report {args.from_report} -> "
            f"ops={args.ops or '(none)'} batch_axis={args.batch_axis}",
            flush=True,
        )
    if args.ops is None:
        args.ops = "nms,focal,matching"

    # Smoke defaults: small enough that a 2-vCPU box finishes in seconds.
    hw = _parse_hw(args.hw) if args.hw else ((256, 256) if args.smoke else None)
    batch = args.batch if args.batch is not None else (2 if args.smoke else None)
    steps = args.steps if args.steps is not None else (4 if args.smoke else None)
    args.steps = steps if steps is not None else 30

    from batchai_retinanet_horovod_coco_tpu.obs import trace as obs_trace

    if args.trace:
        obs_trace.configure(args.obs_dir, process_label="tune")

    from batchai_retinanet_horovod_coco_tpu.utils.backend import (
        announce_devices,
        enable_compile_cache,
    )

    enable_compile_cache()
    announce_devices("tune")

    try:
        from batchai_retinanet_horovod_coco_tpu.tune import search as search_lib

        kwargs = {}
        if hw is not None:
            kwargs["hw"] = hw
        if batch is not None:
            kwargs["batch"] = batch
        doc = search_lib.run_search(
            ops=tuple(p for p in args.ops.split(",") if p),
            steps=args.steps,
            include_semantic=args.include_semantic,
            search_batches=args.batch_axis,
            device_kind=args.device_kind,
            **kwargs,
        )

        from batchai_retinanet_horovod_coco_tpu.tune import (
            schedule as schedule_lib,
        )

        summary = {
            "device_kind": doc["device_kind"],
            "entries": doc["entries"],
            "trials": len(doc["trials"]),
            "failed": sum(
                1 for t in doc["trials"] if t["status"] == "failed"
            ),
            "skipped": sum(
                1 for t in doc["trials"] if t["status"] == "skipped"
            ),
        }
        if args.dry_run:
            print(json.dumps(doc, indent=2, sort_keys=True))
            return 0
        path = schedule_lib.save_schedule(doc, args.out_root)
        summary["artifact"] = path
        print(json.dumps(summary, sort_keys=True), flush=True)
        return 0
    finally:
        if args.trace:
            obs_trace.export()
            merged = obs_trace.merge_traces(out_name="tune_trace.json")
            print(f"# trace written to {merged}", flush=True)


if __name__ == "__main__":
    sys.exit(main())

"""Shared CLI glue for anchor hyperparameters (train.py / convert_model.py /
debug.py).

keras-retinanet carried custom anchor parameters in a ``--config`` ini and
baked them into the saved model (SURVEY.md M5/M11); here the equivalent is a
single flag surface (``add_anchor_flags``) plus a JSON sidecar persisted next
to the checkpoint (``save_anchor_config``), so eval/export/debug can never
silently regenerate default anchors for a model trained with custom ones —
anchors parameterize box decoding, so a mismatch produces garbage detections
with no error anywhere.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os

from batchai_retinanet_horovod_coco_tpu.ops.anchors import AnchorConfig

_ANCHOR_FILE = "anchor_config.json"
_FLAG_FIELDS = ("sizes", "strides", "ratios", "scales")


def float_list(text: str) -> tuple[float, ...]:
    """argparse type for comma-separated floats ('32,64' → (32.0, 64.0))."""
    try:
        values = tuple(float(v) for v in text.split(",") if v.strip())
    except ValueError:
        raise argparse.ArgumentTypeError(f"not a float list: {text!r}")
    if not values:
        raise argparse.ArgumentTypeError("empty list")
    return values


def add_anchor_flags(parser) -> None:
    """The anchor flag surface, identical on every tool that builds anchors."""
    parser.add_argument("--anchor-sizes", type=float_list, default=None,
                        metavar="S3,S4,S5,S6,S7",
                        help="anchor base size per pyramid level "
                             "(default 32,64,128,256,512)")
    parser.add_argument("--anchor-strides", type=float_list, default=None,
                        metavar="T3,T4,T5,T6,T7",
                        help="anchor stride per pyramid level "
                             "(default 8,16,32,64,128)")
    parser.add_argument("--anchor-ratios", type=float_list, default=None,
                        help="aspect ratios (default 0.5,1,2)")
    parser.add_argument("--anchor-scales", type=float_list, default=None,
                        help="octave scales (default 1,2^(1/3),2^(2/3))")


def add_data_pipeline_flags(parser) -> None:
    """The host input-pipeline flag surface (train.py; one definition so
    tools that grow a pipeline later can't drift).

    Sizing guidance lives in RUNBOOK.md ("Feeding the chips"): threads
    plateau at ~2 effective workers (PIL JPEG decode holds the GIL), so on
    hosts that must exceed ~35 imgs/s the process pool is the lever.
    """
    parser.add_argument("--workers", "--data-workers", dest="workers",
                        type=int, default=16,
                        help="decode THREADS for the in-process pool "
                             "(ignored when --data-worker-procs > 0 "
                             "selects the multiprocess producer)")
    parser.add_argument("--data-worker-procs", type=int, default=0,
                        help="decode worker PROCESSES writing into shared-"
                             "memory ring buffers (data/shm_pipeline.py); "
                             "0 = in-process thread pool.  Use ~1 process "
                             "per 35 imgs/s of step demand; bit-identical "
                             "batches either way")
    parser.add_argument("--data-worker-timeout", type=float, default=120.0,
                        help="seconds a head-of-line batch may stall before "
                             "the multiprocess pipeline raises (crash "
                             "detection is immediate; this bounds WEDGED "
                             "workers)")
    parser.add_argument("--device-prefetch", type=int, default=2,
                        help="batches transferred host->device ahead of the "
                             "step by a background thread (double "
                             "buffering); 0 = synchronous transfer")


def add_comm_flags(parser) -> None:
    """The gradient-communication flag surface of train.py."""
    parser.add_argument("--comm-compress", default="none",
                        choices=["none", "int8", "bf16"],
                        help="gradient-compression wire format "
                             "(comm/compress.py): int8 = bucketed "
                             "per-block symmetric int8 with error "
                             "feedback (~5/8 the exact bytes-on-wire), "
                             "bf16 = round-to-nearest bf16 (~3/4); the "
                             "reduce phase stays exact f32 either way.  "
                             "Composes with --shard-weight-update (the "
                             "compression moves to the ZeRO update "
                             "gather).  none = byte-identical "
                             "pre-ISSUE-13 step")
    parser.add_argument("--comm-overlap", action="store_true",
                        help="issue each schedule stage's (backbone/fpn/"
                             "heads) compressed collective from INSIDE "
                             "the backward pass (comm/overlap.py "
                             "custom-vjp staging) instead of one fused "
                             "pass after it; identical values, earlier "
                             "wire time.  DP path only: with "
                             "--shard-weight-update the compression is "
                             "the post-update gather and this flag is "
                             "ignored with a structured warning")
    parser.add_argument("--comm-bucket-mb", type=float, default=4.0,
                        help="bucket capacity in MB: leaves pack per "
                             "stage into flat buckets of this size so "
                             "small leaves share one quantized "
                             "collective; a bucket under "
                             "min_bucket_bytes stays exact")
    parser.add_argument("--comm-no-error-feedback", action="store_true",
                        help="disable the error-feedback residual "
                             "(comm state): quantization error is then "
                             "dropped each step instead of carried — "
                             "debugging/ablation only")
    # Topology-aware hierarchical tree (ISSUE 16): per-hop policy +
    # the slice-count knob that activates it.
    parser.add_argument("--comm-slices", type=int, default=None,
                        metavar="N",
                        help="slice count of the two-level device "
                             "grouping (parallel/mesh.py CommTopology): "
                             "with N > 1 and distinct per-hop modes the "
                             "gradient collective becomes hierarchical "
                             "— exact f32 within each ICI slice, "
                             "compressed exchange only on the "
                             "cross-slice DCN hop.  Default: derived "
                             "from the devices' slice_index (real "
                             "multi-slice TPU) or the "
                             "RETINANET_COMM_SLICES env; on the "
                             "virtual CPU mesh pass e.g. 2 to emulate "
                             "2 slices x 4 devices")
    parser.add_argument("--comm-ici-mode", default=None,
                        choices=["none", "int8", "bf16"],
                        help="wire format of the intra-slice (ICI) "
                             "hops once a topology engages; default "
                             "none = the fast wire stays exact f32.  "
                             "A compressed ici mode must equal the dcn "
                             "mode (which is just the flat tree)")
    parser.add_argument("--comm-dcn-mode", default=None,
                        choices=["none", "int8", "bf16"],
                        help="wire format of the cross-slice (DCN) hop "
                             "once a topology engages; default: "
                             "inherit --comm-compress — so "
                             "'--comm-compress int8 --comm-slices 2' "
                             "alone gives exact-ICI / int8-DCN")
    parser.add_argument("--comm-dcn-bucket-mb", type=float, default=None,
                        metavar="MB",
                        help="bucket capacity for the hierarchical "
                             "plan, sized for the DCN hop (the wire "
                             "that actually hurts); default: inherit "
                             "--comm-bucket-mb")


def make_comm_config(args):
    """CommConfig (or None) from the flags above."""
    from batchai_retinanet_horovod_coco_tpu.comm import CommConfig

    compress = getattr(args, "comm_compress", "none") or "none"
    overlap = bool(getattr(args, "comm_overlap", False))
    ici_mode = getattr(args, "comm_ici_mode", None)
    dcn_mode = getattr(args, "comm_dcn_mode", None)
    dcn_bucket_mb = getattr(args, "comm_dcn_bucket_mb", None)
    if (
        compress == "none"
        and not overlap
        and (dcn_mode or "none") == "none"
        and (ici_mode or "none") == "none"
    ):
        return None
    return CommConfig(
        compress=compress,
        overlap=overlap,
        bucket_mb=float(getattr(args, "comm_bucket_mb", 4.0)),
        error_feedback=not getattr(args, "comm_no_error_feedback", False),
        ici_mode=ici_mode,
        dcn_mode=dcn_mode,
        dcn_bucket_mb=(
            None if dcn_bucket_mb is None else float(dcn_bucket_mb)
        ),
    )


def add_obs_flags(parser) -> None:
    """The observability flag surface (train.py / evaluate.py; ISSUE 3).

    One definition so every tool that grows tracing exposes the same
    knobs.  With both flags off the subsystem costs nothing: spans check
    one module-level bool and heartbeats are attribute stores."""
    parser.add_argument("--obs-trace", action="store_true",
                        help="record trace spans (step loop, data "
                             "pipeline, shm decode workers, prefetch, "
                             "eval consumer) and export a Perfetto-"
                             "loadable Chrome trace JSON into --obs-dir "
                             "at exit (obs/trace.py)")
    parser.add_argument("--obs-dir", default=None,
                        help="observability artifact directory (trace "
                             "JSON, watchdog stack dumps); default "
                             "artifacts/obs when --obs-trace is set")
    parser.add_argument("--obs-stall-timeout", type=float, default=120.0,
                        help="seconds a registered component may go "
                             "without a heartbeat before the watchdog "
                             "dumps a stall diagnosis (structured JSON + "
                             "all-thread stacks; it never kills the run "
                             "— obs/watchdog.py).  Only takes effect "
                             "with --obs-trace/--obs-dir (the subsystem "
                             "is otherwise fully disabled)")
    # Live telemetry + SLO surface (ISSUE 9, obs/telemetry.py + obs/slo.py)
    parser.add_argument("--obs-port", type=int, default=None, metavar="PORT",
                        help="start a drain-safe stdlib HTTP status "
                             "server on this port (0 = ephemeral, "
                             "printed at startup) exposing the live "
                             "telemetry registry during the run: GET "
                             "/metrics (Prometheus text exposition), "
                             "/healthz (watchdog-backed liveness — 503 "
                             "names the stalled component), /statusz "
                             "(JSON snapshot).  Read-only; daemon "
                             "threads — it can never wedge a pod exit")
    parser.add_argument("--slo-rule", action="append", default=None,
                        metavar="METRIC{>,<}THR[@FOR_S]",
                        help="declarative SLO over a telemetry snapshot "
                             "metric, evaluated by a monitor thread; a "
                             "sustained breach emits exactly ONE "
                             "structured slo_violation event (JSONL + "
                             "trace instant + PERF_REPORT violations "
                             "section).  THR 'x1.5' means regression vs "
                             "a rolling-median baseline.  Examples: "
                             "'serve_request_latency_ms.p99>250@30', "
                             "'train_step_time_ms>x1.5@60'.  Repeatable; "
                             "a watchdog-stall rule is always included")
    parser.add_argument("--slo-poll-s", type=float, default=5.0,
                        help="SLO monitor poll interval (seconds)")
    # Numerics flight recorder (ISSUE 10, obs/numerics.py)
    parser.add_argument("--numerics", action="store_true",
                        help="fuse the in-step numerics summary into the "
                             "compiled train step: pre-clip global + "
                             "per-layer-group gradient norms, update/"
                             "param ratio, non-finite count, and the "
                             "cross-replica agreement probe on mesh "
                             "runs (~2 extra global reduces per step; "
                             "the summary lands in metrics.jsonl as "
                             "structured 'numerics' records, in the "
                             "telemetry gauges the built-in nonfinite/"
                             "grad-norm-spike SLO rules watch, and in "
                             "PERF_REPORT's numerics section).  The "
                             "NaN-provenance NUMERICS_DUMP.json on a "
                             "tripped finite-check is always armed, "
                             "with or without this flag")


def add_durability_flags(parser) -> None:
    """The preemption/recovery flag surface (ISSUE 11, train.py).  One
    definition so the chaos harness (scripts/chaos.py) and any future
    tool that grows resume semantics expose identical knobs."""
    parser.add_argument("--resume-elastic", action="store_true",
                        help="on resume, re-derive the input-stream "
                             "position from the checkpoint manifest "
                             "(consumed batches = restored step) so no "
                             "batch is replayed or skipped — including "
                             "when the world size changed since the save "
                             "(the ZeRO optimizer state reshards "
                             "automatically; utils/checkpoint.py).  "
                             "Requires the same --batch-size and --seed "
                             "the checkpoint was written with (validated "
                             "against the manifest)")
    parser.add_argument("--auto-resume", action="store_true",
                        help="self-healing numerics resume: on a "
                             "non-finite abort, restore the last healthy "
                             "checkpoint (the pre-save gate guarantees "
                             "finiteness), reseed the data order and "
                             "exclude the poison batch's image ids "
                             "recorded in NUMERICS_DUMP.json, emit one "
                             "structured auto_resume event, and continue "
                             "to --steps")
    parser.add_argument("--max-auto-resumes", type=int, default=3,
                        help="give up (re-raise the abort) after this "
                             "many auto-resumes in one invocation")
    parser.add_argument("--inject-nan-step", type=int, default=None,
                        metavar="N",
                        help="FAULT INJECTION (scripts/chaos.py): poison "
                             "the N-th training batch with NaN, once per "
                             "process — exercises the numerics abort + "
                             "--auto-resume path end-to-end on a real "
                             "run.  Never use outside chaos testing")


def add_serve_flags(parser) -> None:
    """The inference-server flag surface (serve/frontend.py and
    serve/fleet.py CLIs)."""
    parser.add_argument("--serve-max-delay-ms", type=float, default=10.0,
                        help="dynamic-batching deadline: a partial batch "
                             "fires at most this long after its first "
                             "request reaches the batcher (in continuous "
                             "mode the deadline is the upper bound; the "
                             "dispatch gate usually seals first)")
    parser.add_argument("--serve-batching", default="continuous",
                        choices=["continuous", "deadline"],
                        help="continuous (default): slot-pool in-flight "
                             "batching — batch N+1 assembles while N runs "
                             "and seals the instant the device is ready; "
                             "deadline: the classic deadline-only "
                             "coalescing (comparison/benchmark mode)")
    parser.add_argument("--serve-admission-queue", type=int, default=128,
                        help="bounded front-door queue; a full queue "
                             "REJECTS (sheds) instead of growing — "
                             "overload becomes explicit 503s, not "
                             "unbounded latency")
    parser.add_argument("--serve-bucket-queue", type=int, default=64,
                        help="bounded per-bucket coalescing queue (full "
                             "= shed with reason bucket_queue_full)")
    parser.add_argument("--serve-workers", type=int, default=2,
                        help="host decode/resize worker threads (the "
                             "serve router)")
    parser.add_argument("--serve-timeout-s", type=float, default=None,
                        help="default per-request deadline (expired "
                             "requests are rejected, never occupy a "
                             "batch row); unset = no deadline")
    parser.add_argument("--serve-drain-timeout-s", type=float, default=30.0,
                        help="graceful close() waits this long for "
                             "in-flight requests before rejecting the "
                             "remainder")
    parser.add_argument("--replica-id", default=None,
                        help="stable identity carried in /healthz load "
                             "fields (fleet routing / canary attribution "
                             "— ISSUE 12); default host-pid.  The fleet "
                             "CLI pins it across restarts so a breaker-"
                             "open replica is re-admitted as itself")


def make_serve_config(args):
    """ServeConfig from the flags above (lazy import: the serve package
    pulls the data/obs layers, which CLI-only callers may not need)."""
    from batchai_retinanet_horovod_coco_tpu.serve.common import ServeConfig

    return ServeConfig(
        max_delay_ms=args.serve_max_delay_ms,
        continuous=getattr(args, "serve_batching", "continuous")
        == "continuous",
        admission_queue=args.serve_admission_queue,
        bucket_queue=args.serve_bucket_queue,
        preprocess_workers=args.serve_workers,
        default_timeout_s=args.serve_timeout_s,
        drain_timeout_s=args.serve_drain_timeout_s,
    )


def configure_obs(args, process_label: str = "main", sink=None):
    """Bring up the obs subsystem from the flags above; returns the obs
    dir (None = disabled).  Call BEFORE building pipelines so spawned shm
    workers inherit the trace env contract."""
    if not (getattr(args, "obs_trace", False) or getattr(args, "obs_dir", None)):
        return None
    from batchai_retinanet_horovod_coco_tpu import obs

    return obs.enable(
        args.obs_dir or "artifacts/obs",
        process_label=process_label,
        stall_after=getattr(args, "obs_stall_timeout", 120.0),
        sink=sink,
    )


def make_pipeline_worker_kwargs(args) -> dict:
    """PipelineConfig kwargs for the worker/prefetch flags above."""
    return dict(
        num_workers=args.workers,
        num_worker_procs=getattr(args, "data_worker_procs", 0) or 0,
        worker_timeout=getattr(args, "data_worker_timeout", 120.0),
    )


def make_anchor_config(args) -> AnchorConfig:
    """AnchorConfig from the CLI flags (defaults where flags are unset).

    One config object threads through the model (head sizing), the train
    step, detection, and export so they can never disagree.
    """
    default = AnchorConfig()
    kw = {}
    if args.anchor_sizes is not None:
        kw["sizes"] = args.anchor_sizes
    if args.anchor_strides is not None:
        for s in args.anchor_strides:
            if not float(s).is_integer():
                raise SystemExit(
                    f"--anchor-strides must be whole numbers, got {s}"
                )
        kw["strides"] = tuple(int(s) for s in args.anchor_strides)
    if args.anchor_ratios is not None:
        kw["ratios"] = args.anchor_ratios
    if args.anchor_scales is not None:
        kw["scales"] = args.anchor_scales
    for key in ("sizes", "strides"):
        if key in kw and len(kw[key]) != len(default.levels):
            raise SystemExit(
                f"--anchor-{key} needs {len(default.levels)} entries "
                f"(one per pyramid level {default.levels}), got {len(kw[key])}"
            )
    return dataclasses.replace(default, **kw) if kw else default


def save_anchor_config(snapshot_dir: str, config: AnchorConfig) -> None:
    """Persist the anchor config next to the checkpoints (process 0 only).

    Atomic (temp file + rename) and skipped when unchanged: peer processes
    read this file at startup with no barrier in between, so a truncating
    rewrite could be observed half-written.
    """
    os.makedirs(snapshot_dir, exist_ok=True)
    if load_anchor_config(snapshot_dir) == config:
        return
    path = os.path.join(snapshot_dir, _ANCHOR_FILE)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(dataclasses.asdict(config), f, indent=1)
    os.replace(tmp, path)


def load_anchor_config(snapshot_dir: str | None) -> AnchorConfig | None:
    if not snapshot_dir:
        return None
    path = os.path.join(snapshot_dir, _ANCHOR_FILE)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        raw = json.load(f)
    return AnchorConfig(**{k: tuple(v) for k, v in raw.items()})


def resolve_anchor_config(
    args, snapshot_dir: str | None, fresh: bool = False
) -> AnchorConfig:
    """Combine CLI flags with the config persisted beside the checkpoint.

    - flags given, no saved config (or they match): use the flags;
    - no flags, saved config present: use the saved one (an eval/export/
      resume run never has to repeat the flags);
    - both present and DIFFERENT: abort — mixing anchors across a
      checkpoint boundary decodes garbage, never do it silently.
    - ``fresh`` (--no-resume): the run deliberately ignores prior state,
      so the flags (or defaults) win and the stale sidecar is ignored
      (the caller's save then overwrites it).
    """
    from_flags = make_anchor_config(args)
    if fresh:
        return from_flags
    flags_given = any(
        getattr(args, f"anchor_{k}") is not None for k in _FLAG_FIELDS
    )
    saved = load_anchor_config(snapshot_dir)
    if saved is None:
        return from_flags
    if not flags_given:
        if saved != AnchorConfig():
            print(f"using anchor config persisted in {snapshot_dir}")
        return saved
    if from_flags != saved:
        raise SystemExit(
            f"anchor flags conflict with the config persisted in "
            f"{snapshot_dir} (trained with {saved}); drop the flags to use "
            "the saved config, or point --snapshot-path elsewhere"
        )
    return from_flags

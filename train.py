#!/usr/bin/env python
"""RetinaNet-on-COCO training entrypoint — the reference `train.py` surface,
TPU-native underneath.

Reference parity (SURVEY.md W1/M11, §5.6): argparse CLI with a dataset
subcommand (`train.py coco <path>`), flags for batch size / lr / steps /
snapshot path / backbone / freeze-backbone / image sides.  What changed
underneath (BASELINE.json:5): hvd.init → `jax.distributed.initialize`;
`hvd.DistributedOptimizer`'s NCCL allreduce → `lax.pmean` over a `data` mesh
axis inside ONE jit-compiled SPMD step; Keras fit_generator → an explicit
step loop; rank-0 .h5 snapshots → orbax multi-host checkpoints; the CocoEval
callback → an on-device detect + numpy mAP oracle eval hook.

The five BASELINE.json configs are runnable by name via ``--preset``:

  cpu-inference  single-image COCO inference smoke (configs[0])
  coco-mini      single-device overfit training (configs[1])
  dp8            single-host 8-chip data-parallel training (configs[2])
  pod            multi-host pod training, full COCO2017 1333x800 (configs[3])
  eval           on-device batched NMS + mAP@[.5:.95] eval (configs[4])

A second kind of model trains through the same loop (train/task.py):
``train.py lm-synthetic`` builds a language model and trains it on seeded
packed token sequences (data/tokens.py).  ``--model <config.json>`` takes the
published keys and picks the model by ``model_type``: ``granitemoehybrid``
(Granite 4.0-H: Mamba-2 mixers and a grouped-query attention layer per
period, models/granite_hybrid.py; benchmark/configs/granite-4.0-h-micro-p1.json)
or ``deepseek_v2`` (latent attention, routed and shared experts of which this
chip holds a share, models/deepseek_v2.py;
benchmark/configs/deepseek-v2-lite-ep8.json) or ``nemotron_h`` (Nemotron-H:
every layer ONE mixer, a Mamba-2 scan with grouped B and C, squared-ReLU
routed and shared experts behind a sigmoid router, or grouped-query attention,
models/nemotron_h.py; benchmark/configs/nemotron-3-nano-30b-ep16.json) or
``KeyeVL2`` (Keye-VL-2.0's language model: grouped-query attention over the
keys a learned indexer selects, exactly the top 2048 a query, and softmax-routed
experts without a shared one, models/keye_vl2.py;
benchmark/configs/keye-vl2-30b-a3b-ep8.json) or ``olmo_hybrid`` (gated-delta-rule
linear attention beside full attention, models/olmo_hybrid.py;
benchmark/configs/olmo-hybrid-7b-p1.json) or ``afmoe`` (Trinity: sliding-window
and full attention layers mixed, an output gate, sigmoid-routed experts with a
shared one, models/afmoe.py; benchmark/configs/trinity-mini-ep8.json).
``--model tiny`` (the default: one Granite period of ten layers at width 64),
``--model tiny-moe`` (one dense and two expert layers, 4 of 16 experts held),
``--model tiny-nemotron`` (the pattern MEM*E, 2 groups, 2 of 8 experts held)
``--model tiny-keye`` (three layers, a query keeps 24 keys, 4 of 16 experts
held), ``--model tiny-olmo`` (one period of three delta-rule layers and an
attention layer) and ``--model tiny-afmoe`` (a dense and three expert layers, a
window of 16 keys in three of four, 2 of 8 experts held) are what the CPU tests run.  The LM task is single-chip until an issue brings its sharding:
``--num-devices`` above 1 is refused.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

PRESETS: dict[str, dict] = {
    # BASELINE.json configs[0]: single-image CPU-reference inference.
    "cpu-inference": {"eval_only": True, "batch_size": 1, "num_devices": 1},
    # configs[1]: focal+smooth-L1 training on COCO-mini, single device.
    "coco-mini": {
        "batch_size": 2,
        "steps": 500,
        "num_devices": 1,
        "eval_every": 0,
        "schedule": "constant",
    },
    # configs[2]: single-host 8-chip DP (psum gradient allreduce).
    "dp8": {"batch_size": 16, "num_devices": 8},
    # configs[3]: multi-host pod, full COCO2017 at 1333x800 multiscale.
    "pod": {
        "batch_size": 256,
        "num_devices": 0,  # 0 = all global devices
        "distributed_auto": True,
        "steps": 90000 // 16,  # ~12 epochs at global batch 256
    },
    # configs[4]: COCO eval — on-device batched NMS + mAP computation.
    "eval": {"eval_only": True},
}


from batchai_retinanet_horovod_coco_tpu.data.pipeline import (  # noqa: E402
    default_buckets,
)
from batchai_retinanet_horovod_coco_tpu.models.retinanet import (  # noqa: E402
    BACKBONES,
)


# Shared with convert_model.py / debug.py — one anchor surface (utils/cli.py).
from batchai_retinanet_horovod_coco_tpu.utils.cli import (  # noqa: E402
    add_anchor_flags,
    add_comm_flags,
    add_data_pipeline_flags,
    add_durability_flags,
    add_obs_flags,
    configure_obs,
    make_anchor_config,
    make_comm_config,
    make_pipeline_worker_kwargs,
    resolve_anchor_config,
    save_anchor_config,
)


def build_parser() -> argparse.ArgumentParser:
    # allow_abbrev=False: preset-default resolution compares raw argv flag
    # names against dest names, which only works with unabbreviated flags.
    p = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
        allow_abbrev=False,
    )
    p.add_argument("--preset", choices=sorted(PRESETS), default=None,
                   help="named BASELINE.json config; explicit flags override")

    sub = p.add_subparsers(dest="dataset_type", required=True)
    coco = sub.add_parser(
        "coco", help="train on a COCO-format dataset", allow_abbrev=False
    )
    coco.add_argument("coco_path", help="dataset root")
    coco.add_argument("--train-annotations",
                      default="annotations/instances_train2017.json")
    coco.add_argument("--train-images", default="train2017")
    coco.add_argument("--val-annotations",
                      default="annotations/instances_val2017.json")
    coco.add_argument("--val-images", default="val2017")
    csvp = sub.add_parser(
        "csv", help="train on a CSV-format dataset "
        "(keras-retinanet annotations.csv/classes.csv)", allow_abbrev=False,
    )
    csvp.add_argument("csv_annotations", help="annotations CSV "
                      "(path,x1,y1,x2,y2,class_name)")
    csvp.add_argument("csv_classes", help="classes CSV (class_name,id)")
    csvp.add_argument("--val-csv-annotations", default=None,
                      help="validation annotations CSV (default: none)")
    csvp.add_argument("--image-dir", default=None,
                      help="base dir for image paths (default: the "
                           "annotations file's directory)")
    pascal = sub.add_parser(
        "pascal", help="train on a Pascal VOC dataset (VOCdevkit layout)",
        allow_abbrev=False,
    )
    pascal.add_argument("pascal_path", help="VOCdevkit year root "
                        "(contains Annotations/, JPEGImages/, ImageSets/)")
    pascal.add_argument("--train-split", default="trainval")
    pascal.add_argument("--val-split", default="test")
    pascal.add_argument("--skip-difficult", action="store_true",
                        help="drop difficult objects entirely (default: "
                             "keep as ignore regions)")
    synth = sub.add_parser(
        "synthetic", help="generated dataset (air-gapped dev/CI path)",
        allow_abbrev=False,
    )
    synth.add_argument("--synthetic-root", default="/tmp/synthetic_coco")
    synth.add_argument("--synthetic-images", type=int, default=64)
    synth.add_argument("--synthetic-classes", type=int, default=3)
    synth.add_argument("--synthetic-size", default="256",
                       help="source image size: N (square) or HxW — e.g. "
                            "800x1344 generates images that land exactly in "
                            "the flagship bucket (make convergence-full)")

    _add_lm_parser(sub)

    for sp in (coco, csvp, pascal, synth):
        # Also accepted after the subcommand; SUPPRESS so the subparser
        # doesn't clobber a top-level --preset with its default.
        sp.add_argument("--preset", choices=sorted(PRESETS),
                        default=argparse.SUPPRESS, help=argparse.SUPPRESS)
        g = sp.add_argument_group("model")
        g.add_argument("--backbone", default="resnet50", choices=BACKBONES)
        g.add_argument("--norm", default="gn", choices=["gn", "bn", "frozen_bn"])
        g.add_argument("--stem", default="space_to_depth",
                       choices=["conv", "space_to_depth", "space_to_depth4"],
                       help="stem formulation; space_to_depth is the "
                            "math-identical MLPerF reformulation, ~4%% "
                            "faster on TPU (models/resnet.py)")
        g.add_argument("--pack-width", action="store_true",
                       help="width-packed stage2 (ResNet only): fold W "
                            "pairs into channels so the C=64 stage fills "
                            "the MXU lanes; math-identical, measured "
                            "SLOWER on v5e at the flagship bucket "
                            "(bandwidth-bound stage) — opt-in for "
                            "narrow-channel-bound shapes (models/resnet.py)")
        g.add_argument("--f32", action="store_true",
                       help="compute in float32 (default bfloat16)")
        # Anchor hyperparameters (keras-retinanet --config ini parity,
        # SURVEY.md M5/M11): shared surface, utils/cli.py.
        add_anchor_flags(g)
        g.add_argument("--freeze-backbone", action="store_true")
        g.add_argument("--pretrained-backbone", default=None,
                       help="torch resnet50 state dict (.pth/.npz) to import; "
                            "use with --norm frozen_bn (the reference recipe)")

        g = sp.add_argument_group("data")
        g.add_argument("--batch-size", type=int, default=16,
                       help="GLOBAL batch size (split over devices)")
        g.add_argument("--image-min-side", type=int, default=800)
        g.add_argument("--image-max-side", type=int, default=1333)
        g.add_argument("--max-gt", type=int, default=None,
                       help="gt boxes padded per image; default auto-sizes "
                            "to the dataset's true per-image max (COCO "
                            "images can exceed 100) so no box is dropped")
        # --workers / --data-workers / --data-worker-procs /
        # --data-worker-timeout / --device-prefetch (utils/cli.py — shared
        # surface; TPU-VM hosts have ~112 vCPUs and need ~1 core per
        # 3 imgs/s of step demand).
        add_data_pipeline_flags(g)
        g.add_argument("--random-transform", action="store_true",
                       help="full random affine + photometric augmentation "
                            "(reference --random-transform; default is "
                            "hflip-only)")

        g = sp.add_argument_group("optimization")
        g.add_argument("--steps", type=int, default=90000)
        g.add_argument("--lr", type=float, default=0.01)
        g.add_argument("--optimizer", default="sgd", choices=["sgd", "adam"])
        g.add_argument("--schedule", default="multistep",
                       choices=["multistep", "cosine", "constant", "plateau"])
        g.add_argument("--plateau-factor", type=float, default=0.1,
                       help="LR multiplier on plateau (--schedule plateau)")
        g.add_argument("--plateau-patience", type=int, default=2,
                       help="non-improving windows before reducing")
        g.add_argument("--plateau-window", type=int, default=1000,
                       help="steps of loss averaged per window (epoch analogue)")
        g.add_argument("--plateau-min-delta", type=float, default=1e-4,
                       help="absolute loss improvement below which a window "
                            "counts as a plateau")
        g.add_argument("--warmup-steps", type=int, default=500)
        g.add_argument("--weight-decay", type=float, default=1e-4)
        g.add_argument("--seed", type=int, default=0)

        g = sp.add_argument_group("loop / io")
        g.add_argument("--snapshot-path", default=None,
                       help="checkpoint directory (enables checkpointing)")
        g.add_argument("--checkpoint-every", type=int, default=1000)
        g.add_argument("--no-resume", action="store_true")
        # --resume-elastic / --auto-resume / --max-auto-resumes /
        # --inject-nan-step: preemption & recovery surface (ISSUE 11,
        # utils/cli.py — shared with scripts/chaos.py).
        add_durability_flags(g)
        g.add_argument("--eval-every", type=int, default=0)
        g.add_argument("--async-eval", action="store_true",
                       help="run the mid-training eval hook in a background "
                            "thread on a snapshotted param copy instead of "
                            "blocking the step stream (single-process only; "
                            "multi-host falls back to synchronous — "
                            "train/loop.py::_AsyncEvalRunner)")
        g.add_argument("--log-every", type=int, default=20)
        g.add_argument("--log-dir", default=None)
        g.add_argument("--tensorboard", action="store_true")
        g.add_argument("--profile-dir", default=None,
                       help="write a jax.profiler trace of a few steps here "
                            "(device operations with the step's named scopes, "
                            "and the loop's own spans as rn.* annotations; "
                            "RUNBOOK section 9)")
        # --obs-trace / --obs-dir / --obs-stall-timeout: structured trace
        # spans + stall watchdog across train/data/eval (utils/cli.py —
        # shared surface, obs/ subsystem).
        add_obs_flags(g)
        g.add_argument("--debug-nans", action="store_true",
                       help="numerical sanitizer (SURVEY.md 5.2): enable "
                            "jax_debug_nans so the originating op of a "
                            "NaN/Inf is reported; the loop independently "
                            "aborts on a non-finite loss either way")
        g.add_argument("--eval-only", action="store_true")
        g.add_argument("--score-threshold", type=float, default=0.05)
        g.add_argument("--nms-threshold", type=float, default=0.5)
        g.add_argument("--max-detections", type=int, default=300)
        g.add_argument("--weighted-average", action="store_true",
                       help="weight the VOC mAP by per-class annotation "
                            "counts (reference Evaluate flag; csv/pascal)")

        g = sp.add_argument_group("distributed")
        g.add_argument("--num-devices", type=int, default=1,
                       help="devices in the data mesh; 0 = all global devices")
        g.add_argument("--platform", default="auto",
                       choices=["auto", "cpu", "tpu"],
                       help="cpu: run the full SPMD path on a virtual CPU "
                            "mesh of --num-devices (CI / laptops, "
                            "SURVEY.md §7.3); auto: default backend")
        g.add_argument("--shard-weight-update", action="store_true",
                       help="ZeRO-style weight-update sharding: "
                            "reduce-scatter grads, 1/N optimizer state per "
                            "device, all_gather params (SURVEY.md §2.4)")
        # --comm-compress / --comm-overlap / --comm-bucket-mb /
        # --comm-no-error-feedback: the gradient-communication policy
        # surface (utils/cli.py).
        add_comm_flags(g)
        g.add_argument("--spatial-shards", type=int, default=1,
                       help="shard every image's H axis over this many "
                            "chips on a 2-D data x space mesh (GSPMD conv "
                            "halo exchanges — the sequence/context-parallel "
                            "analogue, SURVEY.md §5.7); must divide "
                            "--num-devices; exclusive with "
                            "--shard-weight-update/--comm-compress")
        g.add_argument("--allow-data-axis-divergence", action="store_true",
                       help="accept the measured gradient divergence of "
                            "deep-backbone spatial training on meshes "
                            "with a data axis >= 2 (round-5 finding; see "
                            "make_train_step_spatial's 'Data-axis "
                            "envelope' docstring)")
        g.add_argument("--distributed-auto", action="store_true",
                       help="jax.distributed.initialize() from TPU metadata")
        g.add_argument("--coordinator-address", default=None)
        g.add_argument("--num-processes", type=int, default=None)
        g.add_argument("--process-id", type=int, default=None)
    return p


def _add_lm_parser(sub) -> None:
    """``lm-synthetic``: its own, short flag surface (no image, anchor,
    eval or mesh flags apply)."""
    from batchai_retinanet_horovod_coco_tpu.models.language import BY_TYPE, PRESETS

    presets = ", ".join(PRESETS)
    lm = sub.add_parser(
        "lm-synthetic", allow_abbrev=False,
        help=f"train a language model ({', '.join(e[1] for e in BY_TYPE.values())}, "
             "by the config's model_type) on seeded packed token sequences "
             f"(single chip; --model {presets} on a CPU)",
    )
    g = lm.add_argument_group("model")
    g.add_argument("--model", default="tiny",
                   help=f"one of {presets} (the CPU tests' presets: each "
                        "its model module's TINY) "
                        "or a JSON file with the published config.json keys, "
                        f"whose model_type ({', '.join(BY_TYPE)}) picks the "
                        "model: the files of benchmark/configs/")
    g = lm.add_argument_group("data")
    g.add_argument("--seq-len", type=int, default=64,
                   help="tokens per packed sequence")
    g.add_argument("--batch-size", type=int, default=2,
                   help="sequences per step")
    g.add_argument("--doc-len-median", type=float, default=16.0,
                   help="median document length (log-normal, sigma 1.3, "
                        "clipped to [--doc-len-min, --seq-len]); the "
                        "benchmark's mix uses 512 at --seq-len 8192")
    g.add_argument("--doc-len-min", type=int, default=4)
    g.add_argument("--layout-seed", type=int, default=None,
                   help="draw the documents' lengths from a generator of "
                        "their own: the packing is then the same for every "
                        "--seed, which moves token ids and weights alone")
    g = lm.add_argument_group("optimization")
    g.add_argument("--steps", type=int, default=20)
    g.add_argument("--lr", type=float, default=3e-4,
                   help="constant; AdamW beta 0.9/0.95, decoupled decay "
                        "0.1 on matrices only, clip at global norm 1.0")
    g.add_argument("--seed", type=int, default=0)
    g = lm.add_argument_group("loop / io")
    g.add_argument("--snapshot-path", default=None,
                   help="checkpoint directory (enables checkpointing)")
    g.add_argument("--checkpoint-every", type=int, default=1000)
    g.add_argument("--no-resume", action="store_true")
    g.add_argument("--log-every", type=int, default=4)
    g.add_argument("--log-dir", default=None)
    g.add_argument("--profile-dir", default=None,
                   help="write a jax.profiler trace of a few steps here")
    add_obs_flags(g)
    g = lm.add_argument_group("distributed")
    g.add_argument("--num-devices", type=int, default=1,
                   help="must be 1: the LM task has no sharding yet")
    g.add_argument("--platform", default="auto", choices=["auto", "cpu", "tpu"])


def parse_args(argv=None):
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.preset:
        explicit = {
            a[2:].replace("-", "_").split("=")[0]
            for a in (argv if argv is not None else sys.argv[1:])
            if a.startswith("--")
        }
        for k, v in PRESETS[args.preset].items():
            if k not in explicit and hasattr(args, k):
                setattr(args, k, v)
    return args


def make_datasets(args):
    from batchai_retinanet_horovod_coco_tpu.data import (
        CocoDataset,
        CsvDataset,
        make_synthetic_coco,
    )

    if args.dataset_type == "csv":
        # keep_empty: explicit 'path,,,,,' rows are intentional negative
        # (background-only) images; the reference CSVGenerator trains on them.
        train = CsvDataset(
            args.csv_annotations, args.csv_classes, image_dir=args.image_dir,
            keep_empty=True,
        )
        val = None
        if args.val_csv_annotations:
            val = CsvDataset(
                args.val_csv_annotations, args.csv_classes,
                image_dir=args.image_dir, keep_empty=True,
            )
        return train, val

    if args.dataset_type == "pascal":
        from batchai_retinanet_horovod_coco_tpu.data import PascalVocDataset

        # keep_empty: the reference PascalVocGenerator keeps every id in the
        # split file, background-only and difficult-only images included.
        train = PascalVocDataset(
            args.pascal_path, split=args.train_split,
            skip_difficult=args.skip_difficult, keep_empty=True,
        )
        val = PascalVocDataset(
            args.pascal_path, split=args.val_split,
            skip_difficult=args.skip_difficult, keep_empty=True,
        )
        return train, val

    if args.dataset_type == "synthetic":
        raw = str(args.synthetic_size)
        if "x" in raw:
            h, w = raw.split("x", 1)
            size = (int(h), int(w))
        else:
            size = (int(raw), int(raw))
        train_ann = make_synthetic_coco(
            args.synthetic_root, num_images=args.synthetic_images,
            num_classes=args.synthetic_classes, image_size=size,
            seed=args.seed, split="train",
        )
        val_ann = make_synthetic_coco(
            args.synthetic_root, num_images=max(8, args.synthetic_images // 4),
            num_classes=args.synthetic_classes, image_size=size,
            seed=args.seed + 1, split="val",
        )
        train = CocoDataset(train_ann, os.path.join(args.synthetic_root, "train"))
        val = CocoDataset(
            val_ann, os.path.join(args.synthetic_root, "val"), keep_empty=True
        )
        return train, val

    root = args.coco_path
    train = CocoDataset(
        os.path.join(root, args.train_annotations),
        os.path.join(root, args.train_images),
    )
    val = CocoDataset(
        os.path.join(root, args.val_annotations),
        os.path.join(root, args.val_images),
        keep_empty=True,
    )
    return train, val


def main(argv=None) -> dict[str, float]:
    args = parse_args(argv)
    # Observability bring-up precedes everything that spawns threads or
    # worker processes: the shm decode workers inherit the trace env
    # contract at spawn, so tracing must be configured before any
    # pipeline is built.  The finalize runs even when the run dies — the
    # partial trace (+ the watchdog's stall dump) IS the post-mortem.
    obs_dir = configure_obs(args, process_label="train")
    run = _run_lm if args.dataset_type == "lm-synthetic" else _run
    if obs_dir is None:
        return run(args)
    if not args.log_dir:
        # The perf doctor (obs/analyze) reads the run's events JSONL next
        # to its trace: an obs-enabled run without an explicit --log-dir
        # logs into the obs dir so the report never lacks its events half.
        args.log_dir = obs_dir
    try:
        return run(args)
    finally:
        from batchai_retinanet_horovod_coco_tpu import obs

        merged = obs.finalize()
        if merged:
            print(f"obs: merged Chrome trace at {merged} "
                  "(load in Perfetto / chrome://tracing)", flush=True)
            # Auto-emit PERF_REPORT.json next to the trace.  Analysis can
            # never crash the run: auto_emit swallows its own failures
            # into ONE structured perf_report_error line, and the import
            # is guarded for the same reason.
            try:
                from batchai_retinanet_horovod_coco_tpu.obs.analyze import (
                    auto_emit,
                )

                report = auto_emit(obs_dir)
            except Exception as e:  # never mask the run's own outcome
                import json as _json

                print(
                    _json.dumps(
                        {"event": "perf_report_error", "error": repr(e)[:500]}
                    ),
                    file=sys.stderr,
                    flush=True,
                )
                report = None
            if report:
                print(
                    f"obs: perf report at {report} (reproduce offline: "
                    "python -m batchai_retinanet_horovod_coco_tpu.obs."
                    f"analyze {obs_dir})",
                    flush=True,
                )


def _start_telemetry(args, logger):
    """Live-telemetry bring-up (ISSUE 9): the --obs-port status server
    (GET /metrics /healthz /statusz over the process-default registry the
    train loop feeds) and the SLO monitor (--slo-rule + the built-in
    watchdog-stall rule), violations sinking into the run's metrics
    JSONL.  Returns (status_server | None, slo_monitor | None); the
    caller owns the bounded, idempotent teardown — both are daemon-
    threaded and can never wedge a pod exit."""
    port = getattr(args, "obs_port", None)
    rule_specs = getattr(args, "slo_rule", None) or []
    if port is None and not rule_specs:
        return None, None
    from batchai_retinanet_horovod_coco_tpu.obs import slo, telemetry

    telemetry.enable()  # arm the loop's push record sites (one bool)
    server = None
    if port is not None:
        server = telemetry.start_http_server(telemetry.default(), port=port)
        print(
            f"obs: telemetry on http://{server.host}:{server.port} "
            "(/metrics /healthz /statusz)",
            flush=True,
        )
    monitor = slo.SloMonitor(
        telemetry.default(),
        # Built-ins first: the watchdog-stall rule, the immediate
        # nonfinite rule (ISSUE 10 — fed by the loop's abort path and
        # the in-step numerics summary), and the grad-norm-spike
        # regression rule (rolling-median baseline; silent until the
        # train_grad_norm gauge exists, so serve/eval runs are
        # unaffected).  User --slo-rule specs append after.
        [slo.stall_rule(), slo.nonfinite_rule(), slo.grad_norm_spike(),
         # Checkpoint staleness (ISSUE 11): silent until two saves have
         # landed (the age/interval gauge needs a measured cadence), so
         # un-checkpointed runs never see it evaluate.
         slo.ckpt_staleness_rule(),
         # Gradient-compression EF health (ISSUE 13): always armed —
         # silent until the train_ef_residual gauge exists, i.e. on
         # every run without --comm-compress.
         slo.ef_residual_spike(),
         # Per-hop variant (ISSUE 16): the DCN hop is the only one
         # that quantizes under a hierarchical topology; silent until
         # the train_ef_residual_dcn gauge exists (flat runs never
         # create it).
         slo.ef_residual_spike(hop="dcn")]
        + [slo.parse_rule(s) for s in rule_specs],
        sink=logger,
        poll_interval=getattr(args, "slo_poll_s", 5.0),
    ).start()
    return server, monitor


def _elastic_skip_batches(args) -> dict:
    """--resume-elastic: the stream plan that continues exactly where the
    checkpointed run stopped — ``{"skip", "data_seed", "exclude_ids"}``.

    The loop consumes ONE batch per process per step at every world size
    (the global batch is split over processes), so the position within a
    stream is ``step - stream_base_step`` (base 0 for a virgin run; an
    --auto-resume heal RESTARTS the stream at its restore step with a new
    seed and exclusions, and records all three in the manifest so this
    derivation survives the heal).  The global batch size must match the
    manifest (validated; a change makes the position meaningless, so it
    aborts loudly), and so must --seed for a virgin stream; for a healed
    stream the manifest's effective seed/exclusions WIN — they are the
    order that was actually consumed.  At the same world size the
    continuation is sample-exact (chaos-pinned bit-identical losses);
    across a world-size change the per-shard record partition differs, so
    it is position-exact and distribution-equivalent (PARITY.md).
    """
    from batchai_retinanet_horovod_coco_tpu.utils.checkpoint import (
        read_manifest,
    )

    plan = {
        "skip": 0,
        "data_seed": int(args.seed),
        "exclude_ids": (),
        "stream_base_step": 0,
    }
    manifest = read_manifest(args.snapshot_path)
    if manifest is None:
        return plan
    meta = manifest.get("metadata") or {}
    base = int(meta.get("stream_base_step") or 0)
    saved_gb = meta.get("global_batch_size")
    if saved_gb is not None and int(saved_gb) != int(args.batch_size):
        raise SystemExit(
            f"--resume-elastic: global_batch_size changed since the "
            f"checkpoint was written ({saved_gb} -> {args.batch_size}); "
            "the stream position is only re-derivable at the batch size "
            "the manifest recorded.  Re-run with the original value, or "
            "drop --resume-elastic to resume with a restarted stream."
        )
    saved_seed = meta.get("data_seed")
    if base == 0 and saved_seed is not None and int(saved_seed) != int(
        args.seed
    ):
        raise SystemExit(
            f"--resume-elastic: data_seed changed since the checkpoint "
            f"was written ({saved_seed} -> {args.seed}); the stream "
            "position is only re-derivable with the data order the "
            "manifest recorded.  Re-run with the original value, or drop "
            "--resume-elastic to resume with a restarted stream."
        )
    if saved_seed is not None:
        plan["data_seed"] = int(saved_seed)  # healed stream: manifest wins
    plan["exclude_ids"] = tuple(
        int(i) for i in (meta.get("exclude_ids") or [])
    )
    plan["stream_base_step"] = base
    plan["skip"] = max(0, int(manifest.get("step") or 0) - base)
    if plan["skip"] or base:
        print(
            json.dumps(
                {
                    "event": "elastic_resume",
                    "restored_step": int(manifest.get("step") or 0),
                    "skip_batches_per_process": plan["skip"],
                    "stream_base_step": base,
                    "data_seed": plan["data_seed"],
                    "excluded": len(plan["exclude_ids"]),
                    "saved_world": meta.get("shard_count"),
                    "zero_world_size": manifest.get("zero_world_size"),
                }
            ),
            flush=True,
        )
    return plan


def _read_poison_ids(dump_dir: str | None) -> list[int]:
    """The tripped batch's source image ids from NUMERICS_DUMP.json (the
    numerics abort wrote it just before raising); [] when unavailable."""
    if not dump_dir:
        return []
    path = os.path.join(dump_dir, "NUMERICS_DUMP.json")
    try:
        with open(path) as f:
            dump = json.load(f)
    except (OSError, json.JSONDecodeError):
        return []
    try:
        return [int(i) for i in (dump.get("batch_image_ids") or [])]
    except (TypeError, ValueError):
        return []


def _auto_resume_plan(args, attempt: int, exc: BaseException) -> dict | None:
    """Decide whether a numerics abort is self-healable (--auto-resume)
    and with what; None = re-raise.  Requires a restorable checkpoint
    (guaranteed finite by the loop's pre-save gate) and a remaining
    attempt budget; the plan reseeds the data order and carries the
    poison batch's image ids for exclusion."""
    if not getattr(args, "auto_resume", False):
        return None
    if attempt > getattr(args, "max_auto_resumes", 3):
        return None
    if not args.snapshot_path or args.no_resume:
        return None
    from batchai_retinanet_horovod_coco_tpu.utils.checkpoint import (
        latest_step as ckpt_latest_step,
    )

    restored = ckpt_latest_step(args.snapshot_path)
    if restored is None:
        return None  # nothing healthy on disk — the abort stands
    dump_dir = getattr(args, "obs_dir", None) or args.log_dir
    return {
        "attempt": attempt,
        "restored_step": int(restored),
        # A deterministic reseed: the new (seed, epoch) permutation makes
        # the post-resume order disjoint from the aborted one, and the
        # exclusion below guarantees the poison batch cannot recur even
        # if an image repeats.
        "data_seed": int(args.seed) + 7919 * attempt,
        "exclude_ids": _read_poison_ids(dump_dir),
        "error": str(exc)[:300],
    }


class _NanInjector:
    """--inject-nan-step fault hook (scripts/chaos.py): poison the N-th
    consumed batch, exactly once per PROCESS — ``latch`` is shared across
    auto-resume attempts so the fault cannot re-fire on the healed
    stream.  The NaN goes into the IMAGE tensor (the uint8 production
    batch is lifted to float32 first — normalize_images passes float
    through — because uint8 cannot carry a NaN, and poisoning gt boxes
    does NOT trip the sanitizer: NaN IoU comparisons are all False, so
    matching classifies the poisoned anchors as 'ignore' and the NaN
    never reaches the loss)."""

    def __init__(self, inner, at_batch: int, latch: dict):
        self._inner = inner
        self._at = int(at_batch)
        self._latch = latch
        self._count = 0

    @property
    def stats(self):
        return getattr(self._inner, "stats", None)

    def __iter__(self):
        return self

    def __next__(self):
        batch = next(self._inner)
        self._count += 1
        if not self._latch["done"] and self._count == self._at:
            self._latch["done"] = True
            images = batch.images.astype(np.float32, copy=True)
            images[0, 0, 0, 0] = np.nan
            batch = batch._replace(images=images)
            print(
                json.dumps(
                    {
                        "event": "chaos_nan_injected",
                        "batch": self._count,
                        "image_ids": [int(i) for i in batch.image_ids],
                    }
                ),
                file=sys.stderr, flush=True,
            )
        return batch


def _run_lm(args) -> dict[str, float]:
    """``lm-synthetic``: model, optimizer, state and the packed source, then
    the same ``run_training`` as detection, with the LM task."""
    if args.num_devices != 1:
        raise SystemExit(
            "lm-synthetic trains on one chip: the LM task has no sharding "
            "yet (train/task.py); drop --num-devices"
        )
    if args.platform != "auto":
        jax.config.update("jax_platforms", args.platform)

    from batchai_retinanet_horovod_coco_tpu.data.tokens import (
        PackedTokensConfig,
        packed_token_batches,
    )
    from batchai_retinanet_horovod_coco_tpu.models.language import build_language_model
    from batchai_retinanet_horovod_coco_tpu.train import create_train_state
    from batchai_retinanet_horovod_coco_tpu.train.loop import LoopConfig, run_training
    from batchai_retinanet_horovod_coco_tpu.train.optim import (
        OptimizerConfig,
        make_optimizer,
    )
    from batchai_retinanet_horovod_coco_tpu.train.task import LMTask
    from batchai_retinanet_horovod_coco_tpu.utils.backend import (
        announce_devices,
        enable_compile_cache,
    )
    from batchai_retinanet_horovod_coco_tpu.obs.events import EventSink

    enable_compile_cache()
    announce_devices("train")
    model, task = build_language_model(args.model), LMTask()
    config = model.config
    tx, schedule = make_optimizer(OptimizerConfig(
        optimizer="adamw", schedule="constant", warmup_steps=0,
        base_lr=args.lr, world_size=1, adam_b2=0.95,
        weight_decay=0.1, clip_global_norm=1.0,
    ))
    state = jax.jit(
        lambda key: create_train_state(
            model, tx, (1, 8), key, example_dtype=task.example_dtype
        )
    )(jax.random.key(args.seed))
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    print(
        f"lm-synthetic: {model.describe()}, d={config.hidden_size}, "
        f"vocabulary {config.vocab_size}, {n_params / 1e6:.1f} M parameters; "
        f"{args.batch_size} x {args.seq_len} tokens per step",
        flush=True,
    )
    batches = packed_token_batches(PackedTokensConfig(
        vocab_size=config.vocab_size, seq_len=args.seq_len,
        batch_size=args.batch_size, doc_len_median=args.doc_len_median,
        doc_len_min=args.doc_len_min, seed=args.seed,
        layout_seed=args.layout_seed,
    ))
    logger = EventSink(log_dir=args.log_dir)
    telem_server, slo_monitor = _start_telemetry(args, logger)
    try:
        state = run_training(
            model, state, batches, None,
            LoopConfig(
                total_steps=args.steps,
                log_every=args.log_every,
                checkpoint_every=(
                    args.checkpoint_every if args.snapshot_path else 0
                ),
                checkpoint_dir=args.snapshot_path,
                resume=not args.no_resume,
                profile_dir=args.profile_dir,
                numerics=args.numerics,
                numerics_dump_dir=args.obs_dir or args.log_dir or None,
                rng_seed=args.seed,
                ckpt_metadata={
                    "global_batch_size": args.batch_size,
                    "data_seed": args.seed,
                },
            ),
            schedule=schedule, logger=logger, task=task,
        )
        return {"final_step": float(int(state.step))}
    finally:
        batches.close()
        if slo_monitor is not None:
            slo_monitor.stop()
        if telem_server is not None:
            telem_server.close()


def _run(args) -> dict[str, float]:
    if args.platform != "auto":
        # Must land before any backend initialization.  The CPU path also
        # forces enough virtual host devices for the requested mesh
        # (xla_force_host_platform_device_count is read at backend init).
        if args.platform == "cpu" and args.num_devices > 1:
            flags = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    f"{flags} --xla_force_host_platform_device_count="
                    f"{args.num_devices}"
                ).strip()
        jax.config.update("jax_platforms", args.platform)

    if args.debug_nans:
        # SURVEY.md §5.2 numerical sanitizer: every jit result is checked
        # and the failing op re-run un-jitted for a precise report.
        jax.config.update("jax_debug_nans", True)

    from batchai_retinanet_horovod_coco_tpu.data import (
        PipelineConfig,
        build_pipeline,
        resolve_max_gt,
    )
    from batchai_retinanet_horovod_coco_tpu.evaluate.detect import (
        DetectConfig,
        run_coco_eval,
    )
    from batchai_retinanet_horovod_coco_tpu.launch import (
        DistributedConfig,
        initialize_distributed,
        shard_info,
    )
    from batchai_retinanet_horovod_coco_tpu.models import (
        RetinaNetConfig,
        build_retinanet,
    )
    from batchai_retinanet_horovod_coco_tpu.parallel import (
        derive_topology,
        make_mesh,
    )
    from batchai_retinanet_horovod_coco_tpu.train import create_train_state
    from batchai_retinanet_horovod_coco_tpu.train.loop import LoopConfig, run_training
    from batchai_retinanet_horovod_coco_tpu.train.optim import (
        OptimizerConfig,
        make_optimizer,
    )
    from batchai_retinanet_horovod_coco_tpu.obs.events import EventSink

    initialize_distributed(
        DistributedConfig(
            auto=args.distributed_auto,
            coordinator_address=args.coordinator_address,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )
    )
    # After the distributed bring-up (jax.distributed.initialize must
    # precede the first backend touch), before anything is built.
    from batchai_retinanet_horovod_coco_tpu.utils.backend import (
        announce_devices,
        enable_compile_cache,
    )

    enable_compile_cache()
    announce_devices("train")
    num_devices = args.num_devices or len(jax.devices())
    spatial_shards = int(getattr(args, "spatial_shards", 1) or 1)
    if spatial_shards > 1:
        if num_devices % spatial_shards:
            raise SystemExit(
                f"--spatial-shards {spatial_shards} must divide "
                f"--num-devices {num_devices}"
            )
        if (
            getattr(args, "shard_weight_update", False)
            or getattr(args, "comm_compress", "none") != "none"
            or getattr(args, "comm_overlap", False)
        ):
            raise SystemExit(
                "--spatial-shards is exclusive with --shard-weight-update "
                "and --comm-compress/--comm-overlap"
            )
        if not args.f32:
            # The SPMD partitioner miscompiles the bf16 spatial train step
            # at flagship width (wrong cls_loss, 14-60x wrong grads;
            # train/step.py::make_train_step_spatial docstring + the bf16
            # spatial canary test).  Refuse loudly rather than train on
            # silently corrupted gradients.
            raise SystemExit(
                "--spatial-shards requires --f32: bf16 spatial train "
                "steps are miscompiled by XLA's SPMD partitioner "
                "(validated on the CPU mesh rig; TPU unvalidated — see "
                "make_train_step_spatial's docstring)"
            )
        # Fail fast on the strided-conv sharding envelope for EVERY bucket
        # this run will compile, instead of letting make_train_step_spatial
        # raise mid-training when the offending bucket first arrives.
        # (default_buckets is the module-level import — a function-local
        # re-import here would shadow it for the whole function and break
        # every non-spatial run with UnboundLocalError.)
        from batchai_retinanet_horovod_coco_tpu.train.step import (
            _degenerate_strided_conv_heights,
        )

        bad = {
            f"{h}x{w}": _degenerate_strided_conv_heights(h, spatial_shards)
            for h, w in default_buckets(
                args.image_min_side, args.image_max_side
            )
            if _degenerate_strided_conv_heights(h, spatial_shards)
        }
        if bad:
            raise SystemExit(
                f"--spatial-shards {spatial_shards} puts bucket(s) "
                f"{sorted(bad)} inside the XLA strided-conv weight-grad "
                "bug envelope (conv input heights "
                f"{sorted(set(sum(bad.values(), [])))} at ~[0.5, 2) rows "
                "per shard; see make_train_step_spatial).  Use "
                "--spatial-shards 4 or fewer, which is always outside "
                "the envelope"
            )
        if (
            jax.process_count() > 1
            and len(jax.local_devices()) % spatial_shards
        ):
            # The space axis must stay within one host: the per-process
            # batch assembly hands each process its own full-H images, so a
            # space row straddling hosts would silently stitch H-slices of
            # DIFFERENT hosts' images into one "global" image.
            raise SystemExit(
                f"--spatial-shards {spatial_shards} must divide the "
                f"per-host device count {len(jax.local_devices())} on "
                "multi-host runs (the space axis cannot span hosts)"
            )
        from batchai_retinanet_horovod_coco_tpu.parallel.mesh import (
            make_mesh_2d,
        )
        from batchai_retinanet_horovod_coco_tpu.train.step import (
            _SPATIAL_GRAD_VALIDATED_BACKBONES,
            _data_axis_risky_stage_heights,
        )

        data_size = num_devices // spatial_shards
        risky_buckets = {
            f"{h}x{w}": _data_axis_risky_stage_heights(h, spatial_shards)
            for h, w in default_buckets(
                args.image_min_side, args.image_max_side
            )
            if _data_axis_risky_stage_heights(h, spatial_shards)
        }
        if (
            data_size > 1
            and risky_buckets
            and args.backbone not in _SPATIAL_GRAD_VALIDATED_BACKBONES
            and not args.allow_data_axis_divergence
        ):
            # Round-5 finding: deep-backbone spatial training on meshes
            # with data >= 2 computes measurably wrong gradients when a
            # backbone stage lands at <= 1 row per shard (f64-
            # persistent, ~3x worse per data doubling).  Fail fast here
            # with the same policy make_train_step_spatial enforces.
            raise SystemExit(
                f"--spatial-shards {spatial_shards} on {num_devices} "
                f"devices gives a (data={data_size}, space="
                f"{spatial_shards}) mesh, and bucket(s) "
                f"{sorted(risky_buckets)} put backbone-stage maps at "
                "<= 1 row per shard, where deep-backbone spatial "
                "training with a data axis >= 2 computes measurably "
                "divergent gradients (see make_train_step_spatial's "
                "'Data-axis envelope').  Use --num-devices == "
                "--spatial-shards for the pure-spatial mode, larger "
                "--image-min/max-side, plain DP, or pass "
                "--allow-data-axis-divergence to accept the measured "
                "error"
            )
        mesh = make_mesh_2d(data_size, spatial_shards)
        comm_topology = None  # spatial mesh: no hierarchical comm path
    else:
        data_size = num_devices
        # Two-level comm topology (ISSUE 16): --comm-slices / the env
        # override / real per-device slice indices resolve to slice ×
        # intra-slice grouping; None on flat (single-slice) machines.
        # Derived BEFORE the mesh so device order interleaves slices
        # (mesh position d on slice d % S) — the invariant that keeps
        # hierarchical EF residuals in global bucket order for
        # checkpoint resharding.
        comm_topology = (
            derive_topology(num_devices, getattr(args, "comm_slices", None))
            if num_devices > 1
            else None
        )
        mesh = (
            make_mesh(num_devices, topology=comm_topology)
            if num_devices > 1
            else None
        )
    if args.batch_size % data_size:
        raise SystemExit(
            f"--batch-size {args.batch_size} not divisible by the data-mesh "
            f"size {data_size}"
        )

    train_ds, val_ds = make_datasets(args)
    num_classes = train_ds.num_classes
    # Auto-size gt padding to the data (silent truncation poisons targets);
    # an explicit --max-gt is honored and the pipeline counts what it drops.
    args.max_gt = resolve_max_gt(
        args.max_gt, *(ds for ds in (train_ds, val_ds) if ds is not None)
    )
    if val_ds is None and (args.eval_only or args.eval_every):
        raise SystemExit(
            "no validation set: pass --val-csv-annotations to evaluate"
        )

    # Flags + the config persisted beside the checkpoint (conflict = abort);
    # persist on fresh training so eval/export/resume never need the flags.
    anchor_config = resolve_anchor_config(
        args, args.snapshot_path, fresh=args.no_resume
    )
    if args.snapshot_path and not args.eval_only and jax.process_index() == 0:
        save_anchor_config(args.snapshot_path, anchor_config)
    model = build_retinanet(
        RetinaNetConfig(
            num_classes=num_classes,
            backbone=args.backbone,
            norm_kind=args.norm,
            stem=args.stem,
            pack_width=getattr(args, "pack_width", False),
            anchor=anchor_config,
            dtype=jnp.float32 if args.f32 else jnp.bfloat16,
        )
    )
    opt_config = OptimizerConfig(
        optimizer=args.optimizer,
        base_lr=args.lr,
        global_batch_size=args.batch_size,
        world_size=jax.process_count(),
        warmup_steps=args.warmup_steps,
        total_steps=args.steps,
        schedule=args.schedule,
        plateau_factor=args.plateau_factor,
        plateau_patience=args.plateau_patience,
        plateau_window=args.plateau_window,
        plateau_min_delta=args.plateau_min_delta,
        weight_decay=args.weight_decay,
        freeze_backbone=args.freeze_backbone,
    )
    shard_update = bool(getattr(args, "shard_weight_update", False))
    if shard_update and num_devices <= 1:
        raise SystemExit("--shard-weight-update needs --num-devices > 1")
    # Gradient-communication policy: the flags resolve to ONE CommConfig;
    # it composes with --shard-weight-update (compressed ZeRO update
    # gather).
    comm_cfg = make_comm_config(args)
    if comm_cfg is not None and num_devices <= 1:
        raise SystemExit(
            "--comm-compress/--comm-overlap need --num-devices > 1 "
            "(compression rides the mesh collectives)"
        )
    if comm_cfg is not None and comm_cfg.overlap and shard_update:
        # ZeRO compresses the POST-update gather; there is no backward
        # gradient collective for overlap to restage.  One structured
        # line, then drop the flag (never a silent no-op).
        print(
            json.dumps({
                "event": "comm_overlap_ignored",
                "reason": (
                    "--comm-overlap is a DP-path mechanism; "
                    "--shard-weight-update compresses the post-update "
                    "gather instead"
                ),
            }),
            file=sys.stderr, flush=True,
        )
        comm_cfg = dataclasses.replace(comm_cfg, overlap=False)
    # Sharded-update mode swaps in the cross-shard global-norm clip — same
    # chain position, same clip value, one source of truth (parallel/zero.py).
    from batchai_retinanet_horovod_coco_tpu.parallel.mesh import DATA_AXIS

    tx, schedule = make_optimizer(
        opt_config, shard_clip_axis=DATA_AXIS if shard_update else None
    )
    buckets = default_buckets(args.image_min_side, args.image_max_side)
    init_hw = buckets[0]

    def build_state():
        """Fresh TrainState from the run's flags — called once at startup
        and again per --auto-resume attempt (the poisoned state was
        donated into the aborted step; the loop's resume then restores
        the last healthy checkpoint into this template)."""
        state = create_train_state(
            model, tx, (1, *init_hw, 3), jax.random.key(args.seed),
            init_opt_state=not shard_update,
        )
        if shard_update:
            from batchai_retinanet_horovod_coco_tpu.parallel import (
                init_sharded_opt_state,
                replicated_sharding,
            )

            # Replicate params over the GLOBAL mesh first: on multi-host
            # runs they come out of init committed to the local default
            # device, which a shard_map over a cross-process mesh cannot
            # reshard implicitly.
            params = jax.device_put(state.params, replicated_sharding(mesh))
            state = state.replace(
                params=params,
                opt_state=init_sharded_opt_state(tx, params, mesh),
            )
        if comm_cfg is not None and comm_cfg.needs_state and mesh is not None:
            # Zeroed EF residuals in the layout the step expects (per
            # bucket for DP, per leaf for ZeRO); host numpy — the loop's
            # replication block places them data-axis-sharded, and a
            # checkpoint restore reshards into these shapes.
            from batchai_retinanet_horovod_coco_tpu.comm import (
                init_comm_state,
            )

            state = state.replace(
                comm_state=init_comm_state(
                    state.params, comm_cfg, mesh.size, zero=shard_update,
                    topology=comm_topology,
                )
            )
        if args.pretrained_backbone:
            from batchai_retinanet_horovod_coco_tpu.models.import_weights import (
                apply_backbone_weights,
                convert_torch_resnet50,
                load_state_dict,
            )

            imp_params, imp_stats = convert_torch_resnet50(
                load_state_dict(args.pretrained_backbone)
            )
            new_params, new_stats = apply_backbone_weights(
                state.params, state.batch_stats, imp_params, imp_stats
            )
            state = state.replace(params=new_params, batch_stats=new_stats)
            print(f"imported backbone weights from {args.pretrained_backbone}")
        return state

    state = build_state()

    shard_index, shard_count = shard_info()
    if args.batch_size % shard_count:
        raise SystemExit(
            f"--batch-size {args.batch_size} not divisible by "
            f"{shard_count} host processes"
        )
    local_batch = args.batch_size // shard_count
    pipe_common = dict(
        buckets=buckets,
        min_side=args.image_min_side,
        max_side=args.image_max_side,
        max_gt=args.max_gt,
        seed=args.seed,
        # --workers / --data-worker-procs / --data-worker-timeout: the
        # multiprocess shared-memory producer when procs > 0 (RUNBOOK.md
        # "Feeding the chips"), the thread pool otherwise.
        **make_pipeline_worker_kwargs(args),
    )
    train_transform = None
    if getattr(args, "random_transform", False):
        from batchai_retinanet_horovod_coco_tpu.data import TransformConfig

        train_transform = TransformConfig()
    detect_config = DetectConfig(
        score_threshold=args.score_threshold,
        iou_threshold=args.nms_threshold,
        max_detections=args.max_detections,
        anchor=anchor_config,
    )

    def eval_fn(eval_state) -> dict[str, float]:
        # Val work is SHARDED across processes: each host decodes its slice
        # of the val set and detects on its LOCAL devices; the detections
        # all-gather before scoring (evaluate/detect.py).  The reference ran
        # the whole eval on rank 0 (SURVEY.md M10) — at pod scale that is
        # hosts× redundant decode; here host work scales 1/process_count.
        # Only process 0 logs the (identical, post-gather) metrics.
        if shard_count > 1:
            from batchai_retinanet_horovod_coco_tpu.parallel.mesh import (
                make_local_mesh,
            )

            eval_mesh = make_local_mesh()
            eval_batch = max(
                len(jax.local_devices()),
                args.batch_size // shard_count,
            )
            # The training state is replicated over the GLOBAL mesh; a
            # local-mesh program cannot consume it directly.  Replicated →
            # every shard is addressable → one host copy suffices; re-upload
            # it ONCE onto the local mesh (process-local put) so the detect
            # fn is not fed numpy — that would re-transfer ~450 MB of
            # params+optimizer state per eval batch.
            from batchai_retinanet_horovod_coco_tpu.parallel.mesh import (
                replicated_sharding,
            )

            # Detection needs only params/batch_stats/step.  Drop opt_state
            # BEFORE the host round-trip: (a) under --shard-weight-update the
            # optimizer slots are sharded P(DATA_AXIS) over the global mesh,
            # so their shards are non-addressable from one host and
            # device_get would raise; (b) even replicated, it halves the
            # per-eval host<->device traffic (optimizer slots ~= params).
            # comm_state (EF residuals) drops with it: detection never
            # reads it, and under compression its leaves are data-axis-
            # sharded over the GLOBAL mesh (non-addressable cross-host).
            eval_state = eval_state.replace(opt_state=(), comm_state=())
            eval_state = jax.device_put(
                jax.device_get(eval_state), replicated_sharding(eval_mesh)
            )
        else:
            eval_mesh = mesh
            eval_batch = args.batch_size
            from batchai_retinanet_horovod_coco_tpu.parallel.mesh import (
                SPACE_AXIS,
            )

            if mesh is not None and SPACE_AXIS in mesh.axis_names:
                # Eval is batch-parallel: flatten the 2-D train mesh so the
                # space-axis chips do real work instead of replaying the
                # data rows' detection pass (detect shards over `data`
                # only).  Round the eval batch up to the flat mesh size.
                from jax.sharding import Mesh as _Mesh

                from batchai_retinanet_horovod_coco_tpu.parallel.mesh import (
                    DATA_AXIS,
                    replicated_sharding,
                )

                eval_mesh = _Mesh(
                    mesh.devices.reshape(-1), axis_names=(DATA_AXIS,)
                )
                n = eval_mesh.size
                eval_batch = ((args.batch_size + n - 1) // n) * n
                eval_state = eval_state.replace(opt_state=(), comm_state=())
                eval_state = jax.device_put(
                    eval_state, replicated_sharding(eval_mesh)
                )
        val_batches = build_pipeline(
            val_ds,
            PipelineConfig(
                batch_size=eval_batch, shuffle=False, hflip_prob=0.0,
                shard_index=shard_index, shard_count=shard_count,
                **pipe_common,
            ),
            train=False,
        )
        return run_coco_eval(
            eval_state, model, val_ds, val_batches, detect_config,
            mesh=eval_mesh,
            # CSV/Pascal datasets additionally report the reference's
            # Evaluate-callback metric (VOC AP@0.5 per class) from the same
            # detection pass.
            voc_metrics=args.dataset_type in ("csv", "pascal"),
            voc_weighted_average=args.weighted_average,
        )

    # run_config feeds the JSONL run-header's config digest: two runs in
    # one log dir are the same experiment iff their digests match.
    logger = EventSink(
        args.log_dir, tensorboard=args.tensorboard, run_config=vars(args)
    )
    if getattr(args, "obs_trace", False) or getattr(args, "obs_dir", None):
        # The sink outlives every watchdog poll (closed only at process
        # end), so stall diagnoses land in metrics.jsonl next to the
        # metrics they interrupt — configure_obs ran before the logger
        # existed, so the attachment happens here.
        from batchai_retinanet_horovod_coco_tpu.obs import watchdog

        watchdog.default().sink = logger

    # Live telemetry around the run (status server + SLO monitor); the
    # teardown is bounded and idempotent, so a traced run's obs finalize
    # (main()'s finally) always runs after a clean telemetry drain.
    telem_server, slo_monitor = _start_telemetry(args, logger)
    try:
        if args.eval_only:
            if args.snapshot_path:
                from batchai_retinanet_horovod_coco_tpu.utils.checkpoint import (
                    CheckpointManager,
                )

                state = CheckpointManager(args.snapshot_path).restore(state)
                if mesh is None:
                    # Restore returns HOST numpy; put once so the detect
                    # programs don't re-transfer params on every dispatch
                    # (read-only use — no donation — so a plain put is
                    # safe here, unlike the training path).
                    state = jax.device_put(state)
            if mesh is not None and shard_count == 1:
                # Multi-host skips this: restored arrays are committed to
                # local devices (cross-host device_put is unsupported on
                # some backends) and the sharded eval_fn pulls state to
                # host anyway.
                from batchai_retinanet_horovod_coco_tpu.parallel.mesh import (
                    replicated_sharding,
                )

                state = jax.device_put(state, replicated_sharding(mesh))
            metrics = eval_fn(state)
            logger.log(int(state.step), metrics, prefix="eval")
            return metrics

        # Durability surface (ISSUE 11).  The manifest records the
        # data-order facts; --resume-elastic re-derives the stream
        # position (consumed batches per process == restored step, at any
        # world size — the global batch is validated unchanged).
        numerics_dump_dir = (
            getattr(args, "obs_dir", None) or args.log_dir or None
        )
        # MUTATED in place on --auto-resume: run_training builds a fresh
        # CheckpointManager (which copies this dict) per attempt, so
        # post-heal checkpoints record the EFFECTIVE stream facts — seed,
        # exclusions, and the step the reseeded stream restarted at —
        # which _elastic_skip_batches trusts over the CLI flags.
        ckpt_metadata = {
            "global_batch_size": args.batch_size,
            "data_seed": args.seed,
            "shard_count": shard_count,
            "stream_base_step": 0,
            "exclude_ids": [],
        }
        skip_batches = 0
        data_seed = args.seed
        exclude_ids: tuple[int, ...] = ()
        if (
            getattr(args, "resume_elastic", False)
            and args.snapshot_path
            and not args.no_resume
        ):
            stream_plan = _elastic_skip_batches(args)
            skip_batches = stream_plan["skip"]
            data_seed = stream_plan["data_seed"]
            exclude_ids = stream_plan["exclude_ids"]
            # The continuing run is the SAME stream: its checkpoints
            # keep the stream identity (incl. a healed stream's base).
            ckpt_metadata.update(
                data_seed=data_seed,
                exclude_ids=list(exclude_ids),
                stream_base_step=stream_plan["stream_base_step"],
            )

        loop_config = LoopConfig(
            total_steps=args.steps,
            log_every=args.log_every,
            checkpoint_every=(
                args.checkpoint_every if args.snapshot_path else 0
            ),
            eval_every=args.eval_every,
            checkpoint_dir=args.snapshot_path,
            resume=not args.no_resume,
            profile_dir=args.profile_dir,
            device_prefetch=args.device_prefetch,
            async_eval=args.async_eval,
            # Numerics flight recorder (obs/numerics.py): the in-step
            # summary gate; the provenance dump lands in the obs dir (or
            # --log-dir without one) on a tripped finite-check either way.
            numerics=getattr(args, "numerics", False),
            numerics_dump_dir=numerics_dump_dir,
            rng_seed=args.seed,
            ckpt_metadata=ckpt_metadata,
        )
        run_eval_fn = (
            eval_fn
            if (args.eval_every or args.dataset_type in ("coco", "pascal")
                or (args.dataset_type == "csv" and val_ds is not None))
            else None
        )

        # Self-healing numerics resume (--auto-resume): each attempt gets
        # a fresh pipeline (reseeded, poison ids excluded) and a fresh
        # state template; run_training's resume restores the last HEALTHY
        # checkpoint (the pre-save gate keeps poisoned states off disk).
        # data_seed/exclude_ids/skip_batches start from the elastic plan
        # above (a virgin run: args.seed, none, 0).
        attempt = 0
        injector_latch = {"done": False}  # one injection per PROCESS
        while True:
            train_batches = build_pipeline(
                train_ds,
                PipelineConfig(
                    batch_size=local_batch, shuffle=True,
                    transform=train_transform,
                    shard_index=shard_index, shard_count=shard_count,
                    skip_batches=skip_batches, exclude_ids=exclude_ids,
                    **{**pipe_common, "seed": data_seed},
                ),
                train=True,
            )
            batches = train_batches
            if getattr(args, "inject_nan_step", None):
                batches = _NanInjector(
                    train_batches, args.inject_nan_step, injector_latch
                )
            try:
                state = run_training(
                    model,
                    state,
                    batches,
                    num_classes,
                    loop_config,
                    mesh=mesh,
                    schedule=schedule,
                    anchor_config=anchor_config,
                    shard_weight_update=shard_update,
                    comm=comm_cfg,
                    topology=comm_topology,
                    allow_data_axis_divergence=args.allow_data_axis_divergence,
                    eval_fn=run_eval_fn,
                    logger=logger,
                )
                break
            except FloatingPointError as exc:
                attempt += 1
                plan = _auto_resume_plan(args, attempt, exc)
                if plan is None:
                    raise
                data_seed, exclude_ids = (
                    plan["data_seed"],
                    tuple(sorted(set(exclude_ids) | set(plan["exclude_ids"]))),
                )
                # A reseed is a NEW deterministic order starting at the
                # restore step: skip nothing, and record the effective
                # stream facts (seed, exclusions, base step) in every
                # subsequent checkpoint's manifest so a later
                # --resume-elastic re-derives THIS stream's position —
                # not the aborted original's (which would silently
                # replay/skip batches).
                skip_batches = 0
                ckpt_metadata.update(
                    data_seed=data_seed,
                    exclude_ids=list(exclude_ids),
                    stream_base_step=plan["restored_step"],
                )
                # ONE structured auto_resume event per resume — in the
                # JSONL next to the metrics it interrupts, and on stderr
                # for bare runs.
                payload = {**plan, "exclude_ids": list(exclude_ids)}
                logger.event("auto_resume", **payload)
                print(
                    json.dumps({"event": "auto_resume", **payload}),
                    file=sys.stderr, flush=True,
                )
                state = build_state()
            finally:
                # Deterministic pipeline teardown (previously left to the
                # GC finalizer): decode workers/threads are reaped HERE,
                # so shm workers export their trace files BEFORE main()'s
                # obs finalize merges — a GC-time close would orphan them
                # from trace.json.
                train_batches.close()
        return {"final_step": float(int(state.step))}
    finally:
        if slo_monitor is not None:
            slo_monitor.stop()
        if telem_server is not None:
            telem_server.close()


if __name__ == "__main__":
    main()

"""On-device detection: forward → decode → clip → batched NMS, one XLA program.

Replaces the reference's separate "inference model" conversion step and its
``Anchors → RegressBoxes → ClipBoxes → FilterDetections`` layer stack
(SURVEY.md M3/M6, call stack 3.5, ``bin/convert_model.py``): here inference
is just another jitted function over the same train-state params, with the
whole post-processing (sigmoid, top-k pre-select, class-masked NMS) running
on the TPU per BASELINE.json configs[4] ("on-device batched NMS").

``run_coco_eval`` is the dataset-level driver (the ``CocoEval`` callback /
``evaluate_coco()`` equivalent, SURVEY.md M10): stream the eval pipeline,
detect per static shape bucket (one compiled program each), rescale boxes to
original image coordinates on host, and hand COCO-format results to the
numpy mAP oracle (evaluate/coco_eval.py).

Since ISSUE 2 the driver is a THREE-STAGE PIPELINE (default; the strictly
sequential path survives as ``pipelined=False`` and stays bit-identical):

1. **device prefetch** — the shared ``prefetch_map`` helper
   (data/prefetch.py, the train loop's double-buffering machinery) moves
   eval batches host→device up to ``device_prefetch`` batches ahead, so
   detect compute overlaps the next batch's decode + DMA;
2. **one-behind async dispatch** — the jitted detect program for batch N is
   dispatched before batch N−1's results are pulled, so the host-side
   ``device_get`` + box rescale + COCO-format conversion of batch N−1
   overlap batch N's on-device NMS;
3. **background scoring consumer** — conversion and (single-process)
   incremental COCOeval matching (``StreamingCocoEval``) run in a consumer
   thread behind a bounded queue with the shm-pipeline's error contract:
   a consumer crash re-raises in the driver, ``close()`` never hangs.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
from typing import Any, Callable, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from batchai_retinanet_horovod_coco_tpu.data import pipeline as pipeline_lib
from batchai_retinanet_horovod_coco_tpu.data.coco import CocoDataset
from batchai_retinanet_horovod_coco_tpu.data.pipeline import Batch
from batchai_retinanet_horovod_coco_tpu.evaluate.coco_eval import evaluate_detections
from batchai_retinanet_horovod_coco_tpu.evaluate.voc_eval import (
    evaluate_detections_voc,
)
from batchai_retinanet_horovod_coco_tpu.obs import trace, watchdog
from batchai_retinanet_horovod_coco_tpu.ops import anchors as anchors_lib
from batchai_retinanet_horovod_coco_tpu.ops import boxes as boxes_lib
from batchai_retinanet_horovod_coco_tpu.ops import nms as nms_lib
from batchai_retinanet_horovod_coco_tpu.parallel.mesh import DATA_AXIS
from batchai_retinanet_horovod_coco_tpu.train.state import model_variables


@dataclasses.dataclass(frozen=True)
class DetectConfig:
    """FilterDetections-equivalent knobs (reference defaults, SURVEY.md M6)."""

    score_threshold: float = 0.05
    iou_threshold: float = 0.5
    # Candidates that survive to NMS (the reference's 1000): unlike the two
    # backend fields below this one changes what is detected.
    pre_nms_size: int = 1000
    max_detections: int = 300
    # NMS suppression backend: "xla" | "pallas" (ops/pallas/nms.py, exact
    # and bit-identical; untimed on the chip, so not the default).
    nms_impl: str = "xla"
    # (K, K) IoU tile width of the Pallas kernel (ops/pallas/nms.DEFAULT_BLOCK_K).
    nms_block_k: int = 256
    # Interpreter-mode Pallas (CPU tests of the fused suppression path).
    nms_interpret: bool = False
    codec: boxes_lib.BoxCodecConfig = boxes_lib.BoxCodecConfig()
    anchor: anchors_lib.AnchorConfig = anchors_lib.AnchorConfig()

    def __post_init__(self):
        # A typo must raise, not take the XLA branch of nms_fn_for's
        # == "pallas" comparison in silence.
        if self.nms_impl not in ("xla", "pallas"):
            raise ValueError(
                f"nms_impl must be 'xla' or 'pallas', got {self.nms_impl!r}"
            )


def nms_fn_for(
    config: DetectConfig,
) -> Callable[[jnp.ndarray, jnp.ndarray], nms_lib.Detections]:
    """``(boxes (B, A, 4), scores (B, A, K)) → Detections`` — the one
    place the XLA-vs-Pallas suppression dispatch lives."""
    if config.nms_impl == "pallas":
        from batchai_retinanet_horovod_coco_tpu.ops.pallas import (
            nms as pallas_nms,
        )

        def nms(boxes, scores):
            return pallas_nms.batched_multiclass_nms_pallas(
                boxes,
                scores,
                score_threshold=config.score_threshold,
                iou_threshold=config.iou_threshold,
                pre_nms_size=config.pre_nms_size,
                max_detections=config.max_detections,
                block_k=config.nms_block_k,
                interpret=config.nms_interpret,
            )
    else:

        def nms(boxes, scores):
            return nms_lib.batched_multiclass_nms(
                boxes,
                scores,
                score_threshold=config.score_threshold,
                iou_threshold=config.iou_threshold,
                pre_nms_size=config.pre_nms_size,
                max_detections=config.max_detections,
            )

    return nms


def _detect_body(
    model, image_hw: tuple[int, int], config: DetectConfig
) -> Callable[[Any, jnp.ndarray], nms_lib.Detections]:
    """The ONE detection pipeline every factory wraps: normalize → forward →
    sigmoid → decode → clip → batched NMS.  Shared so the batch-sharded and
    spatially-sharded paths can never drift from the single-device one.

    The NMS backend dispatch lives here too (:func:`nms_fn_for`):
    ``nms_impl == "pallas"`` swaps the suppression stage for the fused
    blocked kernel (ops/pallas/nms.py), which shares candidate selection
    and compaction with the XLA path and is bit-identical to it
    (tests/unit/test_pallas_nms.py)."""
    anchors = jnp.asarray(
        anchors_lib.anchors_for_image_shape(image_hw, config.anchor)
    )
    nms = nms_fn_for(config)

    def detect(state, images: jnp.ndarray) -> nms_lib.Detections:
        # uint8 batches normalize on device (data/pipeline.normalize_images).
        images = pipeline_lib.normalize_images(images)
        outputs = model.apply(model_variables(state), images, train=False)
        scores = jax.nn.sigmoid(outputs["cls_logits"])  # (B, A, K)
        boxes = boxes_lib.decode_boxes(
            anchors[None], outputs["box_deltas"], config.codec
        )
        boxes = boxes_lib.clip_boxes(boxes, image_hw)
        return nms(boxes, scores)

    return detect


def make_detect_fn(
    model,
    image_hw: tuple[int, int],
    config: DetectConfig = DetectConfig(),
    mesh: Mesh | None = None,
) -> Callable[[Any, jnp.ndarray], nms_lib.Detections]:
    """Jitted (state, images (B,H,W,3)) → batched Detections for one bucket.

    With ``mesh``, the batch shards over the ``data`` axis and results gather
    back — eval uses every chip instead of the reference's rank-0-only path.
    """
    detect = _detect_body(model, image_hw, config)

    if mesh is None:
        return jax.jit(detect)

    sharded = shard_map(
        detect,
        mesh=mesh,
        in_specs=(P(), P(DATA_AXIS)),
        out_specs=P(DATA_AXIS),
        check_vma=False,
    )
    return jax.jit(sharded)


def compile_detect_fn(
    model,
    state,
    image_hw: tuple[int, int],
    batch_size: int,
    config: DetectConfig = DetectConfig(),
    mesh: Mesh | None = None,
    input_dtype: Any = None,
) -> Callable[[jnp.ndarray], nms_lib.Detections]:
    """AOT-lower + compile ONE bucket's detect program at a fixed batch
    size; returns ``call(images) -> Detections`` with ``state`` closed over.

    The load/dispatch path of the serve engine (serve/engine.py), which
    needs every (bucket, batch-size) executable built BEFORE traffic
    arrives, with the
    multi-second compile attributed by a trace span instead of hiding
    inside the first dispatch.  Inputs default to uint8 — the raw pipeline
    format; normalization runs inside the program (``_detect_body``).
    """
    fn = make_detect_fn(model, image_hw, config, mesh=mesh)
    spec = jax.ShapeDtypeStruct(
        (batch_size, *image_hw, 3),
        jnp.uint8 if input_dtype is None else input_dtype,
    )
    with trace.span(
        "aot_compile_detect",
        bucket=f"{image_hw[0]}x{image_hw[1]}",
        batch=batch_size,
    ):
        compiled = fn.lower(state, spec).compile()

    def call(images: jnp.ndarray) -> nms_lib.Detections:
        return compiled(state, images)

    return call


def make_detect_fn_spatial(
    model,
    image_hw: tuple[int, int],
    config: DetectConfig = DetectConfig(),
    mesh: Mesh | None = None,
) -> Callable[[Any, jnp.ndarray], nms_lib.Detections]:
    """Detection with the IMAGE sharded across chips (spatial partitioning).

    The long-axis analogue of sequence/context parallelism for a CNN
    detector (SURVEY.md §2.4/§5.7): instead of sharding the batch, the
    image's H axis is sharded over the mesh and XLA GSPMD inserts halo
    exchanges for every conv — ring-attention's "pass the boundary"
    communication pattern, compiled automatically.  Useful when a single
    very large image (or tiny batch) must use many chips; per-image latency
    drops instead of throughput rising.

    Built with ``jit`` + sharding constraints rather than ``shard_map``:
    spatial conv partitioning needs the compiler's halo machinery, which
    manual per-device code would have to hand-roll.  Outputs are gathered
    (the anchor-major reshape reshards after the conv-heavy stage; NMS runs
    replicated, it is negligible next to the backbone).
    """
    from jax.sharding import NamedSharding

    if mesh is None:
        raise ValueError("spatial detection needs a mesh")
    rep = NamedSharding(mesh, P())
    img_sharding = NamedSharding(mesh, P(None, DATA_AXIS))  # shard H
    return jax.jit(
        _detect_body(model, image_hw, config),
        in_shardings=(rep, img_sharding),
        out_shardings=rep,
    )


def detections_to_coco(
    det: nms_lib.Detections,
    image_ids: np.ndarray,
    scales: np.ndarray,
    valid_rows: np.ndarray,
    label_to_cat_id: dict[int, int],
    image_sizes: dict[int, tuple[int, int]] | None = None,
) -> list[dict]:
    """Device Detections (one batch) → COCO result dicts in ORIGINAL coords.

    Boxes come back in resized-image coordinates; dividing by the per-image
    scale restores original coordinates (SURVEY.md M10 "rescale boxes").
    The device-side clip is to the static bucket extent (which includes
    padding), so with ``image_sizes`` ({image_id: (width, height)}) boxes are
    re-clamped to the true image bounds here; degenerate (zero-area) boxes —
    e.g. spurious hits entirely inside the padding — are dropped.
    """
    boxes = np.asarray(det.boxes, dtype=np.float64)
    scores = np.asarray(det.scores, dtype=np.float64)
    labels = np.asarray(det.labels)
    valid = np.asarray(det.valid)

    results: list[dict] = []
    for i in range(boxes.shape[0]):
        if not valid_rows[i]:
            continue  # eval padding row
        inv = 1.0 / float(scales[i])
        img_id = int(image_ids[i])
        wh = image_sizes.get(img_id) if image_sizes else None
        for j in np.flatnonzero(valid[i]):
            x1, y1, x2, y2 = boxes[i, j] * inv
            if wh is not None:
                x1, x2 = np.clip([x1, x2], 0.0, wh[0])
                y1, y2 = np.clip([y1, y2], 0.0, wh[1])
                if x2 <= x1 or y2 <= y1:
                    continue
            results.append(
                {
                    "image_id": img_id,
                    "category_id": int(label_to_cat_id[int(labels[i, j])]),
                    "bbox": [x1, y1, x2 - x1, y2 - y1],
                    "score": float(scores[i, j]),
                }
            )
    return results


def coco_gt_from_dataset(dataset: CocoDataset) -> tuple[list[dict], list[int]]:
    """Ground-truth annotation dicts + image-id list from a CocoDataset.

    Crowd annotations come through with ``iscrowd=1`` and per-annotation
    areas are preserved, so the oracle's ignore/area-range semantics match
    pycocotools on real COCO.  For full-fidelity eval construct the dataset
    with ``keep_empty=True`` (annotation-less images still collect FPs).
    """
    gts: list[dict] = []
    ann_id = 1
    for rec in dataset.records:
        for boxes, labels, areas, iscrowd in (
            (rec.boxes, rec.labels, rec.areas, 0),
            (rec.crowd_boxes, rec.crowd_labels, rec.crowd_areas, 1),
        ):
            for box, label, area in zip(boxes, labels, areas):
                x1, y1, x2, y2 = (float(v) for v in box)
                gts.append(
                    {
                        "id": ann_id,
                        "image_id": rec.image_id,
                        "category_id": dataset.label_to_cat_id[int(label)],
                        "bbox": [x1, y1, x2 - x1, y2 - y1],
                        "area": float(area),
                        "iscrowd": iscrowd,
                    }
                )
                ann_id += 1
    return gts, [rec.image_id for rec in dataset.records]


def _device_images(batch: Batch, mesh: Mesh | None):
    """Enqueue one eval batch's images host→device (sharded over ``mesh``).

    The eval twin of the train loop's ``_device_batch``: called from the
    prefetch thread so the DMA dispatch happens off the detect-dispatch
    path.  Process-local by design — multi-host eval runs on a LOCAL mesh
    over this process's shard of the val set (train.py's eval hook).
    """
    if mesh is None:
        return jax.device_put(batch.images)
    from jax.sharding import NamedSharding

    return jax.device_put(batch.images, NamedSharding(mesh, P(DATA_AXIS)))


class _EvalConsumer:
    """Stage-3 background consumer: device Detections → COCO result dicts
    (+ optional per-batch scoring hook), behind a bounded queue.

    Mirrors the shm pipeline's error contract
    (tests/unit/test_eval_pipeline.py):

    - a crash in the consumer (conversion or the scoring hook) re-raises
      in the DRIVER at its next ``put()``/``finish()`` — never a silent
      hang or a swallowed partial score;
    - ``close()`` stops the thread promptly even mid-queue (both ends are
      stop-gated) and is idempotent;
    - batches are consumed FIFO by one thread, so ``results`` is ordered
      exactly as the sequential path orders it (bit-identical output).
    """

    _DONE = object()

    def __init__(
        self,
        label_to_cat_id: dict[int, int],
        image_sizes: dict[int, tuple[int, int]] | None,
        on_batch: Callable[[list[dict], Sequence[int]], None] | None = None,
        maxsize: int = 4,
    ):
        self._label_to_cat_id = label_to_cat_id
        self._image_sizes = image_sizes
        self._on_batch = on_batch
        self._queue: queue.Queue = queue.Queue(maxsize=max(1, maxsize))
        self._stop = threading.Event()
        self._error: BaseException | None = None
        self.results: list[dict] = []
        # watchdog: registers in _run() at thread start.
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="eval-consumer"
        )
        self._thread.start()

    def _run(self) -> None:
        # Every poll iteration beats (the idle get(timeout) included — a
        # waiting consumer is healthy); only a WEDGED conversion/scoring
        # callback stops the heartbeat, which is exactly the previously
        # invisible failure the watchdog exists to name (ISSUE 3).
        hb = watchdog.register(
            "eval-consumer",
            details=lambda: {
                "qsize": self._queue.qsize(),
                "results": len(self.results),
            },
        )
        try:
            while not self._stop.is_set():
                hb.beat()
                try:
                    item = self._queue.get(timeout=0.1)
                except queue.Empty:
                    continue
                if item is self._DONE:
                    return
                det, image_ids, scales, valid = item
                with trace.span("eval_convert"):
                    batch_results = detections_to_coco(
                        det,
                        image_ids,
                        scales,
                        valid,
                        self._label_to_cat_id,
                        image_sizes=self._image_sizes,
                    )
                self.results.extend(batch_results)
                if self._on_batch is not None:
                    done = [
                        int(i) for i, v in zip(image_ids, valid) if v
                    ]
                    with trace.span("eval_score"):
                        self._on_batch(batch_results, done)
                if trace.enabled():
                    trace.counter("eval_consumer.qsize", self._queue.qsize())
        except BaseException as exc:  # re-raised in the driver
            self._error = exc
            self._stop.set()  # unblock a driver waiting on a full queue
        finally:
            hb.close()

    def _raise_pending(self) -> None:
        if self._error is not None:
            raise RuntimeError("eval consumer thread failed") from self._error

    def put(self, det, image_ids, scales, valid) -> None:
        """Hand one fetched batch to the consumer; raises its pending error."""
        self._raise_pending()
        if not pipeline_lib.stop_gated_put(
            self._queue, (det, image_ids, scales, valid), self._stop
        ):
            self._raise_pending()
            raise RuntimeError("eval consumer stopped")

    def finish(self) -> list[dict]:
        """Drain, join, surface any consumer error → ordered results."""
        pipeline_lib.stop_gated_put(self._queue, self._DONE, self._stop)
        self._thread.join()
        self._raise_pending()
        return self.results

    def close(self) -> None:
        """Abort without draining (driver unwinding on its own error)."""
        self._stop.set()
        self._thread.join(timeout=10)


def collect_detections(
    state,
    model,
    dataset: CocoDataset,
    batches: Iterable[Batch],
    config: DetectConfig = DetectConfig(),
    mesh: Mesh | None = None,
    *,
    pipelined: bool = True,
    device_prefetch: int = 2,
    detect_fns: dict[tuple[int, int], Callable] | None = None,
    on_batch: Callable[[list[dict], Sequence[int]], None] | None = None,
) -> list[dict]:
    """Run detection over an eval batch stream → COCO result dicts.

    One detect function is compiled per shape bucket encountered (static
    shapes, SURVEY.md §7.3 hard part 1); the cache keys on (H, W).  Pass
    ``detect_fns`` to share compiled programs across calls.

    ``pipelined`` selects the three-stage overlapped driver (module
    docstring); ``False`` is the strictly sequential reference path.  Both
    produce identical results in identical order
    (tests/unit/test_eval_pipeline.py pins bitwise equality).  ``on_batch``
    (if given) observes each batch's converted results plus the image ids
    it completed — in the consumer THREAD when pipelined, inline otherwise.
    """
    if detect_fns is None:
        detect_fns = {}
    image_sizes = {
        rec.image_id: (rec.width, rec.height) for rec in dataset.records
    }

    def fn_for(hw: tuple[int, int]) -> Callable:
        fn = detect_fns.get(hw)
        if fn is None:
            # AOT point: the jit wrapper is built here and compiles at its
            # first dispatch — mark it so a trace attributes the one-time
            # multi-second gap per bucket to compilation, not a stall.
            with trace.span("build_detect_fn", bucket=f"{hw[0]}x{hw[1]}"):
                fn = detect_fns[hw] = make_detect_fn(
                    model, hw, config, mesh=mesh
                )
        return fn

    if not pipelined:
        results: list[dict] = []
        for batch in batches:
            hw = batch.images.shape[1:3]
            det = jax.device_get(fn_for(hw)(state, jnp.asarray(batch.images)))
            batch_results = detections_to_coco(
                det,
                batch.image_ids,
                batch.scales,
                batch.valid,
                dataset.label_to_cat_id,
                image_sizes=image_sizes,
            )
            results.extend(batch_results)
            if on_batch is not None:
                on_batch(
                    batch_results,
                    [int(i) for i, v in zip(batch.image_ids, batch.valid) if v],
                )
        return results

    from batchai_retinanet_horovod_coco_tpu.data.prefetch import prefetch_map

    consumer = _EvalConsumer(
        dataset.label_to_cat_id, image_sizes, on_batch=on_batch
    )
    # Stage 1: host→device transfer runs in the prefetch thread, ``depth``
    # batches ahead of dispatch.  Shape/metadata stay host-side.
    staged = prefetch_map(
        batches,
        lambda b: (
            b.images.shape,
            _device_images(b, mesh),
            b.image_ids,
            b.scales,
            b.valid,
        ),
        depth=device_prefetch,
        thread_name="eval-device-prefetch",
    )
    # Stage 2: dispatch batch N, then pull batch N−1 (its program has
    # already finished or is ahead in the device stream): the device_get +
    # conversion of N−1 overlap N's forward+NMS on device.  The driver
    # carries its own heartbeat: the consumer beats on every idle poll and
    # the prefetch thread idles behind a full queue, so a wedge HERE —
    # device_get hanging on a dead device stream is the canonical one —
    # would otherwise be the only component with no liveness signal.
    hb = watchdog.register(
        "eval-driver", details=lambda: {"results": len(consumer.results)}
    )
    pending: tuple | None = None

    def fetch(det):
        with trace.span("detect_fetch"):
            fetched = jax.device_get(det)
        hb.beat()
        return fetched

    try:
        for shape, images_dev, image_ids, scales, valid in staged:
            hb.beat()
            with trace.span("detect_dispatch"):
                det = fn_for(shape[1:3])(state, images_dev)  # async dispatch
            if pending is not None:
                prev_det, prev_meta = pending
                fetched = fetch(prev_det)
                hb.idle()  # a full consumer queue is backpressure
                # Named span so the perf doctor can tell consumer
                # backpressure (slow host conversion/scoring) apart from
                # fetch blocking (slow device NMS) in the same driver.
                with trace.span("eval_put_wait"):
                    consumer.put(fetched, *prev_meta)
                hb.beat()
            pending = (det, (image_ids, scales, valid))
        if pending is not None:
            prev_det, prev_meta = pending
            pending = None
            fetched = fetch(prev_det)
            hb.idle()
            with trace.span("eval_put_wait"):
                consumer.put(fetched, *prev_meta)
        hb.idle()  # finish() legitimately blocks on the consumer's drain
        return consumer.finish()
    finally:
        staged.close()
        consumer.close()
        hb.close()


def allgather_process_detections(results: list[dict]) -> list[dict]:
    """Merge per-process detection shards across hosts.

    The sharded-eval gather: each process detects only ITS slice of the val
    set (the reference evaluated on rank 0 only — at pod scale that is
    hosts× redundant decode, SURVEY.md M10); the COCO result dicts pack into
    a fixed-width float64 array, pad to the max per-process count, and
    all-gather at the host level.  Every process returns the full merged
    list, so the subsequent scoring is identical everywhere (process 0
    logs).  Single-process: identity.
    """
    if jax.process_count() == 1:
        return results
    from jax.experimental import multihost_utils

    # Two packs: int64 ids would be canonicalized to int32 (and float64 to
    # float32) without jax_enable_x64, so 64-bit image ids (date-encoded COCO
    # ids are legal) travel as uint32 (lo, hi) halves; bbox/score are f32 on
    # device anyway, so the f32 pack loses nothing vs the unsharded path.
    n = len(results)
    ids = np.zeros((n, 3), np.uint32)  # image_id lo/hi, category_id
    vals = np.zeros((n, 5), np.float32)  # bbox xywh, score
    for i, r in enumerate(results):
        image_id = int(r["image_id"])
        ids[i] = [image_id & 0xFFFFFFFF, image_id >> 32, r["category_id"]]
        vals[i] = [*r["bbox"], r["score"]]
    counts = np.asarray(
        multihost_utils.process_allgather(np.uint32(n))
    ).reshape(-1)
    n_max = int(counts.max())
    if n_max == 0:
        return []
    ids_g = np.asarray(
        multihost_utils.process_allgather(
            np.pad(ids, ((0, n_max - n), (0, 0)))
        )
    )
    vals_g = np.asarray(
        multihost_utils.process_allgather(
            np.pad(vals, ((0, n_max - n), (0, 0)))
        )
    )
    merged: list[dict] = []
    for p in range(ids_g.shape[0]):
        for j in range(int(counts[p])):
            merged.append(
                {
                    "image_id": int(ids_g[p, j, 0]) | (int(ids_g[p, j, 1]) << 32),
                    "category_id": int(ids_g[p, j, 2]),
                    "bbox": [float(v) for v in vals_g[p, j, :4]],
                    "score": float(vals_g[p, j, 4]),
                }
            )
    return merged


def run_coco_eval(
    state,
    model,
    dataset: CocoDataset,
    batches: Iterable[Batch],
    config: DetectConfig = DetectConfig(),
    mesh: Mesh | None = None,
    voc_metrics: bool = False,
    voc_weighted_average: bool = False,
    gather: bool = True,
    pipelined: bool = True,
    device_prefetch: int = 2,
    detect_fns: dict[tuple[int, int], Callable] | None = None,
) -> dict[str, float]:
    """Full eval pass: detect everything, then mAP via the numpy oracle.

    ``pipelined`` (default) runs the three-stage overlapped driver (module
    docstring): prefetch → one-behind async detect → background consumer.
    When the detections need no cross-process merge, the consumer
    additionally scores INCREMENTALLY (``StreamingCocoEval``), so the
    per-image COCO matching overlaps device NMS instead of running as a
    serial epilogue; metrics are identical either way
    (tests/unit/test_eval_pipeline.py).  ``pipelined=False`` is the
    strictly sequential reference path.

    With ``voc_metrics``, the same detection pass additionally yields
    PASCAL-VOC AP@0.5 per class (the reference's ``Evaluate`` callback
    metric for CSV/custom datasets, evaluate/voc_eval.py), merged into the
    returned dict under ``voc_*`` keys; ``voc_weighted_average`` weights
    the VOC mean by per-class annotation counts (the callback's flag).

    Multi-host: feed each process its shard of the val set (pipeline
    ``shard_index/shard_count``), detect on a LOCAL mesh, and the shards
    merge here via ``allgather_process_detections`` (``gather=False`` skips
    the merge for a deliberately process-local eval).
    """
    gt, img_ids = coco_gt_from_dataset(dataset)
    # Streaming scoring needs the full result set to BE this process's
    # result set: with a pending cross-process merge, score post-gather.
    scorer = None
    if pipelined and (not gather or jax.process_count() == 1):
        from batchai_retinanet_horovod_coco_tpu.evaluate.coco_eval import (
            StreamingCocoEval,
        )

        scorer = StreamingCocoEval(
            gt, img_ids, cat_ids=list(dataset.label_to_cat_id.values())
        )
    dt = collect_detections(
        state,
        model,
        dataset,
        batches,
        config,
        mesh=mesh,
        pipelined=pipelined,
        device_prefetch=device_prefetch,
        detect_fns=detect_fns,
        on_batch=scorer.add if scorer is not None else None,
    )
    if gather:
        dt = allgather_process_detections(dt)
    if scorer is not None:
        metrics = scorer.finish()
    else:
        metrics = evaluate_detections(gt, dt, img_ids=img_ids)
    if voc_metrics:
        metrics.update(
            evaluate_detections_voc(
                gt, dt, weighted_average=voc_weighted_average
            )
        )
    return metrics

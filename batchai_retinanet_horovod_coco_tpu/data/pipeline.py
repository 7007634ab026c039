"""Host input pipeline: decode → augment → resize → bucket-pad → batch.

Parity target: keras-retinanet's ``Generator`` hot loop (SURVEY.md M8, call
stack 3.3) — JPEG decode, random flip, aspect-preserving resize to
min-side/max-side (800/1333 for the flagship config, BASELINE.json:10), and
batching — minus everything the TPU rebuild moves on device (anchor targets).

TPU-first redesign decisions:
- **Static shape buckets** (SURVEY.md §7.3 hard part 1): every image is
  resized (aspect preserved) then padded into one of a small set of fixed
  (H, W) buckets chosen by aspect ratio; batches are formed within a bucket,
  so XLA compiles one program per bucket instead of one per unique padded
  shape.
- GT boxes are padded to a fixed ``max_gt`` with a validity mask; target
  assignment happens on device.
- Normalization is ImageNet-style RGB mean/std (a redesign of the reference's
  caffe BGR mean-subtract; the convention only needs to match the backbone
  init, which is ours).
- Deterministic: one PRNG per (seed, epoch); multi-host sharding is plain
  index sharding by ``process_index`` (the grain/tf.data idiom), replacing
  the reference's implicit per-rank generator seeding.
- Decode + resize fan out over a thread pool; batches are prefetched by a
  background thread into a bounded queue (the reference used Keras'
  ``fit_generator`` worker pool).
"""

from __future__ import annotations

import dataclasses
import logging
import queue
import threading
from collections.abc import Iterator
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple

import numpy as np

logger = logging.getLogger(__name__)

from batchai_retinanet_horovod_coco_tpu.data.transforms import cv2  # shared fallback

from batchai_retinanet_horovod_coco_tpu.data.coco import CocoDataset, ImageRecord
from batchai_retinanet_horovod_coco_tpu.obs import trace, watchdog
from batchai_retinanet_horovod_coco_tpu.data.transforms import (
    TransformConfig,
    apply_random_transform,
)

IMAGENET_MEAN = np.array([0.485, 0.456, 0.406], dtype=np.float32)
IMAGENET_STD = np.array([0.229, 0.224, 0.225], dtype=np.float32)
# Normalize as two fused in-place passes: x*scale - offset == (x/255-m)/s.
_NORM_SCALE = (1.0 / (255.0 * IMAGENET_STD)).astype(np.float32)
_NORM_OFFSET = (IMAGENET_MEAN / IMAGENET_STD).astype(np.float32)
# uint8 batch padding ≈ the dataset mean, i.e. ~0.0 in normalized space —
# matching the reference's pad-with-zeros-AFTER-preprocessing semantics.
_PAD_PIXEL = np.round(IMAGENET_MEAN * 255.0).astype(np.uint8)


def normalize_images(images):
    """Device-side ImageNet normalization for uint8 image batches.

    TPU-first redesign of the reference's host-side ``preprocess_image``
    (SURVEY.md M8): the pipeline ships uint8 (4x less host work, host RAM
    and PCIe traffic); this cast+scale runs on device, where XLA fuses it
    into the stem conv's input. f32 inputs pass through unchanged
    (pre-normalized arrays from tests/tools keep working).
    """
    import jax.numpy as jnp

    if images.dtype != jnp.uint8:
        return images
    x = images.astype(jnp.float32)
    return x * jnp.asarray(_NORM_SCALE) - jnp.asarray(_NORM_OFFSET)


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    batch_size: int = 2
    # (H, W) buckets; an image goes to the first bucket whose aspect class
    # matches (landscape → wide bucket, portrait → tall, near-square → square).
    buckets: tuple[tuple[int, int], ...] = ((800, 1344), (1344, 800), (1024, 1024))
    min_side: int = 800
    max_side: int = 1333
    max_gt: int = 100
    hflip_prob: float = 0.5
    # Full random-affine + photometric augmentation (the reference's
    # --random-transform recipe, data/transforms.py). When set, it replaces
    # the flip-only path — configure flips via TransformConfig.flip_x_prob.
    transform: TransformConfig | None = None
    shuffle: bool = True
    seed: int = 0
    # Multi-host sharding: this process sees records[shard_index::shard_count].
    shard_index: int = 0
    shard_count: int = 1
    num_workers: int = 8
    # > 0 selects the multiprocess shared-memory pipeline (shm_pipeline.py):
    # that many decode/augment/resize worker PROCESSES writing into
    # preallocated shared-memory ring buffers, sidestepping the GIL ceiling
    # of the thread pool (PIL JPEG decode holds the GIL; the per-worker
    # thread sweep plateaus at 2).  0 (default) keeps the in-process thread
    # pool — the right choice under pytest and on low-resource hosts.
    # Both paths emit bit-identical batches for a fixed seed.
    num_worker_procs: int = 0
    # Bounded-stall watchdog for the multiprocess path: a worker crash is
    # detected via liveness within ~0.2 s, and a WEDGED (alive but stuck)
    # worker surfaces as a raised exception after this many seconds of a
    # head-of-line batch making no progress — never a silent hang.
    worker_timeout: float = 120.0
    # multiprocessing start method for the worker processes.  "spawn" is the
    # default: forking a process that has initialized JAX/XLA (thread pools,
    # possibly a TPU client) is unsafe; spawned workers import only the data
    # layer (numpy/PIL/cv2), never jax.
    mp_start_method: str = "spawn"
    prefetch: int = 4
    drop_remainder: bool = True
    # Elastic resume (ISSUE 11): skip this many ALREADY-CONSUMED batches
    # before emitting the first one (train only).  Batch composition is a
    # pure function of (seed, epoch, shard) — ``batch_plans`` — so skipping
    # k plans without decoding re-derives the exact stream position of a
    # run that consumed k batches: no batch replayed, none skipped.  The
    # train loop consumes one batch per process per step, so a resume at
    # step r passes r here (train.py --resume-elastic).
    skip_batches: int = 0
    # Self-healing numerics resume (ISSUE 11): source image_ids that must
    # never be emitted again — ``--auto-resume`` passes the poison batch's
    # ids from NUMERICS_DUMP.json so the batch that tripped the abort
    # cannot recur.  Applied after the epoch shuffle, before sharding, in
    # ``epoch_indices`` (shared by the thread and shm producers).
    exclude_ids: tuple[int, ...] = ()
    # Default: ship uint8 and normalize ON DEVICE (see normalize_images).
    # True restores the reference's host-side f32 preprocessing.
    host_normalize: bool = False


def dataset_max_gt(dataset) -> int:
    """Largest per-image annotation count in the dataset (crowds excluded —
    only ``record.boxes`` feed training targets)."""
    return max((len(r.boxes) for r in dataset.records), default=0)


def resolve_max_gt(requested: int | None, *datasets, cap: int = 512) -> int:
    """The pipeline's gt-padding size for a run.

    ``None`` (auto) sizes to the datasets' true per-image maximum — no
    silent truncation, COCO images can carry >100 boxes — rounded up to a
    multiple of 8 for layout friendliness and clamped to [8, cap].  An
    explicit value is honored as-is; ``build_pipeline`` then counts and
    logs what it drops.
    """
    if requested is not None:
        return requested
    need = max((dataset_max_gt(ds) for ds in datasets), default=0)
    return max(8, min(round_up(max(need, 1), 8), cap))


@dataclasses.dataclass
class PipelineStats:
    """Mutable counters a pipeline exposes (``.stats`` on the iterator).

    Truncation means an image carried more than ``max_gt`` boxes: the
    overflow boxes vanish from the training targets (their anchors become
    background and are actively penalized), so it must be visible.
    """

    truncated_boxes: int = 0
    truncated_images: int = 0


class Batch(NamedTuple):
    images: np.ndarray  # (B, H, W, 3) uint8 raw (device normalizes; see
    # normalize_images) or float32 pre-normalized when host_normalize=True
    gt_boxes: np.ndarray  # (B, max_gt, 4) float32, resized coords
    gt_labels: np.ndarray  # (B, max_gt) int32
    gt_mask: np.ndarray  # (B, max_gt) bool
    image_ids: np.ndarray  # (B,) int64
    scales: np.ndarray  # (B,) float32: resized / original
    valid: np.ndarray  # (B,) bool: False for eval padding rows


def round_up(x: int, multiple: int) -> int:
    return ((x + multiple - 1) // multiple) * multiple


def stop_gated_put(q: queue.Queue, item, stop: threading.Event) -> bool:
    """Blocking put into a bounded queue that aborts when ``stop`` is set.

    The one producer→consumer handoff idiom every pipeline producer in this
    package uses (thread pool, shm coordinator, device-prefetch feeder): a
    plain blocking put would leak the producer thread forever if the
    consumer disappears while the queue is full.  Returns False on abort.
    """
    while not stop.is_set():
        try:
            q.put(item, timeout=0.1)
            return True
        except queue.Full:
            continue
    return False


def default_buckets(min_side: int, max_side: int) -> tuple[tuple[int, int], ...]:
    """Static (H, W) shape buckets covering the resize rule's output range.

    The single source of truth for bucket derivation — train.py and
    debug.py consume this, so the shapes the tools report match the shapes
    the train step compiles for.

    Two buckets suffice, PROVABLY: ``resize_scale`` maps every source to
    resized dims with min(rh, rw) <= min_side <= lo and max(rh, rw) <=
    max_side <= hi, so a landscape/square result (rh <= rw) always fits
    (lo, hi) and a portrait result fits (hi, lo).  Rounds 1-4 carried a
    third round_up((lo+hi)/2) "mid" square bucket for mild portraits; the
    round-5 exhaustive source-size scan (tests/unit/test_buckets.py)
    showed it is UNREACHABLE under that argument for every config — and
    for the images it targeted the portrait bucket pads less anyway
    (933x800 resized: 0.33 Mpx waste in 1344x800 vs 0.44 in 1088x1088).
    Dropping it removes a dead compiled program per run (one fewer
    ~minutes-long bucket compile at pod bring-up).
    """
    lo = round_up(min_side, 32)
    hi = round_up(max_side, 32)
    if lo == hi:
        return ((lo, lo),)
    return ((lo, hi), (hi, lo))


def resize_scale(h: int, w: int, min_side: int, max_side: int) -> float:
    """Reference resize rule: scale so min side = min_side, capped by max_side."""
    scale = min_side / min(h, w)
    if scale * max(h, w) > max_side:
        scale = max_side / max(h, w)
    return scale


def bucket_for_source(
    h: int,
    w: int,
    min_side: int,
    max_side: int,
    buckets: tuple[tuple[int, int], ...],
) -> tuple[int, int]:
    """Bucket a SOURCE-resolution image lands in: the pipeline's own
    resize rule + rounding + bucket pick, in one place — shared by the
    pipeline's batch former and by ``debug.py buckets`` so the measured
    bucket shares cannot drift from what the producer actually does."""
    scale = resize_scale(h, w, min_side, max_side)
    return pick_bucket(int(round(h * scale)), int(round(w * scale)), buckets)


def pick_bucket(
    h: int, w: int, buckets: tuple[tuple[int, int], ...]
) -> tuple[int, int]:
    """Smallest bucket that fits (h, w); falls back to the largest-area one."""
    fitting = [b for b in buckets if b[0] >= h and b[1] >= w]
    if fitting:
        return min(fitting, key=lambda b: b[0] * b[1])
    return max(buckets, key=lambda b: b[0] * b[1])


def resize_for_bucket(
    image: np.ndarray,
    bucket: tuple[int, int],
    min_side: int,
    max_side: int,
) -> tuple[np.ndarray, float]:
    """Aspect-preserving resize of ONE decoded uint8 HWC image into
    ``bucket`` — the single source of truth for inference-time geometry,
    shared by ``load_example`` (train/eval pipeline) and the serve
    router (serve/router.py), so a served image can never be resized
    differently from the eval pipeline that pinned the model's metrics.

    Applies the reference resize rule (``resize_scale``) capped so the
    result fits the bucket (extreme aspect ratios).  Returns
    ``(image, scale)``; when no resize is needed the input array is
    returned as-is and boxes must NOT be rescaled (callers key off the
    shape changing, matching the historical behavior bit-for-bit).
    """
    h, w = image.shape[:2]
    bh, bw = bucket
    scale = min(resize_scale(h, w, min_side, max_side), bh / h, bw / w)
    nh = min(bh, int(round(h * scale)))
    nw = min(bw, int(round(w * scale)))
    if (nh, nw) != (h, w):
        if cv2 is not None:  # ~3x PIL for bilinear resize; releases the GIL
            image = cv2.resize(image, (nw, nh), interpolation=cv2.INTER_LINEAR)
        else:
            from PIL import Image

            image = np.asarray(
                Image.fromarray(image).resize((nw, nh), Image.BILINEAR),
                dtype=np.uint8,
            )
    return image, scale


def load_example(
    dataset: CocoDataset,
    record: ImageRecord,
    config: PipelineConfig,
    rng: np.random.Generator | None,
    bucket: tuple[int, int],
) -> tuple[np.ndarray, np.ndarray, np.ndarray, float]:
    """Decode + (train-only) flip + resize one image into ``bucket``.

    Returns (image HWC — raw uint8 by default, f32 normalized when
    ``config.host_normalize`` — boxes (N,4) resized, labels, scale).
    The image is NOT yet padded to the bucket, but is guaranteed to fit it:
    when no bucket fits the reference resize rule (extreme aspect ratios),
    the scale is capped so the image fits the one the producer chose.
    """
    from PIL import Image

    with Image.open(dataset.image_path(record)) as im:
        image = np.asarray(im.convert("RGB"), dtype=np.uint8)
    boxes = record.boxes.copy()
    labels = record.labels.copy()
    h, w = image.shape[:2]

    if rng is not None and config.transform is not None:
        image, boxes, labels = apply_random_transform(
            image, boxes, labels, config.transform, rng
        )
    elif rng is not None and config.hflip_prob > 0 and rng.random() < config.hflip_prob:
        image = image[:, ::-1]
        x1 = boxes[:, 0].copy()
        boxes[:, 0] = w - boxes[:, 2]
        boxes[:, 2] = w - x1

    image, scale = resize_for_bucket(
        image, bucket, config.min_side, config.max_side
    )
    if image.shape[:2] != (h, w):
        boxes = boxes * scale
    if config.host_normalize:
        image = image.astype(np.float32)
        image *= _NORM_SCALE
        image -= _NORM_OFFSET
    return image, boxes, labels, scale


_PAD_TEMPLATES: dict[tuple[int, int], np.ndarray] = {}


def _pad_template(bh: int, bw: int) -> np.ndarray:
    """Contiguous (bh, bw, 3) uint8 array of the pad pixel, cached per
    bucket shape.

    Assigning the raw (3,) ``_PAD_PIXEL`` into a strided destination takes
    numpy's generic inner loop — measured 21 ms/batch at the flagship
    bucket, dwarfing the actual image copies (~5 ms) and, in the thread
    path, all of it spent HOLDING THE GIL inside the producer.  Copying
    from a materialized template is a plain strided memcpy (~1 ms).
    """
    tmpl = _PAD_TEMPLATES.get((bh, bw))
    if tmpl is None:
        tmpl = np.empty((bh, bw, 3), dtype=np.uint8)
        for c in range(3):
            tmpl[..., c] = _PAD_PIXEL[c]
        _PAD_TEMPLATES[(bh, bw)] = tmpl
    return tmpl


def _assemble(
    examples: list[tuple[np.ndarray, np.ndarray, np.ndarray, float]],
    image_ids: list[int],
    bucket: tuple[int, int],
    config: PipelineConfig,
    stats: PipelineStats | None = None,
) -> Batch:
    b = len(examples)
    bh, bw = bucket
    if config.host_normalize:
        images = np.zeros((b, bh, bw, 3), dtype=np.float32)
    else:
        # Pad with the dataset-mean pixel == ~0.0 in normalized space (the
        # reference padded with zeros AFTER preprocessing).  Only the pad
        # MARGINS are filled below — at the flagship bucket the image covers
        # most of the slot, so a full-slab prefill would roughly double the
        # assembly's memory traffic for bytes that are then overwritten.
        images = np.empty((b, bh, bw, 3), dtype=np.uint8)
    pad = None if config.host_normalize else _pad_template(bh, bw)
    gt_boxes = np.zeros((b, config.max_gt, 4), dtype=np.float32)
    gt_labels = np.zeros((b, config.max_gt), dtype=np.int32)
    gt_mask = np.zeros((b, config.max_gt), dtype=bool)
    scales = np.zeros((b,), dtype=np.float32)
    for i, (img, boxes, labels, scale) in enumerate(examples):
        h, w = img.shape[:2]
        images[i, :h, :w] = img
        if pad is not None:
            if h < bh:
                images[i, h:] = pad[h:]
            if w < bw:
                images[i, :h, w:] = pad[:h, w:]
        n = min(len(boxes), config.max_gt)
        if stats is not None and len(boxes) > n:
            stats.truncated_boxes += len(boxes) - n
            stats.truncated_images += 1
        gt_boxes[i, :n] = boxes[:n]
        gt_labels[i, :n] = labels[:n]
        gt_mask[i, :n] = True
        scales[i] = scale
    return Batch(
        images=images,
        gt_boxes=gt_boxes,
        gt_labels=gt_labels,
        gt_mask=gt_mask,
        image_ids=np.asarray(image_ids, dtype=np.int64),
        scales=scales,
        valid=np.ones((b,), dtype=bool),
    )


def example_rng(
    config: PipelineConfig, train: bool, epoch: int, idx: int
) -> np.random.Generator | None:
    """Per-example PRNG keyed on (seed, epoch, idx) — the determinism
    contract both the thread and multiprocess producers share: an example's
    augmentation depends only on these three ints, never on which worker
    (thread OR process) happened to decode it."""
    if not train:
        return None
    return np.random.default_rng(
        np.random.SeedSequence([config.seed, epoch, idx])
    )


def epoch_indices(
    dataset, config: PipelineConfig, train: bool, epoch: int
) -> list[int]:
    """This shard's record indices for ``epoch``, shuffled per (seed, epoch).

    ``config.exclude_ids`` drops records AFTER the shuffle and before
    sharding: the (seed, epoch) permutation is unchanged, the excluded
    images simply leave holes — so the auto-resume exclusion perturbs the
    stream minimally and deterministically on every shard.
    """
    idx = np.arange(len(dataset.records))
    if train and config.shuffle:
        np.random.default_rng(
            np.random.SeedSequence([config.seed, epoch])
        ).shuffle(idx)
    if config.exclude_ids:
        excluded = {int(i) for i in config.exclude_ids}
        idx = np.asarray(
            [
                i
                for i in idx
                if int(dataset.records[i].image_id) not in excluded
            ],
            dtype=np.int64,
        )
    return list(idx[config.shard_index :: config.shard_count])


def batch_plans(
    dataset, config: PipelineConfig, train: bool, epoch: int
) -> Iterator[tuple[tuple[int, int], list[int], list[int], bool]]:
    """Deterministic batch composition for one epoch, shared by the thread
    and multiprocess producers so their emission order is identical by
    construction: yields (bucket, record_indices, image_ids, short) in the
    exact order batches are emitted."""
    indices = epoch_indices(dataset, config, train, epoch)
    by_bucket: dict[tuple[int, int], list[int]] = {}
    for i in indices:
        r = dataset.records[i]
        by_bucket.setdefault(
            bucket_for_source(
                r.height, r.width, config.min_side, config.max_side,
                config.buckets,
            ),
            [],
        ).append(i)
    for bucket, idxs in by_bucket.items():
        for start in range(0, len(idxs), config.batch_size):
            chunk = idxs[start : start + config.batch_size]
            if len(chunk) < config.batch_size and (
                train and config.drop_remainder
            ):
                continue
            ids = [dataset.records[i].image_id for i in chunk]
            short = not train and len(chunk) < config.batch_size
            yield bucket, chunk, ids, short


def _warn_truncation(dataset, config: PipelineConfig) -> None:
    over = sum(1 for r in dataset.records if len(r.boxes) > config.max_gt)
    if over:
        logger.warning(
            "max_gt=%d truncates %d/%d images (dataset max %d boxes/image); "
            "overflow boxes are DROPPED from training targets. Pass an "
            "explicit larger --max-gt to keep them.",
            config.max_gt, over, len(dataset.records), dataset_max_gt(dataset),
        )


class _PipelineIterator:
    """Iterator over batches exposing live ``stats`` (PipelineStats)."""

    def __init__(
        self, gen: Iterator[Batch], stats: PipelineStats, stop: threading.Event
    ):
        self._gen = gen
        self._stop = stop
        self.stats = stats

    def __iter__(self):
        return self

    def __next__(self) -> Batch:
        return next(self._gen)

    def close(self) -> None:
        """Stop the producer thread.

        Signals the stop event directly (generator ``.close()`` alone is a
        no-op on a never-started generator, which would leak the producer).
        """
        self._stop.set()
        self._gen.close()


def build_pipeline(
    dataset: CocoDataset,
    config: PipelineConfig,
    train: bool = True,
) -> _PipelineIterator:
    """Infinite (train) or single-epoch (eval) iterator of bucketed batches.

    Train: shuffles per epoch, groups records by bucket, yields full batches.
    Eval: preserves order, no augmentation, pads the final batch with
    ``valid=False`` rows so every record is evaluated exactly once.

    ``config.num_worker_procs > 0`` routes to the multiprocess shared-memory
    producer (shm_pipeline.py) — same batches, bit-identical for a fixed
    seed, decoded by worker processes instead of GIL-bound threads.
    """
    _warn_truncation(dataset, config)
    if config.num_worker_procs > 0:
        from batchai_retinanet_horovod_coco_tpu.data.shm_pipeline import (
            build_shm_pipeline,
        )

        return build_shm_pipeline(dataset, config, train)
    stats = PipelineStats()

    out: queue.Queue = queue.Queue(maxsize=max(1, config.prefetch))
    stop = threading.Event()
    _SENTINEL = object()

    def _put(item) -> bool:
        return stop_gated_put(out, item, stop)

    def producer() -> None:
        # watchdog-exempt (pool): decode-pool threads surface through
        # future.result() on THIS (registered) thread — a wedged decode
        # stalls the producer heartbeat, which is the attributable signal.
        pool = ThreadPoolExecutor(max_workers=config.num_workers)
        hb = watchdog.register(
            "pipe-producer", details=lambda: {"qsize": out.qsize()}
        )
        try:
            _produce(pool, hb)
        except BaseException as exc:  # propagate to the consumer; never hang
            _put(exc)
        finally:
            hb.close()
            pool.shutdown(wait=False)

    def _produce(pool: ThreadPoolExecutor, hb) -> None:
            from collections import deque

            # Keep several batches' decode futures in flight so the pool
            # never drains at a batch boundary (the naive submit-one-batch/
            # wait/assemble loop caps parallelism at batch_size and measured
            # ~11 imgs/s regardless of worker count).  Batches are EMITTED
            # in submission order — determinism is unchanged.
            max_inflight = max(
                2, -(-config.num_workers // max(1, config.batch_size)) + 1
            )
            inflight: deque = deque()

            def flush_one() -> bool:
                futures, ids, bucket, short = inflight.popleft()
                with trace.span("pipe_decode_wait"):
                    examples = [f.result() for f in futures]
                hb.beat()  # decode progress = fleet liveness
                with trace.span("pipe_assemble"):
                    batch = _assemble(examples, ids, bucket, config, stats)
                if short:
                    batch = _pad_batch(batch, config.batch_size)
                hb.idle()  # a full output queue is backpressure, not a stall
                ok = _put(batch)
                hb.beat()
                return ok

            epoch = 0
            # Elastic resume: already-consumed batches are skipped at the
            # PLAN level — no decode, no RNG draw, just plan arithmetic —
            # so fast-forwarding to step r costs milliseconds, not a
            # replay of r batches of JPEG work.
            to_skip = config.skip_batches if train else 0
            while not stop.is_set():
                for bucket, chunk, ids, short in batch_plans(
                    dataset, config, train, epoch
                ):
                    if to_skip > 0:
                        to_skip -= 1
                        continue
                    futures = [
                        pool.submit(
                            load_example,
                            dataset,
                            dataset.records[i],
                            config,
                            example_rng(config, train, epoch, int(i)),
                            bucket,
                        )
                        for i in chunk
                    ]
                    inflight.append((futures, ids, bucket, short))
                    if len(inflight) >= max_inflight and not flush_one():
                        return
                if not train:
                    while inflight:
                        if not flush_one():
                            return
                    _put(_SENTINEL)
                    return
                epoch += 1

    # watchdog: registers in producer() at thread start.
    thread = threading.Thread(
        target=producer, daemon=True, name="pipe-producer"
    )
    thread.start()

    def iterate() -> Iterator[Batch]:
        try:
            while True:
                item = out.get()
                if item is _SENTINEL:
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            stop.set()

    return _PipelineIterator(iterate(), stats, stop)


def _pad_batch(batch: Batch, batch_size: int) -> Batch:
    """Pad a short eval batch to full size with valid=False rows."""
    b = batch.images.shape[0]
    pad = batch_size - b

    def pad0(x: np.ndarray) -> np.ndarray:
        widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
        return np.pad(x, widths)

    return Batch(
        images=pad0(batch.images),
        gt_boxes=pad0(batch.gt_boxes),
        gt_labels=pad0(batch.gt_labels),
        gt_mask=pad0(batch.gt_mask),
        image_ids=pad0(batch.image_ids),
        scales=pad0(batch.scales),
        valid=np.concatenate([batch.valid, np.zeros(pad, dtype=bool)]),
    )

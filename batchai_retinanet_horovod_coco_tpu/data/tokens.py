"""A seeded source of packed token sequences for the language-model task.

Documents of heavy-tailed length arrive in a stream and are packed in
arrival order into sequences of ``seq_len`` tokens: no padding, the document
that meets a sequence's end is cut there and its rest opens the next
sequence (a document of its own to the model, which sees no further back
than the sequence).  ``segment_ids`` number a sequence's documents from 0,
which is all the model is told of the boundaries: the state-space scan
resets, attention and the loss stay inside a document
(models/granite_hybrid.py).

Synthetic: token ids are uniform over the vocabulary and lengths log-normal,
enough to drive the step at the shapes and the boundary statistics of
packed web or code documents.  Batches are host numpy, fed through the
loop's device-prefetch thread like image batches (train/loop.py).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Iterator, NamedTuple

import numpy as np

from batchai_retinanet_horovod_coco_tpu.obs import trace


class TokenBatch(NamedTuple):
    tokens: np.ndarray  # (B, T) int32
    segment_ids: np.ndarray  # (B, T) int32: 0, 1, 2, ... per document of a row
    sequence_ids: np.ndarray  # (B,) int64: position of the row in the stream


@dataclasses.dataclass(frozen=True)
class PackedTokensConfig:
    vocab_size: int
    seq_len: int
    batch_size: int = 1
    doc_len_median: float = 512.0
    doc_len_sigma: float = 1.3  # of the logarithm
    doc_len_min: int = 16  # and at most seq_len
    seed: int = 0
    # None: ``seed`` draws the documents' lengths and then the token ids, from one generator.  A number: the
    # lengths come from a generator of their own with this seed, so ``segment_ids`` are the same for every
    # ``seed``, which then moves the token ids alone (a benchmark cell whose work follows the packing).
    layout_seed: int | None = None


def packed_token_batches(config: PackedTokensConfig) -> Iterator[TokenBatch]:
    """The endless stream of batches; the same config gives the same stream."""
    rng = np.random.default_rng(config.seed)
    lengths = rng if config.layout_seed is None else np.random.default_rng(config.layout_seed)
    t = config.seq_len
    left = 0  # tokens of the current document still to place
    row = 0
    while True:
        with trace.span("pack_assemble"):
            segment_ids = np.empty((config.batch_size, t), np.int32)
            for r in range(config.batch_size):
                at = doc = 0
                while at < t:
                    if left == 0:
                        drawn = lengths.lognormal(math.log(config.doc_len_median), config.doc_len_sigma)
                        left = int(np.clip(round(drawn), config.doc_len_min, t))
                    n = min(left, t - at)
                    segment_ids[r, at:at + n] = doc
                    at, left, doc = at + n, left - n, doc + 1
            tokens = rng.integers(0, config.vocab_size, (config.batch_size, t), dtype=np.int32)
            ids = np.arange(row, row + config.batch_size, dtype=np.int64)
            row += config.batch_size
        yield TokenBatch(tokens, segment_ids, ids)

"""``PackedTokensConfig.layout_seed`` (data/tokens.py): ``None`` leaves the stream
of every accepted cell bit for bit what it was (against the generator as the
parent commit had it, written out here), and a number fixes the packing for every
``seed``, which then draws the token ids alone."""

import itertools
import math

import numpy as np
import pytest

from batchai_retinanet_horovod_coco_tpu.data.tokens import PackedTokensConfig, packed_token_batches


def parents_stream(vocab_size, seq_len, batch_size, doc_len_median, doc_len_sigma, doc_len_min, seed):
    """``packed_token_batches`` as PR 39's tree had it: one generator draws the
    lengths and then the ids."""
    rng = np.random.default_rng(seed)
    left = 0
    while True:
        segment_ids = np.empty((batch_size, seq_len), np.int32)
        for r in range(batch_size):
            at = doc = 0
            while at < seq_len:
                if left == 0:
                    drawn = rng.lognormal(math.log(doc_len_median), doc_len_sigma)
                    left = int(np.clip(round(drawn), doc_len_min, seq_len))
                n = min(left, seq_len - at)
                segment_ids[r, at:at + n] = doc
                at, left, doc = at + n, left - n, doc + 1
        yield rng.integers(0, vocab_size, (batch_size, seq_len), dtype=np.int32), segment_ids


# the accepted language-model cells' parameters (their traffic files and configurations' vocabulary slices)
CELLS = {
    "granite-h-train-pack8k": dict(vocab_size=12544, seq_len=8192, batch_size=1, doc_len_median=512, doc_len_sigma=1.3,
                                   doc_len_min=16),
    "dsv2-lite-train-pack8k": dict(vocab_size=12800, seq_len=8192, batch_size=2, doc_len_median=512, doc_len_sigma=1.3,
                                   doc_len_min=16),
    "keye-vl2-train-doc16k": dict(vocab_size=18992, seq_len=16384, batch_size=1, doc_len_median=512, doc_len_sigma=1.3,
                                  doc_len_min=16384),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("seed", [0, 2100415840, 2**31 + 12345])
def test_without_a_layout_seed_the_stream_is_the_parents_bit_for_bit(cell, seed):
    kw = CELLS[cell]
    ours = packed_token_batches(PackedTokensConfig(seed=seed, **kw))
    for batch, (tokens, segment_ids) in zip(itertools.islice(ours, 4), parents_stream(seed=seed, **kw)):
        np.testing.assert_array_equal(batch.tokens, tokens)
        np.testing.assert_array_equal(batch.segment_ids, segment_ids)
        assert batch.tokens.dtype == batch.segment_ids.dtype == np.int32


@pytest.mark.parametrize("seed", [0, 1, 905418237, 2**31 + 12345])
def test_a_layout_seed_fixes_the_packing_and_the_seed_draws_the_ids_alone(seed):
    kw = dict(vocab_size=128, seq_len=64, batch_size=2, doc_len_median=16, doc_len_min=4)
    pool = lambda **more: list(itertools.islice(packed_token_batches(PackedTokensConfig(**kw, **more)), 4))
    fixed, other = pool(seed=seed, layout_seed=7), pool(seed=seed + 1, layout_seed=7)
    for a, b in zip(fixed, other):
        np.testing.assert_array_equal(a.segment_ids, b.segment_ids)
    assert not np.array_equal(fixed[0].tokens, other[0].tokens)
    # the lengths are the layout seed's: another one packs otherwise, and the lengths of the seed itself are not used
    assert not all(np.array_equal(a.segment_ids, b.segment_ids) for a, b in zip(fixed, pool(seed=seed, layout_seed=8)))
    assert not all(np.array_equal(a.segment_ids, b.segment_ids) for a, b in zip(fixed, pool(seed=7)))
    # the ids are the first numbers the seed's generator draws (nothing of it went into lengths)
    np.testing.assert_array_equal(fixed[0].tokens, np.random.default_rng(seed).integers(0, 128, (2, 64), dtype=np.int32))
    for batch in fixed:
        for row in batch.segment_ids:
            assert row[0] == 0 and set(np.diff(row)) <= {0, 1}  # contiguous documents, no padding


def test_the_same_configuration_gives_the_same_stream_with_a_layout_seed():
    cfg = PackedTokensConfig(vocab_size=128, seq_len=64, batch_size=1, seed=3, layout_seed=20261001)
    for a, b in zip(itertools.islice(packed_token_batches(cfg), 3), packed_token_batches(cfg)):
        np.testing.assert_array_equal(a.tokens, b.tokens)
        np.testing.assert_array_equal(a.segment_ids, b.segment_ids)

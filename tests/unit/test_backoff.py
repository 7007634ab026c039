"""utils/backoff.py — the one retry/backoff schedule (ISSUE 12 satellite).

The schedule is pinned EXACTLY: geometric growth, ceiling clamp,
explicit-schedule override (the bench probe's env grammar), and
deterministic-seeded jitter — same (policy, attempt) always means the
same delay, different seeds decorrelate.
"""

from __future__ import annotations

import pytest

from batchai_retinanet_horovod_coco_tpu.utils.backoff import BackoffPolicy


class TestSchedule:
    def test_geometric_with_ceiling_exact(self):
        p = BackoffPolicy(
            max_tries=6, base_s=0.5, multiplier=2.0, ceiling_s=3.0
        )
        assert p.delays() == [0.5, 1.0, 2.0, 3.0, 3.0]

    def test_single_try_has_no_sleeps(self):
        assert BackoffPolicy(max_tries=1).delays() == []

    def test_explicit_schedule_reuses_last_value(self):
        p = BackoffPolicy(max_tries=5, schedule=(10.0, 30.0))
        assert p.delays() == [10.0, 30.0, 30.0, 30.0]

    def test_delay_is_pure_per_attempt(self):
        p = BackoffPolicy(max_tries=4, base_s=1.0, jitter=0.3, seed=42)
        # Same (policy, attempt) → same delay, in any call order.
        assert p.delay_s(2) == p.delay_s(2)
        assert p.delays() == [p.delay_s(0), p.delay_s(1), p.delay_s(2)]

    def test_jitter_deterministic_per_seed_and_bounded(self):
        a = BackoffPolicy(max_tries=8, base_s=1.0, multiplier=1.0,
                          jitter=0.2, seed=1)
        b = BackoffPolicy(max_tries=8, base_s=1.0, multiplier=1.0,
                          jitter=0.2, seed=1)
        c = BackoffPolicy(max_tries=8, base_s=1.0, multiplier=1.0,
                          jitter=0.2, seed=2)
        assert a.delays() == b.delays()  # reproducible
        assert a.delays() != c.delays()  # decorrelated across seeds
        for d in a.delays():  # bounded by the jitter fraction
            assert 0.8 <= d <= 1.2

    def test_huge_attempt_counts_never_overflow(self):
        """A breaker probing a permanently dead replica grows its open
        count without bound; the geometric term must saturate at the
        ceiling, not overflow a float (2.0**1024 does)."""
        p = BackoffPolicy(
            max_tries=1_000_000, base_s=0.5, multiplier=2.0, ceiling_s=10.0
        )
        assert p.delay_s(1024) == 10.0
        assert p.delay_s(10_000_000) == 10.0
        jittered = BackoffPolicy(
            max_tries=1_000_000, base_s=0.5, multiplier=2.0,
            ceiling_s=10.0, jitter=0.2, seed=5,
        )
        assert 8.0 <= jittered.delay_s(5000) <= 12.0

    def test_zero_jitter_is_exact(self):
        p = BackoffPolicy(max_tries=3, base_s=2.0, multiplier=3.0,
                          ceiling_s=100.0, jitter=0.0, seed=99)
        assert p.delays() == [2.0, 6.0]

    def test_invalid_configs_raise(self):
        with pytest.raises(ValueError):
            BackoffPolicy(max_tries=0)
        with pytest.raises(ValueError):
            BackoffPolicy(jitter=1.0)
        with pytest.raises(ValueError):
            BackoffPolicy(schedule=())


class TestRetry:
    def test_retry_sleeps_the_exact_schedule(self):
        p = BackoffPolicy(max_tries=4, base_s=0.5, multiplier=2.0,
                          ceiling_s=10.0)
        slept: list[float] = []
        results = iter(["down", "down", "down", "down"])
        attempts, last = p.retry(
            lambda: next(results), sleep=slept.append
        )
        assert attempts == 4
        assert last == "down"
        assert slept == [0.5, 1.0, 2.0]  # max_tries - 1 sleeps, exact

    def test_retry_stops_on_success(self):
        p = BackoffPolicy(max_tries=5, base_s=1.0)
        slept: list[float] = []
        results = iter(["down", None])
        attempts, last = p.retry(lambda: next(results), sleep=slept.append)
        assert attempts == 2 and last is None
        assert slept == [1.0]  # only the sleep before the success

    def test_retry_custom_ok_predicate(self):
        p = BackoffPolicy(max_tries=3, base_s=0.1)
        attempts, last = p.retry(
            lambda: 7, ok=lambda r: r == 7, sleep=lambda _s: None
        )
        assert attempts == 1 and last == 7

"""Invariant lint engine tests (ISSUE 5): per-rule bite fixtures, the
uniform suppression grammar, the committed non-growing baseline, the
clean-run over the live tree, and the audit_collectives async dedupe.

Contract mirrored from test_obs.py::test_audit_threads_clean: each rule
must FLAG a minimal bad snippet (the "bite" test) and PASS its suppressed
twin, and the live tree must be clean against the committed baseline —
so deleting any package-side compliance (unbounding a serve queue,
removing a rationale) fails tier-1, not just ``make lint``.

jax-free by design: the analysis package is stdlib-only and these tests
never compile a program.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import textwrap
import threading

import pytest

from batchai_retinanet_horovod_coco_tpu.analysis import engine
from batchai_retinanet_horovod_coco_tpu.utils import locks

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def run_rule(source: str, rule: str, in_package: bool = True):
    """Lint one snippet with one rule; returns the FileResult."""
    return engine.lint_source(
        "snippet.py", "snippet.py", textwrap.dedent(source),
        rule_names=[rule], in_package=in_package,
    )


def findings(source: str, rule: str, in_package: bool = True):
    return run_rule(source, rule, in_package).findings


# ---- bounded-queues ------------------------------------------------------


class TestBoundedQueues:
    def test_bites_on_unbounded_queue(self):
        got = findings(
            """
            import queue
            q = queue.Queue()
            """,
            "bounded-queues",
        )
        assert len(got) == 1 and "maxsize" in got[0].message

    def test_suppressed_twin_passes(self):
        res = run_rule(
            """
            import queue
            # lint: bounded-queues: drained synchronously before returning
            q = queue.Queue()
            """,
            "bounded-queues",
        )
        assert res.findings == [] and len(res.suppressed) == 1

    def test_maxsize_positional_keyword_and_mp_context(self):
        ok = """
        import queue
        a = queue.Queue(8)
        b = queue.Queue(maxsize=4)
        c = ctx.Queue(maxsize=2)
        """
        assert findings(ok, "bounded-queues") == []

    def test_maxsize_zero_is_still_unbounded(self):
        """Stdlib semantics: maxsize <= 0 means infinite — spelling the
        unboundedness explicitly must not lint clean."""
        for src in ("queue.Queue(0)", "queue.Queue(maxsize=0)",
                    "queue.Queue(maxsize=-1)"):
            got = findings(f"import queue\nq = {src}\n", "bounded-queues")
            assert len(got) == 1 and "infinite" in got[0].message, src

    def test_simple_queue_always_flagged(self):
        got = findings(
            """
            from queue import SimpleQueue
            q = SimpleQueue()
            """,
            "bounded-queues",
        )
        assert len(got) == 1 and "no capacity bound" in got[0].message


# ---- thread-error-contract -----------------------------------------------


class TestThreadErrorContract:
    def test_bites_on_target_without_forwarding(self):
        got = findings(
            """
            import threading

            def runner():
                while True:
                    work()

            t = threading.Thread(target=runner)
            """,
            "thread-error-contract",
        )
        assert len(got) == 1 and "no broad except" in got[0].message

    def test_bites_on_swallowed_crash(self):
        got = findings(
            """
            import threading

            def runner():
                try:
                    work()
                except Exception:
                    pass

            t = threading.Thread(target=runner)
            """,
            "thread-error-contract",
        )
        # Both defects: the swallow AND the absence of a forwarding handler.
        assert len(got) == 2
        assert any("swallows" in f.message for f in got)

    def test_forwarding_target_passes(self):
        ok = """
        import threading

        def runner(out):
            try:
                work()
            except BaseException as e:
                out.put(e)

        t = threading.Thread(target=runner, args=(q,))
        """
        assert findings(ok, "thread-error-contract") == []

    def test_narrow_except_pass_is_legal(self):
        ok = """
        import queue
        import threading

        def runner(q, out):
            try:
                while True:
                    try:
                        q.get(timeout=1)
                    except queue.Empty:
                        pass
            except BaseException as e:
                out.put(e)

        t = threading.Thread(target=runner)
        """
        assert findings(ok, "thread-error-contract") == []

    def test_suppressed_twin_passes(self):
        res = run_rule(
            """
            import threading

            def runner():
                while True:
                    work()

            # lint: thread-error-contract: fire-and-forget beeper, crash harmless
            t = threading.Thread(target=runner)
            """,
            "thread-error-contract",
        )
        assert res.findings == [] and len(res.suppressed) == 1

    def test_swallow_finding_suppressed_at_handler_line(self):
        """The broad-except-swallows finding anchors at the handler, so
        (per the rule docstring) the suppression goes on/above the
        ``except`` line — the spawn-site comment covers the companion
        no-forwarding finding."""
        res = run_rule(
            """
            import threading

            def runner():
                try:
                    work()
                # lint: thread-error-contract: crash surfaced by probe timeout
                except Exception:
                    pass

            # lint: thread-error-contract: fire-and-forget beeper, crash harmless
            t = threading.Thread(target=runner)
            """,
            "thread-error-contract",
        )
        assert res.findings == [], res.findings
        assert len(res.suppressed) == 2

    def test_resolves_methods_and_partial(self):
        got = findings(
            """
            import functools
            import threading

            class P:
                def _producer(self):
                    while True:
                        work()

                def start(self):
                    self._t = threading.Thread(
                        target=functools.partial(self._producer)
                    )
            """,
            "thread-error-contract",
        )
        assert len(got) == 1 and "_producer" in got[0].message


# ---- jit-purity ----------------------------------------------------------


class TestJitPurity:
    def test_bites_on_time_in_jitted_def(self):
        got = findings(
            """
            import time
            import jax

            def step(x):
                t0 = time.time()
                return x + t0

            step_c = jax.jit(step)
            """,
            "jit-purity",
        )
        assert len(got) == 1 and "time.time()" in got[0].message

    def test_bites_on_print_in_decorated_fn(self):
        got = findings(
            """
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("n",))
            def step(x, n):
                print(x)
                return x * n
            """,
            "jit-purity",
        )
        assert len(got) == 1 and "print()" in got[0].message

    def test_bites_on_item_and_np_random_in_shard_map(self):
        got = findings(
            """
            import numpy as np
            from jax import shard_map

            def step(x):
                noise = np.random.rand(4)
                return x.item() + noise

            f = shard_map(step, mesh=None, in_specs=None, out_specs=None)
            """,
            "jit-purity",
        )
        assert len(got) == 2
        assert any("host RNG" in f.message for f in got)
        assert any(".item()" in f.message for f in got)

    def test_pure_fn_and_jax_debug_print_pass(self):
        ok = """
        import jax

        def step(x):
            jax.debug.print("x = {}", x)
            return x * 2

        step_c = jax.jit(step)
        lam = jax.jit(lambda images: images + 1)
        """
        assert findings(ok, "jit-purity") == []

    def test_suppressed_twin_passes(self):
        res = run_rule(
            """
            import jax

            def step(x):
                # lint: jit-purity: trace-time banner, intentionally once
                print("tracing step")
                return x

            step_c = jax.jit(step)
            """,
            "jit-purity",
        )
        assert res.findings == [] and len(res.suppressed) == 1

    def test_pure_callback_subtree_is_sanctioned(self):
        """ISSUE 20: jax.pure_callback / io_callback are THE supported
        host-escape hatches — host effects inside their callback argument
        run outside the trace by contract and must not be flagged."""
        ok = """
        import jax
        from jax.experimental import io_callback

        def step(x):
            y = jax.pure_callback(lambda v: print(v), x.dtype, x)
            io_callback(lambda v: open("/tmp/l", "a").write(str(v)), None, y)
            return y

        step_c = jax.jit(step)
        """
        assert findings(ok, "jit-purity") == []

    def test_host_effect_outside_callback_still_bites(self):
        """The sanction covers ONLY the callback call's subtree."""
        got = findings(
            """
            import jax

            def step(x):
                print("tracing")
                y = jax.pure_callback(lambda v: print(v), x.dtype, x)
                return y

            step_c = jax.jit(step)
            """,
            "jit-purity",
        )
        assert len(got) == 1 and "print()" in got[0].message

    def test_lru_cache_on_jitted_fn_bites(self):
        got = findings(
            """
            import functools
            import jax

            @jax.jit
            @functools.lru_cache(maxsize=None)
            def step(x):
                return x * 2
            """,
            "jit-purity",
        )
        assert len(got) == 1
        assert "lru_cache" in got[0].message
        assert "tracer" in got[0].message

    def test_lru_cache_via_call_form_bites(self):
        got = findings(
            """
            import functools
            import jax

            @functools.cache
            def step(x):
                return x * 2

            step_c = jax.jit(step)
            """,
            "jit-purity",
        )
        assert len(got) == 1 and "functools.cache" in got[0].message

    def test_lru_cache_suppressed_twin_passes(self):
        res = run_rule(
            """
            import functools
            import jax

            @jax.jit
            # lint: jit-purity: keyed on static python ints only
            @functools.lru_cache(maxsize=8)
            def step(x):
                return x * 2
            """,
            "jit-purity",
        )
        assert res.findings == [] and len(res.suppressed) == 1


# ---- monotonic-clock -----------------------------------------------------


class TestMonotonicClock:
    def test_bites_on_time_time(self):
        got = findings("import time\nt0 = time.time()\n", "monotonic-clock")
        assert len(got) == 1 and "monotonic_s" in got[0].message

    def test_bites_on_from_import_alias(self):
        got = findings(
            "from time import time as now\nt0 = now()\n", "monotonic-clock"
        )
        assert len(got) == 1

    def test_second_clock_banned_in_package_only(self):
        src = "import time\nt0 = time.perf_counter()\n"
        assert len(findings(src, "monotonic-clock", in_package=True)) == 1
        assert findings(src, "monotonic-clock", in_package=False) == []

    def test_suppressed_twin_passes(self):
        res = run_rule(
            """
            import time
            stamp = time.time()  # lint: monotonic-clock: run header wall time
            """,
            "monotonic-clock",
        )
        assert res.findings == [] and len(res.suppressed) == 1


# ---- collective-safety ---------------------------------------------------


class TestCollectiveSafety:
    def test_bites_on_rank_conditional_collective(self):
        got = findings(
            """
            import jax
            from jax import lax

            def step(x):
                if jax.process_index() == 0:
                    x = lax.psum(x, "data")
                return x
            """,
            "collective-safety",
        )
        assert len(got) == 1 and "process_index" in got[0].message

    def test_bites_in_else_branch_and_ternary(self):
        got = findings(
            """
            from jax import lax

            def step(x, rank):
                if rank == 0:
                    y = x
                else:
                    y = lax.pmean(x, "data")
                z = lax.psum(x, "data") if rank else x
                return y + z
            """,
            "collective-safety",
        )
        assert len(got) == 2

    def test_unconditional_and_host_side_rank_work_pass(self):
        ok = """
        import jax
        from jax import lax

        def step(x):
            x = lax.pmean(x, "data")
            if jax.process_index() == 0:
                log_metrics(x)
            return x
        """
        assert findings(ok, "collective-safety") == []

    def test_suppressed_twin_passes(self):
        res = run_rule(
            """
            from jax import lax

            def step(x, rank):
                if rank >= 0:
                    # lint: collective-safety: condition replica-identical by construction
                    x = lax.psum(x, "data")
                return x
            """,
            "collective-safety",
        )
        assert res.findings == [] and len(res.suppressed) == 1


# ---- watchdog-coverage ---------------------------------------------------


class TestWatchdogCoverage:
    BAD = """
    import threading

    t = threading.Thread(target=print)
    t.start()
    """

    def test_bites_on_unwatched_spawn(self):
        got = findings(self.BAD, "watchdog-coverage")
        assert len(got) == 1 and "watchdog.register" in got[0].message

    def test_legacy_marker_and_register_pass(self):
        ok_marker = """
        import threading

        # watchdog: registers in run() at thread start
        t = threading.Thread(target=print)
        """
        ok_register = """
        import threading
        from batchai_retinanet_horovod_coco_tpu.obs import watchdog

        hb = watchdog.register("worker")
        t = threading.Thread(target=print)
        """
        assert findings(ok_marker, "watchdog-coverage") == []
        assert findings(ok_register, "watchdog-coverage") == []

    def test_uniform_suppression_passes(self):
        res = run_rule(
            """
            import threading

            # lint: watchdog-coverage: short-lived helper, joined two lines down
            t = threading.Thread(target=print)
            """,
            "watchdog-coverage",
        )
        assert res.findings == [] and len(res.suppressed) == 1


# ---- atomic-artifacts ----------------------------------------------------


class TestAtomicArtifacts:
    def test_bites_on_rename_free_write(self):
        got = findings(
            """
            import json

            def write_manifest(path, doc):
                with open(path, "w") as f:
                    json.dump(doc, f)
            """,
            "atomic-artifacts",
        )
        assert len(got) == 1 and "rename commit" in got[0].message

    def test_binary_and_exclusive_modes_bite_too(self):
        src = """
        def a(p, data):
            with open(p, "wb") as f:
                f.write(data)

        def b(p, data):
            with open(p, mode="x") as f:
                f.write(data)
        """
        assert len(findings(src, "atomic-artifacts")) == 2

    def test_inline_rename_commit_passes(self):
        got = findings(
            """
            import json
            import os

            def write_manifest(path, doc):
                tmp = path + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(doc, f)
                os.replace(tmp, path)
            """,
            "atomic-artifacts",
        )
        assert got == []

    def test_atomicio_helper_passes(self):
        got = findings(
            """
            import json
            from batchai_retinanet_horovod_coco_tpu.utils.atomicio import (
                atomic_write_text,
            )

            def write_manifest(path, doc, extra):
                atomic_write_text(path, json.dumps(doc))
                with open(path + ".sidecar", "w") as f:
                    f.write(extra)
            """,
            "atomic-artifacts",
        )
        assert got == []

    def test_append_and_read_modes_exempt(self):
        src = """
        def sink(p):
            with open(p, "a") as f:
                f.write("line")
            with open(p) as f:
                return f.read()
        """
        res = run_rule(src, "atomic-artifacts")
        assert res.findings == []
        assert res.stats.get("atomic-artifacts", 0) == 0  # no write-trunc sites

    def test_nested_helper_does_not_sanction_outer_write(self):
        # The rename lives in a DIFFERENT function that shares the module;
        # the outer bare write is still a finding.
        got = findings(
            """
            import os

            def committer(tmp, path):
                os.replace(tmp, path)

            def sloppy(path, text):
                with open(path, "w") as f:
                    f.write(text)
            """,
            "atomic-artifacts",
        )
        assert len(got) == 1 and got[0].line == 8

    def test_suppressed_twin_passes(self):
        res = run_rule(
            """
            def sink(path, text):
                # lint: atomic-artifacts: write-once private temp, unlinked on error
                with open(path, "w") as f:
                    f.write(text)
            """,
            "atomic-artifacts",
        )
        assert res.findings == [] and len(res.suppressed) == 1

    def test_out_of_package_exempt(self):
        got = findings(
            """
            def driver(path):
                with open(path, "w") as f:
                    f.write("bench artifact")
            """,
            "atomic-artifacts",
            in_package=False,
        )
        assert got == []


# ---- suppression grammar -------------------------------------------------


class TestSuppressionGrammar:
    def test_missing_rationale_does_not_suppress_and_is_a_finding(self):
        res = run_rule(
            """
            import queue
            # lint: bounded-queues:
            q = queue.Queue()
            """,
            "bounded-queues",
        )
        assert len(res.findings) == 1  # original finding survives
        assert any(
            "missing rationale" in f.message for f in res.grammar_findings
        )

    def test_unknown_rule_name_is_a_finding(self):
        res = run_rule(
            """
            import queue
            # lint: bounded-quues: typo'd rule name
            q = queue.Queue()
            """,
            "bounded-queues",
        )
        assert len(res.findings) == 1
        assert any("unknown rule" in f.message for f in res.grammar_findings)

    def test_comma_list_and_trailing_comment_placement(self):
        res = run_rule(
            """
            import queue
            import time
            q = queue.Queue()  # lint: bounded-queues, monotonic-clock: both justified here
            """,
            "bounded-queues",
        )
        assert res.findings == [] and len(res.suppressed) == 1

    def test_lint_text_inside_string_is_not_a_suppression(self):
        res = run_rule(
            '''
            import queue
            DOC = """
            # lint: bounded-queues: not a real comment
            """
            q = queue.Queue()
            ''',
            "bounded-queues",
        )
        assert len(res.findings) == 1

    def test_unused_suppressions_reported(self):
        res = run_rule(
            """
            import queue
            # lint: bounded-queues: nothing to suppress here
            q = queue.Queue(maxsize=4)
            """,
            "bounded-queues",
        )
        assert len(res.unused_suppressions) == 1


# ---- baseline mechanics --------------------------------------------------


class TestBaseline:
    def _write_tree(self, tmp_path, bounded: bool):
        pkg = tmp_path / engine.PACKAGE_NAME
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        size = "maxsize=4" if bounded else ""
        (pkg / "mod.py").write_text(
            f"import queue\nq = queue.Queue({size})\n"
        )
        return tmp_path

    def test_grandfathered_finding_passes(self, tmp_path):
        root = self._write_tree(tmp_path, bounded=False)
        bl = tmp_path / "baseline.json"
        engine.write_baseline(str(bl), [engine.Finding(
            rule="bounded-queues",
            path=os.path.join(engine.PACKAGE_NAME, "mod.py"),
            line=2, message="", snippet="q = queue.Queue()",
        )])
        report = engine.run(str(root), baseline_path=str(bl))
        assert report["ok"], report
        assert len(report["grandfathered"]) == 1 and report["new"] == []

    def test_new_finding_fails(self, tmp_path):
        root = self._write_tree(tmp_path, bounded=False)
        bl = tmp_path / "baseline.json"
        engine.write_baseline(str(bl), [])
        report = engine.run(str(root), baseline_path=str(bl))
        assert not report["ok"] and len(report["new"]) == 1

    def test_stale_baseline_entry_fails(self, tmp_path):
        """Non-growing: a FIXED finding must be removed from the baseline."""
        root = self._write_tree(tmp_path, bounded=True)
        bl = tmp_path / "baseline.json"
        engine.write_baseline(str(bl), [engine.Finding(
            rule="bounded-queues",
            path=os.path.join(engine.PACKAGE_NAME, "mod.py"),
            line=2, message="", snippet="q = queue.Queue()",
        )])
        report = engine.run(str(root), baseline_path=str(bl))
        assert not report["ok"] and len(report["stale_baseline"]) == 1

    def test_baseline_is_line_insensitive(self, tmp_path):
        root = self._write_tree(tmp_path, bounded=False)
        mod = root / engine.PACKAGE_NAME / "mod.py"
        mod.write_text("import queue\n\n\n\n" + "q = queue.Queue()\n")
        bl = tmp_path / "baseline.json"
        engine.write_baseline(str(bl), [engine.Finding(
            rule="bounded-queues",
            path=os.path.join(engine.PACKAGE_NAME, "mod.py"),
            line=2, message="", snippet="q = queue.Queue()",
        )])
        report = engine.run(str(root), baseline_path=str(bl))
        assert report["ok"], report


# ---- the live tree -------------------------------------------------------


class TestLiveTree:
    def test_tree_is_clean(self):
        """Tier-1 wiring of the whole engine: the repo lints clean against
        the committed baseline — new violations (e.g. unbounding a serve
        queue, a fresh time.time(), a rank-guarded psum) fail HERE, not
        just in ``make lint``."""
        report = engine.run(REPO_ROOT)
        assert report["new"] == [], report["new"]
        assert report["stale_baseline"] == [], report["stale_baseline"]
        assert report["ok"]

    def test_scan_is_not_vacuous(self):
        """Every rule actually inspected real constructs in this tree (a
        rule that silently stops matching would otherwise pass forever)."""
        report = engine.run(REPO_ROOT)
        stats = report["stats"]
        assert report["files_scanned"] >= 80, report["files_scanned"]
        assert stats.get("bounded-queues", 0) >= 9, stats
        assert stats.get("thread-error-contract", 0) >= 8, stats
        assert stats.get("jit-purity", 0) >= 10, stats
        assert stats.get("monotonic-clock", 0) >= 3, stats
        assert stats.get("collective-safety", 0) >= 10, stats
        assert stats.get("watchdog-coverage", 0) >= 12, stats
        # Most artifact writers now go through utils.atomicio (no raw
        # open); the floor covers the surviving inline tmp+rename sites
        # (anchor sidecar, trace export, perf report, numerics dump,
        # checkpoint writer).
        assert stats.get("atomic-artifacts", 0) >= 5, stats
        # ISSUE 20 project rules: acceptance floors — the lock graph must
        # resolve real acquisition sites and the vocabulary checker must
        # see real emit sites (live counts: ~133 / ~69 / ~205).
        assert stats.get("lock-order", 0) >= 20, stats
        assert stats.get("event-vocabulary", 0) >= 40, stats
        assert stats.get("lock-held-blocking", 0) >= 50, stats
        assert len(report["exports"]["lock_identities"]) >= 15, (
            report["exports"]["lock_identities"])

    def test_compliance_is_load_bearing(self):
        """Removing one package-side compliance makes the engine fail:
        strip the shm pipeline's bounded-queues rationales and the two
        mp.Queue constructions become NEW findings (the acceptance
        criterion's 'deleting any one rule's compliance' probe)."""
        path = os.path.join(
            REPO_ROOT, engine.PACKAGE_NAME, "data", "shm_pipeline.py"
        )
        with open(path) as f:
            src = f.read()
        stripped = "\n".join(
            line for line in src.splitlines()
            if "# lint: bounded-queues:" not in line
        )
        res = engine.lint_source(path, "data/shm_pipeline.py", stripped,
                                 rule_names=["bounded-queues"])
        assert len(res.findings) == 2, res.findings

    def test_cli_json_and_exit_code(self):
        proc = subprocess.run(
            [sys.executable, "-m",
             "batchai_retinanet_horovod_coco_tpu.analysis", "--json"],
            capture_output=True, text=True, cwd=REPO_ROOT, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
        report = json.loads(proc.stdout.strip().splitlines()[-1])
        assert report["ok"]
        assert set(report["rules"]) == set(engine.all_rule_names())
        assert set(report["rules"]) >= {"lock-order", "lock-held-blocking",
                                        "event-vocabulary"}

    def test_cli_unknown_rule_is_a_clean_error(self):
        """A typo'd --rule must exit 2 with the known-rule list, not die
        with a raw KeyError traceback deep in the walk."""
        proc = subprocess.run(
            [sys.executable, "-m",
             "batchai_retinanet_horovod_coco_tpu.analysis",
             "--rule", "bounded-quues"],
            capture_output=True, text=True, cwd=REPO_ROOT, timeout=120,
        )
        assert proc.returncode == 2, proc.stdout + proc.stderr
        assert "unknown rule" in proc.stderr
        assert "bounded-queues" in proc.stderr  # the known list is shown
        assert "Traceback" not in proc.stderr
        try:
            engine.run(REPO_ROOT, rule_names=["bounded-quues"])
        except ValueError as e:
            assert "unknown rule" in str(e)
        else:
            raise AssertionError("engine.run accepted an unknown rule")

    def test_cli_refuses_update_baseline_with_rule_filter(self, tmp_path):
        """--update-baseline from a single-rule run would rewrite the
        baseline with only that rule's findings, silently dropping every
        other rule's grandfathered entries — refused, baseline untouched."""
        baseline = tmp_path / "baseline.json"
        baseline.write_text("[]\n")
        proc = subprocess.run(
            [sys.executable, "-m",
             "batchai_retinanet_horovod_coco_tpu.analysis",
             "--rule", "bounded-queues", "--update-baseline",
             "--baseline", str(baseline)],
            capture_output=True, text=True, cwd=REPO_ROOT, timeout=120,
        )
        assert proc.returncode == 2, proc.stdout + proc.stderr
        assert "full run" in proc.stderr
        assert baseline.read_text() == "[]\n"


# ---- audit_threads shim compat -------------------------------------------


class TestAuditThreadsShim:
    def _shim(self):
        sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
        try:
            import audit_threads
        finally:
            sys.path.pop(0)
        return audit_threads

    def test_shim_api_surface(self, tmp_path):
        shim = self._shim()
        bad = tmp_path / "rogue.py"
        bad.write_text("import threading\nt = threading.Thread(target=f)\n")
        v = shim.audit_file(str(bad))
        assert len(v) == 1
        assert set(v[0]) == {"path", "line", "callee", "reason"}
        assert v[0]["callee"] == "Thread"
        assert shim.audit_package(str(tmp_path)) == v

    def test_shim_accepts_engine_suppression_grammar(self, tmp_path):
        shim = self._shim()
        ok = tmp_path / "covered.py"
        ok.write_text(
            "import threading\n"
            "# lint: watchdog-coverage: joined before return\n"
            "t = threading.Thread(target=f)\n"
        )
        assert shim.audit_file(str(ok)) == []

    def test_shim_cli_exit_codes(self, tmp_path):
        script = os.path.join(REPO_ROOT, "scripts", "audit_threads.py")
        clean = subprocess.run(
            [sys.executable, script], capture_output=True, text=True,
            cwd=REPO_ROOT, timeout=120,
        )
        assert clean.returncode == 0, clean.stdout + clean.stderr
        bad = tmp_path / "rogue.py"
        bad.write_text("import threading\nt = threading.Thread(target=f)\n")
        dirty = subprocess.run(
            [sys.executable, script, str(tmp_path), "--json"],
            capture_output=True, text=True, cwd=REPO_ROOT, timeout=120,
        )
        assert dirty.returncode == 1
        doc = json.loads(dirty.stdout)
        assert len(doc["violations"]) == 1


# ---- audit_collectives async dedupe --------------------------------------


class TestAuditCollectivesDedupe:
    def _mod(self):
        sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
        try:
            import audit_collectives
        finally:
            sys.path.pop(0)
        return audit_collectives

    def test_async_start_counts_result_half_only(self):
        """ISSUE 5 satellite: async ``-start`` results are
        (operand, result) tuples — the payload must match the sync form,
        not double it (the over-count previously documented as a caveat)."""
        ac = self._mod()
        sync = "  %ar = f32[1000]{0} all-reduce(f32[1000]{0} %p)\n"
        async_pair = (
            "  %ars = (f32[1000]{0}, f32[1000]{0}) "
            "all-reduce-start(f32[1000]{0} %p)\n"
            "  %ard = f32[1000]{0} all-reduce-done(%ars)\n"
        )
        s = ac.audit_hlo_text(sync)["all-reduce"]
        a = ac.audit_hlo_text(async_pair)["all-reduce"]
        assert s == {"count": 1, "payload_bytes": 4000}
        assert a == s, f"async form must audit identically: {a} vs {s}"

    def test_variadic_async_start_and_done_not_double_counted(self):
        ac = self._mod()
        hlo = (
            "  %vars = ((f32[10]{0}, f32[20]{0}), (f32[10]{0}, f32[20]{0}))"
            " all-reduce-start(%a, %b)\n"
            "  %vard = (f32[10]{0}, f32[20]{0}) all-reduce-done(%vars)\n"
            "  %ags = (f32[8]{0}, f32[64]{0}) all-gather-start(f32[8]{0} %x)\n"
            "  %agd = f32[64]{0} all-gather-done(%ags)\n"
        )
        r = ac.audit_hlo_text(hlo)
        assert r["all-reduce"] == {"count": 1, "payload_bytes": 120}, r
        assert r["all-gather"] == {"count": 1, "payload_bytes": 256}, r

    def test_sync_tuple_result_unchanged(self):
        """The pinned CPU modules' variadic sync all-reduce (a plain tuple
        of gradient leaves) still counts every element."""
        ac = self._mod()
        hlo = "  %ar = (f32[10]{0}, f32[20]{0}) all-reduce(%a, %b)\n"
        r = ac.audit_hlo_text(hlo)
        assert r["all-reduce"] == {"count": 1, "payload_bytes": 120}, r


# ---- lock-order / lock-held-blocking fixtures (ISSUE 20) -----------------


FIXTURE_DIR = os.path.join(REPO_ROOT, "tests", "fixtures", "lockgraph")

_CYC = "lockgraph.cyclic.Trio."
_DIA = "lockgraph.diamond.Diamond."
_OUTER = "lockgraph.indirect.Outer._lock"
_INNER = "lockgraph.indirect.Inner._lock"


def _lock_tree(tmp_path, modules):
    """A throwaway tree shaped like the real package, populated with the
    selected ``tests/fixtures/lockgraph`` modules; returns (root, empty
    baseline path)."""
    sub = tmp_path / engine.PACKAGE_NAME / "lockgraph"
    sub.mkdir(parents=True)
    (tmp_path / engine.PACKAGE_NAME / "__init__.py").write_text("")
    (sub / "__init__.py").write_text("")
    for m in modules:
        shutil.copy(os.path.join(FIXTURE_DIR, m + ".py"),
                    str(sub / (m + ".py")))
    bl = tmp_path / "baseline.json"
    engine.write_baseline(str(bl), [])
    return str(tmp_path), str(bl)


class TestLockOrder:
    def test_finds_exactly_the_cycle(self, tmp_path):
        """The whole fixture set contains exactly ONE deadlock (cyclic.py's
        A->B->C->A); the diamond and the indirect edge must not add false
        cycles, and the finding must name all three acquisition chains."""
        root, bl = _lock_tree(
            tmp_path, ["cyclic", "diamond", "indirect", "suppressed"])
        report = engine.run(root, baseline_path=bl,
                            rule_names=["lock-order"])
        assert len(report["new"]) == 1, report["new"]
        f = report["new"][0]
        assert "potential deadlock" in f["message"]
        for ident in (_CYC + "_a", _CYC + "_b", _CYC + "_c"):
            assert ident in f["message"], f["message"]
        cyc_rel = os.path.join(engine.PACKAGE_NAME, "lockgraph", "cyclic.py")
        assert list(f["paths"]) == [cyc_rel]
        assert not report["ok"]

    def test_diamond_is_acyclic_and_edges_exported(self, tmp_path):
        root, bl = _lock_tree(tmp_path, ["diamond", "indirect"])
        report = engine.run(root, baseline_path=bl,
                            rule_names=["lock-order"])
        assert report["new"] == [] and report["ok"], report["new"]
        edges = {(e["src"], e["dst"])
                 for e in report["exports"]["lock_order_edges"]}
        for src, dst in (("_top", "_left"), ("_top", "_right"),
                         ("_top", "_bottom"), ("_left", "_bottom"),
                         ("_right", "_bottom")):
            assert (_DIA + src, _DIA + dst) in edges, edges
        assert (_OUTER, _INNER) in edges, edges  # one-level resolution

    def test_new_edge_vs_committed_order_fails_with_via(self, tmp_path):
        """Drift discipline: an edge the committed file lacks fails the
        run, and the one-level-indirect edge's finding names the callee
        acquisition it was resolved through."""
        root, bl = _lock_tree(tmp_path, ["diamond", "indirect"])
        r0 = engine.run(root, baseline_path=bl, rule_names=["lock-order"])
        committed = [e for e in r0["exports"]["lock_order_edges"]
                     if e["src"] != _OUTER]
        from batchai_retinanet_horovod_coco_tpu.analysis.rules import (
            lock_graph,
        )
        order = tmp_path / "order.json"
        lock_graph.write_lock_order(str(order), committed)
        report = engine.run(root, baseline_path=bl,
                            rule_names=["lock-order"],
                            lock_order_path=str(order))
        assert not report["ok"] and len(report["new"]) == 1, report["new"]
        msg = report["new"][0]["message"]
        assert "not in the committed" in msg
        assert "call lockgraph.indirect.Inner.poke()" in msg, msg

    def test_stale_committed_edge_fails(self, tmp_path):
        root, bl = _lock_tree(tmp_path, ["diamond", "indirect"])
        r0 = engine.run(root, baseline_path=bl, rule_names=["lock-order"])
        from batchai_retinanet_horovod_coco_tpu.analysis.rules import (
            lock_graph,
        )
        order = tmp_path / "order.json"
        lock_graph.write_lock_order(
            str(order),
            r0["exports"]["lock_order_edges"]
            + [{"src": "lockgraph.ghost.A", "dst": "lockgraph.ghost.B"}])
        report = engine.run(root, baseline_path=bl,
                            rule_names=["lock-order"],
                            lock_order_path=str(order))
        assert not report["ok"] and len(report["new"]) == 1, report["new"]
        assert "stale committed lock-order edge" in report["new"][0]["message"]

    def test_committed_order_matching_is_clean(self, tmp_path):
        root, bl = _lock_tree(tmp_path, ["diamond", "indirect"])
        r0 = engine.run(root, baseline_path=bl, rule_names=["lock-order"])
        from batchai_retinanet_horovod_coco_tpu.analysis.rules import (
            lock_graph,
        )
        order = tmp_path / "order.json"
        lock_graph.write_lock_order(
            str(order), r0["exports"]["lock_order_edges"])
        report = engine.run(root, baseline_path=bl,
                            rule_names=["lock-order"],
                            lock_order_path=str(order))
        assert report["ok"] and report["new"] == [], report["new"]

    def test_cycle_fingerprint_is_cross_file_and_line_insensitive(
            self, tmp_path):
        """A cycle finding baselines on (rule, sorted-path-set, snippet):
        the grandfathered entry matches regardless of its recorded line."""
        root, bl = _lock_tree(tmp_path, ["cyclic"])
        r0 = engine.run(root, baseline_path=bl, rule_names=["lock-order"])
        d = r0["new"][0]
        bl2 = tmp_path / "baseline2.json"
        engine.write_baseline(str(bl2), [engine.Finding(
            rule=d["rule"], path=d["path"], line=999, message="",
            snippet=d["snippet"], paths=d["paths"],
        )])
        r1 = engine.run(root, baseline_path=str(bl2),
                        rule_names=["lock-order"])
        assert r1["ok"], r1["new"]
        assert len(r1["grandfathered"]) == 1 and r1["new"] == []


class TestLockHeldBlocking:
    def test_bites_direct_and_via_callee_and_suppressed_twin(self, tmp_path):
        root, bl = _lock_tree(tmp_path, ["suppressed"])
        report = engine.run(root, baseline_path=bl,
                            rule_names=["lock-held-blocking"])
        assert len(report["new"]) == 2, report["new"]
        msgs = [f["message"] for f in report["new"]]
        assert all("time.sleep" in m for m in msgs)
        assert all("lockgraph.suppressed.Sleeper._lock (acquired" in m
                   for m in msgs), msgs  # full hold-site path named
        assert any("via lockgraph.suppressed.Sleeper._nap()" in m
                   for m in msgs), msgs  # one-level blocking path
        assert len(report["suppressed"]) == 1, report["suppressed"]


class TestEngineParallelAndCache:
    def test_jobs_report_identical(self, tmp_path):
        root, bl = _lock_tree(
            tmp_path, ["cyclic", "diamond", "indirect", "suppressed"])
        serial = engine.run(root, baseline_path=bl, jobs=1)
        par = engine.run(root, baseline_path=bl, jobs=4)
        assert serial == par

    def test_parse_cache_invalidated_on_edit(self, tmp_path):
        """Warm-cache runs must still see edits: rewriting the innermost
        diamond acquisition to re-take ``_top`` creates a left<->top cycle
        that the second (cache-warm) run must report."""
        root, bl = _lock_tree(tmp_path, ["diamond"])
        r0 = engine.run(root, baseline_path=bl, rule_names=["lock-order"])
        assert r0["ok"]
        mod = tmp_path / engine.PACKAGE_NAME / "lockgraph" / "diamond.py"
        mod.write_text(mod.read_text().replace(
            "with self._bottom:", "with self._top:"))
        r1 = engine.run(root, baseline_path=bl, rule_names=["lock-order"])
        assert any("potential deadlock" in f["message"]
                   for f in r1["new"]), r1["new"]

    def test_cli_refuses_update_lock_order_with_rule_filter(self):
        proc = subprocess.run(
            [sys.executable, "-m",
             "batchai_retinanet_horovod_coco_tpu.analysis",
             "--rule", "lock-order", "--update-lock-order"],
            capture_output=True, text=True, cwd=REPO_ROOT, timeout=120,
        )
        assert proc.returncode == 2, proc.stdout + proc.stderr
        assert "full run" in proc.stderr


# ---- event-vocabulary ----------------------------------------------------


class TestEventVocabulary:
    def _tree(self, tmp_path, suppress_rogue: bool = False):
        pkg = tmp_path / engine.PACKAGE_NAME
        obs = pkg / "obs"
        obs.mkdir(parents=True)
        (pkg / "__init__.py").write_text("")
        (obs / "__init__.py").write_text("")
        reader_rel = f"{engine.PACKAGE_NAME}/reader.py"
        (obs / "vocabulary.py").write_text(textwrap.dedent(f"""
            VOCABULARY = {{
                "good_event": {{"kinds": ("event",),
                                "consumers": ("{reader_rel}",)}},
                "ghost_event": {{"kinds": ("event",),
                                 "consumers": ("{reader_rel}",)}},
                "stale_event": {{"kinds": ("series",), "consumers": ()}},
                "lost_event": {{"kinds": ("event",),
                                "consumers": ("no/such/file.py",)}},
            }}
        """))
        sup = ("  # lint: event-vocabulary: ad-hoc debug counter\n"
               if suppress_rogue else "")
        (pkg / "emitter.py").write_text(
            "def go(sink, reg):\n"
            '    sink.event("good_event", n=1)\n'
            '    sink.event("lost_event")\n'
            f"{sup}"
            '    reg.counter("rogue_series")\n'
        )
        (pkg / "reader.py").write_text(
            "def read(ev):\n"
            '    return ev["event"] in ("good_event", "ghost_event")\n'
        )
        bl = tmp_path / "baseline.json"
        engine.write_baseline(str(bl), [])
        return str(tmp_path), str(bl)

    def test_flags_unregistered_orphan_and_stale(self, tmp_path):
        root, bl = self._tree(tmp_path)
        report = engine.run(root, baseline_path=bl,
                            rule_names=["event-vocabulary"])
        msgs = sorted(f["message"] for f in report["new"])
        assert len(msgs) == 4, msgs
        assert any("emitted-but-unregistered" in m and "rogue_series" in m
                   for m in msgs), msgs
        assert any("consumed-but-never-emitted" in m and "ghost_event" in m
                   and "reader.py" in m for m in msgs), msgs
        assert any("registered-but-never-emitted" in m and "stale_event" in m
                   for m in msgs), msgs
        assert any("not a scanned file" in m and "no/such/file.py" in m
                   for m in msgs), msgs
        assert report["stats"]["event-vocabulary"] >= 3
        assert "good_event" in report["exports"]["event_names_emitted"]

    def test_suppressed_emit_site_passes(self, tmp_path):
        root, bl = self._tree(tmp_path, suppress_rogue=True)
        report = engine.run(root, baseline_path=bl,
                            rule_names=["event-vocabulary"])
        assert not any("rogue_series" in f["message"]
                       for f in report["new"]), report["new"]
        assert any("rogue_series" in f["message"]
                   for f in report["suppressed"])

    def test_fixture_trees_without_vocabulary_are_exempt(self, tmp_path):
        root, bl = _lock_tree(tmp_path, ["diamond"])
        report = engine.run(root, baseline_path=bl,
                            rule_names=["event-vocabulary"])
        assert report["new"] == [] and report["ok"]


# ---- runtime lock-order witness (utils/locks.py) -------------------------


def test_witness_armed_in_tier1():
    """tests/conftest.py arms RETINANET_LOCK_DEBUG for the whole tier, so
    every multithreaded test validates the committed order for free."""
    assert os.environ.get(locks.ENV_FLAG) == "1"
    assert locks.enabled()


class TestLockWitness:
    @pytest.fixture(autouse=True)
    def _armed(self, monkeypatch):
        monkeypatch.setenv(locks.ENV_FLAG, "1")
        locks._set_committed_for_testing(set())
        locks.reset_observed()
        yield
        locks._set_committed_for_testing(None)
        locks.reset_observed()

    def test_disabled_is_identity(self, monkeypatch):
        """PARITY: with the flag off, make_lock returns a PLAIN lock."""
        monkeypatch.setenv(locks.ENV_FLAG, "0")
        assert type(locks.make_lock("x")) is type(threading.Lock())
        assert type(locks.make_rlock("x")) is type(threading.RLock())

    def test_committed_order_passes_and_inversion_raises(self):
        locks._set_committed_for_testing({("fix.A", "fix.B")})
        a, b = locks.make_lock("fix.A"), locks.make_lock("fix.B")
        with a:
            with b:
                pass  # the committed direction: clean
        with b:
            with pytest.raises(locks.LockOrderViolation) as ei:
                with a:
                    pass
        msg = str(ei.value)
        # Both chains named: this thread's actual chain and the committed.
        assert "[fix.B -> fix.A]" in msg, msg
        assert "'fix.A' -> 'fix.B'" in msg, msg

    def test_unknown_pairs_recorded_not_raised(self):
        a, b = locks.make_lock("w.A"), locks.make_lock("w.B")
        with a:
            with b:
                pass
        assert ("w.A", "w.B") in locks.observed_edges()

    def test_reentry_never_checked(self):
        locks._set_committed_for_testing({("r.A", "r.B")})
        r = locks.make_rlock("r.B")
        with r:
            with r:  # same-name reentry: exempt by design
                pass

    def test_condition_over_debug_rlock(self):
        cv = threading.Condition(locks.make_rlock("cv.lock"))
        with cv:
            cv.notify_all()

    def test_static_edges_drive_the_witness(self, tmp_path):
        """End-to-end over the fixture package: the edges the STATIC rule
        computes become the committed order the RUNTIME witness enforces —
        replaying the diamond's sanctioned order passes, the inverted
        acquisition raises."""
        root, bl = _lock_tree(tmp_path, ["diamond"])
        report = engine.run(root, baseline_path=bl,
                            rule_names=["lock-order"])
        edges = {(e["src"], e["dst"])
                 for e in report["exports"]["lock_order_edges"]}
        assert (_DIA + "_top", _DIA + "_bottom") in edges
        locks._set_committed_for_testing(edges)
        top = locks.make_lock(_DIA + "_top")
        bottom = locks.make_lock(_DIA + "_bottom")
        with top:
            with bottom:
                pass
        with bottom:
            with pytest.raises(locks.LockOrderViolation):
                with top:
                    pass

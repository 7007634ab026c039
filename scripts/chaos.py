#!/usr/bin/env python
"""chaos: fault-injection harness for the durability subsystem (ISSUE 11).

Drives a REAL ``train.py`` CPU training subprocess through a kill
schedule and asserts the crash-safety contract the checkpoint protocol
promises (utils/checkpoint.py):

- **Save-phase kills** — ``RETINANET_CHAOS_KILL=<phase>@<n>`` makes the
  subprocess SIGKILL itself at the n-th crossing of a named protocol
  phase (snapshot, tmp_write, manifest_commit, rename, finalize).  After
  EVERY kill: no published ``ckpt-*`` dir may be torn (manifest present
  and consistent), and a plain resume run must complete and produce
  losses BIT-IDENTICAL to an uninterrupted baseline at every step —
  ``--resume-elastic`` re-derives the stream position, so step k sees
  the same batch in both runs.
- **Mid-step kills** — the driver SIGKILLs the subprocess from outside
  once the log shows a target step, covering the window between saves.
- **Torn-dir triage** — manufactured damage (deleted manifest,
  truncated leaf, stray .tmp dir) must be skipped to the previous
  complete checkpoint, and the resume still completes.
- **NaN auto-resume** — ``--inject-nan-step`` poisons one mid-run batch;
  with ``--auto-resume`` the run must complete to the target step with
  EXACTLY ONE structured ``auto_resume`` event, a NUMERICS_DUMP.json,
  and the poison batch's image ids excluded from the healed stream.
- **Comm leg** (``--comm`` / ``make chaos-comm``, ISSUE 13) — SIGKILL a
  ``--comm-compress int8`` run (2 virtual devices) mid-save; the
  surviving checkpoint must carry the EF residual leaves, the resume
  must restore them (or cleanly zero them with ONE structured
  ``ef_reset`` event), and the resumed losses must rejoin the
  uninterrupted compressed baseline's envelope.  The hierarchical
  sub-leg (ISSUE 16) repeats the schedule at ``--comm-slices 2`` on 4
  devices and additionally requires the surviving residual leaves to be
  keyed per hop (``@dcn``) — proving the per-hop EF state survives
  SIGKILL + resume.

Every training subprocess runs ``--platform cpu``: the harness never
touches an accelerator.

Modes: ``--smoke`` (one mid-save kill + one NaN leg; the check-static
CI leg), default full schedule (>= 20 kills).
Exit 0 = contract held; 1 = violation (each printed as one
``chaos FAIL:`` line).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time  # lint-exempt scripts/: subprocess wall timing only

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

_failures: list[str] = []

# Every save-protocol phase, in write order (utils/checkpoint.py).
PHASES = ("snapshot", "tmp_write", "manifest_commit", "rename", "finalize")


def check(ok: bool, what: str) -> None:
    if not ok:
        _failures.append(what)
        print(f"chaos FAIL: {what}", flush=True)


def _base_cmd(work: str, steps: int, extra: list[str] | None = None) -> list[str]:
    return [
        sys.executable, os.path.join(_REPO, "train.py"), "synthetic",
        "--platform", "cpu", "--backbone", "resnet_test", "--f32",
        "--image-min-side", "64", "--image-max-side", "64",
        "--synthetic-size", "64", "--synthetic-images", "16",
        "--synthetic-classes", "3",
        "--synthetic-root", os.path.join(work, "data"),
        "--batch-size", "4", "--num-devices", "1", "--workers", "2",
        "--max-gt", "8", "--seed", "0", "--log-every", "1",
        "--steps", str(steps),
        "--snapshot-path", os.path.join(work, "ckpt"),
        "--checkpoint-every", "2",
        "--log-dir", os.path.join(work, "logs"),
    ] + (extra or [])


def _run(cmd: list[str], env_extra: dict | None = None,
         timeout: float = 900.0) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run(
        cmd, env=env, capture_output=True, text=True, timeout=timeout,
    )


def _run_until_step_then_kill(
    cmd: list[str], work: str, kill_at_step: int, timeout: float = 900.0
) -> int:
    """Launch and SIGKILL from OUTSIDE once metrics.jsonl shows the step
    — the mid-step half of the schedule (between-save windows)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        cmd, env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL
    )
    metrics = os.path.join(work, "logs", "metrics.jsonl")
    deadline = time.monotonic() + timeout
    try:
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                return proc.returncode  # died early — caller flags it
            for rec in _records(metrics):
                if rec.get("step", -1) >= kill_at_step:
                    proc.kill()
                    proc.wait(timeout=30)
                    return -signal.SIGKILL
            time.sleep(0.2)
        proc.kill()
        proc.wait(timeout=30)
        return -999  # timed out waiting for the step
    finally:
        if proc.poll() is None:
            proc.kill()


def _records(metrics_path: str) -> list[dict]:
    out = []
    try:
        with open(metrics_path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    pass  # a killed run may leave one torn tail line
    except OSError:
        pass
    return out


def _losses_by_step(metrics_path: str) -> dict[int, float]:
    """step -> train/loss over ALL runs appended to the file; a later run
    overwrites (resume re-logs nothing, so collisions only happen when a
    killed step re-runs after resume — and then bit-equality is exactly
    the claim under test)."""
    out: dict[int, float] = {}
    for rec in _records(metrics_path):
        if "step" in rec and "train/loss" in rec and "event" not in rec:
            out[int(rec["step"])] = rec["train/loss"]
    return out


def _events(metrics_path: str, kind: str) -> list[dict]:
    return [r for r in _records(metrics_path) if r.get("event") == kind]


def _validate_ckpt_dir(work: str, context: str) -> None:
    """No PUBLISHED checkpoint may be torn, ever — the core protocol
    claim.  (Dirs without a manifest cannot exist under the protocol;
    .tmp-* leftovers are expected and invisible to restore.)"""
    from batchai_retinanet_horovod_coco_tpu.utils.checkpoint import (
        _load_manifest,
    )

    d = os.path.join(work, "ckpt")
    if not os.path.isdir(d):
        return
    for name in sorted(os.listdir(d)):
        if not name.startswith("ckpt-"):
            continue
        manifest = _load_manifest(os.path.join(d, name))
        check(
            manifest is not None,
            f"{context}: published {name} is torn (protocol violation)",
        )


def _fresh_workdir(tag: str) -> str:
    work = tempfile.mkdtemp(prefix=f"chaos_{tag}_")
    return work


def _baseline(steps: int) -> tuple[str, dict[int, float]]:
    work = _fresh_workdir("baseline")
    r = _run(_base_cmd(work, steps))
    check(r.returncode == 0, f"baseline run failed rc={r.returncode}: "
                             f"{r.stderr[-500:]}")
    losses = _losses_by_step(os.path.join(work, "logs", "metrics.jsonl"))
    check(
        set(losses) == set(range(1, steps + 1)),
        f"baseline logged steps {sorted(losses)} != 1..{steps}",
    )
    return work, losses


def _kill_leg(
    tag: str, kill_env: str | None, baseline: dict[int, float], steps: int,
    kill_at_step: int | None = None,
) -> None:
    """One scheduled kill: run with the kill armed, assert it fired and
    the checkpoint dir survived; resume; assert completion + bit-identical
    losses vs the baseline."""
    work = _fresh_workdir(tag)
    cmd = _base_cmd(work, steps, ["--resume-elastic"])
    if kill_env is not None:
        r = _run(cmd, env_extra={"RETINANET_CHAOS_KILL": kill_env})
        check(
            r.returncode != 0,
            f"{tag}: kill {kill_env} never fired (rc 0 — schedule vacuous)",
        )
    else:
        rc = _run_until_step_then_kill(cmd, work, kill_at_step)
        check(rc == -signal.SIGKILL, f"{tag}: external kill failed rc={rc}")
    _validate_ckpt_dir(work, tag)
    resume = _run(cmd)
    check(
        resume.returncode == 0,
        f"{tag}: resume failed rc={resume.returncode}: "
        f"{resume.stderr[-500:]}",
    )
    _validate_ckpt_dir(work, f"{tag}/post-resume")
    losses = _losses_by_step(os.path.join(work, "logs", "metrics.jsonl"))
    check(
        losses.get(steps) is not None,
        f"{tag}: resumed run never reached step {steps}",
    )
    mismatches = {
        s: (losses[s], baseline[s])
        for s in losses
        if s in baseline and losses[s] != baseline[s]
    }
    check(
        not mismatches,
        f"{tag}: losses not bit-identical to baseline: {mismatches}",
    )
    if not _failures:
        shutil.rmtree(work, ignore_errors=True)


def _torn_dir_legs(baseline: dict[int, float], steps: int) -> None:
    """Manufactured damage: restore must skip to the previous complete
    checkpoint and the run must still finish."""
    from batchai_retinanet_horovod_coco_tpu.utils.checkpoint import (
        latest_step,
    )

    src = _fresh_workdir("torn_src")
    r = _run(_base_cmd(src, steps, ["--resume-elastic"]))
    check(r.returncode == 0, f"torn-src run failed rc={r.returncode}")
    ckpt = os.path.join(src, "ckpt")
    newest = latest_step(ckpt)
    check(newest == steps, f"torn-src latest {newest} != {steps}")

    def damage_and_resume(tag: str, damage) -> None:
        work = _fresh_workdir(tag)
        shutil.rmtree(work)
        shutil.copytree(src, work)
        damage(os.path.join(work, "ckpt"))
        got = latest_step(os.path.join(work, "ckpt"))
        check(
            got is not None and got < steps,
            f"{tag}: damaged newest not skipped (latest={got})",
        )
        resume = _run(_base_cmd(work, steps + 2, ["--resume-elastic"]))
        check(
            resume.returncode == 0,
            f"{tag}: resume after damage failed rc={resume.returncode}: "
            f"{resume.stderr[-500:]}",
        )
        losses = _losses_by_step(os.path.join(work, "logs", "metrics.jsonl"))
        mism = {
            s: (losses[s], baseline[s])
            for s in losses
            if s in baseline and losses[s] != baseline[s]
        }
        check(not mism, f"{tag}: post-damage losses diverged: {mism}")
        if not _failures:
            shutil.rmtree(work, ignore_errors=True)

    damage_and_resume(
        "torn_manifest",
        lambda d: os.unlink(os.path.join(d, f"ckpt-{newest}", "manifest.json")),
    )

    def truncate(d):
        leaf = os.path.join(d, f"ckpt-{newest}", "leaf_00001.npy")
        with open(leaf, "r+b") as f:
            f.truncate(max(1, os.path.getsize(leaf) // 2))

    damage_and_resume("torn_leaf", truncate)
    damage_and_resume(
        "stray_tmp",
        lambda d: (
            os.makedirs(os.path.join(d, ".tmp-99-1"), exist_ok=True),
            os.unlink(os.path.join(d, f"ckpt-{newest}", "manifest.json")),
        ),
    )
    if not _failures:
        shutil.rmtree(src, ignore_errors=True)


def _nan_leg(steps: int = 12, inject_at: int = 7) -> None:
    """Injected NaN + --auto-resume: completes to target with exactly one
    auto_resume event, a provenance dump, and the poison ids excluded."""
    work = _fresh_workdir("nan")
    cmd = _base_cmd(
        work, steps,
        ["--auto-resume", "--inject-nan-step", str(inject_at)],
    )
    r = _run(cmd)
    check(
        r.returncode == 0,
        f"nan: auto-resume run failed rc={r.returncode}: {r.stderr[-800:]}",
    )
    metrics = os.path.join(work, "logs", "metrics.jsonl")
    resumes = _events(metrics, "auto_resume")
    check(
        len(resumes) == 1,
        f"nan: expected exactly one auto_resume event, got {len(resumes)}",
    )
    losses = _losses_by_step(metrics)
    check(
        losses.get(steps) is not None,
        f"nan: healed run never reached step {steps}",
    )
    dump = os.path.join(work, "logs", "NUMERICS_DUMP.json")
    check(os.path.exists(dump), "nan: no NUMERICS_DUMP.json landed")
    if resumes:
        ev = resumes[0]
        check(
            bool(ev.get("exclude_ids")),
            "nan: auto_resume event carries no excluded poison ids",
        )
        check(
            ev.get("restored_step", -1) < inject_at,
            f"nan: restored step {ev.get('restored_step')} not before the "
            f"poison step {inject_at}",
        )
    if not _failures:
        shutil.rmtree(work, ignore_errors=True)


# ---------------------------------------------------------------------------
# Comm leg (ISSUE 13): SIGKILL under gradient compression + error feedback
# ---------------------------------------------------------------------------
#
# The EF residual is TRAINING STATE: it carries the quantization error the
# next step must add back, so a crash/restore cycle that silently dropped
# it would re-bias the compressed gradients with nothing in the logs.
# This leg kills a real --comm-compress int8 CPU run (2 virtual devices —
# compression rides the mesh collectives) mid-save and asserts the
# durability contract: the checkpoint carries ['comm_state'] leaves, the
# resume either restores them or cleanly zeros them with EXACTLY ONE
# structured ef_reset event, and the resumed losses rejoin the
# uninterrupted compressed baseline's envelope.


def _comm_cmd(work: str, steps: int, hier: bool = False) -> list[str]:
    cmd = _base_cmd(
        work, steps, ["--resume-elastic", "--comm-compress", "int8"]
    )
    # Compression needs a mesh: virtual CPU devices (train.py forces
    # xla_force_host_platform_device_count in the subprocess).  The
    # hierarchical leg (ISSUE 16) emulates 2 slices x 2 devices via
    # --comm-slices, which moves the EF residuals to the DCN hop.
    i = cmd.index("--num-devices")
    cmd[i + 1] = "4" if hier else "2"
    if hier:
        cmd += ["--comm-slices", "2"]
    return cmd


def _comm_leg(steps: int = 8, hier: bool = False) -> None:
    from batchai_retinanet_horovod_coco_tpu.utils.checkpoint import (
        read_manifest,
    )

    tag = "comm-hier" if hier else "comm"
    # Uninterrupted compressed baseline (its own losses — int8+EF drifts
    # from the exact run by design, so the envelope is compressed-vs-
    # compressed).
    base = _fresh_workdir(f"{tag}_base".replace("-", "_"))
    r = _run(_comm_cmd(base, steps, hier))
    check(
        r.returncode == 0,
        f"{tag}: baseline failed rc={r.returncode}: {r.stderr[-500:]}",
    )
    baseline = _losses_by_step(os.path.join(base, "logs", "metrics.jsonl"))
    check(
        baseline.get(steps) is not None,
        f"{tag}: baseline never reached step {steps}",
    )

    work = _fresh_workdir(f"{tag}_kill".replace("-", "_"))
    cmd = _comm_cmd(work, steps, hier)
    r = _run(cmd, env_extra={"RETINANET_CHAOS_KILL": "tmp_write@2"})
    check(
        r.returncode != 0,
        f"{tag}: mid-save kill never fired (rc 0 — schedule vacuous)",
    )
    _validate_ckpt_dir(work, tag)
    manifest = read_manifest(os.path.join(work, "ckpt"))
    check(manifest is not None, f"{tag}: no restorable checkpoint survived")
    if manifest is not None:
        ef_paths = [
            e["path"]
            for e in manifest.get("leaves", [])
            if e["path"].startswith("['comm_state']")
        ]
        check(
            bool(ef_paths),
            f"{tag}: surviving checkpoint carries no EF residual leaves "
            "(comm_state was not checkpointed)",
        )
        if hier:
            # The hierarchical tree keys its residuals per hop — the
            # checkpoint must carry the @dcn layout, or a resume would
            # silently zero them (layout mismatch -> ef_reset).
            check(
                any("@dcn" in p for p in ef_paths),
                f"{tag}: EF residual leaves are not keyed per hop "
                f"(no @dcn in {ef_paths})",
            )
    resume = _run(cmd)
    check(
        resume.returncode == 0,
        f"{tag}: resume failed rc={resume.returncode}: "
        f"{resume.stderr[-500:]}",
    )
    metrics = os.path.join(work, "logs", "metrics.jsonl")
    ef_resets = _events(metrics, "ef_reset")
    check(
        len(ef_resets) <= 1,
        f"{tag}: expected 0 (restored) or 1 (cleanly zeroed) ef_reset "
        f"events, got {len(ef_resets)}",
    )
    losses = _losses_by_step(metrics)
    check(
        losses.get(steps) is not None,
        f"{tag}: resumed run never reached step {steps}",
    )
    # Same world size + --resume-elastic: a restore that carried the EF
    # state replays the baseline essentially exactly (tight envelope);
    # the announced zero-and-continue path perturbs the first resumed
    # steps at quantization-error scale, so its envelope is the loose
    # one — either way the trajectory must rejoin the uninterrupted
    # compressed baseline.
    rtol = 5e-2 if ef_resets else 1e-5
    bad = {
        s: (losses[s], baseline[s])
        for s in losses
        if s in baseline
        and abs(losses[s] - baseline[s]) > rtol * max(abs(baseline[s]), 1e-9)
    }
    check(
        not bad,
        f"{tag}: resumed losses left the baseline envelope: {bad}",
    )
    if not _failures:
        shutil.rmtree(base, ignore_errors=True)
        shutil.rmtree(work, ignore_errors=True)


# ---------------------------------------------------------------------------
# Serve fleet leg (ISSUE 12): kill-a-replica + SLO-gated canary rollback
# ---------------------------------------------------------------------------
#
# System under test: the REAL fleet CLI (python -m …serve.fleet) over
# stub-engine replica subprocesses — the serve-side twin of the training
# kill schedule above.  Two legs:
#
# - kill: SIGKILL one replica subprocess mid-load; every accepted request
#   must complete or shed WITH A REASON (zero hung clients, zero silent
#   drops), the router's /healthz must stay 200 throughout, and after the
#   supervisor respawns the replica the breaker must readmit it (traffic
#   lands on it again).
# - canary: a deliberately slow stub canary joins behind the canary gate;
#   the p99 regression must produce EXACTLY ONE canary_rollback event and
#   leave the fleet at baseline weights, with traffic unharmed.


def _fleet_payload() -> bytes:
    import io

    import numpy as np
    from PIL import Image

    buf = io.BytesIO()
    Image.fromarray(np.zeros((64, 64, 3), np.uint8)).save(buf, "PNG")
    return buf.getvalue()


def _http_get(url: str, timeout: float = 10.0):
    """(status, body_bytes); 4xx/5xx are data, socket errors raise."""
    import urllib.error
    import urllib.request

    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


class _FleetUnderTest:
    """One fleet-CLI subprocess + line-readers for its structured stdout
    (spawn/respawn events) and stderr (breaker/canary events)."""

    def __init__(self, tag: str, extra_args: list[str]):
        import threading

        self.tag = tag
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        self.proc = subprocess.Popen(
            [sys.executable, "-m",
             "batchai_retinanet_horovod_coco_tpu.serve.fleet",
             "--http", "0"] + extra_args,
            env=env, cwd=_REPO, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        self.stdout_lines: list[str] = []
        self.stderr_lines: list[str] = []

        def reader(stream, into):
            try:
                for line in stream:
                    into.append(line.rstrip("\n"))
            except Exception as e:  # crash channel: visible in the report
                into.append(f"__reader_error__ {e!r}")

        # watchdog: harness-local pipe readers; liveness is witnessed by
        # the driver's own bounded waits, not the obs watchdog.
        self._readers = [
            threading.Thread(
                target=reader, args=(self.proc.stdout, self.stdout_lines),
                daemon=True,
            ),
            threading.Thread(
                target=reader, args=(self.proc.stderr, self.stderr_lines),
                daemon=True,
            ),
        ]
        for t in self._readers:
            t.start()
        try:
            self.base_url = self._wait_for_url()
        except Exception:
            # Constructor failure = no handle for the caller's finally:
            # kill the fleet CLI here (its own teardown reaps the
            # replica children) so a wedged bring-up can't leak
            # processes holding pinned ports into the next CI run.
            self.stop()
            raise

    def _wait_for_url(self, timeout: float = 180.0) -> str:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"{self.tag}: fleet CLI died rc={self.proc.returncode}: "
                    f"{self.stderr_lines[-5:]}"
                )
            for line in self.stdout_lines:
                if line.startswith("fleet serving on "):
                    return line.split("fleet serving on ", 1)[1].split()[0]
            time.sleep(0.1)
        raise RuntimeError(f"{self.tag}: fleet CLI never started serving")

    def events(self, kind: str) -> list[dict]:
        out = []
        for line in self.stdout_lines + self.stderr_lines:
            try:
                rec = json.loads(line)
            except (json.JSONDecodeError, ValueError):
                continue
            if isinstance(rec, dict) and rec.get("event") == kind:
                out.append(rec)
        return out

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=10)


def _fleet_storm(
    base_url: str, payload: bytes, total: int, clients: int,
    mid_action=None, request_timeout: float = 30.0,
) -> dict:
    """Drive ``total`` requests from ``clients`` threads; every request
    must RESOLVE (2xx/4xx/5xx all count — a hang or router socket error
    does not).  ``mid_action()`` runs once, halfway through."""
    import threading
    import urllib.error
    import urllib.request

    lock = threading.Lock()
    counts = {"ok": 0, "shed": 0, "timeout": 0, "server_error": 0,
              "router_unreachable": 0, "hung": 0, "other": 0}
    issued = [0]
    acted = [False]

    def one_request():
        req = urllib.request.Request(
            f"{base_url}/detect", data=payload, method="POST"
        )
        try:
            with urllib.request.urlopen(req, timeout=request_timeout) as r:
                json.loads(r.read().decode())
                return "ok"
        except urllib.error.HTTPError as e:
            body = {}
            try:
                body = json.loads(e.read().decode())
            except Exception:
                pass
            if e.code == 503:
                # A shed MUST carry a machine-readable reason.
                return "shed" if body.get("reason") else "other"
            if e.code == 504:
                return "timeout"
            return "server_error"
        except TimeoutError:
            return "hung"  # the contract violation this leg exists for
        except Exception as e:
            if "timed out" in str(e).lower():
                return "hung"
            return "router_unreachable"

    def client():
        try:
            while True:
                with lock:
                    if issued[0] >= total:
                        return
                    issued[0] += 1
                    n = issued[0]
                    fire = n == max(1, total // 2) and not acted[0]
                    if fire:
                        acted[0] = True
                if fire and mid_action is not None:
                    mid_action()
                outcome = one_request()
                with lock:
                    counts[outcome] += 1
        except Exception as e:  # crash channel: a dead client = hung reqs
            with lock:
                counts["other"] += 1
            print(f"chaos FAIL: storm client crashed: {e!r}", flush=True)

    # watchdog: harness-local load generators; every request is bounded
    # by its own urlopen timeout, the driver joins with a budget below.
    threads = [
        threading.Thread(target=client, daemon=True)
        for _ in range(clients)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=request_timeout * total / max(1, clients) + 60)
    counts["submitted"] = issued[0]
    counts["resolved"] = sum(
        counts[k] for k in ("ok", "shed", "timeout", "server_error")
    )
    return counts


def _wait_until(predicate, timeout: float, what: str) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            if predicate():
                return True
        except Exception:
            pass
        time.sleep(0.25)
    check(False, what)
    return False


def _fleet_status(base_url: str) -> dict:
    code, body = _http_get(f"{base_url}/fleet")
    return json.loads(body.decode()) if code == 200 else {}


def _metric_value(base_url: str, name: str) -> float:
    sys.path.insert(0, _REPO)
    try:
        from batchai_retinanet_horovod_coco_tpu.obs.telemetry import (
            parse_exposition,
        )
    finally:
        sys.path.pop(0)
    code, body = _http_get(f"{base_url}/metrics")
    if code != 200:
        return float("nan")
    _types, samples = parse_exposition(body.decode())
    return samples.get(name, 0.0)


def _serve_kill_leg() -> None:
    """SIGKILL one replica mid-load: zero hangs, zero silent drops,
    router 200 throughout, breaker reopens after the respawn."""
    import threading

    fleet = _FleetUnderTest("serve_kill", [
        "--spawn", "2", "--stub-engine", "--stub-delay-ms", "30",
        "--poll-interval", "0.2", "--respawn-delay-s", "0.5",
        "--fleet-timeout-s", "20",
    ])
    try:
        spawned = fleet.events("fleet_replica_spawned")
        check(len(spawned) == 2, f"expected 2 spawns, saw {len(spawned)}")
        victim = spawned[0]

        # Router-liveness watcher: /healthz must be 200 THROUGHOUT.
        bad_healthz: list[tuple] = []
        stop_watch = threading.Event()

        def watch_healthz():
            try:
                while not stop_watch.wait(0.1):
                    code, _ = _http_get(
                        f"{fleet.base_url}/healthz", timeout=5
                    )
                    if code != 200:
                        bad_healthz.append((time.monotonic(), code))
            except Exception as e:  # crash channel → leg fails loudly
                bad_healthz.append((time.monotonic(), repr(e)))

        # watchdog: harness-local probe loop, bounded by stop_watch below.
        watcher = threading.Thread(target=watch_healthz, daemon=True)
        watcher.start()

        counts = _fleet_storm(
            fleet.base_url, _fleet_payload(), total=60, clients=4,
            mid_action=lambda: os.kill(victim["pid"], signal.SIGKILL),
        )
        stop_watch.set()
        watcher.join(timeout=10)

        check(counts["hung"] == 0, f"kill leg: hung clients: {counts}")
        check(
            counts["router_unreachable"] == 0 and counts["other"] == 0,
            f"kill leg: router dropped/garbled requests: {counts}",
        )
        check(
            counts["resolved"] == counts["submitted"],
            f"kill leg: silent drops: {counts}",
        )
        check(counts["ok"] > 0, f"kill leg: nothing completed: {counts}")
        check(
            not bad_healthz,
            f"kill leg: router /healthz flapped: {bad_healthz[:5]}",
        )
        check(
            _metric_value(fleet.base_url, "fleet_breaker_open_total") >= 1,
            "kill leg: breaker never opened on the killed replica",
        )

        # The supervisor respawns the victim in place; the half-open
        # probe must readmit it (breaker re-closes).
        _wait_until(
            lambda: len(fleet.events("fleet_replica_respawned")) >= 1,
            60, "kill leg: victim was never respawned",
        )
        rid = victim["replica_id"]
        _wait_until(
            lambda: any(
                r["replica_id"] == rid and r["state"] == "closed"
                for r in _fleet_status(fleet.base_url).get("replicas", [])
            ),
            60, "kill leg: breaker never readmitted the respawned replica",
        )
        post = _fleet_storm(
            fleet.base_url, _fleet_payload(), total=8, clients=2
        )
        check(
            post["ok"] == post["submitted"],
            f"kill leg: post-respawn traffic unhealthy: {post}",
        )
        # Fleet metric families exist on the scrape surface.
        _code, metrics_body = _http_get(f"{fleet.base_url}/metrics")
        for fam in ("fleet_requests_completed_total", "fleet_replica_weight",
                    "fleet_request_latency_ms", "fleet_breaker_state"):
            check(
                fam.encode() in metrics_body,
                f"kill leg: {fam} missing from fleet /metrics",
            )
        # ISSUE 14: the leg runs under CONTINUOUS in-flight batching (the
        # serve default) — the replicas must advertise the slot-pool load
        # fields the router's weight formula consumes.
        loads = [
            r.get("load", {})
            for r in _fleet_status(fleet.base_url).get("replicas", [])
        ]
        check(
            any(
                "free_slots" in ld and "slot_capacity" in ld for ld in loads
            ),
            "kill leg: no replica advertises the continuous slot-pool "
            f"load fields (free_slots/slot_capacity): {loads}",
        )
    finally:
        fleet.stop()


def _serve_canary_leg() -> None:
    """An injected-slow canary behind the gate: exactly one
    canary_rollback, fleet back to baseline weights, traffic unharmed."""
    fleet = _FleetUnderTest("serve_canary", [
        "--spawn", "2", "--stub-engine", "--stub-delay-ms", "2",
        "--canary-stub-delay-ms", "250", "--canary-weight", "0.5",
        "--canary-p99-factor", "3", "--canary-for-s", "0.5",
        "--canary-poll-s", "0.2", "--poll-interval", "0.2",
        "--fleet-timeout-s", "20",
    ])
    try:
        counts = _fleet_storm(
            fleet.base_url, _fleet_payload(), total=60, clients=4
        )
        check(
            counts["resolved"] == counts["submitted"]
            and counts["hung"] == 0,
            f"canary leg: requests lost during rollout: {counts}",
        )
        _wait_until(
            lambda: _metric_value(
                fleet.base_url, "fleet_canary_rollback_total"
            ) == 1.0,
            60, "canary leg: rollback never fired",
        )
        # More traffic — the gate must NOT fire again (exactly once).
        post = _fleet_storm(
            fleet.base_url, _fleet_payload(), total=20, clients=2
        )
        check(
            post["resolved"] == post["submitted"] and post["hung"] == 0,
            f"canary leg: post-rollback traffic lost: {post}",
        )
        check(
            _metric_value(
                fleet.base_url, "fleet_canary_rollback_total"
            ) == 1.0,
            "canary leg: canary_rollback fired more than once",
        )
        rollbacks = fleet.events("canary_rollback")
        check(
            len(rollbacks) == 1,
            f"canary leg: expected 1 canary_rollback event, saw "
            f"{len(rollbacks)}",
        )
        status = _fleet_status(fleet.base_url)
        by_id = {r["replica_id"]: r for r in status.get("replicas", [])}
        check(
            status.get("canary_outcome") == "rolled_back",
            f"canary leg: outcome {status.get('canary_outcome')!r}",
        )
        check(
            by_id.get("canary", {}).get("state") == "drained"
            and by_id.get("canary", {}).get("weight") == 0,
            f"canary leg: canary not drained: {by_id.get('canary')}",
        )
        baseline_ok = all(
            by_id.get(rid, {}).get("state") == "closed"
            and by_id.get(rid, {}).get("weight", 0) > 0
            for rid in ("replica-0", "replica-1")
        )
        check(
            baseline_ok,
            f"canary leg: fleet not back at baseline weights: {by_id}",
        )
    finally:
        fleet.stop()


def _paced_storm(
    base_url: str, payload: bytes, times: list[float], clients: int,
    mid_action=None, request_timeout: float = 30.0,
) -> dict:
    """Open-loop load: fire one request per entry of ``times`` (absolute
    seconds from leg start — the seeded arrival schedule), bounded by a
    worker pool so a lagging fleet backs pressure up into occupancy
    instead of unbounded client threads.  ``mid_action()`` runs once,
    as the halfway arrival is claimed.  Same outcome taxonomy as
    ``_fleet_storm``: every request must RESOLVE."""
    import threading
    import urllib.error
    import urllib.request

    lock = threading.Lock()
    counts = {"ok": 0, "shed": 0, "timeout": 0, "server_error": 0,
              "router_unreachable": 0, "hung": 0, "other": 0}
    idx = [0]
    acted = [False]
    t0 = time.monotonic()

    def one_request():
        req = urllib.request.Request(
            f"{base_url}/detect", data=payload, method="POST"
        )
        try:
            with urllib.request.urlopen(req, timeout=request_timeout) as r:
                json.loads(r.read().decode())
                return "ok"
        except urllib.error.HTTPError as e:
            body = {}
            try:
                body = json.loads(e.read().decode())
            except Exception:
                pass
            if e.code == 503:
                return "shed" if body.get("reason") else "other"
            if e.code == 504:
                return "timeout"
            return "server_error"
        except TimeoutError:
            return "hung"
        except Exception as e:
            if "timed out" in str(e).lower():
                return "hung"
            return "router_unreachable"

    def client():
        try:
            while True:
                with lock:
                    if idx[0] >= len(times):
                        return
                    i = idx[0]
                    idx[0] += 1
                    fire = i == len(times) // 2 and not acted[0]
                    if fire:
                        acted[0] = True
                delay = times[i] - (time.monotonic() - t0)
                if delay > 0:
                    time.sleep(delay)
                if fire and mid_action is not None:
                    mid_action()
                outcome = one_request()
                with lock:
                    counts[outcome] += 1
        except Exception as e:  # crash channel: a dead client = hung reqs
            with lock:
                counts["other"] += 1
            print(f"chaos FAIL: storm client crashed: {e!r}", flush=True)

    # watchdog: harness-local load generators; every request is bounded
    # by its own urlopen timeout, the driver joins with a budget below.
    threads = [
        threading.Thread(target=client, daemon=True)
        for _ in range(clients)
    ]
    for t in threads:
        t.start()
    budget = (times[-1] if times else 0.0) + request_timeout * 4 + 60
    for t in threads:
        t.join(timeout=budget)
    counts["submitted"] = idx[0]
    counts["resolved"] = sum(
        counts[k] for k in ("ok", "shed", "timeout", "server_error")
    )
    return counts


def _closed_replicas(base_url: str) -> list[str]:
    return [
        r["replica_id"]
        for r in _fleet_status(base_url).get("replicas", [])
        if r["state"] == "closed"
    ]


def _serve_autoscale_leg() -> None:
    """The seeded diurnal/spike day against a 1..3 autoscaling stub
    fleet, with a mid-spike SIGKILL of the seed replica: the fleet must
    scale 1→N under the spike, lose nothing (every request resolves,
    zero hangs), repair the preempted replica, and come back down to
    one replica once the day goes quiet."""
    sys.path.insert(0, _REPO)
    try:
        from batchai_retinanet_horovod_coco_tpu.utils.arrivals import (
            diurnal_spike_schedule,
        )
    finally:
        sys.path.pop(0)

    fleet = _FleetUnderTest("serve_autoscale", [
        "--spawn", "1", "--stub-engine", "--stub-delay-ms", "60",
        "--poll-interval", "0.2", "--respawn-delay-s", "0.3",
        "--fleet-timeout-s", "20",
        "--autoscale", "--min-replicas", "1", "--max-replicas", "3",
        "--target-occupancy", "0.15:0.5", "--autoscale-for-s", "0.4",
        "--autoscale-up-cooldown-s", "1",
        "--autoscale-down-cooldown-s", "2",
        "--autoscale-interval-s", "0.2",
    ])
    try:
        check(
            len(fleet.events("autoscaler_armed")) == 1,
            "autoscale leg: autoscaler_armed never emitted",
        )
        spawned = fleet.events("fleet_replica_spawned")
        check(
            len(spawned) == 1, f"expected 1 seed spawn, saw {len(spawned)}"
        )
        killed: list[str] = []

        def preempt():
            """SIGKILL a replica that is ROUTABLE at kill time — the
            autoscaler may have already scaled the seed replica away
            during the pre-spike lull, so the victim is chosen live."""
            pids: dict[str, int] = {}
            for e in (fleet.events("fleet_replica_spawned")
                      + fleet.events("fleet_replica_respawned")):
                pids[e["replica_id"]] = e["pid"]  # latest pid wins
            for rid in _closed_replicas(fleet.base_url):
                if rid not in pids:
                    continue
                try:
                    os.kill(pids[rid], signal.SIGKILL)
                except ProcessLookupError:
                    continue
                killed.append(rid)
                return
        # One compressed "day": sinusoidal base with a 4x burst window —
        # the ~55 rps spike saturates one 60ms-stub replica (≈16 rps)
        # and MUST force a scale-up; the window is wide enough (~6 s of
        # arrivals) that the breach re-sustains after the mid-spike
        # SIGKILL resets it.
        times = diurnal_spike_schedule(
            450, base_rate=12.0, seed=5, period_s=20.0, amplitude=0.5,
            spikes=((0.55, 0.5, 4.0),),
        )
        counts = _paced_storm(
            fleet.base_url, _fleet_payload(), times, clients=10,
            mid_action=preempt,
        )
        check(bool(killed), "autoscale leg: found no routable replica "
                            "to SIGKILL mid-spike")
        check(counts["hung"] == 0, f"autoscale leg: hung clients: {counts}")
        check(
            counts["router_unreachable"] == 0 and counts["other"] == 0,
            f"autoscale leg: dropped/garbled requests: {counts}",
        )
        check(
            counts["resolved"] == counts["submitted"],
            f"autoscale leg: silent drops: {counts}",
        )
        check(counts["ok"] > 0, f"autoscale leg: nothing completed: {counts}")
        # The spike forced at least one scale-up...
        ups = [
            e for e in fleet.events("autoscale_decision")
            if e.get("decision") == "scale_up"
        ]
        check(bool(ups), "autoscale leg: no scale_up decision under spike")
        check(
            _metric_value(fleet.base_url, "fleet_scale_up_total") >= 1,
            "autoscale leg: fleet_scale_up_total never incremented",
        )
        check(
            len(fleet.events("fleet_replica_joined")) >= 1,
            "autoscale leg: no replica joined the router",
        )
        # ... the SIGKILLed seed replica was repaired (respawn budget) ...
        _wait_until(
            lambda: len(fleet.events("fleet_replica_respawned")) >= 1,
            60, "autoscale leg: preempted replica never respawned",
        )
        # ... and the quiet tail of the day scales back down to min.
        _wait_until(
            lambda: len(_closed_replicas(fleet.base_url)) == 1
            and _metric_value(
                fleet.base_url, "fleet_scale_down_total"
            ) >= 1,
            90, "autoscale leg: fleet never scaled back down to 1",
        )
        # Post-scale-down traffic still serves (zero-drop drain).
        post = _fleet_storm(
            fleet.base_url, _fleet_payload(), total=8, clients=2
        )
        check(
            post["ok"] == post["submitted"],
            f"autoscale leg: post-scale-down traffic unhealthy: {post}",
        )
        # The decision surface is on the scrape.
        _code, metrics_body = _http_get(f"{fleet.base_url}/metrics")
        for fam in ("fleet_replicas_desired", "fleet_replicas_active",
                    "fleet_occupancy", "fleet_scale_up_total",
                    "fleet_scale_down_total"):
            check(
                fam.encode() in metrics_body,
                f"autoscale leg: {fam} missing from fleet /metrics",
            )
    finally:
        fleet.stop()


def _serve_scale_to_zero_leg() -> None:
    """A cold tier (min_replicas=0): strict idleness takes the fleet to
    ZERO replicas; the first request sheds at the edge and that demand
    signal respawns capacity — the client's retry loop lands."""
    fleet = _FleetUnderTest("serve_scale_zero", [
        "--spawn", "1", "--stub-engine", "--stub-delay-ms", "5",
        "--poll-interval", "0.2", "--fleet-timeout-s", "20",
        "--autoscale", "--min-replicas", "0", "--max-replicas", "2",
        "--target-occupancy", "0.15:0.6", "--autoscale-for-s", "0.4",
        "--autoscale-up-cooldown-s", "0.5",
        "--autoscale-down-cooldown-s", "1",
        "--autoscale-interval-s", "0.2",
    ])
    try:
        warm = _fleet_storm(
            fleet.base_url, _fleet_payload(), total=4, clients=2
        )
        check(
            warm["ok"] == warm["submitted"],
            f"scale-to-zero leg: warm traffic unhealthy: {warm}",
        )
        # Idle → the last replica drains away: an EMPTY fleet.
        _wait_until(
            lambda: not _fleet_status(fleet.base_url).get("replicas"),
            90, "scale-to-zero leg: idle fleet never reached 0 replicas",
        )
        downs = [
            e for e in fleet.events("autoscale_decision")
            if e.get("decision") == "scale_down"
        ]
        check(
            bool(downs) and downs[-1].get("reason") == "idle",
            f"scale-to-zero leg: expected an idle scale_down: {downs}",
        )
        # First request hits the empty fleet: a REASONED shed, then the
        # demand signal scales from zero and a bounded retry loop lands.
        payload = _fleet_payload()
        deadline = time.monotonic() + 90
        outcomes = []
        recovered = False
        while time.monotonic() < deadline:
            code, body = 0, b""
            try:
                import urllib.request
                req = urllib.request.Request(
                    f"{fleet.base_url}/detect", data=payload,
                    method="POST",
                )
                with urllib.request.urlopen(req, timeout=20) as r:
                    code, body = r.status, r.read()
            except Exception as e:
                import urllib.error
                if isinstance(e, urllib.error.HTTPError):
                    code, body = e.code, e.read()
            outcomes.append(code)
            if code == 200:
                recovered = True
                break
            time.sleep(0.5)
        check(
            recovered,
            f"scale-to-zero leg: fleet never recovered from zero "
            f"(outcomes {outcomes[-10:]})",
        )
        wakes = [
            e for e in fleet.events("autoscale_decision")
            if e.get("reason") == "demand_scale_from_zero"
        ]
        check(
            len(wakes) >= 1,
            "scale-to-zero leg: no demand_scale_from_zero decision",
        )
    finally:
        fleet.stop()


def run_serve_legs() -> None:
    """The fleet serve schedule (``make fleet-smoke`` / ``--serve``).
    Since ISSUE 14 the replicas run CONTINUOUS in-flight batching (the
    serve default; the kill leg pins the advertised slot-pool fields),
    so the chaos contracts are proven against the slot-pool path."""
    _serve_kill_leg()
    _serve_canary_leg()


def run_autoscale_legs() -> None:
    """The autoscaling schedule (``make scale-smoke`` / ``--autoscale``,
    ISSUE 19): the diurnal/spike 1→N→1 leg with a mid-spike SIGKILL,
    then the scale-to-zero cold-tier leg."""
    _serve_autoscale_leg()
    if not _failures:
        _serve_scale_to_zero_leg()


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--smoke", action="store_true",
                   help="bounded CI leg: one mid-save SIGKILL + one NaN "
                        "auto-resume (make chaos-smoke)")
    p.add_argument("--serve", action="store_true",
                   help="serve fleet legs only (make fleet-smoke): "
                        "SIGKILL one stub replica mid-load behind the "
                        "fleet router (zero hangs/silent drops, router "
                        "200s throughout, breaker reopens after respawn) "
                        "+ the slow-canary rollback leg (exactly one "
                        "canary_rollback, fleet back to baseline)")
    p.add_argument("--autoscale", action="store_true",
                   help="autoscale legs only (make scale-smoke): the "
                        "seeded diurnal/spike day against a 1..3 "
                        "autoscaling stub fleet with a mid-spike "
                        "SIGKILL (1→N on the spike, preemption "
                        "repaired, back to 1 when quiet, zero "
                        "hangs/drops), then the scale-to-zero cold "
                        "tier (idle fleet reaches 0 replicas and "
                        "recovers on the first request)")
    p.add_argument("--comm", action="store_true",
                   help="comm leg only (make chaos-comm): SIGKILL a "
                        "--comm-compress int8 run mid-save; the resume "
                        "must restore the EF residual state (or cleanly "
                        "zero it with one structured ef_reset event) and "
                        "rejoin the uninterrupted compressed baseline")
    p.add_argument("--steps", type=int, default=10,
                   help="target step count for kill legs")
    p.add_argument("--kills-per-phase", type=int, default=4,
                   help="full mode: occurrences per save phase "
                        "(5 phases x 4 = the >= 20-kill schedule)")
    args = p.parse_args(argv)

    if args.serve:
        run_serve_legs()
        print(json.dumps({
            "chaos": "ok" if not _failures else "FAIL",
            "failures": _failures,
        }), flush=True)
        return 1 if _failures else 0

    if args.autoscale:
        run_autoscale_legs()
        print(json.dumps({
            "chaos": "ok" if not _failures else "FAIL",
            "failures": _failures,
        }), flush=True)
        return 1 if _failures else 0

    if args.comm:
        _comm_leg()
        if not _failures:
            _comm_leg(hier=True)  # per-hop EF durability (ISSUE 16)
        print(json.dumps({
            "chaos": "ok" if not _failures else "FAIL",
            "failures": _failures,
        }), flush=True)
        return 1 if _failures else 0

    steps = args.steps
    baseline_dir, baseline = _baseline(steps)
    if _failures:
        return 1

    if args.smoke:
        _kill_leg("smoke_midsave", "tmp_write@1", baseline, steps)
        _nan_leg()
    else:
        kills = 0
        for n in range(1, args.kills_per_phase + 1):
            for phase in PHASES:
                _kill_leg(f"{phase}@{n}", f"{phase}@{n}", baseline, steps)
                kills += 1
                if _failures:
                    break
            if _failures:
                break
        # Mid-step (between saves) external kills.
        if not _failures:
            for at in (3, 5):
                _kill_leg(
                    f"midstep_{at}", None, baseline, steps, kill_at_step=at
                )
                kills += 2 - 1
        if not _failures:
            _torn_dir_legs(baseline, steps)
            _nan_leg()
        if not _failures:
            _comm_leg()  # compression+EF durability (ISSUE 13)
        if not _failures:
            _comm_leg(hier=True)  # per-hop EF durability (ISSUE 16)
        if not _failures:
            run_serve_legs()  # the serve-side half of the full schedule
        if not _failures:
            run_autoscale_legs()  # elasticity contracts (ISSUE 19)
        print(f"# chaos: {kills} scheduled kills executed", flush=True)

    if not _failures:
        shutil.rmtree(baseline_dir, ignore_errors=True)
    print(json.dumps({
        "chaos": "ok" if not _failures else "FAIL",
        "failures": _failures,
    }), flush=True)
    return 1 if _failures else 0


if __name__ == "__main__":
    sys.exit(main())

# Cluster lifecycle targets — the operator surface of the reference's W3/W4
# layer (SURVEY.md §2.1: Makefile + Batch AI cluster/job JSON), retargeted at
# Cloud TPU pod slices.  Every target delegates to launch/cluster.py, which
# is unit-tested and supports DRY=1 to print the gcloud command instead of
# running it.
#
#   make create NAME=ret-pod ACCEL=v5litepod-256
#   make submit NAME=ret-pod TRAIN_ARGS="--preset pod coco /mnt/coco"
#   make status NAME=ret-pod
#   make delete NAME=ret-pod
#   make test | make smoke | make chip-smoke

NAME ?= retinanet-pod
ZONE ?= us-east5-b
ACCEL ?= v5litepod-256
TRAIN_ARGS ?= --preset pod coco /mnt/coco
DRY ?=
DRYFLAG = $(if $(DRY),--dry-run,)
CLUSTER = python -m batchai_retinanet_horovod_coco_tpu.launch.cluster

.PHONY: create submit status delete test test-timings smoke chip-smoke \
	canaries convergence-full lint lint-obs check-static \
	perf-report telemetry-smoke numerics-smoke chaos chaos-smoke \
	chaos-comm fleet-smoke fleet-obs-smoke stream-smoke scale-smoke

create:
	$(CLUSTER) create --name $(NAME) --zone $(ZONE) --accelerator $(ACCEL) $(DRYFLAG)

submit:
	$(CLUSTER) submit --name $(NAME) --zone $(ZONE) $(DRYFLAG) -- $(TRAIN_ARGS)

status:
	$(CLUSTER) status --name $(NAME) --zone $(ZONE) $(DRYFLAG)

delete:
	$(CLUSTER) delete --name $(NAME) --zone $(ZONE) $(DRYFLAG)

test:
	python -m pytest tests/ -q

# Regenerate the committed timing snapshot (budget mechanism,
# tests/conftest.py): run the fast tier, write TEST_TIMINGS.md (seconds by
# file and the slowest tests, from the junit file).  Six workers by file:
# the shape of the driver's own run of the tier.  A failing tier stops
# make before the snapshot is written.
test-timings:
	python -m pytest tests/ -q -m "not slow" \
	  -p xdist -n 6 --dist loadfile \
	  --junitxml=/tmp/fast_tier_timings.xml
	python scripts/update_test_timings.py /tmp/fast_tier_timings.xml

# End-to-end synthetic smoke on a virtual CPU mesh (no data, no TPU needed).
smoke:
	python train.py synthetic --platform cpu --backbone resnet_test --f32 \
	  --image-min-side 64 --image-max-side 64 --batch-size 8 --num-devices 8 \
	  --steps 20 --synthetic-size 64

# The quickest proof that the system still starts on the chip: train →
# eval → checkpoint → export → serve at flagship width, plus every Pallas
# kernel against its jnp path, in one process.  Needs a TPU and fails
# without one (there is no CPU mode; `make smoke` is the CPU smoke).
chip-smoke:
	python chip_smoke.py

# All four XLA-partitioner canaries in one shot (VERDICT r5 next-round #5):
# each asserts its bug's PRESENCE on the current jax/XLA (or skips when the
# installed version doesn't exhibit it) — a flip after a jax upgrade is the
# signal to re-measure the guards.  Filing-ready upstream text per repro:
# scripts/xla_repros/ISSUES.md.
canaries:
	python -m pytest tests/distributed/test_spatial_train.py -q -k canary

# Invariant lint engine (ISSUE 5): project-wide AST passes encoding the
# repo's concurrency/jit/clock/collective contracts — bounded-queues,
# thread-error-contract, jit-purity, monotonic-clock, collective-safety,
# watchdog-coverage — against the committed baseline
# (batchai_retinanet_horovod_coco_tpu/analysis/baseline.json; new findings
# fail, fixed grandfathered ones must be removed via --update-baseline, so
# the baseline only shrinks).  `make lint` = engine + both legacy audits
# (the watchdog shim, and the HLO collective audit at reduced width on a
# tiny virtual mesh — the slow leg, ~1 min of XLA compile).  Suppression
# grammar: '# lint: <rule>: <why>' with a REQUIRED rationale.  Also runs
# in tier-1 (tests/unit/test_lint.py::TestLiveTree).
# --jobs 8: the per-file phase fans out over a thread pool (ISSUE 20);
# the report is byte-identical to the serial run.
lint:
	python -m batchai_retinanet_horovod_coco_tpu.analysis --jobs 8
	python scripts/audit_threads.py
	python scripts/audit_collectives.py --reduced --devices 2

# Live telemetry smoke (ISSUE 9): CPU serve smoke over a stub engine →
# scrape + schema-check GET /metrics (request-latency summary, shed
# counters, queue-depth gauges, Prometheus text format) and GET /healthz
# (200 live → 503 naming the stalled component under an injected
# watchdog stall → recovery), plus the registry-vs-snapshot consistency
# check.  No chip, no dataset — CI-safe; also aggregated into
# check-static.
telemetry-smoke:
	JAX_PLATFORMS=cpu python scripts/telemetry_smoke.py

# Numerics flight recorder smoke (ISSUE 10): CPU train smoke with an
# injected mid-run NaN → asserts, without any rerun, that ONE
# NUMERICS_DUMP.json lands naming the first non-finite layer, the
# built-in nonfinite SLO rule fires EXACTLY ONCE (metrics.jsonl + trace
# timeline), the auto-emitted PERF_REPORT ranks the numerics:divergence
# verdict #1, and the numerics-off step leaks no summary keys.  No chip,
# no dataset — CI-safe; aggregated into check-static.
numerics-smoke:
	JAX_PLATFORMS=cpu python scripts/numerics_smoke.py

# Fault-injection harness (ISSUE 11, scripts/chaos.py): SIGKILL a real
# CPU training subprocess at every phase of the checkpoint write protocol
# (snapshot, tmp-write, manifest-commit, rename, finalize — >= 20
# scheduled kills) plus mid-step external kills, manufactured torn
# checkpoint dirs, and an injected-NaN --auto-resume leg; asserts a
# restorable checkpoint survives EVERY kill and the resumed run's losses
# are bit-identical to an uninterrupted baseline (--resume-elastic
# re-derives the stream position).  chaos-smoke is the bounded CI leg
# (one mid-save kill + the NaN leg, ~4 subprocess runs).
chaos:
	JAX_PLATFORMS=cpu RETINANET_LOCK_DEBUG=1 python scripts/chaos.py

chaos-smoke:
	JAX_PLATFORMS=cpu RETINANET_LOCK_DEBUG=1 python scripts/chaos.py --smoke

# Comm chaos leg alone (ISSUE 13, scripts/chaos.py --comm): SIGKILL a
# compressed+EF training run mid-save, assert the resume restores the EF
# residual state from the checkpoint (or cleanly zeros it with ONE
# structured ef_reset event) and the losses rejoin the uninterrupted
# baseline envelope.  Also part of the full `make chaos` schedule.
chaos-comm:
	JAX_PLATFORMS=cpu RETINANET_LOCK_DEBUG=1 python scripts/chaos.py --comm

# Serve-fleet chaos (ISSUE 12, scripts/chaos.py --serve): the REAL fleet
# CLI over 2 stub-engine replica subprocesses — SIGKILL one mid-load and
# assert every request completes or sheds WITH A REASON (zero hung
# clients, zero silent drops), the router's /healthz stays 200 and its
# /metrics scrape carries the fleet families throughout, and the circuit
# breaker readmits the replica after the supervisor respawns it; then a
# deliberately slow stub canary behind the SLO gate must produce EXACTLY
# ONE canary_rollback event with the fleet back at baseline weights.
# CPU-only, no dataset — wired into check-static.
fleet-smoke:
	JAX_PLATFORMS=cpu RETINANET_LOCK_DEBUG=1 python scripts/chaos.py --serve

# Fleet observability smoke (ISSUE 15, scripts/fleet_obs_smoke.py): the
# real fleet CLI + 2 stub replicas with --obs-trace on — SIGKILL one
# replica (exactly ONE fleet-availability slo_violation, breaker readmits
# the respawn), force a shed-driven re-dispatch with both replicas alive
# (one trace id, serve_request spans on BOTH replica tracks of the merged
# trace.json), check federated fleet /metrics equals each replica's own
# exposition after quiescing, and run `obs.analyze --fleet` over the
# artifacts — the verdict must NAME the killed replica.  CPU-only, no
# dataset — wired into check-static.
fleet-obs-smoke:
	JAX_PLATFORMS=cpu RETINANET_LOCK_DEBUG=1 python scripts/fleet_obs_smoke.py

# Streaming detection smoke (ISSUE 18, scripts/stream_smoke.py): the real
# fleet CLI + 2 stub-video replicas — 3 seeded drift streams race
# single-image traffic over HTTP /stream/*, the frame-delta cache must
# hit on the drift plateaus, track ids must hold stable between scene
# cuts, and a mid-stream SIGKILL of a pinned replica must re-pin each of
# its streams with exactly one stream_repinned event and ZERO dropped
# frames.  CPU-only, no dataset — wired into check-static.
stream-smoke:
	JAX_PLATFORMS=cpu RETINANET_LOCK_DEBUG=1 python scripts/stream_smoke.py

# Autoscaling smoke (ISSUE 19, scripts/chaos.py --autoscale): the seeded
# diurnal/spike day against a real 1..3 autoscaling stub fleet — the
# spike must scale 1→N (a mid-spike SIGKILL is repaired through the
# respawn budget), the quiet tail must scale back to 1, and every
# request resolves (zero hangs, zero silent drops); then the cold tier:
# an idle min_replicas=0 fleet reaches ZERO replicas and the first
# request's shed (demand_scale_from_zero) respawns capacity so the
# client's retry lands.  CPU-only, no dataset — wired into check-static.
scale-smoke:
	JAX_PLATFORMS=cpu RETINANET_LOCK_DEBUG=1 python scripts/chaos.py --autoscale

# Aggregate for everything chip-free: one target CI can
# run without touching an accelerator (chaos-smoke DOES run a few real
# CPU training subprocesses over generated synthetic data — budget the
# job for minutes, not seconds).
check-static: lint telemetry-smoke numerics-smoke chaos-smoke fleet-smoke fleet-obs-smoke stream-smoke scale-smoke
	@echo "check-static: lint engine + watchdog audit + HLO collective audit + telemetry smoke + numerics smoke + chaos smoke + fleet smoke + fleet obs smoke + stream smoke + scale smoke all green"

# Static watchdog-coverage audit alone (ISSUE 3; now a shim over the lint
# engine's watchdog-coverage rule — same CLI, same exit codes).  Also runs
# in tier-1 (tests/unit/test_obs.py::test_audit_threads_clean).
lint-obs:
	python scripts/audit_threads.py

# Perf doctor (ISSUE 8, obs/analyze): turn an obs dir's own artifacts
# (merged trace.json + metrics.jsonl) into one machine-readable
# <OBS_DIR>/PERF_REPORT.json — step-time decomposition, pipeline overlap
# efficiency, queue/stall correlation, MFU estimate, ranked top-3
# bottleneck verdict (RUNBOOK "Perf doctor").  perf-report analyzes an
# existing obs dir (OBS_DIR, default artifacts/obs — any --obs-trace run
# auto-emits the same report at exit; this target is the post-hoc path).
OBS_DIR ?= artifacts/obs
perf-report:
	python -m batchai_retinanet_horovod_coco_tpu.obs.analyze $(OBS_DIR)

# Flagship-resolution convergence artifact (VERDICT r2 #2): the REAL recipe
# — resnet50 frozen_bn, multistep decays at 2/3 and 8/9 of --steps, warmup,
# weight decay — at the 800x1344 bucket, on synthetic data generated at
# exactly that shape, on the real chip, through the CLI.  Writes
# artifacts/convergence_full/metrics.jsonl (train curve + eval mAP at each
# --eval-every); the committed copy is the evidence, rerunnable with this
# one command (~45 min on v5e-1; host-pipeline-bound on few-core boxes).
# --lr 0.16 at global batch 8 = effective peak 5e-3 under the linear-scaling
# rule (train/optim.py: lr * global_batch / 256 — the reference's hvd.size()
# scaling, which a single-chip run must compensate for).
convergence-full:
	python train.py synthetic --synthetic-size 800x1344 --synthetic-images 64 \
	  --synthetic-classes 3 --synthetic-root /tmp/synthetic_coco_full \
	  --backbone resnet50 --norm frozen_bn --batch-size 8 --lr 0.16 \
	  --steps 2500 --warmup-steps 250 --schedule multistep \
	  --image-min-side 800 --image-max-side 1344 \
	  --eval-every 500 --log-every 50 --workers 8 \
	  --snapshot-path /tmp/convergence_full_ckpt --checkpoint-every 500 \
	  --log-dir artifacts/convergence_full

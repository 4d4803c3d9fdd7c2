# Dev entry points (the reference's Maven/devtools tier, L0).
PY ?= python

# Hard wall-clock ceiling on every smoke drill: a wedged device (or a
# deadlocked drill) must fail THIS step in minutes, not hang the CI job
# until its global limit (docs/FAULTS.md).  -k 10 escalates to SIGKILL
# when the SIGTERM grace expires — the drills' subprocess trees are
# kill-safe by design (that is half of what they drill).
SMOKE_TIMEOUT ?= 600
SMOKE = timeout -k 10 $(SMOKE_TIMEOUT)

.PHONY: test test-fast metrics-smoke feeder-smoke chaos-smoke rescue-smoke service-smoke coalesce-smoke fleet-smoke job-smoke pod-smoke device-smoke warm-smoke agg-smoke trace-smoke native clean

test:
	$(PY) -m pytest tests/ -q

# Fast tier: every subsystem's functional tests, minus the heavy
# differential/fuzz/adapter/jit-compile suites (marked @pytest.mark.slow).
# Budget: < 5 min on a 1-core host (VERDICT r05 item 8) — the wall time
# prints on every run so drift is visible immediately.
# No -x: CI runs this target, and a fail-fast tier would hide every
# failure after the first (one CI round-trip per broken test).
test-fast:
	@start=$$(date +%s); \
	$(PY) -m pytest tests/ -q -m "not slow"; rc=$$?; \
	echo "fast-tier wall time: $$(( $$(date +%s) - start ))s (budget 300s)"; \
	exit $$rc

# Telemetry smoke: boot a sidecar with the /metrics endpoint, parse one
# batch, scrape over HTTP and fail on malformed Prometheus exposition or
# missing stage metrics (docs/OBSERVABILITY.md).  CI runs this after the
# fast tier.
metrics-smoke:
	$(SMOKE) $(PY) -m logparser_tpu.tools.metrics_smoke

# Feeder smoke: the sharded ingest fabric (2 workers x 2 shard sizes x
# 2 transports — zero-copy shared-memory ring AND the pickled escape
# hatch — over a demolog corpus) must be byte- and parse-parity-
# identical to single-process parse_blob, with the feeder_* metric
# families (ring counters included) exposed and zero leaked /dev/shm
# segments after pool teardown (docs/FEEDER.md).  CI runs this after
# metrics-smoke.
feeder-smoke:
	$(SMOKE) $(PY) -m logparser_tpu.tools.feeder_smoke

# Chaos smoke: the fault-injection matrix (every fault class in
# tools/chaos.py x ring+pickle transports at 2 real process workers) —
# every faulted run must RECOVER to byte parity with the corpus (worker
# respawn + shard replay, poison-shard quarantine, ring-fault re-frame,
# transport demotion), the recovery ledger counters must move, and no
# /dev/shm segment may leak (docs/FEEDER.md "Failure model & recovery").
# CI runs this after feeder-smoke.
chaos-smoke:
	$(SMOKE) $(PY) -m logparser_tpu.tools.chaos_smoke

# Rescue smoke: dirty corpus with forced ~5% device rejects — the former
# overflow class must stay on device (full-int64 decoder), the forced
# rejects must rescue bit-identically through the batched pipeline above
# a throughput floor, and /metrics must expose the per-reason
# oracle_routed_lines_total counters.  CI runs this after feeder-smoke.
rescue-smoke:
	$(SMOKE) $(PY) -m logparser_tpu.tools.rescue_smoke

# Service smoke: the serving-tier robustness drill (docs/SERVICE.md) —
# a loadgen burst at 2x the admission budget against a live sidecar must
# produce ZERO connection resets (all refusals structured BUSY frames),
# /metrics must expose the shed/session families, and a graceful drain
# with a session in flight must flip /readyz to 503, complete the
# admitted work, and leak no session threads.  CI runs this after
# chaos-smoke.
service-smoke:
	$(SMOKE) $(PY) -m logparser_tpu.tools.service_smoke

# Coalesce smoke: the continuous-batching drill (docs/SERVICE.md
# "Continuous batching") — K concurrent sessions with interleaved
# mixed-size requests through the cross-session coalescer must receive
# ARROW payloads BYTE-identical to solo parsing (zero resets), at least
# one shared batch must carry >1 session, the coalesce metric families
# must be live on /metrics, and the C++ reference client
# (native/svc_client.cc) must replay the golden protocol vector with
# byte-identical payloads and drive live requests.  CI runs this after
# service-smoke.
coalesce-smoke:
	$(SMOKE) $(PY) -m logparser_tpu.tools.coalesce_smoke

# Fleet smoke: the replicated front tier's failover drill
# (docs/SERVICE.md "Fleet") — a front over 3 real sidecar processes
# must serve byte-identically to a solo sidecar, absorb a 1-of-3
# SIGKILL under loadgen traffic with ZERO resets (structured
# BUSY{sidecar_failover} frames only) and respawn the dead slot, and
# complete a live rolling restart with zero failed requests — with the
# merged fleet /metrics exposition valid.  CI runs this after
# coalesce-smoke.
fleet-smoke:
	$(SMOKE) $(PY) -m logparser_tpu.tools.fleet_smoke

# Job smoke: the durable batch tier's kill-drill (docs/JOBS.md) — run a
# corpus->sharded-Arrow job, SIGKILL (-9) it mid-run from outside, and
# resume from the manifest: the merged output (data + reject tables)
# must be byte-identical to a single-shot run, committed shards must
# never be re-parsed, and no temp file or shm segment may leak.  CI
# runs this after service-smoke.
job-smoke:
	$(SMOKE) $(PY) -m logparser_tpu.tools.job_smoke

# Pod smoke: the pod-scale fabric's kill drill (docs/JOBS.md "Pod
# jobs") — a 2-host pod (each host a real subprocess of the per-host
# jobs CLI, parsing data-parallel over a forced multi-device mesh via
# XLA_FLAGS) must survive a SIGKILL of one host mid-run: partial merge
# legal, lost host resumed with committed shards never re-parsed, and
# the final merged output byte-identical to a single-host run — with
# the pod_* metric families live and zero leaked shm/tmp.  CI runs
# this after job-smoke.
pod-smoke:
	$(SMOKE) $(PY) -m logparser_tpu.tools.pod_smoke

# Device smoke: the device-tier fault drills (docs/FAULTS.md) — each
# chaos-injected device fault (RESOURCE_EXHAUSTED mid-stream, sticky
# OOM -> bucket clamp, wedged execution under the deadline, failed jit
# compile -> oracle demotion, byte-budget structured reject) must
# recover with output BYTE-IDENTICAL to the undisturbed run and zero
# aborted batches, with the same parser instance still serving every
# ingest surface afterwards; plus the jobs CLI's SIGTERM preemption
# drill (exit 3, resume re-parses zero committed shards).  CI runs
# this after pod-smoke.
device-smoke:
	$(SMOKE) $(PY) -m logparser_tpu.tools.device_chaos_smoke

# Warm-boot smoke: the persistent compile cache's acceptance drill
# (docs/COMPILE.md) — a real sidecar cold-boots against an empty cache
# (first request compiles, the background prewarmer lands every bucket
# ladder rung incl. the coalesced-batch shape on disk), then a FRESH
# sidecar warm-boots against the same cache and must compile NOTHING:
# parser_compile_total{phase=lower|compile} == 0 (deserialize only,
# counter-asserted over /metrics), prewarm all cache-served, ARROW
# payload byte-identical to the cold boot's, exposition valid.  CI
# runs this after device-smoke.
warm-smoke:
	$(SMOKE) $(PY) -m logparser_tpu.tools.warm_smoke

# Analytics smoke: the on-device aggregation pushdown's exactness
# contract (docs/ANALYTICS.md) — a LIVE service session configured with
# an aggregate spec must return a state EQUAL to the host-oracle
# referee (garbage + forced long-overflow fold rows included) while
# recording positive analytics_d2h_bytes_saved_total; an aggregate job
# SIGKILLed mid-run and resumed must merge byte-identical sidecars AND
# AggregateState wire bytes vs a single-shot run; zero leaked
# threads/tmp/shm and a valid exposition.  CI runs this after
# device-smoke.
agg-smoke:
	$(SMOKE) $(PY) -m logparser_tpu.tools.agg_smoke

# Distributed-tracing + flight-recorder drill (docs/OBSERVABILITY.md
# "Tracing"): a real two-session front fleet must produce ONE connected
# trace — two front_session roots, their service_request spans linked
# into a single shared coalesce_batch span with pipeline-stage children
# — and a SIGUSR2 flight dump from a live sidecar must name the
# injected device fault it silently absorbed during warmup.  CI runs
# this after agg-smoke.
trace-smoke:
	$(SMOKE) $(PY) -m logparser_tpu.tools.trace_smoke

lint:
	$(PY) -m ruff check logparser_tpu tests
	$(PY) -m mypy logparser_tpu --no-error-summary

# Build the C++ host tier (ctypes library); falls back to numpy when absent.
native:
	$(PY) -c "from logparser_tpu.native import native_available; print('native:', native_available())"

clean:
	rm -rf logparser_tpu/native/_build build dist *.egg-info
	find . -name __pycache__ -type d -exec rm -rf {} +

.PHONY: all native tsan stress stress-faults chaos chaos-write test check perf-smoke bench-stripe trace-gate landing-gate cache-gate qos-gate pushdown-gate coldstart-gate scrub-gate kvpage-smoke multichip-gate autotune-gate passthru-gate tier-gate lint-strom sanitize sanitize-smoke clean

all: native

native:
	$(MAKE) -C csrc

tsan:
	$(MAKE) -C csrc tsan

stress:
	$(MAKE) -C csrc stress

# Randomized fault-plan stress on the loopback fake (fixed seed, so CI
# failures reproduce): transient plans must heal byte-identically through
# the retry/fallback ladder, persistent plans must latch within the task
# deadline.  Override STROM_STRESS_SEED / STROM_STRESS_ROUNDS to widen.
stress-faults:
	JAX_PLATFORMS=cpu python -m nvme_strom_tpu.testing.stress_faults
	JAX_PLATFORMS=cpu python -m pytest tests/test_faults.py -q -m faults

# Deterministic member-survival gate (PR 6): seeded fault schedules
# (fail-stop, flaky, slow member, corrupt-once, fail-stop-then-rejoin)
# through the mirrored striped fake plus one native leg, asserting byte
# identity, bounded latency and legal health transitions.  Override
# STROM_CHAOS_SEED / STROM_CHAOS_ROUNDS to widen.
chaos:
	JAX_PLATFORMS=cpu python -m nvme_strom_tpu.testing.chaos
	JAX_PLATFORMS=cpu python -m pytest tests/test_chaos.py -q -m chaos

# Write-side survival gate (ISSUE 11): seeded write-path fail-stop with
# mirror failover + dirty-extent resync replay, ENOSPC first-error latch,
# torn-mirror heal under write_verify, and SIGKILL-mid-save checkpoint
# crash consistency (strom_ckpt verify rides inside).  Same seed knobs.
chaos-write:
	JAX_PLATFORMS=cpu python -m nvme_strom_tpu.testing.chaos write
	JAX_PLATFORMS=cpu python -m pytest tests/test_write_faults.py -q -m faults

STRESS_FILE := /tmp/strom_stress_src.bin

# The gate runs what we build (VERDICT r2 #6): the pytest suite, then the
# native-engine concurrency stress — plain (asserts batched-submission
# syscall discipline) and TSAN (a data race introduced into
# strom_engine.cc fails here).  TSAN needs ASLR-compatible runtimes; an
# environment where the sanitizer itself cannot start is skipped with a
# notice, a real race report is a hard failure.
test: native stress
	python -m pytest tests/ -x -q
	@test -f $(STRESS_FILE) || dd if=/dev/urandom of=$(STRESS_FILE) bs=1M count=8 status=none
	csrc/stress_test $(STRESS_FILE) 8 20
	@out=$$(csrc/stress_test_tsan $(STRESS_FILE) 4 8 2>&1); rc=$$?; \
	echo "$$out" | tail -1; \
	if [ $$rc -ne 0 ]; then \
	  if echo "$$out" | grep -qi "unexpected memory mapping\|personality\|re-exec\|FATAL: ThreadSanitizer: unsupported"; then \
	    echo "TSAN cannot start in this runtime; stress_test_tsan skipped"; \
	  else \
	    echo "$$out"; exit 1; \
	  fi; \
	fi
	@echo "multichip dryrun (virtual 8-device mesh)..."
	@XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	  python -c "import jax; jax.config.update('jax_platforms','cpu'); \
	  import __graft_entry__ as g; g.dryrun_multichip(8); \
	  print('dryrun OK')"

# The perf-marked pytest assertions (counters and bytes, CPU only).  The
# headline bench.py needs a TPU and fails without one; on the chip run
# `python chip_smoke.py` (see README "Running on the chip").
perf-smoke:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m perf

# Member-lane scale-out smoke (PR 5): the 2-member latency-bound
# synthetic must beat single-member through the engine's per-member
# submission lanes (ratio > 1.0) — deterministic on any disk, since the
# synthetic curve is bounded by aggregate in-flight window, not media.
# The full 1/2/4 curve (real files + synthetic, journaled to
# STRIPE_SCALING.jsonl) is `python bench.py --stripe-scaling`.
bench-stripe:
	BENCH_SMOKE=1 BENCH_STRIPE_MEMBERS=1,2 BENCH_STRIPE_MIN_RATIO=1.0 \
	  JAX_PLATFORMS=cpu python bench.py --stripe-scaling
	@echo "bench-stripe ok"

# Trace-overhead gate (ISSUE 7): a 64MB direct-read pass under
# trace_policy=sampled must ride within 3% of off (A/B interleaved
# medians) — the production-safety contract for always-on sampled
# tracing.  Override STROM_TRACE_GATE_RUNS / STROM_TRACE_GATE_PCT.
trace-gate:
	JAX_PLATFORMS=cpu python -m nvme_strom_tpu.testing.trace_gate
	JAX_PLATFORMS=cpu python -m pytest tests/test_trace.py -q -m trace

# Zero-copy landing gate (ISSUE 8): on the direct-eligible synthetic
# config the pipeline must deliver bytes_touched_per_byte_delivered
# <= 1.05 (the staging hop's second touch is gone), and landing=direct
# must stay byte-identical to landing=staged down the fault ladder
# (transient fail-stop, corrupt-once re-read, hedged legs).  Override
# STROM_LANDING_GATE_RATIO to widen.
landing-gate:
	JAX_PLATFORMS=cpu python -m nvme_strom_tpu.testing.landing_gate
	JAX_PLATFORMS=cpu python -m pytest tests/test_landing.py -q -m landing

# Residency-tier gate (ISSUE 9): on the latency-injected synthetic a
# hot rescan must beat the cold scan >= 2x (every chunk served from the
# owned pinned-RAM tier, no engine submission), results must stay
# byte-identical under eviction pressure, and a write-back-invalidated
# extent must never be served stale.  Override STROM_CACHE_GATE_RATIO.
cache-gate:
	JAX_PLATFORMS=cpu python -m nvme_strom_tpu.testing.cache_gate
	JAX_PLATFORMS=cpu python -m pytest tests/test_cache.py -q -m cache

# Unified-tiering gate (ISSUE 20): one placement/migration engine over
# HBM -> pinned RAM -> SSD.  On the latency-injected thrash config (a
# seeded-shuffle working set at ~0.8x the combined capacity) the unified
# space must beat the split-tier baseline >= 1.3x, bytes must stay
# identical under promotion/demotion churn, and demand faults must keep
# filling through a mirror leg after a mid-run member fail-stop.
# Override STROM_TIER_GATE_RATIO.
tier-gate:
	JAX_PLATFORMS=cpu python -m nvme_strom_tpu.testing.tier_gate
	JAX_PLATFORMS=cpu python -m pytest tests/test_tiering.py -q -m tiering

# Compute-pushdown gate (ISSUE 14): on the latency-injected compressible
# synthetic the packed scan's effective logical GB/s must beat the
# same-run raw transport >= 1.2x (it moves ~1/ratio of the wire chunks
# for the same logical rows), Query-path pushdown answers must stay
# byte-identical to the unpacked scan under residency eviction churn,
# and a mid-scan member fail-stop must serve packed extents from the
# mirror partner with the aggregate unchanged.  Override
# STROM_PUSHDOWN_GATE_RATIO.
pushdown-gate:
	JAX_PLATFORMS=cpu python -m nvme_strom_tpu.testing.pushdown_gate
	JAX_PLATFORMS=cpu python -m pytest tests/test_pushdown.py -q -m pushdown

# Cold-start gate (ISSUE 15): depth-pipelined weight streaming must
# beat the serial load-then-adopt baseline by STROM_COLDSTART_GATE_RATIO
# (default 2x) on the latency-injected synthetic checkpoint, land every
# leaf byte-identical under crc verification, adopt layers in order
# (asserted from weight_stream flight-recorder spans), and refuse a
# flipped byte with EBADMSG.  The serving pytest marker rides along.
coldstart-gate:
	JAX_PLATFORMS=cpu python -m nvme_strom_tpu.testing.coldstart_gate
	JAX_PLATFORMS=cpu python -m pytest tests/test_serving.py -q -m serving

# KV-paging A/B smoke (ISSUE 15): the serving KV block pool over a
# paired-mirror spill, working set 4x hbm_cache_bytes, every block
# byte-identical including one seeded mirror-member fail-stop pass;
# journals to KVPAGE_AB.jsonl and fails on any identity miss.
kvpage-smoke:
	BENCH_SMOKE=1 JAX_PLATFORMS=cpu python bench.py --kvpage

# QoS fairness gate (ISSUE 12): against a real stromd on the
# latency-injected synthetic, 3:1-weighted tenants must receive bytes
# within 25% of 3:1 while both are backlogged, and a latency-class
# tenant's p95 queue wait must stay bounded under a bulk antagonist.
# Override STROM_QOS_GATE_RATIO / _TOL / _P95_MS.
qos-gate:
	JAX_PLATFORMS=cpu python -m nvme_strom_tpu.testing.qos_gate
	JAX_PLATFORMS=cpu python -m pytest tests/test_daemon.py -q -m daemon

# Resident-integrity gate (ISSUE 16): seeded bit-rot in all three
# residency tiers (host ARC slab, HBM extent, KV spill block) must be
# detected by the background scrubber and healed byte-identically from
# SSD / the mirror leg — with the rotten member health-debited — and a
# mid-run memlock-budget shrink must shed + degrade to pass-through
# with zero reader-visible ENOMEM.  The `integrity` pytest marker
# rides along.
scrub-gate:
	JAX_PLATFORMS=cpu python -m nvme_strom_tpu.testing.scrub_gate
	JAX_PLATFORMS=cpu python -m pytest tests/test_integrity.py -q -m integrity

# Multichip gate (ISSUE 17): sharded loading over 1/2/4 virtual hosts
# on the latency-bound synthetic must scale aggregate GB/s >= 1.6x at
# 2 hosts and >= 2.8x at 4 (every page one serialized latency-bearing
# request per host session), the gathered array must equal the file
# bytes at every host count, and the 2-host sharded cold-start wall
# must be <= 0.6x single-host.  Journals to MULTICHIP_SCALING.jsonl;
# the `multihost` pytest marker rides along.  Override
# STROM_MULTICHIP_GATE_RATIO2 / _RATIO4 / _COLD_RATIO / _ROUNDS.
multichip-gate:
	JAX_PLATFORMS=cpu python -m nvme_strom_tpu.testing.multichip_gate
	JAX_PLATFORMS=cpu python -m pytest tests/test_shardload.py -q -m multihost

# Self-driving data-path gate (ISSUE 18): from deliberately bad static
# knobs (submit_window=2, 256K request cap) on the latency-injected
# synthetic, the online controller must converge to >= 1.5x the static
# throughput within 20 epochs with byte identity throughout and a
# settled knob trajectory (no step reversals in the last 5 epochs); a
# seeded mid-run member fail-stop must freeze tuning with no throughput
# cliff beyond the degraded floor; the strided-scan readahead leg must
# reach >= 0.5 cache hit ratio under its token-bucket byte budget; and
# readahead=off must move no counters.  The `autotune` pytest marker
# rides along.  Override STROM_AUTOTUNE_RATIO / STROM_AUTOTUNE_EPOCHS /
# STROM_AUTOTUNE_DEGRADED_X / STROM_RA_HIT_RATIO.
autotune-gate:
	JAX_PLATFORMS=cpu python -m nvme_strom_tpu.testing.autotune_gate
	JAX_PLATFORMS=cpu python -m pytest tests/test_autotune.py -q -m autotune

# Raw-passthrough gate (ISSUE 19): on the deterministic URING_CMD
# emulator, a fragmented + partially-ineligible layout must read
# byte-identical through the mixed passthrough/O_DIRECT split, a seeded
# mirrored-member fail-stop must fall off the passthrough lane with
# every exit counted, engine_backend pinned to uring/threadpool must
# move the same bytes with zero passthrough counters, and the
# submit-overhead A/B row must journal to PASSTHRU_AB.jsonl.  The
# `passthru` pytest marker rides along.
passthru-gate:
	JAX_PLATFORMS=cpu python -m nvme_strom_tpu.testing.passthru_gate
	JAX_PLATFORMS=cpu python -m pytest tests/test_passthru.py -q -m passthru

# stromlint (ISSUE 10): the project-invariant static checker — lock
# discipline, buffer lifetimes, native-ABI drift against csrc/strom_tpu.h,
# stats/trace surface completeness, config hygiene.  Zero unsuppressed
# findings and zero stale baseline entries or the gate fails; the
# analyzer's own test suite (the `lint` marker) rides along.
lint-strom:
	JAX_PLATFORMS=cpu python -m nvme_strom_tpu.analysis
	JAX_PLATFORMS=cpu python -m pytest tests/test_stromlint.py -q -m lint

# ASan/UBSan gate for the native engine (ISSUE 10 satellite): build
# strom_engine.cc + stress_test.cc under address+UB sanitizers and run
# the full concurrency stress; any report aborts the binary and fails
# the target.  The TSan variant of the same stress is part of `make
# test` (stress_test_tsan, with a skip when TSAN cannot start in the
# runtime).
sanitize:
	$(MAKE) -C csrc sanitize
	@test -f $(STRESS_FILE) || dd if=/dev/urandom of=$(STRESS_FILE) bs=1M count=8 status=none
	csrc/stress_test_asan $(STRESS_FILE) 8 20
	@echo "sanitize ok (ASan/UBSan clean)"

# Fast variant riding in `make check`: same sanitized binary, short pass.
sanitize-smoke:
	$(MAKE) -C csrc sanitize
	@test -f $(STRESS_FILE) || dd if=/dev/urandom of=$(STRESS_FILE) bs=1M count=8 status=none
	csrc/stress_test_asan $(STRESS_FILE) 2 4
	@echo "sanitize-smoke ok"

# The everyday gate: static analysis first (cheapest, fails fastest),
# then tier-1 tests plus the perf smokes, the seeded member-survival
# schedules, the trace-overhead, landing and cache gates, and the
# short sanitizer pass.
check: lint-strom sanitize-smoke perf-smoke bench-stripe chaos chaos-write trace-gate landing-gate cache-gate tier-gate qos-gate pushdown-gate coldstart-gate scrub-gate kvpage-smoke multichip-gate autotune-gate passthru-gate
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m "not slow"

clean:
	$(MAKE) -C csrc clean

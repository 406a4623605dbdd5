.PHONY: all native tsan stress stress-faults chaos chaos-write test check perf-smoke scrub-gate lint-strom sanitize sanitize-smoke clean

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
# chip benchmark is `python -m benchmark.run` (BENCHMARK.json, PERF.md);
# the chip bring-up drive is `python chip_smoke.py` (README "Running on
# the chip").
perf-smoke:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m perf

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
# then the short sanitizer pass, the perf smokes, the seeded
# member-survival schedules, the resident-integrity gate and the
# tier-1 tests.
check: lint-strom sanitize-smoke perf-smoke chaos chaos-write scrub-gate
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m "not slow"

clean:
	$(MAKE) -C csrc clean

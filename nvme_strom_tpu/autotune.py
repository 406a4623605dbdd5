"""Self-driving data path (ISSUE 18): per-session online autotuner +
trace-driven predictive readahead.

Every hot-path knob the engine grew across PRs 4-17 (``submit_window``,
the per-member chunk cap, ``hedge_ms``, lane count) is a static config
Var, while the observability stack already measures everything a
controller needs.  This module closes the sensors->knobs loop:

* **AutoTuner** — one controller per :class:`~.engine.Session`.  Each
  epoch (``autotune_interval_ms``) it samples the global and per-member
  latency-histogram deltas plus the delivered-byte delta, and feeds a
  :class:`HillClimber` that adjusts, per stripe member, the effective
  submit window (which is also the member's executor-lane width on the
  Python path), the chunk/coalesce cap, and the hedge latch — plus the
  global native lane count at engine-rebuild boundaries.  All bounds
  come from each Var's declared ``minval``/``maxval`` (the stromlint
  ``config-bounds`` rule makes an unbounded controlled knob a finding).
  When the fault ladder has any member in suspect/quarantined/rejoining
  the controller FREEZES — it never fights the health machine.
* **ReadaheadPredictor** — per-source stride + extent-graph successor
  detection over recent demand submit spans.  Predictions are issued as
  bounded speculative fills into the PR 9 residency tier through the
  normal fault ladder, budgeted by a :class:`~.daemon.qos.TokenBucket`
  (``readahead_budget_mb_s``) so prefetch can never starve demand
  reads; speculative fills are provenance-tagged so the ARC ghost lists
  are never trained by speculation (cache.py).

Both halves follow the flight recorder's one-branch-when-off contract:
``autotune``/``readahead`` are read once at Session construction and
the engine hot paths test plain attributes.  The satellite fold of the
per-member :class:`~.engine.AdaptiveChunkSizer` lives in
:meth:`AutoTuner.chunk_cap`: the tuner hosts the sizer dict and is the
single writer of the effective chunk cap — ``autotune=off`` preserves
the sizer's halve/restore behavior bit-for-bit.
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import deque
from typing import Dict, List, Optional, Tuple

from .cache import residency_cache as _rcache
from .config import config
from .daemon.qos import TokenBucket
from .stats import hist_percentiles, stats
from .trace import recorder as _trace

__all__ = ["Reading", "KnobFamily", "HillClimber", "ReadaheadPredictor",
           "AutoTuner"]


class Reading:
    """One epoch's sensor deltas.

    ``throughput`` is delivered bytes per nanosecond of wall clock over
    the epoch (only ratios between epochs matter), ``p99_ns`` the worst
    per-member p99 service latency from the histogram deltas (global
    histogram when no member delta has mass), ``nreq`` the completed
    request count — 0 marks an idle epoch the climber must not
    attribute a probe to."""

    __slots__ = ("throughput", "p99_ns", "nreq")

    def __init__(self, throughput: float = 0.0,
                 p99_ns: Optional[int] = None, nreq: int = 0) -> None:
        self.throughput = float(throughput)
        self.p99_ns = p99_ns
        self.nreq = int(nreq)

    @property
    def idle(self) -> bool:
        return self.nreq <= 0


class KnobFamily:
    """One controlled knob across stripe members.

    Hard bounds come from the backing Var's declared minval/maxval;
    steps are geometric (x2 / /2) and clamp per member, so members can
    diverge only at the bounds.  ``armed=False`` (e.g. the hedge latch
    under ``hedge_policy=off``) removes the family from probing without
    losing its state."""

    __slots__ = ("name", "lo", "hi", "integral", "armed", "values")

    def __init__(self, name: str, lo: float, hi: float, *,
                 integral: bool = True) -> None:
        self.name = name
        self.lo = float(lo)
        self.hi = float(hi)
        self.integral = bool(integral)
        self.armed = True
        self.values: Dict[int, float] = {}

    def _clamp(self, v: float) -> float:
        v = min(max(v, self.lo), self.hi)
        return float(int(v)) if self.integral else v

    def ensure(self, member: int, initial: float) -> None:
        if member not in self.values:
            self.values[member] = self._clamp(float(initial))

    def stepped(self, direction: str) -> Dict[int, float]:
        """{member: new value} for one geometric step; empty when every
        member is already pinned at the relevant bound."""
        out: Dict[int, float] = {}
        for m, v in self.values.items():
            nv = self._clamp(v * 2.0 if direction == "up" else v / 2.0)
            if nv != v:
                out[m] = nv
        return out


class HillClimber:
    """Pure hill-climb policy: knob families + epoch readings in,
    step/revert/freeze decisions out.  No session coupling, so unit
    tests drive it with synthetic readings (tests/test_autotune.py).

    Probe lifecycle (two epochs per decision):

    * epoch N — apply one geometric probe on one (family, direction);
    * epoch N+1 — compare the reading against the pre-probe baseline.
      An accepted probe (throughput gain >= ``min_gain`` with p99
      within ``p99_tol`` x baseline) keeps climbing the same direction
      immediately; a rejection or p99 regression steps BACK and marks
      the (family, direction) pair rejected at that value.

    Rejected markers are the hysteresis: a settled trajectory never
    re-probes a direction whose outcome it has already measured at the
    current operating point, so it cannot oscillate (no reversals once
    settled).  Accepted steps also mark the opposite direction rejected
    — the climb just came from there and measured it worse.  Idle
    epochs defer evaluation; a freeze (the health machine owns the
    stripe) reverts any outstanding probe and suspends probing, while
    rejected markers survive the freeze."""

    def __init__(self, families: List[KnobFamily], *,
                 min_gain: float = 0.05, p99_tol: float = 1.5,
                 cooldown: int = 4) -> None:
        self.families = list(families)
        self.min_gain = float(min_gain)
        self.p99_tol = float(p99_tol)
        self.cooldown = int(cooldown)
        #: per-epoch event tuples — the knob-trajectory record
        self.history: List[list] = []
        self._probe: Optional[tuple] = None  # (family, dir, {m: old})
        self._baseline: Optional[Reading] = None
        self._cooldown: Dict[Tuple[str, str], int] = {}
        self._rejected: Dict[Tuple[str, str], Dict[int, float]] = {}

    def family(self, name: str) -> Optional[KnobFamily]:
        for fam in self.families:
            if fam.name == name:
                return fam
        return None

    def step(self, reading: Reading, *, frozen: bool = False) -> List[tuple]:
        """One epoch: returns [(kind, family, direction, values)] with
        kind in step/revert/freeze (values is {member: applied value},
        None for freeze)."""
        events: List[tuple] = []
        for k in [k for k, v in self._cooldown.items() if v <= 1]:
            del self._cooldown[k]
        for k in self._cooldown:
            self._cooldown[k] -= 1
        if frozen:
            if self._probe is not None:
                fam, d, olds = self._probe
                fam.values.update(olds)
                self._probe = None
                events.append(("revert", fam.name, d, dict(olds)))
            self._baseline = None
            events.append(("freeze", None, None, None))
            self.history.append(events)
            return events
        if reading.idle:
            # no traffic: nothing to attribute an outstanding probe to
            self.history.append(events)
            return events
        if self._probe is not None:
            events.extend(self._evaluate(reading))
        else:
            self._baseline = reading
            ev = self._try_probe()
            if ev is not None:
                events.append(ev)
        self.history.append(events)
        return events

    def _evaluate(self, reading: Reading) -> List[tuple]:
        fam, d, olds = self._probe
        self._probe = None
        base = self._baseline
        gain = (reading.throughput / base.throughput
                if base is not None and base.throughput > 0 else 0.0)
        p99_bad = bool(base is not None and base.p99_ns and reading.p99_ns
                       and reading.p99_ns > base.p99_ns * self.p99_tol)
        if gain >= 1.0 + self.min_gain and not p99_bad:
            # accepted: the opposite direction is now measured-worse
            opp = "down" if d == "up" else "up"
            self._rejected[(fam.name, opp)] = dict(fam.values)
            self._rejected.pop((fam.name, d), None)
            self._baseline = reading
            nxt = self._apply(fam, d)
            return [("step", fam.name, d, nxt)] if nxt else []
        fam.values.update(olds)
        self._rejected[(fam.name, d)] = dict(olds)
        self._cooldown[(fam.name, d)] = self.cooldown
        self._baseline = reading
        return [("revert", fam.name, d, dict(olds))]

    def _try_probe(self) -> Optional[tuple]:
        for fam in self.families:
            if not fam.armed or not fam.values:
                continue
            for d in ("up", "down"):
                key = (fam.name, d)
                if key in self._cooldown:
                    continue
                rej = self._rejected.get(key)
                if rej is not None and rej == fam.values:
                    continue
                nxt = self._apply(fam, d)
                if nxt:
                    return ("step", fam.name, d, nxt)
        return None

    def _apply(self, fam: KnobFamily, d: str) -> Optional[Dict[int, float]]:
        """Apply one geometric step on *fam* as the outstanding probe;
        None when every member is pinned at the bound."""
        olds = dict(fam.values)
        stepped = fam.stepped(d)
        if not stepped:
            return None
        fam.values.update(stepped)
        self._probe = (fam, d, olds)
        return dict(fam.values)


class ReadaheadPredictor:
    """Access-pattern model for one source, in chunk-grid units.

    A constant-stride detector over the last three demand spans (equal
    stride AND equal extent) predicts the next span; non-strided but
    repeating walks fall back to an extent-graph successor table — the
    last observed follower of each span start."""

    __slots__ = ("_recent", "_succ")

    def __init__(self) -> None:
        self._recent: deque = deque(maxlen=8)   # (first_chunk, nchunks)
        self._succ: Dict[int, Tuple[int, int]] = {}

    def observe(self, first: int, nchunks: int) -> None:
        if self._recent:
            pf, _pn = self._recent[-1]
            if first != pf:
                self._succ[pf] = (int(first), int(nchunks))
                if len(self._succ) > 512:
                    self._succ.pop(next(iter(self._succ)))
        self._recent.append((int(first), int(nchunks)))

    def predict(self) -> Optional[Tuple[int, int]]:
        r = self._recent
        if len(r) >= 3:
            (f0, n0), (f1, n1), (f2, n2) = r[-3], r[-2], r[-1]
            s = f2 - f1
            if s != 0 and f1 - f0 == s and n0 == n1 == n2:
                return f2 + s, n2
        if r:
            return self._succ.get(r[-1][0])
        return None


class AutoTuner:
    """Per-session controller thread: sensors -> knobs, plus the
    predictive-readahead issue loop.

    ``autotune``/``readahead``/``autotune_interval_ms``/
    ``readahead_budget_mb_s`` are read once at Session construction
    (the recorder/cache configure() convention); with both off the
    session pays one predicted branch per hot-path site and no thread
    is spawned.  ``step_epoch()`` is public so tests drive epochs
    synchronously and deterministically."""

    #: token-bucket burst: this many seconds of budget may be issued
    #: back-to-back before shaping bites (floor 1 MiB)
    BURST_S = 0.25
    #: executor-lane width ceiling the window knob may drive a member
    #: pool to (native lanes are separately capped at 16 rings)
    MAX_POOL_WIDTH = 64
    #: chunks per speculative fill ceiling (one fill never outweighs a
    #: demand task's planning slice)
    MAX_PREFETCH_CHUNKS = 64

    def __init__(self, session) -> None:
        self._sess = session
        self.enabled = bool(config.get("autotune"))
        self.ra_active = bool(config.get("readahead"))
        self.active = self.enabled or self.ra_active
        self.interval_s = max(float(config.get("autotune_interval_ms")),
                              10.0) / 1e3
        #: the per-member AdaptiveChunkSizer dict (PR 4/5), hosted HERE
        #: so the controller is the single writer of the effective chunk
        #: cap; Session._chunk_sizers aliases this dict for test access
        self.chunk_sizers: Dict[int, object] = {}
        self.freeze_reason = ""
        self.last_step = ""
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # applied per-member knob values (hot paths read these dicts
        # directly; epoch application keeps them in sync with the
        # climber's family values)
        self._windows: Dict[int, int] = {}
        self._caps: Dict[int, int] = {}
        self._hedges: Dict[int, float] = {}
        self._last_sample: Optional[tuple] = None
        self._climber: Optional[HillClimber] = None
        if self.enabled:
            self._climber = self._make_climber()
        dvar = config.describe().get("dma_max_size")
        self._dma_lo = int(dvar.minval) if dvar and dvar.minval else 4 << 10
        self._dma_hi = int(dvar.maxval) if dvar and dvar.maxval else 16 << 20
        # readahead state: id(source) -> (weakref, predictor, chunk_size)
        self._predictors: Dict[int, tuple] = {}
        self._issued: deque = deque(maxlen=256)
        self._issued_set: set = set()
        self._ra_rate = float(config.get("readahead_budget_mb_s")) * (1 << 20)
        self._bucket = TokenBucket(
            self._ra_rate, max(self._ra_rate * self.BURST_S, 1 << 20))

    # -- controller policy wiring -------------------------------------

    @staticmethod
    def _make_climber() -> HillClimber:
        vars_ = config.describe()

        def bounds(name: str, lo: float, hi: float) -> Tuple[float, float]:
            v = vars_.get(name)
            if v is not None:
                if v.minval is not None:
                    lo = float(v.minval)
                if v.maxval is not None:
                    hi = float(v.maxval)
            return lo, hi

        wlo, whi = bounds("submit_window", 1, 256)
        clo, chi = bounds("coalesce_limit", 0, 256 << 20)
        dlo, _dhi = bounds("dma_max_size", 4 << 10, 16 << 20)
        hlo, hhi = bounds("hedge_ms", 0.0, 60000.0)
        return HillClimber([
            KnobFamily("window", max(wlo, 1.0), whi),
            KnobFamily("cap", max(clo, dlo), chi),
            KnobFamily("hedge_ms", max(hlo, 1.0), hhi, integral=False),
        ])

    def _applied(self, fname: str) -> dict:
        return {"window": self._windows, "cap": self._caps,
                "hedge_ms": self._hedges}[fname]

    def _seed_members(self) -> None:
        """Arm knob families for every member the stats registry has
        seen (member 0 always exists), at the current static values —
        the controller starts where the operator's config sits."""
        members = set(stats.member_snapshot()) | {0}
        init = {"window": float(max(int(config.get("submit_window")), 1)),
                "cap": float(int(config.get("dma_max_size"))),
                "hedge_ms": float(config.get("hedge_ms"))}
        for fam in self._climber.families:
            v0 = init[fam.name]
            for m in members:
                if m not in fam.values:
                    fam.ensure(m, v0)
                    applied = self._applied(fam.name)
                    applied[m] = int(fam.values[m]) if fam.integral \
                        else fam.values[m]
            if fam.name == "hedge_ms":
                # never probe a knob with no effect: the hedge latch is
                # dead weight under hedge_policy=off
                fam.armed = str(config.get("hedge_policy")) != "off"

    # -- sensors -------------------------------------------------------

    def _read_sensors(self) -> Reading:
        """Epoch deltas of delivered bytes, the global service-latency
        histogram, and every per-member histogram (worst member p99 is
        the regression signal; the global histogram covers the Python
        pool path, whose per-member service times feed the aggregate)."""
        now = time.monotonic_ns()
        counters = stats.snapshot(debug=True, reset_max=False).counters
        total = counters.get("total_dma_length", 0)
        hist = stats.lat_hist_snapshot()
        mh = stats.member_hist_snapshot()
        last, self._last_sample = self._last_sample, (now, total, hist, mh)
        if last is None:
            return Reading(0.0, None, 0)
        dt = max(now - last[0], 1)
        dbytes = total - last[1]
        dh = [a - b for a, b in zip(hist, last[2])]
        nreq = sum(dh)
        p99 = None
        for m, h in mh.items():
            prev = last[3].get(m)
            dm = [a - b for a, b in zip(h, prev)] if prev else list(h)
            if sum(dm):
                mp99 = hist_percentiles(dm, (0.99,))[0]
                if mp99 and (p99 is None or mp99 > p99):
                    p99 = mp99
        if p99 is None and nreq:
            p99 = hist_percentiles(dh, (0.99,))[0]
        return Reading(dbytes / dt, p99, nreq)

    def _health_freeze(self) -> bool:
        """Freeze predicate: the controller never fights the fault
        ladder — any member off plain HEALTHY suspends probing."""
        try:
            bad = self._sess._member_health.unhealthy_members()
        except Exception:   # noqa: BLE001 — sensors must not kill tuning
            bad = []
        if bad:
            m, state = bad[0]
            self.freeze_reason = f"member {m} {state}"
            return True
        self.freeze_reason = ""
        return False

    # -- epoch ---------------------------------------------------------

    def step_epoch(self) -> None:
        """One controller epoch: sample sensors, run the climber, apply
        knob movements, then run one readahead issue pass.  Public so
        tests drive it synchronously; the background thread calls
        exactly this."""
        if self.enabled:
            self._tune_epoch()
        if self.ra_active:
            self.readahead_tick()

    def _tune_epoch(self) -> None:
        self._seed_members()
        reading = self._read_sensors()
        frozen = self._health_freeze()
        events = self._climber.step(reading, frozen=frozen)
        for kind, fname, direction, vals in events:
            if kind == "freeze":
                stats.add("nr_autotune_freeze")
                if _trace.active:
                    _trace.instant("autotune_step",
                                   args={"dir": "freeze",
                                         "reason": self.freeze_reason})
                continue
            stats.add("nr_autotune_step" if kind == "step"
                      else "nr_autotune_revert")
            self.last_step = f"{fname}:{direction}" \
                + (" (revert)" if kind == "revert" else "")
            self._apply(fname, direction, vals, kind)
            if _trace.active:
                _trace.instant(
                    "autotune_step",
                    args={"knob": fname, "dir": direction, "kind": kind,
                          "values": {str(m): v for m, v in vals.items()}})
        self._publish_knobs()

    def _apply(self, fname: str, direction: str, vals: Dict[int, float],
               kind: str) -> None:
        applied = self._applied(fname)
        retire: List[int] = []
        for m, v in vals.items():
            nv = float(v) if fname == "hedge_ms" else int(v)
            if applied.get(m) != nv:
                applied[m] = nv
                if fname == "window":
                    retire.append(m)
        sess = self._sess
        for m in retire:
            # the member's executor lane is recreated at the tuned
            # width on its next submit; queued work drains on the old
            try:
                sess._retire_member_pool(m)
            except Exception:   # noqa: BLE001 — knobs must not kill I/O
                pass
        if fname == "window" and kind == "step" and direction == "up" \
                and retire:
            # engine-rebuild boundary: give the native engine one lane
            # per unit of tuned concurrency, up to its 16-ring cap
            try:
                sess._autotune_scale_lanes(max(self._windows.values()))
            except Exception:   # noqa: BLE001
                pass

    def _publish_knobs(self) -> None:
        for m in self._windows:
            stats.member_knobs(m, window=self._windows.get(m),
                               cap=self._caps.get(m),
                               hedge_ms=self._hedges.get(m),
                               step=self.last_step,
                               freeze=self.freeze_reason)

    # -- effective knobs (engine indirection) --------------------------

    def submit_window(self, default: int) -> int:
        """Effective planning-slice width (max across members: the
        slice is a per-task global while lane widths are per member)."""
        w = self._windows
        return max(w.values()) if w else default

    def pool_width(self, member: int, default: int) -> int:
        """Tuned executor-lane width for *member* (the real concurrency
        bound on the Python path), clamped to MAX_POOL_WIDTH."""
        if not self.enabled:
            return default
        v = self._windows.get(member)
        return default if v is None else max(1, min(int(v),
                                                    self.MAX_POOL_WIDTH))

    def dma_cap(self, default: int) -> int:
        """Effective request split/coalesce cap for the planner, from
        the tuned per-member caps (max), inside dma_max_size's declared
        bounds."""
        caps = self._caps
        if not caps:
            return default
        return max(self._dma_lo, min(max(caps.values()), self._dma_hi))

    def hedge_delay(self, member: int, base_s: float) -> float:
        """Tuned hedge latch for *member* in seconds; the health
        machine's policy decision (None = no hedging) stays upstream."""
        v = self._hedges.get(member)
        return base_s if v is None else max(float(v), 1.0) / 1e3

    def chunk_cap(self, floor: int, limit: int, member: int = 0) -> int:
        """Single writer of the effective chunk cap (satellite fold of
        the PR 4/5 AdaptiveChunkSizer): the sizer stays the burst
        halve/restore policy, the tuner supplies its ceiling.  With
        ``autotune=off`` this is bit-for-bit the old Session._adaptive_cap."""
        if self.enabled:
            tuned = self._caps.get(member)
            if tuned is not None:
                limit = max(floor, int(tuned))
        szr = self.chunk_sizers.get(member)
        if szr is None or szr.floor != floor or szr.limit != limit:
            from .engine import AdaptiveChunkSizer
            szr = self.chunk_sizers[member] = AdaptiveChunkSizer(floor, limit)
        return szr.effective

    # -- predictive readahead ------------------------------------------

    def observe_submit(self, source, chunk_size: int, chunk_ids) -> None:
        """Feed one demand submit span (engine hot path; called only
        when ``ra_active`` and never for speculative tasks, so the
        predictor cannot train on its own prefetches)."""
        sid = id(source)
        ent = self._predictors.get(sid)
        if ent is None or ent[0]() is not source or ent[2] != chunk_size:
            if len(self._predictors) >= 64:
                self._gc_predictors()
            try:
                ref = weakref.ref(source)
            except TypeError:
                return
            ent = (ref, ReadaheadPredictor(), int(chunk_size))
            self._predictors[sid] = ent
        ent[1].observe(min(chunk_ids), len(chunk_ids))

    def _gc_predictors(self) -> None:
        for sid in [s for s, e in self._predictors.items() if e[0]() is None]:
            del self._predictors[sid]

    def readahead_tick(self) -> None:
        """One issue pass: predict per source, drop already-resident
        and already-issued spans, then fill through the normal fault
        ladder under the token-bucket budget — over-budget predictions
        are SKIPPED (counted), never blocked on, so prefetch cannot
        starve demand reads."""
        if not self.ra_active or not _rcache.active:
            return
        now = time.monotonic()
        for sid, (wref, pred, cs) in list(self._predictors.items()):
            src = wref()
            if src is None:
                self._predictors.pop(sid, None)
                continue
            p = pred.predict()
            if p is None:
                continue
            first, n = p
            try:
                size = int(src.size)
            except Exception:   # noqa: BLE001 — source may be closing
                continue
            total = (size + cs - 1) // cs
            if first < 0 or first >= total:
                continue
            n = max(1, min(int(n), total - first, self.MAX_PREFETCH_CHUNKS))
            key = (sid, first, n)
            if key in self._issued_set:
                continue
            skey = _rcache.source_key(src)
            ids = [cid for cid in range(first, first + n)
                   if not _rcache.peek(skey, cid * cs,
                                       min(cs, size - cid * cs))]
            if not ids:
                self._remember(key)
                continue
            nbytes = sum(min(cs, size - cid * cs) for cid in ids)
            if self._ra_rate <= 0 \
                    or self._bucket.ready_in(nbytes, now) > 0:
                # budget exhausted (or budget 0 = predict-only): skip,
                # never wait — demand reads own the device time
                stats.add("nr_readahead_skip")
                continue
            self._bucket.consume(nbytes, now)
            self._remember(key)
            self._prefetch(src, ids, cs, nbytes)

    def _remember(self, key: tuple) -> None:
        if len(self._issued) == self._issued.maxlen:
            self._issued_set.discard(self._issued[0])
        self._issued.append(key)
        self._issued_set.add(key)

    def _prefetch(self, src, ids: List[int], cs: int, nbytes: int) -> None:
        sess = self._sess
        t0 = time.monotonic_ns()
        try:
            handle, _buf = sess.alloc_dma_buffer(len(ids) * cs)
        except Exception:   # noqa: BLE001 — allocation pressure: skip
            stats.add("nr_readahead_skip")
            return
        try:
            res = sess.memcpy_ssd2ram(src, handle, ids, cs,
                                      speculative=True)
            sess.memcpy_wait(res.dma_task_id, timeout=60.0)
            stats.add("nr_readahead_fill")
            stats.add("bytes_readahead", nbytes)
            if _trace.active:
                _trace.span("readahead_fill", t0, time.monotonic_ns(),
                            offset=ids[0] * cs, length=nbytes,
                            args={"chunks": len(ids)})
        except Exception:   # noqa: BLE001 — prefetch must never surface
            pass            # errors; demand reads retry through the ladder
        finally:
            try:
                sess.unmap_buffer(handle)
            except Exception:   # noqa: BLE001
                pass

    # -- lifecycle ------------------------------------------------------

    def start(self) -> None:
        """Spawn the controller thread (no-op with both halves off)."""
        if not self.active or self._thread is not None:
            return
        t = threading.Thread(target=self._loop, daemon=True,
                             name="strom-autotune")
        self._thread = t
        t.start()

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.step_epoch()
            except Exception:   # noqa: BLE001 — the controller must
                pass            # never take the data path down with it

    def stop(self) -> None:
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=2.0)

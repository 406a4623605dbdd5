"""GUC-style configuration registry.

Capability analog of the reference's four config tiers (SURVEY.md SS5.6):
PostgreSQL GUCs ``nvme_strom.*`` (reference pgsql/nvme_strom.c:1561-1640),
kernel module params ``verbose``/``stat_info`` (kmod/nvme_strom.c:76-82), CLI
flags, and OS deploy configs.  Here the tiers are, lowest to highest
precedence:

1. built-in defaults (registered below),
2. a config file (``strom_tpu.conf``, ``key = value`` lines; path from
   ``$STROM_TPU_CONF`` or ``./strom_tpu.conf``),
3. environment variables ``STROM_TPU_<NAME>`` (upper-cased),
4. runtime ``set()`` calls.

Each variable carries type, bounds and an optional cross-variable validation
hook, matching the reference's GUC bounds + ``_PG_init`` validation (chunk
size power-of-two, buffer a multiple of chunk; pgsql/nvme_strom.c:1637-1640).
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

__all__ = ["ConfigError", "Var", "Config", "config"]


class ConfigError(ValueError):
    pass


def _parse_bool(s: str) -> bool:
    v = s.strip().lower()
    if v in ("1", "true", "on", "yes"):
        return True
    if v in ("0", "false", "off", "no"):
        return False
    raise ConfigError(f"invalid boolean: {s!r}")


_SUFFIX = {"k": 1 << 10, "m": 1 << 20, "g": 1 << 30, "t": 1 << 40}


def _parse_size(s: str) -> int:
    """Parse '256k', '16m', '1g' or a plain integer (bytes)."""
    v = s.strip().lower()
    if v and v[-1] in _SUFFIX:
        return int(float(v[:-1]) * _SUFFIX[v[-1]])
    return int(v, 0)


@dataclass
class Var:
    name: str
    default: Any
    kind: str  # 'int' | 'size' | 'float' | 'bool' | 'str'
    minval: Optional[float] = None
    maxval: Optional[float] = None
    help: str = ""
    validate: Optional[Callable[[Any, "Config"], None]] = None
    #: back-compat alias: get/set on this name transparently resolve to
    #: the named canonical var (one stored value, two names).  The alias
    #: re-declares kind and bounds so surfaces that introspect the Var
    #: (the autotuner's clamp range, describe()) see the same contract.
    alias_of: Optional[str] = None

    def parse(self, raw: Any) -> Any:
        if self.kind == "bool":
            return raw if isinstance(raw, bool) else _parse_bool(str(raw))
        if self.kind == "int":
            val = raw if isinstance(raw, int) and not isinstance(raw, bool) else int(str(raw), 0)
        elif self.kind == "size":
            val = raw if isinstance(raw, int) and not isinstance(raw, bool) else _parse_size(str(raw))
        elif self.kind == "float":
            val = float(raw)
        elif self.kind == "str":
            return str(raw)
        else:  # pragma: no cover
            raise ConfigError(f"unknown kind {self.kind}")
        if self.minval is not None and val < self.minval:
            raise ConfigError(f"{self.name}={val} below minimum {self.minval}")
        if self.maxval is not None and val > self.maxval:
            raise ConfigError(f"{self.name}={val} above maximum {self.maxval}")
        return val


def _check_pow2(val: int, _cfg: "Config") -> None:
    if val & (val - 1):
        raise ConfigError(f"value {val} must be a power of two")


def _check_io_backend(val: str, _cfg: "Config") -> None:
    if val not in ("auto", "io_uring", "threadpool", "python"):
        raise ConfigError(f"io_backend must be auto|io_uring|threadpool|python, got {val!r}")


def _check_engine_backend(val: str, _cfg: "Config") -> None:
    if val not in ("auto", "passthru", "uring", "threadpool"):
        raise ConfigError(
            f"engine_backend must be auto|passthru|uring|threadpool, got {val!r}")


def _check_ici_permute(val: str, _cfg: "Config") -> None:
    if val not in ("auto", "pallas", "xla"):
        raise ConfigError(f"ici_permute must be auto|pallas|xla, got {val!r}")


def _check_h2d_path(val: str, _cfg: "Config") -> None:
    if val not in ("auto", "plain", "pinned_host"):
        raise ConfigError(f"h2d_path must be auto|plain|pinned_host, "
                          f"got {val!r}")


def _check_landing(val: str, _cfg: "Config") -> None:
    if val not in ("auto", "direct", "staged"):
        raise ConfigError(f"landing must be auto|direct|staged, got {val!r}")


def _check_numa_policy(val: str, _cfg: "Config") -> None:
    if val in ("auto", "off"):
        return
    if val.startswith("node:"):
        try:
            if int(val[5:]) >= 0:
                return
        except ValueError:
            pass
    raise ConfigError(f"numa_policy must be auto|off|node:N, got {val!r}")


def _check_hedge_policy(val: str, _cfg: "Config") -> None:
    if val not in ("off", "p99", "fixed"):
        raise ConfigError(f"hedge_policy must be off|p99|fixed, got {val!r}")


def _check_mirror(val: str, _cfg: "Config") -> None:
    if val not in ("none", "paired"):
        raise ConfigError(f"mirror must be none|paired, got {val!r}")


def _check_trace_policy(val: str, _cfg: "Config") -> None:
    if val not in ("off", "sampled", "all"):
        raise ConfigError(f"trace_policy must be off|sampled|all, got {val!r}")


def _check_integrity(val: str, _cfg: "Config") -> None:
    if val not in ("off", "transitions", "always"):
        raise ConfigError(f"integrity must be off|transitions|always, "
                          f"got {val!r}")


def _check_qos_class(val: str, _cfg: "Config") -> None:
    if val not in ("latency", "normal", "bulk"):
        raise ConfigError(f"qos_default_class must be latency|normal|bulk, "
                          f"got {val!r}")


def _check_pushdown(val: str, _cfg: "Config") -> None:
    if val not in ("auto", "on", "off"):
        raise ConfigError(f"pushdown must be auto|on|off, got {val!r}")


def _check_pushdown_codecs(val: str, _cfg: "Config") -> None:
    bad = [c for c in val.split(",") if c.strip()
           and c.strip() not in ("bitpack", "dict", "rle")]
    if bad:
        raise ConfigError(f"pushdown_codecs must be a comma list of "
                          f"bitpack|dict|rle, got {bad[0]!r}")


def _check_coalesce_limit(val: int, cfg: "Config") -> None:
    # 0 = coalescing off; otherwise the merge window must cover at least
    # one dma_max_size request or planning could emit nothing mergeable
    if val and val < cfg.get("dma_max_size"):
        raise ConfigError(f"coalesce_limit {val} below dma_max_size "
                          f"{cfg.get('dma_max_size')} (set 0 to disable)")


def _check_buffer_multiple(val: int, cfg: "Config") -> None:
    chunk = cfg.get("chunk_size")
    if chunk and val % chunk:
        raise ConfigError(f"buffer_size {val} must be a multiple of chunk_size {chunk}")


class Config:
    """Thread-safe layered config store."""

    def __init__(self) -> None:
        self._lock = threading.RLock()
        self._vars: Dict[str, Var] = {}
        self._values: Dict[str, Any] = {}
        self._register_builtins()
        self._load_file()
        self._load_env()

    # -- registration ------------------------------------------------------
    def register(self, var: Var) -> None:
        with self._lock:
            if var.name in self._vars:
                raise ConfigError(f"duplicate config var {var.name}")
            if var.alias_of is not None and var.alias_of not in self._vars:
                raise ConfigError(f"alias {var.name} targets unknown "
                                  f"var {var.alias_of}")
            self._vars[var.name] = var
            if var.alias_of is None:  # aliases store no value of their own
                self._values[var.name] = var.parse(var.default) if var.kind != "str" else var.default

    def _register_builtins(self) -> None:
        reg = self.register
        # pgsql GUC analogs (reference pgsql/nvme_strom.c:1561-1635)
        reg(Var("enabled", True, "bool", help="turn the direct-load scan path on/off"))
        reg(Var("chunk_size", 16 << 20, "size", minval=1 << 16, maxval=1 << 30,
                help="scan chunk size (default 16MB)", validate=_check_pow2))
        reg(Var("buffer_size", 1 << 30, "size", minval=1 << 20,
                help="DMA staging pool size (default 1GB)",
                validate=_check_buffer_multiple))
        reg(Var("numa_node_mask", -1, "int", help="bitmask of NUMA nodes usable for DMA buffers (-1 = all)"))
        reg(Var("async_depth", 8, "int", minval=1, maxval=1024,
                help="in-flight DMA tasks per scan ring (default 8)"))
        reg(Var("seq_page_cost", 0.25, "float", minval=0.0,
                help="planner cost per page for direct scan, fraction of VFS cost"))
        reg(Var("debug_no_threshold", False, "bool",
                help="force direct scan regardless of table size (test hook)"))
        # kernel-module-param analogs (kmod/nvme_strom.c:76-82,139-146)
        reg(Var("verbose", 0, "int", minval=0, maxval=2, help="debug log verbosity"))
        reg(Var("stat_info", True, "bool", help="collect per-stage statistics"))
        reg(Var("dma_max_size", 1 << 20, "size", minval=4 << 10, maxval=16 << 20,
                help="max merged I/O request (default 1MB, tuned for modern "
                     "NVMe; the reference capped at 256KB for 2017-era disks, "
                     "kmod/nvme_strom.c:139-146)",
                validate=_check_pow2))
        # TPU-framework-specific knobs
        reg(Var("io_backend", "auto", "str",
                help="'auto' | 'io_uring' | 'threadpool' | 'python'",
                validate=_check_io_backend))
        reg(Var("engine_backend", "auto", "str",
                help="native engine failover ladder position: 'auto' "
                     "tries nvme_passthru -> io_uring -> threadpool, "
                     "'passthru' demands the raw NVMe rung (session "
                     "falls back with the refusal counted when the host "
                     "cannot), 'uring'/'threadpool' skip the passthru "
                     "probe entirely — bit-for-bit the pre-v4 path",
                validate=_check_engine_backend))
        reg(Var("passthru_dev_glob", "/dev/ng*n*", "str",
                help="glob for the NVMe character device the passthrough "
                     "rung probes (first match wins; env "
                     "NSTPU_PASSTHRU_DEV overrides with an exact path)"))
        reg(Var("queue_depth", 32, "int", minval=1, maxval=4096,
                help="io_uring submission queue depth / outstanding requests"))
        reg(Var("engine_rings", 0, "int", minval=0, maxval=16,
                help="engine lane (queue) count; stripe members map "
                     "member mod lanes, each lane an independent submit "
                     "lock + reaper/workers + in-flight window (per-"
                     "device blk-mq HW queue analog).  0 = AUTO: the "
                     "session scales lanes to the stripe member count at "
                     "first striped submit (single-file sources stay at "
                     "one lane).  A fixed count pins it — set to the "
                     "number of DISTINCT physical NVMe devices backing "
                     "the stripe.  Env NSTPU_RINGS overrides for "
                     "experiments."))
        reg(Var("member_queue_depth", 0, "int", minval=0, maxval=4096,
                help="per-lane in-flight window when the engine scales "
                     "out to one lane per stripe member (engine_rings=0 "
                     "auto, or explicit >1).  0 inherits queue_depth; "
                     "lower it on shared backing disks where N full-"
                     "depth lanes would just multiply seek"))
        reg(Var("numa_policy", "auto", "str",
                help="NUMA placement for per-member engine lanes: "
                     "'auto' pins each member's reaper/worker threads to "
                     "the CPUs of the member device's local node (sysfs "
                     "probe; unknown node = leave unpinned), 'node:N' "
                     "pins every lane to node N, 'off' never touches "
                     "affinity.  The pgsql extension's node-local DMA "
                     "buffer + backend binding analog "
                     "(pgsql/nvme_strom.c:353-446,1126-1181)",
                validate=_check_numa_policy))
        reg(Var("staging_buffers", 3, "int", minval=2, maxval=16,
                help="pinned host staging buffers for the SSD->HBM pipeline (triple-buffered default)"))
        reg(Var("scan_dispatch_batch", 4, "int", minval=1, maxval=64,
                help="jitted-call coalescing width for streamed scan "
                     "compute: fold this many device-resident page "
                     "batches per kernel DISPATCH (one traced call over "
                     "K batches) instead of dispatching per batch.  On "
                     "a backend with high per-dispatch latency that "
                     "latency otherwise dominates streamed scans; 1 "
                     "disables (default not measured on chip)"))
        reg(Var("h2d_depth_max", 4, "int", minval=1, maxval=64,
                help="ceiling for the ADAPTIVE H2D pipeline depth: the "
                     "scan executor and checkpoint restore start 2-deep "
                     "and deepen while the consumer observes itself "
                     "blocking on transfer readiness, so consumer-tier "
                     "paths ride H2D bursts the way the mq32 loader does "
                     "instead of paying a fence per batch"))
        reg(Var("h2d_path", "auto", "str",
                help="host->HBM transfer path: 'plain' device_put from "
                     "the page-aligned pinned staging buffer (PJRT zero-"
                     "copies when alignment allows), 'pinned_host' two-"
                     "stage DMA through the PJRT pinned_host memory "
                     "space, 'auto' picks plain (default not measured "
                     "on chip); an unavailable pinned_host space is an "
                     "error, never a silent plain",
                validate=_check_h2d_path))
        reg(Var("landing", "auto", "str",
                help="destination landing for pipeline commands: "
                     "'direct' demands the zero-copy path (engine reads "
                     "land in an owned page-aligned LandingBuffer the "
                     "device array then ALIASES — no staging hop; "
                     "ineligible commands fall back staged with a "
                     "warning), 'staged' forces the pinned staging "
                     "ring, 'auto' picks direct whenever alignment, "
                     "dtype and backend allow (per-command choice "
                     "recorded in stats nr_landing_* and the flight "
                     "recorder's landing spans)",
                validate=_check_landing))
        reg(Var("backend_fence_timeout", 60.0, "float", minval=0.0,
                help="seconds a device fence (block_until_ready) may "
                     "block before the backend is declared LOST and "
                     "in-flight staging fails with ENODEV instead of "
                     "hanging (0 = unbounded; the reference's revocation "
                     "callback blocks until DMA drains, kmod/pmemmap.c:"
                     "149-208 — here the transport itself can die, so "
                     "the drain must be bounded)"))
        # fault-tolerance layer (PR 1): retry / deadline / checksum knobs
        reg(Var("io_retries", 3, "int", minval=0, maxval=64,
                help="max re-attempts of a direct read after a TRANSIENT "
                     "error before degrading to the buffered path "
                     "(0 = fail on first error, reference behaviour)"))
        reg(Var("retry_backoff_ms", 5.0, "float", minval=0.0,
                help="exponential-backoff base delay between direct-read "
                     "retries (doubles per attempt, jittered)"))
        reg(Var("retry_backoff_max_ms", 1000.0, "float", minval=0.0,
                help="backoff ceiling per retry sleep"))
        reg(Var("retry_jitter", 0.5, "float", minval=0.0, maxval=1.0,
                help="uniform jitter fraction applied to each backoff "
                     "sleep (0.5 = delay drawn from [0.5d, 1.0d])"))
        reg(Var("io_fallback", True, "bool",
                help="degrade to the buffered read path for an extent "
                     "after transient-retry exhaustion, and to the "
                     "threadpool backend when io_uring setup/submit "
                     "fails (off = latch the error instead)"))
        reg(Var("task_deadline_s", 60.0, "float", minval=0.0,
                help="per-DMA-task deadline: the watchdog latches "
                     "ETIMEDOUT on tasks RUNNING past this and cancels "
                     "their not-yet-started chunks, so memcpy_wait can "
                     "never hang (0 = no deadline)"))
        reg(Var("checksum_verify", False, "bool",
                help="verify per-page crc32c (heap page header word 7) "
                     "after chunks land; mismatches re-read then latch "
                     "EBADMSG.  Checksummed loads ride the instrumented "
                     "python I/O path"))
        reg(Var("checksum_retries", 2, "int", minval=0, maxval=16,
                help="re-reads attempted on a checksum mismatch before "
                     "the task latches a CORRUPTION error"))
        reg(Var("quarantine_after", 8, "int", minval=1, maxval=1 << 20,
                help="consecutive direct-read failures on one stripe "
                     "member before it is quarantined (reads route "
                     "buffered until quarantine_s expires)"))
        reg(Var("quarantine_s", 30.0, "float", minval=0.0,
                help="seconds a quarantined member stays on the "
                     "buffered path before the health machine moves it "
                     "to REJOINING and the token-bucket warmup re-probes "
                     "the direct path"))
        # member-health state machine + hedging + mirroring (PR 6)
        reg(Var("suspect_ratio", 6.0, "float", minval=1.0,
                help="a member whose service-latency p99 drifts past "
                     "suspect_ratio x the stripe median p99 (log2-ns "
                     "histograms, >=2 members with samples) is marked "
                     "SUSPECT: still served direct, but hedge-eligible; "
                     "it recovers at half the ratio (hysteresis)"))
        reg(Var("hedge_policy", "off", "str",
                help="hedged reads on the Python member-pool path: 'off' "
                     "never hedges, 'fixed' re-issues a chunk still in "
                     "flight after hedge_ms on the mirror member (or the "
                     "buffered path), 'p99' derives the latch from the "
                     "member's own p99 with hedge_ms as the floor; first "
                     "completion wins, the loser is discarded",
                validate=_check_hedge_policy))
        reg(Var("hedge_ms", 20.0, "float", minval=0.0, maxval=60000.0,
                help="hedge latch for hedge_policy=fixed, and the latch "
                     "floor for hedge_policy=p99"))
        reg(Var("mirror", "none", "str",
                help="default stripe mirror map for striped sources: "
                     "'paired' treats member 2k+1 as a byte-identical "
                     "replica of member 2k (RAID-10 style) so a failed "
                     "member's extents are served from its mirror at "
                     "direct speed; 'none' stripes every member (RAID-0)",
                validate=_check_mirror))
        reg(Var("canary_interval_s", 1.0, "float", minval=0.0,
                help="period of the background canary prober: FAILED "
                     "members get a small direct read to detect recovery "
                     "(-> REJOINING), REJOINING members accumulate warmup "
                     "successes toward HEALTHY (0 = no canaries)"))
        reg(Var("rejoin_successes", 8, "int", minval=1, maxval=1 << 20,
                help="consecutive direct-read/canary successes a "
                     "REJOINING member needs before it is HEALTHY again"))
        reg(Var("rejoin_tokens_s", 16.0, "float", minval=0.0,
                help="token-bucket refill rate (direct reads per second) "
                     "allowed onto a REJOINING member during warmup; "
                     "requests past the bucket ride the mirror/buffered "
                     "path (0 = no throttle: rejoin at full rate).  The "
                     "dirty-extent resync replay draws from the same "
                     "bucket, so it doubles as the resync budget"))
        reg(Var("write_verify", False, "bool",
                help="read each retired aligned write leg back at wait "
                     "time and compare crc32c against the submitted "
                     "bytes; a mismatch (torn or misdirected write) "
                     "latches EBADMSG.  Costs one extra read per write "
                     "leg; legs journaled for resync are skipped"))
        reg(Var("join_build_host_max", 256 << 20, "size", minval=1 << 12,
                help="largest on-disk build-side table loaded whole "
                     "(one projection scan) when partitioning a join "
                     "build over the mesh; above it the build streams "
                     "in partition-sized Grace passes so host RAM stays "
                     "bounded to one partition + a scan batch "
                     "(pgsql/nvme_strom.c:1186-1260 discipline)"))
        reg(Var("join_broadcast_max", 64 << 20, "size", minval=1 << 10,
                help="largest build side (keys+values bytes) the join "
                     "replicates to every device; above it the planner "
                     "switches to the partitioned hash join (hash-"
                     "repartition both sides, local sorted-probe per "
                     "partition) instead of OOMing the broadcast"))
        reg(Var("pin_memory", False, "bool",
                help="mlock/hugepage-back staging buffers; right for bare-metal "
                     "PCIe DMA; off by default (not measured on chip)"))
        reg(Var("require_nvme_backing", False, "bool",
                help="strict eligibility: CHECK_FILE reports UNSUPPORTED "
                     "unless the file sits on raw NVMe or md-RAID0-of-NVMe "
                     "(the reference's hard requirement, kmod/nvme_strom.c:"
                     "229-438); off by default because the engine can drive "
                     "any O_DIRECT file, at uncharacterized speed"))
        # direct-path saturation knobs (PR 4): coalescing + pipelining
        reg(Var("coalesce_limit", 8 << 20, "size", minval=0, maxval=256 << 20,
                help="upper bound on a COALESCED direct read: file- and "
                     "dest-contiguous extents within one member merge "
                     "beyond dma_max_size up to this many bytes before "
                     "submission (the reference's request-merge window, "
                     "kmod/nvme_strom.c:1473-1505).  0 disables "
                     "coalescing; must be >= dma_max_size when set",
                validate=_check_coalesce_limit))
        reg(Var("submit_window", 16, "int", minval=1, maxval=256,
                help="chunks planned+submitted per submission slice of a "
                     "multi-chunk read: the engine slices the chunk list "
                     "into windows and pushes the next window while the "
                     "previous is in flight, so queue occupancy does not "
                     "drain at chunk-plan boundaries.  Smaller windows "
                     "start the first I/O sooner but pay per-window "
                     "submission overhead; 16 x 1MB chunks keeps both "
                     "negligible on one disk"))
        reg(Var("chunk_adaptive", True, "bool",
                help="adapt the effective coalesced-request cap between "
                     "dma_max_size and coalesce_limit from observed "
                     "per-request service latency (AdaptiveH2DDepth "
                     "analog on the SSD side); off pins the cap at "
                     "coalesce_limit"))
        reg(Var("cache_arbitration", True, "bool",
                help="probe the page cache and route hot chunks through the write-back path "
                     "(kmod/nvme_strom.c:1639-1663 analog)"))
        reg(Var("cache_threshold", 0.5, "float", minval=0.0, maxval=1.0,
                help="cached-page fraction above which a chunk takes the write-back path"))
        # unified extent address space (ISSUE 20): one capacity Var per
        # tier, with the pre-unification names kept as transparent
        # aliases (one stored value, two names — see MIGRATION.md)
        reg(Var("tier_ram_bytes", 0, "size", minval=0, maxval=1 << 50,
                help="capacity of the RAM tier of the unified extent "
                     "space (pinned-host-RAM extent slabs with ARC "
                     "eviction, cache.residency_cache): hits are served "
                     "by memcpy with no engine submission and no "
                     "mincore probe, misses demand-fault slabs in at "
                     "wait time after the fault ladder heals them, "
                     "HBM-tier victims demote into this tier.  0 "
                     "(default) disables the tier entirely — one branch "
                     "per task.  Read at Session construction "
                     "(tiering.extent_space.configure())"))
        reg(Var("cache_bytes", 0, "size", minval=0, maxval=1 << 50,
                alias_of="tier_ram_bytes",
                help="alias of tier_ram_bytes (pre-unification name)"))
        # LLM serving: HBM residency tier + weight streaming + KV paging
        # (ISSUE 15)
        reg(Var("tier_hbm_bytes", 0, "size", minval=0, maxval=1 << 50,
                help="capacity of the HBM tier of the unified extent "
                     "space (serving.hbm_tier): extents the RAM tier "
                     "touches twice migrate up into device-resident "
                     "buffers (exclusive under tier_unified — the RAM "
                     "copy is surrendered) and are served with no host "
                     "memcpy at all; eviction demotes the bytes back "
                     "into the RAM tier.  0 (default) disables the "
                     "tier entirely — one branch per task.  Read at "
                     "Session construction "
                     "(tiering.extent_space.configure())"))
        reg(Var("hbm_cache_bytes", 0, "size", minval=0, maxval=1 << 50,
                alias_of="tier_hbm_bytes",
                help="alias of tier_hbm_bytes (pre-unification name)"))
        reg(Var("tier_kv_block_bytes", 64 << 10, "size", minval=4 << 10,
                maxval=16 << 20,
                help="KV-cache page size for serving.kvcache block "
                     "pools: the unit of HBM pinning, RAM slotting and "
                     "SSD spill I/O (power of two; it is the pool's "
                     "chunk grid on the spill source)",
                validate=_check_pow2))
        reg(Var("kv_block_bytes", 64 << 10, "size", minval=4 << 10,
                maxval=16 << 20, alias_of="tier_kv_block_bytes",
                help="alias of tier_kv_block_bytes (pre-unification "
                     "name)"))
        reg(Var("tier_unified", True, "bool",
                help="one placement/migration engine across HBM → "
                     "pinned RAM → SSD (tiering.extent_space): second-"
                     "touch promotion migrates extents up EXCLUSIVELY "
                     "(the RAM copy is surrendered, so the tiers pool "
                     "capacity), HBM victims demote down into RAM.  "
                     "false reverts to three isolated tiers — no "
                     "promotion, evictions drop — the A/B baseline "
                     "bench.py --tiering measures against"))
        # resident-data integrity domain (ISSUE 16): checksummed tiers,
        # background scrub, pressure-driven degradation
        reg(Var("integrity", "off", "str",
                help="resident-data checksumming across the residency "
                     "hierarchy (host ARC slabs, HBM extents, KV blocks "
                     "incl. SSD spill): 'off' stores no checksums — one "
                     "branch per fill; 'transitions' stores crc32c at "
                     "fill time and re-verifies on every tier transition "
                     "(promote, demote, page-in, page-out); 'always' "
                     "additionally verifies on every lease-served read.  "
                     "A mismatch marks the entry stale under its lease "
                     "rules and the reader falls back to SSD (fail-open, "
                     "never EBADMSG from a cached copy).  Read at "
                     "Session construction (integrity.domain.configure())",
                validate=_check_integrity))
        reg(Var("scrub_bytes_per_sec", 0, "size", minval=0,
                help="background scrubber rate limit: a session thread "
                     "walks resident extents of all tiers verifying "
                     "stored crc32c at most this many bytes per second; "
                     "mismatches are healed by re-reading through the "
                     "fault ladder (host/HBM) or the mirror leg (KV "
                     "spill) and debit the stripe member's health "
                     "machine when attributable.  0 (default) disables "
                     "the scrubber; requires integrity != off.  Re-read "
                     "each scrub tick"))
        reg(Var("memlock_budget", 0, "size", minval=0,
                help="upper bound on bytes the residency cache may pin "
                     "with mlock(2): fills beyond the budget are refused "
                     "(pass-through to SSD, nr_pressure_passthrough) and "
                     "shrinking it mid-run sheds pinned slabs "
                     "(nr_pressure_shed) — readers never see ENOMEM.  "
                     "0 (default) = unlimited (bounded only by "
                     "RLIMIT_MEMLOCK, whose failures run the slab "
                     "unpinned and count nr_cache_mlock_fail).  Read at "
                     "residency_cache.configure()"))
        reg(Var("weight_stream_depth", 2, "int", minval=1, maxval=16,
                help="layers of a streamed checkpoint in flight at "
                     "once during serving.weights cold-start: layer "
                     "N+1's SSD reads land in its own LandingBuffer "
                     "while layer N's buffers are adopted as device "
                     "arrays (double-buffered default)"))
        # multi-host scale-out (ISSUE 17): sharded SSD loading + on-fabric
        # shard movement
        reg(Var("shard_hosts", 0, "int", minval=0, maxval=4096,
                help="virtual/physical host count the sharded loading "
                     "paths plan ownership for: each host's engine "
                     "session reads only the extent shards its local "
                     "NVMe set holds (member % shard_hosts, "
                     "stripe.host_of) before the on-fabric "
                     "redistribution.  0 (default) = single-host "
                     "planning unless a call site passes hosts "
                     "explicitly"))
        reg(Var("ici_permute", "auto", "str",
                validate=_check_ici_permute,
                help="transport for the device-to-device ring permute "
                     "that redistributes shards after a multi-host "
                     "load: 'pallas' = semaphore-paired async remote "
                     "DMA (pltpu.make_async_remote_copy) on HBM-resident "
                     "blocks, 'xla' = jax.lax.ppermute (the only "
                     "transport off-TPU, and the byte oracle for the "
                     "pallas lane), 'auto' = pallas iff the backend is "
                     "TPU"))
        reg(Var("kv_migrate", True, "bool",
                help="allow cross-host KV-block migration: a hot host "
                     "sheds whole sequence chains to a cold peer pool "
                     "over the remote-copy lane (KvBlockPool.migrate/"
                     "shed_to_peer); off refuses with EOPNOTSUPP so a "
                     "fleet can pin sequences to their home host"))
        # flight recorder + end-to-end task tracing (PR 7)
        reg(Var("trace_policy", "off", "str",
                help="per-task span tracing into the flight recorder: "
                     "'off' costs one branch per event site and records "
                     "nothing, 'sampled' traces 1-in-N tasks (N from "
                     "trace_sample_rate; the production setting — "
                     "overhead gated <=3% by `make trace-gate`), 'all' "
                     "traces every task (debugging/chaos).  Read at "
                     "Session construction (trace.recorder.configure())",
                validate=_check_trace_policy))
        reg(Var("trace_sample_rate", 0.01, "float", minval=0.0, maxval=1.0,
                help="fraction of tasks traced under trace_policy="
                     "sampled (0.01 = every 100th task, deterministic "
                     "1-in-round(1/rate) selection so runs reproduce)"))
        reg(Var("trace_ring_events", 8192, "int", minval=256, maxval=1 << 20,
                help="flight-recorder capacity per thread (bounded ring; "
                     "oldest events overwrite, the dump reports the "
                     "overwrite count)"))
        # shared serving daemon + per-tenant QoS (ISSUE 12)
        reg(Var("daemon_socket", "", "str",
                help="stromd Unix-socket path; empty = the per-uid default "
                     "under the temp dir (protocol.default_socket_path)"))
        reg(Var("daemon_max_sessions", 64, "int", minval=0,
                help="max concurrently attached client sessions "
                     "(0 = unlimited); further attaches get EAGAIN"))
        reg(Var("daemon_dispatch", 2, "int", minval=0, maxval=64,
                help="stromd dispatcher threads draining the QoS queue "
                     "into the engine (0 = none until "
                     "start_dispatchers(), the deterministic-test idiom)"))
        reg(Var("daemon_quota_tasks", 0, "int", minval=0,
                help="per-tenant in-flight task quota (0 = unlimited); "
                     "submits over quota are rejected with EAGAIN "
                     "backpressure, never queued unboundedly"))
        reg(Var("daemon_quota_bytes", 0, "size", minval=0,
                help="per-tenant in-flight byte quota (0 = unlimited); "
                     "the memlock-budget knob — see deploy checklist 17"))
        reg(Var("qos_quantum", 256 << 10, "size", minval=4 << 10,
                help="deficit-round-robin quantum: bytes of deficit one "
                     "round earns a weight-1.0 tenant; fairness slack is "
                     "within one quantum per tenant"))
        reg(Var("qos_default_class", "normal", "str",
                help="QoS class for tenants that do not request one at "
                     "attach: 'latency' > 'normal' > 'bulk' (strict "
                     "priority between classes)",
                validate=_check_qos_class))
        reg(Var("qos_default_weight", 1.0, "float", minval=0.001,
                help="DRR weight for tenants that do not request one "
                     "(bytes delivered scale ~linearly with weight "
                     "within a class)"))
        reg(Var("qos_rate", 0, "size", minval=0,
                help="default per-tenant token-bucket rate in bytes/s "
                     "(0 = unshaped); a gated tenant yields its slot "
                     "instead of idling the lane"))
        reg(Var("qos_burst", 8 << 20, "size", minval=64 << 10,
                help="token-bucket burst capacity in bytes: how far a "
                     "shaped tenant may exceed its rate transiently"))
        # compute pushdown: packed columnar extents decoded on-chip (ISSUE 14)
        reg(Var("pushdown", "auto", "str",
                help="packed-extent scans for pushdown-eligible queries: "
                     "'auto' takes the packed representation when the "
                     "per-column cost decision says the denser wire "
                     "format wins (observed codec ratio vs the live h2d "
                     "estimate), 'on' always scans a fresh .cpk sidecar "
                     "when one exists, 'off' never does",
                validate=_check_pushdown))
        reg(Var("pushdown_codecs", "bitpack,dict,rle", "str",
                help="codecs the packed-extent encoder may choose from "
                     "(comma list of bitpack|dict|rle; raw is always "
                     "available).  Narrowing this forces a representation "
                     "— e.g. 'rle' alone for run-length-only tables",
                validate=_check_pushdown_codecs))
        reg(Var("pushdown_chip_ratio", 1.15, "float", minval=1.0,
                help="chip-decode threshold: minimum observed codec ratio "
                     "(logical/packed bytes) for on-chip expansion to pay "
                     "for its decode dispatch; below it the column "
                     "expands on the host (or ships raw when the whole "
                     "scan compresses worse than this)"))
        reg(Var("pushdown_h2d_gbps", 0.0, "float", minval=0.0,
                help="override the planner's h2d link estimate in GB/s "
                     "(0 = auto: live H2D rate meter, else this device "
                     "kind's figure in nvme_strom_tpu/device_figures.py; "
                     "an unknown kind is an error unless this is set)"))
        reg(Var("pushdown_ssd_gbps", 0.0, "float", minval=0.0,
                help="override the planner's SSD read estimate in GB/s "
                     "(0 = auto: the live rate of this process's direct "
                     "reads, else unknown, which plans as h2d-bound); "
                     "together with pushdown_h2d_gbps this decides "
                     "host-vs-chip expansion, so tests can force either "
                     "decision deterministically"))
        # self-driving data path (ISSUE 18): online controller + readahead
        reg(Var("autotune", False, "bool",
                help="per-session online controller: each epoch it samples "
                     "the per-member latency histograms and occupancy "
                     "deltas and hill-climbs the effective submit window, "
                     "per-member chunk cap and hedge latch (plus lane "
                     "count at engine-rebuild boundaries) inside each "
                     "var's declared min/max bounds, stepping back on p99 "
                     "regression and freezing while the health machine "
                     "has a member off HEALTHY.  off = the static knobs "
                     "and the PR 4/5 adaptive sizer behave bit-for-bit "
                     "as before, at one predicted branch per read"))
        reg(Var("autotune_interval_ms", 250.0, "float", minval=10.0,
                maxval=60000.0,
                help="controller epoch length: how often the autotune "
                     "loop samples sensor deltas and takes one "
                     "hill-climb step (also the readahead predictor's "
                     "issue cadence)"))
        reg(Var("readahead", False, "bool",
                help="trace-driven predictive readahead: a per-source "
                     "predictor watches recent submit spans (stride and "
                     "extent-successor detection) and issues bounded "
                     "prefetch fills into the residency tier through the "
                     "normal fault ladder.  Requires cache_bytes > 0; "
                     "speculative fills are provenance-tagged so ARC "
                     "ghost lists never train on speculation"))
        reg(Var("readahead_budget_mb_s", 64.0, "float", minval=0.0,
                maxval=65536.0,
                help="token-bucket budget for prefetch fills in MB/s so "
                     "readahead can never starve demand reads; a predicted "
                     "extent whose bytes exceed the bucket is skipped "
                     "(counted nr_readahead_skip), never queued (0 = "
                     "predict but issue nothing)"))

    # -- layered loading ---------------------------------------------------
    def _load_file(self) -> None:
        path = os.environ.get("STROM_TPU_CONF", "strom_tpu.conf")
        if not os.path.isfile(path):
            return
        with open(path) as f:
            for lineno, line in enumerate(f, 1):
                line = line.split("#", 1)[0].strip()
                if not line:
                    continue
                if "=" not in line:
                    raise ConfigError(f"{path}:{lineno}: expected key = value")
                key, _, raw = line.partition("=")
                self.set(key.strip(), raw.strip())

    def _load_env(self) -> None:
        for name in list(self._vars):
            env = os.environ.get("STROM_TPU_" + name.upper())
            if env is not None:
                self.set(name, env)

    # -- access ------------------------------------------------------------
    def get(self, name: str) -> Any:
        with self._lock:
            if name not in self._vars:
                raise ConfigError(f"unknown config var {name}")
            alias = self._vars[name].alias_of
            return self._values[alias or name]

    def set(self, name: str, raw: Any) -> None:
        with self._lock:
            if name not in self._vars:
                raise ConfigError(f"unknown config var {name}")
            var = self._vars[name]
            if var.alias_of is not None:
                name = var.alias_of  # one stored value, two names
                var = self._vars[name]
            val = var.parse(raw)
            old = self._values[name]
            self._values[name] = val
            try:
                # cross-variable invariants can be broken by *either* side
                # changing, so every validator re-runs on any set
                for v in self._vars.values():
                    if v.validate is not None and v.alias_of is None:
                        v.validate(self._values[v.name], self)
            except ConfigError:
                self._values[name] = old
                raise

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return dict(self._values)

    def restore(self, snapshot: Dict[str, Any]) -> None:
        """Atomically restore a snapshot().

        Per-key set() can fail spuriously when cross-variable invariants
        (chunk/buffer multiples) are violated mid-restore by key order;
        this applies the whole snapshot, then validates once."""
        with self._lock:
            old = dict(self._values)
            self._values.update({k: v for k, v in snapshot.items()
                                 if k in self._vars
                                 and self._vars[k].alias_of is None})
            try:
                for v in self._vars.values():
                    if v.validate is not None and v.alias_of is None:
                        v.validate(self._values[v.name], self)
            except ConfigError:
                self._values = old
                raise

    def describe(self) -> Dict[str, Var]:
        return dict(self._vars)


#: process-global config instance (import-time singleton, like GUCs)
config = Config()

"""stromlint tests: every rule family fires on a bad fixture and stays
quiet on the corrected one; inline suppression and the baseline ratchet
behave; the real tree's ABI bindings pass against the real header and
fail against a perturbed one; and the lock-discipline fixes this PR made
(exporter double-spawn, concurrent Session.close) hold under threads.

Fixtures are tiny in-memory Projects — stromlint discovers its anchors
by content (STAT_FIELDS, lib.nstpu_*, EVENT_SCHEMA, Var(...)), so a
five-line SourceFile exercises the same code path as the real package.
"""

import json
import os
import re
import textwrap
import threading

import pytest

from nvme_strom_tpu.analysis import abi as abi_mod
from nvme_strom_tpu.analysis import buffers, confcheck, locks, surface
from nvme_strom_tpu.analysis.cli import main as lint_main
from nvme_strom_tpu.analysis.cli import run_rules
from nvme_strom_tpu.analysis.core import (BaselineError, Finding, Project,
                                          SourceFile, apply_baseline,
                                          format_finding, load_baseline)

pytestmark = pytest.mark.lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def proj(files, header=None, docs=None):
    srcs = [SourceFile(p, textwrap.dedent(t)) for p, t in files.items()]
    return Project("/fixture", srcs, header_text=header, doc_texts=docs)


def rules_of(findings):
    return sorted({f.rule for f in findings})


# -- locks -----------------------------------------------------------------

LOCKSET_BAD = """
    import threading

    class S:
        def __init__(self):
            self._lock = threading.Lock()
            self.items = []

        def guarded(self):
            with self._lock:
                self.items.append(1)

        def raced(self):
            self.items = []
    """


class TestLocks:
    def test_lockset_fires_on_unguarded_mutation(self):
        found = locks.run(proj({"pkg/mod.py": LOCKSET_BAD}))
        assert "locks.lockset" in rules_of(found)
        (f,) = [f for f in found if f.rule == "locks.lockset"]
        assert "S.items" in f.message and "raced" in f.message

    def test_lockset_quiet_when_guarded(self):
        good = """
            import threading

            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.items = []

                def guarded(self):
                    with self._lock:
                        self.items.append(1)

                def also_guarded(self):
                    with self._lock:
                        self.items = []
            """
        assert locks.run(proj({"pkg/mod.py": good})) == []

    def test_lockset_propagates_through_private_helpers(self):
        # helper-of-helper only ever runs under _lock: no finding
        src = """
            import threading

            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.items = []

                def top(self):
                    with self._lock:
                        self.items.append(0)
                        self._mid()

                def _mid(self):
                    self._leaf()

                def _leaf(self):
                    self.items.pop()
            """
        assert locks.run(proj({"pkg/mod.py": src})) == []

    def test_check_then_act_fires(self):
        src = """
            import threading

            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.buf = None

                def lazy_init(self):
                    if self.buf is None:
                        self.buf = object()
            """
        found = locks.run(proj({"pkg/mod.py": src}))
        assert rules_of(found) == ["locks.check-then-act"]

    def test_check_then_act_quiet_under_lock(self):
        src = """
            import threading

            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.buf = None

                def lazy_init(self):
                    with self._lock:
                        if self.buf is None:
                            self.buf = object()
            """
        assert locks.run(proj({"pkg/mod.py": src})) == []

    def test_order_cycle_fires(self):
        src = """
            import threading

            class S:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def ab(self):
                    with self._a:
                        with self._b:
                            pass

                def ba(self):
                    with self._b:
                        with self._a:
                            pass
            """
        found = locks.run(proj({"pkg/mod.py": src}))
        assert "locks.order" in rules_of(found)

    def test_swap_lock_must_be_outermost(self):
        src = """
            import threading

            class S:
                def __init__(self):
                    self._member_lock = threading.Lock()
                    self._lane_lock = threading.Lock()

                def inverted(self):
                    with self._member_lock:
                        with self._lane_lock:
                            pass
            """
        found = locks.run(proj({"pkg/mod.py": src}))
        assert "locks.swap-order" in rules_of(found)
        good = """
            import threading

            class S:
                def __init__(self):
                    self._member_lock = threading.Lock()
                    self._lane_lock = threading.Lock()

                def correct(self):
                    with self._lane_lock:
                        with self._member_lock:
                            pass
            """
        assert not [f for f in locks.run(proj({"pkg/mod.py": good}))
                    if f.rule == "locks.swap-order"]


# -- buffers ---------------------------------------------------------------

class TestBuffers:
    def test_unreleased_local_mmap_fires(self):
        src = """
            import mmap

            def leak(n):
                buf = mmap.mmap(-1, n)
                buf[0:1] = b"x"
            """
        found = buffers.run(proj({"pkg/mod.py": src}))
        assert rules_of(found) == ["buffers.release"]

    def test_closed_local_mmap_quiet(self):
        src = """
            import mmap

            def ok(n):
                buf = mmap.mmap(-1, n)
                try:
                    buf[0:1] = b"x"
                finally:
                    buf.close()
            """
        assert buffers.run(proj({"pkg/mod.py": src})) == []

    def test_owner_slab_handoff_quiet(self):
        src = """
            import mmap

            def fill(n):
                buf = mmap.mmap(-1, n)
                return _Entry(buf, n)
            """
        assert buffers.run(proj({"pkg/mod.py": src})) == []

    def test_self_attr_without_release_fires(self):
        src = """
            import mmap

            class Pool:
                def __init__(self, n):
                    self.slab = mmap.mmap(-1, n)
            """
        found = buffers.run(proj({"pkg/mod.py": src}))
        assert rules_of(found) == ["buffers.release"]
        good = src + textwrap.dedent("""
                def close(self):
                    self.slab.close()
            """)
        assert buffers.run(proj({"pkg/mod.py": good})) == []

    def test_returned_raw_mmap_is_escape(self):
        src = """
            import mmap

            def grab(n):
                return mmap.mmap(-1, n)
            """
        found = buffers.run(proj({"pkg/mod.py": src}))
        assert rules_of(found) == ["buffers.escape"]

    def test_raw_slab_escape_from_cache_module(self):
        src = """
            def peek(entry):
                return entry.mm
            """
        found = buffers.run(proj({"pkg/cache.py": src}))
        assert rules_of(found) == ["buffers.escape"]
        # the same return outside cache.py is not the lease invariant
        assert buffers.run(proj({"pkg/other.py": src})) == []


# -- abi -------------------------------------------------------------------

FIXTURE_HEADER = """
#define NSTPU_API_VERSION 3
#define NSTPU_MAX_DEPTH 64

enum nstpu_ctr {
    NSTPU_CTR_SUBMITS,
    NSTPU_CTR_BYTES,
    NSTPU_CTR__MAX,
};

typedef struct nstpu_params {
    uint64_t size;
    int32_t  depth;
} nstpu_params;

int nstpu_open(const char *path, uint64_t size);
int64_t nstpu_read(int h, uint64_t off);
"""

FIXTURE_BINDINGS = """
    import ctypes

    API_VERSION = 3
    MAX_DEPTH = 64
    NATIVE_COUNTERS = ("submits", "bytes")

    class Params(ctypes.Structure):
        _fields_ = [("size", ctypes.c_uint64), ("depth", ctypes.c_int32)]

    lib.nstpu_open.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
    lib.nstpu_read.argtypes = [ctypes.c_int, ctypes.c_uint64]
    lib.nstpu_read.restype = ctypes.c_int64
    """


class TestAbi:
    def test_fixture_bindings_match_fixture_header(self):
        found = abi_mod.run(proj({"pkg/_native/__init__.py":
                                  FIXTURE_BINDINGS},
                                 header=FIXTURE_HEADER))
        assert found == []

    def test_perturbed_fixture_header_fires(self):
        drifted = (FIXTURE_HEADER
                   .replace("NSTPU_API_VERSION 3", "NSTPU_API_VERSION 4")
                   .replace("NSTPU_CTR_SUBMITS,\n    NSTPU_CTR_BYTES",
                            "NSTPU_CTR_BYTES,\n    NSTPU_CTR_SUBMITS")
                   .replace("int32_t  depth", "uint64_t depth"))
        found = abi_mod.run(proj({"pkg/_native/__init__.py":
                                  FIXTURE_BINDINGS}, header=drifted))
        msgs = " | ".join(f.message for f in found)
        assert rules_of(found) == ["abi.drift"]
        assert "API_VERSION" in msgs          # drifted #define
        assert "NATIVE_COUNTERS" in msgs      # reordered enum
        assert "depth" in msgs                # changed field type

    def test_wrong_arg_count_fires(self):
        bad = FIXTURE_BINDINGS.replace(
            "lib.nstpu_open.argtypes = [ctypes.c_char_p, ctypes.c_uint64]",
            "lib.nstpu_open.argtypes = [ctypes.c_char_p]")
        found = abi_mod.run(proj({"pkg/_native/__init__.py": bad},
                                 header=FIXTURE_HEADER))
        assert any("2 args" in f.message for f in found)

    def test_real_bindings_match_real_header(self):
        project = Project.from_root(REPO)
        assert project.header_text, "csrc/strom_tpu.h missing from the repo"
        assert abi_mod.run(project) == []

    def test_real_bindings_fail_against_perturbed_real_header(self):
        project = Project.from_root(REPO)
        perturbed = re.sub(
            r"(#define\s+NSTPU_API_VERSION\s+)(\d+)",
            lambda m: m.group(1) + str(int(m.group(2)) + 1),
            project.header_text)
        assert perturbed != project.header_text
        project.header_text = perturbed
        found = abi_mod.run(project)
        assert any(f.rule == "abi.drift" and "API_VERSION" in f.message
                   for f in found)

    def test_strom_check_abi_gate(self, capsys):
        from nvme_strom_tpu.tools.strom_check import check_abi
        assert check_abi() is True
        out = capsys.readouterr().out
        assert "native abi" in out


# -- surface ---------------------------------------------------------------

class TestSurface:
    def test_undeclared_counter_fires(self):
        src = """
            STAT_FIELDS = ("nr_reads",)

            def bump(stats):
                stats.add("nr_reads", 1)
                stats.add("nr_writes", 1)
            """
        found = surface.run(proj({"pkg/api.py": src}))
        assert rules_of(found) == ["surface.undeclared"]
        assert "nr_writes" in found[0].message

    def test_stage_names_a_declared_pair_and_its_ring_span(self):
        """stats.stage("x") bumps nr_x/clk_x; its ring= keyword emits a
        recorder span, which the schema must hold as a span."""
        src = """
            STAT_FIELDS = ("nr_plan", "clk_plan")
            EVENT_SCHEMA = {"plan": "span", "wait": "instant"}

            def go(stats):
                with stats.stage("plan", ring="plan"):
                    pass
                with stats.stage("wait", ring="wait"):
                    pass
            """
        found = surface.run(proj({"pkg/api.py": src}))
        assert rules_of(found) == ["surface.trace-kind",
                                   "surface.undeclared"]
        assert {"nr_wait", "clk_wait"} == {
            f.message.split("'")[1] for f in found
            if f.rule == "surface.undeclared"}

    def test_stat_render_generic_dump_covers_everything(self):
        files = {
            "pkg/api.py": "STAT_FIELDS = ('nr_reads', 'bytes_read')\n",
            "pkg/tools/tpu_stat.py": """
                def show(c):
                    for k in sorted(c):
                        print(k, c[k])
                """,
        }
        assert surface.run(proj(files)) == []

    def test_stat_render_missing_counter_fires(self):
        files = {
            "pkg/api.py": "STAT_FIELDS = ('nr_reads',)\n",
            "pkg/tools/tpu_stat.py": "def show(c):\n    print(c['other'])\n",
        }
        found = surface.run(proj(files))
        assert rules_of(found) == ["surface.stat-render"]

    def test_prom_render_skipped_counter_needs_labeled_series(self):
        files = {
            "pkg/api.py": "STAT_FIELDS = ('nr_reads', 'nr_skipme_x')\n",
            "pkg/trace.py": """
                def render_prometheus(c):
                    out = []
                    for k in sorted(c):
                        if "skipme" in k:
                            continue
                        out.append(k)
                    return out
                """,
        }
        found = surface.run(proj(files))
        assert rules_of(found) == ["surface.prom-render"]
        assert "nr_skipme_x" in found[0].message
        covered = dict(files)
        covered["pkg/trace.py"] = files["pkg/trace.py"].replace(
            "return out", 'out.append("nr_skipme_x")\n    return out')
        assert surface.run(proj(covered)) == []

    def test_trace_schema_missing_entry_fires(self):
        src = """
            EVENT_SCHEMA = {"plan": "span"}

            def go(rec):
                with rec.span("plan"):
                    rec.instant("mystery")
            """
        found = surface.run(proj({"pkg/trace.py": src}))
        assert rules_of(found) == ["surface.trace-schema"]
        assert "mystery" in found[0].message

    def test_trace_kind_mismatch_and_stale_and_pair(self):
        src = """
            EVENT_SCHEMA = {
                "plan": "instant",
                "ghost": "span",
                "load_begin": "span",
            }

            def go(rec):
                with rec.span("plan"):
                    pass
            """
        found = surface.run(proj({"pkg/trace.py": src}))
        assert rules_of(found) == ["surface.trace-kind",
                                   "surface.trace-pair",
                                   "surface.trace-stale"]

    def test_trace_clean_fixture(self):
        src = """
            EVENT_SCHEMA = {"plan": "span", "retry": "instant"}

            def go(rec):
                with rec.span("plan"):
                    rec.instant("retry")
            """
        assert surface.run(proj({"pkg/trace.py": src})) == []


# -- config ----------------------------------------------------------------

class TestConfig:
    def test_unread_var_fires(self):
        files = {"pkg/config.py": 'Var("dead_knob", 1)\n'}
        found = confcheck.run(proj(files, docs={"README.md": "dead_knob"}))
        assert rules_of(found) == ["config.unread"]
        files["pkg/engine.py"] = 'x = config.get("dead_knob")\n'
        assert confcheck.run(proj(files,
                                  docs={"README.md": "dead_knob"})) == []

    def test_undocumented_var_fires(self):
        files = {
            "pkg/config.py": 'Var("stealth_knob", 1)\n',
            "pkg/engine.py": 'x = config.get("stealth_knob")\n',
        }
        found = confcheck.run(proj(files, docs={"README.md": "other text"}))
        assert rules_of(found) == ["config.undocumented"]

    def test_config_bounds_fires_for_unbounded_controlled_knob(self):
        files = {
            "pkg/config.py": 'Var("tuned_knob", 4, "int", minval=1)\n',
            "pkg/autotune.py": 'x = config.get("tuned_knob")\n',
        }
        found = confcheck.run(proj(files, docs={"README.md": "tuned_knob"}))
        assert rules_of(found) == ["config.bounds"]
        assert "maxval" in found[0].message

    def test_config_bounds_clean_when_declared_or_exempt(self):
        files = {
            "pkg/config.py": (
                'Var("tuned_knob", 4, "int", minval=1, maxval=64)\n'
                'Var("gate_knob", False, "bool")\n'
                'Var("free_knob", 9, "int")\n'  # not autotune-read: exempt
            ),
            "pkg/autotune.py": ('x = config.get("tuned_knob")\n'
                                'y = config.get("gate_knob")\n'),
            "pkg/engine.py": 'z = config.get("free_knob")\n',
        }
        docs = {"README.md": "tuned_knob gate_knob free_knob"}
        assert confcheck.run(proj(files, docs=docs)) == []

    def test_errno_taxonomy(self):
        src = """
            import errno

            class ErrorClass:
                TRANSIENT = 1

            _TRANSIENT_ERRNOS = frozenset((errno.EIO, errno.ENOPE_FAKE))
            _BOGUS_ERRNOS = frozenset((errno.EIO,))
            """
        found = confcheck.run(proj({"pkg/api.py": src}))
        msgs = " | ".join(f.message for f in found)
        assert rules_of(found) == ["config.errno-taxonomy"]
        assert "ENOPE_FAKE" in msgs and "BOGUS" in msgs

    def test_errno_taxonomy_clean(self):
        src = """
            import errno

            class ErrorClass:
                TRANSIENT = 1

            _TRANSIENT_ERRNOS = frozenset((errno.EIO, errno.EAGAIN))
            """
        assert confcheck.run(proj({"pkg/api.py": src})) == []


# -- suppression + baseline ratchet ---------------------------------------

class TestSuppression:
    def _project(self, marker=""):
        src = LOCKSET_BAD.replace("self.items = []\n    ",
                                  f"self.items = []{marker}\n    ", 1)
        # marker lands on the raced() body line (the second occurrence is
        # __init__'s; replace targets the raced one below)
        src = textwrap.dedent(LOCKSET_BAD)
        lines = src.splitlines()
        idx = max(i for i, l in enumerate(lines) if "self.items = []" in l)
        lines[idx] += marker
        return Project("/fixture",
                       [SourceFile("pkg/mod.py", "\n".join(lines))])

    def test_unsuppressed_fixture_fires(self):
        assert run_rules(self._project()) != []

    def test_inline_rule_suppression(self):
        assert run_rules(
            self._project("  # stromlint: ignore[locks.lockset]")) == []

    def test_inline_family_suppression(self):
        assert run_rules(self._project("  # stromlint: ignore[locks]")) == []

    def test_bare_ignore_suppresses_all(self):
        assert run_rules(self._project("  # stromlint: ignore")) == []

    def test_other_rule_ignore_does_not_suppress(self):
        assert run_rules(
            self._project("  # stromlint: ignore[buffers.release]")) != []

    def test_standalone_comment_covers_next_line(self):
        src = textwrap.dedent(LOCKSET_BAD).splitlines()
        idx = max(i for i, l in enumerate(src) if "self.items = []" in l)
        indent = src[idx][:len(src[idx]) - len(src[idx].lstrip())]
        src.insert(idx, f"{indent}# stromlint: ignore[locks.lockset]")
        project = Project("/fixture",
                          [SourceFile("pkg/mod.py", "\n".join(src))])
        assert run_rules(project) == []


class TestBaseline:
    FINDING = Finding("pkg/mod.py", 14, "locks.lockset",
                      "S.items is guarded by _lock elsewhere but mutated "
                      "here (in raced) without it")

    def entry(self, **over):
        e = {"rule": "locks.lockset", "file": "pkg/mod.py",
             "match": "S.items", "reason": "fixture exemption"}
        e.update(over)
        return e

    def _baseline(self, tmp_path, entries):
        p = tmp_path / "stromlint.baseline"
        p.write_text(json.dumps({"entries": entries}))
        return load_baseline(str(p))

    def test_matching_entry_baselines_finding(self, tmp_path):
        b = self._baseline(tmp_path, [self.entry()])
        remaining, stale = apply_baseline([self.FINDING], b)
        assert remaining == [] and stale == []

    def test_new_finding_not_absorbed(self, tmp_path):
        b = self._baseline(tmp_path, [self.entry()])
        extra = Finding("pkg/mod.py", 30, "locks.lockset",
                        "S.other is guarded by _lock elsewhere but mutated")
        remaining, _ = apply_baseline([self.FINDING, extra], b)
        assert remaining == [extra]

    def test_stale_entry_reported(self, tmp_path):
        b = self._baseline(tmp_path, [self.entry(),
                                      self.entry(match="S.gone")])
        remaining, stale = apply_baseline([self.FINDING], b)
        assert remaining == [] and len(stale) == 1
        assert stale[0]["match"] == "S.gone"

    def test_entry_without_reason_rejected(self, tmp_path):
        with pytest.raises(BaselineError):
            self._baseline(tmp_path, [self.entry(reason="")])

    def test_missing_baseline_is_empty(self, tmp_path):
        b = load_baseline(str(tmp_path / "nope"))
        assert b.entries == []


# -- CLI / gate ------------------------------------------------------------

class TestCli:
    def test_format_is_file_line_rule_message(self):
        f = Finding("a/b.py", 7, "locks.lockset", "boom")
        assert format_finding(f) == "a/b.py:7 locks.lockset boom"

    def test_list_families(self, capsys):
        assert lint_main(["--list"]) == 0
        out = capsys.readouterr().out
        for family in ("locks", "buffers", "abi", "surface", "config"):
            assert family in out

    def test_unknown_family_is_usage_error(self):
        assert lint_main(["--rule", "nonsense"]) == 2

    def test_real_tree_is_clean(self, capsys):
        # the make lint-strom gate: the shipped tree + shipped baseline
        assert lint_main(["--root", REPO]) == 0
        err = capsys.readouterr().err
        assert "clean" in err

    def test_stale_baseline_fails_run(self, tmp_path, capsys):
        bad = tmp_path / "stale.baseline"
        bad.write_text(json.dumps({"entries": [{
            "rule": "locks.lockset", "file": "no/such.py",
            "match": "nothing", "reason": "stale on purpose"}]}))
        assert lint_main(["--root", REPO, "--baseline", str(bad)]) == 1
        assert "stale baseline entry" in capsys.readouterr().err


# -- regression tests for the lock fixes this PR made ----------------------

class TestLockFixRegressions:
    def test_start_export_spawns_exactly_one_exporter(self, tmp_path):
        # other suites' Sessions leave the GLOBAL registry's default
        # exporter alive in-process; count only the threads we add
        from nvme_strom_tpu.stats import StatRegistry

        def exporters():
            return {t for t in threading.enumerate()
                    if t.name == "strom-stat-export" and t.is_alive()}

        reg = StatRegistry()
        path = str(tmp_path / "stat.json")
        before = exporters()
        barrier = threading.Barrier(8)

        def racer():
            barrier.wait()
            reg.start_export(path, interval=10.0)

        threads = [threading.Thread(target=racer) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        ours = exporters() - before
        try:
            assert len(ours) == 1
        finally:
            reg.stop_export()
        assert not any(t.is_alive() for t in ours)
        assert os.path.exists(path)   # stop wrote the final snapshot

    def test_stop_export_idempotent_and_concurrent(self, tmp_path):
        from nvme_strom_tpu.stats import StatRegistry
        reg = StatRegistry()
        reg.start_export(str(tmp_path / "stat.json"), interval=10.0)
        errors = []

        def stopper():
            try:
                reg.stop_export()
            except Exception as e:     # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=stopper) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert errors == []
        assert getattr(reg, "_exporter", None) is None

    def test_concurrent_session_close_single_teardown(self):
        from nvme_strom_tpu.engine import Session
        sess = Session(io_backend="python")
        barrier = threading.Barrier(6)
        errors = []

        def closer():
            barrier.wait()
            try:
                sess.close()
            except Exception as e:     # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=closer) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert errors == []
        sess.close()                   # still idempotent afterwards

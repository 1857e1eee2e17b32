"""vclint rule tests (positive + negative fixtures per rule, baseline and
pragma round-trips) and REPRO_SANITIZE runtime-sanitizer tests (mutating a
copy=False ref raises with the acquiring site; unsanitized behavior stays
byte-identical)."""
import sys
import textwrap
import threading
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "tools"))

from vclint import ALL_RULES                                   # noqa: E402
from vclint.engine import load_baseline, run                   # noqa: E402
from vclint.model import build_project                         # noqa: E402
from vclint.rules_blocking import BlockingCallRule             # noqa: E402
from vclint.rules_excepts import SilentExceptRule              # noqa: E402
from vclint.rules_locks import LockedElsewhereRule, LockOrderRule  # noqa: E402
from vclint.rules_trace import SpanContextRule                 # noqa: E402
from vclint.rules_zerocopy import (ZeroCopyMutationRule,       # noqa: E402
                                   ZeroCopyRetentionRule)

from repro.core import sanitize                                # noqa: E402
from repro.core.objects import WorkUnit, deepcopy_obj, spec_equal  # noqa: E402
from repro.core.store import ObjectStore                       # noqa: E402


def check(rule_cls, source, relpath="mod.py"):
    project = build_project([(relpath, textwrap.dedent(source))])
    return rule_cls().check(project)


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------- VCL001

LOCK_CYCLE = """
    import threading

    class A:
        def __init__(self):
            self._lock = threading.Lock()
            self.b = B()

        def m1(self):
            with self._lock:
                self.b.m2()

    class B:
        def __init__(self):
            self._lock = threading.Lock()

        def m2(self):
            with self._lock:
                pass

        def m3(self, a: "A"):
            with self._lock:
                a.m1()
"""


def test_vcl001_cycle_flagged():
    findings = check(LockOrderRule, LOCK_CYCLE)
    assert any(f.detail.startswith("cycle:") for f in findings)


def test_vcl001_consistent_order_clean():
    src = LOCK_CYCLE.replace('def m3(self, a: "A"):', "def m3(self):") \
                    .replace("a.m1()", "pass")
    assert check(LockOrderRule, src) == []


def test_vcl001_forbidden_store_under_watch_lock():
    src = """
        import threading

        class ObjectStore:
            def __init__(self):
                self._lock = threading.RLock()

            def lookup(self):
                with self._lock:
                    return 1

        class _Watch:
            def __init__(self, store: ObjectStore):
                self._cv = threading.Condition()
                self.store = store

            def bad(self):
                with self._cv:
                    self.store.lookup()
    """
    findings = check(LockOrderRule, src)
    assert any(f.detail.startswith("forbidden:") for f in findings)


def test_vcl001_nonreentrant_reacquire():
    src = """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def outer(self):
                with self._lock:
                    with self._lock:
                        pass
    """
    findings = check(LockOrderRule, src)
    assert any(f.detail.startswith("reacquire:") for f in findings)
    # the same shape on an RLock is legal
    assert check(LockOrderRule, src.replace("Lock()", "RLock()")) == []


# ---------------------------------------------------------------- VCL002

BLOCKING_RECONCILE = """
    import time

    class Shard:
        def reconcile(self, item):
            self._settle()

        def _settle(self):
            time.sleep(0.5)
"""


def test_vcl002_sleep_reachable_from_reconcile():
    findings = check(BlockingCallRule, BLOCKING_RECONCILE,
                     relpath="core/syncer.py")
    assert len(findings) == 1
    assert findings[0].detail == "time.sleep"
    assert "reachable from cooperative entry Shard.reconcile" \
        in findings[0].message


def test_vcl002_sleep_reachable_through_pipeline_hook():
    # a method or function handed to a ShardedPipeline runs inside its
    # shards' reconcile, in whatever module it is defined
    src = """
        import time

        def stamp(tl):
            time.sleep(0.5)

        class Worker:
            def work(self, item):
                time.sleep(0.5)

        class Owner:
            def __init__(self):
                w = Worker()
                self.pipe = ShardedPipeline(self, sync_one=w.work,
                                            stamp_done=stamp,
                                            sync_fast=self._fast)

            def _fast(self, items):
                time.sleep(0.5)
    """
    findings = check(BlockingCallRule, src, relpath="core/other.py")
    assert sorted(f.qualname for f in findings) == [
        "Owner._fast", "Worker.work", "stamp"]


def test_vcl002_entry_modules_only():
    # same code outside the five concurrency modules: not an entry
    assert check(BlockingCallRule, BLOCKING_RECONCILE,
                 relpath="core/other.py") == []


def test_vcl002_condition_wait_through_blocking_get():
    src = """
        import threading

        class Q:
            def __init__(self):
                self._cv = threading.Condition()

            def get(self, timeout=None):
                with self._cv:
                    self._cv.wait(timeout)

        class Shard:
            def __init__(self):
                self.q = Q()

            def reconcile(self, item):
                self.q.get()
    """
    findings = check(BlockingCallRule, src, relpath="core/syncer.py")
    assert [f.detail for f in findings] == ["wait:.wait"]
    assert findings[0].qualname == "Q.get"
    assert "Condition.wait" in findings[0].message


def test_vcl002_nonblocking_poll_not_descended():
    src = """
        import threading

        class Q:
            def __init__(self):
                self._cv = threading.Condition()

            def get(self, timeout=None):
                with self._cv:
                    self._cv.wait(timeout)

        class Shard:
            def __init__(self):
                self.q = Q()

            def reconcile(self, item):
                self.q.get(timeout=0)
    """
    assert check(BlockingCallRule, src, relpath="core/syncer.py") == []


def test_vcl002_sleep_zero_exempt():
    src = BLOCKING_RECONCILE.replace("time.sleep(0.5)", "time.sleep(0)")
    assert check(BlockingCallRule, src, relpath="core/syncer.py") == []


# ---------------------------------------------------------------- VCL003

def test_vcl003_mutations_of_zero_copy_refs():
    src = """
        class Consumer:
            def bad(self, store):
                objs = store.list("WorkUnit", copy=False)
                objs[0].status.phase = "X"
                first = objs[0]
                first.status.conditions.append(1)
                head = store.peek()
                head.count += 1
    """
    findings = check(ZeroCopyMutationRule, src)
    assert [f.detail for f in findings] == [
        "assign:objs", "mutate:first.append", "augassign:head"]


def test_vcl003_copy_true_and_cleansers_clean():
    src = """
        from repro.core.objects import deepcopy_obj

        class Consumer:
            def fine(self, store):
                objs = store.list("WorkUnit")
                objs[0].status.phase = "X"
                refs = store.list("WorkUnit", copy=False)
                mine = deepcopy_obj(refs[0])
                mine.status.phase = "Y"
                snapshot = list(store.list("WorkUnit", copy=False))
                snapshot.sort(key=str)
    """
    assert check(ZeroCopyMutationRule, src) == []


# ---------------------------------------------------------------- VCL004

def test_vcl004_silent_swallow_flagged():
    src = """
        def f(x):
            try:
                return x()
            except Exception:
                pass
    """
    findings = check(SilentExceptRule, src)
    assert [f.detail for f in findings] == ["swallow:1"]


def test_vcl004_handled_excepts_clean():
    src = """
        import logging

        class C:
            def logged(self, x):
                try:
                    return x()
                except Exception:
                    logging.warning("boom")

            def counted(self, x):
                try:
                    return x()
                except Exception:
                    self.errors += 1

            def metriced(self, x):
                try:
                    return x()
                except Exception:
                    self.metrics.inc("errors")

            def reraised(self, x):
                try:
                    return x()
                except Exception:
                    raise

            def narrow(self, x):
                try:
                    return x()
                except KeyError:
                    pass
    """
    assert check(SilentExceptRule, src) == []


# ---------------------------------------------------------------- VCL005

VCL005_SRC = """
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0

        def locked_path(self):
            with self._lock:
                self.count += 1

        def bare_path(self):
            self.count = 5
"""


def test_vcl005_bare_write_flagged():
    findings = check(LockedElsewhereRule, VCL005_SRC)
    assert [f.detail for f in findings] == ["bare:count"]
    assert "C.bare_path" == findings[0].qualname


def test_vcl005_locked_helper_convention_clean():
    src = VCL005_SRC.replace("def bare_path(self):",
                             "def bare_path_locked(self):")
    assert check(LockedElsewhereRule, src) == []


# ---------------------------------------------------------------- VCL006

VCL006_SRC = """
    class Worker:
        def __init__(self, tracer):
            self.tracer = tracer

        def good(self):
            with self.tracer.start_span("step") as sp:
                sp.set_attr("k", 1)

        def good_multi(self, other):
            with self.tracer.start_span("a"), other.start_span("b"):
                pass

        def bad(self):
            sp = self.tracer.start_span("step")
            do_work()
            sp.close()

        def exempt_factories(self):
            root = self.tracer.start_pending("propagation")
            self.tracer.record("fast", 0.0, 1.0)
            self.tracer.record_from("00-x-y-01", "fast", 0.0, 1.0)
            return root
"""


def test_vcl006_unmanaged_start_span_flagged():
    findings = check(SpanContextRule, VCL006_SRC)
    assert [f.detail for f in findings] == ["span:1"]
    assert findings[0].qualname == "Worker.bad"


def test_vcl006_with_and_exempt_factories_clean():
    src = VCL006_SRC.replace(
        "            sp = self.tracer.start_span(\"step\")\n"
        "            do_work()\n"
        "            sp.close()",
        "            pass")
    assert check(SpanContextRule, src) == []


# ---------------------------------------------------------------- VCL007

def test_vcl007_retained_refs_flagged():
    src = """
        class Hooked:
            def bad(self, store, meter, audit):
                objs = store.list("WorkUnit", copy=False)
                audit.record("t", "get", "WorkUnit", obj=objs[0])
                for o in objs:
                    meter.add("t", "object_bytes", o.metadata)
                head = store.peek()
                audit.record_from(head.status)
    """
    findings = check(ZeroCopyRetentionRule, src)
    assert [f.detail for f in findings] == [
        "retain:record:objs", "retain:add:o...metadata",
        "retain:record_from:head...status"]
    assert all("retain" in f.message or "hook" in f.message
               for f in findings)


def test_vcl007_scalars_and_copies_clean():
    src = """
        from repro.core import deepcopy_obj, obj_nbytes

        class Hooked:
            def fine(self, store, meter, audit, seen):
                objs = store.list("WorkUnit", copy=False)
                # extracted scalars: no live ref crosses the hook boundary
                audit.record("t", "get", "WorkUnit",
                             name=objs[0].metadata.name)
                meter.add("t", "object_bytes", float(obj_nbytes(objs[0])))
                mine = deepcopy_obj(objs[0])
                audit.record("t", "get", "WorkUnit", obj=mine)
                # set.add on a non-meter receiver is not a sink
                seen.add(objs[0])
                # copy=True reads are never tainted
                safe = store.list("WorkUnit")
                audit.record("t", "list", "WorkUnit", obj=safe[0])
    """
    assert check(ZeroCopyRetentionRule, src) == []


# ------------------------------------------------- baseline + pragma engine

def _write_mod(tmp_path, source):
    (tmp_path / "mod.py").write_text(textwrap.dedent(source))


def test_baseline_round_trip(tmp_path, monkeypatch):
    _write_mod(tmp_path, """
        def f(x):
            try:
                return x()
            except Exception:
                pass
    """)
    monkeypatch.chdir(tmp_path)
    lines = []
    rules = [SilentExceptRule()]
    assert run(["mod.py"], rules, emit=lines.append) == 1
    fp = next(l for l in lines if "fingerprint:" in l).split()[-1]

    baseline = tmp_path / "baseline.txt"
    baseline.write_text(f"{fp}  # reviewed: fallback is the handling\n")
    assert load_baseline(str(baseline)) == {
        fp: "reviewed: fallback is the handling"}
    lines.clear()
    assert run(["mod.py"], rules, baseline_path=str(baseline),
               emit=lines.append) == 0
    assert any("1 suppressed" in l for l in lines)

    # a fixed finding turns the entry stale (warned, not fatal)
    _write_mod(tmp_path, "def f(x):\n    return x()\n")
    lines.clear()
    assert run(["mod.py"], rules, baseline_path=str(baseline),
               emit=lines.append) == 0
    assert any("stale baseline entry" in l for l in lines)


def test_inline_pragma_suppresses(tmp_path, monkeypatch):
    _write_mod(tmp_path, """
        def f(x):
            try:
                return x()
            except Exception:  # vclint: disable=VCL004 fallback by design
                pass
    """)
    monkeypatch.chdir(tmp_path)
    assert run(["mod.py"], [SilentExceptRule()], emit=lambda s: None) == 0


def test_repo_src_is_clean(monkeypatch):
    """The shipped tree + baseline must keep `python -m vclint src` green."""
    monkeypatch.chdir(REPO)
    rc = run(["src"], [cls() for cls in ALL_RULES],
             baseline_path=str(REPO / "tools" / "vclint" / "baseline.txt"),
             emit=lambda s: None)
    assert rc == 0


# ------------------------------------------------------- runtime sanitizer

def mk_unit(name):
    u = WorkUnit()
    u.metadata.name = name
    u.metadata.namespace = "default"
    return u


@pytest.fixture
def sanitized_store(monkeypatch):
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    s = ObjectStore("sanitized")
    s.create(mk_unit("a"))
    yield s
    s.close()


def test_sanitizer_mutation_raises_with_site(sanitized_store):
    refs = sanitized_store.list("WorkUnit", copy=False)
    with pytest.raises(sanitize.ZeroCopyMutationError) as ei:
        refs[0].status.phase = "Hacked"
    msg = str(ei.value)
    assert "copy=False" in msg and "Ref acquired at" in msg
    assert "test_vclint.py" in msg     # blames the acquiring consumer
    # containers inside the objects are frozen too, deeply (the outer
    # list is a fresh per-call list in both modes, so it stays mutable)
    with pytest.raises(sanitize.ZeroCopyMutationError):
        refs[0].metadata.labels["k"] = "v"
    with pytest.raises(sanitize.ZeroCopyMutationError):
        refs[0].status.conditions.append(None)
    # the store itself stays pristine
    assert sanitized_store.get("WorkUnit", "default", "a").status.phase \
        != "Hacked"


def test_sanitizer_watch_events_frozen(sanitized_store):
    w = sanitized_store.watch("WorkUnit", copy=False)
    sanitized_store.create(mk_unit("b"))
    ev = w.next(timeout=1.0)
    with pytest.raises(sanitize.ZeroCopyMutationError):
        ev.object.status.phase = "Hacked"
    w.close()


def test_sanitizer_frozen_refs_still_read_like_the_real_thing(
        sanitized_store):
    ref = sanitized_store.list("WorkUnit", copy=False)[0]
    assert isinstance(ref, WorkUnit)
    assert type(ref).kind == "WorkUnit"
    assert ref.metadata.name == "a"
    copied = sanitized_store.get("WorkUnit", "default", "a")
    assert spec_equal(ref, copied) and ref == copied
    # deepcopy_obj thaws a frozen proxy back to the mutable real class
    thawed = deepcopy_obj(ref)
    assert type(thawed) is WorkUnit
    thawed.status.phase = "Running"    # mutable again


def test_unsanitized_zero_copy_identity(monkeypatch):
    """With the env var unset, copy=False behavior is byte-identical:
    plain classes, true store refs, no proxies anywhere."""
    monkeypatch.delenv("REPRO_SANITIZE", raising=False)
    s = ObjectStore("plain")
    s.create(mk_unit("a"))
    refs = s.list("WorkUnit", copy=False)
    assert type(refs) is list
    assert type(refs[0]) is WorkUnit
    assert refs[0] is s._objects[("WorkUnit", "default", "a")]
    w = s.watch("WorkUnit", copy=False)
    s.create(mk_unit("b"))
    ev = w.next(timeout=1.0)
    assert type(ev.object) is WorkUnit
    assert ev.object is s._objects[("WorkUnit", "default", "b")]
    w.close()
    s.close()


def test_watchdog_lock_reports_long_holds():
    wl = sanitize.WatchdogLock(threading.Lock(), "test-lock",
                               warn_seconds=0.005)
    with wl:
        time.sleep(0.02)
    assert wl.long_holds == 1
    with wl:
        pass
    assert wl.long_holds == 1          # short holds don't trip it

"""Tests for the hstream-analyze static-analysis suite (ISSUE 4).

Each pass gets: a seeded violation caught in fixture code (positive),
clean fixture code producing nothing (negative), and waiver/baseline
suppression. A final full-tree run asserts the real repository carries
zero non-baselined findings — the analyzer's acceptance bar.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

import helpers

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.analyze import (  # noqa: E402
    Finding,
    SourceFile,
    load_baseline,
    load_tree,
    run_passes,
    write_baseline,
)
from tools.analyze.passes import (  # noqa: E402
    atomicity,
    blocking,
    casdiscipline,
    dispatch,
    errcontract,
    lifecycle,
    lockorder,
    locks,
    overflow,
    purity,
    registry,
    retrace,
    shardmap,
    timeunit,
    waitholding,
)


def src(rel: str, code: str) -> SourceFile:
    return SourceFile(rel, rel, textwrap.dedent(code))


def rules_of(findings: list[Finding]) -> set[str]:
    return {f.rule for f in findings}


def run_one(mod, files) -> list[Finding]:
    """Run one pass and apply waivers like the framework does."""
    by_rel = {f.rel: f for f in files}
    out = []
    for f in mod.run(files, REPO):
        s = by_rel.get(f.path)
        if s is not None and s.waived(f.line, f.rule):
            continue
        out.append(f)
    return out


# ---- locks -----------------------------------------------------------------


LOCKED_CLASS = '''
import threading

class Box:
    def __init__(self):
        self._lock = threading.Lock()
        self._val = 0

    def bump(self):
        with self._lock:
            self._val += 1

    def reset(self):
        with self._lock:
            self._val = 0

    def peek(self):
        return self._val{waiver}
'''


def test_lock_guard_positive():
    out = run_one(locks, [src("m.py", LOCKED_CLASS.format(waiver=""))])
    assert rules_of(out) == {"lock-guard"}
    (f,) = out
    assert "_val" in f.message and "peek" in f.message


def test_lock_guard_waiver_suppresses():
    code = LOCKED_CLASS.format(waiver="  # analyze: ok lock-guard")
    assert run_one(locks, [src("m.py", code)]) == []


def test_lock_guard_negative_all_locked():
    code = LOCKED_CLASS.format(waiver="").replace(
        "    def peek(self):\n        return self._val",
        "    def peek(self):\n        with self._lock:\n"
        "            return self._val")
    assert run_one(locks, [src("m.py", code)]) == []


def test_lock_guard_locked_suffix_method_exempt():
    code = '''
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._val = 0

        def bump(self):
            with self._lock:
                self._val += 1
                self._flush_locked()

        def drain(self):
            with self._lock:
                self._val = 0

        def _flush_locked(self):
            self._val += 2  # runs under the caller's lock
    '''
    assert run_one(locks, [src("m.py", code)]) == []


def test_lock_guard_wrong_lock_flagged():
    """Holding a DIFFERENT lock of the same class is not protection:
    the access still races the real guard's writers."""
    code = '''
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._cv = threading.Condition()
            self._val = 0

        def bump(self):
            with self._lock:
                self._val += 1

        def reset(self):
            with self._lock:
                self._val = 0

        def peek(self):
            with self._cv:          # wrong lock!
                return self._val
    '''
    out = run_one(locks, [src("m.py", code)])
    assert len(out) == 1 and out[0].rule == "lock-guard"
    assert "_cv" in out[0].message and "_lock" in out[0].message


def test_lock_order_inversion_flagged():
    code = '''
    import threading

    class Two:
        def __init__(self):
            self._a_lock = threading.Lock()
            self._b_lock = threading.Lock()

        def forward(self):
            with self._a_lock:
                with self._b_lock:
                    pass

        def backward(self):
            with self._b_lock:
                with self._a_lock:
                    pass
    '''
    out = run_one(locks, [src("m.py", code)])
    assert rules_of(out) == {"lock-order"}
    assert len(out) == 2  # both sites named


# ---- lockorder: whole-program cycle (ISSUE 14) -----------------------------


# the seeded CROSS-CLASS inversion the per-class rule cannot see: the
# task calls into the supervisor under its own lock, the supervisor
# reaches back under ITS lock. Wiring types the `sup` attribute; the
# local constructor types `t`.
CROSS_CLASS_INVERSION = '''
import threading

class Task:
    def __init__(self):
        self.state_lock = threading.Lock()
        self.sup = None
        self.v = 0

    def die(self):
        with self.state_lock:
            self.sup.note_death(self){waiver_a}

    def poke(self):
        with self.state_lock:
            self.v += 1

class Supervisor:
    def __init__(self):
        self._lock = threading.Lock()
        self.tasks = []

    def note_death(self, t):
        with self._lock:
            self.tasks.append(t)

    def cancel(self):
        with self._lock:
            t = Task()
            t.poke()

def wire():
    t = Task()
    t.sup = Supervisor()
    return t
'''


def test_lockorder_cross_class_cycle_with_witness_path():
    out = run_one(lockorder,
                  [src("m.py", CROSS_CLASS_INVERSION.format(waiver_a=""))])
    assert rules_of(out) == {"lockorder-cycle"}
    assert len(out) == 2  # every edge of the ring is flagged
    msgs = " | ".join(f.message for f in out)
    # the full witness ring is printed, plus the per-edge call chain
    assert "Task.state_lock -> Supervisor._lock" in msgs \
        or "Supervisor._lock -> Task.state_lock" in msgs
    assert "self.sup.note_death" in msgs
    assert "t.poke" in msgs


def test_lockorder_waiver_on_one_edge_suppresses_whole_cycle():
    """A reviewed rationale on ANY edge breaks the ring — the sibling
    edges must not keep nagging."""
    code = CROSS_CLASS_INVERSION.format(
        waiver_a="  # analyze: ok lockorder-cycle")
    assert run_one(lockorder, [src("m.py", code)]) == []


def test_lockorder_consistent_order_clean():
    code = CROSS_CLASS_INVERSION.format(waiver_a="").replace(
        "        with self._lock:\n            t = Task()\n"
        "            t.poke()",
        "        t = Task()\n        t.poke()")
    assert run_one(lockorder, [src("m.py", code)]) == []


def test_lockorder_condition_alias_collapses_onto_lock():
    """Condition(self._lock) IS self._lock: acquiring the condition
    then the lock of another class must not split one mutex into two
    graph nodes (which would fabricate or hide cycles)."""
    code = '''
    import threading

    class A:
        def __init__(self):
            self._lock = threading.Lock()
            self._cv = threading.Condition(self._lock)
            self.b = B()

        def via_cv(self):
            with self._cv:
                self.b.touch()

        def via_lock(self):
            with self._lock:
                self.b.touch()

    class B:
        def __init__(self):
            self._lock = threading.Lock()
            self.n = 0

        def touch(self):
            with self._lock:
                self.n += 1
    '''
    f = src("m.py", code)
    edges = lockorder._collect_edges(
        [f], lockorder.conc.build_program([f]))
    assert set(edges) == {("A._lock", "B._lock")}  # ONE source node


# ---- atomicity: check-then-act (ISSUE 14) ----------------------------------


CHECK_THEN_ACT = '''
import threading

class Sup:
    def __init__(self):
        self._lock = threading.Lock()
        self._pending = {{}}

    def add(self, q):
        with self._lock:
            self._pending[q] = 1

    def drop(self, q):
        with self._lock:
            has = self._pending.get(q)
        if has:{waiver}
            with self._lock:
                self._pending.pop(q)
'''


def test_atomicity_check_then_act_flagged():
    out = run_one(atomicity,
                  [src("m.py", CHECK_THEN_ACT.format(waiver=""))])
    assert rules_of(out) == {"atomicity-check-act"}
    (f,) = out
    assert "drop" in f.message and "_pending" in f.message


def test_atomicity_waiver_suppresses():
    code = CHECK_THEN_ACT.format(waiver="  # analyze: ok atomicity-check-act")
    assert run_one(atomicity, [src("m.py", code)]) == []


def test_atomicity_recheck_idiom_clean():
    """Re-acquire + re-check before acting is the check-twice idiom."""
    code = CHECK_THEN_ACT.format(waiver="").replace(
        "            with self._lock:\n                "
        "self._pending.pop(q)",
        "            with self._lock:\n                "
        "if q in self._pending:\n                    "
        "self._pending.pop(q)")
    assert run_one(atomicity, [src("m.py", code)]) == []


def test_atomicity_snapshot_return_clean():
    """Reading under the lock and only RETURNING/reporting the value
    is the snapshot idiom — no act, no finding."""
    code = CHECK_THEN_ACT.format(waiver="").replace(
        "        if has:\n            with self._lock:\n"
        "                self._pending.pop(q)",
        "        return has")
    assert run_one(atomicity, [src("m.py", code)]) == []


def test_atomicity_single_critical_section_clean():
    """Check and act inside ONE with block: nothing outlives the
    lock."""
    code = '''
    import threading

    class Sup:
        def __init__(self):
            self._lock = threading.Lock()
            self._pending = {}

        def add(self, q):
            with self._lock:
                self._pending[q] = 1

        def drop(self, q):
            with self._lock:
                has = self._pending.get(q)
                if has:
                    self._pending.pop(q)
    '''
    assert run_one(atomicity, [src("m.py", code)]) == []


# ---- waitholding (ISSUE 14) ------------------------------------------------


JOIN_UNDER_LOCK = '''
import threading

class Box:
    def __init__(self):
        self._lock = threading.Lock()
        self._thread = threading.Thread(target=self._run)
        self._done = threading.Event()

    def _run(self):
        pass

    def stop(self):
        with self._lock:
            self._thread.join(){waiver}
'''


def test_waitholding_join_under_lock_flagged():
    out = run_one(waitholding,
                  [src("m.py", JOIN_UNDER_LOCK.format(waiver=""))])
    assert rules_of(out) == {"wait-holding"}
    (f,) = out
    assert "join()" in f.message and "Box._lock" in f.message


def test_waitholding_waiver_suppresses():
    code = JOIN_UNDER_LOCK.format(waiver="  # analyze: ok wait-holding")
    assert run_one(waitholding, [src("m.py", code)]) == []


def test_waitholding_join_outside_lock_clean():
    code = JOIN_UNDER_LOCK.format(waiver="").replace(
        "        with self._lock:\n            self._thread.join()",
        "        self._thread.join()")
    assert run_one(waitholding, [src("m.py", code)]) == []


def test_waitholding_event_wait_and_queue_put_under_lock_flagged():
    code = '''
    import queue
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._done = threading.Event()
            self._q = queue.Queue(maxsize=4)

        def bad_wait(self):
            with self._lock:
                self._done.wait()

        def bad_put(self, item):
            with self._lock:
                self._q.put(item)

        def ok_nowait(self, item):
            with self._lock:
                self._q.put_nowait(item)
    '''
    out = run_one(waitholding, [src("m.py", code)])
    assert len(out) == 2
    msgs = " | ".join(f.message for f in out)
    assert "wait()" in msgs and "put()" in msgs
    assert "ok_nowait" not in msgs


def test_waitholding_condition_idiom_exempt():
    """Waiting on the HELD condition releases it — never flagged,
    including a Condition aliased onto the held lock."""
    code = '''
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._cv = threading.Condition(self._lock)

        def wait_directly(self):
            with self._cv:
                self._cv.wait()

        def wait_via_alias(self):
            with self._lock:
                self._cv.wait()
    '''
    assert run_one(waitholding, [src("m.py", code)]) == []


def test_waitholding_appendfront_lane_shape_recognized_and_waived():
    """Regression (ISSUE 14): the real append-front lane-lock put is
    RECOGNIZED by the pass (lock families via locktrace.lock_list /
    Lock() lists + blocking put under a family member) and suppressed
    only by its reviewed waiver — if recognition regresses, the
    waiver goes dead and this test fails."""
    with open(os.path.join(REPO, "hstream_tpu", "server",
                           "appendfront.py"), encoding="utf-8") as fh:
        text = fh.read()
    real = SourceFile("appendfront.py",
                      "hstream_tpu/server/appendfront.py", text)
    raw = waitholding.run([real], REPO)  # waivers NOT applied
    assert any(f.rule == "wait-holding"
               and "AppendFront.submit" in f.message for f in raw)
    assert run_one(waitholding, [real]) == []  # waiver suppresses


# ---- blocking --------------------------------------------------------------


def test_blocking_handler_sleep_flagged():
    code = '''
    import time

    class FooServicer:
        def Append(self, request, context):
            time.sleep(1.0)
            return request

        def helper(self, request):
            time.sleep(1.0)  # lowercase: not an RPC handler
    '''
    out = run_one(blocking, [src("m.py", code)])
    assert len(out) == 1 and out[0].rule == "blocking-hot"
    assert "time.sleep" in out[0].message


def test_blocking_unbounded_get_in_worker_loop():
    code = '''
    class W:
        def _work_loop(self):
            while True:
                item = self._q.get()
                bounded = self._q.get(timeout=0.5)
                self._stop.wait(0.1)
                d = {}.get("x")  # dict.get: not a wait
    '''
    out = run_one(blocking, [src("m.py", code)])
    assert len(out) == 1
    assert "unbounded get()" in out[0].message


def test_blocking_scrape_path_file_io():
    code = '''
    import os

    def sample(ctx):
        for _p, _d, _f in os.walk("/tmp/x"):
            pass
    '''
    out = run_one(blocking,
                  [src("hstream_tpu/stats/prometheus.py", code)])
    assert len(out) == 1 and "directory walk" in out[0].message
    # same code outside the scrape path is fine
    assert run_one(blocking, [src("hstream_tpu/other.py", code)]) == []


def test_blocking_thread_run_covered_and_bounded_ok():
    code = '''
    import threading, time

    class W(threading.Thread):
        def run(self):
            time.sleep(2)

    class Quiet(threading.Thread):
        def run(self):
            self._ev.wait(0.5)
            self._t.join(1.0)
    '''
    out = run_one(blocking, [src("m.py", code)])
    assert len(out) == 1 and "W.run" in out[0].message


def test_blocking_supervisor_backoff_sleep_carved_out():
    """ISSUE 8 carve-out: a *Supervisor class's restart thread OWNS its
    latency budget — backoff time.sleep between restart attempts is
    sanctioned. Every OTHER blocking call in the supervisor is still
    flagged, and the same sleep in a non-Supervisor worker stays hot."""
    code = '''
    import time

    class QuerySupervisor:
        def _restart_loop(self):
            while True:
                time.sleep(0.5)   # backoff between attempts: OK
                self._q.get()     # unbounded wait: still flagged

    class RetryWorker:
        def _restart_loop(self):
            time.sleep(0.5)       # no Supervisor suffix: flagged
    '''
    out = run_one(blocking, [src("m.py", code)])
    msgs = sorted(f.message for f in out)
    assert len(out) == 2, msgs
    assert "time.sleep" in msgs[0]
    assert "RetryWorker._restart_loop" in msgs[0]
    assert "unbounded get()" in msgs[1]
    assert "QuerySupervisor._restart_loop" in msgs[1]


# ---- purity ----------------------------------------------------------------


def test_purity_decorated_impure_calls():
    code = '''
    import time, random
    import jax

    @jax.jit
    def step(x):
        t = time.time()
        r = random.random()
        return x + t + r

    @jax.jit
    def pure(x):
        return x * 2
    '''
    out = run_one(purity, [src("m.py", code)])
    assert rules_of(out) == {"jax-impure"}
    assert len(out) == 2
    assert all("step" in f.message for f in out)


def test_purity_jit_by_name_and_closure_mutation():
    code = '''
    import jax

    def build():
        seen = []

        def step(x):
            seen.append(x)
            return x

        return jax.jit(step)
    '''
    out = run_one(purity, [src("m.py", code)])
    assert len(out) == 1
    assert "mutates closed-over 'seen'" in out[0].message


def test_purity_shard_map_attribute_store():
    code = '''
    import jax

    class E:
        def compile(self):
            def step(s, x):
                self.calls = 1
                return s

            self.step = jax.jit(jax.shard_map(step, mesh=None))
    '''
    out = run_one(purity, [src("m.py", code)])
    assert len(out) == 1 and "self.calls" in out[0].message


def test_purity_join_probe_kernel_shapes():
    """The interval-join kernel builders' shape — closures returning a
    decorated @jax.jit kernel from a factory — must be in the purity
    pass's scope: an impure probe/evict kernel is flagged, the clean
    twin (the real lattice.join_probe_insert / join_evict shape) is
    not."""
    bad = '''
    import time
    import jax
    import jax.numpy as jnp

    def join_probe_insert(cap, bcap, match_cap, nm, no):
        @jax.jit
        def probe_insert(mine, other, batch, n, within, cutoff):
            t = time.time()  # trace-frozen wall clock
            return mine, batch + t

        return probe_insert

    def join_evict(cap, nl, nr):
        hits = []

        @jax.jit
        def evict(left, right, cutoff, delta):
            hits.append(cutoff)  # trace-time mutation
            return left, right

        return evict
    '''
    out = run_one(purity, [src("m.py", bad)])
    assert rules_of(out) == {"jax-impure"}
    assert len(out) == 2
    assert any("probe_insert" in f.message for f in out)
    assert any("evict" in f.message for f in out)

    clean = '''
    import jax
    import jax.numpy as jnp

    def join_probe_insert(cap, bcap, match_cap, nm, no):
        @jax.jit
        def probe_insert(mine, other, batch, n, within, cutoff):
            order = jnp.argsort(batch[0])
            return mine, batch[:, order]

        return probe_insert

    def join_evict(cap, nl, nr):
        @jax.jit
        def evict(left, right, cutoff, delta):
            alive = left["ts"] >= cutoff
            return left, right, jnp.sum(alive)

        return evict
    '''
    assert run_one(purity, [src("m.py", clean)]) == []


def test_purity_donated_reuse():
    code = '''
    import numpy as np
    from hstream_tpu.engine import lattice

    class E:
        def go(self, staged):
            step = lattice.compiled_encoded_step(
                self.spec, donate_words=True)
            self.state = step(self.state, staged.words)
            return np.asarray(staged.words)  # donated!
    '''
    out = run_one(purity, [src("m.py", code)])
    assert rules_of(out) == {"jax-donated-reuse"}
    (f,) = out
    assert "staged.words" in f.message


def test_purity_donated_no_reuse_clean():
    code = '''
    from hstream_tpu.engine import lattice

    class E:
        def go(self, staged):
            step = lattice.compiled_encoded_step(
                self.spec, donate_words=True)
            self.state = step(
                self.state,
                staged.words)
            return []
    '''
    assert run_one(purity, [src("m.py", code)]) == []


# ---- errcontract -----------------------------------------------------------


ERRORS_FIXTURE = '''
import grpc

class HStreamError(Exception):
    grpc_status = grpc.StatusCode.INTERNAL

class NotFoundish(HStreamError):
    grpc_status = grpc.StatusCode.NOT_FOUND

class Exhausted(HStreamError):
    grpc_status = grpc.StatusCode.RESOURCE_EXHAUSTED
'''

HANDLERS_FIXTURE = '''
import grpc

def handler(context):
    raise NotFoundish("x")

def other(context):
    raise Exhausted("y")

def explicit(context):
    context.abort(grpc.StatusCode.FAILED_PRECONDITION, "z")
'''


def _contract_files(gateway_codes: str, retryable: str,
                    non_retryable: str):
    gw = f'''
    import grpc

    _STATUS = {{{gateway_codes}}}
    '''
    rt = f'''
    import grpc

    RETRYABLE_CODES = frozenset({{{retryable}}})
    NON_RETRYABLE_CODES = frozenset({{{non_retryable}}})
    '''
    return [
        src(errcontract.ERRORS_FILE, ERRORS_FIXTURE),
        src("hstream_tpu/server/handlers.py", HANDLERS_FIXTURE),
        src(errcontract.GATEWAY_FILE, gw),
        src(errcontract.RETRY_FILE, rt),
    ]


def test_errcontract_gaps_flagged():
    files = _contract_files(
        "grpc.StatusCode.NOT_FOUND: 404",          # missing 2 mappings
        "grpc.StatusCode.RESOURCE_EXHAUSTED, "
        "grpc.StatusCode.ABORTED",                 # ABORTED never emitted
        "grpc.StatusCode.NOT_FOUND")
    out = run_one(errcontract, files)
    by_rule = {}
    for f in out:
        by_rule.setdefault(f.rule, []).append(f.message)
    # FAILED_PRECONDITION + RESOURCE_EXHAUSTED lack HTTP mappings
    assert len(by_rule["err-http"]) == 2
    # FAILED_PRECONDITION unclassified
    assert any("FAILED_PRECONDITION" in m
               for m in by_rule["err-retry-class"])
    # ABORTED retried but never emitted
    assert any("ABORTED" in m for m in by_rule["err-dead-retry"])


def test_errcontract_complete_contract_clean():
    files = _contract_files(
        "grpc.StatusCode.NOT_FOUND: 404, "
        "grpc.StatusCode.RESOURCE_EXHAUSTED: 429, "
        "grpc.StatusCode.FAILED_PRECONDITION: 400",
        "grpc.StatusCode.RESOURCE_EXHAUSTED, "
        "grpc.StatusCode.UNAVAILABLE",             # transport: exempt
        "grpc.StatusCode.NOT_FOUND, "
        "grpc.StatusCode.FAILED_PRECONDITION")
    assert run_one(errcontract, files) == []


HINTED_ERRORS_FIXTURE = '''
import grpc

class HStreamError(Exception):
    grpc_status = grpc.StatusCode.INTERNAL

class NotLeaderish(HStreamError):
    grpc_status = grpc.StatusCode.UNAVAILABLE

    def __init__(self, message="", leader_hint=None):
        super().__init__(message)
        self.leader_hint = leader_hint
'''

HINTED_HANDLERS_FIXTURE = '''
def handler(context):
    raise NotLeaderish("fenced", leader_hint="addr")
'''


def _hinted_files(retry_body: str):
    gw = '''
    import grpc

    _STATUS = {grpc.StatusCode.UNAVAILABLE: 503,
               grpc.StatusCode.INTERNAL: 500}
    '''
    return [
        src(errcontract.ERRORS_FILE, HINTED_ERRORS_FIXTURE),
        src("hstream_tpu/server/handlers.py", HINTED_HANDLERS_FIXTURE),
        src(errcontract.GATEWAY_FILE, gw),
        src(errcontract.RETRY_FILE, retry_body),
    ]


def test_errcontract_hinted_contract_clean():
    """A hint-carrying class whose status is hinted-classified AND
    bare-non-retryable passes all three hinted rules."""
    files = _hinted_files('''
    import grpc

    RETRYABLE_CODES = frozenset()
    NON_RETRYABLE_CODES = frozenset({grpc.StatusCode.UNAVAILABLE,
                                     grpc.StatusCode.INTERNAL})
    HINTED_RETRYABLE_CODES = frozenset({grpc.StatusCode.UNAVAILABLE})
    ''')
    assert run_one(errcontract, files) == []


def test_errcontract_hinted_gaps_flagged():
    """Unclassified hint status, a dead hinted code, and a hinted code
    whose bare form escaped NON_RETRYABLE each fire their rule."""
    files = _hinted_files('''
    import grpc

    RETRYABLE_CODES = frozenset()
    NON_RETRYABLE_CODES = frozenset({grpc.StatusCode.INTERNAL})
    HINTED_RETRYABLE_CODES = frozenset({grpc.StatusCode.ABORTED})
    ''')
    out = run_one(errcontract, files)
    rules = {f.rule for f in out}
    # UNAVAILABLE (the hint class's status) is not hinted-classified
    assert "err-hinted-unclassified" in rules
    # ABORTED is hinted but no hint class emits it
    assert "err-dead-hint" in rules
    # ABORTED's bare form is not in NON_RETRYABLE_CODES
    assert "err-hinted-bare" in rules
    # the hinted check scopes to RAISED hint classes only: INTERNAL
    # (the base class, never raised) must not fire it
    assert not any("INTERNAL" in f.message for f in out
                   if f.rule == "err-hinted-unclassified")


def test_errcontract_real_tree_tables_agree():
    """Table-driven check against the LIVE modules: every status the
    server can emit has an HTTP mapping and a retryability class, and
    every retried status is emitted (or transport-generated)."""
    import grpc

    from hstream_tpu.client import retry as retry_mod
    from hstream_tpu.http_gateway import _STATUS

    files = load_tree(REPO)
    by_rel = {f.rel: f for f in files}
    classes = errcontract._error_classes(
        by_rel[errcontract.ERRORS_FILE].tree)
    emitted = set(errcontract._emitted(files, classes))
    assert "RESOURCE_EXHAUSTED" in emitted  # sanity: extraction works
    assert "NOT_FOUND" in emitted
    http = {c.name for c in _STATUS}
    retryable = {c.name for c in retry_mod.RETRYABLE_CODES}
    non_retryable = {c.name for c in retry_mod.NON_RETRYABLE_CODES}
    assert emitted <= http
    assert emitted <= (retryable | non_retryable)
    assert retryable <= emitted | errcontract.TRANSPORT_CODES
    # the classification itself is coherent
    assert not (retryable & non_retryable)
    assert grpc.StatusCode.RESOURCE_EXHAUSTED in retry_mod.RETRYABLE_CODES
    # the NOT_LEADER contract (ISSUE 9): hinted codes are an overlay on
    # non-retryable — followable only WITH a hint, never blanket-retried
    hinted = {c.name for c in retry_mod.HINTED_RETRYABLE_CODES}
    assert hinted <= non_retryable
    assert grpc.StatusCode.UNAVAILABLE in retry_mod.HINTED_RETRYABLE_CODES
    assert "UNAVAILABLE" in emitted  # NotLeaderError is raised for real


# ---- lifecycle -------------------------------------------------------------


def test_lifecycle_unjoined_thread_flagged():
    code = '''
    import threading

    class Runner:
        def start(self):
            self._thread = threading.Thread(target=self._run)
            self._thread.start()

        def stop(self):
            self._stop.set()  # signalled but never joined
    '''
    out = run_one(lifecycle, [src("m.py", code)])
    assert len(out) == 1 and out[0].rule == "resource-leak"
    assert "_thread" in out[0].message


def test_lifecycle_unrelated_join_gives_no_credit():
    """os.path.join / a string sep.join in the same function must not
    count as teardown of an unreaped resource."""
    code = '''
    import os
    import threading

    class Runner:
        def start(self):
            self._pool = threading.Thread(target=self._run)

        def path_for(self, name):
            return os.path.join(self.root, name)
    '''
    out = run_one(lifecycle, [src("m.py", code)])
    assert len(out) == 1 and "_pool" in out[0].message


def test_lifecycle_joined_and_alias_shapes_clean():
    code = '''
    import threading
    from concurrent import futures

    class Runner:
        def start(self):
            self._thread = threading.Thread(target=self._run)
            self._pool = futures.ThreadPoolExecutor(2)
            self._workers = [threading.Thread(target=self._run)
                             for _ in range(2)]

        def stop(self):
            t = self._thread
            t.join(timeout=5)
            self._pool.shutdown(wait=True)
            for w in self._workers:
                w.join(timeout=5)
    '''
    assert run_one(lifecycle, [src("m.py", code)]) == []


# ---- registry --------------------------------------------------------------


def test_registry_unknown_metric_flagged():
    code = '''
    def f(stats, events):
        stats.stream_stat_add("no_such_metric_xyz", "s")
        events.append("no_such_kind_xyz", "msg")
    '''
    out = run_one(registry, [src("hstream_tpu/fixture.py", code)])
    unknown = [f for f in out if f.rule == "registry-unknown"]
    assert len(unknown) == 2
    assert any("no_such_metric_xyz" in f.message for f in unknown)
    assert any("no_such_kind_xyz" in f.message for f in unknown)


def test_registry_dead_entry_flagged():
    # a fixture-only tree references nothing: every registered metric
    # shows up as dead — proving direction 2 works
    out = run_one(registry, [src("hstream_tpu/fixture.py", "x = 1\n")])
    dead = [f for f in out if f.rule == "registry-dead"]
    assert any("append_total" in f.message for f in dead)


def test_registry_stage_names_cross_checked():
    """ISSUE 13 satellite: trace-span stage / kernel-family literals
    are checked against tracing.TRACE_STAGES / KERNEL_FAMILIES — a
    renamed stage silently orphans its histogram series and spans."""
    code = '''
    from hstream_tpu.common.tracing import kernel_family, trace_span

    def f(tracer, tr, stats, obs):
        with trace_span(tracer, "stepp"):        # typo'd stage
            pass
        with trace_span(tracer, "step"):         # declared: clean
            pass
        with kernel_family("probes", obs):       # typo'd family
            pass
        with kernel_family("probe", obs):        # declared: clean
            pass
        tr.record_span("q1", "emitt", trace_id="t", span_id="s",
                       t0_ms=0.0, dur_ms=1.0)    # typo'd span stage
        stats.observe("freshness_lag_ms", "ingress", 1.0)  # typo'd
        stats.observe("freshness_lag_ms", "ingest", 1.0)   # declared
        stats.observe("append_latency_ms", "anystream", 1.0)  # not a
        # stage-labeled histogram: stream labels are free-form
    '''
    out = run_one(registry, [src("hstream_tpu/fixture.py", code)])
    stage = [f for f in out if f.rule == "registry-stage"]
    assert len(stage) == 4, stage
    assert any("stepp" in f.message for f in stage)
    assert any("probes" in f.message for f in stage)
    assert any("emitt" in f.message for f in stage)
    assert any("ingress" in f.message for f in stage)


def test_registry_stage_clean_on_live_tree():
    """Every stage/family literal in the production tree is declared."""
    from tools.analyze import load_tree

    out = [f for f in registry.run(load_tree(REPO), REPO)
           if f.rule == "registry-stage"]
    assert out == [], out


def test_registry_family_call_sites_checked():
    """ISSUE 15 satellite: stat-family call sites are checked against
    the declared table (stats/families.STAT_FAMILIES) — the X-macro
    property, enforced: an undeclared family name is a finding, not a
    runtime KeyError on a cold path."""
    code = '''
    def f(stats):
        stats.stat_add("no_such_family_xyz", "s", 1.0)     # undeclared
        stats.stat_add("append_in_bytes", "s", 1.0)        # declared
        stats.stat_rate("deliverred_records", "sub")       # typo'd
        stats.stat_rate("delivered_records", "sub")        # declared
        stats.stat_ladder("emit_rows", "q1")               # declared
        stats.stat_sum("close_cycle", "q1")                # typo'd
    '''
    out = run_one(registry, [src("hstream_tpu/fixture.py", code)])
    fam = [f for f in out if f.rule == "registry-family"]
    assert len(fam) == 3, fam
    assert any("no_such_family_xyz" in f.message for f in fam)
    assert any("deliverred_records" in f.message for f in fam)
    assert any("close_cycle" in f.message for f in fam)
    # declared families never misreport under the legacy rule either
    assert not any("append_in_bytes" in f.message for f in out
                   if f.rule in ("registry-family", "registry-unknown"))


def test_registry_family_dead_entry_flagged():
    """Direction 2 covers the family table too: a declared family no
    call site feeds is a dead registry entry."""
    out = run_one(registry, [src("hstream_tpu/fixture.py", "x = 1\n")])
    dead = [f for f in out if f.rule == "registry-dead"]
    assert any("delivered_records" in f.message for f in dead)
    assert any("emit_rows" in f.message for f in dead)


def test_registry_family_clean_on_live_tree():
    """Every stat-family literal in the production tree names a
    declared family, and every declared family has a live call site."""
    from tools.analyze import load_tree

    out = [f for f in registry.run(load_tree(REPO), REPO)
           if f.rule == "registry-family"
           or (f.rule == "registry-dead"
               and "time_series" in f.message)]
    assert out == [], out


# ---- dispatch (ISSUE 7) ----------------------------------------------------


HOT = "hstream_tpu/engine/executor.py"  # a dispatch-sync hot-path rel


def test_dispatch_fetch_in_loop_blows_budget():
    """The canonical regression: a fetch per window inside a contract
    function — the exact shape the fused close exists to prevent."""
    code = '''
    import numpy as np
    from hstream_tpu.engine import lattice

    class Ex:
        def _compile(self):
            fns = lattice.compiled(self.spec)
            self._extract_touched = fns.extract_touched

        # contract: dispatches<=1 fetches<=1
        def drain(self):
            state, packed = self._extract_touched(self.state)
            out = []
            for w in self.windows:
                out.append(np.asarray(packed[w]))
            return out
    '''
    out = run_one(dispatch, [src("m.py", code)])
    assert rules_of(out) == {"dispatch-budget"}
    (f,) = out
    assert "loop" in f.message and "self.windows" in f.message


def test_dispatch_static_count_exceeds_budget():
    code = '''
    import numpy as np
    from hstream_tpu.engine import lattice

    class Ex:
        def _compile(self):
            fns = lattice.compiled(self.spec)
            self._extract_touched = fns.extract_touched

        # contract: dispatches<=1 fetches<=1
        def close(self):
            s1 = self._extract_touched(self.state)
            s2 = self._extract_touched(self.state)
            return np.asarray(s1), np.asarray(s2)
    '''
    out = run_one(dispatch, [src("m.py", code)])
    assert len(out) == 2  # dispatches AND fetches exceeded
    assert all(f.rule == "dispatch-budget" for f in out)
    assert any("dispatch site(s)" in f.message for f in out)
    assert any("fetch site(s)" in f.message for f in out)


def test_dispatch_shape_group_stacking_and_branches_clean():
    """The repo's real drain shape — early-return branches take the
    max, the by_shape stacking loop is the sanctioned ONE-fetch-per-
    compiled-shape idiom — fits dispatches<=1 fetches<=1."""
    code = '''
    import jax.numpy as jnp
    import numpy as np

    class Ex:
        # contract: dispatches<=0 fetches<=1
        def drain_closed(self):
            if not self._pending:
                return []
            if len(self._pending) == 1:
                return np.asarray(self._pending[0])
            by_shape = {}
            for starts, packed in self._pending:
                by_shape.setdefault(packed.shape, []).append(packed)
            out = []
            for group in by_shape.values():
                out.append(np.asarray(jnp.stack(group)))
            return out
    '''
    assert run_one(dispatch, [src("m.py", code)]) == []


def test_dispatch_append_path_is_policed():
    """ISSUE 12: the framed append path is registered hot — it is
    host-only BY CONTRACT (dispatches<=0 fetches<=0), so a device sync
    creeping into the ingress door is flagged, bare or budgeted."""
    bare = '''
    import numpy as np

    class AppendFront:
        def submit(self, logid, payloads):
            return np.asarray(self.state)
    '''
    out = run_one(dispatch,
                  [src("hstream_tpu/server/appendfront.py", bare)])
    assert len(out) == 1 and out[0].rule == "dispatch-sync"
    budgeted = bare.replace(
        "        def submit(self, logid, payloads):",
        "        # contract: dispatches<=0 fetches<=0\n"
        "        def submit(self, logid, payloads):")
    out = run_one(dispatch,
                  [src("hstream_tpu/common/colframe.py", budgeted)])
    assert len(out) == 1 and out[0].rule == "dispatch-budget"


def test_dispatch_sync_in_hot_path_flagged_and_contract_exempts():
    bare = '''
    import numpy as np

    class Ex:
        def hot(self):
            return np.asarray(self.state["count"])
    '''
    out = run_one(dispatch, [src(HOT, bare)])
    assert len(out) == 1 and out[0].rule == "dispatch-sync"
    # the same sync under a declared budget is sanctioned + checked
    annotated = bare.replace("        def hot(self):",
                             "        # contract: fetches<=1\n"
                             "        def hot(self):")
    assert run_one(dispatch, [src(HOT, annotated)]) == []
    # and outside the kernel/executor layer it is not policed
    assert run_one(dispatch, [src("hstream_tpu/server/x.py", bare)]) \
        == []


def test_dispatch_host_typed_asarray_not_a_fetch():
    code = '''
    import numpy as np

    class Ex:
        def ingest(self, ts_ms):
            return np.asarray(ts_ms, dtype=np.int64)
    '''
    assert run_one(dispatch, [src(HOT, code)]) == []


SESSION_HOT = "hstream_tpu/engine/session.py"  # ISSUE 10 hot-path rel


def test_dispatch_session_kernels_are_dispatch_sites():
    """The session kernel factories count as dispatches: a second step
    dispatch (or a per-cycle fetch loop) inside a session contract
    function blows the budget — the shape the fused session step
    exists to prevent."""
    code = '''
    import numpy as np
    from hstream_tpu.engine import lattice

    class SessionExecutor:
        # contract: dispatches<=1 fetches<=0
        def _process_device(self, packed):
            step = lattice.session_step_kernel(
                self.spec, self.schema, self.layout, 512, 4096)
            a = step(self.arena, packed)
            b = step(a, packed)      # second dispatch: budget blown
            return b
    '''
    out = run_one(dispatch, [src(SESSION_HOT, code)])
    assert len(out) == 1 and out[0].rule == "dispatch-budget"
    assert "dispatch site(s)" in out[0].message


def test_dispatch_session_extract_fetch_loop_flagged():
    """A fetch per pending close cycle inside drain_closed — the
    stacked pow2 drain exists to prevent exactly this."""
    code = '''
    import numpy as np
    from hstream_tpu.engine import lattice

    class SessionExecutor:
        # contract: dispatches<=0 fetches<=1
        def drain_closed(self):
            out = []
            for codes, packed in self._pending:
                out.append(np.asarray(packed))
            return out
    '''
    out = run_one(dispatch, [src(SESSION_HOT, code)])
    assert rules_of(out) == {"dispatch-budget"}
    assert "loop" in out[0].message


def test_dispatch_session_unannotated_sync_flagged():
    """session.py is a dispatch-sync hot-path file now: a bare device
    sync without a contract budget is a hot-path regression."""
    code = '''
    import numpy as np

    class SessionExecutor:
        def _peek_device(self):
            return np.asarray(self._dev["arena"]["code"])
    '''
    out = run_one(dispatch, [src(SESSION_HOT, code)])
    assert len(out) == 1 and out[0].rule == "dispatch-sync"


def test_dispatch_contract_syntax_error_flagged():
    code = '''
    class Ex:
        # contract: dispatch<=1
        def f(self):
            return 1
    '''
    out = run_one(dispatch, [src("m.py", code)])
    assert len(out) == 1 and out[0].rule == "dispatch-contract-syntax"


def test_dispatch_waiver_suppresses():
    code = '''
    import numpy as np

    class Ex:
        def hot(self):
            # analyze: ok dispatch-sync — test waiver
            return np.asarray(self.state["count"])
    '''
    assert run_one(dispatch, [src(HOT, code)]) == []


# ---- retrace (ISSUE 7) -----------------------------------------------------


def test_retrace_uncached_jit_flagged():
    code = '''
    import jax

    class Ex:
        def step_batch(self, batch):
            f = jax.jit(self._step)      # fresh wrapper per call!
            return f(batch)
    '''
    out = run_one(retrace, [src("m.py", code)])
    assert len(out) == 1 and out[0].rule == "retrace-uncached-jit"
    assert "step_batch" in out[0].message


def test_retrace_factory_shapes_sanctioned():
    code = '''
    import functools

    import jax

    @functools.lru_cache(maxsize=64)
    def compiled_step(cap):
        @jax.jit
        def step(state, batch):
            return state

        return step

    def build_extract(spec):
        return jax.jit(lambda s: s)

    @jax.jit
    def rebase(state, delta):
        return state
    '''
    assert run_one(retrace, [src("m.py", code)]) == []


def test_retrace_traced_branch_flagged_none_test_exempt():
    bad = '''
    import jax

    @jax.jit
    def step(x, n):
        if n > 0:
            return x + n
        return x
    '''
    out = run_one(retrace, [src("m.py", bad)])
    assert len(out) == 1 and out[0].rule == "retrace-traced-branch"
    assert "'n'" in out[0].message

    ok = '''
    import jax

    @jax.jit
    def step(x, mask=None):
        if mask is None:
            return x
        return x * mask
    '''
    assert run_one(retrace, [src("m.py", ok)]) == []


def test_retrace_float_static_arg_flagged():
    code = '''
    import jax

    def step(state, rate=0.5):
        return state * rate

    compiled = jax.jit(step, static_argnums=(1,))
    '''
    out = run_one(retrace, [src("m.py", code)])
    assert len(out) == 1 and out[0].rule == "retrace-static-arg"
    assert "rate" in out[0].message


def test_retrace_raw_len_shape_key_flagged():
    bad = '''
    from hstream_tpu.engine import lattice

    def probe(batch, dev):
        kern = lattice.join_probe_insert(
            dev["cap"], len(batch), dev["match_cap"], 2, 2)
        return kern
    '''
    out = run_one(retrace, [src("m.py", bad)])
    assert len(out) == 1 and out[0].rule == "retrace-shape-key"
    ok = bad.replace("len(batch)", "bcap")
    assert run_one(retrace, [src("m.py", ok)]) == []


def test_retrace_session_factory_raw_len_shape_key_flagged():
    """The session kernel factories key their compile cache on the
    pow2-padded batch/segment capacity; a raw len() defeats it —
    one XLA executable per distinct batch size (ISSUE 10)."""
    bad = '''
    from hstream_tpu.engine import lattice

    def step(dev, schema, batch, packed):
        kern = lattice.session_step_kernel(
            dev["spec"], schema, dev["layout"], dev["cap"], len(batch))
        return kern(dev["arena"], packed)
    '''
    out = run_one(retrace, [src("m.py", bad)])
    assert len(out) == 1 and out[0].rule == "retrace-shape-key"
    ok = bad.replace("len(batch)", "bcap")
    assert run_one(retrace, [src("m.py", ok)]) == []
    # the merge-mode factory is covered too
    bad2 = bad.replace("session_step_kernel(\n"
                       "            dev[\"spec\"], schema, "
                       "dev[\"layout\"], dev[\"cap\"], len(batch))",
                       "session_merge_kernel(\n"
                       "            dev[\"spec\"], dev[\"cap\"], "
                       "len(batch))")
    out2 = run_one(retrace, [src("m.py", bad2)])
    assert len(out2) == 1 and out2[0].rule == "retrace-shape-key"


# ---- overflow (ISSUE 7) ----------------------------------------------------


def test_overflow_arith_on_int32_cast_ts():
    """The seeded 'raw int32 ts arithmetic' violation: narrowing
    BEFORE subtracting wraps before any guard can fire."""
    code = '''
    import numpy as np

    class Ex:
        def ingest(self, ts_ms):
            rel = np.asarray(ts_ms).astype(np.int32) - self.epoch
            return rel
    '''
    out = run_one(overflow, [src("m.py", code)])
    assert rules_of(out) == {"overflow-ts-arith"}


def test_overflow_unguarded_narrow_flagged_guarded_clean():
    bad = '''
    import numpy as np

    class Ex:
        def wm(self):
            return np.int32(self.watermark_abs - self.epoch)
    '''
    out = run_one(overflow, [src("m.py", bad)])
    assert rules_of(out) == {"overflow-narrowing"}

    guarded = '''
    import numpy as np

    class Ex:
        def wm(self):
            rel = self.watermark_abs - self.epoch
            if rel >= (1 << 31):
                raise OverflowError("span")
            return np.int32(rel)
    '''
    assert run_one(overflow, [src("m.py", guarded)]) == []


def test_overflow_rebase_call_counts_as_guard():
    code = '''
    import numpy as np

    class Ex:
        def ingest(self, bts):
            self._maybe_rebase(int(bts.min()), int(bts.max()))
            return (bts - self.t0).astype(np.int32)
    '''
    assert run_one(overflow, [src("m.py", code)]) == []


def test_overflow_device_code_exempt():
    """Jitted kernels (and helpers they call) compute in the rebased
    int32 space by design — the host guards the boundary."""
    code = '''
    import jax
    import jax.numpy as jnp

    def pack_rows(count, win_start):
        return jnp.broadcast_to(jnp.asarray(win_start, jnp.int32),
                                count.shape)

    def build_extract(spec):
        @jax.jit
        def extract(state, slot):
            ts32 = state["ts"].astype(jnp.int32)
            return pack_rows(state["count"], ts32)

        return extract
    '''
    assert run_one(overflow, [src("m.py", code)]) == []


def test_overflow_non_time_names_not_matched():
    code = '''
    import numpy as np

    def shape_stats(counts):
        return counts.astype(np.int32)
    '''
    assert run_one(overflow, [src("m.py", code)]) == []


# ---- shardmap (ISSUE 7) ----------------------------------------------------


SHARD_CLEAN = '''
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

def build(mesh, data_axis="data"):
    def merged(state):
        return jax.lax.psum(state, data_axis)

    def step_local(state, batch):
        shard = jax.lax.axis_index(data_axis)
        return merged(state) + shard

    return jax.jit(jax.shard_map(step_local, mesh=mesh))
'''


def test_shardmap_clean_bodies_pass():
    assert run_one(shardmap, [src("m.py", SHARD_CLEAN)]) == []


def test_shardmap_callback_in_body_flagged():
    """The seeded callback-in-shard_map violation."""
    code = SHARD_CLEAN.replace(
        "        shard = jax.lax.axis_index(data_axis)",
        "        shard = jax.lax.axis_index(data_axis)\n"
        "        jax.debug.print(\"shard {s}\", s=shard)")
    out = run_one(shardmap, [src("m.py", code)])
    assert rules_of(out) == {"shardmap-callback"}
    assert "jax.debug.print" in out[0].message


def test_shardmap_host_fetch_in_body_flagged():
    code = SHARD_CLEAN.replace(
        "        return merged(state) + shard",
        "        import numpy as np\n"
        "        return np.asarray(merged(state)) + shard")
    out = run_one(shardmap, [src("m.py", code)])
    assert rules_of(out) == {"shardmap-callback"}
    assert "np.asarray" in out[0].message


def test_shardmap_collective_outside_body_flagged():
    code = '''
    import jax

    def merge_on_host(partials):
        return jax.lax.psum(partials, "data")
    '''
    out = run_one(shardmap, [src("m.py", code)])
    assert rules_of(out) == {"shardmap-collective"}


def test_shardmap_axis_typo_flagged():
    code = '''
    import jax
    from jax.sharding import Mesh

    def build(devices):
        mesh = Mesh(devices, ("data", "key"))

        def step_local(state):
            return jax.lax.psum(state, "dta")

        return jax.shard_map(step_local, mesh=mesh)
    '''
    out = run_one(shardmap, [src("m.py", code)])
    assert "shardmap-axis" in rules_of(out)
    (f,) = [f for f in out if f.rule == "shardmap-axis"]
    assert "'dta'" in f.message and "data" in f.message


SESSION_SHARDED = '''
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

def build(mesh, key_axis="key"):
    def step_local(arena, packed):
        shard = jax.lax.axis_index(key_axis)
        owned = (packed[0] % 8) == shard
        packed = packed.at[2].set(
            jnp.where(owned, packed[2], packed[2] & ~1))
        return arena, packed

    return jax.jit(jax.shard_map(step_local, mesh=mesh))
'''


def test_shardmap_session_ownership_mask_clean():
    """ISSUE 16 shape: the sharded session arena's ownership masking
    (axis_index inside the body, ZERO collectives) must pass clean —
    axis_index is a mesh-bound primitive, legal only under shard_map,
    and the session lattice keeps it there."""
    assert run_one(shardmap, [src("m.py", SESSION_SHARDED)]) == []


def test_shardmap_join_concat_gather_clean():
    """ISSUE 16 shape: the sharded join's ICI concat point — tiled
    all_gather of per-shard match buffers along the key axis inside
    the shard_map body — is mesh-legal and must not be flagged."""
    code = '''
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    def build(mesh, key_axis="key"):
        def probe_local(store, batch):
            shard = jax.lax.axis_index(key_axis)
            kid = batch[2] * 0 + shard
            kid = jax.lax.all_gather(kid, key_axis, tiled=True)
            return store, kid

        return jax.jit(jax.shard_map(probe_local, mesh=mesh))
    '''
    assert run_one(shardmap, [src("m.py", code)]) == []


def test_shardmap_session_gather_outside_body_flagged():
    """The inverse pin: an all_gather in a helper NEVER wrapped by
    shard_map (e.g. a session drain trying to concat host-side) is the
    unbound-axis trap the pass exists for."""
    code = '''
    import jax

    def drain_concat(parts):
        return jax.lax.all_gather(parts, "key", tiled=True)
    '''
    out = run_one(shardmap, [src("m.py", code)])
    assert rules_of(out) == {"shardmap-collective"}


def test_shardmap_session_callback_in_body_flagged():
    """A host fetch inside the session step body (per-shard sync —
    would serialize the mesh) keeps tripping shardmap-callback."""
    code = SESSION_SHARDED.replace(
        "        return arena, packed",
        "        import numpy as np\n"
        "        return arena, np.asarray(packed)")
    out = run_one(shardmap, [src("m.py", code)])
    assert rules_of(out) == {"shardmap-callback"}


# ---- analyze CLI --json ----------------------------------------------------


def test_cli_json_output(tmp_path):
    """--json emits one machine-readable array of the NEW findings."""
    mini = tmp_path / "mini"
    (mini / "hstream_tpu").mkdir(parents=True)
    (mini / "tools").mkdir()
    (mini / "hstream_tpu" / "box.py").write_text(
        textwrap.dedent(LOCKED_CLASS.format(waiver="")))
    base = str(tmp_path / "b.json")
    r = subprocess.run(
        [sys.executable, "-m", "tools.analyze", "--only", "locks",
         "--repo", str(mini), "--baseline", base, "--json"],
        capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 1
    records = json.loads(r.stdout)
    assert len(records) == 1
    rec = records[0]
    assert rec["rule"] == "lock-guard"
    assert rec["pass"] == "locks"  # owning pass per record (ISSUE 14)
    assert rec["path"] == "hstream_tpu/box.py"
    assert isinstance(rec["line"], int) and rec["line"] > 0
    assert "_val" in rec["message"]
    # a clean tree emits an empty array and exits 0
    (mini / "hstream_tpu" / "box.py").write_text("x = 1\n")
    r = subprocess.run(
        [sys.executable, "-m", "tools.analyze", "--only", "locks",
         "--repo", str(mini), "--baseline", base, "--json"],
        capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 0 and json.loads(r.stdout) == []


def test_cli_json_stable_order_and_pass_names(tmp_path):
    """--json output is a total order over (path, line, rule, message)
    and every record names its owning pass — CI annotators must not
    have to re-sort or re-derive the rule->pass mapping (ISSUE 14)."""
    from tools.analyze import all_passes, rule_passes

    owners = rule_passes()
    for name, mod in all_passes().items():
        for rid in mod.RULES:
            assert owners[rid] == name
    mini = tmp_path / "mini"
    (mini / "hstream_tpu").mkdir(parents=True)
    (mini / "tools").mkdir()
    # two findings from two passes in one file: locks + waitholding
    (mini / "hstream_tpu" / "box.py").write_text(textwrap.dedent('''
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._val = 0
            self._thread = threading.Thread(target=self.bump)

        def bump(self):
            with self._lock:
                self._val += 1

        def reset(self):
            with self._lock:
                self._val = 0

        def peek(self):
            return self._val

        def stop(self):
            with self._lock:
                self._thread.join()
    '''))
    base = str(tmp_path / "b.json")
    r = subprocess.run(
        [sys.executable, "-m", "tools.analyze", "--repo", str(mini),
         "--baseline", base, "--json"],
        capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 1
    records = json.loads(r.stdout)
    assert len(records) >= 2
    keys = [(x["path"], x["line"], x["rule"], x["message"])
            for x in records]
    assert keys == sorted(keys)
    by_rule = {x["rule"]: x["pass"] for x in records}
    assert by_rule.get("lock-guard") == "locks"
    assert by_rule.get("wait-holding") == "waitholding"


# ---- RetraceGuard: runtime recompile contract (ISSUE 7) --------------------


def test_retrace_guard_counts_first_compile():
    import jax
    import jax.numpy as jnp

    from hstream_tpu.common.tracing import RetraceGuard

    f = jax.jit(lambda x: x * 3 + 1)
    with RetraceGuard() as g:
        f(jnp.zeros(5))
    assert g.count >= 1  # fresh wrapper: at least its own compile
    with RetraceGuard() as g2:
        f(jnp.zeros(5))
    assert g2.count == 0  # cached executable: no recompile


def test_retrace_guard_zero_steady_state_fused_close(retrace_guard):
    """50 post-warmup fused-close batches compile NOTHING (the
    acceptance contract)."""
    ex, feed, warm = helpers.smoke_tumbling_config()
    for i in range(warm):
        feed(i)
    ex.block_until_ready()
    with retrace_guard():
        for i in range(warm, warm + 50):
            feed(i)
        ex.block_until_ready()


def _mesh(shape):
    """None, or the 1x8 key-sharded mesh over the 8 virtual devices
    tests/conftest.py gives the process."""
    if shape is None:
        return None
    import jax

    from hstream_tpu.parallel import make_mesh

    assert jax.device_count() >= 8, f"{jax.device_count()} devices"
    return make_mesh(n_data=1, n_key=8)


@pytest.mark.parametrize("mesh", [None, "1x8"])
def test_retrace_guard_zero_steady_state_device_session(retrace_guard,
                                                        mesh):
    """50 post-warmup device-session micro-batches (steps, close
    extracts, stacked deferred drains) compile NOTHING (ISSUE 10),
    single-chip and key-sharded over 8 devices (ISSUE 16: sharded
    activation, arena step/merge and stacked drains all compile during
    warm-up)."""
    ex, feed, warm = helpers.smoke_session_config(mesh=_mesh(mesh))
    for b in range(warm):
        feed(b)
    ex.flush_changes()
    ex.block_until_ready()
    assert ex._dev is not None, "device sessions did not activate"
    with retrace_guard():
        for b in range(warm, warm + 50):
            feed(b)
        ex.flush_changes()
        ex.block_until_ready()
    st = ex.session_stats
    assert st["step_dispatches"] == st["batches"]


@pytest.mark.parametrize("mesh", [None, "1x8"])
def test_retrace_guard_zero_steady_state_device_join(retrace_guard, mesh):
    """50 post-warmup device-join micro-batches compile NOTHING,
    single-chip and key-sharded over 8 devices (fused probe+insert and
    evict included)."""
    ex, feed, warm = helpers.smoke_join_config(mesh=_mesh(mesh))
    for b in range(warm):
        feed(b)
    ex.flush_changes()
    ex.block_until_ready()
    assert ex._dev is not None, "device join did not activate"
    with retrace_guard():
        for b in range(warm, warm + 50):
            feed(b)
        ex.flush_changes()
        ex.block_until_ready()


def test_kernel_recompiles_counter_taps_compiles():
    import jax
    import jax.numpy as jnp

    from hstream_tpu.common.tracing import install_recompile_counter
    from hstream_tpu.stats import StatsHolder

    stats = StatsHolder()
    install_recompile_counter(stats, stream="_test")
    jax.jit(lambda x: x - 7)(jnp.zeros(3))
    assert stats.stream_stat_get("kernel_recompiles", "_test") >= 1


def test_named_guard_attributes_recompiles_to_stream():
    """ISSUE 13 satellite: a compile observed while a NAMED guard is
    active counts against that stream, not the sink's default
    pseudo-stream — per-query recompile evidence used to collapse
    into `_process` unrecoverably."""
    import jax
    import jax.numpy as jnp

    from hstream_tpu.common.tracing import (
        RetraceGuard,
        install_recompile_counter,
    )
    from hstream_tpu.stats import StatsHolder

    stats = StatsHolder()
    install_recompile_counter(stats, stream="_namedtest")
    with RetraceGuard(name="q-attr-1") as g:
        jax.jit(lambda x: x * 3 + 11)(jnp.zeros(5))
    assert g.count >= 1
    named = stats.stream_stat_get("kernel_recompiles", "q-attr-1")
    assert named >= 1
    # the default sink stream saw NONE of the named-guard compiles
    assert stats.stream_stat_get("kernel_recompiles",
                                 "_namedtest") == 0
    # with no named guard active, attribution falls back to the
    # sink's stream as before
    jax.jit(lambda x: x * 5 + 13)(jnp.zeros(5))
    assert stats.stream_stat_get("kernel_recompiles",
                                 "_namedtest") >= 1
    assert stats.stream_stat_get("kernel_recompiles",
                                 "q-attr-1") == named


def test_compile_family_attribution_via_kernel_family():
    """A compile triggered inside a kernel_family scope lands in the
    factory_recompiles counter under that family."""
    import jax
    import jax.numpy as jnp

    from hstream_tpu.common.tracing import (
        install_recompile_counter,
        kernel_family,
    )
    from hstream_tpu.stats import StatsHolder

    stats = StatsHolder()
    install_recompile_counter(stats, stream="_famtest")
    seen = []
    with kernel_family("probe", lambda fam, s: seen.append((fam, s))):
        jax.jit(lambda x: x - 21)(jnp.zeros(7))
    assert stats.stream_stat_get("factory_recompiles", "probe") >= 1
    assert seen and seen[0][0] == "probe" and seen[0][1] >= 0.0


# ---- waivers / baseline / framework ----------------------------------------


def test_waiver_on_preceding_comment_line():
    code = LOCKED_CLASS.format(waiver="").replace(
        "        return self._val",
        "        # analyze: ok lock-guard\n        return self._val")
    assert run_one(locks, [src("m.py", code)]) == []


def test_waiver_bare_ok_covers_all_rules():
    code = LOCKED_CLASS.format(waiver="  # analyze: ok")
    assert run_one(locks, [src("m.py", code)]) == []


def test_baseline_roundtrip_suppresses(tmp_path):
    f = Finding("lock-guard", "m.py", 17, "unguarded read of '_val'")
    path = str(tmp_path / "baseline.json")
    write_baseline([f], path)
    base = load_baseline(path)
    assert f.key() in base
    # line drift does not un-baseline a finding
    drifted = Finding("lock-guard", "m.py", 99, f.message)
    assert drifted.key() in base
    # a different message is a NEW finding
    other = Finding("lock-guard", "m.py", 17, "unguarded read of '_x'")
    assert other.key() not in base


def test_cli_baseline_gate(tmp_path):
    """End-to-end: a seeded violation fails the CLI, gets baselined,
    then passes; a waiver also clears it."""
    mini = tmp_path / "mini"
    (mini / "hstream_tpu").mkdir(parents=True)
    (mini / "tools").mkdir()
    bad = textwrap.dedent(LOCKED_CLASS.format(waiver=""))
    (mini / "hstream_tpu" / "box.py").write_text(bad)
    base = str(tmp_path / "b.json")

    def cli(*extra):
        return subprocess.run(
            [sys.executable, "-m", "tools.analyze", "--only", "locks",
             "--repo", str(mini), "--baseline", base, *extra],
            capture_output=True, text=True, cwd=REPO)

    r = cli()
    assert r.returncode == 1 and "lock-guard" in r.stdout
    assert "rule docs" in r.stdout  # failure prints the fired docs
    r = cli("--write-baseline")
    assert r.returncode == 0
    r = cli()
    assert r.returncode == 0 and "baselined" in r.stdout
    # stats mode emits per-rule counts
    r = cli("--stats")
    assert "lock-guard" in r.stdout and r.returncode == 0


def test_write_baseline_with_only_preserves_other_passes(tmp_path):
    """`--only X --write-baseline` must not drop baseline entries owned
    by the passes that did not run."""
    from tools.analyze import BASELINE_PATH  # noqa: F401 — docs anchor

    path = str(tmp_path / "b.json")
    kept = Finding("resource-leak", "a.py", 3, "leaked thread")
    write_baseline([kept], path)
    # rewrite for the locks pass only: resource-leak entries survive
    new = Finding("lock-guard", "b.py", 9, "unguarded read of '_x'")
    write_baseline([new], path, keep_rules={"resource-leak"})
    base = load_baseline(path)
    assert kept.key() in base and new.key() in base
    # a full rewrite (no keep_rules) replaces everything
    write_baseline([new], path)
    base = load_baseline(path)
    assert kept.key() not in base and new.key() in base


# ---- casdiscipline (ISSUE 19) ---------------------------------------------


def test_cas_blind_write_on_protocol_key_flagged():
    code = '''
    def publish(store, node):
        store.meta_put("cluster/nodes/" + node, b"{}")
        store.meta_put("scheduler/query/q1", b"{}")
        store.meta_delete("vcs/flow/limits")
        store.meta_put(META_EPOCH, b"3")
    '''
    out = run_one(casdiscipline, [src("m.py", code)])
    assert rules_of(out) == {"cas-blind-meta-write"}
    assert len(out) == 4


def test_cas_blind_write_ignores_data_plane_and_dynamic_keys():
    code = '''
    def ok(store, e, key):
        store.meta_put("snapshots/q1/0", b"...")   # data plane
        store.meta_put(e.meta_key, e.meta_value)   # replication apply
        store.meta_put(key, b"x")                  # dynamic
        store.meta_cas("scheduler/query/q1", None, b"{}")  # the idiom
    '''
    assert run_one(casdiscipline, [src("m.py", code)]) == []


def test_cas_blind_write_waiver_suppresses():
    code = '''
    def stamp(store):
        # analyze: ok cas-blind-meta-write
        store.meta_put("replica/node_id", b"n1")
    '''
    assert run_one(casdiscipline, [src("m.py", code)]) == []


def test_cas_put_version_from_same_function_get_is_clean():
    code = '''
    def claim(ctx, key, value):
        for _ in range(16):
            cur = ctx.config.get(key)
            try:
                ctx.config.put(key, value,
                               base_version=None if cur is None else cur[0])
                return
            except VersionMismatch:
                continue

    def bump(ctx):
        cur = ctx.config.get("cluster/boot_epoch")
        version, raw = cur
        ctx.config.put("cluster/boot_epoch", b"2", base_version=version)
        ctx.config.delete("cluster/boot_epoch", base_version=cur[0])
    '''
    assert run_one(casdiscipline, [src("m.py", code)]) == []


def test_cas_put_foreign_version_flagged():
    code = '''
    def overwrite(ctx, key, value, cached_version):
        ctx.config.put(key, value, base_version=cached_version)

    def constant(ctx, key, value):
        ctx.config.put(key, value, base_version=3)

    def stale(ctx, key):
        ctx.config.delete(key, base_version=ctx.last_seen)
    '''
    out = run_one(casdiscipline, [src("m.py", code)])
    assert rules_of(out) == {"cas-put-foreign-version"}
    assert len(out) == 3
    assert any("cached_version" in f.message for f in out)
    assert any("constant version" in f.message for f in out)


def test_cas_epoch_nonmonotone_flagged_and_guard_clears():
    # module mentions load_epoch -> the replication epoch plane
    code = '''
    from store import load_epoch

    class F:
        def promote(self, epoch):
            self._epoch = epoch          # no guard in scope

        def accept(self, request):
            if request.epoch > self._epoch:
                self._epoch = int(request.epoch)

        def boot(self, local):
            self._epoch = load_epoch(local)

        def bump(self):
            self._epoch = self._epoch + 1
    '''
    out = run_one(casdiscipline, [src("m.py", code)])
    assert rules_of(out) == {"cas-epoch-nonmonotone"}
    (f,) = out
    assert "promote" in f.message


def test_cas_epoch_rule_skips_engine_time_epochs():
    # no load_epoch/boot_epoch/META_EPOCH in the module: `epoch` here
    # is the executor's timestamp base, not a fencing token
    code = '''
    class Executor:
        def _rebase(self, min_ts, back):
            self.epoch = min_ts - back
    '''
    assert run_one(casdiscipline, [src("m.py", code)]) == []


def test_cas_lease_raw_interval_comparison_flagged():
    code = '''
    def live(record, now_ms, interval_ms, lease_ms):
        age = now_ms - record["hb_ms"]
        if age <= 3 * interval_ms:       # re-derives the bound: BUG
            return True
        return age <= lease_ms           # the clamped lease: fine
    '''
    out = run_one(casdiscipline, [src("m.py", code)])
    assert rules_of(out) == {"cas-lease-raw"}
    assert len(out) == 1


def test_casdiscipline_live_tree_only_carries_reviewed_waivers():
    """Triage verdict, pinned: the production tree is CLEAN after
    waivers, and the waivers are LOAD-BEARING — stripping the
    follower-plane waivers in store/replica.py re-exposes exactly the
    reviewed findings (9 blind single-writer meta writes + 1
    caller-guarded epoch assignment). A stale waiver or a new
    violation both break this test."""
    files = load_tree(REPO)
    assert run_one(casdiscipline, files) == []
    replica = next(f for f in files
                   if f.rel == "hstream_tpu/store/replica.py")
    raw = [f for f in casdiscipline.run(files, REPO)
           if f.path == replica.rel]
    blind = [f for f in raw if f.rule == "cas-blind-meta-write"]
    epoch = [f for f in raw if f.rule == "cas-epoch-nonmonotone"]
    assert len(blind) == 9, blind
    assert len(epoch) == 1, epoch
    for f in raw:  # every one is suppressed by a reviewed waiver
        assert replica.waived(f.line, f.rule), f


# ---- timeunit (ISSUE 19) ---------------------------------------------------


def test_timeunit_mix_flagged():
    code = '''
    import time

    def deadline(now_ms, timeout_s):
        return now_ms + timeout_s            # 1000x off

    def age(start_ms):
        return time.time() - start_ms        # seconds minus ms

    def expired(hb_ms, lease_timeout_s):
        if hb_ms > time.monotonic():
            return True
        return hb_ms - lease_timeout_s > 0
    '''
    out = run_one(timeunit, [src("m.py", code)])
    assert rules_of(out) == {"timeunit-mix"}
    assert len(out) == 4


def test_timeunit_conversion_factor_clears():
    code = '''
    import time

    def ok(now_ms, timeout_s, dur_ms):
        a = now_ms + timeout_s * 1000
        b = time.time() * 1e3 - dur_ms
        c = now_ms * 0.001 - timeout_s
        d = int(time.time() * 1000) - dur_ms
        return a, b, c, d
    '''
    assert run_one(timeunit, [src("m.py", code)]) == []


def test_timeunit_ignores_non_time_identifiers():
    code = '''
    def ok(stats, args, items, vals):
        total = stats + args                 # trailing s != seconds
        if items > vals:
            return total
        ms = 5
        return ms + 3                        # same-unit arithmetic
    '''
    assert run_one(timeunit, [src("m.py", code)]) == []


def test_timeunit_waiver_suppresses():
    code = '''
    def f(now_ms, timeout_s):
        return now_ms + timeout_s  # analyze: ok timeunit-mix
    '''
    assert run_one(timeunit, [src("m.py", code)]) == []


def test_timeunit_live_tree_clean():
    assert run_one(timeunit, load_tree(REPO)) == []


# ---- waiver-dead (stale-waiver audit, ISSUE 19) ----------------------------


def test_dead_waiver_flagged_live_waiver_not():
    code = '''
    def f(now_ms, timeout_s, x_ms, y_ms):
        a = now_ms + timeout_s  # analyze: ok timeunit-mix
        b = x_ms + y_ms         # analyze: ok timeunit-mix
        return a + b
    '''
    out, _rules = run_passes([src("m.py", code)], only=["timeunit"])
    assert rules_of(out) == {"waiver-dead"}
    (f,) = out
    assert f.line == 4  # the same-unit line: its waiver excuses nothing
    assert "timeunit-mix" in f.message


def test_dead_waiver_scoped_to_selected_passes():
    code = '''
    def f():
        return 1  # analyze: ok lock-guard
    '''
    # lock-guard's pass did not run: the waiver is not auditable here
    out, _ = run_passes([src("m.py", code)], only=["timeunit"])
    assert out == []
    # ... and IS dead once its pass runs
    out, _ = run_passes([src("m.py", code)], only=["locks"])
    assert rules_of(out) == {"waiver-dead"}


def test_bare_waiver_audited_only_on_full_runs():
    from tools.analyze import _dead_waivers

    files = [src("m.py", "x = 1  # analyze: ok\n")]
    assert _dead_waivers(files, {"timeunit-mix"}, {},
                         all_selected=False) == []
    out = _dead_waivers(files, {"timeunit-mix"}, {}, all_selected=True)
    assert [f.rule for f in out] == ["waiver-dead"]


def test_comment_line_waiver_credits_next_line_suppression():
    code = '''
    def f(now_ms, timeout_s):
        # analyze: ok timeunit-mix
        return now_ms + timeout_s
    '''
    out, _ = run_passes([src("m.py", code)], only=["timeunit"])
    assert out == []


def test_waiver_dead_live_tree_clean():
    """Every waiver in the production tree still suppresses a finding
    of every rule it names — the 27 reviewed exceptions are all
    load-bearing."""
    out, _ = run_passes(load_tree(REPO))
    assert [f for f in out if f.rule == "waiver-dead"] == []


def test_full_tree_runs_clean():
    """Acceptance bar: the repository carries ZERO non-baselined
    findings, and the baseline itself is EMPTY (every true positive
    was fixed; deliberate exceptions carry inline waivers)."""
    r = subprocess.run(
        [sys.executable, "-m", "tools.analyze"],
        capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    assert json.loads(open(os.path.join(
        REPO, "tools", "analyze", "baseline.json")).read()) == []

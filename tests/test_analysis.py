"""Tests for maggy_tpu.analysis: the four static checkers (each proven
live against a firing fixture and quiet on a clean one), the runtime
lock-order witness, the tier-1 package-must-be-clean enforcement, and
regression tests for the two real bugs the checkers surfaced in this
repo (the Reporter._async_kick rollover race and the dead FINAL
``span`` payload key)."""

from __future__ import annotations

import os
import threading
import time

import numpy as np
import pytest

from maggy_tpu.analysis import analyze_paths, run_analysis
from maggy_tpu.analysis import witness as witness_mod
from maggy_tpu.analysis.witness import Witness

pytestmark = pytest.mark.analysis


def _write(tmp_path, name, text):
    p = tmp_path / name
    p.write_text(text)
    return str(p)


def _findings(results, checker):
    return [f for f in results.get(checker, []) if not f.suppressed]


# ------------------------------------------------------------------ guards


GUARDS_BAD = '''
import threading

class Store:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = {}

    def put(self, k, v):
        with self._lock:
            self._items[k] = v

    def drop(self, k):
        with self._lock:
            self._items.pop(k, None)

    def rogue(self, k, v):
        self._items[k] = v  # write without the lock
'''

GUARDS_ANNOTATED_BAD = '''
import threading

class Flagged:
    def __init__(self):
        self._lock = threading.Lock()
        self._state = "idle"  # guarded-by: _lock

    def set_state(self, s):
        with self._lock:
            self._state = s

    def peek(self):
        return self._state  # unguarded READ of an annotated attr
'''

GUARDS_CLEAN = '''
import threading

class Store:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = {}  # guarded-by: _lock

    def put(self, k, v):
        with self._lock:
            self._items[k] = v

    def get(self, k):
        with self._lock:
            return self._items.get(k)
'''


class TestGuardsChecker:
    def test_inferred_unguarded_write_fires(self, tmp_path):
        path = _write(tmp_path, "g_bad.py", GUARDS_BAD)
        out = _findings(analyze_paths([path], checkers=("guards",)),
                        "guards")
        assert len(out) == 1
        assert "write of Store._items without holding" in out[0].message
        assert out[0].line == GUARDS_BAD.splitlines().index(
            "        self._items[k] = v  # write without the lock") + 1

    def test_annotated_unguarded_read_fires(self, tmp_path):
        path = _write(tmp_path, "g_ann.py", GUARDS_ANNOTATED_BAD)
        out = _findings(analyze_paths([path], checkers=("guards",)),
                        "guards")
        assert len(out) == 1
        assert "read of Flagged._state" in out[0].message
        assert "guarded-by annotation" in out[0].message

    def test_clean_fixture_is_quiet(self, tmp_path):
        path = _write(tmp_path, "g_clean.py", GUARDS_CLEAN)
        assert _findings(analyze_paths([path], checkers=("guards",)),
                         "guards") == []

    def test_annassign_annotation_fires(self, tmp_path):
        # Regression: a typed __init__ assignment (ast.AnnAssign, e.g.
        # ``self._state: str = "idle"``) used to be skipped by the
        # annotation indexer, silently discarding its guarded-by contract
        # — most of the package's annotated state is typed, so the
        # package gate was green without checking any of it.
        text = GUARDS_ANNOTATED_BAD.replace(
            'self._state = "idle"  # guarded-by: _lock',
            'self._state: str = "idle"  # guarded-by: _lock')
        path = _write(tmp_path, "g_typed.py", text)
        out = _findings(analyze_paths([path], checkers=("guards",)),
                        "guards")
        assert len(out) == 1
        assert "read of Flagged._state" in out[0].message
        assert "guarded-by annotation" in out[0].message

    def test_unguarded_ok_suppresses_with_reason(self, tmp_path):
        text = GUARDS_ANNOTATED_BAD.replace(
            "return self._state  # unguarded READ of an annotated attr",
            "return self._state  # unguarded-ok: racy peek is advisory")
        path = _write(tmp_path, "g_supp.py", text)
        results = analyze_paths([path], checkers=("guards",))
        assert _findings(results, "guards") == []
        supp = [f for f in results["guards"] if f.suppressed]
        assert len(supp) == 1 and supp[0].reason == "racy peek is advisory"

    def test_reasonless_suppression_is_a_finding(self, tmp_path):
        text = GUARDS_ANNOTATED_BAD.replace(
            "return self._state  # unguarded READ of an annotated attr",
            "return self._state  # unguarded-ok:")
        path = _write(tmp_path, "g_noreason.py", text)
        out = _findings(analyze_paths([path], checkers=("guards",)),
                        "guards")
        assert len(out) == 1
        assert "without a reason" in out[0].message


# ---------------------------------------------------------------- lockorder


LOCKORDER_BAD = '''
import threading

class A:
    def __init__(self, b):
        self.l1 = threading.Lock()
        self.b = b

    def forward(self):
        with self.l1:
            with self.b.l2:
                pass

class B:
    def __init__(self, a):
        self.l2 = threading.Lock()
        self.a = a

    def backward(self):
        with self.l2:
            with self.a.l1:
                pass
'''

LOCKORDER_CLEAN = '''
import threading

class A:
    def __init__(self, b):
        self.l1 = threading.Lock()
        self.b = b

    def forward(self):
        with self.l1:
            with self.b.l2:
                pass

    def also_forward(self):
        with self.l1:
            with self.b.l2:
                pass

class B:
    def __init__(self, a):
        self.l2 = threading.Lock()
        self.a = a
'''


class TestLockOrderChecker:
    def test_cycle_fires(self, tmp_path):
        path = _write(tmp_path, "lo_bad.py", LOCKORDER_BAD)
        out = _findings(analyze_paths([path], checkers=("lockorder",)),
                        "lockorder")
        assert len(out) == 1
        assert "lock-order cycle" in out[0].message
        assert "A.l1" in out[0].message and "B.l2" in out[0].message

    def test_consistent_order_is_quiet(self, tmp_path):
        path = _write(tmp_path, "lo_clean.py", LOCKORDER_CLEAN)
        assert _findings(analyze_paths([path], checkers=("lockorder",)),
                         "lockorder") == []

    def test_canonical_order_respects_edges(self, tmp_path):
        from maggy_tpu.analysis.astindex import parse_package
        from maggy_tpu.analysis.lockorder import build_graph, canonical_order

        path = _write(tmp_path, "lo_clean.py", LOCKORDER_CLEAN)
        index = parse_package(None, paths=[path])
        order = canonical_order(build_graph(index))
        assert order.index("A.l1") < order.index("B.l2")

    def test_suppressed_edge_needs_reason(self, tmp_path):
        text = LOCKORDER_BAD.replace(
            "        with self.l2:\n            with self.a.l1:",
            "        with self.l2:\n            # lock-order-ok: proven never concurrent with forward\n            with self.a.l1:")
        path = _write(tmp_path, "lo_supp.py", text)
        out = _findings(analyze_paths([path], checkers=("lockorder",)),
                        "lockorder")
        assert out == []  # suppressed with a reason: no cycle reported

    def test_call_crossing_edge_detected(self, tmp_path):
        text = '''
import threading

class C:
    def __init__(self):
        self.outer = threading.Lock()
        self.inner = threading.Lock()

    def leaf(self):
        with self.inner:
            pass

    def top(self):
        with self.outer:
            self.leaf()

    def inverted(self):
        with self.inner:
            with self.outer:
                pass
'''
        path = _write(tmp_path, "lo_call.py", text)
        out = _findings(analyze_paths([path], checkers=("lockorder",)),
                        "lockorder")
        # outer -> inner exists only THROUGH the call; inverted closes
        # the cycle.
        assert len(out) == 1 and "lock-order cycle" in out[0].message


# ------------------------------------------------------------------ rpcconf


RPCCONF_BAD = '''
class MiniServer:
    def __init__(self):
        self._handlers = {}
        self._register_handlers()

    def _register_handlers(self):
        self._handlers["PING"] = self._ping
        self._handlers["GHOST"] = self._ghost

    def _ping(self, msg):
        return {"type": "OK", "echo": msg["payload"], "extra": msg["missing"]}

    def _ghost(self, msg):
        return {"type": "OK"}

    def handle_message(self, msg):
        t0 = 0
        self.metrics.histogram("rpc.handle_ms." + msg["type"]).observe(t0)
        return self._handlers[msg["type"]](msg)


class MiniClient:
    def ping(self):
        return self._request({"type": "PING", "payload": "x",
                              "dead_key": 1})
'''

RPCCONF_CLEAN = '''
class MiniServer:
    def __init__(self):
        self._handlers = {}
        self._register_handlers()

    def _register_handlers(self):
        self._handlers["PING"] = self._ping

    def _ping(self, msg):
        return {"type": "OK", "echo": msg["payload"]}

    def handle_message(self, msg):
        t0 = 0
        self.metrics.histogram("rpc.handle_ms." + msg["type"]).observe(t0)
        return self._handlers[msg["type"]](msg)


class MiniClient:
    def ping(self):
        return self._request({"type": "PING", "payload": "x"})
'''


class TestRpcConfChecker:
    def test_bad_fixture_fires_all_three_ways(self, tmp_path):
        path = _write(tmp_path, "rpc_bad.py", RPCCONF_BAD)
        out = _findings(analyze_paths([path], checkers=("rpcconf",)),
                        "rpcconf")
        msgs = "\n".join(f.message for f in out)
        # 1. registered verb with no producer anywhere
        assert "verb GHOST is registered but has no producer" in msgs
        # 2. handler indexes a key no producer sends (KeyError on delivery)
        assert "indexes msg['missing']" in msgs
        # 3. producer sends a key no handler reads (dead vocabulary)
        assert "sends key 'dead_key'" in msgs

    def test_clean_fixture_is_quiet(self, tmp_path):
        path = _write(tmp_path, "rpc_clean.py", RPCCONF_CLEAN)
        assert _findings(analyze_paths([path], checkers=("rpcconf",)),
                         "rpcconf") == []

    def test_missing_dispatch_timing_fires(self, tmp_path):
        text = RPCCONF_CLEAN.replace(
            '        self.metrics.histogram("rpc.handle_ms." + msg["type"]).observe(t0)\n',
            "")
        path = _write(tmp_path, "rpc_untimed.py", text)
        out = _findings(analyze_paths([path], checkers=("rpcconf",)),
                        "rpcconf")
        assert len(out) == 1
        assert "no rpc.handle_ms.<verb> dispatch timing" in out[0].message

    def test_rpc_ok_suppresses(self, tmp_path):
        text = RPCCONF_BAD.replace(
            '        self._handlers["GHOST"] = self._ghost',
            '        # rpc-ok: produced by an external CLI, invisible here\n'
            '        self._handlers["GHOST"] = self._ghost')
        path = _write(tmp_path, "rpc_supp.py", text)
        out = _findings(analyze_paths([path], checkers=("rpcconf",)),
                        "rpcconf")
        assert not any("GHOST" in f.message for f in out)


# ------------------------------------------------------------- journalvocab


VOCAB_FIXTURE = '''
SPAN_PHASES = ("queued", "running")
EVENT_KINDS = frozenset({"trial"})
REQUEUE_REASONS = frozenset()
'''

EMIT_CLEAN = '''
def emit_all(t, tid):
    t.trial_event(tid, "queued")
    t.trial_event(tid, "running")
    t.event("trial", phase="queued")

def consume(ev):
    return ev.get("phase") == "running"
'''

EMIT_TYPO = '''
def emit_all(t, tid):
    t.trial_event(tid, "queued")
    t.trial_event(tid, "running")
    t.event("trial")
    t.trial_event(tid, "runing")  # emitter typo
'''

CONSUME_TYPO = '''
def emit_all(t, tid):
    t.trial_event(tid, "queued")
    t.trial_event(tid, "running")
    t.event("trial")

def consume(ev):
    return ev.get("phase") == "runningg"  # consumer typo
'''


class TestJournalVocabChecker:
    def test_emitter_typo_fires(self, tmp_path):
        paths = [_write(tmp_path, "vocab.py", VOCAB_FIXTURE),
                 _write(tmp_path, "emit.py", EMIT_TYPO)]
        out = _findings(analyze_paths(paths, checkers=("journalvocab",)),
                        "journalvocab")
        assert len(out) == 1
        assert "emitted phase 'runing' is not in the journal" \
            in out[0].message

    def test_orphan_vocab_entry_fires(self, tmp_path):
        # "running" is in the vocabulary but nothing ever emits it: a
        # consumer match that can never fire (the emitter-only direction's
        # mirror image).
        emit_one = ('def emit_all(t, tid):\n'
                    '    t.trial_event(tid, "queued")\n'
                    '    t.event("trial")\n')
        paths = [_write(tmp_path, "vocab.py", VOCAB_FIXTURE),
                 _write(tmp_path, "emit.py", emit_one)]
        out = _findings(analyze_paths(paths, checkers=("journalvocab",)),
                        "journalvocab")
        assert len(out) == 1
        assert "vocabulary entry 'running'" in out[0].message
        assert "never emitted" in out[0].message

    def test_consumer_typo_fires(self, tmp_path):
        paths = [_write(tmp_path, "vocab.py", VOCAB_FIXTURE),
                 _write(tmp_path, "code.py", CONSUME_TYPO)]
        out = _findings(analyze_paths(paths, checkers=("journalvocab",)),
                        "journalvocab")
        assert len(out) == 1
        assert "consumer matches phase 'runningg'" in out[0].message
        assert "can never fire" in out[0].message

    def test_clean_fixture_is_quiet(self, tmp_path):
        paths = [_write(tmp_path, "vocab.py", VOCAB_FIXTURE),
                 _write(tmp_path, "code.py", EMIT_CLEAN)]
        assert _findings(analyze_paths(paths, checkers=("journalvocab",)),
                         "journalvocab") == []

    PLAN_VOCAB = VOCAB_FIXTURE + '''
COMPILED_FIELDS = ("warm", "flash_plan", "moe_plan", "moe_ops", "remat_plan")
'''
    PLAN_CALLS = EMIT_CLEAN + '''
def trace_parts(scopes):
    remember_plan("flash", "fwd q128")
    remember_plan("moe", "experts 0+4/8", scopes)
    remember_plan("{}", "layer keeps flash_out")
'''

    @pytest.mark.parametrize("said,finding", [
        ("remat", None),
        ("rematt", "emitted compiled_field 'rematt_plan' is not in"),
        ("flash", "entry 'remat_plan' (COMPILED_FIELDS) is said by no"),
    ])
    def test_compiled_fields_are_held_to_the_remember_plan_calls(
            self, tmp_path, said, finding):
        # A kind nobody listed, and a listed plan that no traced part says
        # (``warm`` is the trainer's own field, not a plan: never asked for).
        paths = [_write(tmp_path, "vocab.py", self.PLAN_VOCAB),
                 _write(tmp_path, "code.py", self.PLAN_CALLS.format(said))]
        out = _findings(analyze_paths(paths, checkers=("journalvocab",)),
                        "journalvocab")
        if finding is None:
            assert out == []
        else:
            assert any(finding in f.message for f in out)
            assert len(out) == (2 if said == "rematt" else 1)

    SCOPE_VOCAB = VOCAB_FIXTURE + '''
COMPILED_FIELDS = ("warm", "step_ops", "step_mixed")
STEP_SCOPES = ("optimizer", "moe_experts", "moe_shared")
'''
    SCOPE_CALLS = EMIT_CLEAN + '''
import jax
STEP_FIELDS = ("step_ops", {mixed!r})
SCOPES = ("moe_experts", {listed!r})
SHARED_SCOPE = {shared!r}

@jax.named_scope("optimizer")
def update(x):
    with jax.named_scope({opened!r}):
        x = x + 1
    with jax.named_scope(SHARED_SCOPE):
        return x
'''

    @pytest.mark.parametrize("changed,finding", [
        ({}, None),
        ({"opened": "moe_expert"},  # opened, and in no list
         "emitted step_scope 'moe_expert' is not in"),
        ({"shared": "moe_shard"},  # ... through a module constant
         "emitted step_scope 'moe_shard' is not in"),
        ({"opened": "optimizer"},  # listed, and nothing opens it
         "entry 'moe_experts' (STEP_SCOPES) is never emitted"),
        ({"listed": "moe_combine"},  # a model's table names a stranger
         "scope 'moe_combine' of a scopes table is not in STEP_SCOPES"),
        ({"mixed": "step_mixd"},  # a field the vocabulary does not hold
         "emitted compiled_field 'step_mixd' is not in"),
    ])
    def test_scopes_and_step_fields_are_held_both_ways(
            self, tmp_path, changed, finding):
        # ``step_ops`` ends like a kind's field and no `remember_plan` says
        # it: the ``*_FIELDS`` table of the module that writes it does.
        said = {"mixed": "step_mixed", "listed": "moe_shared",
                "shared": "moe_shared", "opened": "moe_experts", **changed}
        paths = [_write(tmp_path, "vocab.py", self.SCOPE_VOCAB),
                 _write(tmp_path, "code.py", self.SCOPE_CALLS.format(**said))]
        out = _findings(analyze_paths(paths, checkers=("journalvocab",)),
                        "journalvocab")
        if finding is None:
            assert out == []
        else:
            assert any(finding in f.message for f in out), \
                [f.message for f in out]

    def test_the_package_opens_every_scope_it_lists(self):
        # Every `jax.named_scope` of the package is in STEP_SCOPES and the
        # other way round (the package run below has no finding), and the
        # models' tables are parts of the one list.
        from maggy_tpu.models import moe, ouro, smallthinker
        from maggy_tpu.ops import ssd
        from maggy_tpu.telemetry import plans, vocab

        assert set(moe.SCOPES + (moe.SHARED_SCOPE,) + ssd.SCOPES
                   + ouro.LOOP_SCOPES + smallthinker.ATTN_SCOPES) \
            <= set(vocab.STEP_SCOPES)
        assert set(plans.STEP_FIELDS) <= set(vocab.COMPILED_FIELDS)
        report = run_analysis(checkers=("journalvocab",))
        assert report["summary"] == {"journalvocab": 0}

    def test_the_package_says_every_plan_it_lists(self):
        # The model's `remember_plan("remat", ...)` is what `remat_plan`
        # in COMPILED_FIELDS stands on: without the entry the package run
        # has a finding, with it none.
        from maggy_tpu.telemetry import vocab

        assert {"flash_plan", "moe_plan", "moe_ops", "remat_plan"} \
            <= set(vocab.COMPILED_FIELDS)
        report = run_analysis(checkers=("journalvocab",))
        assert report["summary"] == {"journalvocab": 0}

    def test_package_vocab_module_exists(self):
        # The real vocabulary module the checker verifies against.
        from maggy_tpu.telemetry import vocab

        assert "queued" in vocab.SPAN_PHASES
        assert "trial" in vocab.EVENT_KINDS
        assert vocab.REQUEUE_REASONS <= vocab.ALL_REASONS


# ------------------------------------------------------------------ witness


class TestWitnessUnit:
    def test_forbidden_edge_is_a_violation(self):
        w = Witness(["A.x", "B.y"])
        w.note_acquire(1, "B.y")
        w.note_acquire(2, "A.x")  # acquiring earlier-ordered while holding later
        assert len(w.violations) == 1
        v = w.violations[0]
        assert v.held == "B.y" and v.acquired == "A.x"
        with pytest.raises(AssertionError):
            w.check()

    def test_canonical_order_edge_is_clean(self):
        w = Witness(["A.x", "B.y"])
        w.note_acquire(1, "A.x")
        w.note_acquire(2, "B.y")
        assert w.violations == []
        assert ("A.x", "B.y") in w.edges
        w.check()

    def test_release_unwinds_held_set(self):
        w = Witness(["A.x", "B.y"])
        w.note_acquire(1, "B.y")
        w.note_release(1)
        w.note_acquire(2, "A.x")  # nothing held anymore: no edge at all
        assert w.violations == [] and w.edges == {}

    def test_two_instances_of_one_decl_are_unordered(self):
        w = Witness(["Trial.lock"])
        w.note_acquire(1, "Trial.lock")
        w.note_acquire(2, "Trial.lock")
        assert w.violations == [] and w.edges == {}

    def test_forbidden_edge_records_every_occurrence(self):
        # Regression: violations were only recorded the FIRST time an
        # edge was seen. With one env-armed witness shared across soaks
        # (each counting violations from its own install point), a
        # repeat offense in a later soak would slice to nothing and the
        # soak would pass despite observing the forbidden interleaving.
        w = Witness(["A.x", "B.y"])
        for _ in range(2):
            w.note_acquire(1, "B.y")
            w.note_acquire(2, "A.x")
            w.note_release(2)
            w.note_release(1)
        assert len(w.violations) == 2
        assert len(w.edges) == 1  # edge inventory stays deduped

    def test_site_named_locks_record_but_never_violate(self):
        w = Witness(["A.x"])
        w.note_acquire(1, "some/file.py:10")
        w.note_acquire(2, "A.x")
        assert ("some/file.py:10", "A.x") in w.edges
        assert w.violations == []


class TestWitnessInstall:
    def test_package_lock_wrapped_foreign_lock_passthrough(self):
        w = witness_mod.install()
        try:
            from maggy_tpu.telemetry.metrics import MetricsRegistry

            reg = MetricsRegistry()
            assert type(reg._lock).__name__ == "_WitnessLock"
            assert reg._lock._name == "MetricsRegistry._lock"
            # Allocated from THIS test file (outside the package): real.
            foreign = threading.Lock()
            assert type(foreign).__name__ != "_WitnessLock"
            # Wrapped locks still work as locks.
            reg.counter("c").inc()
            assert reg.counter("c").value == 1
        finally:
            witness_mod.uninstall()
        assert threading.Lock is witness_mod._REAL_LOCK
        assert w.violations == []

    def test_install_is_idempotent(self):
        w1 = witness_mod.install()
        try:
            assert witness_mod.install() is w1
        finally:
            witness_mod.uninstall()

    def test_condition_over_wrapped_rlock(self):
        """The fleet scheduler's wake condition wraps its RLock: wait/
        notify must work through the witness wrapper (the _release_save/
        _acquire_restore/_is_owned protocol), and the witness must not
        warn on the reentrant traffic."""
        witness_mod.install()
        try:
            from maggy_tpu.fleet.scheduler import FleetScheduler

            sched = FleetScheduler(fleet_size=1)
            assert type(sched._lock).__name__ == "_WitnessLock"
            woke = []

            def waiter():
                with sched._wake:
                    woke.append(sched._wake.wait(timeout=2.0))

            t = threading.Thread(target=waiter)
            t.start()
            time.sleep(0.1)
            with sched._wake:
                sched._wake.notify_all()
            t.join(timeout=5)
            assert woke == [True]
            # Reentrant acquisition through the wrapper is silent.
            with sched._lock:
                with sched._lock:
                    pass
            w = witness_mod.active_witness()
            assert w.violations == []
        finally:
            witness_mod.uninstall()

    def test_forbidden_runtime_edge_detected(self):
        w = witness_mod.install()
        try:
            from maggy_tpu.fleet.scheduler import FleetScheduler
            from maggy_tpu.telemetry.metrics import MetricsRegistry

            sched = FleetScheduler(fleet_size=1)
            reg = MetricsRegistry()
            a, b = sorted(
                [(w.positions["FleetScheduler._lock"], sched._lock),
                 (w.positions["MetricsRegistry._lock"], reg._lock)])
            with a[1]:
                with b[1]:  # canonical direction: clean
                    pass
            assert w.violations == []
            with b[1]:
                with a[1]:  # inverted: forbidden
                    pass
            assert len(w.violations) == 1
        finally:
            witness_mod.uninstall()


def _witness_train(lr, units, reporter=None):
    acc = 1.0 - ((lr - 0.1) ** 2 + ((units - 32) / 64.0) ** 2)
    for step in range(3):
        time.sleep(0.02)
        if reporter is not None:
            reporter.broadcast(acc * (step + 1) / 3.0, step=step)
    return {"metric": acc}


@pytest.mark.timeout(180)
class TestWitnessExperiment:
    """The tier-1 witnessed run the acceptance criteria require: a real
    experiment under the instrumented lock wrappers finishes with real
    acquisition edges recorded and ZERO forbidden ones."""

    def test_experiment_under_witness_zero_forbidden_edges(self, tmp_path):
        from maggy_tpu import OptimizationConfig, Searchspace, experiment
        from maggy_tpu.core.environment import EnvSing
        from maggy_tpu.core.environment.abstractenvironment import LocalEnv

        env = LocalEnv(base_dir=str(tmp_path / "exp"))
        EnvSing.set_instance(env)
        w = witness_mod.install()
        try:
            config = OptimizationConfig(
                name="witnessed", num_trials=4, optimizer="randomsearch",
                searchspace=Searchspace(lr=("DOUBLE", [0.0, 0.2]),
                                        units=("INTEGER", [8, 64])),
                direction="max", num_workers=2, hb_interval=0.02, seed=5,
                es_policy="none")
            result = experiment.lagom(_witness_train, config)
        finally:
            witness_mod.uninstall()
            EnvSing.reset()
        assert result["num_trials"] == 4
        snap = w.snapshot()
        assert snap["edge_count"] > 0, \
            "a real experiment must exercise nested acquisitions"
        assert snap["violations"] == []


# ----------------------------------------------------- package enforcement


@pytest.mark.timeout(180)
class TestPackageConformance:
    """The tier-1 gate: the installed package must analyze clean — every
    remaining suppression carries a written reason. A regression in any
    checker's vocabulary or a new unguarded access fails HERE, in CI,
    before any soak could ever hit the race."""

    @pytest.fixture(scope="class")
    def report(self):
        return run_analysis()

    def test_no_unsuppressed_findings(self, report):
        assert report["findings"] == [], \
            "unannotated findings:\n" + "\n".join(
                repr(f) for f in report["findings"])

    def test_every_suppression_has_a_reason(self, report):
        for f in report["suppressed"]:
            assert f.reason, "reasonless suppression: {!r}".format(f)

    def test_lock_inventory_and_order(self, report):
        # ~40 locks per the issue; the exact count moves with the code,
        # the floor pins that lock DISCOVERY keeps working.
        assert report["num_locks"] >= 30
        assert len(report["lock_order"]) >= 30
        assert len(report["lock_edges"]) >= 20
        # The canonical order is total over the discovered locks.
        assert len(report["lock_order"]) == len(set(report["lock_order"]))

    def test_cli_exits_zero(self, capsys):
        from maggy_tpu.analysis.__main__ import main

        assert main([]) == 0
        out = capsys.readouterr().out
        assert "0 finding(s)" in out


# ---------------------------------------------------------- real-bug tests


class _PendingDeviceMetric:
    """Device-array stand-in whose value is never ready, with a hook run
    inside ``is_ready`` — the exact interleaving window of the
    _async_kick rollover race."""

    shape = ()
    dtype = np.dtype("float32")

    def __init__(self, on_is_ready=None):
        self.copy_calls = 0
        self._hook = on_is_ready

    def is_ready(self):
        if self._hook is not None:
            self._hook()
        return False

    def copy_to_host_async(self):
        self.copy_calls += 1

    def __float__(self):
        return 0.5


class TestReporterAsyncKickRollover:
    """Regression for the guards-checker finding fixed in this PR: the
    heartbeat thread's async-copy kick wrote ``_async_kick`` WITHOUT the
    reporter lock. If the trial rolled over (reset()) between the
    ready-check and the kick, the write resurrected the RETIRED trial's
    device array as the NEXT trial's in-flight kick."""

    def test_rollover_mid_get_data_suppresses_kick(self):
        from maggy_tpu.core.reporter import Reporter

        rep = Reporter()
        rep.reset(trial_id="t1")
        metric = _PendingDeviceMetric(
            on_is_ready=lambda: rep.reset(trial_id="t2"))
        rep.broadcast(metric, step=0)
        data = rep.get_data()
        # The rolled-over reporter must NOT have kicked the retired
        # trial's array, nor kept it as in-flight state.
        assert metric.copy_calls == 0
        assert rep._async_kick is None
        # Nothing shippable this beat (value pending, no prior cache).
        assert data["metric"] is None and data["step"] is None

    def test_no_rollover_kicks_exactly_once(self):
        from maggy_tpu.core.reporter import Reporter

        rep = Reporter()
        rep.reset(trial_id="t1")
        metric = _PendingDeviceMetric()
        rep.broadcast(metric, step=0)
        rep.get_data()
        rep.get_data()  # second beat: kick already in flight, no re-kick
        assert metric.copy_calls == 1
        assert rep._async_kick is metric


class TestFinalPayloadConformance:
    """Regression for the rpcconf finding fixed in this PR: FINAL
    payloads carried a ``span`` key no handler or driver callback ever
    read (the driver attributes FINALs through the span tracker by trial
    id). Dead keys are exactly how the retried-FINAL race hid; the
    checker now flags them, and this pins the wire shape."""

    def _client(self, sent):
        from maggy_tpu.core import rpc

        c = object.__new__(rpc.Client)
        c.last_info = {"epoch": 3}
        c._request = lambda msg, **kw: (sent.update(msg), {"type": "OK"})[1]
        c._handle_final_reply = lambda resp: None
        return c

    def test_final_sends_no_dead_span_key(self):
        from maggy_tpu.core.reporter import Reporter

        sent = {}
        c = self._client(sent)
        rep = Reporter()
        rep.reset(trial_id="t1", span="s1")
        rep.broadcast(0.7, step=0)
        c.finalize_metric(0.7, rep)
        assert sent["type"] == "FINAL"
        assert sent["trial_id"] == "t1"
        assert sent["value"] == 0.7
        assert "span" not in sent
        # The run-epoch echo IS read (the driver's stale-FINAL guard
        # drops a dead run's FINAL by epoch mismatch) — not a dead key.
        assert sent["epoch"] == 3

    def test_error_and_preempt_finals_conform_too(self):
        from maggy_tpu.core.reporter import Reporter

        sent = {}
        c = self._client(sent)
        rep = Reporter()
        rep.reset(trial_id="t2", span="s2")
        c.finalize_error("t2", rep)
        assert sent["type"] == "FINAL" and sent["error"] is True
        assert "span" not in sent
        sent.clear()
        rep.reset(trial_id="t3", span="s3")
        c.preempt_ack("t3", rep, step=4)
        assert sent["type"] == "FINAL" and sent["preempted"] is True
        assert sent["step"] == 4
        assert "span" not in sent

"""Compile-once trial hot path: warm-state harness, compile telemetry,
and the no-stale-params guarantee.

ROADMAP item 3. The tentpole claims under test:

- program identity is derived automatically (model config + mesh +
  strategy + swept-optimizer family) and repeat-shape trials share one
  warm slot — the compiled step, the shardings and the two init programs;
- the warm path NEVER leaks state: a warm trial's losses are bit-identical
  to a cold runner's (the slot holds programs, never a trial's arrays;
  cold, warm and vectorized run one init sequence), and warm_start=False
  reproduces the legacy build-per-trial behavior;
- the opaque ttfm splits into journaled phases (init/trace/compile/
  first_step) with warm + persistent-cache hit rates, replayable from the
  journal and rendered by monitor/trace/bench.
"""

import glob
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from maggy_tpu.models import MnistCNN
from maggy_tpu.parallel import make_mesh
from maggy_tpu.train import (Trainer, clear_warm, cross_entropy_loss,
                             swept_transform, warm_cache)
from maggy_tpu.train import warm
from maggy_tpu.telemetry.runnerstats import RunnerStats


def loss_fn(logits, batch):
    return cross_entropy_loss(logits, batch["labels"])


MODEL = MnistCNN(kernel_size=3, pool_size=2, features=4, num_classes=2)
RNG = np.random.default_rng(0)
X = RNG.normal(size=(32, 8, 8, 1)).astype(np.float32)
Y = (RNG.normal(size=(32,)) > 0).astype(np.int32)
EXAMPLE = (jnp.zeros((1, 8, 8, 1)),)


def mesh1():
    return make_mesh({"data": 1}, devices=jax.devices()[:1])


def make_trainer(lr, warm_start=None, step_key=None, tx=None):
    return Trainer(MODEL, tx or swept_transform(optax.adam, learning_rate=lr),
                   loss_fn, mesh1(), warm_start=warm_start,
                   step_key=step_key)


def run_trial(lr, steps=3, warm_start=None):
    tr = make_trainer(lr, warm_start=warm_start)
    tr.init(jax.random.key(0), EXAMPLE)
    losses = []
    for _ in range(steps):
        batch = tr.place_batch({"inputs": (jnp.asarray(X),),
                                "labels": jnp.asarray(Y)})
        losses.append(float(tr.step(batch)))
    return tr, losses


@pytest.fixture(autouse=True)
def fresh_cache():
    clear_warm()
    yield
    clear_warm()


class TestProgramKeys:
    def test_swept_family_shares_one_slot(self):
        t1 = make_trainer(3e-3)
        t2 = make_trainer(1e-4)
        assert t1._slot is t2._slot
        assert t1._step is t2._step
        assert len(warm_cache()) == 1

    def test_different_optimizer_family_does_not_share(self):
        t1 = make_trainer(1e-3)
        t2 = Trainer(MODEL, swept_transform(optax.sgd, learning_rate=1e-3),
                     loss_fn, mesh1())
        assert t1._slot is not t2._slot

    def test_plain_tx_gets_private_slot(self):
        n0 = len(warm_cache())
        t1 = Trainer(MODEL, optax.adam(1e-3), loss_fn, mesh1())
        t2 = Trainer(MODEL, optax.adam(1e-3), loss_fn, mesh1())
        # Distinct transform objects may bake distinct constants into the
        # program: never shared, and never churning the shared LRU.
        assert t1._slot is not t2._slot
        assert len(warm_cache()) == n0

    def test_manual_step_key_still_shares(self):
        t1 = make_trainer(1e-3, step_key=("k",))
        t2 = make_trainer(5e-3, step_key=("k",))
        assert t1._slot is t2._slot

    def test_warm_start_false_is_legacy(self):
        t = make_trainer(1e-3, warm_start=False)
        assert t._slot is None
        assert len(warm_cache()) == 0

    def test_lambda_loss_misses(self):
        t1 = Trainer(MODEL, swept_transform(optax.adam, learning_rate=1e-3),
                     lambda o, b: cross_entropy_loss(o, b["labels"]), mesh1())
        t2 = Trainer(MODEL, swept_transform(optax.adam, learning_rate=1e-3),
                     lambda o, b: cross_entropy_loss(o, b["labels"]), mesh1())
        assert t1._slot is not t2._slot

    def test_unhashable_model_degrades_to_private_slot(self):
        """The DEFAULT warm path must never reject a model that trained
        fine before it existed — an unhashable program component (e.g. a
        flax module holding a list-typed field) degrades to a private
        slot instead of raising at Trainer construction."""
        import flax.linen as nn

        class ListModel(nn.Module):
            feats: list  # lists are unhashable -> the module is too

            @nn.compact
            def __call__(self, x):
                for f in self.feats:
                    x = nn.Dense(f)(x)
                return x

        n0 = len(warm_cache())
        t = Trainer(ListModel(feats=[4, 2]),
                    swept_transform(optax.adam, learning_rate=1e-3),
                    loss_fn, mesh1())
        assert t._slot is not None  # private: AOT split + telemetry kept
        assert len(warm_cache()) == n0  # and the shared LRU untouched

    def test_schedule_hparam_is_family_less(self):
        """A schedule/callable hyperparameter reprs by object id: two
        identical constructions would mint DISTINCT families, each trial
        a never-matching shared-LRU key evicting genuinely warm programs.
        Such transforms must stay family-less (private slot)."""
        sched = optax.cosine_decay_schedule(0.1, 100)
        tx = swept_transform(optax.adam, learning_rate=sched)
        assert warm.opt_family(tx) is None
        n0 = len(warm_cache())
        t1 = Trainer(MODEL, tx, loss_fn, mesh1())
        t2 = Trainer(
            MODEL,
            swept_transform(optax.adam,
                            learning_rate=optax.cosine_decay_schedule(
                                0.1, 100)),
            loss_fn, mesh1())
        assert t1._slot is not t2._slot
        assert len(warm_cache()) == n0  # the shared LRU is not churned

    def test_stringly_static_hparams_still_share(self):
        """Repr-stable statics (str/bool/numbers/tuples) keep the family:
        identical constructions share one program."""
        f1 = warm.opt_family(swept_transform(
            optax.adamw, learning_rate=1e-3, weight_decay=1e-4))
        f2 = warm.opt_family(swept_transform(
            optax.adamw, learning_rate=3e-3, weight_decay=5e-4))
        assert f1 is not None and f1 == f2


class TestWarmCacheBounds:
    def test_lru_bound_and_clear(self):
        cache = warm.WarmCache(maxsize=2)
        a, hit_a = cache.slot("a")
        assert not hit_a
        cache.slot("b")
        cache.slot("c")  # evicts "a"
        assert len(cache) == 2
        assert "a" not in cache.keys()
        a2, hit_a2 = cache.slot("a")
        assert not hit_a2 and a2 is not a
        cache.clear()
        assert len(cache) == 0

    def test_lru_touch_refreshes(self):
        cache = warm.WarmCache(maxsize=2)
        cache.slot("a")
        cache.slot("b")
        cache.slot("a")  # touch
        cache.slot("c")  # evicts "b", not "a"
        assert set(cache.keys()) == {"a", "c"}

    def test_env_bound(self, monkeypatch):
        monkeypatch.setenv("MAGGY_TPU_WARM_SLOTS", "3")
        assert warm.WarmCache().maxsize == 3


class TestShardingMemo:
    """Satellite: place_batch/data.py reuse one memoized sharding per
    (mesh, shape) instead of re-deriving specs per leaf per step."""

    def test_cached_batch_sharding_memoizes(self):
        from maggy_tpu.parallel.sharding import (batch_sharding,
                                                 cached_batch_sharding)

        m = mesh1()
        a = cached_batch_sharding(m, (8, 4))
        assert cached_batch_sharding(m, (8, 4)) is a
        assert a == batch_sharding(m, shape=(8, 4))
        assert cached_batch_sharding(m, (8, 2)) == \
            batch_sharding(m, shape=(8, 2))

    def test_distinct_meshes_do_not_collide(self):
        from maggy_tpu.parallel.sharding import cached_batch_sharding

        m1 = make_mesh({"data": 1}, devices=jax.devices()[:1])
        m2 = make_mesh({"data": 2}, devices=jax.devices()[:2])
        assert cached_batch_sharding(m1, (8, 4)).mesh is m1
        assert cached_batch_sharding(m2, (8, 4)).mesh is m2


class TestRebindHyperparams:
    def test_rebinds_injected_values_only(self):
        tx = swept_transform(optax.adam, learning_rate=2e-3)
        params = {"w": jnp.zeros((3,))}
        state = tx.init(params)
        rebound = warm.rebind_hyperparams(state, {"learning_rate": 9e-1,
                                                  "not_there": 1.0})
        assert float(rebound.hyperparams["learning_rate"]) == \
            pytest.approx(9e-1)
        assert rebound.hyperparams["learning_rate"].dtype == \
            state.hyperparams["learning_rate"].dtype
        # non-hyperparam leaves untouched
        assert jax.tree_util.tree_structure(rebound) == \
            jax.tree_util.tree_structure(state)

    def test_plain_state_passthrough(self):
        tx = optax.adam(1e-3)
        state = tx.init({"w": jnp.zeros((3,))})
        rebound = warm.rebind_hyperparams(state, {"learning_rate": 1.0})
        assert jax.tree_util.tree_structure(rebound) == \
            jax.tree_util.tree_structure(state)


class TestNoStateLeak:
    """The acceptance bar: the warm path never changes training values."""

    def test_warm_trials_match_cold_bitwise(self):
        _, w1 = run_trial(3e-3)
        _, w2 = run_trial(1e-3)          # warm: same programs + rebind
        _, w3 = run_trial(7e-4)
        _, c1 = run_trial(3e-3, warm_start=False)
        _, c2 = run_trial(1e-3, warm_start=False)
        _, c3 = run_trial(7e-4, warm_start=False)
        assert w1 == c1
        assert w2 == c2, "stale params leaked through the warm slot"
        assert w3 == c3

    def test_warm_hit_counted_and_slot_holds_no_trial_array(self):
        import gc
        import weakref

        c0 = warm.counters()
        with warm.trial_scope(trial_id="t1", enabled=True):
            t1, _ = run_trial(3e-3)
            slot, ikey = t1._slot, t1._init_ikey
            leaves = [weakref.ref(x) for x in jax.tree_util.tree_leaves(
                (t1.variables, t1.opt_state))]
            del t1
        # The trial is over and its Trainer dropped: nothing the slot
        # keeps (step, executables, init entry) reaches an array of it.
        gc.collect()
        assert leaves and all(ref() is None for ref in leaves), \
            "the warm slot keeps a finished trial's state alive"
        assert slot.get_init(ikey) is not None
        t2, _ = run_trial(1e-3)
        assert t2._slot is slot
        delta = {k: warm.counters()[k] - c0[k] for k in c0}
        assert delta["warm_hits"] == 1 and delta["warm_misses"] == 1

    def test_resumed_trial_reuses_slot_and_starts_bit_fresh(self):
        from maggy_tpu.core.executors.context import TrialContext

        t1, _ = run_trial(3e-3)
        ctx = TrialContext("resumed", "/nowhere/resumed", "/nowhere", {},
                           info={"resume_step": 3})
        assert ctx.resume_step == 3
        with warm.trial_scope(trial_id=ctx.trial_id, enabled=True):
            t2 = make_trainer(1e-3)
            t2.init(jax.random.key(0), EXAMPLE)
            # A resume/promotion trial restores a checkpoint over its
            # init: it reuses the compiled programs...
            assert t2._slot is t1._slot
            assert t2._slot.get_init(t2._init_ikey) is \
                t1._slot.get_init(t1._init_ikey)
            # ...and its pre-restore state is a bit-fresh init, whatever
            # the trial before it trained.
            t_cold = make_trainer(1e-3, warm_start=False)
            t_cold.init(jax.random.key(0), EXAMPLE)
            for a, b in zip(
                    jax.tree_util.tree_leaves((t2.variables, t2.opt_state)),
                    jax.tree_util.tree_leaves((t_cold.variables,
                                               t_cold.opt_state))):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_scope_disabled_forces_legacy(self):
        with warm.trial_scope(trial_id="t", enabled=False):
            t = make_trainer(1e-3)
        assert t._slot is None


def _init_programs(slot):
    """The jitted callables a slot's init entries hold."""
    out = []
    for entry in slot.inits.values():
        out.append(entry.init_jit)
        if entry.opt_init is not None:
            out.append(entry.opt_init[1])
    return out


_FAMILIES = {
    "adamw": (optax.adamw, {"weight_decay": 1e-4}),
    "sgd_momentum": (optax.sgd, {"momentum": 0.9}),
}


class TestOneInitSequence:
    """Cold, warm and vectorized trials run ONE init sequence: the entry's
    jitted initializer, then the family's jitted optimizer init with this
    trial's hyperparameters rebound."""

    def test_cold_warm_and_vmap_build_the_same_two_programs(self):
        from maggy_tpu.train import VmapTrainer

        cold = make_trainer(3e-3).init(jax.random.key(0), EXAMPLE)
        slot = cold._slot
        programs = _init_programs(slot)
        assert len(programs) == 2  # one initializer, one optimizer init
        warm_tr = make_trainer(1e-3).init(jax.random.key(0), EXAMPLE)
        vt = VmapTrainer(MODEL, optax.adam,
                         [{"learning_rate": 1e-3}, {"learning_rate": 5e-3}],
                         loss_fn, mesh1())
        vt.init(jax.random.key(0), EXAMPLE)
        assert warm_tr._slot is slot and vt._slot is slot
        after = _init_programs(slot)
        assert len(after) == 2
        assert all(a is b for a, b in zip(programs, after))
        # ...each traced and compiled once, by the cold trial.
        assert [fn._cache_size() for fn in after] == [1, 1]
        assert [fn.__name__ for fn in after] == ["init_variables",
                                                 "init_opt_state"]

    @pytest.mark.parametrize("family", sorted(_FAMILIES))
    def test_opt_state_is_an_eager_init_with_own_hyperparams(self, family):
        factory, statics = _FAMILIES[family]

        def trainer(lr):
            return make_trainer(lr, tx=swept_transform(
                factory, learning_rate=lr, **statics))

        cold = trainer(3e-3).init(jax.random.key(0), EXAMPLE)
        warm_tr = trainer(1e-4).init(jax.random.key(0), EXAMPLE)
        assert warm_tr._slot is cold._slot
        for tr, lr in ((cold, 3e-3), (warm_tr, 1e-4)):
            eager = tr.tx.init(tr.variables["params"])
            assert jax.tree_util.tree_structure(tr.opt_state) == \
                jax.tree_util.tree_structure(eager)
            for a, b in zip(jax.tree_util.tree_leaves(tr.opt_state),
                            jax.tree_util.tree_leaves(eager)):
                assert a.dtype == b.dtype
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            assert float(tr.opt_state.hyperparams["learning_rate"]) == \
                pytest.approx(lr)

    def test_jitted_opt_init_shards_moments_like_an_eager_one(self):
        """Under jit ``zeros_like`` no longer sees where the parameters
        live; the program has to say it, or an fsdp trial's moments land
        whole on one device. One step only: the AOT step's outputs do not come back in the
        shardings it was lowered for on a multi-device mesh (ROADMAP
        B-I 2), which is not this test's subject."""
        from maggy_tpu.models import BertConfig, BertEncoder

        mesh = make_mesh({"fsdp": 4}, devices=jax.devices()[:4])
        tr = Trainer(BertEncoder(BertConfig.tiny()),
                     swept_transform(optax.adam, learning_rate=1e-3),
                     loss_fn, mesh, strategy="fsdp")
        tokens = jnp.asarray(RNG.integers(0, 128, size=(8, 16)), jnp.int32)
        tr.init(jax.random.key(0), (tokens[:1],))
        sharded = 0
        for moment in (tr.opt_state.inner_state[0].mu,
                       tr.opt_state.inner_state[0].nu):
            for p, m in zip(jax.tree_util.tree_leaves(tr.variables["params"]),
                            jax.tree_util.tree_leaves(moment)):
                assert m.sharding.is_equivalent_to(p.sharding, p.ndim)
                sharded += not p.sharding.is_fully_replicated
        assert sharded, "no parameter was sharded; nothing was exercised"
        cold = [x.sharding for x in jax.tree_util.tree_leaves(tr.opt_state)]
        batch = tr.place_batch({"inputs": (tokens,),
                                "labels": jnp.asarray(Y[:8])})
        assert np.isfinite(float(tr.step(batch)))
        warm_tr = Trainer(tr.model,
                          swept_transform(optax.adam, learning_rate=5e-3),
                          loss_fn, mesh, strategy="fsdp")
        warm_tr.init(jax.random.key(0), (tokens[:1],))
        assert warm_tr._slot is tr._slot
        for sh, x in zip(cold, jax.tree_util.tree_leaves(warm_tr.opt_state)):
            assert x.sharding.is_equivalent_to(sh, x.ndim)

    def test_family_less_transform_inits_eagerly(self):
        tr = make_trainer(None, tx=optax.adam(1e-3))
        tr.init(jax.random.key(0), EXAMPLE)
        assert [fn.__name__ for fn in _init_programs(tr._slot)] == \
            ["init_variables"]

    def test_enabled_ignores_the_retired_environment_switch(self,
                                                            monkeypatch):
        monkeypatch.setenv("MAGGY_TPU_WARM_START", "0")
        assert warm.enabled()
        assert make_trainer(1e-3)._slot is not None
        with warm.trial_scope(trial_id="t", enabled=False):
            assert not warm.enabled()

    def test_sweep_starts_no_prebuild_thread(self, monkeypatch):
        import threading

        started = []
        real_start = threading.Thread.start

        def start(self):
            started.append(self.name)
            real_start(self)

        monkeypatch.setattr(threading.Thread, "start", start)
        for lr in (3e-3, 1e-3, 7e-4):
            with warm.trial_scope(trial_id=str(lr), enabled=True):
                run_trial(lr, steps=1)
        assert "reinit-prebuild" not in started
        assert not [t for t in threading.enumerate()
                    if t.name == "reinit-prebuild"]


class TestRunnerStatsCompile:
    def test_ms_fields_accumulate_and_ship_at_trial_end(self):
        stats = RunnerStats()
        stats.trial_start("t1")
        stats.note_compile(warm=False, init_ms=100.0)
        stats.note_compile(trace_ms=50.0, compile_ms=200.0)
        stats.note_compile(trace_ms=25.0)  # second shape: accumulates
        stats.on_broadcast(0)
        # The record ships at trial END so a compile AFTER the first
        # metric (a new batch shape mid-trial) still lands in it...
        assert not stats.snapshot_delta().get("compile_events")
        stats.note_compile(trace_ms=10.0, compile_ms=40.0)
        stats.trial_end("t1")
        events = stats.snapshot_delta()["compile_events"]
        assert len(events) == 1
        rec = events[0]
        assert rec["trial"] == "t1" and rec["warm"] is False
        assert rec["trace_ms"] == 85.0 and rec["compile_ms"] == 240.0
        assert rec["ttfm_ms"] >= 0
        # ...but the first-step residual charges only the phases
        # attributed BEFORE the first metric (the post-metric compile is
        # not part of ttfm).
        assert rec["first_step_ms"] == pytest.approx(
            max(0.0, rec["ttfm_ms"] - 375.0), abs=0.2)

    def test_trial_without_broadcast_ships_at_end(self):
        stats = RunnerStats()
        stats.trial_start("t1")
        stats.note_compile(warm=True, init_ms=5.0)
        stats.trial_end("t1")
        events = stats.snapshot_delta()["compile_events"]
        assert len(events) == 1
        assert "ttfm_ms" not in events[0]

    def test_requeue_restores_compile_events(self):
        stats = RunnerStats()
        stats.trial_start("t1")
        stats.note_compile(warm=True)
        stats.trial_end("t1")
        delta = stats.snapshot_delta()
        assert delta["compile_events"]
        stats.requeue_delta(delta)
        again = stats.snapshot_delta()
        assert again["compile_events"] == delta["compile_events"]
        assert not stats.snapshot_delta().get("compile_events")

    def test_counters_ship_as_fields(self):
        stats = RunnerStats()
        stats.note_counter("warm_hits")
        stats.note_counter("xla_cache_misses", 2)
        snap = stats.snapshot()
        assert snap["warm_hits"] == 1 and snap["xla_cache_misses"] == 2


class TestTelemetryMerge:
    def test_compiled_journaled_once_and_counted(self):
        from maggy_tpu.telemetry import Telemetry

        telem = Telemetry()
        rec = {"trial": "t1", "warm": True, "init_ms": 2.0, "ttfm_ms": 5.0}
        telem.record_runner_stats(0, {"compile_events": [rec]})
        # Re-delivery (requeued delta racing a successful ship): the
        # journal keeps ONE compiled event and the counter doesn't double.
        telem.record_runner_stats(0, {"compile_events": [rec]})
        events = [e for e in telem.events() if e.get("phase") == "compiled"]
        assert len(events) == 1
        assert events[0]["warm"] is True and events[0]["partition"] == 0
        assert telem.metrics.counter("compile.warm_hits").value == 1
        assert telem.metrics.counter("compile.warm_misses").value == 0

    def test_counter_fields_become_gauges(self):
        from maggy_tpu.telemetry import Telemetry

        telem = Telemetry()
        telem.record_runner_stats(1, {"warm_hits": 3, "xla_cache_hits": 2})
        snap = telem.metrics.snapshot()
        assert snap["gauges"]["runner.warm_hits.p1"] == 3
        assert snap["gauges"]["runner.xla_cache_hits.p1"] == 2


class TestStopFlushesPendingStats:
    """The LAST trial's compile record must not die with the runner: when
    GSTOP ends the work loop, the pending rstats delta (finalized at trial
    end, waiting on a heartbeat that will never fire) is flushed by
    Client.stop as one final idle-shaped beat."""

    def test_client_stop_ships_pending_compile_events(self):
        from maggy_tpu.core.rpc import Client, OptimizationServer
        from maggy_tpu.telemetry import Telemetry

        class _Driver:
            def enqueue(self, msg):
                pass

            def get_trial(self, trial_id):
                return None

        telem = Telemetry()
        server = OptimizationServer(num_executors=1)
        server.attach_driver(_Driver())
        server.telemetry = telem
        addr = server.start()
        try:
            client = Client(addr, 0, 0, 10.0, server.secret_hex)
            stats = RunnerStats()
            client.runner_stats = stats
            stats.trial_start("last_trial")
            stats.note_compile(warm=True, init_ms=3.0)
            stats.trial_end("last_trial")
            # No heartbeat thread ever ran: the record is still pending.
            client.stop()
        finally:
            server.stop()
        events = [e for e in telem.events() if e.get("phase") == "compiled"]
        assert len(events) == 1 and events[0]["trial"] == "last_trial"

    def test_stop_with_dead_server_does_not_raise(self):
        from maggy_tpu.core.rpc import Client, OptimizationServer

        server = OptimizationServer(num_executors=1)
        addr = server.start()
        client = Client(addr, 0, 0, 10.0, server.secret_hex)
        stats = RunnerStats()
        client.runner_stats = stats
        stats.trial_start("t")
        stats.note_compile(warm=False, init_ms=1.0)
        stats.trial_end("t")
        server.stop()
        client.stop()  # single attempt fails silently, no retry stall


def _compiled_ev(trial, t, warm_flag, ttfm, partition=0, **extra):
    return {"t": t, "ev": "trial", "trial": trial, "phase": "compiled",
            "partition": partition, "warm": warm_flag, "ttfm_ms": ttfm,
            **extra}


class TestDeriveCompileBlock:
    def test_block_shape(self):
        from maggy_tpu.telemetry import derive

        events = [
            _compiled_ev("a", 1.0, False, 4000.0, init_ms=1000.0,
                         trace_ms=300.0, compile_ms=2000.0,
                         first_step_ms=700.0),
            _compiled_ev("b", 2.0, True, 30.0, init_ms=2.0,
                         first_step_ms=28.0),
            _compiled_ev("c", 3.0, True, 40.0, init_ms=3.0,
                         first_step_ms=37.0),
            {"t": 4.0, "ev": "runner_stats", "partition": 0,
             "xla_cache_hits": 2, "xla_cache_misses": 1},
            {"t": 5.0, "ev": "runner_stats", "partition": 0,
             "xla_cache_hits": 5, "xla_cache_misses": 1},
            {"t": 5.0, "ev": "runner_stats", "partition": 1,
             "xla_cache_hits": 1, "xla_cache_misses": 4},
        ]
        comp = derive(events)["compile"]
        assert comp["warm_hits"] == 2 and comp["warm_misses"] == 1
        assert comp["warm_hit_rate"] == pytest.approx(2 / 3, abs=1e-3)
        assert comp["ttfm_cold"]["median_ms"] == 4000.0
        assert comp["ttfm_warm"]["median_ms"] == 40.0
        assert comp["compile_ms"]["n"] == 1
        # cumulative counters: LAST per partition, summed over partitions
        assert comp["cache"] == {"hits": 6, "misses": 5,
                                 "hit_rate": pytest.approx(6 / 11, abs=1e-3)}

    def test_counter_reset_banks_dead_attempt(self):
        """A replaced runner (chaos kill, pool respawn) restarts its
        cumulative counters at zero — the dead attempt's totals must stay
        in the sums, not be erased by the overwrite."""
        from maggy_tpu.telemetry import derive

        events = [
            {"t": 1.0, "ev": "runner_stats", "partition": 0,
             "xla_cache_hits": 7, "xla_cache_misses": 2},
            # partition 0's process dies; the respawn restarts at zero.
            {"t": 2.0, "ev": "runner_stats", "partition": 0,
             "xla_cache_hits": 1, "xla_cache_misses": 1},
            {"t": 3.0, "ev": "runner_stats", "partition": 0,
             "xla_cache_hits": 3, "xla_cache_misses": 1},
        ]
        comp = derive(events)["compile"]
        assert comp["cache"]["hits"] == 10  # 7 banked + 3 current
        assert comp["cache"]["misses"] == 3  # 2 banked + 1 current

    def test_empty_without_warm_data(self):
        from maggy_tpu.telemetry import derive

        assert derive([{"t": 1.0, "ev": "trial", "trial": "a",
                        "phase": "queued"}])["compile"] == {}


class TestTraceCompileSlices:
    def test_sub_slices_rendered(self):
        from maggy_tpu.telemetry.trace import build_trace, validate_trace

        events = [
            {"t": 10.0, "ev": "trial", "trial": "t1", "phase": "assigned",
             "partition": 0},
            {"t": 10.1, "ev": "trial", "trial": "t1", "phase": "running",
             "partition": 0},
            _compiled_ev("t1", 10.2, False, 400.0, init_ms=100.0,
                         trace_ms=50.0, compile_ms=200.0,
                         first_step_ms=50.0),
            {"t": 11.0, "ev": "trial", "trial": "t1", "phase": "finalized",
             "partition": 0},
        ]
        trace = build_trace(events)
        validate_trace(trace)
        comp = [e for e in trace["traceEvents"] if e.get("cat") == "compile"]
        names = [e["name"] for e in comp]
        assert names == ["init (cold)", "trace (cold)", "compile (cold)",
                         "first_step (cold)"]
        # sequential layout from the running edge (t=10.1, t0=10.0 ->
        # 100000 us), widths from the ms durations
        assert comp[0]["ts"] == 100000
        assert comp[0]["dur"] == 100000  # init_ms=100.0
        assert comp[1]["ts"] == comp[0]["ts"] + comp[0]["dur"]

    def test_warm_trial_renders_warm_tag(self):
        from maggy_tpu.telemetry.trace import build_trace

        events = [
            {"t": 1.0, "ev": "trial", "trial": "t", "phase": "assigned",
             "partition": 0},
            {"t": 1.1, "ev": "trial", "trial": "t", "phase": "running",
             "partition": 0},
            _compiled_ev("t", 1.2, True, 30.0, init_ms=2.0,
                         first_step_ms=28.0),
        ]
        comp = [e for e in build_trace(events)["traceEvents"]
                if e.get("cat") == "compile"]
        assert [e["name"] for e in comp] == ["init (warm)",
                                             "first_step (warm)"]


class TestEnableCompileCache:
    """util.enable_compile_cache: the cache directory is placed from
    outside (JAX_COMPILATION_CACHE_DIR) or is ONE fixed path inside the
    checkout; never a temp dir, and never silently unarmed."""

    @pytest.fixture
    def config_updates(self, monkeypatch):
        """Record jax.config.update calls instead of applying them (the
        test process's own compile cache must stay as it is)."""
        calls = {}
        monkeypatch.setattr(jax.config, "update",
                            lambda key, value: calls.__setitem__(key, value))
        monkeypatch.delenv("MAGGY_TPU_NO_COMPILE_CACHE", raising=False)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        return calls

    def test_env_placed_dir_is_not_set_in_code(self, monkeypatch, tmp_path,
                                               config_updates):
        from maggy_tpu import util

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        assert util.enable_compile_cache() == str(tmp_path / "c")
        # JAX took the directory from the environment at import; the
        # program only lowers the two persistence thresholds.
        assert config_updates == {
            "jax_persistent_cache_min_compile_time_secs": 0.0,
            "jax_persistent_cache_min_entry_size_bytes": 0}
        assert not (tmp_path / "c").exists()

    def test_unset_uses_the_fixed_path_inside_the_checkout(
            self, monkeypatch, tmp_path, config_updates):
        from maggy_tpu import util

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert util.COMPILE_CACHE_DIR == os.path.join(repo, ".jax_cache")
        monkeypatch.setattr(util, "COMPILE_CACHE_DIR", str(tmp_path / "fixed"))
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        first = util.enable_compile_cache()
        assert first == str(tmp_path / "fixed") and os.path.isdir(first)
        assert config_updates["jax_compilation_cache_dir"] == first
        assert config_updates[
            "jax_persistent_cache_min_compile_time_secs"] == 0.0
        assert util.enable_compile_cache() == first  # safe to re-call

    def test_cpu_default_off(self, monkeypatch, config_updates):
        from maggy_tpu import util

        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        # XLA:CPU AOT entries embed host ISA features; CPU runs default
        # the cache off unless the environment places a directory.
        assert util.enable_compile_cache() is None
        assert config_updates == {}

    def test_disabled_by_env(self, monkeypatch, tmp_path, config_updates):
        from maggy_tpu import util

        monkeypatch.setenv("MAGGY_TPU_NO_COMPILE_CACHE", "1")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert util.enable_compile_cache() is None
        assert config_updates == {}

    def test_unarmed_cache_says_so(self, monkeypatch, tmp_path,
                                   config_updates):
        from maggy_tpu import util

        blocker = tmp_path / "file"
        blocker.write_text("")
        monkeypatch.setattr(util, "COMPILE_CACHE_DIR", str(blocker / "sub"))
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        with pytest.warns(UserWarning, match="NOT armed"):
            assert util.enable_compile_cache() is None
        assert config_updates == {}


class TestMonitorRendering:
    def test_render_telem_compile_line(self):
        from maggy_tpu.monitor import render_telem

        snap = {"enabled": True, "metrics": {}, "journal": {},
                "spans": {"compile": {
                    "warm_hits": 5, "warm_misses": 1, "warm_hit_rate": 0.833,
                    "ttfm_warm": {"median_ms": 30.0, "p95_ms": 40.0, "n": 5},
                    "ttfm_cold": {"median_ms": 4000.0, "p95_ms": 4000.0,
                                  "n": 1},
                    "cache": {"hits": 3, "misses": 1, "hit_rate": 0.75}}}}
        out = render_telem(snap)
        assert "compile-once: 5 warm / 1 cold (hit rate 0.833)" in out
        assert "xla persistent cache: 3 hits / 1 misses" in out

    def test_no_compile_line_without_data(self):
        from maggy_tpu.monitor import render_telem

        out = render_telem({"enabled": True, "metrics": {}, "journal": {},
                            "spans": {}})
        assert "compile-once" not in out


# --------------------------------------------------------- end-to-end sweeps

@pytest.fixture
def local_env(tmp_path):
    from maggy_tpu.core.environment import EnvSing
    from maggy_tpu.core.environment.abstractenvironment import LocalEnv

    env = LocalEnv(base_dir=str(tmp_path / "exp"))
    EnvSing.set_instance(env)
    yield env
    EnvSing.reset()


def _exp_dir(env):
    base = env.base_dir
    return os.path.join(base, sorted(os.listdir(base))[-1])


def _save_tree(path, tree):
    leaves = jax.tree_util.tree_leaves(tree)
    np.savez(path, **{"l{}".format(i): np.asarray(x)
                      for i, x in enumerate(leaves)})


def _load_tree(path, like):
    leaves, treedef = jax.tree_util.tree_flatten(like)
    data = np.load(path)
    return jax.tree_util.tree_unflatten(
        treedef, [jnp.asarray(data["l{}".format(i)])
                  for i in range(len(leaves))])


def warm_sweep_train_fn(lr, reporter=None):
    """Repeat-shape trial: same model/mesh/shapes every time, lr swept
    through the optimizer family — one program for the whole sweep."""
    tr = make_trainer(lr)
    tr.init(jax.random.key(0), EXAMPLE)
    loss = None
    for i in range(3):
        batch = tr.place_batch({"inputs": (jnp.asarray(X),),
                                "labels": jnp.asarray(Y)})
        loss = tr.step(batch)
        if reporter is not None:
            reporter.broadcast(-loss, step=i)
    return {"metric": -float(loss)}


@pytest.mark.perf
@pytest.mark.timeout(180)
class TestWarmSweepSmoke:
    """Satellite CI gate: a 3-trial repeat-shape sweep must journal >= 1
    warm hit and warm ttfm strictly under cold ttfm (CPU-safe bounds: the
    cold trial pays a real XLA compile, a warm one only dispatch)."""

    def test_repeat_shape_sweep_journals_warm_hits(self, local_env):
        from maggy_tpu import OptimizationConfig, Searchspace, experiment
        from maggy_tpu.telemetry import JOURNAL_NAME, replay_journal

        config = OptimizationConfig(
            name="warm_smoke", num_trials=3, optimizer="randomsearch",
            searchspace=Searchspace(lr=("DOUBLE_LOG", [1e-4, 1e-2])),
            direction="max", num_workers=1, hb_interval=0.05,
            es_policy="none", seed=0,
        )
        experiment.lagom(warm_sweep_train_fn, config)
        derived = replay_journal(
            os.path.join(_exp_dir(local_env), JOURNAL_NAME))
        comp = derived["compile"]
        assert comp.get("warm_hits", 0) >= 1, comp
        assert comp["warm_misses"] == 1  # exactly the first trial compiled
        warm_ttfm = comp["ttfm_warm"]["median_ms"]
        cold_ttfm = comp["ttfm_cold"]["median_ms"]
        assert warm_ttfm < cold_ttfm, \
            "warm ttfm {} not under cold {}".format(warm_ttfm, cold_ttfm)

    def test_warm_start_false_journals_no_warm_hits(self, local_env):
        from maggy_tpu import OptimizationConfig, Searchspace, experiment
        from maggy_tpu.telemetry import JOURNAL_NAME, replay_journal

        config = OptimizationConfig(
            name="legacy_smoke", num_trials=2, optimizer="randomsearch",
            searchspace=Searchspace(lr=("DOUBLE_LOG", [1e-4, 1e-2])),
            direction="max", num_workers=1, hb_interval=0.05,
            es_policy="none", seed=0, warm_start=False,
        )
        experiment.lagom(warm_sweep_train_fn, config)
        derived = replay_journal(
            os.path.join(_exp_dir(local_env), JOURNAL_NAME))
        comp = derived["compile"]
        # Legacy mode still measures (cold ttfm/init attribution) but can
        # never hit a warm slot.
        assert comp.get("warm_hits", 0) == 0
        assert comp.get("warm_misses", 0) == 2


def asha_warm_train_fn(lr, budget=1, reporter=None, ctx=None):
    """ASHA trial on the warm path: a promoted trial RESTORES its parent's
    final params (checkpoint-forking), so warm-slot reuse must hand it
    bit-fresh buffers to restore into — any stale-params leak shifts its
    loss trajectory."""
    tr = make_trainer(lr)
    tr.init(jax.random.key(0), EXAMPLE)
    parent = ctx.parent_trial_id
    assert not hasattr(ctx, "needs_fresh_state")
    if parent is not None:
        tr.variables = _load_tree(
            os.path.join(ctx.exp_dir, parent, "final_params.npz"),
            tr.variables)
    steps = max(1, int(2 * (ctx.budget or 1)))
    losses = []
    for i in range(steps):
        batch = tr.place_batch({"inputs": (jnp.asarray(X),),
                                "labels": jnp.asarray(Y)})
        losses.append(float(tr.step(batch)))
        if reporter is not None:
            reporter.broadcast(-losses[-1], step=i)
    _save_tree(os.path.join(ctx.trial_dir, "final_params.npz"),
               tr.variables)
    with open(os.path.join(ctx.trial_dir, "warm_record.json"), "w") as f:
        json.dump({"lr": lr, "parent": parent, "steps": steps,
                   "losses": losses}, f)
    return {"metric": -losses[-1]}


def fork_warm_train_fn(lr, budget=1, reporter=None, ctx=None):
    """ASHA trial that checkpoints (orbax, through ``ctx``), so that the
    driver dispatches a promotion as a checkpoint FORK: the child lands on
    a warm slot with ``forked_from`` and ``resume_step`` set, inits like
    any trial and restores the staged checkpoint over that."""
    tr = make_trainer(lr)
    tr.init(jax.random.key(0), EXAMPLE)
    start = 0
    if ctx.resume_step is not None:
        live = {"variables": tr.variables, "opt_state": tr.opt_state}
        state = ctx.restore_checkpoint(jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding), live))
        tr.variables, tr.opt_state = state["variables"], state["opt_state"]
        start = ctx.resume_step + 1
    total = int(2 * (ctx.budget or 1))
    losses = []
    for step in range(start, total):
        batch = tr.place_batch({"inputs": (jnp.asarray(X),),
                                "labels": jnp.asarray(Y)})
        losses.append(float(tr.step(batch)))
        reporter.broadcast(-losses[-1], step=step)
    ctx.save_checkpoint(total - 1, {"variables": tr.variables,
                                    "opt_state": tr.opt_state})
    with open(os.path.join(ctx.trial_dir, "warm_record.json"), "w") as f:
        json.dump({"lr": lr, "start": start, "total": total,
                   "losses": losses, "forked_from": ctx.forked_from}, f)
    return {"metric": -losses[-1]}


@pytest.mark.chaos
@pytest.mark.timeout(240)
class TestWarmNeverLeaksAcrossDispatch:
    """Satellite: ASHA re-dispatch and preemption resume onto a WARM
    runner must produce step-for-step the same losses as a cold runner."""

    def _cold_losses(self, lr, steps, start_params_path=None):
        tr = make_trainer(lr, warm_start=False)
        tr.init(jax.random.key(0), EXAMPLE)
        if start_params_path is not None:
            tr.variables = _load_tree(start_params_path, tr.variables)
        losses = []
        for _ in range(steps):
            batch = tr.place_batch({"inputs": (jnp.asarray(X),),
                                    "labels": jnp.asarray(Y)})
            losses.append(float(tr.step(batch)))
        return losses

    def test_asha_promotions_on_warm_runner_match_cold(self, local_env):
        from maggy_tpu import OptimizationConfig, Searchspace, experiment
        from maggy_tpu.optimizers.asha import Asha

        config = OptimizationConfig(
            name="asha_warm", num_trials=6,
            optimizer=Asha(reduction_factor=2, resource_min=1,
                           resource_max=4),
            searchspace=Searchspace(lr=("DOUBLE", [1e-4, 5e-3])),
            direction="max", num_workers=1, hb_interval=0.05, seed=3,
            es_policy="none",
        )
        experiment.lagom(asha_warm_train_fn, config)
        exp_dir = _exp_dir(local_env)
        records = {}
        for path in glob.glob(os.path.join(exp_dir, "*",
                                           "warm_record.json")):
            with open(path) as f:
                records[os.path.basename(os.path.dirname(path))] = \
                    json.load(f)
        assert any(r["parent"] for r in records.values()), \
            "no promotion happened; the scenario was not exercised"
        for trial_id, rec in records.items():
            start = None
            if rec["parent"]:
                start = os.path.join(exp_dir, rec["parent"],
                                     "final_params.npz")
            cold = self._cold_losses(rec["lr"], rec["steps"],
                                     start_params_path=start)
            assert rec["losses"] == cold, \
                "trial {} diverged from cold run".format(trial_id)

    def test_forked_trials_on_warm_runner_match_cold(self, local_env):
        from maggy_tpu import OptimizationConfig, Searchspace, experiment
        from maggy_tpu.optimizers.asha import Asha

        config = OptimizationConfig(
            name="fork_warm", num_trials=6,
            optimizer=Asha(reduction_factor=2, resource_min=1,
                           resource_max=4),
            searchspace=Searchspace(lr=("DOUBLE", [1e-4, 5e-3])),
            direction="max", num_workers=1, hb_interval=0.05, seed=3,
            es_policy="none",
        )
        experiment.lagom(fork_warm_train_fn, config)
        records = {}
        for path in glob.glob(os.path.join(_exp_dir(local_env), "*",
                                           "warm_record.json")):
            with open(path) as f:
                records[os.path.basename(os.path.dirname(path))] = \
                    json.load(f)
        forked = [r for r in records.values() if r["forked_from"]]
        assert forked and all(r["start"] > 0 for r in forked), \
            "no promotion forked; the scenario was not exercised"
        for trial_id, rec in records.items():
            # A fork continues its parent (same lr, same seed, same
            # batch), so a cold from-scratch run of the whole budget is
            # the oracle for the steps the child ran.
            cold = self._cold_losses(rec["lr"], rec["total"])
            assert rec["losses"] == cold[rec["start"]:], \
                "trial {} diverged from cold run".format(trial_id)

    def test_preempt_resume_on_warm_runner_matches_cold(self, local_env,
                                                        tmp_path):
        from maggy_tpu.chaos.harness import preempt_plan, run_soak

        def preempt_warm_train_fn(lr, units, reporter=None, ctx=None):
            import time as _time

            adam_lr = max(float(lr), 1e-4)
            tr = make_trainer(adam_lr)
            tr.init(jax.random.key(0), EXAMPLE)
            start = 0
            if ctx is not None and ctx.resume_step is not None:
                # Full state (params AND optimizer moments): a resume must
                # continue the trajectory exactly, not restart adam.
                tr.variables, tr.opt_state = _load_tree(
                    os.path.join(ctx.trial_dir, "checkpoints",
                                 str(ctx.resume_step), "state.npz"),
                    (tr.variables, tr.opt_state))
                start = ctx.resume_step + 1
            for step in range(start, 6):
                batch = tr.place_batch({"inputs": (jnp.asarray(X),),
                                        "labels": jnp.asarray(Y)})
                loss = float(tr.step(batch))
                step_dir = os.path.join(ctx.trial_dir, "checkpoints",
                                        str(step))
                os.makedirs(step_dir, exist_ok=True)
                _save_tree(os.path.join(step_dir, "state.npz"),
                           (tr.variables, tr.opt_state))
                with open(os.path.join(ctx.trial_dir, "losses.jsonl"),
                          "a") as f:
                    f.write(json.dumps({"step": step, "loss": loss,
                                        "lr": adam_lr}) + "\n")
                _time.sleep(0.04)
                if reporter is not None:
                    reporter.broadcast(-loss, step=step)
            return {"metric": -loss}

        report = run_soak(plan=preempt_plan(seed=7, nth=2),
                          train_fn=preempt_warm_train_fn, num_trials=4,
                          workers=2, hb_interval=0.05,
                          hb_loss_timeout=30.0,
                          base_dir=str(tmp_path / "soak"))
        assert report["ok"], report["violations"]
        resumed = [p for p in report["preemptions"]
                   if p.get("outcome") == "preempted"
                   and p.get("checkpointed")]
        assert resumed, "no checkpointed preemption; scenario not exercised"
        exp_dir = os.path.dirname(report["journal"])
        for losses_path in glob.glob(os.path.join(exp_dir, "*",
                                                  "losses.jsonl")):
            by_step = {}
            lr = None
            with open(losses_path) as f:
                for line in f:
                    rec = json.loads(line)
                    assert rec["step"] not in by_step, \
                        "step {} re-ran after resume".format(rec["step"])
                    by_step[rec["step"]] = rec["loss"]
                    lr = rec["lr"]
            assert sorted(by_step) == list(range(6))
            cold = self._cold_losses(lr, 6)
            got = [by_step[i] for i in range(6)]
            assert got == cold, \
                "{} diverged from cold run".format(losses_path)

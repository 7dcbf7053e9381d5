"""Gang-scheduled multi-chip trials (maggy_tpu/gang.py).

Covers every layer of the gang path:

- declaration: GangSpec validation / normalization, the Searchspace GANG
  entry, and the config-level pool gating;
- placement: GangPlacer best-fit aligned contiguous blocks,
  fragmentation-stall accounting, dead-chip avoidance, release;
- replay: ``replay_pack`` pure math over a synthetic journal;
- driver: gang-sized requeues skipped-but-RETAINED by undersized
  runners through ``_pop_requeue`` and served INTACT to an assembled
  gang, never split;
- fleet: contiguous gang-block reservations routing block runners only
  to the owning experiment;
- telemetry: gang grouped lanes + pack markers in the Perfetto export;
- warm: the concurrent donating re-init prebuild (ROADMAP item 3
  follow-up);
- chaos: invariant 8 (whole, exactly-once gang revocation) as a pure
  journal check, plus the kill_gang_member plan validation;
- e2e: the mixed 1-chip ASHA + 4-chip fsdp sweep on the 8-fake-device
  CPU fleet with utilization and gang-vs-reference parity gates.
"""


import pytest

from maggy_tpu.config import OptimizationConfig
from maggy_tpu.core.driver.optimization_driver import OptimizationDriver
from maggy_tpu.core.environment import EnvSing
from maggy_tpu.core.environment.abstractenvironment import LocalEnv
from maggy_tpu.gang import (GANG_PARAM, GangPlacer, GangSpec,
                            config_declares_gangs, config_max_gang_chips,
                            replay_pack)
from maggy_tpu.searchspace import Searchspace
from maggy_tpu.trial import Trial


def _space():
    return Searchspace(lr=("DOUBLE", [0.0, 1.0]))


# ------------------------------------------------------------ declaration


class TestGangSpec:
    def test_default_mesh_from_strategy(self):
        spec = GangSpec(4, strategy="fsdp")
        assert spec.mesh == {"fsdp": 4}
        assert GangSpec(2, strategy="tp").mesh == {"model": 2}
        assert GangSpec(1).mesh == {"data": 1}

    def test_mesh_product_must_match_chips(self):
        with pytest.raises(ValueError, match="multiplies to"):
            GangSpec(4, mesh={"data": 2})
        GangSpec(4, mesh={"data": 2, "model": 2})  # ok

    def test_composite_strategy_needs_explicit_mesh(self):
        with pytest.raises(ValueError, match="explicit mesh"):
            GangSpec(4, strategy="fsdp_tp")
        spec = GangSpec(4, mesh={"fsdp": 2, "model": 2},
                        strategy="fsdp_tp")
        assert spec.chips == 4

    def test_invalid_strategy_rejected(self):
        with pytest.raises(Exception):
            GangSpec(2, strategy="warpdrive")

    def test_from_value_forms(self):
        spec = GangSpec(4, strategy="fsdp")
        assert GangSpec.from_value(spec) is spec
        assert GangSpec.from_value(spec.to_dict()) == spec
        assert GangSpec.from_value(2) == GangSpec(2)

    def test_config_helpers(self):
        config = OptimizationConfig(
            name="g", num_trials=4, optimizer="randomsearch",
            searchspace=_space(), direction="max", num_workers=4,
            chips_per_budget={1: GangSpec(1), 4: GangSpec(4, strategy="fsdp")})
        assert config_declares_gangs(config)
        assert config_max_gang_chips(config) == 4

    def test_int_shorthand_declares_gangs_on_thread_pool(self):
        """config.py: 'a bare int N is shorthand for GangSpec(N)' on the
        gang-scheduling pools — the two config helpers must agree, or a
        {budget: 4} sweep silently runs its 4-chip trials on one chip
        (and spuriously errors at driver init when num_workers < 4)."""
        config = OptimizationConfig(
            name="g", num_trials=4, optimizer="randomsearch",
            searchspace=_space(), direction="max", num_workers=4,
            chips_per_budget={1: 1, 4: 4})
        assert config_declares_gangs(config)
        assert config_max_gang_chips(config) == 4
        # On the elastic pool the same ints size respawnable pinned
        # runners — NOT gangs.
        elastic = OptimizationConfig(
            name="g", num_trials=4, optimizer="randomsearch",
            searchspace=_space(), direction="max", num_workers=4,
            pool="elastic", total_chips=4, chips_per_budget={1: 1, 4: 4})
        assert not config_declares_gangs(elastic)
        assert config_max_gang_chips(elastic) == 4

    def test_searchspace_gang_entry_normalizes_to_dicts(self):
        sp = Searchspace(lr=("DOUBLE", [0.0, 1.0]),
                         gang=("GANG", [GangSpec(1),
                                        GangSpec(4, strategy="fsdp")]))
        vals = sp.get("gang")
        assert all(isinstance(v, dict) for v in vals)
        assert vals[1]["chips"] == 4 and vals[1]["strategy"] == "fsdp"
        config = OptimizationConfig(
            name="g", num_trials=4, optimizer="randomsearch",
            searchspace=sp, direction="max", num_workers=4)
        assert config_declares_gangs(config)
        assert config_max_gang_chips(config) == 4

    def test_gang_entry_resolved_by_type_not_name(self, tmp_path):
        """A GANG entry may be named anything ("topology", ...): the
        driver resolves it by TYPE. A by-name lookup would pass config
        validation and then silently run every trial unsharded on one
        chip."""
        EnvSing.set_instance(LocalEnv(base_dir=str(tmp_path / "exp")))
        try:
            config = OptimizationConfig(
                name="g", num_trials=4, optimizer="randomsearch",
                searchspace=Searchspace(
                    lr=("DOUBLE", [0.0, 1.0]),
                    topology=("GANG", [GangSpec(4, strategy="fsdp")])),
                direction="max", num_workers=4, pool="thread",
                es_policy="none")
            drv = OptimizationDriver(config, "app", 0)
            try:
                assert drv._gang_mode and drv._gang_param == "topology"
                trial = Trial(
                    {"lr": 0.5,
                     "topology": GangSpec(4, strategy="fsdp").to_dict()})
                assert drv._gang_spec_for(trial) == \
                    GangSpec(4, strategy="fsdp")
            finally:
                drv.stop()
        finally:
            EnvSing.reset()

    def test_tpe_counts_gang_categories(self):
        """searchspace.py: GANG is 'index-encoded like CATEGORICAL for
        BO surrogates' — TPE's KDE cardinality must agree, or gang
        shapes beyond index 1 are unreachable through its categorical
        resampling."""
        from maggy_tpu.optimizers.bayes.tpe import TPE

        sp = Searchspace(
            lr=("DOUBLE", [0.0, 1.0]),
            gang=("GANG", [GangSpec(1), GangSpec(2),
                           GangSpec(4, strategy="fsdp")]))
        tpe = object.__new__(TPE)
        tpe.searchspace = sp
        assert TPE._n_categories(tpe) == [0, 3]
        assert sp.var_types() == ["c", "u"]

    def test_multiple_gang_entries_rejected(self):
        with pytest.raises(ValueError, match="at most one"):
            OptimizationConfig(
                name="g", num_trials=4, optimizer="randomsearch",
                searchspace=Searchspace(a=("GANG", [GangSpec(2)]),
                                        b=("GANG", [GangSpec(4)])),
                direction="max", num_workers=4, pool="thread")

    def test_gang_declarations_rejected_off_thread_pools(self):
        with pytest.raises(ValueError, match="gang"):
            OptimizationConfig(
                name="g", num_trials=4, optimizer="randomsearch",
                searchspace=_space(), direction="max", num_workers=4,
                pool="elastic", total_chips=4,
                chips_per_budget={4: GangSpec(4, strategy="fsdp")})
        with pytest.raises(ValueError, match="GANG"):
            OptimizationConfig(
                name="g", num_trials=4, optimizer="randomsearch",
                searchspace=Searchspace(gang=("GANG", [GangSpec(2)])),
                direction="max", num_workers=4, pool="process")


# -------------------------------------------------------------- placement


class TestGangPlacer:
    def test_aligned_best_fit(self):
        placer = GangPlacer(8)
        assert placer.reserve("a", 4, free=set(range(8))) == [0, 1, 2, 3]
        assert placer.reserve("b", 4, free={4, 5, 6, 7}) == [4, 5, 6, 7]
        assert placer.stalls == 0

    def test_best_fit_prefers_smallest_free_run(self):
        # Free runs: [0,1] and [4..7]; a 2-gang should take the small run
        # and preserve the big one for a later 4-gang.
        placer = GangPlacer(8)
        free = {0, 1, 4, 5, 6, 7}
        assert placer.reserve("two", 2, free=free) == [0, 1]
        assert placer.reserve("four", 4, free={4, 5, 6, 7}) == [4, 5, 6, 7]

    def test_free_unaligned_window_beats_stall(self):
        """Chips 0 and 7 busy, 1-6 free: the fully free UNALIGNED window
        [1-4] must assemble NOW — not stall behind chip 0 inside the
        aligned [0-3] while journaling a bogus fragmentation stall."""
        p = GangPlacer(8)
        assert p.reserve("t", 4, free={1, 2, 3, 4, 5, 6}) == [1, 2, 3, 4]
        assert p.stalls == 0

    def test_fragmentation_stall_counted_and_drains(self):
        placer = GangPlacer(8)
        # 4 chips free but scattered: no contiguous aligned window is
        # fully free -> stall, and the window with fewest busy chips is
        # reserved so it drains toward assembly.
        block = placer.reserve("g", 4, free={0, 2, 4, 6})
        assert block is not None and len(block) == 4
        assert placer.stalls == 1

    def test_avoid_excludes_dead_chips(self):
        placer = GangPlacer(8)
        block = placer.reserve("g", 4, free={1, 2, 3, 4, 5, 6, 7},
                               avoid={0})
        assert 0 not in block and len(block) == 4

    def test_reservations_sticky_and_disjoint(self):
        placer = GangPlacer(8)
        a = placer.reserve("a", 4, free=set(range(8)))
        assert placer.reserve("a", 4, free=set(range(8))) == a  # sticky
        b = placer.reserve("b", 4, free=set(range(8)))
        assert not set(a) & set(b)
        assert placer.owner_of(a[0]) == "a"
        placer.release("a")
        assert placer.owner_of(a[0]) is None

    def test_no_admissible_window(self):
        placer = GangPlacer(4)
        placer.reserve("a", 4, free=set(range(4)))
        assert placer.reserve("b", 4, free=set()) is None


# ------------------------------------------------------------------ replay


class TestReplayPack:
    def test_utilization_math(self):
        # 8 chips; one 4-chip gang busy 0..10, one 1-chip trial busy
        # 0..10: busy = 50 chip-seconds over an 8*10 window.
        events = [
            {"ev": "pack", "t": 0.0, "op": "init", "chips": 8},
            {"ev": "pack", "t": 0.0, "op": "reserve", "gang": "g1"},
            {"ev": "trial", "t": 1.0, "trial": "g1",
             "phase": "gang_assembled", "chips": [0, 1, 2, 3]},
            {"ev": "trial", "t": 0.0, "trial": "s1", "phase": "running"},
            {"ev": "trial", "t": 10.0, "trial": "s1", "phase": "finalized"},
            {"ev": "trial", "t": 10.0, "trial": "g1",
             "phase": "gang_released"},
        ]
        out = replay_pack(events)
        assert out["chips"] == 8
        assert out["gangs_assembled"] == 1
        assert out["busy_chip_seconds"] == pytest.approx(46.0)
        assert out["chip_seconds_utilization"] == pytest.approx(
            46.0 / 80.0, abs=1e-3)
        assert out["assembly_latency"]["n"] == 1
        assert out["assembly_latency"]["median_ms"] == pytest.approx(
            1000.0, abs=1.0)

    def test_stalls_and_open_gang(self):
        events = [
            {"ev": "pack", "t": 0.0, "op": "init", "chips": 4},
            {"ev": "pack", "t": 0.0, "op": "stall", "gang": "g"},
            {"ev": "pack", "t": 0.0, "op": "reserve", "gang": "g"},
            {"ev": "trial", "t": 1.0, "trial": "g",
             "phase": "gang_assembled", "chips": [0, 1]},
            # Journal ends mid-gang (crash): the open interval counts.
            {"ev": "trial", "t": 3.0, "trial": "x", "phase": "running"},
            {"ev": "trial", "t": 5.0, "trial": "x", "phase": "finalized"},
        ]
        out = replay_pack(events)
        assert out["fragmentation_stalls"] == 1
        assert out["busy_chip_seconds"] == pytest.approx(2 * 4.0 + 2.0)


# ------------------------------------------- driver retention + assembly


class TestGangRequeueRetention:
    """The issue's retention contract: an N-chip requeue is
    skipped-but-retained by undersized runners and served intact to a
    matching gang, never split."""

    @pytest.fixture
    def gdriver(self, tmp_path):
        EnvSing.set_instance(LocalEnv(base_dir=str(tmp_path / "exp")))
        config = OptimizationConfig(
            name="gang_requeue", num_trials=16, optimizer="randomsearch",
            searchspace=_space(), direction="max", num_workers=8, seed=2,
            es_policy="none", pool="thread",
            chips_per_budget={1: GangSpec(1),
                              4: GangSpec(4, strategy="fsdp")},
        )
        drv = OptimizationDriver(config, "app", 0)
        yield drv
        drv.stop()
        EnvSing.reset()

    def _orphan(self, drv, budget):
        trial = Trial({"lr": 0.5, "budget": budget})
        drv._trial_store[trial.trial_id] = trial
        drv._requeue.append(trial.trial_id)
        return trial

    def test_gang_requeue_retained_for_any_single_runner(self, gdriver):
        trial = self._orphan(gdriver, budget=4)
        # Neither a capacity-less thread runner nor ANY single capacity
        # may be served the gang trial — retained for assembly.
        assert gdriver._pop_requeue(None) is None
        assert gdriver._pop_requeue(4) is None
        assert trial.trial_id in gdriver._requeue

    def test_plain_requeue_still_served_across_gang_entry(self, gdriver):
        gang = self._orphan(gdriver, budget=4)
        small = self._orphan(gdriver, budget=1)
        assert gdriver._pop_requeue(None) is small
        assert gang.trial_id in gdriver._requeue

    def test_requeued_gang_trial_assembles_whole(self, gdriver):
        trial = self._orphan(gdriver, budget=4)
        res = gdriver.server.reservations
        for p in range(8):
            res.add({"partition_id": p})
        gdriver.controller.config_buffer = []  # no fresh suggestions
        gdriver._assign_next(0, None)
        # One idle tick from a single free runner is enough: the placer
        # reserves [0..3], every free runner whose chip falls inside is
        # conscripted, and the fully-held gang dispatches to the leader.
        assert res.get_assigned_trial(0) == trial.trial_id
        assert res.gang_members(trial.trial_id) == [0, 1, 2, 3]
        assert trial.trial_id not in gdriver._requeue
        info = trial.info_dict["gang"]
        assert info["chips"] == [0, 1, 2, 3] and info["leader"] == 0
        assert info["strategy"] == "fsdp" and info["mesh"] == {"fsdp": 4}

    def test_held_member_gets_no_single_chip_work(self, gdriver):
        trial = self._orphan(gdriver, budget=4)
        res = gdriver.server.reservations
        for p in range(8):
            res.add({"partition_id": p})
        gdriver.controller.config_buffer = []
        gdriver._assign_next(0, None)
        small = self._orphan(gdriver, budget=1)
        # Runner 1 is a held gang member: its idle tick must not take
        # the 1-chip trial away from the gang's mesh.
        gdriver._assign_next(1, None)
        assert res.get_assigned_trial(1) is None
        assert small.trial_id in gdriver._requeue
        # A free runner outside the block serves it.
        gdriver._assign_next(5, None)
        assert res.get_assigned_trial(5) == small.trial_id
        del trial

    def test_dead_busy_chip_inside_block_replans(self, gdriver):
        """A sticky reserved block containing a chip that died while
        BUSY (never gang-held) must be released and re-planned — not
        park the gang forever."""
        trial = self._orphan(gdriver, budget=4)
        res = gdriver.server.reservations
        for p in range(8):
            res.add({"partition_id": p})
        for p in (2, 5, 6, 7):
            res.assign_trial(p, "busy-{}".format(p))
        gdriver.controller.config_buffer = []
        gdriver._assign_next(0, None)
        # Free {0,1,3,4}: the [0..3] window has 1 busy chip vs 3 in
        # [4..7], so the stalled reservation picks [0..3] (chip 2 busy).
        assert gdriver._placer.block_of(trial.trial_id) == [0, 1, 2, 3]
        assert res.get_assigned_trial(0) is None  # not assembled yet
        # Chip 2's runner dies while still busy: the block can never
        # fully free. The next service pass must re-plan around it.
        res.mark_released(2)
        gdriver._assign_next(4, None)
        assert gdriver._placer.block_of(trial.trial_id) == [4, 5, 6, 7]
        # The old holds were dropped with the stale block.
        assert res.gang_of(0) is None and res.gang_of(1) is None
        # Chips 5-7 finish their 1-chip work and are conscripted.
        for p in (5, 6, 7):
            res.clear_trial_if(p, "busy-{}".format(p))
            gdriver._assign_next(p, None)
        assert res.get_assigned_trial(4) == trial.trial_id
        assert res.gang_members(trial.trial_id) == [4, 5, 6, 7]

    def test_revoked_leaders_inflight_final_dropped(self, gdriver):
        """Invariant 8's driver half: after a gang revocation the
        requeue is authoritative — a FINAL the (healthy, aborted) leader
        had in flight must be dropped, not finalize the revoked trial."""
        trial = self._orphan(gdriver, budget=4)
        res = gdriver.server.reservations
        for p in range(8):
            res.add({"partition_id": p})
        gdriver.controller.config_buffer = []
        gdriver._assign_next(0, None)
        assert res.get_assigned_trial(0) == trial.trial_id
        gdriver._gang_lost_msg_callback(
            {"trial_id": trial.trial_id, "partition_id": 1})
        assert trial.trial_id in gdriver._requeue
        # The leader finished its last step before the STOP landed:
        gdriver._final_msg_callback(
            {"type": "FINAL", "trial_id": trial.trial_id,
             "partition_id": 0, "value": 0.5})
        assert trial.final_metric is None               # not finalized
        assert trial.trial_id in gdriver._trial_store
        assert gdriver.result["num_trials"] == 0
        # The drop branch hands the reporting runner next work, which
        # immediately reassembles a fresh gang for the requeued trial —
        # re-running it, exactly what the revocation demands.
        assert trial.trial_id in gdriver._requeue or \
            len(res.gang_members(trial.trial_id)) == 4

    def test_orphaned_revocation_stop_cleared_by_raced_final(self, gdriver):
        """A reservation-level abort armed for the healthy leader must
        not outlive the leader's raced FINAL: dropped-as-stale still
        means the aborted computation ENDED, and a persisting stop would
        later abort a healthy re-run of the same trial on this runner."""
        trial = self._orphan(gdriver, budget=4)
        res = gdriver.server.reservations
        for p in range(8):
            res.add({"partition_id": p})
        gdriver.controller.config_buffer = []
        gdriver._assign_next(0, None)
        gdriver._gang_lost_msg_callback(
            {"trial_id": trial.trial_id, "partition_id": 1})
        with res.lock:
            assert res._table[0].get("stop_trial") == trial.trial_id
        # The leader's FINAL was already in flight; the drop branch must
        # also consume the now-moot stop.
        gdriver._final_msg_callback(
            {"type": "FINAL", "trial_id": trial.trial_id,
             "partition_id": 0, "value": 0.5})
        assert not res.pop_stop(0, trial.trial_id)

    def test_stale_epoch_final_dropped_after_same_leader_redispatch(
            self, gdriver):
        """The requeue-membership guard is blind when a revoked gang
        reassembles onto its OLD leader before the dead run's FINAL
        lands (waiting=False, assigned==trial): the run-epoch stamp must
        drop that FINAL — on real hardware its collective had a dead
        member."""
        trial = self._orphan(gdriver, budget=4)
        res = gdriver.server.reservations
        for p in range(8):
            res.add({"partition_id": p})
        gdriver.controller.config_buffer = []
        gdriver._assign_next(0, None)
        assert res.get_assigned_trial(0) == trial.trial_id
        gdriver._gang_lost_msg_callback(
            {"trial_id": trial.trial_id, "partition_id": 1})
        # Reassembly lands on the same block, same leader, BEFORE the
        # old run's FINAL arrives.
        gdriver._assign_next(0, None)
        assert res.get_assigned_trial(0) == trial.trial_id
        assert trial.run_epoch == 1
        gdriver._final_msg_callback(
            {"type": "FINAL", "trial_id": trial.trial_id,
             "partition_id": 0, "value": 0.5, "epoch": 0})
        assert trial.final_metric is None           # dead run dropped
        assert trial.trial_id in gdriver._trial_store
        assert res.get_assigned_trial(0) == trial.trial_id  # run 2 intact
        # The live run's FINAL (current epoch) finalizes normally.
        gdriver._final_msg_callback(
            {"type": "FINAL", "trial_id": trial.trial_id,
             "partition_id": 0, "value": 0.7, "epoch": 1})
        assert trial.final_metric == 0.7

    def test_release_returns_members_to_pool(self, gdriver):
        trial = self._orphan(gdriver, budget=4)
        res = gdriver.server.reservations
        for p in range(8):
            res.add({"partition_id": p})
        gdriver.controller.config_buffer = []
        gdriver._assign_next(0, None)
        assert res.gang_members(trial.trial_id)
        gdriver._release_gang(trial.trial_id, why="finalized")
        assert res.gang_members(trial.trial_id) == []
        assert res.gang_of(1) is None
        assert gdriver._placer.owner_of(0) is None


# ------------------------------------------------------------- fleet block


class TestFleetGangBlock:
    def _sched(self, size):
        from maggy_tpu.fleet.scheduler import FleetScheduler

        return FleetScheduler(size)

    def _entry(self, sched, name, **policy):
        from maggy_tpu.fleet.scheduler import FleetPolicy

        class _StubDriver:
            experiment_done = False
            exp_dir = None

        entry = sched.submit(name, FleetPolicy(**policy))
        sched.activate(entry, _StubDriver(), lambda pid: None, slots=16)
        return entry

    def test_block_is_aligned_sticky_and_disjoint(self, tmp_path):
        sched = self._sched(8)
        a = self._entry(sched, "a")
        b = self._entry(sched, "b")
        block_a = sched.request_gang(a, 4)
        assert block_a == [0, 1, 2, 3]
        assert sched.request_gang(a, 4) == block_a  # sticky
        block_b = sched.request_gang(b, 4)
        assert block_b == [4, 5, 6, 7]
        sched.release_gang(a)
        with sched._lock:
            assert sched._gang_owner_locked(0) is None
            assert sched._gang_owner_locked(4) is b

    def test_oversized_gang_rejected_not_clamped(self, tmp_path):
        """A gang larger than the fleet must fail loudly: silently
        clamping would latch a too-small block and park the gang's
        demand forever."""
        sched = self._sched(4)
        entry = self._entry(sched, "big")
        with pytest.raises(ValueError, match="never assemble"):
            sched.request_gang(entry, 8)

    def test_block_runner_binds_only_to_owner(self, tmp_path):
        sched = self._sched(4)
        owner = self._entry(sched, "owner")
        other = self._entry(sched, "other")
        sched.request_gang(owner, 2)
        # Runners 0/1 sit inside owner's block: they must bind to owner
        # even when fair share would hand them to "other".
        e0, _ = sched.next_binding(0, timeout=1)
        e1, _ = sched.next_binding(1, timeout=1)
        assert e0 is owner and e1 is owner
        e2, _ = sched.next_binding(2, timeout=1)
        assert e2 is other


# ---------------------------------------------------------------- telemetry


class TestTraceGangLanes:
    def test_gang_band_and_pack_markers(self):
        from maggy_tpu.telemetry.trace import build_trace, validate_trace

        events = [
            {"ev": "pack", "t": 0.0, "op": "init", "chips": 8},
            {"ev": "pack", "t": 0.1, "op": "reserve", "gang": "g1",
             "block": [0, 1, 2, 3]},
            {"ev": "trial", "t": 0.2, "trial": "g1", "phase": "assigned",
             "partition": 0},
            {"ev": "trial", "t": 0.2, "trial": "g1",
             "phase": "gang_assembled", "partition": 0,
             "members": [0, 1, 2, 3], "chips": [0, 1, 2, 3],
             "strategy": "fsdp"},
            {"ev": "trial", "t": 0.3, "trial": "g1", "phase": "running",
             "partition": 0},
            {"ev": "trial", "t": 0.9, "trial": "g1", "phase": "finalized",
             "partition": 0},
            {"ev": "trial", "t": 0.9, "trial": "g1",
             "phase": "gang_released", "partition": 0,
             "members": [0, 1, 2, 3]},
        ]
        trace = build_trace(events)
        assert validate_trace(trace) > 0
        evs = trace["traceEvents"]
        bands = [e for e in evs if e.get("cat") == "gang"
                 and e.get("ph") == "X"]
        # One identical band slice per member partition, on the gang lane.
        assert len(bands) == 4
        assert {b["pid"] for b in bands} == {1, 2, 3, 4}
        assert all(b["tid"] == 1 for b in bands)
        assert all(b["args"]["strategy"] == "fsdp" for b in bands)
        packs = [e for e in evs if e.get("cat") == "pack"]
        assert len(packs) == 2
        lanes = [e for e in evs if e.get("name") == "thread_name"
                 and e["args"]["name"] == "gang"]
        assert len(lanes) == 4

    def test_open_gang_closes_at_journal_end(self):
        from maggy_tpu.telemetry.trace import build_trace

        events = [
            {"ev": "trial", "t": 0.0, "trial": "g", "phase": "assigned",
             "partition": 0},
            {"ev": "trial", "t": 0.0, "trial": "g",
             "phase": "gang_assembled", "partition": 0, "members": [0, 1],
             "chips": [0, 1], "strategy": "dp"},
            {"ev": "trial", "t": 2.0, "trial": "x", "phase": "queued"},
        ]
        bands = [e for e in build_trace(events)["traceEvents"]
                 if e.get("cat") == "gang"]
        assert len(bands) == 2 and all(b["dur"] >= 1 for b in bands)


# --------------------------------------------------------------- chaos unit


class TestGangChaosInvariant:
    def _events(self, requeues=1, released=True, reassembled=True,
                finalized=True):
        evs = [
            {"ev": "trial", "t": 0.0, "trial": "g", "phase": "queued"},
            {"ev": "trial", "t": 1.0, "trial": "g",
             "phase": "gang_assembled", "partition": 0,
             "members": [0, 1, 2, 3]},
            {"ev": "chaos", "t": 1.1, "kind": "kill_gang_member",
             "trial": "g", "partition": 1, "leader": 0},
        ]
        if released:
            evs.append({"ev": "trial", "t": 1.5, "trial": "g",
                        "phase": "gang_released", "members": [0, 1, 2, 3]})
        for i in range(requeues):
            evs.append({"ev": "trial", "t": 1.6 + i * 0.1, "trial": "g",
                        "phase": "requeued", "partition": 1,
                        "reason": "gang_member_lost"})
        if reassembled:
            evs.append({"ev": "trial", "t": 2.0, "trial": "g",
                        "phase": "gang_assembled", "partition": 2,
                        "members": [2, 3, 4, 5]})
        if finalized:
            evs.append({"ev": "trial", "t": 3.0, "trial": "g",
                        "phase": "finalized", "partition": 2})
        evs.append({"ev": "experiment", "t": 4.0, "phase": "finalized"})
        return evs

    def _check(self, events):
        from maggy_tpu.chaos.harness import check_invariants

        return check_invariants(events, requeue_bound_s=10.0,
                                stall_flag_bound_s=None)

    def test_clean_revocation_passes(self):
        report = self._check(self._events())
        assert report["ok"], report["violations"]
        assert report["gang_revocations"][0]["outcome"] == "revoked"
        assert report["gang_revocations"][0]["requeues"] == 1

    def test_over_requeue_flagged(self):
        report = self._check(self._events(requeues=2))
        assert any("over-requeue" in v for v in report["violations"])

    def test_missing_release_flagged(self):
        report = self._check(self._events(released=False))
        assert any("not released" in v for v in report["violations"])

    def test_missing_reassembly_flagged(self):
        report = self._check(self._events(reassembled=False))
        assert any("never reassembled" in v for v in report["violations"])

    def test_race_lost_to_final_is_benign(self):
        evs = [
            {"ev": "trial", "t": 0.0, "trial": "g", "phase": "queued"},
            {"ev": "trial", "t": 1.0, "trial": "g",
             "phase": "gang_assembled", "partition": 0,
             "members": [0, 1]},
            {"ev": "chaos", "t": 1.1, "kind": "kill_gang_member",
             "trial": "g", "partition": 1, "leader": 0},
            {"ev": "trial", "t": 1.2, "trial": "g", "phase": "finalized",
             "partition": 0},
            {"ev": "trial", "t": 1.2, "trial": "g",
             "phase": "gang_released", "members": [0, 1]},
            {"ev": "experiment", "t": 2.0, "phase": "finalized"},
        ]
        report = self._check(evs)
        assert report["ok"], report["violations"]
        assert report["gang_revocations"][0]["outcome"] == \
            "completed_before_detection"

    def test_plan_validation(self):
        from maggy_tpu.chaos.plan import FaultSpec

        FaultSpec("kill_gang_member",
                  trigger={"on_phase": "gang_assembled"})  # ok
        with pytest.raises(ValueError, match="runner fault"):
            FaultSpec("kill_gang_member", trigger={"nth": 1})


# ----------------------------------------------------------------- e2e soak


class TestTopologyGuards:
    """runner ≈ chip by index: both soaks must fail LOUDLY when the
    initialized backend has fewer devices than the placer spans —
    otherwise every gang trial dies on a missing chip and (in the chaos
    soak) the injected kill always 'loses the race', verifying
    nothing."""

    def test_pack_soak_guards_device_count(self):
        import jax

        from maggy_tpu.gang import run_pack_soak

        with pytest.raises(RuntimeError, match="devices"):
            run_pack_soak(workers=2 * jax.device_count())

    def test_gang_chaos_soak_guards_device_count(self):
        import jax

        from maggy_tpu.chaos.harness import run_gang_soak

        with pytest.raises(RuntimeError, match="devices"):
            run_gang_soak(workers=2 * jax.device_count())


@pytest.mark.timeout(300)
def test_mixed_sweep_pack_soak(tmp_path):
    """The acceptance scenario: a mixed 1-chip ASHA + 4-chip fsdp sweep
    completes on the 8-fake-device CPU fleet with chip-seconds
    utilization >= 0.7, no scheduling deadlock, and every gang trial's
    final loss matching the single-process sharded reference."""
    from maggy_tpu.gang import run_pack_soak

    report = run_pack_soak(base_dir=str(tmp_path / "pack"))
    assert report["ok"], report["violations"]
    assert report["pack"]["gangs_assembled"] >= 1
    assert report["pack"]["chip_seconds_utilization"] >= 0.7
    assert report["parity"]
    for p in report["parity"]:
        assert p["abs_err"] <= 1e-4


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.timeout(600)
def test_gang_chaos_soak(tmp_path):
    """Invariant 8 end to end: one member of the first assembled gang
    killed mid-trial; the whole lease is revoked and the trial requeues
    exactly once, under the lock-order witness."""
    from maggy_tpu.chaos.harness import run_gang_soak

    report = run_gang_soak(base_dir=str(tmp_path / "gangchaos"),
                           lock_witness=True)
    assert report["ok"], report["violations"]
    revoked = [r for r in report["gang_revocations"]
               if r["outcome"] == "revoked"]
    assert revoked and revoked[0]["requeues"] == 1
    assert not report["witness"]["violations"]

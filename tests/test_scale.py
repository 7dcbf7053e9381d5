"""Service-scale control plane: per-tenant dispatch pools, batched
heartbeats, indexed fleet scheduling, admission shedding, and the
bounded spool scan.

The fast lane (``scale`` marker, tier-1) stresses the SharedServer with
hundreds of simulated tenants, pins the connection-bookkeeping and
backpressure behavior, and unit-tests the scheduler indexes. The
original 64-runner single-driver soak stays ``slow``.
"""

import json
import socket
import threading
import time

import pytest

from maggy_tpu import OptimizationConfig, Searchspace, experiment
from maggy_tpu.core.environment import EnvSing
from maggy_tpu.core.environment.abstractenvironment import LocalEnv
from maggy_tpu.core.rpc import (MessageSocket, OptimizationServer, Server,
                                SharedServer)
from maggy_tpu.fleet.scheduler import (Fleet, FleetPolicy, FleetSaturated,
                                       FleetScheduler)


@pytest.fixture(autouse=True)
def local_env(tmp_path):
    env = LocalEnv(base_dir=str(tmp_path / "exp"))
    EnvSing.set_instance(env)
    yield env
    EnvSing.reset()


def train_trivial(lr, units, reporter=None):
    if reporter is not None:
        reporter.broadcast(lr, step=0)
    return {"metric": lr}


def _wait_until(predicate, timeout=10.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return predicate()


def _send_frame(sock, msg, secret):
    MessageSocket.send_msg(sock, msg, secret)
    return MessageSocket.recv_msg(sock, secret)


# ------------------------------------------------ shared-server stress


@pytest.mark.scale
class TestSharedServerStress:
    """Tier-1 stress: ~200 simulated tenants route frames through ONE
    SharedServer concurrently — per-secret routing must be exact, no
    frame may cross tenants, and each connection's frames must be
    handled (and replied) in order by its tenant's dispatch pool."""

    TENANTS = 200
    FRAMES = 3
    DRIVERS = 16

    @pytest.mark.timeout(120)
    def test_200_tenants_route_concurrently_in_order(self):
        shared = SharedServer()
        servers = []
        received = []  # per-tenant list of seqs, appended by the handler
        try:
            for i in range(self.TENANTS):
                srv = Server(num_executors=1,
                             secret="{:032x}".format(i + 1))
                log = []
                received.append(log)

                def mark(msg, tenant=i, log=log):
                    log.append(msg["seq"])
                    return {"type": "MARK", "tenant": tenant,
                            "seq": msg["seq"]}

                srv._handlers["MARK"] = mark
                servers.append(srv)
                addr = shared.attach(srv)
            errors = []

            def drive(tenant_ids):
                for tid in tenant_ids:
                    try:
                        sock = socket.create_connection(addr, timeout=30)
                        sock.settimeout(30)
                        try:
                            for seq in range(self.FRAMES):
                                resp = _send_frame(
                                    sock, {"type": "MARK", "seq": seq},
                                    servers[tid].secret)
                                if resp.get("tenant") != tid \
                                        or resp.get("seq") != seq:
                                    errors.append(
                                        (tid, seq, resp))
                        finally:
                            sock.close()
                    except Exception as e:  # noqa: BLE001
                        errors.append((tid, repr(e)))

            threads = [
                threading.Thread(
                    target=drive,
                    args=(range(k, self.TENANTS, self.DRIVERS),))
                for k in range(self.DRIVERS)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=90)
            assert not errors, errors[:10]
            # Zero cross-tenant delivery + per-connection pool ordering:
            # each tenant's handler saw exactly its own frames, in the
            # order its connection sent them.
            for i, log in enumerate(received):
                assert log == list(range(self.FRAMES)), (i, log)
            # Connection bookkeeping: every disconnect pruned its
            # per-connection state (the churn-leak regression).
            assert _wait_until(
                lambda: not shared._buffers and not shared._conn_server)
        finally:
            shared.stop()


@pytest.mark.scale
class TestSharedServerBookkeeping:
    """Disconnect paths must prune _buffers/_conn_server — including the
    sever-mid-frame path, where a drop used to be followed by further
    frames from the stale local buffer re-binding the closed socket."""

    def _shared_with_tenant(self):
        shared = SharedServer()
        srv = Server(num_executors=1, secret="ab" * 16)
        srv._handlers["MARK"] = lambda msg: {"type": "MARK",
                                             "seq": msg["seq"]}
        addr = shared.attach(srv)
        return shared, srv, addr

    def test_clean_disconnect_prunes_state(self):
        shared, srv, addr = self._shared_with_tenant()
        try:
            sock = socket.create_connection(addr, timeout=10)
            sock.settimeout(10)
            assert _send_frame(sock, {"type": "MARK", "seq": 0},
                               srv.secret)["seq"] == 0
            sock.close()
            assert _wait_until(
                lambda: not shared._buffers and not shared._conn_server)
        finally:
            shared.stop()

    def test_bad_mac_mid_buffer_does_not_rebind(self):
        """One send carrying [good][bad-MAC][good]: the bad frame drops
        the connection, and the trailing good frame must NOT be
        dispatched or re-bind the closed socket into _conn_server."""
        import msgpack as _msgpack
        import struct as _struct

        shared, srv, addr = self._shared_with_tenant()
        try:
            handled = []
            orig = srv._handlers["MARK"]
            srv._handlers["MARK"] = lambda msg: (handled.append(msg["seq"])
                                                 or orig(msg))
            payload = _msgpack.packb({"type": "MARK", "seq": 1},
                                     use_bin_type=True)
            bad = _struct.pack(">I", len(payload)) + b"\x00" * 32 + payload
            sock = socket.create_connection(addr, timeout=10)
            sock.settimeout(10)
            from maggy_tpu.core.rpc import _LEN, _sign
            good = _msgpack.packb({"type": "MARK", "seq": 0},
                                  use_bin_type=True)
            frame0 = _LEN.pack(len(good)) + _sign(srv.secret, good) + good
            good2 = _msgpack.packb({"type": "MARK", "seq": 2},
                                   use_bin_type=True)
            frame2 = _LEN.pack(len(good2)) + _sign(srv.secret, good2) + good2
            sock.sendall(frame0 + bad + frame2)
            # The bad frame kills the connection. The first frame may or
            # may not get its reply out first (its handler runs on the
            # tenant pool, racing the loop's drop — the client retry
            # path covers the loss); the frame AFTER the bad one must
            # never be handled or re-bind the closed socket.
            try:
                assert MessageSocket.recv_msg(sock, srv.secret)["seq"] == 0
            except ConnectionError:
                pass
            assert _wait_until(
                lambda: not shared._buffers and not shared._conn_server)
            assert 2 not in handled
            assert handled in ([], [0])
            sock.close()
        finally:
            shared.stop()

    def test_oversized_frame_drops_and_prunes(self):
        import struct as _struct

        shared, srv, addr = self._shared_with_tenant()
        try:
            sock = socket.create_connection(addr, timeout=10)
            sock.sendall(_struct.pack(">I", 1 << 30) + b"\x00" * 32)
            assert _wait_until(
                lambda: not shared._buffers and not shared._conn_server)
            sock.close()
        finally:
            shared.stop()


# --------------------------------------------- dispatch-pool isolation


@pytest.mark.scale
class TestDispatchPoolIsolation:
    """The head-of-line fix at the unit level: a tenant whose handler
    sleeps must not delay another tenant's replies (pool ON), and must
    delay them with the legacy shared-loop dispatch (pool OFF) — the
    same A/B bench.py --scale runs end to end."""

    def _two_tenants(self, dispatch_pool):
        shared = SharedServer(dispatch_pool=dispatch_pool)
        slow = Server(num_executors=1, secret="aa" * 16)
        slow._handlers["SLEEP"] = lambda msg: (time.sleep(0.4)
                                               or {"type": "OK"})
        fast = Server(num_executors=1, secret="bb" * 16)
        addr = shared.attach(slow)
        shared.attach(fast)
        return shared, slow, fast, addr

    @pytest.mark.timeout(60)
    def test_pool_isolates_fast_tenant(self):
        shared, slow, fast, addr = self._two_tenants(True)
        try:
            s_sock = socket.create_connection(addr, timeout=10)
            f_sock = socket.create_connection(addr, timeout=10)
            f_sock.settimeout(10)
            MessageSocket.send_msg(s_sock, {"type": "SLEEP"}, slow.secret)
            time.sleep(0.05)  # the slow handler is now mid-sleep
            t0 = time.monotonic()
            assert _send_frame(f_sock, {"type": "QUERY"},
                               fast.secret)["done"] is False
            assert time.monotonic() - t0 < 0.2
            MessageSocket.recv_msg(s_sock, slow.secret)
            s_sock.close()
            f_sock.close()
        finally:
            shared.stop()

    @pytest.mark.timeout(60)
    def test_legacy_loop_dispatch_blocks_fast_tenant(self):
        shared, slow, fast, addr = self._two_tenants(False)
        try:
            s_sock = socket.create_connection(addr, timeout=10)
            f_sock = socket.create_connection(addr, timeout=10)
            f_sock.settimeout(10)
            MessageSocket.send_msg(s_sock, {"type": "SLEEP"}, slow.secret)
            time.sleep(0.05)
            t0 = time.monotonic()
            assert _send_frame(f_sock, {"type": "QUERY"},
                               fast.secret)["done"] is False
            assert time.monotonic() - t0 > 0.2
            MessageSocket.recv_msg(s_sock, slow.secret)
            s_sock.close()
            f_sock.close()
        finally:
            shared.stop()

    @pytest.mark.timeout(60)
    def test_backpressure_sheds_at_queue_bound(self):
        from maggy_tpu.telemetry import Telemetry

        shared = SharedServer(dispatch_pool=True, tenant_queue_depth=1)
        srv = Server(num_executors=1, secret="cc" * 16)
        srv.telemetry = Telemetry(enabled=True)
        release = threading.Event()
        srv._handlers["HOLD"] = lambda msg: (release.wait(timeout=20)
                                             or {"type": "OK"})
        addr = shared.attach(srv)
        try:
            sock = socket.create_connection(addr, timeout=10)
            # One frame occupies the worker, one fills the depth-1
            # queue, further frames overflow -> shed + drop.
            for _ in range(8):
                try:
                    MessageSocket.send_msg(sock, {"type": "HOLD"},
                                           srv.secret)
                except OSError:
                    break
                time.sleep(0.02)
            counter = srv.telemetry.metrics.counter(
                "rpc.tenant.backpressure_drops")
            assert _wait_until(lambda: counter.value >= 1, timeout=10)
            sheds = [e for e in srv.telemetry.events()
                     if e.get("ev") == "shed" and e.get("scope") == "rpc"]
            assert sheds and sheds[0]["queue_depth"] == 1
            assert _wait_until(
                lambda: not shared._buffers and not shared._conn_server)
            release.set()
            sock.close()
        finally:
            release.set()
            shared.stop()


# ---------------------------------------------------- batched heartbeats


@pytest.mark.scale
class TestBatchedHeartbeats:
    def test_queue_beat_coalesces_same_trial_and_bounds(self):
        from maggy_tpu import constants
        from maggy_tpu.core.rpc import Client

        pending = []
        Client._queue_beat(pending, {
            "type": "METRIC", "trial_id": "t1", "value": 1.0, "step": 0,
            "logs": ["a"], "span": "s1", "rstats": {"x": 1}})
        Client._queue_beat(pending, {
            "type": "METRIC", "trial_id": "t1", "value": 2.0, "step": 1,
            "logs": ["b"], "span": "s1"})
        # Same trial: coalesced to the freshest sample, logs concatenated,
        # rstats stripped (it requeues through the runner-stats buffer).
        assert len(pending) == 1
        assert pending[0]["value"] == 2.0 and pending[0]["step"] == 1
        assert pending[0]["logs"] == ["a", "b"]
        assert "rstats" not in pending[0]
        Client._queue_beat(pending, {
            "type": "METRIC", "trial_id": "t2", "value": 3.0, "step": 0,
            "logs": [], "span": "s2"})
        assert [b["trial_id"] for b in pending] == ["t1", "t2"]
        # Bound: oldest beats drop first.
        for i in range(constants.CLIENT_MAX_PENDING_BEATS + 4):
            Client._queue_beat(pending, {
                "type": "METRIC", "trial_id": "t{}".format(3 + i),
                "value": float(i), "step": 0, "logs": [], "span": None})
        assert len(pending) == constants.CLIENT_MAX_PENDING_BEATS

    def test_queue_beat_bounds_coalesced_logs(self):
        """A chatty trial over a long outage must not grow ONE banked
        beat without bound (a >MAX_FRAME batch could never ship)."""
        from maggy_tpu import constants
        from maggy_tpu.core.rpc import Client

        pending = []
        for i in range(constants.CLIENT_MAX_PENDING_LOG_LINES // 10 + 5):
            Client._queue_beat(pending, {
                "type": "METRIC", "trial_id": "t1", "value": float(i),
                "step": i, "logs": ["line-{}-{}".format(i, j)
                                    for j in range(10)], "span": None})
        assert len(pending) == 1
        logs = pending[0]["logs"]
        assert len(logs) == constants.CLIENT_MAX_PENDING_LOG_LINES
        # Newest lines survive, oldest drop.
        assert logs[-1].startswith("line-{}".format(
            constants.CLIENT_MAX_PENDING_LOG_LINES // 10 + 4))

    def test_batch_verb_replays_beats_and_replies_for_newest(self):
        from tests.test_rpc import FakeDriver

        class StopTrial:
            def __init__(self):
                self.lock = threading.Lock()

            def get_early_stop(self):
                return True

            def get_preempt(self):
                return False

        driver = FakeDriver()
        driver.trials["t_new"] = StopTrial()
        server = OptimizationServer(num_executors=1)
        server.attach_driver(driver)
        addr = server.start()
        try:
            sock = socket.create_connection(addr, timeout=10)
            sock.settimeout(10)
            resp = _send_frame(sock, {
                "type": "BATCH", "partition_id": 0, "task_attempt": 0,
                "beats": [
                    {"type": "METRIC", "trial_id": "t_old", "value": 1.0,
                     "step": 5, "logs": ["old"], "span": None},
                    {"type": "METRIC", "trial_id": "t_new", "value": 2.0,
                     "step": 0, "logs": [], "span": None},
                ]}, server.secret)
            # Every beat reached the driver (stale metric history is
            # data, not noise) ...
            metrics = [m for m in driver.messages
                       if m.get("type") == "METRIC"]
            assert [m["trial_id"] for m in metrics] == ["t_old", "t_new"]
            assert all(m["partition_id"] == 0 for m in metrics)
            # ... and the reply is the NEWEST beat's (its trial is
            # early-stop flagged -> STOP).
            assert resp["type"] == "STOP"
            sock.close()
        finally:
            server.stop()


# ------------------------------------------------- scheduler indexes


@pytest.mark.scale
class TestSchedulerIndexedAdmission:
    def test_admission_pops_priority_then_submit_order(self):
        sched = FleetScheduler(fleet_size=2, max_active=1)
        first = sched.submit("first", FleetPolicy(priority="normal"))
        sched.submit("low", FleetPolicy(priority="low"))
        sched.submit("high", FleetPolicy(priority="high"))
        sched.submit("normal2", FleetPolicy(priority="normal"))
        assert first.state == "active"
        sched.finish(first)
        assert sched._entries["high"].state == "active"
        sched.finish(sched._entries["high"])
        assert sched._entries["normal2"].state == "active"
        sched.finish(sched._entries["normal2"])
        assert sched._entries["low"].state == "active"

    def test_max_queued_sheds_with_journal_and_counter(self):
        from maggy_tpu.telemetry import Telemetry

        telem = Telemetry(enabled=True)
        sched = FleetScheduler(fleet_size=1, max_active=1, max_queued=2,
                               telemetry=telem)
        sched.submit("a", FleetPolicy())  # admitted
        sched.submit("b", FleetPolicy())  # queued 1
        sched.submit("c", FleetPolicy())  # queued 2
        assert sched.saturated()
        with pytest.raises(FleetSaturated):
            sched.submit("d", FleetPolicy())
        snap = sched.snapshot()
        assert snap["shed"] == 1 and snap["queue_depth"] == 2
        sheds = [e for e in telem.events() if e.get("ev") == "shed"]
        assert sheds and sheds[0]["exp"] == "d" \
            and sheds[0]["scope"] == "admission"
        assert telem.metrics.counter("fleet.shed_total").value == 1
        # Draining the queue un-saturates admission.
        sched.finish(sched._entries["a"])
        assert not sched.saturated()
        sched.submit("d", FleetPolicy())

    def test_wait_admitted_blocks_until_slot_frees(self):
        sched = FleetScheduler(fleet_size=1, max_active=1)
        a = sched.submit("a", FleetPolicy())
        b = sched.submit("b", FleetPolicy())
        assert sched.wait_admitted(a, timeout=1.0)
        assert not sched.wait_admitted(b, timeout=0.2)
        sched.finish(a)
        assert sched.wait_admitted(b, timeout=5.0)
        # A stopped fleet never admits: wait_admitted returns False
        # instead of parking the submission thread forever.
        sched.stop()
        c_entry = sched.submit("c", FleetPolicy(priority="low"))
        assert c_entry.state == "queued"
        assert not sched.wait_admitted(c_entry, timeout=1.0)

    def test_targets_cache_invalidated_on_admission(self):
        class DoneLess:
            experiment_done = False

        sched = FleetScheduler(fleet_size=4)
        a = sched.submit("a", FleetPolicy())
        b = sched.submit("b", FleetPolicy())
        sched.activate(a, DoneLess(), lambda pid: None, slots=4)
        sched.activate(b, DoneLess(), lambda pid: None, slots=4)
        with sched._lock:
            assert sched._targets_locked() == {"a": 2, "b": 2}
        c = sched.submit("c", FleetPolicy(weight=2.0))
        sched.activate(c, DoneLess(), lambda pid: None, slots=4)
        # No TTL wait: activation invalidated the cache.
        with sched._lock:
            targets = sched._targets_locked()
        assert targets["c"] == 2 and targets["a"] == 1 and targets["b"] == 1

    def test_sweeps_iterate_only_admitted_entries(self):
        """500 queued tenants must not appear in the binding sweep's
        candidate set (the O(experiments) -> O(active) fix)."""
        sched = FleetScheduler(fleet_size=2, max_active=3)
        for i in range(500):
            sched.submit("e{:03d}".format(i), FleetPolicy())
        with sched._lock:
            assert len(sched._active) == 3
            assert sched._queued_count == 497
            targets = sched._compute_targets_locked()
        assert len(targets) == 0  # none activated yet -> not ready()
        assert sched.snapshot()["queue_depth"] == 497


@pytest.mark.scale
class TestDeferredActivation:
    @pytest.mark.timeout(60)
    def test_queued_tenant_builds_no_driver(self, tmp_path):
        base = str(tmp_path / "runs")
        started = threading.Event()
        release = threading.Event()

        def blocker(lr, units, reporter=None):
            started.set()
            release.wait(timeout=30)
            return {"metric": lr}

        def cfg(name):
            return OptimizationConfig(
                name=name, num_trials=1, optimizer="randomsearch",
                searchspace=Searchspace(lr=("DOUBLE", [0.0, 0.2]),
                                        units=("INTEGER", [8, 64])),
                direction="max", hb_interval=0.1, es_policy="none",
                experiment_dir=base, telemetry=False, health=False)

        fleet = Fleet(runners=1, max_active=1,
                      home_dir=str(tmp_path / "fleet"))
        try:
            with fleet:
                a = experiment.lagom_submit(blocker, cfg("blk"),
                                            fleet=fleet, block=False)
                assert started.wait(timeout=30)
                b = experiment.lagom_submit(train_trivial, cfg("queued"),
                                            fleet=fleet, block=False)
                time.sleep(0.5)
                # Still queued: no driver (no run dir claim, no server,
                # no telemetry) exists for the waiting tenant.
                assert b.entry.state == "queued"
                assert b.entry.driver is None
                release.set()
                assert a.result(timeout=60)["num_trials"] == 1
                assert b.result(timeout=60)["num_trials"] == 1
                assert b.entry.driver is not None
        finally:
            release.set()


# --------------------------------------------------------- spool bound


@pytest.mark.scale
class TestSpoolBoundedScan:
    class _FakeFleet:
        def __init__(self, saturated=False):
            self.scheduler = self
            self._saturated = saturated

        def saturated(self):
            return self._saturated

    def _write_specs(self, env, spool, n, start=0):
        env.mkdir(spool)
        for i in range(start, start + n):
            env.dump(json.dumps({"name": "s{}".format(i)}),
                     "{}/s{:03d}.json".format(spool, i))

    def test_seen_set_skips_resolved_specs(self, local_env, tmp_path,
                                           monkeypatch):
        from maggy_tpu.fleet import __main__ as fleet_main

        submitted = []
        monkeypatch.setattr(
            fleet_main, "_submit_spec",
            lambda fleet, spec, handles, base_dir=None:
            submitted.append(spec["name"]))
        spool = str(tmp_path / "queue")
        self._write_specs(local_env, spool, 5)
        seen = set()
        fleet = self._FakeFleet()
        n = fleet_main._drain_spool(fleet, local_env, spool, {}, seen=seen)
        assert n == 5 and len(seen) == 5
        # Second drain: zero exists() probes for already-resolved specs.
        calls = []
        orig_exists = local_env.exists
        monkeypatch.setattr(
            local_env, "exists",
            lambda path: calls.append(path) or orig_exists(path))
        assert fleet_main._drain_spool(fleet, local_env, spool, {},
                                       seen=seen) == 0
        assert calls == []
        # A NEW spec costs exactly one probe.
        self._write_specs(local_env, spool, 1, start=5)
        assert fleet_main._drain_spool(fleet, local_env, spool, {},
                                       seen=seen) == 1
        assert len(calls) == 1

    def test_saturated_fleet_leaves_specs_unclaimed(self, local_env,
                                                    tmp_path, monkeypatch):
        from maggy_tpu.fleet import __main__ as fleet_main

        monkeypatch.setattr(
            fleet_main, "_submit_spec",
            lambda *a, **k: pytest.fail("must not submit while saturated"))
        spool = str(tmp_path / "queue")
        self._write_specs(local_env, spool, 3)
        seen = set()
        assert fleet_main._drain_spool(self._FakeFleet(saturated=True),
                                       local_env, spool, {}, seen=seen) == 0
        # No claim markers were burnt: a later unsaturated drain gets all.
        assert not [n for n in local_env.ls(spool)
                    if n.endswith(".claimed")]
        submitted = []
        monkeypatch.setattr(
            fleet_main, "_submit_spec",
            lambda fleet, spec, handles, base_dir=None:
            submitted.append(spec["name"]))
        assert fleet_main._drain_spool(self._FakeFleet(), local_env,
                                       spool, {}, seen=seen) == 3
        assert len(submitted) == 3

    def test_raced_saturation_unburns_claim(self, local_env, tmp_path,
                                            monkeypatch):
        """A claim that races into FleetSaturated (concurrent submit
        filled the queue between the pre-claim check and the submit)
        must be un-burnt — marker deleted, name forgotten — so the spec
        is retried once the queue drains instead of being lost."""
        from maggy_tpu.fleet import __main__ as fleet_main
        from maggy_tpu.fleet.scheduler import FleetSaturated

        spool = str(tmp_path / "queue")
        self._write_specs(local_env, spool, 1)
        calls = {"n": 0}

        def submit(fleet, spec, handles, base_dir=None):
            calls["n"] += 1
            if calls["n"] == 1:
                raise FleetSaturated("raced")
            handles[spec["name"]] = object()

        monkeypatch.setattr(fleet_main, "_submit_spec", submit)
        seen = set()
        handles = {}
        assert fleet_main._drain_spool(self._FakeFleet(), local_env,
                                       spool, handles, seen=seen) == 0
        assert not [n for n in local_env.ls(spool)
                    if n.endswith(".claimed")]
        assert not seen
        assert fleet_main._drain_spool(self._FakeFleet(), local_env,
                                       spool, handles, seen=seen) == 1
        assert handles


# --------------------------------------------- slow-tenant chaos smoke


@pytest.mark.scale
@pytest.mark.chaos
class TestSlowTenantIsolation:
    @pytest.mark.timeout(180)
    def test_slow_tenant_soak_pooled_holds_isolation_bound(self, tmp_path):
        from maggy_tpu.fleet.soak import run_slow_tenant_soak

        report = run_slow_tenant_soak(
            dispatch_pool=True, base_dir=str(tmp_path / "slow"),
            lock_witness=True)
        assert report["ok"], report["violations"]
        assert report["detail"]["injections"] > 0
        # The witness actually observed lock traffic, cleanly.
        assert report["witness"]["edges"] > 0
        assert report["witness"]["violations"] == 0
        rtts = [v for v in
                report["detail"]["victim_reply_rtt_ms"].values()
                if v is not None]
        assert rtts and max(rtts) <= \
            report["detail"]["victim_rtt_bound_ms"]


# ----------------------------------------------- original 64-runner soak


@pytest.mark.slow
class TestConcurrencyScale:
    def test_64_concurrent_runners_complete_200_trials(self):
        config = OptimizationConfig(
            name="scale64", num_trials=200, optimizer="randomsearch",
            searchspace=Searchspace(lr=("DOUBLE", [0.0, 1.0]),
                                    units=("INTEGER", [1, 1000])),
            direction="max", num_workers=64, hb_interval=0.5,
            seed=0, es_policy="none",
        )
        t0 = time.monotonic()
        result = experiment.lagom(train_trivial, config)
        wall = time.monotonic() - t0
        assert result["num_trials"] == 200
        assert result["best_val"] is not None
        # Control-plane throughput sanity: 200 trivial trials through 64
        # runners must take seconds, not minutes (each trial costs ~no
        # compute; the wall is scheduling + RPC round trips).
        assert wall < 120, "control plane too slow: {:.1f}s".format(wall)

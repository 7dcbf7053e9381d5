"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Multi-chip TPU hardware is unavailable in CI; sharding paths are validated on
8 virtual CPU devices via XLA host-platform device multiplexing (the
documented JAX approach for testing pjit/shard_map without accelerators).
"""

import os

# Both variables are read when JAX creates its CPU client (first use), so
# setting them here, before any test imports jax, is early enough.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test; deselect with -m 'not slow'")
    config.addinivalue_line(
        "markers",
        "timeout(seconds): hard SIGALRM bound — the test FAILS with a "
        "TimeoutError instead of silently eating a CI budget")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection tests (maggy_tpu.chaos). The deterministic "
        "single-process smoke stays in the fast lane; the multi-process "
        "soak is additionally marked slow. Select with -m chaos.")
    config.addinivalue_line(
        "markers",
        "health: live health-engine tests (maggy_tpu.telemetry.health) — "
        "straggler/hang/RTT detection and the stall->flag chaos "
        "invariant. Select with -m health.")
    config.addinivalue_line(
        "markers",
        "perf: scheduling-performance smoke tests with generous CPU "
        "bounds (e.g. the journal-replayed hand-off gap) — fast enough "
        "for tier-1, so hand-off regressions fail in CI instead of only "
        "surfacing in bench.py. Select with -m perf.")
    config.addinivalue_line(
        "markers",
        "analysis: static concurrency/protocol conformance analysis "
        "(maggy_tpu.analysis) — the four checkers against firing/clean "
        "fixtures, the runtime lock-order witness, and the tier-1 "
        "package-must-analyze-clean gate. Select with -m analysis.")
    config.addinivalue_line(
        "markers",
        "obs: live observability plane tests (maggy_tpu.telemetry.obs) — "
        "the /metrics-/status-/healthz-/profilez HTTP surface, the "
        "Prometheus rendering, health-triggered profile capture, and the "
        "tier-1 scrape-vs-journal smoke. Select with -m obs.")
    config.addinivalue_line(
        "markers",
        "fleet: shared-fleet scheduler tests (maggy_tpu.fleet) — "
        "multiplexing concurrent experiments over one runner fleet with "
        "fair share, priorities, and checkpoint-assisted preemption. "
        "Select with -m fleet.")
    config.addinivalue_line(
        "markers",
        "agent: remote fleet-agent tests (maggy_tpu.fleet.agent) — "
        "fleet tickets, the AJOIN/ABIND/ADONE wire contract, "
        "cross-experiment re-binding, agent-death lease revocation "
        "(invariant 11), and remote-gang rendezvous wiring. The real-"
        "subprocess soak is additionally marked slow. Select with "
        "-m agent.")
    config.addinivalue_line(
        "markers",
        "scale: service-scale control-plane tests — SharedServer "
        "per-tenant dispatch pools, multi-hundred-tenant routing stress, "
        "batched heartbeats, indexed fleet admission/shedding, and the "
        "slow-tenant isolation smoke. The fast smokes run in tier-1; "
        "the big churn soaks live in bench.py --scale. Select with "
        "-m scale.")
    config.addinivalue_line(
        "markers",
        "failover: crash-only driver failover tests (core/driver/"
        "recovery.py, chaos/driver_soak.py) — journal-replay "
        "reconstruction, cross-incarnation RPC acceptance, run-dir "
        "adoption, the FINAL-path durability barrier, and invariant 13. "
        "The real-subprocess kill_driver soak is additionally marked "
        "slow. Select with -m failover.")
    config.addinivalue_line(
        "markers",
        "fork: checkpoint-forking search tests — fork/copy staging, the "
        "driver's fork stamp + genealogy + checkpoint GC, bitwise "
        "fork-parity e2e, parent-affinity scheduling, and the offline "
        "invariant-14 checker. The kill-mid-fork soak is `python -m "
        "maggy_tpu.chaos --fork`; the A/B gate is `bench.py --fork`. "
        "Select with -m fork.")
    config.addinivalue_line(
        "markers",
        "sink: fleet-wide telemetry fan-in tests (maggy_tpu.telemetry."
        "sink) — the JSINK journal sink service, client shipper "
        "degrade/re-ship exactly-once seam (invariant 12), clock-offset "
        "estimation, metrics federation, and the unified Perfetto "
        "trace. Select with -m sink.")
    config.addinivalue_line(
        "markers",
        "goodput: chip-time goodput ledger tests (maggy_tpu.telemetry."
        "goodput) — the offline journal fold (closed bucket taxonomy, "
        "exact closure, gang chip-multiplication, rotation/failover "
        "seams), clock-offset-corrected merges, rework attribution "
        "(chaos invariant 15), and the per-tenant fleet roll-up. The "
        "A/B gate is `bench.py --goodput`; the fault-free control soak "
        "is `python -m maggy_tpu.chaos --goodput`. Select with "
        "-m goodput.")
    config.addinivalue_line(
        "markers",
        "vmap: vectorized micro-trial tests (train/vmap.py, "
        "config.vmap_lanes) — K-lane VmapTrainer bitwise parity vs "
        "scalar runs, lane masking/refill, driver block assembly with "
        "scalar fallback for incompatible configs, lane-tagged journal "
        "edges, and the lane_idle goodput split. The kill-mid-block "
        "soak is `python -m maggy_tpu.chaos --vmap`; the A/B gate is "
        "`bench.py --vmap`. Select with -m vmap.")


@pytest.fixture(autouse=True)
def _hard_timeout(request):
    """Enforce @pytest.mark.timeout(N) without the pytest-timeout plugin.

    SIGALRM interrupts the main thread wherever it is blocked (joins, lock
    waits, subprocess polls), so a livelocked test surfaces as a failed
    test with a stack trace, not a hung CI job. Worker threads/processes
    the test leaked are cleaned by their own daemon/terminate paths."""
    m = request.node.get_closest_marker("timeout")
    if m is None:
        yield
        return
    import signal
    import threading

    if threading.current_thread() is not threading.main_thread():
        yield
        return
    seconds = int(m.args[0])

    def _abort(signum, frame):
        raise TimeoutError(
            "test exceeded its {}s hard timeout (marker)".format(seconds))

    old = signal.signal(signal.SIGALRM, _abort)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture
def tmp_experiment_dir(tmp_path):
    d = tmp_path / "experiments"
    d.mkdir()
    return str(d)

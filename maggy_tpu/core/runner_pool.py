"""Runner pools: the fan-out substrate replacing Spark executors.

The reference fans out via ``sc.parallelize(range(N), N).foreachPartition``
(`driver.py:96-106`) onto long-lived Spark executors. Here a RunnerPool
launches N trial-runner workers and blocks until all return:

- `ThreadRunnerPool`: N in-process threads. Default for single-host runs —
  JAX releases the GIL during XLA compute, and concurrent trials on one
  host naturally share the chip(s). Also the test substrate (SURVEY.md §4's
  "in-process fake runner" made real).
- `ProcessRunnerPool`: N forked/spawned local processes, one JAX runtime
  each; used when trials must not share a Python runtime.
- `TPURunnerPool`: N processes, each pinned to a disjoint TPU chip sub-slice
  via TPU_VISIBLE_CHIPS/TPU_PROCESS_BOUNDS env vars, so >=64 concurrent
  trials can run on a v4-32 pod (BASELINE north star). Process env setup
  must happen BEFORE jax/libtpu initialization, hence process pools.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import threading
import time
import traceback
from abc import ABC, abstractmethod
from typing import Callable, Iterable, List, Optional


_DEVICE_PROBE_CODE = """\
import sys
import jax
ds = jax.local_devices()
coords = [getattr(d, "coords", None) for d in ds]
n_chips = len(ds) if None in coords else len({tuple(c) for c in coords})
sys.stdout.write("{} {}".format(n_chips, len(ds)))
"""


def _probe_local_devices(timeout_s: float = 120.0):
    """(chips, devices) counted in a THROWAWAY subprocess. The driver
    process must never initialize the JAX/libtpu backend itself: for
    process/TPU pools the children pin chips via env vars read at THEIR
    backend init, and a driver-side init would claim every local chip
    first (the exact hazard process pools exist to avoid). Chips are
    counted by distinct device.coords — on 2-TensorCore chips (v2/v3)
    devices != chips and TPU_VISIBLE_CHIPS pinning is per chip. The
    child's stderr is kept and quoted when the probe fails, so a failed
    probe says why."""
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, "-c", _DEVICE_PROBE_CODE],
        timeout=timeout_s, stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    if proc.returncode != 0:
        raise RuntimeError(
            "device probe exited with code {}: {}".format(
                proc.returncode,
                proc.stderr.decode("utf-8", "replace").strip()[-2000:]))
    chips, devices = proc.stdout.decode().split()
    return int(chips), int(devices)


def resolve_num_workers(config) -> int:
    """``num_workers="auto"``: size the pool from the runtime device
    inventory instead of a hardcoded count — the TPU-native analogue of
    the reference reading the executor count from cluster conf at runtime
    (`hopsworks.py:236-244`). One runner per local chip subset for the
    TPU pool; one per local device otherwise. Remote pools must stay
    explicit: agents JOIN dynamically, the driver only caps admission."""
    nw = getattr(config, "num_workers", 1)
    if nw != "auto":
        return int(nw)
    pool = getattr(config, "pool", "thread")
    if pool == "remote":
        raise ValueError(
            "num_workers='auto' is for local pools; remote agents join "
            "dynamically — set the admission cap explicitly.")
    try:
        chips, devices = _probe_local_devices()
    except Exception as e:  # noqa: BLE001 - probe subprocess failed/hung
        raise ValueError(
            "num_workers='auto' could not probe the device inventory "
            "({!r}); pass an explicit count.".format(e)) from e
    if pool in ("tpu", "elastic"):
        return max(1, chips // max(1, getattr(config, "chips_per_trial", 1)))
    return max(1, devices)


class RunnerPool(ABC):
    def __init__(self, num_workers: int):
        self.num_workers = num_workers

    @abstractmethod
    def run(self, worker_fn: Callable[[int], None]) -> List[BaseException]:
        """Run ``worker_fn(partition_id)`` on all workers; block until done.

        Returns the list of runner failures (exceptions or RuntimeErrors for
        dead processes) instead of raising: a dead runner is survivable — the
        driver requeues its trial onto surviving runners (heartbeat-loss
        detection) and only escalates if the experiment could not complete.
        """

    def terminate(self) -> None:
        """Force-stop all workers (best effort). Used when the experiment is
        already doomed (e.g. a dead SPMD rank) and surviving workers may be
        wedged waiting on it. Threads cannot be killed — only process-backed
        pools act on this."""

    def kill_worker(self, partition_id: int) -> bool:
        """Kill ONE hung worker (best effort), leaving the rest of the pool
        running. Called by heartbeat-loss detection: a runner wedged inside
        an uninterruptible native call (XLA compile, a stuck device op)
        stops heartbeating but never returns, and without this its
        process would block the pool's final join forever — the hang case
        Spark's task-retry machinery covered for free in the reference.
        Returns True if a worker was actually killed. Thread pools cannot
        kill (Python threads are not interruptible): they return False and
        rely on the requeue alone, so wedge-resilience needs a process
        pool ('process'/'tpu')."""
        return False

    def stall_worker(self, partition_id: int, duration_s: float) -> bool:
        """Freeze ONE worker for ``duration_s`` seconds (fault injection:
        maggy_tpu.chaos ``stall_runner`` — the straggler/compile-stall
        simulator). Process pools SIGSTOP the process and SIGCONT it from
        a timer; thread pools return False and the chaos engine falls
        back to a cooperative RPC-hook stall."""
        return False


class ThreadRunnerPool(RunnerPool):
    def run(self, worker_fn: Callable[[int], None]) -> List[BaseException]:
        errors: List[BaseException] = []
        lock = threading.Lock()

        def target(pid: int):
            try:
                worker_fn(pid)
            except BaseException as e:  # noqa: BLE001
                with lock:
                    errors.append(e)
                traceback.print_exc()

        threads = [
            threading.Thread(target=target, args=(i,), name="runner-{}".format(i))
            for i in range(self.num_workers)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return errors


#: TPU_CHIPS_PER_PROCESS_BOUNDS for a sub-slice of k chips, where the
#: installed libtpu cannot derive it from the visible set (see pin_env).
_SUBSLICE_BOUNDS = {2: "1,2,1"}


def pin_env(chips: Iterable[int]) -> dict:
    """Env vars pinning one process to the given local TPU chips. libtpu
    reads them at backend init, so they must be set before the process
    first uses JAX — the TPU analogue of the reference pinning one GPU per
    Spark executor.

    What the installed libtpu (0.0.34) needs, as measured on a four-chip
    v5e host (2x2, ``TPU_CHIPS_PER_HOST_BOUNDS=2,2,1`` in the machine's
    environment; CHANGES.md PR 21):

    - one chip: ``TPU_VISIBLE_CHIPS=<i>`` and ``ALLOW_MULTIPLE_LIBTPU_LOAD=1``
      are enough. Four such processes, one per chip, initialise and run side
      by side; adding ``TPU_CHIPS_PER_PROCESS_BOUNDS=1,1,1`` and
      ``TPU_PROCESS_BOUNDS=1,1,1`` changes nothing.
    - two chips (``0,1`` or ``2,3``): the visible set alone FAILS at init
      ("number of devices found in the host does not match the topology,
      expected 4, actual: 2"). It initialises with
      ``TPU_CHIPS_PER_PROCESS_BOUNDS=1,2,1`` and ``TPU_PROCESS_BOUNDS=1,1,1``;
      ``2,1,1`` fails. Other pairs and collectives across the pair were not
      measured.
    - all four chips: the machine's own bounds apply; nothing is added.

    Inside a pinned process the devices are renumbered from id 0 with
    coords from (0,0,0), so ``TPU_VISIBLE_CHIPS`` is the only record of
    WHICH chips the process holds.
    """
    chips = list(chips)
    env = {
        "TPU_VISIBLE_CHIPS": ",".join(str(c) for c in chips),
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }
    bounds = _SUBSLICE_BOUNDS.get(len(chips))
    if bounds is not None:
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = bounds
        env["TPU_PROCESS_BOUNDS"] = "1,1,1"
    return env


def chip_env(index: int, chips_per_trial: int = 1) -> dict:
    """``pin_env`` for runner ``index`` of a pool of equal sub-slices:
    chips [index*k, (index+1)*k). Shared by the local TPURunnerPool and the
    remote agent's --chips-per-agent / --agent-index flags (one agent per
    chip subset on each pod VM)."""
    return pin_env(range(index * chips_per_trial,
                         (index + 1) * chips_per_trial))


def _check_leased_chips(visible: str) -> None:
    """A pinned runner must run on the chips it was leased, or not at all.
    JAX drops to the CPU when libtpu finds no chip and JAX_PLATFORMS does
    not forbid it; a runner that carried on there would report CPU trials
    under a TPU pool's name. Skipped only when JAX_PLATFORMS puts ``cpu``
    first (CPU tests and rehearsals, where the pin is a marker)."""
    from maggy_tpu.util import cpu_first_platform

    if cpu_first_platform():
        return
    import jax

    backend, devices = jax.default_backend(), jax.local_devices()
    chips = len({tuple(d.coords) for d in devices}) if backend == "tpu" else 0
    if chips != len(visible.split(",")):
        raise RuntimeError(
            "runner leased TPU chip(s) {} but its JAX backend is {!r} with "
            "{} TPU chip(s) ({}); refusing to register".format(
                visible, backend, chips, devices))


def _process_entry(worker_fn, pid, chip_env):
    # Device pinning must precede any jax import in the child.
    for k, v in (chip_env or {}).items():
        os.environ[k] = v
    if chip_env:
        _check_leased_chips(chip_env["TPU_VISIBLE_CHIPS"])
    worker_fn(pid)


def _stall_process(p, duration_s: float) -> bool:
    """SIGSTOP ``p`` now, SIGCONT it from a daemon timer after
    ``duration_s`` (fault injection: a straggler whose heartbeats freeze
    mid-trial). Best effort: a process that exits during the stall is
    simply not resumed."""
    import signal
    import threading as _threading

    if not (p.is_alive() and p.pid):
        return False
    try:
        os.kill(p.pid, signal.SIGSTOP)
    except OSError:
        return False

    def _resume():
        try:
            if p.is_alive():
                os.kill(p.pid, signal.SIGCONT)
        except OSError:
            pass

    t = _threading.Timer(duration_s, _resume)
    t.daemon = True
    t.start()
    return True


class ProcessRunnerPool(RunnerPool):
    """One OS process per runner. ``train_fn`` must be module-level picklable
    (declarative specs travel; closures need ThreadRunnerPool)."""

    def __init__(self, num_workers: int, start_method: str = "spawn",
                 chip_env_fn: Optional[Callable[[int], dict]] = None):
        super().__init__(num_workers)
        self.start_method = start_method
        self.chip_env_fn = chip_env_fn
        self._procs: list = []

    def terminate(self) -> None:
        for p in self._procs:
            if p.is_alive():
                p.terminate()

    def kill_worker(self, partition_id: int) -> bool:
        # SIGKILL, not SIGTERM: a SIGSTOPped or native-wedged process never
        # runs a TERM handler (for a stopped process TERM stays pending
        # until SIGCONT), while KILL reaps it unconditionally.
        if 0 <= partition_id < len(self._procs):
            p = self._procs[partition_id]
            if p.is_alive():
                p.kill()
                return True
        return False

    def stall_worker(self, partition_id: int, duration_s: float) -> bool:
        if 0 <= partition_id < len(self._procs):
            return _stall_process(self._procs[partition_id], duration_s)
        return False

    def run(self, worker_fn: Callable[[int], None]) -> List[BaseException]:
        ctx = mp.get_context(self.start_method)
        procs = []
        for i in range(self.num_workers):
            env = self.chip_env_fn(i) if self.chip_env_fn else {}
            p = ctx.Process(target=_process_entry, args=(worker_fn, i, env),
                            name="runner-{}".format(i))
            p.start()
            procs.append(p)
        self._procs = procs
        failures: List[BaseException] = []
        for p in procs:
            p.join()
            if p.exitcode != 0:
                failures.append(RuntimeError(
                    "Runner process {} died (exit code {}).".format(p.name, p.exitcode)))
        return failures


class TPURunnerPool(ProcessRunnerPool):
    """Per-trial TPU chip pinning: runner i sees only its chip subset.

    On a TPU VM with C local chips and ``chips_per_trial`` k, runner i gets
    chips [i*k, (i+1)*k) through ``chip_env``; each runner checks before it
    registers that it really holds them (``_check_leased_chips``).
    """

    def __init__(self, num_workers: int, chips_per_trial: int = 1,
                 total_chips: Optional[int] = None):
        if total_chips is not None and num_workers * chips_per_trial > total_chips:
            raise ValueError(
                "{} workers x {} chips/trial exceeds the {} chips on this "
                "host.".format(num_workers, chips_per_trial, total_chips)
            )

        super().__init__(
            num_workers, start_method="spawn",
            chip_env_fn=lambda i: chip_env(i, chips_per_trial))
        self.chips_per_trial = chips_per_trial
        self.total_chips = total_chips


class ElasticTPURunnerPool(RunnerPool):
    """Budget-sized chip sub-slices: SURVEY §7.3's slice-repartitioning
    problem. Each runner is an ephemeral pinned process; when the driver
    decides a runner's capacity no longer matches the schedule's needs
    (chips_per_budget), the runner exits with a resize request and this
    dispatcher respawns it pinned to the new chip count — libtpu reads the
    pinning env before backend init, so resizing is exit+respawn by
    construction. A chip free-list enforces sum(leases) <= total_chips;
    respawns wait until enough chips free up (the driver resizes idle
    runners toward parked work, so chips migrate instead of deadlocking).
    """

    def __init__(self, num_workers: int, total_chips: int,
                 chips_per_trial: int = 1, start_method: str = "spawn",
                 should_stop: Optional[Callable[[], bool]] = None,
                 resize_dir: Optional[str] = None):
        super().__init__(num_workers)
        if num_workers * chips_per_trial > total_chips:
            raise ValueError(
                "{} workers x {} chips exceeds the {}-chip lease budget"
                .format(num_workers, chips_per_trial, total_chips))
        self.total_chips = total_chips
        self.chips_per_trial = chips_per_trial
        self.start_method = start_method
        self.should_stop = should_stop or (lambda: False)
        import tempfile

        self.resize_dir = resize_dir or tempfile.mkdtemp(prefix="maggy_resize_")
        self._procs: dict = {}  # pid -> (process, chips_set)
        self._spawn_time: dict = {}  # pid -> monotonic start of current proc
        self._free: set = set()
        # Respawns queued for chips: [(partition_id, chips_needed)]. Kept on
        # self (under _lock) so the driver's resize watchdog can tell
        # "queued for chips" (healthy waiting — re-arm the watch) from
        # "process died before registering" (nothing will ever register —
        # expire the watch and reclaim the in-flight credit). spawn_stamp()
        # returns None for BOTH, which is exactly the ambiguity that leaked
        # credits before.
        self._pending_respawns: list = []  # guarded-by: _lock
        self._lock = threading.Lock()

    def spawn_stamp(self, partition_id: int):
        """Monotonic spawn time of the partition's CURRENT process, or
        None when no process exists (respawn still queued for chips).

        The driver's resize watchdog compares stamps, not ages: at resize
        request time the partition still runs its PRE-resize process, so a
        bare age check would see that (old, long-lived) process and kill a
        runner that is merely winding down. Only a process spawned AFTER
        the request (stamp > the stamp recorded at request time) that then
        fails to register is evidence of a wedged respawn."""
        with self._lock:
            if partition_id not in self._procs:
                return None
            return self._spawn_time.get(partition_id)

    def spawn_age(self, partition_id: int):
        """Seconds since the partition's CURRENT process was spawned, or
        None when no process exists."""
        t0 = self.spawn_stamp(partition_id)
        return None if t0 is None else time.monotonic() - t0

    def pending_respawn(self, partition_id: int) -> bool:
        """True while the partition still has a future: its respawn is
        QUEUED for chips, or a process exists RIGHT NOW (covers the race
        where the queued respawn was spawned between the watchdog's
        spawn_stamp() read and this call — without the _procs check the
        watchdog would misread that healthy just-spawned runner as 'died
        before registering' and kill it). False is terminal — a pid never
        re-enters _procs or the pending list once it left both — so the
        watchdog can safely expire the watch and reclaim the in-flight
        credit on a False."""
        with self._lock:
            if partition_id in self._procs:
                return True
            return any(pid == partition_id
                       for pid, _ in self._pending_respawns)

    def _resize_file(self, partition_id: int) -> str:
        return os.path.join(self.resize_dir, "{}.resize".format(partition_id))

    def _spawn(self, ctx, worker_fn, partition_id: int, chips: set):
        env = {
            **pin_env(sorted(chips)),
            "MAGGY_TPU_CAPACITY": str(len(chips)),
            "MAGGY_TPU_RESIZE_FILE": self._resize_file(partition_id),
        }
        p = ctx.Process(target=_process_entry,
                        args=(worker_fn, partition_id, env),
                        name="runner-{}".format(partition_id))
        p.start()
        self._procs[partition_id] = (p, chips)
        self._spawn_time[partition_id] = time.monotonic()

    def kill_worker(self, partition_id: int) -> bool:
        with self._lock:
            entry = self._procs.get(partition_id)
            if entry and entry[0].is_alive():
                entry[0].kill()
                return True
        return False

    def stall_worker(self, partition_id: int, duration_s: float) -> bool:
        with self._lock:
            entry = self._procs.get(partition_id)
        return bool(entry) and _stall_process(entry[0], duration_s)

    def terminate(self) -> None:
        with self._lock:
            for p, _ in self._procs.values():
                if p.is_alive():
                    p.terminate()

    def run(self, worker_fn: Callable[[int], None]) -> List[BaseException]:
        import json as _json
        import time as _time

        ctx = mp.get_context(self.start_method)
        chip_ids = list(range(self.total_chips))
        with self._lock:
            for i in range(self.num_workers):
                lease = set(chip_ids[i * self.chips_per_trial:
                                     (i + 1) * self.chips_per_trial])
                self._spawn(ctx, worker_fn, i, lease)
            self._free = set(chip_ids[self.num_workers * self.chips_per_trial:])
        failures: List[BaseException] = []
        while True:
            with self._lock:
                live = dict(self._procs)
            exited = [(pid, p, chips) for pid, (p, chips) in live.items()
                      if not p.is_alive()]
            for pid, p, chips in exited:
                p.join()
                # Read the resize request BEFORE releasing the partition's
                # pool slot: between _procs.pop and the pending append the
                # driver's watchdog would otherwise see stamp=None AND
                # pending_respawn=False — the died-before-registering
                # signature — for a healthy queued respawn.
                resize = None
                rf = self._resize_file(pid)
                if os.path.exists(rf):
                    try:
                        with open(rf) as f:
                            resize = int(_json.load(f)["chips"])
                    except (ValueError, KeyError, OSError):
                        pass
                    try:
                        os.unlink(rf)
                    except OSError:
                        pass
                with self._lock:
                    self._procs.pop(pid, None)
                    self._free |= chips
                    if p.exitcode == 0 and resize:
                        # resize 0 = retire: chips freed, no respawn
                        self._pending_respawns.append((pid, resize))
                if p.exitcode != 0:
                    failures.append(RuntimeError(
                        "Runner process {} died (exit code {})."
                        .format(p.name, p.exitcode)))
            # Serve respawns whose lease fits the free pool.
            with self._lock:
                still_pending = []
                for pid, k in self._pending_respawns:
                    if k > self.total_chips:
                        failures.append(RuntimeError(
                            "Runner {} asked for {} chips but the lease "
                            "budget is {} (check chips_per_budget).".format(
                                pid, k, self.total_chips)))
                        continue
                    if self.should_stop():
                        continue  # experiment over: drop the respawn
                    if len(self._free) >= k:
                        lease = set(sorted(self._free)[:k])
                        self._free -= lease
                        self._spawn(ctx, worker_fn, pid, lease)
                    else:
                        still_pending.append((pid, k))
                self._pending_respawns = still_pending
                pending = list(still_pending)
                alive = any(p.is_alive() for p, _ in self._procs.values())
            if not alive and (not pending or self.should_stop()):
                break
            _time.sleep(0.05)
        return failures


class RemoteRunnerPool(RunnerPool):
    """Cross-host fan-out over DCN: runners are external agent processes
    (``python -m maggy_tpu.runner``) on other machines — TPU VMs of a pod
    slice — that dial the driver's control plane and JOIN.

    Scope note: these agents belong to ONE experiment and exit with it.
    For a PERSISTENT cross-process fleet that outlives any experiment —
    agents leased, preempted, and re-bound across experiments — use
    fleet agents instead (``maggy_tpu/fleet/agent.py``, ``python -m
    maggy_tpu.fleet agent``): same ticket-and-JOIN shape, fleet-scoped.

    The pool spawns nothing. It publishes a join ticket (advertised address
    + shared secret) to the experiment directory — typically a shared
    filesystem or GCS, the same discovery role as the reference POSTing the
    driver address to Hopsworks REST (`hopsworks.py:129-178`) — then waits
    for the experiment to complete. Agents may join at any time up to
    ``num_workers``; the schedule completes with however many joined
    (heartbeat-loss recovery covers agents dying mid-trial).
    """

    def __init__(self, driver):
        super().__init__(driver.num_executors)
        self.driver = driver

    def ticket(self) -> dict:
        drv = self.driver
        host, port = drv.server_addr
        if host in ("0.0.0.0", "", "::"):
            host = drv.env.get_ip_address()
        return {"host": host, "port": port, "secret": drv.secret_for_clients(),
                "app_id": drv.app_id, "run_id": drv.run_id,
                "num_workers": self.num_workers}

    def run(self, worker_fn: Callable[[int], None]) -> List[BaseException]:
        import json
        import time

        from maggy_tpu import constants

        drv = self.driver
        drv.env.dump(json.dumps(self.ticket(), indent=2),
                     drv.exp_dir + "/runner_ticket.json")
        # Trial parallelism proceeds with however many agents join;
        # distributed training NEEDS the full world before anything runs.
        need_all = (drv.server.join_info or {}).get("trial_type") == "distributed"
        deadline = time.monotonic() + constants.REGISTRATION_TIMEOUT_S
        while not drv.experiment_done:
            reservations = drv.server.reservations
            if reservations.done() if need_all else bool(reservations.all()):
                break
            if time.monotonic() > deadline:
                raise TimeoutError(
                    "{} remote runner(s) missing after {}s; ticket at {}".format(
                        reservations.remaining() if need_all else "All",
                        constants.REGISTRATION_TIMEOUT_S,
                        drv.exp_dir + "/runner_ticket.json"))
            time.sleep(0.2)
        # Experiment wait, with an all-agents-dead liveness bound: if every
        # admitted agent has gone silent past the heartbeat-loss timeout,
        # nobody is left to poll GET — requeued trials would never be picked
        # up and this loop would spin forever. Surfacing the failure lets the
        # driver abort with the real cause instead of hanging.
        while not drv.experiment_done:
            time.sleep(0.2)
            bound = drv.server.hb_loss_timeout
            if bound is None:
                continue
            registered = drv.server.reservations.all()
            active = {pid for pid, rec in registered.items()
                      if not rec.get("released")}
            if active and active <= set(drv.server.reservations.silent(bound)):
                return [RuntimeError(
                    "all {} remote agent(s) silent for > {:.0f}s with the "
                    "experiment incomplete; presumed dead (partitions {})".format(
                        len(active), bound, sorted(active)))]
        # Don't let the driver tear the server down under agents that have
        # not yet observed GSTOP — their next poll would hit a dead socket
        # and crash an otherwise-successful agent. Dead agents can't ack, so
        # a grace cap bounds the wait.
        ack_deadline = time.monotonic() + 10.0
        while (not drv.server.reservations.all_released()
               and time.monotonic() < ack_deadline):
            time.sleep(0.1)
        return []

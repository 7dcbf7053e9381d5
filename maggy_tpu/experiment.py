"""`lagom` — the experiment entry points.

Parity: reference `maggy/experiment.py` — one-experiment-at-a-time module
guard (:42-45), `lagom(train_fn, config)` (:48-83), `@singledispatch` driver
dispatch on config type (:86-108), exception handler marking the experiment
FAILED (:111-128), atexit kill-handler (:131-148).

Beyond the reference: per-run state lives in `_Submission` objects handed
out under a lock (the reference's bare module globals let two threads both
pass the ``if RUNNING`` check), and `lagom_submit` attaches an experiment
to a shared runner fleet (`maggy_tpu.fleet`) instead of owning a pool —
any number of submissions may run concurrently in one process, multiplexed
by the fleet scheduler. The classic `lagom()` is the degenerate case: a
single-tenant fleet of one that owns its pool, bit-for-bit unchanged.

"Lagom" (Swedish): just the right amount — keep every runner busy with
asynchronous trials, never more resources than needed.
"""

from __future__ import annotations

import atexit
import itertools
import os
import threading
import time
from functools import singledispatch
from typing import Any, Callable, Optional

from maggy_tpu import util
from maggy_tpu.config import (
    AblationConfig,
    DistributedConfig,
    LagomConfig,
    OptimizationConfig,
)
from maggy_tpu.core.environment import EnvSing

#: Back-compat mirrors of the per-run state (tests and notebooks read /
#: monkeypatch these). The authoritative state is the _Submission registry
#: below — ALL mutation happens under _state_lock.
APP_ID: str | None = None
RUNNING = False
RUN_ID = 0

_state_lock = threading.RLock()
_active_runs: set = set()
_token_counter = itertools.count()


class _Submission:
    """One claimed run: (app_id, run_id) plus the registry token that
    marks it active until `_end_run`."""

    __slots__ = ("token", "app_id", "run_id")

    def __init__(self, token: int, app_id: str, run_id: int):
        self.token = token
        self.app_id = app_id
        self.run_id = run_id


def _begin_run(config, env, exclusive: bool) -> _Submission:
    """Claim per-run state under the lock: resolve the app id, claim a run
    id (atomically — `util.claim_run_id` stakes the run dir with
    `exclusive_create`, so two experiments starting under the same base
    dir can never mint the same id), and register the run as active.

    ``exclusive=True`` is classic `lagom` semantics: refuse while ANY run
    is active in this process. Fleet submissions pass False — concurrency
    is the point — and the unsynchronized two-threads-both-pass-the-check
    hazard of the old module-global ``RUNNING`` flag is gone either way."""
    global APP_ID, RUNNING, RUN_ID
    with _state_lock:
        if exclusive and _active_runs:
            raise RuntimeError("An experiment is already running in this process.")
        if APP_ID is None:
            APP_ID = os.environ.get(
                "MAGGY_TPU_APP_ID",
                "app-{}".format(time.strftime("%Y%m%d-%H%M%S")))
        app_id = APP_ID
        # Scan the SAME directory the driver will register under (a custom
        # experiment_dir must not collide at run 0), via the env's own fs.
        base = getattr(config, "experiment_dir", None) \
            or env.experiment_base_dir()
        if getattr(config, "resume", False):
            # Re-enter the most recent run OF THIS EXPERIMENT (matched by
            # registered name, not just position): one app id hosts many
            # experiments in fleet mode, and the bare most-recent rule
            # would adopt whichever tenant ran last.
            run_id = util.find_resume_run_id(base, app_id,
                                             name=config.name, env=env)
        else:
            run_id = util.claim_run_id(base, app_id, env=env)
        token = next(_token_counter)
        _active_runs.add(token)
        RUNNING = True
        RUN_ID = run_id
        return _Submission(token, app_id, run_id)


def _end_run(sub: _Submission) -> None:
    global RUNNING
    with _state_lock:
        _active_runs.discard(sub.token)
        RUNNING = bool(_active_runs)


def _build_config(config, kwargs) -> LagomConfig:
    """Config-or-kwargs resolution shared by lagom and lagom_submit."""
    if config is None:
        if not kwargs:
            raise TypeError(
                "lagom() needs a config object (OptimizationConfig / "
                "AblationConfig / DistributedConfig) or OptimizationConfig "
                "keyword arguments.")
        return OptimizationConfig(**kwargs)
    if kwargs:
        raise TypeError(
            "Pass EITHER a config object OR keyword arguments, not both "
            "(got config={!r} plus {}).".format(
                type(config).__name__, sorted(kwargs)))
    return config


def lagom(train_fn: Callable, config: LagomConfig = None, **kwargs) -> Any:
    """Launch an experiment: asynchronous HPO, an ablation study, or
    distributed training, selected by the config type.

    Compat: the reference's 0.x notebook style
    ``lagom(train_fn, searchspace=sp, optimizer="randomsearch",
    num_trials=15, direction="max")`` (its README quick start) is accepted —
    keyword arguments build an `OptimizationConfig`.

    One at a time per process (the reference's module guard). To run MANY
    experiments concurrently over one shared runner fleet, use
    ``lagom_submit``."""
    config = _build_config(config, kwargs)
    env = EnvSing.get_instance()
    sub = _begin_run(config, env, exclusive=True)
    driver = None
    try:
        driver = lagom_driver(config, sub.app_id, sub.run_id)
        atexit.register(_exit_handler, driver)
        return driver.run_experiment(train_fn)
    finally:
        _end_run(sub)
        if driver is not None:
            atexit.unregister(_exit_handler)


def lagom_submit(train_fn: Callable, config: LagomConfig = None, *,
                 fleet, priority="normal", weight: float = 1.0,
                 min_runners: int = 0, max_runners: Optional[int] = None,
                 name: Optional[str] = None, block: bool = True,
                 **kwargs) -> Any:
    """Submit an experiment to a shared runner fleet (`maggy_tpu.fleet`).

    Unlike ``lagom``, any number of submissions may run concurrently in
    one process: the fleet's scheduler multiplexes its persistent runners
    across them by ``priority`` class ("high"/"normal"/"low" or an int;
    lower wins), weighted fair share (``weight``), and per-experiment
    quotas (``min_runners`` guaranteed — by preempting over-share,
    lower-priority trials when necessary; ``max_runners`` capped). A
    preempted trial resumes from its last `TrialCheckpointer` step on its
    next runner (requeue-from-scratch when it never checkpointed).

    ``block=True`` (default) waits and returns the experiment result —
    the same value ``lagom`` returns. ``block=False`` returns a
    ``FleetSubmission`` handle (``.result()``/``.done()``) so many
    experiments can be submitted before waiting on any."""
    config = _build_config(config, kwargs)
    # resume=True re-enters the most recent run dir. Concurrent
    # resubmissions racing for the same dir are arbitrated by the
    # driver's exclusive incarnation marker (util.claim_driver_epoch):
    # exactly one adopter wins; the loser's submission fails with
    # RunAdoptionError through the handle — a resubmitted tenant after a
    # driver crash recovers its run from the journal like lagom() does
    # (docs/developer.md "Crash-only recovery").
    handle = fleet.submit(train_fn, config, priority=priority, weight=weight,
                          min_runners=min_runners, max_runners=max_runners,
                          name=name)
    return handle.result() if block else handle


@singledispatch
def lagom_driver(config, app_id: str, run_id: int):
    raise TypeError(
        "Unsupported config type {}; use OptimizationConfig, AblationConfig, "
        "or DistributedConfig.".format(type(config))
    )


@lagom_driver.register(OptimizationConfig)
def _(config: OptimizationConfig, app_id: str, run_id: int):
    from maggy_tpu.core.driver.optimization_driver import OptimizationDriver

    return OptimizationDriver(config, app_id, run_id)


@lagom_driver.register(AblationConfig)
def _(config: AblationConfig, app_id: str, run_id: int):
    from maggy_tpu.core.driver.ablation_driver import AblationDriver

    return AblationDriver(config, app_id, run_id)


@lagom_driver.register(DistributedConfig)
def _(config: DistributedConfig, app_id: str, run_id: int):
    from maggy_tpu.core.driver.distributed_driver import DistributedDriver

    return DistributedDriver(config, app_id, run_id)


def _exit_handler(driver) -> None:
    """Mark the experiment KILLED if the process dies mid-run (reference
    `experiment.py:131-148`)."""
    try:
        if not driver.experiment_done:
            driver.env.finalize_experiment(driver.exp_dir, "KILLED", {})
    except Exception:  # noqa: BLE001 - never raise at interpreter exit
        pass

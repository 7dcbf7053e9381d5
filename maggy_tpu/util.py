"""Framework utilities.

Parity: reference `maggy/util.py` — return-value validation + persistence
`handle_return_val` (:151-191), experiment registration (:264-279), numpy-safe
json (:89-99), progress bar (:71-86), summary builder (:126-148).
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, Optional

import numpy as np

from maggy_tpu import constants
from maggy_tpu.exceptions import MetricTypeError, ReturnTypeError


def json_default_numpy(obj):
    if isinstance(obj, np.integer):
        return int(obj)
    if isinstance(obj, np.floating):
        return float(obj)
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    raise TypeError("Type {} not serializable".format(type(obj)))


def json_dumps_safe(obj: Any) -> str:
    return json.dumps(obj, default=json_default_numpy)


def handle_return_val(return_val: Any, trial_dir: str, optimization_key: str,
                      env=None) -> float:
    """Validate the user function's return value and persist artifacts.

    Accepts a number (the metric) or a dict containing ``optimization_key``;
    writes ``.outputs.json`` + ``.metric`` into the trial dir (reference
    `util.py:151-191`).
    """
    from maggy_tpu.core.environment import EnvSing

    env = env or EnvSing.get_instance()
    if isinstance(return_val, dict):
        if optimization_key not in return_val:
            raise ReturnTypeError(optimization_key, return_val)
        metric = return_val[optimization_key]
        outputs = return_val
    elif isinstance(return_val, constants.USER_FCT.NUMERIC_TYPES) and not isinstance(return_val, bool):
        metric = return_val
        outputs = {optimization_key: return_val}
    else:
        raise ReturnTypeError(optimization_key, return_val)
    if not isinstance(metric, constants.USER_FCT.NUMERIC_TYPES) or isinstance(metric, bool):
        raise MetricTypeError(optimization_key, metric)
    metric = float(metric)
    env.dump(json.dumps(outputs, default=json_default_numpy), trial_dir + "/.outputs.json")
    env.dump(str(metric), trial_dir + "/.metric")
    return metric


def write_hparams_config(exp_dir: str, searchspace, env=None) -> None:
    """Persist the searchspace for TensorBoard-HParams-style tooling
    (reference `tensorboard.py:75-87`)."""
    from maggy_tpu.core.environment import EnvSing

    if searchspace is None:
        return
    env = env or EnvSing.get_instance()
    env.dump(json.dumps(searchspace.to_dict(), indent=2), exp_dir + "/searchspace.json")
    # HParams dashboard column config (real TB event file, torch-free;
    # best-effort — write_experiment_config swallows its own failures).
    from maggy_tpu import tensorboard as tb

    tb.write_experiment_config(exp_dir, searchspace)


def build_summary(exp_dir: str, env=None) -> Dict[str, Any]:
    """Aggregate every trial dir's .hparams.json/.outputs.json into one
    summary (reference `util.py:126-148`)."""
    from maggy_tpu.core.environment import EnvSing

    env = env or EnvSing.get_instance()
    combos = []
    for entry in env.ls(exp_dir):
        tdir = os.path.join(exp_dir, entry)
        hparams_p, outputs_p = tdir + "/.hparams.json", tdir + "/.outputs.json"
        if env.isdir(tdir) and env.exists(outputs_p):
            combo = {"id": entry}
            if env.exists(hparams_p):
                combo["hparams"] = json.loads(env.load(hparams_p))
            combo["outputs"] = json.loads(env.load(outputs_p))
            combos.append(combo)
    summary = {"combinations": combos, "built_at": time.time()}
    env.dump(json.dumps(summary, indent=2, default=json_default_numpy),
             exp_dir + "/.summary.json")
    return summary


def progress_bar(done: int, total: int, width: int = 30) -> str:
    frac = 0 if total == 0 else done / total
    filled = int(width * frac)
    return "[{}{}] {}/{}".format("=" * filled, " " * (width - filled), done, total)


def next_run_id(base_dir: str, app_id: str, env=None) -> int:
    """Monotonic run id per app id under the experiment base dir, checked
    through the environment's filesystem (works for gs:// paths too).

    Scan only — racy by construction (two scanners can see the same next
    id). Starters must go through ``claim_run_id``; this stays the read
    path resume uses to FIND the most recent existing run."""
    from maggy_tpu.core.environment import EnvSing

    env = env or EnvSing.get_instance()
    i = 0
    while env.exists("{}/{}_{}".format(base_dir.rstrip("/"), app_id, i)):
        i += 1
    return i


#: Marker claimed atomically inside a run dir by the experiment that owns
#: it (see claim_run_id).
RUN_CLAIM_FILE = ".run_claim"


def find_resume_run_id(base_dir: str, app_id: str, name: str,
                       env=None) -> int:
    """The run id ``resume=True`` should re-enter: the MOST RECENT run of
    this app whose registered experiment NAME matches ``name``.

    The bare most-recent-run rule is wrong the moment one app id hosts
    more than one experiment (fleet tenants share the process app id):
    a resubmitted tenant would re-enter whichever tenant ran LAST and
    replay someone else's journal. The experiment name in each run dir's
    experiment.json is the identity that disambiguates; runs whose
    metadata is missing/torn are skipped (never adopted blind). Raises
    ``ValueError`` when no matching run exists."""
    from maggy_tpu.core.environment import EnvSing

    env = env or EnvSing.get_instance()
    base = base_dir.rstrip("/")
    last = next_run_id(base, app_id, env=env) - 1
    for i in range(last, -1, -1):
        meta_path = "{}/{}_{}/experiment.json".format(base, app_id, i)
        if not env.exists(meta_path):
            continue
        try:
            meta = json.loads(env.load(meta_path))
        except ValueError:
            continue
        if meta.get("name") == name:
            return i
    raise ValueError(
        "resume=True but no previous run of app '{}' named '{}' exists "
        "under {} ({} run dir(s) scanned)".format(app_id, name, base,
                                                  last + 1))


def claim_run_id(base_dir: str, app_id: str, env=None) -> int:
    """Atomically claim the next free run id: scan like ``next_run_id``,
    then stake the run dir with ``AbstractEnv.exclusive_create`` (hard-link
    exclusivity locally, if_generation_match=0 on GCS) so exactly ONE of N
    concurrent starters — two lagom_submit threads, two processes sharing
    a base dir — wins each id; losers move to the next. Closes the
    scan-then-create TOCTOU that could mint the same run id twice (the
    same fix PR 1 applied to DatasetRegistry.register)."""
    import threading

    from maggy_tpu.core.environment import EnvSing

    env = env or EnvSing.get_instance()
    base = base_dir.rstrip("/")
    i = next_run_id(base, app_id, env=env)
    while True:
        run_dir = "{}/{}_{}".format(base, app_id, i)
        if not env.exists(run_dir):
            marker = "{}/{}".format(run_dir, RUN_CLAIM_FILE)
            payload = json.dumps({"claimed_at": time.time(),
                                  "pid": os.getpid(),
                                  "thread": threading.get_ident()})
            if env.exclusive_create(payload, marker):
                return i
        i += 1


#: Prefix of the per-incarnation adoption markers a driver stakes inside
#: its run dir (see claim_driver_epoch).
DRIVER_EPOCH_PREFIX = ".driver_epoch."


def claim_driver_epoch(run_dir: str, env=None) -> int:
    """Atomically claim the next driver incarnation of ``run_dir``.

    Crash-only recovery lets a restarted driver re-enter an existing run
    dir (``resume=True``) — but the resume SCAN in ``next_run_id`` is
    racy by construction, so two restarting drivers can both decide to
    adopt the same run. The ``.run_claim`` marker cannot arbitrate that
    (it already exists — it belongs to the CRASHED incarnation), so
    adoption goes through its own exclusive marker: scan for the highest
    existing ``.driver_epoch.N``, then ``exclusive_create`` N+1. Exactly
    one adopter wins each epoch; the loser gets ``RunAdoptionError`` (a
    clear exit). Scope: this arbitrates CONCURRENT adopters racing for
    the same epoch — a predecessor that claimed earlier and wedged
    without exiting is instead caught by the resume port rebind (a
    still-bound pre-crash port refuses adoption; Driver.init). Fresh
    runs claim epoch 1 the same way — their run dir was staked
    exclusively by ``claim_run_id``, so the claim cannot race.

    Returns the claimed epoch (1-based)."""
    import threading

    from maggy_tpu.core.environment import EnvSing
    from maggy_tpu.exceptions import RunAdoptionError

    env = env or EnvSing.get_instance()
    run_dir = run_dir.rstrip("/")
    epoch = 1
    while env.exists("{}/{}{}".format(run_dir, DRIVER_EPOCH_PREFIX, epoch)):
        epoch += 1
    payload = json.dumps({"claimed_at": time.time(), "pid": os.getpid(),
                          "thread": threading.get_ident()})
    marker = "{}/{}{}".format(run_dir, DRIVER_EPOCH_PREFIX, epoch)
    if not env.exclusive_create(payload, marker):
        raise RunAdoptionError(
            "run dir {} was adopted by another driver (incarnation marker "
            "{} already claimed); exactly one restarted driver may adopt "
            "a run — this one must exit".format(run_dir,
                                                marker.rsplit("/", 1)[-1]))
    return epoch


def cpu_first_platform() -> bool:
    """Does JAX_PLATFORMS put ``cpu`` first — a CPU test or rehearsal? The
    one home of that rule: such runs keep the persistent compile cache off
    by default, and a pinned runner's chip pin is only a marker there."""
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu"


#: Where the persistent XLA compilation cache lives when the environment
#: does not place it: one fixed directory inside the checkout (git-ignored).
#: The path must not move between runs or processes, so it is derived from
#: the package's location and never from a temp dir, a pid or a clock.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Arm JAX's persistent XLA compilation cache.

    64 concurrent trials with differing hparams compile distinct XLA
    programs (SURVEY.md §7.3 "compile-cache churn"); a shared on-disk cache
    lets runner processes — and successive trials with recurring shapes —
    reuse compiled executables instead of compiling again.

    The directory is placed from outside: when ``JAX_COMPILATION_CACHE_DIR``
    is set JAX has already taken it at import and no directory is set in
    code; otherwise it is ``COMPILE_CACHE_DIR``. Either way the two
    persistence thresholds drop to 0 so every program is cached (trial
    workloads are small; the defaults skip sub-second compiles). Call it
    before the process compiles anything: JAX decides once, at its first
    compile, whether the cache is in use.

    Safe to call repeatedly; disabled by MAGGY_TPU_NO_COMPILE_CACHE=1, and
    by default on CPU-only runs (XLA:CPU AOT cache entries embed host ISA
    features and warn, or SIGILL, on reuse across machines). Returns the
    cache dir, or None when disabled. A directory that cannot be created
    is reported with a warning, never silently.
    """
    if os.environ.get("MAGGY_TPU_NO_COMPILE_CACHE") == "1":
        return None
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        if cpu_first_platform():
            return None
        cache_dir = COMPILE_CACHE_DIR
        try:
            os.makedirs(cache_dir, exist_ok=True)
        except OSError as e:
            import warnings

            warnings.warn(
                "persistent compile cache NOT armed: cannot create {} ({!r}); "
                "set JAX_COMPILATION_CACHE_DIR to a writable directory"
                .format(cache_dir, e), stacklevel=2)
            return None
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir

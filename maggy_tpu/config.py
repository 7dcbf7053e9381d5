"""Typed experiment configs; the config type selects the experiment kind.

Parity: reference `maggy/experiment_config.py:18-81` (LagomConfig base,
OptimizationConfig, AblationConfig, DistributedConfig). Redesigned for TPU:
``DistributedConfig`` describes a JAX mesh + sharding strategy instead of a
torch module, and every config carries ``num_workers`` explicitly (the
reference infers it from Spark dynamic-allocation settings,
`hopsworks.py:236-244`, which has no TPU analogue).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Sequence, Union

from maggy_tpu import constants
from maggy_tpu.searchspace import Searchspace


@dataclass
class LagomConfig:
    """Base config (reference `experiment_config.py:18-23`)."""

    name: str = "maggyTpuExperiment"
    description: str = ""
    hb_interval: float = constants.DEFAULT_HEARTBEAT_INTERVAL_S
    #: Print a live progress line while the experiment runs (the reference
    #: streams a progress bar to Jupyter, `util.py:71-86`).
    verbose: bool = False
    #: Unified telemetry (maggy_tpu.telemetry): trial-span tracing, metric
    #: registry, and the <exp_dir>/telemetry.jsonl journal the TELEM RPC
    #: verb / `monitor --telem` / bench.py read. Record paths are
    #: buffer-only (journal writes happen on a background flusher), so the
    #: default-on cost on the message hot path is a few dict ops.
    telemetry: bool = True
    #: Heartbeat-loss detection shape (used when ``hb_loss_timeout`` is
    #: None): a runner is declared lost after
    #: max(hb_loss_min_s, hb_interval * hb_loss_factor) seconds of
    #: silence. Overridable per experiment so soak/chaos tests can tighten
    #: failure detection without monkeypatching module globals.
    hb_loss_factor: float = constants.HEARTBEAT_LOSS_FACTOR
    hb_loss_min_s: float = constants.HEARTBEAT_LOSS_MIN_S
    #: Fault injection (maggy_tpu.chaos): a FaultPlan instance or a path
    #: to a plan JSON. None (default) = every chaos hook is a no-op. Also
    #: armable without touching code via MAGGY_TPU_CHAOS=<plan.json>.
    chaos: Any = None
    #: Live health engine (maggy_tpu.telemetry.health): periodic
    #: straggler/hang/RTT-degradation analysis over spans + runner stats,
    #: journaled as ``health`` events and surfaced via TELEM /
    #: ``monitor --health``. Requires telemetry; off when telemetry is.
    health: bool = True
    #: Seconds between health checks; None -> max(0.25, hb_interval).
    health_interval_s: Optional[float] = None
    #: Hang watchdog: a partition holding a trial with no journal progress
    #: for ``health_hang_factor * hb_interval`` seconds is flagged (with a
    #: faulthandler thread dump journaled). Deliberately below the
    #: heartbeat-loss shape so sub-loss stalls — which the loss scan can
    #: never see — still surface.
    health_hang_factor: float = 25.0

    #: Live observability plane (maggy_tpu.telemetry.obs): an HTTP server
    #: exposing GET /metrics (Prometheus text format), /status (TELEM
    #: snapshot + live trial-store/reservation/gang/fleet state),
    #: /healthz (200/503 from the health engine's raised findings) and
    #: /profilez (on-demand jax.profiler capture). None (the default) =
    #: OFF: no socket is opened and behavior is bit-for-bit unchanged.
    #: 0 = bind an ephemeral port (journaled as an ``obs_started`` event
    #: so tools can discover it). Also armable without touching code via
    #: MAGGY_TPU_OBS_PORT. One obs server per process — a second
    #: experiment in the same process joins the first one's listener.
    obs_port: Optional[int] = None
    #: Obs bind host. Loopback by default: the endpoints are
    #: unauthenticated (Prometheus-style), so exposing them beyond the
    #: host is an explicit operator decision.
    obs_host: str = "127.0.0.1"

    #: Shared-fleet attachment (maggy_tpu.fleet): a FleetBinding placed
    #: here by ``experiment.lagom_submit`` / ``Fleet.submit`` makes the
    #: driver LEASE runners from the fleet scheduler (weighted fair share,
    #: priority classes, quotas, checkpoint-assisted preemption) and
    #: publish its RPC server on the fleet's shared listener. None (the
    #: default, and always the case for plain ``lagom()``) preserves the
    #: classic single-tenant behavior bit-for-bit — ``lagom()`` is simply
    #: a fleet of one that owns its pool.
    fleet: Any = None
    #: Fleet journal-sink routing (maggy_tpu.telemetry.sink): True makes
    #: a FLEET-ATTACHED experiment ship its telemetry journal to the
    #: fleet's journal sink over the shared socket (one process-wide
    #: shipper thread, no per-tenant flusher — what re-enables telemetry
    #: for 500-tenant churn) instead of writing <exp_dir>/telemetry.jsonl
    #: directly; that local path becomes the degradation fallback the
    #: shipper falls back to (and re-ships from) when the sink is down.
    #: Ignored (plain local journal) without a fleet or with the fleet's
    #: sink disabled. Default False: bit-for-bit the classic layout.
    sink: bool = False

    def resolved_obs_port(self) -> Optional[int]:
        """The observability server port to bind, or None for off: the
        explicit ``obs_port`` field when set, else MAGGY_TPU_OBS_PORT
        (empty/unparsable = off). The ONE home of this resolution — the
        drivers and the fleet both consult it."""
        if self.obs_port is not None:
            return int(self.obs_port)
        return resolved_env_obs_port()

    def resolved_hb_loss_timeout(self) -> float:
        """Seconds of heartbeat silence before a runner/worker is
        declared lost: the explicit ``hb_loss_timeout`` field when set
        (OptimizationConfig/DistributedConfig), else the configured shape
        max(hb_loss_min_s, hb_interval * hb_loss_factor). The ONE home of
        this resolution — both driver families consult it."""
        explicit = getattr(self, "hb_loss_timeout", None)
        if explicit is not None:
            return float(explicit)
        return max(self.hb_loss_min_s,
                   self.hb_interval * self.hb_loss_factor)


def resolved_env_obs_port() -> Optional[int]:
    """MAGGY_TPU_OBS_PORT as an int, or None when unset/empty/garbage."""
    raw = os.environ.get("MAGGY_TPU_OBS_PORT", "").strip()
    if not raw:
        return None
    try:
        return int(raw)
    except ValueError:
        return None


@dataclass
class OptimizationConfig(LagomConfig):
    """Hyperparameter-optimization experiment (reference `experiment_config.py:25-50`).

    ``optimizer`` is a registry name ("randomsearch", "gridsearch", "asha",
    "tpe", "gp", "none") or an AbstractOptimizer instance. ``num_workers`` is
    the number of concurrent trial runners (local processes or TPU sub-slice
    agents); it is clamped to ``num_trials`` by the driver.
    """

    num_trials: int = 1
    optimizer: Union[str, Any] = "randomsearch"
    searchspace: Optional[Searchspace] = None
    optimization_key: str = "metric"
    direction: str = "max"
    es_interval: int = constants.DEFAULT_ES_INTERVAL
    es_min: int = constants.DEFAULT_ES_MIN
    es_policy: Union[str, Any] = constants.DEFAULT_ES_POLICY
    # Concurrent trial runners, or "auto" to size from the runtime device
    # inventory (one runner per local chip subset for pool="tpu", one per
    # local device otherwise) — the reference reads its executor count
    # from cluster conf at runtime (`hopsworks.py:236-244`).
    num_workers: Union[int, str] = 1
    seed: Optional[int] = None
    # Runner substrate: "thread" (in-process), "process" (one JAX runtime
    # per trial), "tpu" (processes pinned to disjoint chip sub-slices),
    # "remote" (external `python -m maggy_tpu.runner` agents join over DCN).
    pool: str = "thread"
    # Control-plane bind host. Defaults to loopback for local pools; set to
    # "0.0.0.0" (the default when pool="remote") to accept remote agents.
    bind_host: Optional[str] = None
    # Per-trial device assignment: how many TPU chips each trial gets
    # (used by pool="tpu").
    chips_per_trial: int = 1
    # Multi-chip trial sizing: budget -> chip need. Two mechanisms share
    # the declaration, selected by the pool:
    # - pool="elastic" (int values): budget-sized chip sub-slices —
    #   runners exit and respawn re-pinned when their capacity doesn't
    #   match the next trial's requirement (SURVEY §7.3's
    #   slice-repartitioning problem). Budgets missing from the map use
    #   chips_per_trial.
    # - pool="thread" / fleet mode (int or maggy_tpu.gang.GangSpec
    #   values): GANG SCHEDULING — the driver assembles N fleet runners
    #   (runner ≈ chip) into one contiguous mesh slice, dispatches the
    #   trial to a designated leader (ctx.gang carries the mesh axes +
    #   strategy), and holds the members until the trial releases. A
    #   bare int N is shorthand for GangSpec(N) (dp mesh). Packing is
    #   topology-aware (best-fit aligned contiguous blocks, journaled
    #   pack events — see docs/user.md "Multi-chip sweeps").
    # A Searchspace GANG entry declares the same thing per trial instead
    # of per budget (and lets the sweep SEARCH over sharding shapes).
    chips_per_budget: Optional[Dict[Any, Any]] = None
    # Total chips the elastic pool may lease (None -> probe the host).
    total_chips: Optional[int] = None
    # Pipelined trial hand-off: the driver pre-materializes controller
    # suggestions on a dedicated suggester thread (up to one per live
    # runner) and the FINAL reply carries the next TRIAL (or GSTOP)
    # inline, so the common hand-off costs zero extra round trips and
    # never waits on a model fit. GET polling remains the fallback
    # (registration, idle wake-ups, requeues). False restores the
    # synchronous pre-pipelining behavior exactly; controllers that
    # override get_suggestion wholesale (no report/suggest split) fall
    # back automatically. See docs/telemetry.md "Hand-off path".
    prefetch: bool = True
    # Compile-once hot path (train/warm.py): runners keep the compiled
    # train step, the computed shardings and the two init programs
    # resident across trials whose program identity matches (model config,
    # mesh topology, strategy, input shapes, swept-optimizer family), so a
    # repeat-shape trial's time-to-first-metric drops from a fresh XLA
    # trace+compile to near dispatch cost. Only programs are kept: every
    # trial's state is initialized anew from its rng and freed when the
    # trial ends. False restores the build-per-trial behavior bit-for-bit.
    warm_start: bool = True
    # Checkpoint-forking search (docs/user.md "Forking search"): an ASHA
    # promotion / PBT exploit-or-continue segment / BO near-duplicate is
    # dispatched with ``forked_from`` + ``resume_step`` stamped into its
    # assignment, the executor stages the parent's checkpoint into the
    # child's trial dir (train/checkpoint.fork_checkpoint), and a ctx-
    # aware train fn RESUMES from that step instead of re-training the
    # parent's prefix — at the top ASHA rungs this recovers the
    # rung-ratio multiple of compute. Requires the train fn to
    # checkpoint via ctx (fns that never checkpoint simply run from
    # scratch — the stamp resolves to no checkpoint and is skipped).
    # False restores from-scratch promotions bit-for-bit.
    fork: bool = True
    # Vectorized micro-trials (docs/user.md "Vectorized sweeps"): the
    # driver packs up to this many COMPATIBLE suggestions (same
    # non-float params, same budget, no gang spec — the driver-side
    # proxy for the warm-cache program key) into one block and delivers
    # the whole block to one runner in a single TRIAL; the executor runs
    # all lanes in lockstep as ONE vmapped program (train/vmap.py), so a
    # small-model sweep fills the chip across the hyperparameter axis
    # instead of one trial at a time. Early stop masks a lane without
    # recompiling; each lane keeps its own span/METRIC/FINAL. 1 (the
    # default) disables block assembly and restores the scalar dispatch
    # path bit-for-bit.
    vmap_lanes: int = 1
    # Capture a jax.profiler trace per trial into its TensorBoard dir.
    profile: bool = False
    # Tee the user train_fn's print() calls into the reporter log channel,
    # streaming them to the driver/monitor on heartbeats (the reference
    # ships prints to Jupyter by patching builtins.print,
    # `trial_executor.py:71-81`). Off by default: reporter.log() is the
    # explicit channel; this flag restores the reference behavior.
    ship_prints: bool = False
    # Declare a runner lost after this many seconds of heartbeat silence
    # while holding a trial (its trial is requeued to another runner).
    # None -> max(HEARTBEAT_LOSS_MIN_S, hb_interval * HEARTBEAT_LOSS_FACTOR).
    hb_loss_timeout: Optional[float] = None
    # Experiment artifact root; defaults to the environment's base dir.
    experiment_dir: Optional[str] = None
    # Resume the most recent interrupted run of this app: finalized trials
    # are reloaded from their trial.json artifacts and skipped; unfinished
    # ones re-run. Pruner (Hyperband/ASHA bracket) state restores from its
    # checkpoint; sampling optimizers must carry a fixed seed.
    resume: bool = False

    def __post_init__(self):
        if self.direction not in ("max", "min"):
            raise ValueError("direction must be 'max' or 'min', got {!r}".format(self.direction))
        if self.pool not in ("thread", "process", "tpu", "elastic", "remote"):
            raise ValueError(
                "pool must be 'thread', 'process', 'tpu', 'elastic', or "
                "'remote'")
        if not isinstance(self.vmap_lanes, int) \
                or isinstance(self.vmap_lanes, bool) or self.vmap_lanes < 1:
            raise ValueError(
                "vmap_lanes must be an int >= 1 (1 = scalar dispatch), "
                "got {!r}".format(self.vmap_lanes))
        if self.vmap_lanes > 1 and self.chips_per_budget is not None:
            raise ValueError(
                "vmap_lanes packs K trials onto ONE chip; gang-scheduled "
                "sweeps (chips_per_budget) size trials the other way — "
                "pick one")
        if self.chips_per_budget is not None and \
                self.pool not in ("elastic", "thread"):
            raise ValueError(
                "chips_per_budget needs pool='elastic' (budget-sized "
                "respawnable pinned workers) or pool='thread' "
                "(gang-scheduled runner groups); got pool={!r}".format(
                    self.pool))
        if self.chips_per_budget is not None and self.pool == "elastic":
            from maggy_tpu.gang import GangSpec

            if any(isinstance(v, (GangSpec, dict))
                   for v in self.chips_per_budget.values()):
                raise ValueError(
                    "GangSpec chips_per_budget values gang-schedule fleet "
                    "runners and need pool='thread' (or fleet mode); the "
                    "elastic pool respawns single pinned runners from int "
                    "chip counts")
        if self.searchspace is not None:
            gang_names = [n for n in self.searchspace.names()
                          if self.searchspace.get_type(n) == "GANG"]
            if gang_names and self.pool not in ("thread",):
                raise ValueError(
                    "a Searchspace GANG entry gang-schedules fleet runners "
                    "and needs pool='thread' (or fleet mode); got "
                    "pool={!r}".format(self.pool))
            if len(gang_names) > 1:
                raise ValueError(
                    "at most one Searchspace GANG entry per sweep (a trial "
                    "runs on one gang); got {}".format(gang_names))
        if isinstance(self.num_workers, str) and self.num_workers != "auto":
            raise ValueError(
                "num_workers must be an int or 'auto', got {!r}".format(
                    self.num_workers))
        if self.bind_host is None and self.pool == "remote":
            self.bind_host = "0.0.0.0"


@dataclass
class AblationConfig(OptimizationConfig):
    """Ablation-study experiment (reference `experiment_config.py:52-66`).

    Subclasses OptimizationConfig for the shared driver-plumbing fields
    (num_workers/pool/direction/...); `optimizer` and the early-stop knobs
    are ignored — ablation schedules are fixed and never early-stop
    (reference `ablation_driver.py:33`).
    """

    ablation_study: Any = None
    ablator: Union[str, Any] = "loco"
    es_policy: str = "none"


@dataclass
class DistributedConfig(LagomConfig):
    """Distributed data/model-parallel training of ONE model (reference
    `experiment_config.py:68-81`, where it carried a torch module + datasets).

    TPU-native version: the user's ``train_fn`` receives a `ShardingEnv`
    (mesh + named shardings + process info) instead of a DDP-wrapped model;
    gradients flow over ICI via XLA collectives inserted by GSPMD.
    """

    #: Flax module / model spec forwarded to the train function.
    model: Any = None
    train_set: Any = None
    test_set: Any = None
    #: Number of participating processes (multi-host world size).
    num_workers: int = 1
    #: Logical mesh axes, e.g. {"data": 8} or {"data": 4, "model": 2}.
    mesh_shape: Dict[str, int] = field(default_factory=dict)
    #: Parallelism strategy name: "dp", "fsdp", "tp", "dp_tp", "sp".
    strategy: str = "dp"
    #: Worker substrate: None/"process" (local processes), "thread" (tests),
    #: "remote" (external `python -m maggy_tpu.runner` agents over DCN).
    backend: Optional[str] = None
    #: Control-plane bind host; defaults to 0.0.0.0 when backend="remote".
    bind_host: Optional[str] = None
    #: Declare a worker dead after this many seconds of heartbeat silence
    #: (the experiment fails — a dead SPMD rank wedges the world).
    #: None -> max(HEARTBEAT_LOSS_MIN_S, hb_interval * HEARTBEAT_LOSS_FACTOR).
    hb_loss_timeout: Optional[float] = None
    #: Capture a jax.profiler trace per worker into the experiment dir.
    profile: bool = False
    experiment_dir: Optional[str] = None

    def __post_init__(self):
        if self.bind_host is None and self.backend == "remote":
            self.bind_host = "0.0.0.0"

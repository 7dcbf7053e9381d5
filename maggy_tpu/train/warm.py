"""Warm-state runner harness: compile-once trial hot path.

ROADMAP item 3. Every trial used to pay a fresh XLA trace+compile (and a
fresh sharded init) for a program byte-identical to the previous trial's —
a time-to-first-metric stall that dwarfs the hand-off PR 4 shortened. The
fix is the pjit idiom ("Scalable Training of Language Models using JAX pjit
and TPUv4", PAPERS.md): program identity is
pinned by *shapes and mesh topology*, not hyperparameter values, so a
runner that keeps the compiled program resident (Podracer-style persistent
actors) only recompiles when the program actually changes.

This module is the mechanism; `train/trainer.py` is the policy:

- ``WarmCache`` — a bounded (LRU, default 4 programs) per-process registry
  of ``WarmSlot`` objects keyed by program identity. A long-lived fleet
  runner serving many experiments must not grow without bound; evicting a
  slot drops its executables. ``clear()`` empties it (exported as
  ``maggy_tpu.train.clear_warm``).
- ``WarmSlot`` — what a repeat-shape trial can reuse, all of it identity
  and none of it value: the jitted step, per-shape AOT-compiled
  executables (the K-lane vectorized one among them, under a key that
  carries the lane count), and per-input-shape init entries (jitted
  initializer + computed shardings, so ``jax.eval_shape`` + unboxing are
  skipped, and the family's jitted optimizer init). A slot never holds an
  array of a trial: a trial's state is freed when its ``Trainer`` is
  dropped.
- **Trial scope** — the executor wraps each trial in ``trial_scope`` so
  warm behavior follows ``config.warm_start`` and compile telemetry lands
  in the trial's ``RunnerStats``.
- **Counters** — warm-slot hits/misses and the persistent XLA compilation
  cache's hits/misses, counted through ``jax.monitoring`` event listeners
  (the warm cache emits ``/maggy_tpu/warm_slot/{hit,miss}`` events; JAX
  itself emits ``/jax/compilation_cache/cache_{hits,misses}``). Counts are
  attributed to the current thread's trial scope (per-runner stats shipped
  on heartbeats) and mirrored in process-global counters for library use.

``MAGGY_TPU_WARM_SLOTS`` overrides the LRU bound.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, Optional, Tuple

#: Default LRU bound: distinct programs kept warm per runner process.
DEFAULT_WARM_SLOTS = 4

#: Per-slot bound on AOT-compiled step executables / init entries (one per
#: distinct input-shape signature within one program family).
PER_SLOT_SHAPES = 8

#: jax.monitoring event names the warm cache emits (counted by the same
#: listener that counts JAX's persistent-compilation-cache events).
WARM_HIT_EVENT = "/maggy_tpu/warm_slot/hit"
WARM_MISS_EVENT = "/maggy_tpu/warm_slot/miss"

#: Counter keys shipped in runner stats / returned by ``counters()``.
COUNTER_KEYS = ("warm_hits", "warm_misses", "xla_cache_hits",
                "xla_cache_misses")

_local = threading.local()

_counters_lock = threading.Lock()
_counters: Dict[str, int] = {k: 0 for k in COUNTER_KEYS}

_listener_lock = threading.Lock()
_listener_installed = False


# --------------------------------------------------------------- trial scope

class _TrialScope:
    __slots__ = ("trial_id", "enabled", "stats")

    def __init__(self, trial_id, enabled, stats):
        self.trial_id = trial_id
        self.enabled = enabled
        self.stats = stats


def current_scope() -> Optional[_TrialScope]:
    return getattr(_local, "scope", None)


class trial_scope:
    """Context manager the trial executor wraps around one train_fn call.

    Arms the thread's warm behavior (``enabled`` mirrors
    ``config.warm_start``) and routes compile telemetry to ``stats`` (a
    ``RunnerStats``)."""

    def __init__(self, trial_id: Optional[str] = None, enabled: bool = True,
                 stats=None):
        self._scope = _TrialScope(trial_id, enabled, stats)

    def __enter__(self) -> "_TrialScope":
        self._prev = getattr(_local, "scope", None)
        _local.scope = self._scope
        return self._scope

    def __exit__(self, exc_type, exc, tb) -> None:
        _local.scope = self._prev


def enabled() -> bool:
    """Is the warm path armed for this thread? The trial scope's flag when
    inside one (``config.warm_start``); outside a trial it is on, and
    ``Trainer(warm_start=False)`` is the way to turn it off."""
    scope = current_scope()
    return scope.enabled if scope is not None else True


def note_compile(**fields: Any) -> None:
    """Record compile-phase telemetry for the current trial (merged into
    its RunnerStats ``compile`` record; ``*_ms`` fields accumulate)."""
    scope = current_scope()
    stats = scope.stats if scope is not None else None
    if stats is not None:
        stats.note_compile(**fields)


def span(name: str, **attrs: Any):
    """`telemetry.runnerstats.span` for the current trial: the phase is
    annotated in the profiler's trace and, inside a trial scope, timed
    into its RunnerStats record (``*_ms`` and ``spans``)."""
    from maggy_tpu.telemetry.runnerstats import span as _span

    scope = current_scope()
    return _span(name, stats=scope.stats if scope is not None else None,
                 **attrs)


# ----------------------------------------------------------------- counters

def _count(key: str, n: int = 1) -> None:
    with _counters_lock:
        _counters[key] = _counters.get(key, 0) + n
    scope = current_scope()
    stats = scope.stats if scope is not None else None
    if stats is not None:
        stats.note_counter(key, n)


def counters() -> Dict[str, int]:
    """Process-global warm/compile-cache counter snapshot."""
    with _counters_lock:
        return dict(_counters)


def _monitoring_listener(event: str, **kwargs: Any) -> None:
    if event == WARM_HIT_EVENT:
        _count("warm_hits")
    elif event == WARM_MISS_EVENT:
        _count("warm_misses")
    elif event == "/jax/compilation_cache/cache_hits":
        _count("xla_cache_hits")
    elif event == "/jax/compilation_cache/cache_misses":
        _count("xla_cache_misses")


def install_monitoring_listener() -> bool:
    """Register the jax.monitoring event listener that turns warm-slot and
    persistent-compilation-cache events into counters. Idempotent; never
    fatal (counting is an observability feature, not a dependency)."""
    global _listener_installed
    with _listener_lock:
        if _listener_installed:
            return True
        try:
            from jax import monitoring

            monitoring.register_event_listener(_monitoring_listener)
            _listener_installed = True
            return True
        except Exception:  # noqa: BLE001 - jax absent/ancient: count nothing
            return False


def record_warm_event(hit: bool) -> None:
    """Emit the warm-slot hit/miss jax.monitoring event (counted by the
    installed listener). Falls back to direct counting if the event bus is
    unavailable."""
    if install_monitoring_listener():
        from jax import monitoring

        monitoring.record_event(WARM_HIT_EVENT if hit else WARM_MISS_EVENT)
    else:
        _count("warm_hits" if hit else "warm_misses")


# -------------------------------------------------------------- program keys

def shape_key(tree) -> str:
    """Hashable signature of a pytree's structure + leaf shapes/dtypes —
    the per-shape identity AOT executables and init entries key on."""
    import jax
    import numpy as np

    leaves, treedef = jax.tree_util.tree_flatten(tree)

    def sig(x):
        shape = getattr(x, "shape", None)
        if shape is None:
            shape = np.shape(x)
        dtype = getattr(x, "dtype", None)
        if dtype is None:
            dtype = np.asarray(x).dtype
        return (tuple(shape), str(dtype))

    return repr((treedef, [sig(x) for x in leaves]))


def swept_info(tx) -> Optional[Dict[str, Any]]:
    """The metadata ``swept_transform`` attached to a transform whose
    hyperparameters are traced inputs, or None for a plain transform."""
    return getattr(getattr(tx, "init", None), "_maggy_swept", None)


def opt_family(tx) -> Optional[tuple]:
    """Value-independent optimizer identity: transforms built by
    ``swept_transform`` from the same factory with the same hyperparameter
    NAMES (and identical repr-stable non-numeric statics) share a family —
    their opt_state structure and the compiled program are identical, only
    the traced hyperparam values differ. None for plain transforms AND for
    swept transforms with object-repr statics (schedules, callables): no
    safe cross-object sharing — constants may be baked into the program,
    and an id-bearing repr would mint a never-matching family per trial."""
    info = swept_info(tx)
    return None if info is None else info["family"]


def rebind_hyperparams(opt_state, hparams: Dict[str, Any]):
    """Return ``opt_state`` with the injected-hyperparameter leaves
    (``optax.inject_hyperparams`` state anywhere inside a chain) replaced
    by ``hparams``' values, preserving leaf dtypes. The init entry's jitted
    optimizer init traced the family's FIRST transform, so its constants
    are overwritten with this trial's."""
    import jax.numpy as jnp

    def rebind(state):
        if hasattr(state, "_replace") and hasattr(state, "_fields"):
            updates = {}
            for f in state._fields:
                v = getattr(state, f)
                if f == "hyperparams" and isinstance(v, dict):
                    new = dict(v)
                    for k, hv in hparams.items():
                        if k in new:
                            new[k] = jnp.asarray(
                                hv, getattr(new[k], "dtype", None))
                    updates[f] = new
                elif isinstance(v, (tuple, list)):
                    updates[f] = rebind(v)
            return state._replace(**updates) if updates else state
        if isinstance(state, (tuple, list)):
            return type(state)(rebind(s) for s in state)
        return state

    return rebind(opt_state)


# -------------------------------------------------------------- cache/slots

class _InitEntry:
    """Per-(program, input-shape) reusable init programs: the jitted
    initializer, the computed shardings (skipping eval_shape + unboxing on
    reuse), and the family's jitted optimizer init."""

    __slots__ = ("init_jit", "shardings", "opt_init")

    def __init__(self, init_jit, shardings):
        self.init_jit = init_jit
        self.shardings = shardings
        # (family, jitted ``tx.init`` of the first transform of that
        # family seen here), one tuple so that runner threads sharing the
        # slot read and replace it whole. The per-trial hyperparameter
        # values are rebound after the call.
        self.opt_init: Optional[tuple] = None


class WarmSlot:
    """One program family's warm state. ``step_jit`` is shared by every
    trial of the family (jax.jit re-traces per input shape internally);
    ``compiled`` holds the AOT-split executables per shape so repeat
    trials skip trace AND compile (a vectorized block's K-lane
    executable too, train/vmap.py); ``inits`` holds per-input-shape init
    entries."""

    __slots__ = ("key", "lock", "step_jit", "compiled", "inits", "aot_ok",
                 "aot_lock")

    def __init__(self, key):
        self.key = key
        self.lock = threading.Lock()
        self.step_jit = None  # guarded-by: lock
        self.compiled: "OrderedDict[str, Any]" = OrderedDict()  # guarded-by: lock
        self.inits: "OrderedDict[Any, _InitEntry]" = OrderedDict()  # guarded-by: lock
        self.aot_ok = True
        # Serializes AOT lower+compile per slot: N thread-pooled runners
        # whose first trials race the same program must produce ONE
        # compile, not N concurrent ones (the plain-jit path gets the
        # same guarantee from pjit's internal cache locking).
        self.aot_lock = threading.Lock()

    def ensure_step(self, build: Callable[[], Any]):
        with self.lock:
            if self.step_jit is None:
                self.step_jit = build()
            return self.step_jit

    def init_entry(self, key, build: Callable[[], _InitEntry]
                   ) -> Tuple[_InitEntry, bool]:
        """Get-or-build the init entry for one input-shape signature;
        returns (entry, hit)."""
        with self.lock:
            entry = self.inits.get(key)
            if entry is not None:
                self.inits.move_to_end(key)
                return entry, True
        built = build()
        with self.lock:
            entry = self.inits.get(key)
            if entry is None:
                entry = built
                self.inits[key] = entry
                while len(self.inits) > PER_SLOT_SHAPES:
                    self.inits.popitem(last=False)
            return entry, False

    def get_init(self, key) -> Optional[_InitEntry]:
        with self.lock:
            return self.inits.get(key)

    def compiled_step(self, key: str):
        with self.lock:
            fn = self.compiled.get(key)
            if fn is not None:
                self.compiled.move_to_end(key)
            return fn

    def store_compiled(self, key: str, fn) -> None:
        with self.lock:
            self.compiled[key] = fn
            while len(self.compiled) > PER_SLOT_SHAPES:
                self.compiled.popitem(last=False)


class WarmCache:
    """Bounded LRU of warm slots keyed by program identity."""

    def __init__(self, maxsize: Optional[int] = None):
        if maxsize is None:
            maxsize = int(os.environ.get("MAGGY_TPU_WARM_SLOTS",
                                         DEFAULT_WARM_SLOTS))
        self.maxsize = max(1, maxsize)
        self._lock = threading.Lock()
        self._slots: "OrderedDict[Any, WarmSlot]" = OrderedDict()  # guarded-by: _lock

    def slot(self, key) -> Tuple[WarmSlot, bool]:
        """Get-or-create the slot for ``key``; returns (slot, existed)."""
        with self._lock:
            slot = self._slots.get(key)
            if slot is not None:
                self._slots.move_to_end(key)
                return slot, True
            slot = WarmSlot(key)
            self._slots[key] = slot
            while len(self._slots) > self.maxsize:
                self._slots.popitem(last=False)
            return slot, False

    def clear(self) -> None:
        with self._lock:
            self._slots.clear()

    def keys(self):
        with self._lock:
            return list(self._slots)

    def __len__(self) -> int:
        with self._lock:
            return len(self._slots)


_CACHE = WarmCache()


def warm_cache() -> WarmCache:
    return _CACHE


def clear_warm() -> None:
    """Drop every warm slot (compiled executables, shardings). The
    explicit unbounded-growth escape hatch for long-lived fleet runners,
    and the isolation reset tests/benches use between A/B arms. Exported as ``maggy_tpu.train.clear_warm``."""
    _CACHE.clear()

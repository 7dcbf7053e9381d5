"""Sharded training: init + jitted step over a named mesh.

This is the TPU data plane the reference delegates to torch DDP
(`dist_executor.py:102,197-223`): params are initialized straight into their
GSPMD shardings (derived from the model zoo's logical annotations), the
train step is one jit with donated state, and XLA emits the gradient
collectives over ICI — there is no wrapper class around the model.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from maggy_tpu.parallel.sharding import logical_axis_rules


def cross_entropy_loss(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    return -jnp.mean(jnp.sum(onehot * logp, axis=-1))


def next_token_loss(logits, tokens):
    """Causal LM loss: predict tokens[t+1] from logits[t]."""
    return cross_entropy_loss(logits[:, :-1], tokens[:, 1:])


def _unbox_and_specs(variables, mesh, strategy):
    """Split flax's Partitioned boxes into (plain pytree, NamedShardings)."""
    import flax.linen as nn
    from jax.sharding import NamedSharding, PartitionSpec as P

    rules = dict(logical_axis_rules(strategy))

    def to_sharding(leaf):
        if isinstance(leaf, nn.Partitioned):
            spec = tuple(rules.get(n, None) if n else None for n in leaf.names)
            # Drop mesh axes that don't exist on this mesh.
            spec = tuple(s if s in mesh.axis_names else None for s in spec)
            return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())

    shardings = jax.tree_util.tree_map(
        to_sharding, variables,
        is_leaf=lambda x: isinstance(x, nn.Partitioned))
    plain = jax.tree_util.tree_map(
        lambda x: x.value if isinstance(x, nn.Partitioned) else x,
        variables, is_leaf=lambda x: isinstance(x, nn.Partitioned))
    return plain, shardings


def init_train_state(
    model,
    tx,
    rng,
    example_inputs: Tuple,
    mesh,
    strategy: str = "dp",
    init_kwargs: Optional[Dict[str, Any]] = None,
    cache_key: Optional[tuple] = None,
):
    """Initialize (params, opt_state) directly INTO their shardings.

    Returns (params, opt_state, shardings) where params is the full flax
    variables dict minus boxes. ``cache_key`` shares the jitted initializer
    across trials of a sweep (same contract as Trainer's step_key); the
    shared entries live in the bounded warm cache (train/warm.py), so a
    fleet runner serving many programs no longer grows without bound.
    """
    from maggy_tpu.train import warm as _warm

    init_kwargs = init_kwargs or {}
    if cache_key is not None:
        slot, _ = _warm.warm_cache().slot(
            ("manual_init", cache_key, model, mesh, strategy))
    else:
        # Uncached: a private throwaway slot — ONE init sequence lives in
        # _init_state_via_slot, so the legacy and warm paths cannot
        # diverge (the bit-for-bit promise of warm_start=False).
        slot = _warm.WarmSlot(None)
    params, opt_state, shardings, _hit, _ikey = _init_state_via_slot(
        slot, model, tx, rng, example_inputs, mesh, strategy,
        init_kwargs)
    return params, opt_state, shardings


def _opt_init_program(tx, psub, param_shardings, mesh):
    """The family's jitted ``tx.init``. Under jit the moments' ``zeros_like``
    no longer sees where the parameters live, so the program says it:
    sub-trees shaped like the parameters shard as the parameters do (where
    an eager ``tx.init`` puts them), every other leaf is replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    pleaves, pdef = jax.tree_util.tree_flatten(psub)
    pshapes = [x.shape for x in pleaves]

    def like_params(x):
        leaves, treedef = jax.tree_util.tree_flatten(x)
        return treedef == pdef and [np.shape(v) for v in leaves] == pshapes

    replicated = NamedSharding(mesh, P())
    out_shardings = jax.tree_util.tree_map(
        lambda x: param_shardings if like_params(x) else replicated,
        jax.eval_shape(tx.init, psub), is_leaf=like_params)

    def init_opt_state(p):
        return tx.init(p)

    return jax.jit(init_opt_state, out_shardings=out_shardings)


def _init_state_via_slot(slot, model, tx, rng, example_inputs, mesh,
                         strategy, init_kwargs):
    """The one init sequence, cold, warm and vectorized: get-or-build the
    per-input-shape init entry (jitted initializer + shardings —
    ``jax.eval_shape`` and the unboxing pass run once per program+shape,
    not once per trial), run the initializer, then the optimizer init. A
    swept-optimizer family's ``tx.init`` is jitted once an entry (an eager
    one is a dispatch a leaf) and its traced hyperparameters are rebound
    to this trial's values; a family-less transform inits eagerly. A cold
    and a warm trial differ only in whether the two programs were already
    built.

    Returns (params, opt_state, shardings, warm_hit, init_key). VALUES
    always come from ``rng``/``tx``: the entry holds programs, never a
    trial's state.
    """
    from maggy_tpu.train import warm as _warm

    init_kwargs = init_kwargs or {}
    ikey = (_warm.shape_key(example_inputs),
            repr(sorted(init_kwargs.items())), _warm.shape_key(rng))

    def build():
        def init_fn(r):
            variables = model.init(r, *example_inputs, **init_kwargs)
            return {k: v for k, v in variables.items() if k != "losses"}

        _, shardings = _unbox_and_specs(
            jax.eval_shape(init_fn, rng), mesh, strategy)

        # The function's name is the program's: ``jit_init_variables`` in
        # the profiler's ``XLA Modules``.
        def init_variables(r):
            plain, _ = _unbox_and_specs(init_fn(r), mesh, strategy)
            return plain

        return _warm._InitEntry(
            jax.jit(init_variables, out_shardings=shardings), shardings)

    entry, hit = slot.init_entry(ikey, build)
    family = _warm.opt_family(tx)
    with mesh:
        params = entry.init_jit(rng)
        nested = "params" in params
        psub = params["params"] if nested else params
        if family is None:
            opt_state = tx.init(psub)
        else:
            opt_init = entry.opt_init
            if opt_init is None or opt_init[0] != family:
                psh = entry.shardings
                opt_init = entry.opt_init = (family, _opt_init_program(
                    tx, psub, psh["params"] if nested else psh, mesh))
            # The program traced the family's FIRST transform: rebind its
            # hyperparameter constants to THIS trial's swept values.
            opt_state = _warm.rebind_hyperparams(
                opt_init[1](psub), _warm.swept_info(tx)["hparams"])
    from maggy_tpu.parallel.sharding import apply_zero_sharding

    opt_state = apply_zero_sharding(
        opt_state, mesh, strategy,
        lambda x, sh: jax.device_put(x, sh) if hasattr(x, "shape") else x)
    return params, opt_state, entry.shardings, hit, ikey


def build_step_fn(
    model,
    tx,
    loss_fn: Callable,
    mesh,
    has_aux_collections: bool = False,
    train_kwargs: Optional[Dict[str, Any]] = None,
    strategy: str = "dp",
):
    """The raw (unjitted) train-step closure ``make_train_step`` jits.

    Exposed separately so the vectorized K-lane path (train/vmap.py) can
    wrap the IDENTICAL computation in ``jax.vmap`` over the stacked state
    axis — one program family, scalar and vectorized."""
    from maggy_tpu.parallel.sharding import apply_zero_sharding

    train_kwargs = train_kwargs or {}

    def train_step(variables, opt_state, batch):
        params = variables["params"]
        aux = {k: v for k, v in variables.items() if k != "params"}

        def compute_loss(p):
            vs = {"params": p, **aux}
            mutable = (list(aux.keys()) if has_aux_collections else []) + ["losses"]
            out, updates = model.apply(
                vs, *batch["inputs"], mutable=mutable, **train_kwargs)
            with jax.named_scope("loss"):
                loss = loss_fn(out, batch)
                # Sowed auxiliary losses (MoE load balancing etc.) join the
                # objective; they are scalars, summed over all sow sites.
                for leaf in jax.tree_util.tree_leaves(
                        updates.pop("losses", {})):
                    loss = loss + jnp.sum(leaf)
            return loss, updates

        with jax.named_scope("loss_and_grad"):
            (loss, new_aux), grads = jax.value_and_grad(
                compute_loss, has_aux=True)(params)
        import optax

        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            opt_state = apply_zero_sharding(
                opt_state, mesh, strategy,
                lambda x, sh: jax.lax.with_sharding_constraint(x, sh))
        return {"params": params, **new_aux} if has_aux_collections else \
            {"params": params, **aux}, opt_state, loss

    # The function's name is the program's: ``jit_train_step`` in the
    # profiler's ``XLA Modules`` (a trial's init is ``jit_init_variables``
    # and ``jit_init_opt_state``).
    return train_step


def make_train_step(
    model,
    tx,
    loss_fn: Callable,
    mesh,
    donate: bool = True,
    has_aux_collections: bool = False,
    train_kwargs: Optional[Dict[str, Any]] = None,
    strategy: str = "dp",
):
    """Build the jitted SPMD train step.

    step(variables, opt_state, batch) -> (variables, opt_state, loss).
    ``loss_fn(logits_or_outputs, batch)`` computes the scalar loss; gradient
    all-reduce/reduce-scatter over the mesh comes from GSPMD. With a
    "zero" strategy part, the updated optimizer state is constrained to
    its data-axis sharding so XLA keeps the moments de-duplicated across
    replicas (shapes are static at trace time, so the constraint costs
    nothing when already satisfied).
    """
    step = build_step_fn(model, tx, loss_fn, mesh,
                         has_aux_collections=has_aux_collections,
                         train_kwargs=train_kwargs, strategy=strategy)
    jit_kwargs = {}
    if donate:
        jit_kwargs["donate_argnums"] = (0, 1)
    return jax.jit(step, **jit_kwargs)


def _has_injected_hparams(state) -> bool:
    """True if any sub-state carries injected hyperparams (swept_transform
    may sit anywhere inside an optax.chain)."""
    if hasattr(state, "hyperparams"):
        return True
    if isinstance(state, (tuple, list)):
        return any(_has_injected_hparams(s) for s in state)
    return False


def swept_transform(opt_factory: Callable, **hparams):
    """Build an optax transform whose hyperparameters are TRACED INPUTS
    (carried in opt_state) instead of baked-in constants.

    ``swept_transform(optax.adam, learning_rate=lr)`` produces identical HLO
    for every lr, so a sweep compiles its train step ONCE: the warm cache
    (train/warm.py) auto-shares the compiled step across trials whose
    optimizer FAMILY (factory + hyperparameter names) matches — no
    ``step_key`` needed — and the persistent compilation cache dedups
    across runner processes (SURVEY.md §7.3 "compile-cache churn" — the
    TPU-native answer is hparams-as-inputs, not N recompiles).
    """
    import numbers

    import optax

    tx = optax.inject_hyperparams(opt_factory)(**hparams)
    numeric = {k: v for k, v in hparams.items()
               if isinstance(v, numbers.Real) and not isinstance(v, bool)}
    statics = {k: v for k, v in hparams.items() if k not in numeric}

    def repr_stable(v):
        # A static hyperparameter joins the shared family only when its
        # repr is value-determined. A schedule/callable/array reprs by
        # object (memory address): two identical constructions would mint
        # DISTINCT families — each trial a never-matching key churning
        # genuinely-warm programs out of the bounded shared LRU. Such
        # transforms stay family-less (private warm slot: AOT split and
        # telemetry, no cross-object sharing).
        if v is None or isinstance(v, (str, bytes, bool, numbers.Number)):
            return True
        if isinstance(v, (tuple, list)):
            return all(repr_stable(x) for x in v)
        return False

    if all(repr_stable(v) for v in statics.values()):
        static = tuple(sorted((k, repr(v)) for k, v in statics.items()))
        family = ("{}.{}".format(
            getattr(opt_factory, "__module__", "?"),
            getattr(opt_factory, "__qualname__", repr(opt_factory))),
            tuple(sorted(numeric)), static)
    else:
        family = None
    try:
        # The marker rides tx.init (a plain function, so setattr works —
        # the GradientTransformation namedtuple itself rejects attributes):
        # warm.opt_family/swept_info read it to derive the value-independent
        # auto program key and the per-trial hyperparams to rebind.
        tx.init._maggy_swept = {"family": family, "hparams": numeric}
    except (AttributeError, TypeError):
        pass  # exotic init callables: loses warm family sharing only
    return tx


class Trainer:
    """Convenience loop: init + step + reporter integration.

    The per-trial training harness for HPO sweeps (models from the zoo,
    optax optimizer, metric heartbeats via the Reporter).

    **Warm path (default).** Program identity is derived automatically —
    (model config, mesh topology, strategy, loss_fn, train_kwargs, and the
    optimizer family for ``swept_transform`` transforms) — and trials whose
    identity matches reuse one warm slot (train/warm.py): the jitted+
    AOT-compiled step, the computed shardings and the two init programs.
    The slot holds programs only; a trial's state lives in its Trainer
    and is freed with it. Build the
    optimizer with ``swept_transform`` so hyperparameters ride in
    opt_state and the whole sweep compiles once; a plain transform keys by
    object identity (never shared across objects — its constants are baked
    into the program). ``warm_start=False`` (or the executor's
    ``config.warm_start=False``) restores the build-per-trial behavior
    bit-for-bit.

    ``step_key``: manual override of the automatic program key — trials
    whose (step_key, model, mesh, strategy) coincide reuse one jitted step
    regardless of optimizer identity. Include the optimizer family in the
    key if the sweep varies it (e.g. ``step_key=("mnist", "adam")``).
    """

    def __init__(self, model, tx, loss_fn, mesh, strategy: str = "dp",
                 train_kwargs: Optional[Dict[str, Any]] = None,
                 has_aux_collections: bool = False,
                 step_key: Optional[tuple] = None,
                 warm_start: Optional[bool] = None):
        from maggy_tpu.train import warm as _warm

        self.model = model
        self.tx = tx
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.strategy = strategy
        self._warm_enabled = _warm.enabled() if warm_start is None \
            else bool(warm_start)
        build = functools.partial(
            make_train_step, model, tx, loss_fn, mesh,
            train_kwargs=train_kwargs,
            has_aux_collections=has_aux_collections, strategy=strategy)
        self._step_key = step_key
        self._step_shared = step_key is not None
        # Flax modules are frozen dataclasses and Mesh hashes by topology,
        # so the key pins the program identity; loss_fn keys by object
        # identity (a per-call lambda simply misses the cache — safe; a
        # module-level loss shares). Manual step_key deliberately excludes
        # tx (the user asserts hparams ride opt_state); the auto key
        # includes the optimizer family/identity so differing programs can
        # never share silently.
        tkr = repr(sorted((train_kwargs or {}).items()))
        self._slot = None
        if step_key is not None:
            key = ("manual", step_key, model, mesh, strategy,
                   has_aux_collections, loss_fn, tkr)
            self._slot, _ = _warm.warm_cache().slot(key)
        elif self._warm_enabled:
            family = _warm.opt_family(self.tx)
            if family is not None:
                key = ("auto", model, mesh, strategy, has_aux_collections,
                       loss_fn, tkr, family)
                try:
                    self._slot, _ = _warm.warm_cache().slot(key)
                except TypeError:
                    # Unhashable program component (e.g. a flax module
                    # with a list-typed field): the DEFAULT path must
                    # never reject a model that trained fine before —
                    # degrade to a private slot (no cross-trial sharing,
                    # AOT split and telemetry kept).
                    self._slot = _warm.WarmSlot(None)
            else:
                # Plain/family-less transform: no safe cross-trial
                # sharing, but a PRIVATE slot still buys the AOT
                # trace/compile split and compile telemetry without
                # churning the shared LRU.
                self._slot = _warm.WarmSlot(None)
        if self._slot is not None:
            self._step = self._slot.ensure_step(build)
        else:
            self._step = build()
        self._init_ikey = None
        self._active_step = None
        self._step_num = 0  # steps since init(), for the step annotation
        self.variables = None
        self.opt_state = None
        self.shardings = None

    def init(self, rng, example_inputs, init_kwargs=None):
        from maggy_tpu.train import warm as _warm

        self._active_step = None
        self._step_num = 0
        with _warm.span("init"):
            if self._slot is not None:
                (self.variables, self.opt_state, self.shardings, hit,
                 self._init_ikey) = _init_state_via_slot(
                    self._slot, self.model, self.tx, rng, example_inputs,
                    self.mesh, self.strategy, init_kwargs)
                _warm.record_warm_event(hit)
                _warm.note_compile(warm=bool(hit))
            else:
                self.variables, self.opt_state, self.shardings = \
                    init_train_state(
                        self.model, self.tx, rng, example_inputs, self.mesh,
                        self.strategy, init_kwargs=init_kwargs)
                _warm.note_compile(warm=False)
        if self._step_shared and not _has_injected_hparams(self.opt_state):
            import warnings

            warnings.warn(
                "Trainer(step_key=...) shares one compiled step across "
                "trials, but this tx bakes its hyperparameters into the "
                "program (use swept_transform) — all sharing trials will "
                "silently run the FIRST trial's optimizer constants.",
                stacklevel=2)
        return self

    def place_batch(self, batch: Dict[str, Any]):
        from maggy_tpu.parallel.sharding import cached_batch_sharding

        def put(x):
            # Sharding memoized by (mesh, leaf shape): steady-state steps
            # skip the per-leaf rule re-derivation (PartitionSpec building)
            # the old per-step tree_map paid.
            sh = cached_batch_sharding(self.mesh, np.shape(x))
            return jax.device_put(jnp.asarray(x), sh)

        with jax.profiler.TraceAnnotation("place_batch"):
            return jax.tree_util.tree_map(put, batch)

    def _resolve_step(self, batch):
        """Warm AOT path: per-shape compiled executables cached on the
        slot, so a repeat-shape trial skips trace AND compile and the
        split is measured (trace_ms/compile_ms telemetry). Any AOT failure
        permanently falls the slot back to the plain jit call — the warm
        path degrades, never breaks."""
        slot = self._slot
        if slot is None or not self._warm_enabled or not slot.aot_ok:
            return self._step
        from maggy_tpu.train import warm as _warm

        key = (self._init_ikey, _warm.shape_key(batch))
        fn = slot.compiled_step(key)
        if fn is None:
            # One compile per (slot, shape), even when N runner threads'
            # first trials race the same program — the losers wait on the
            # winner's executable instead of compiling their own.
            with slot.aot_lock:
                fn = slot.compiled_step(key)
                if fn is None:
                    from maggy_tpu.telemetry import plans as _plans

                    try:
                        with _warm.span("trace"), _plans.traced() as said:
                            lowered = self._step.lower(
                                self.variables, self.opt_state, batch)
                        with _warm.span("compile"):
                            fn = lowered.compile()
                    except Exception:  # noqa: BLE001 - AOT is an optimization
                        slot.aot_ok = False
                        return self._step
                    # What the traced parts said of themselves (the flash
                    # kernels' tiles, an expert layer's share), and which
                    # instructions ran under the scopes they named.
                    _warm.note_compile(**_plans.notes(said, fn))
                    slot.store_compiled(key, fn)
        return fn

    def step(self, batch: Dict[str, Any]) -> float:
        with jax.profiler.StepTraceAnnotation(
                "train_step", step_num=self._step_num), self.mesh:
            self._step_num += 1
            # Steady-state fast path: the batch shape is constant within
            # a trial, so reuse the last resolved executable without
            # recomputing its shape key (pure-Python per-step overhead on
            # the exact path this harness optimizes). A shape change
            # surfaces as the AOT executable's signature TypeError —
            # re-resolve once and retry; the error is re-raised when
            # re-resolution lands on the same fn (a genuine type error).
            fn = self._active_step
            if fn is None:
                fn = self._resolve_step(batch)
                self._active_step = fn
                # The trial's first dispatch, stamped once (first write
                # wins): until now the chip had nothing of it queued.
                import time as _time

                from maggy_tpu.train import warm as _warm

                _warm.note_compile(first_dispatch=round(_time.time(), 6))
            try:
                out = fn(self.variables, self.opt_state, batch)
            except TypeError:
                refreshed = self._resolve_step(batch)
                if refreshed is fn:
                    raise
                self._active_step = refreshed
                out = refreshed(self.variables, self.opt_state, batch)
            self.variables, self.opt_state, loss = out
        return loss

    def fit(self, batches, reporter=None, report_every: int = 1,
            callbacks=()) -> float:
        """Step over ``batches``; returns the final loss.

        The per-step loss is broadcast LAZILY (an un-materialized device
        scalar): `Reporter` pulls it to host on the heartbeat thread, so
        reporting never serializes the pipelined step stream (a blocking
        ``float(loss)`` here would wait for the device on every step).
        ``callbacks`` are `maggy_tpu.
        callbacks.BatchEnd`-style callables invoked as cb(logs, step) with
        the same lazy scalar in ``logs["loss"]``.
        """
        loss = None
        for i, batch in enumerate(batches):
            loss = self.step(self.place_batch(batch))
            if reporter is not None and i % report_every == 0:
                reporter.broadcast(loss, step=i)
            for cb in callbacks:
                cb({"loss": loss}, step=i)
        return float(loss) if loss is not None else float("nan")

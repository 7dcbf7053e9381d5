"""Vectorized micro-trials: K hyperparameter configs as ONE vmapped program.

ROADMAP item 4. Most HPO sweeps train *small* models on *big* chips, yet a
runner slot executes exactly one trial at a time — the chip idles across
the hyperparameter axis. The Podracer/Anakin architecture (PAPERS.md)
batches many learners onto one TPU as a single vmapped program; this
module is that trick wired into the warm-cache harness:

- ``VmapTrainer`` — the K-lane counterpart of ``train.Trainer``. Each lane
  is one trial's hyperparameter binding of the SAME program family
  (``swept_transform``: hyperparams are traced inputs riding in
  opt_state). Init runs the ordinary SCALAR init executable once — so a
  lane's initial state is bitwise-identical to a scalar trial's — and the
  values are stacked (or broadcast-written into the previous block's
  DONATED stacked buffers, the PR-6 donating re-init generalized across
  the lane axis). The train step is ``jax.vmap`` of the exact
  ``build_step_fn`` closure the scalar path jits, AOT-compiled ONCE per
  (program, K, batch shape) into the warm slot's vectorized entry
  (``warm._VmapEntry``) — lockstep steps, one dispatch for K trials.
- **Lane masking** — ``mask_lane(i)`` retires a lane host-side: the
  executable keeps running unchanged (no recompile, surviving lanes'
  losses bitwise untouched) while the masked lane's chip share accrues
  ``lane_idle`` badput in the goodput ledger. The freed lane is re-filled
  at the next re-init boundary: mid-block via ``refill_lane`` (fresh
  scalar-init values scatter-written into the lane's donated row), or at
  the block boundary when the next block's donating re-init overwrites
  every lane.

Bitwise caveat: per-lane parity with scalar trials holds for programs
whose ops batch exactly under ``jax.vmap`` (matmul/elementwise — e.g.
``models.MnistMLP``); batched-kernel convolutions may round differently.
The bench gate pins parity on the MLP sweep.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

from maggy_tpu.train import warm as _warm
from maggy_tpu.train.trainer import (_init_state_via_slot, build_step_fn,
                                     swept_transform)


def stack_trees(trees: Sequence[Any]):
    """Stack K congruent pytrees along a new leading lane axis."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def rebind_hyperparams_stacked(opt_state, lane_hparams: List[Dict[str, Any]]):
    """``warm.rebind_hyperparams`` across the lane axis: every injected-
    hyperparameter leaf (shape ``(K,)`` after stacking) is replaced by the
    per-lane values from ``lane_hparams``."""
    import jax.numpy as jnp

    def rebind(state):
        if hasattr(state, "_replace") and hasattr(state, "_fields"):
            updates = {}
            for f in state._fields:
                v = getattr(state, f)
                if f == "hyperparams" and isinstance(v, dict):
                    new = dict(v)
                    for name in new:
                        vals = [hp.get(name) for hp in lane_hparams]
                        if all(x is not None for x in vals):
                            new[name] = jnp.asarray(
                                vals, getattr(new[name], "dtype", None))
                    updates[f] = new
                elif isinstance(v, (tuple, list)):
                    updates[f] = rebind(v)
            return state._replace(**updates) if updates else state
        if isinstance(state, (tuple, list)):
            return type(state)(rebind(s) for s in state)
        return state

    return rebind(opt_state)


class VmapTrainer:
    """K-lane vectorized training harness (see module docstring).

    ``lane_hparams`` is a list of K dicts of the swept NUMERIC
    hyperparameters, one per lane (e.g. ``[{"learning_rate": 1e-3}, ...]``)
    — every lane shares the optimizer family
    ``swept_transform(opt_factory, **statics, **hp_i)``, so the program is
    identical across lanes and only the traced values differ.
    """

    def __init__(self, model, opt_factory, lane_hparams, loss_fn, mesh,
                 strategy: str = "dp",
                 train_kwargs: Optional[Dict[str, Any]] = None,
                 has_aux_collections: bool = False,
                 warm_start: Optional[bool] = None,
                 **statics: Any):
        if not lane_hparams:
            raise ValueError("need at least one lane")
        names = sorted(lane_hparams[0])
        if any(sorted(hp) != names for hp in lane_hparams):
            raise ValueError(
                "every lane must sweep the SAME hyperparameter names "
                "(one program family); got {}".format(
                    [sorted(hp) for hp in lane_hparams]))
        self.model = model
        self.opt_factory = opt_factory
        self.statics = statics
        self.lane_hparams = [dict(hp) for hp in lane_hparams]
        self.k = len(lane_hparams)
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.strategy = strategy
        self.train_kwargs = train_kwargs
        self.has_aux_collections = has_aux_collections
        self._warm_enabled = _warm.enabled() if warm_start is None \
            else bool(warm_start)
        # Lane 0's transform stands in for the family everywhere a tx is
        # needed: update() reads hyperparams from opt_state, so the same
        # closure serves every lane.
        self.tx = swept_transform(opt_factory, **statics, **lane_hparams[0])
        self.family = _warm.opt_family(self.tx)
        tkr = repr(sorted((train_kwargs or {}).items()))
        self._slot = None
        if self._warm_enabled and self.family is not None:
            key = ("auto", model, mesh, strategy, has_aux_collections,
                   loss_fn, tkr, self.family)
            try:
                self._slot, _ = _warm.warm_cache().slot(key)
            except TypeError:
                self._slot = _warm.WarmSlot(None)
        else:
            self._slot = _warm.WarmSlot(None)
        self._ventry: Optional[_warm._VmapEntry] = None
        self._init_ikey = None
        self._init_entry = None
        self._rng = None
        self._vstep = None  # (batch shape key, compiled K-lane executable)
        self.variables = None  # stacked: leaves lead with the lane axis
        self.opt_state = None
        self._mask = [False] * self.k  # host-side: True = lane retired
        _warm.register_trainer(self)

    # ------------------------------------------------------------------ init

    def _scalar_init(self, rng, example_inputs, init_kwargs):
        """One run of the ordinary SCALAR init path — the exact values a
        scalar cold trial of this family starts from (never the retired
        scalar buffers: blocks donate their own stacked cells)."""
        return _init_state_via_slot(
            self._slot, self.model, self.tx, rng, example_inputs,
            self.mesh, self.strategy, init_kwargs, allow_buffers=False)

    def init(self, rng, example_inputs, init_kwargs=None):
        """Stacked K-lane init. Values come from ONE scalar init (every
        lane of a sweep starts from the same rng, so lanes differ only in
        their injected hyperparams); when the warm slot's vectorized
        entry holds the previous block's retired stacked buffers, the
        broadcast-write DONATES them — fresh values into the retired
        block's memory, lane axis included."""
        import jax
        import jax.numpy as jnp

        with _warm.span("init"):
            self._rng = rng
            params, opt0, shardings, hit, ikey = self._scalar_init(
                rng, example_inputs, init_kwargs)
            self._init_ikey = ikey
            self._ventry = self._slot.vmap_entry(("vmap", ikey), self.k)
            lane_opts = [_warm.rebind_hyperparams(opt0, hp)
                         for hp in self.lane_hparams]
            retired = self._ventry.take_retired() \
                if self._warm_enabled else None
            if retired is not None and not _warm.fresh_state_only():
                old_vars, old_opt, old_family = retired
                try:
                    stacked = self._broadcast_reinit(params, lane_opts,
                                                     old_vars, old_opt)
                except Exception:  # noqa: BLE001 - donation is an optimization
                    stacked = None
                if stacked is not None:
                    self.variables, self.opt_state = stacked
            if self.variables is None:
                self.variables = jax.tree_util.tree_map(
                    lambda x: jnp.stack([x] * self.k), params)
                self.opt_state = stack_trees(lane_opts)
            self._mask = [False] * self.k
            self._vstep = None
        _warm.record_warm_event(bool(hit))
        _warm.note_compile(warm=bool(hit), vmap_lanes=self.k)
        del shardings
        return self

    def _broadcast_reinit(self, params, lane_opts, old_vars, old_opt):
        """Write fresh per-lane values into the previous block's DONATED
        stacked buffers (one jitted broadcast program per shape; XLA
        reuses the retired memory)."""
        import jax
        import jax.numpy as jnp

        fresh_opt = stack_trees(lane_opts)

        def write(fresh_v, fresh_o, old_v, old_o):
            del old_v, old_o  # donated: recycled memory, fresh values
            stacked_v = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None], (self.k,) + x.shape),
                fresh_v)
            return stacked_v, fresh_o

        fn = jax.jit(write, donate_argnums=(2, 3))
        return fn(params, fresh_opt, old_vars, old_opt)

    # ------------------------------------------------------------------ step

    def _resolve_vstep(self, batch):
        """The ONE AOT-compiled K-lane executable, cached on the warm
        slot's vectorized entry: ``jax.vmap`` of the exact scalar step
        closure over the stacked (variables, opt_state) axis with the
        batch broadcast — every block of the family reuses it."""
        import jax

        bkey = _warm.shape_key(batch)
        cached = self._vstep
        if cached is not None and cached[0] == bkey:
            return cached[1]
        ventry = self._ventry
        with ventry.lock:
            stored = ventry.vstep
            if stored is not None and stored[0] == bkey:
                self._vstep = stored
                return stored[1]
        raw = build_step_fn(self.model, self.tx, self.loss_fn, self.mesh,
                            has_aux_collections=self.has_aux_collections,
                            train_kwargs=self.train_kwargs,
                            strategy=self.strategy)
        vstep = jax.jit(jax.vmap(raw, in_axes=(0, 0, None)),
                        donate_argnums=(0, 1))
        try:
            with _warm.span("trace"):
                lowered = vstep.lower(self.variables, self.opt_state, batch)
            with _warm.span("compile"):
                fn = lowered.compile()
        except Exception:  # noqa: BLE001 - AOT is an optimization
            fn = vstep
        stored = (bkey, fn)
        with ventry.lock:
            ventry.vstep = stored
        self._vstep = stored
        return fn

    def step(self, batch):
        """One lockstep step for all K lanes; returns the LAZY per-lane
        loss vector (shape ``(K,)``) — callers index lane rows without
        forcing a device sync."""
        with self.mesh:
            fn = self._resolve_vstep(batch)
            self.variables, self.opt_state, losses = fn(
                self.variables, self.opt_state, batch)
        return losses

    # ------------------------------------------------------------ lane moves

    def mask_lane(self, lane: int) -> None:
        """Retire a lane WITHOUT recompiling: the executable keeps running
        all K rows (surviving lanes' losses bitwise unchanged); the masked
        row's compute is dead until the next re-init boundary re-fills it
        (``lane_idle`` badput in the ledger)."""
        self._mask[lane] = True

    def active_lanes(self) -> List[int]:
        return [i for i in range(self.k) if not self._mask[i]]

    def refill_lane(self, lane: int, hparams: Dict[str, Any],
                    example_inputs=None, init_kwargs=None) -> None:
        """Re-fill a retired lane with a fresh trial mid-block: fresh
        values from the ordinary SCALAR init executable (bitwise-identical
        to a scalar cold trial of the same config), scatter-written into
        the lane's DONATED row of the stacked state."""
        import jax
        import jax.numpy as jnp

        tx = swept_transform(self.opt_factory, **self.statics, **hparams)
        if _warm.opt_family(tx) != self.family:
            raise ValueError(
                "refill hyperparams {} do not match the block's optimizer "
                "family".format(sorted(hparams)))
        if example_inputs is not None:
            params, opt0, _sh, _hit, _ikey = _init_state_via_slot(
                self._slot, self.model, tx, self._rng, example_inputs,
                self.mesh, self.strategy, init_kwargs, allow_buffers=False)
        else:
            params, opt0, _sh, _hit, _ikey = self._refill_from_cached(tx)

        def scatter(sv, so, fv, fo):
            new_v = jax.tree_util.tree_map(
                lambda s, f: s.at[lane].set(f), sv, fv)
            new_o = jax.tree_util.tree_map(
                lambda s, f: s.at[lane].set(jnp.asarray(f, s.dtype))
                if hasattr(s, "at") else s, so, fo)
            return new_v, new_o

        fn = jax.jit(scatter, donate_argnums=(0, 1))
        self.variables, self.opt_state = fn(
            self.variables, self.opt_state, params, opt0)
        self.lane_hparams[lane] = dict(hparams)
        self._mask[lane] = False

    def _refill_from_cached(self, tx):
        """Refill without example inputs: rebuild fresh values from the
        slot's cached init entry (the same jitted scalar initializer)."""
        entry = self._slot.get_init(self._init_ikey) \
            if self._init_ikey is not None else None
        if entry is None:
            raise ValueError("refill_lane needs example_inputs on a cold "
                             "slot (no cached init entry)")
        with self.mesh:
            params = entry.init_jit(self._rng)
            opt0 = tx.init(
                params["params"] if "params" in params else params)
        return params, opt0, entry.shardings, True, self._init_ikey

    # ------------------------------------------------------------ retirement

    def retire_to_warm_cache(self) -> None:
        """Hand the block's STACKED state buffers to the vectorized entry:
        the next block's broadcast re-init donates them (the scalar
        retired-cell contract, generalized across the lane axis)."""
        if self._ventry is None or self.variables is None:
            return
        self._ventry.store_retired(self.variables, self.opt_state,
                                   self.family)
        self.variables = None
        self.opt_state = None


__all__ = ["VmapTrainer", "stack_trees", "rebind_hyperparams_stacked"]

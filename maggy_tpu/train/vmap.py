"""Vectorized micro-trials: K hyperparameter configs as ONE vmapped program.

ROADMAP item 4. Most HPO sweeps train *small* models on *big* chips, yet a
runner slot executes exactly one trial at a time — the chip idles across
the hyperparameter axis. The Podracer/Anakin architecture (PAPERS.md)
batches many learners onto one TPU as a single vmapped program; this
module is that trick wired into the warm-cache harness:

- ``VmapTrainer`` — the K-lane counterpart of ``train.Trainer``. Each lane
  is one trial's hyperparameter binding of the SAME program family
  (``swept_transform``: hyperparams are traced inputs riding in
  opt_state). Init runs the ordinary SCALAR init sequence once — so a
  lane's initial state is bitwise-identical to a scalar trial's — and the
  values are stacked along the lane axis. The train step is ``jax.vmap``
  of the exact ``build_step_fn`` closure the scalar path jits,
  AOT-compiled ONCE per (program, K, batch shape) and kept among the warm
  slot's compiled executables — lockstep steps, one dispatch for K
  trials.
- **Lane masking** — ``mask_lane(i)`` retires a lane host-side: the
  executable keeps running unchanged (no recompile, surviving lanes'
  losses bitwise untouched) while the masked lane's chip share accrues
  ``lane_idle`` badput in the goodput ledger. The freed lane is re-filled
  at the next re-init boundary: mid-block via ``refill_lane`` (fresh
  scalar-init values scatter-written into the lane's donated row), or at
  the block boundary when the next block's init builds every lane anew.

Parity with scalar trials, as the platform gives it. A lane's INITIAL
state is bitwise a scalar trial's (one init sequence), a 1-lane block is
bitwise the scalar run, and lane against lane (whatever the lane's
position or the block's K > 1) and block against block are bitwise. A
K-lane program against the scalar one is not: XLA batches the K matmuls
into one ``dot_general`` that accumulates in another order than the
scalar program's. On XLA:CPU the per-step losses of ``models.MnistMLP``
agree within `LANE_VS_SCALAR_ULP` float32 ulp over the first
`LANE_VS_SCALAR_STEPS` steps (measured, PR 28: 1 ulp on the test's batch
of 32, 2 on the bench's of 128) and the trajectories drift apart from
there (19 ulp after 25 steps at lr 3e-2); on the TPU it is not measured.
The test and the bench gate hold exactly that.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

from maggy_tpu.train import warm as _warm
from maggy_tpu.train.trainer import (_init_state_via_slot, build_step_fn,
                                     swept_transform)


#: Float32 ulp a lane's loss may differ from its scalar run's, per step,
#: over the first `LANE_VS_SCALAR_STEPS` steps (module docstring).
LANE_VS_SCALAR_ULP = 2
LANE_VS_SCALAR_STEPS = 6


def ulp_distance(a, b):
    """Elementwise distance of two float32 arrays in units in the last
    place (finite values of one sign, which losses are)."""
    import numpy as np

    a, b = (np.asarray(x, np.float32).view(np.int32).astype(np.int64)
            for x in (a, b))
    return np.abs(a - b)


def stack_trees(trees: Sequence[Any]):
    """Stack K congruent pytrees along a new leading lane axis."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


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
        self._init_ikey = None
        self._rng = None
        self._init_args = None  # (example_inputs, init_kwargs) of init()
        self._vstep = None  # (batch shape key, compiled K-lane executable)
        self.variables = None  # stacked: leaves lead with the lane axis
        self.opt_state = None
        self._mask = [False] * self.k  # host-side: True = lane retired

    # ------------------------------------------------------------------ init

    def _scalar_init(self, tx, example_inputs, init_kwargs):
        """One run of the ordinary SCALAR init sequence — the exact values
        a scalar cold trial of ``tx`` starts from."""
        return _init_state_via_slot(
            self._slot, self.model, tx, self._rng, example_inputs,
            self.mesh, self.strategy, init_kwargs)

    def init(self, rng, example_inputs, init_kwargs=None):
        """Stacked K-lane init. Values come from ONE scalar init (every
        lane of a sweep starts from the same rng, so lanes differ only in
        their injected hyperparams), stacked along the lane axis."""
        import jax
        import jax.numpy as jnp

        with _warm.span("init"):
            self._rng = rng
            self._init_args = (example_inputs, init_kwargs)
            params, opt0, _shardings, hit, self._init_ikey = \
                self._scalar_init(self.tx, example_inputs, init_kwargs)
            self.variables = jax.tree_util.tree_map(
                lambda x: jnp.stack([x] * self.k), params)
            self.opt_state = stack_trees(
                [_warm.rebind_hyperparams(opt0, hp)
                 for hp in self.lane_hparams])
            self._mask = [False] * self.k
            self._vstep = None
        _warm.record_warm_event(bool(hit))
        _warm.note_compile(warm=bool(hit), vmap_lanes=self.k)
        return self

    # ------------------------------------------------------------------ step

    def _resolve_vstep(self, batch):
        """The ONE AOT-compiled K-lane executable, kept among the warm
        slot's compiled executables under a key that carries the lane
        count: ``jax.vmap`` of the exact scalar step closure over the
        stacked (variables, opt_state) axis with the batch broadcast —
        every block of the family reuses it."""
        import jax

        bkey = _warm.shape_key(batch)
        cached = self._vstep
        if cached is not None and cached[0] == bkey:
            return cached[1]
        key = ("vmap", self.k, self._init_ikey, bkey)
        fn = self._slot.compiled_step(key)
        if fn is not None:
            self._vstep = (bkey, fn)
            return fn
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
        self._slot.store_compiled(key, fn)
        self._vstep = (bkey, fn)
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
        values from the ordinary SCALAR init sequence (bitwise-identical
        to a scalar cold trial of the same config), over ``init()``'s
        inputs unless others are given, scatter-written into the lane's
        DONATED row of the stacked state."""
        import jax
        import jax.numpy as jnp

        tx = swept_transform(self.opt_factory, **self.statics, **hparams)
        if _warm.opt_family(tx) != self.family:
            raise ValueError(
                "refill hyperparams {} do not match the block's optimizer "
                "family".format(sorted(hparams)))
        if self._init_args is None:
            raise ValueError("refill_lane before init()")
        if example_inputs is None:
            example_inputs, init_kwargs = self._init_args
        params, opt0, _sh, _hit, _ikey = self._scalar_init(
            tx, example_inputs, init_kwargs)

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


__all__ = ["VmapTrainer", "stack_trees", "ulp_distance",
           "LANE_VS_SCALAR_ULP", "LANE_VS_SCALAR_STEPS"]

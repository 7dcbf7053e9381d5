"""Per-trial execution context handed to user train functions.

The reference passes only a ``reporter`` into ``train_fn`` (introspected at
`trial_executor.py:142-146`); trial state lives in hidden module globals and
a promoted ASHA trial re-runs from scratch (the wanted-but-missing
optimization noted at reference `hyperband.py:325-326`). Here a trial can
opt into a ``ctx`` argument the same way it opts into ``reporter`` — by
naming it in its signature — and gets:

- its identity (``trial_id``, ``trial_dir``, ``exp_dir``, raw ``params``),
- the multi-fidelity ``budget`` and, for promoted trials, the
  ``parent_trial_id`` (carried in the scheduler's ``info_dict`` and shipped
  with the TRIAL assignment),
- orbax checkpointing scoped to the trial dir (``save_checkpoint`` /
  ``restore_checkpoint``), and
- ``restore_parent(abstract_state)`` — warm-start from the parent's last
  checkpoint, turning ASHA/Hyperband promotions into *continuations*
  instead of re-runs (a direct trials/hour win on TPU, where re-training
  the low-budget prefix wastes MXU time).
"""

from __future__ import annotations

import contextlib
import os
from typing import Any, Dict, Optional


def _ckpt_span(name: str):
    """The current trial's `span` for a checkpoint phase (``ckpt_save`` /
    ``ckpt_restore``): annotated in the profiler's trace and timed into
    the trial's RunnerStats through the warm trial scope (the channel
    note_compile rides). Never fatal: checkpoint accounting must not
    break checkpointing itself."""
    try:
        from maggy_tpu.train.warm import span

        return span(name)
    except Exception:  # noqa: BLE001 - accounting is best-effort
        return contextlib.nullcontext()


class TrialContext:
    def __init__(
        self,
        trial_id: str,
        trial_dir: str,
        exp_dir: str,
        params: Dict[str, Any],
        info: Optional[Dict[str, Any]] = None,
    ):
        self.trial_id = trial_id
        self.trial_dir = trial_dir
        self.exp_dir = exp_dir
        self.params = dict(params)
        self.info: Dict[str, Any] = dict(info or {})
        self._checkpointer = None

    # ----------------------------------------------------------- identity
    @property
    def budget(self) -> Optional[float]:
        """Multi-fidelity budget for this run (None if single-fidelity)."""
        b = self.info.get("run_budget", self.params.get("budget"))
        return None if b in (None, 0) else b

    @property
    def parent_trial_id(self) -> Optional[str]:
        """For a promoted ASHA/Hyperband trial: the trial it continues."""
        return self.info.get("parent")

    @property
    def forked_from(self) -> Optional[Dict[str, Any]]:
        """Checkpoint-fork lineage stamped by the driver (config.fork):
        ``{"trial": <parent id>, "step": <checkpoint step>}`` when this
        trial was dispatched to resume from another trial's checkpoint
        (ASHA promotion, PBT exploit/continue, BO near-duplicate). The
        executor stages the parent's checkpoint into THIS trial's dir
        before the train fn runs, so ``restore_checkpoint`` +
        ``resume_step`` work exactly like a same-trial preemption
        resume. None = from-scratch run."""
        fork = self.info.get("forked_from")
        return dict(fork) if fork else None

    def stage_fork(self) -> Optional[int]:
        """Stage the forked-from parent's checkpoint into this trial's
        dir (idempotent; see train/checkpoint.fork_checkpoint). Returns
        the staged step, or None when there is nothing to fork. The
        executor calls this before the train fn; it is exposed on the
        ctx so library code can re-stage explicitly."""
        fork = self.info.get("forked_from")
        if not fork or not fork.get("trial"):
            return None
        from maggy_tpu.core.environment import EnvSing
        from maggy_tpu.train.checkpoint import fork_checkpoint

        return fork_checkpoint(EnvSing.get_instance(), self.exp_dir,
                               fork["trial"], self.trial_dir,
                               step=fork.get("step"))

    @property
    def resume_step(self) -> Optional[int]:
        """For a preempted-then-requeued trial: the checkpoint step it was
        preempted at (restore via ``restore_checkpoint`` and continue from
        ``resume_step + 1``). None = fresh run (or it never checkpointed
        before preemption — requeue-from-scratch)."""
        step = self.info.get("resume_step")
        return None if step is None else int(step)

    @property
    def gang(self):
        """For a gang-scheduled multi-chip trial: the assembled
        ``maggy_tpu.gang.GangContext`` (member chips, mesh axes,
        strategy, ``build_mesh()``/``sharding_env()`` helpers) the
        driver stamped into the assignment info. None for 1-chip
        trials — a train function can branch on it to run sharded or
        single-device."""
        info = self.info.get("gang")
        if not info:
            return None
        from maggy_tpu.gang import GangContext

        # The member's own partition rides along so a REMOTE gang can
        # resolve this process's jax.distributed process id.
        return GangContext({**info, "partition": self.info.get("partition")})

    # ------------------------------------------------------- checkpointing
    def checkpointer(self):
        if self._checkpointer is None:
            from maggy_tpu.train.checkpoint import TrialCheckpointer

            self._checkpointer = TrialCheckpointer(self.trial_dir)
        return self._checkpointer

    def save_checkpoint(self, step: int, state: Any) -> None:
        with _ckpt_span("ckpt_save"):
            self.checkpointer().save(step, state)

    def restore_checkpoint(self, abstract_state: Any) -> Optional[Any]:
        """Resume this trial's own latest checkpoint (None if absent)."""
        if not os.path.isdir(os.path.join(self.trial_dir, "checkpoints")):
            return None
        with _ckpt_span("ckpt_restore"):
            return self.checkpointer().restore(abstract_state)

    def restore_parent(self, abstract_state: Any) -> Optional[Any]:
        """Warm-start from the promoted parent's checkpoint (None if this
        trial has no parent or the parent saved nothing)."""
        parent = self.parent_trial_id
        if parent is None:
            return None
        from maggy_tpu.train.checkpoint import restore_parent_state

        with _ckpt_span("ckpt_restore"):
            return restore_parent_state(self.exp_dir, parent, abstract_state)

    def close(self) -> None:
        if self._checkpointer is not None:
            self._checkpointer.close()
            self._checkpointer = None


class LaneSet:
    """The train fn's view of a vectorized K-lane block (config.vmap_lanes
    > 1): the per-lane hyperparameters to stack into a `VmapTrainer`, the
    per-lane stop signals the driver's early-stop rule raises, and the
    per-lane retirement hook that sends each lane's own FINAL. A train fn
    opts in by declaring a ``lanes`` keyword parameter; without it the
    executor degrades the block to sequential scalar runs."""

    def __init__(self, lanes, reporter, finalize):
        # Lane descriptors in lane order: {"trial_id", "lane", "params",
        # "span", "epoch", "fork_lane"} (from the block's TRIAL info).
        self.lanes = [dict(entry) for entry in lanes]
        self.reporter = reporter
        self._finalize = finalize
        self._by_id = {entry["trial_id"]: i
                       for i, entry in enumerate(self.lanes)}

    def __len__(self) -> int:
        return len(self.lanes)

    @property
    def trial_ids(self):
        return [entry["trial_id"] for entry in self.lanes]

    @property
    def hparams(self):
        """Per-lane param dicts, lane order — feed to VmapTrainer (the
        caller picks which keys form the stacked hyperparameter axis)."""
        return [dict(entry.get("params") or {}) for entry in self.lanes]

    def lane_of(self, trial_id: str) -> int:
        return self._by_id[trial_id]

    def take_stopped(self):
        """Lane INDICES newly flagged for early stop (each exactly once):
        poll between steps, mask them (`VmapTrainer.mask_lane`), then
        `retire()` each with its final metric."""
        return [self._by_id[tid]
                for tid in self.reporter.take_stopped_lanes()
                if tid in self._by_id]

    def retire(self, lane: int, metric) -> None:
        """Send lane ``lane``'s FINAL now (mid-block): its span closes at
        the moment it stopped contributing, so masked-lane idle time is
        attributable (goodput ``lane_idle``). Lanes never retired here are
        finalized by the executor when the train fn returns."""
        self._finalize(self.lanes[lane], metric)

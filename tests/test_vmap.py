"""Vectorized micro-trials (ROADMAP item 4, `train/vmap.py` +
`config.vmap_lanes`): K program-compatible configs train on one chip as
ONE vmapped program.

Engine layer: lane against lane and block against block are bitwise —
masking a lane, refilling it, or starting the next block on the warm slot
must never perturb any other lane by a single bit — and a 1-lane block is
bitwise the scalar Trainer run. A K-lane program against the scalar one
batches its matmuls, which XLA:CPU accumulates in another order: there
the parity is `LANE_VS_SCALAR_ULP` float32 ulp a step over the first
`LANE_VS_SCALAR_STEPS` steps (`train/vmap.py`, module docstring), and the
tests hold no more and no less.

Driver layer: block admission (`_vmap_blockable_locked`) and program
compatibility (`_vmap_compatible`) must fall back to scalar dispatch for
anything that cannot share a program — unhashable params, non-float
param mismatches, checkpoint resumers/forks.

E2E: lane-tagged journal edges, per-lane FINALs, and the chip-time
ledger's lane split (masked tails billed to `lane_idle`, identity exact).

The kill-mid-block soak is `python -m maggy_tpu.chaos --vmap`; the
trials/hour A/B gate is `bench.py --vmap`.
"""

from __future__ import annotations

import glob
import json
import os

import numpy as np
import pytest

from maggy_tpu.trial import Trial

pytestmark = pytest.mark.vmap

from maggy_tpu.train.vmap import (LANE_VS_SCALAR_STEPS, LANE_VS_SCALAR_ULP,
                                  ulp_distance)

STEPS = LANE_VS_SCALAR_STEPS
LRS = [1e-3, 3e-3, 1e-2, 3e-2]


@pytest.fixture(scope="module")
def engine():
    """Shared engine harness: tiny MnistMLP, one fixed full batch, scalar
    and block run helpers, plus the scalar baseline trajectories (computed
    once — every scalar run shares one warm-compiled step because lr rides
    in opt_state via swept_transform)."""
    import jax
    import jax.numpy as jnp
    import optax

    from maggy_tpu.models import MnistMLP
    from maggy_tpu.parallel import make_mesh
    from maggy_tpu.train import (Trainer, VmapTrainer, clear_warm,
                                 cross_entropy_loss, swept_transform)

    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    model = MnistMLP(features=8, num_classes=2)
    rs = np.random.RandomState(0)
    X = rs.rand(32, 16, 16, 1).astype("float32")
    Y = (X.mean(axis=(1, 2, 3)) > 0.5).astype("int32")
    batch = {"inputs": (jnp.asarray(X),), "labels": jnp.asarray(Y)}
    rng = jax.random.key(0)

    def loss_fn(logits, b):
        return cross_entropy_loss(logits, b["labels"])

    def scalar_run(lr, steps=STEPS):
        tr = Trainer(model, swept_transform(optax.adam, learning_rate=lr),
                     loss_fn, mesh, strategy="dp")
        tr.init(rng, (batch["inputs"][0][:1],))
        return np.asarray([float(tr.step(tr.place_batch(batch)))
                           for _ in range(steps)], np.float32)

    def make_block(lrs=LRS):
        vt = VmapTrainer(model, optax.adam,
                         [{"learning_rate": lr} for lr in lrs],
                         loss_fn, mesh, strategy="dp")
        vt.init(rng, (batch["inputs"][0][:1],))
        return vt

    clear_warm()
    scalar = {lr: scalar_run(lr) for lr in LRS}
    clear_warm()
    vt = make_block()
    block = np.stack([np.asarray(vt.step(batch)) for _ in range(STEPS)])
    h = {
        "batch": batch, "example": (batch["inputs"][0][:1],),
        "scalar_run": scalar_run, "make_block": make_block,
        "clear_warm": clear_warm, "scalar": scalar, "block": block,
    }
    yield h
    clear_warm()


class TestEngineBitwiseParity:
    def test_block_matches_scalar_runs_per_lane(self, engine):
        """The headline property: lane i of the vmapped block is the
        scalar run of config i, to the parity the platform gives, and
        bit-for-bit the same lane of any other block."""
        for i, lr in enumerate(LRS):
            d = ulp_distance(engine["scalar"][lr], engine["block"][:, i])
            assert d.max() <= LANE_VS_SCALAR_ULP, \
                "lane {} (lr={}) is {} ulp from its scalar run".format(
                    i, lr, d)
        vt = engine["make_block"](LRS[::-1] + [5e-3])
        moved = np.stack([np.asarray(vt.step(engine["batch"]))
                          for _ in range(STEPS)])
        assert np.array_equal(moved[:, :len(LRS)][:, ::-1], engine["block"]), \
            "a lane's numbers depend on its position or the block's K"

    def test_one_lane_block_is_the_scalar_run_bitwise(self, engine):
        for lr in LRS[:2]:
            vt = engine["make_block"]([lr])
            block = np.asarray([np.asarray(vt.step(engine["batch"]))[0]
                                for _ in range(STEPS)])
            assert np.array_equal(block, engine["scalar"][lr])

    def test_masked_lane_survivors_bitwise_unchanged(self, engine):
        """Early-stopping lane 1 at step 2 (mask, NOT recompile) must not
        perturb surviving lanes by a single bit."""
        engine["clear_warm"]()
        vt = engine["make_block"]()
        out = []
        for t in range(STEPS):
            if t == 2:
                vt.mask_lane(1)
            out.append(np.asarray(vt.step(engine["batch"])))
        out = np.stack(out)
        for i in (0, 2, 3):
            assert np.array_equal(out[:, i], engine["block"][:, i]), \
                "masking lane 1 perturbed surviving lane {}".format(i)
        assert 1 not in vt.active_lanes()

    def test_refilled_lane_matches_scalar_cold(self, engine):
        """A lane freed by masking and re-filled with a NEW config at the
        re-init boundary trains like a cold scalar trial of that config,
        to the parity the platform gives, and bit-for-bit like that
        config's lane of a fresh block."""
        engine["clear_warm"]()
        vt = engine["make_block"]()
        for t in range(STEPS):
            if t == 2:
                vt.mask_lane(1)
            vt.step(engine["batch"])
        vt.refill_lane(1, {"learning_rate": 5e-3},
                       example_inputs=engine["example"])
        refilled = np.asarray([np.asarray(vt.step(engine["batch"]))[1]
                               for _ in range(STEPS)])
        engine["clear_warm"]()
        cold = engine["scalar_run"](5e-3)
        d = ulp_distance(refilled, cold)
        assert d.max() <= LANE_VS_SCALAR_ULP, \
            "refilled lane is {} ulp from the scalar cold run".format(d)
        fresh = engine["make_block"]([LRS[0], 5e-3, LRS[2], LRS[3]])
        fresh = np.asarray([np.asarray(fresh.step(engine["batch"]))[1]
                            for _ in range(STEPS)])
        assert np.array_equal(refilled, fresh), \
            "refilled lane diverged from the same lane of a fresh block"

    def test_second_block_on_warm_slot_bitwise(self, engine):
        """A second block of the family on the warm slot is invisible in
        the numbers, and runs the first block's K-lane executable."""
        engine["clear_warm"]()
        vt_a = engine["make_block"]()
        for _ in range(2):
            vt_a.step(engine["batch"])
        slot = vt_a._slot
        lane_programs = [k for k in slot.compiled if k[0] == "vmap"]
        assert len(lane_programs) == 1 and lane_programs[0][1] == len(LRS)
        fn_a = slot.compiled[lane_programs[0]]
        del vt_a
        vt_b = engine["make_block"]()
        assert vt_b._slot is slot
        out = np.stack([np.asarray(vt_b.step(engine["batch"]))
                        for _ in range(STEPS)])
        assert np.array_equal(out, engine["block"]), \
            "the warm slot perturbed the next block"
        assert [k for k in slot.compiled if k[0] == "vmap"] == lane_programs
        assert slot.compiled[lane_programs[0]] is fn_a

    def test_refill_without_inputs_reuses_init_inputs(self, engine):
        """``refill_lane`` with no example inputs runs the same init
        sequence over the inputs ``init()`` was given."""
        engine["clear_warm"]()
        vt_a, vt_b = engine["make_block"](), engine["make_block"]()
        vt_a.refill_lane(1, {"learning_rate": 5e-3},
                         example_inputs=engine["example"])
        vt_b.refill_lane(1, {"learning_rate": 5e-3})
        assert len(vt_b._slot.inits) == 1
        for _ in range(2):
            assert np.array_equal(np.asarray(vt_a.step(engine["batch"])),
                                  np.asarray(vt_b.step(engine["batch"])))


class TestBlockAdmission:
    """Driver-side scalar fallback: what can NEVER ride a block."""

    def _driver(self):
        from maggy_tpu.core.driver.optimization_driver import \
            OptimizationDriver

        drv = object.__new__(OptimizationDriver)
        drv._gang_mode = False
        return drv

    def test_unhashable_params_fall_back_scalar(self):
        drv = self._driver()
        assert drv._vmap_blockable_locked(Trial({"lr": 0.1}))
        assert not drv._vmap_blockable_locked(Trial({"lr": [0.1, 0.2]}))

    def test_resumers_and_forks_fall_back_scalar(self):
        drv = self._driver()
        assert not drv._vmap_blockable_locked(
            Trial({"lr": 0.1}, info_dict={"resume_step": 3}))
        assert not drv._vmap_blockable_locked(
            Trial({"lr": 0.1}, info_dict={"forked_from": "t0"}))
        # A BO near-duplicate keeps its parent tag but IS admitted —
        # it rides the block as a fork lane (fresh init, no restore).
        assert drv._vmap_blockable_locked(
            Trial({"lr": 0.1},
                  info_dict={"parent": "t0", "near_duplicate": True}))

    def test_compatibility_is_float_axis_only(self):
        from maggy_tpu.core.driver.optimization_driver import \
            OptimizationDriver

        compat = OptimizationDriver._vmap_compatible
        # Float params are the stacked hyperparameter axis: any values
        # share one program.
        assert compat(Trial({"lr": 0.1, "batch": 128}),
                      Trial({"lr": 0.2, "batch": 128}))
        # Non-float params steer shapes/model config: a mismatch forces
        # a separate program (scalar dispatch or another block).
        assert not compat(Trial({"lr": 0.1, "batch": 128}),
                          Trial({"lr": 0.2, "batch": 256}))
        assert not compat(Trial({"lr": 0.1}), Trial({"lr": 0.1, "mu": 0.9}))
        assert not compat(Trial({"lr": 0.1}),
                          Trial({"lr": 0.1}, trial_type="ablation"))


# ---------------------------------------------------------------- e2e


def _read_journal(exp_dir):
    events = []
    for path in glob.glob(os.path.join(exp_dir, "telemetry.jsonl")):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    events.append(json.loads(line))
    return events


def _train_vec(lr, lanes=None, reporter=None):
    """Closed-form lanes-capable trial. The scalar branch is mandatory:
    every runner's FIRST dispatch is scalar (nothing prefetched yet), and
    incompatible/unhashable suggestions fall back to it forever."""
    import time as _time

    if lanes is None:
        for step in range(5):
            reporter.broadcast(1.0 - (lr - 0.1) ** 2 + 0.001 * step,
                               step=step)
            _time.sleep(0.02)
        return 1.0 - (lr - 0.1) ** 2
    lrs = [h["lr"] for h in lanes.hparams]
    for step in range(5):
        vals = [1.0 - (x - 0.1) ** 2 + 0.001 * step for x in lrs]
        reporter.broadcast_lanes(vals, step=step)
        if step == 1 and len(lanes) >= 2:
            # Server-issued lane stop: masks lane 0 next step, whose
            # tail the goodput ledger must bill to lane_idle.
            reporter.stop_lanes([lanes.trial_ids[0]])
        for i in lanes.take_stopped():
            lanes.retire(i, float(vals[i]))
        _time.sleep(0.02)
    return {tid: 1.0 - (x - 0.1) ** 2
            for tid, x in zip(lanes.trial_ids, lrs)}


@pytest.mark.slow
class TestVmapE2E:
    @pytest.fixture(autouse=True)
    def local_env(self, tmp_path):
        from maggy_tpu.core.environment import EnvSing
        from maggy_tpu.core.environment.abstractenvironment import LocalEnv

        env = LocalEnv(base_dir=str(tmp_path / "exp"))
        EnvSing.set_instance(env)
        yield env
        EnvSing.reset()

    @pytest.mark.timeout(120)
    def test_lane_journal_and_goodput_split(self, local_env):
        from maggy_tpu import OptimizationConfig, Searchspace, experiment
        from maggy_tpu.telemetry.goodput import compute_goodput

        config = OptimizationConfig(
            name="vmap_e2e", num_trials=8, optimizer="randomsearch",
            searchspace=Searchspace(lr=("DOUBLE", [0.0, 0.2])),
            direction="max", num_workers=1, hb_interval=0.05, seed=3,
            es_policy="none", vmap_lanes=4)
        result = experiment.lagom(_train_vec, config)
        assert result["num_trials"] == 8

        exp_dir = os.path.join(local_env.base_dir,
                               os.listdir(local_env.base_dir)[0])
        events = _read_journal(exp_dir)
        lane_assigned = [e for e in events if e.get("phase") == "assigned"
                         and e.get("lane") is not None]
        lane_final = [e for e in events if e.get("phase") == "finalized"
                      and e.get("lane") is not None]
        assert len(lane_assigned) >= 4, "no blocks assembled"
        assert len(lane_final) >= 4, "lanes finalized without lane tags"
        assert {e["block"] for e in lane_assigned}, "lane edges lack block"

        g = compute_goodput(events)
        buckets = g["buckets"]
        # Masked lane tails must be billed to lane_idle, and the ledger
        # identity must stay EXACT with the per-lane split in play.
        assert buckets.get("lane_idle", 0.0) > 0.0
        assert abs(sum(buckets.values()) - g["held_chip_s"]) < 1e-6
        for pid, p in g["per_partition"].items():
            assert abs(sum(p["buckets"].values()) - p["held_s"]) < 1e-6, \
                "ledger identity broken on partition {}".format(pid)

    @pytest.mark.timeout(120)
    def test_scalar_train_fn_degrades_to_sequential(self, local_env):
        """A train fn WITHOUT a ``lanes`` kwarg under vmap_lanes > 1:
        delivered blocks degrade to sequential scalar execution — every
        trial still finalizes with its own metric."""
        from maggy_tpu import OptimizationConfig, Searchspace, experiment

        def train_scalar_only(lr, reporter=None):
            reporter.broadcast(1.0 - (lr - 0.1) ** 2, step=0)
            return 1.0 - (lr - 0.1) ** 2

        config = OptimizationConfig(
            name="vmap_fallback", num_trials=6, optimizer="randomsearch",
            searchspace=Searchspace(lr=("DOUBLE", [0.0, 0.2])),
            direction="max", num_workers=1, hb_interval=0.05, seed=5,
            es_policy="none", vmap_lanes=3)
        result = experiment.lagom(train_scalar_only, config)
        assert result["num_trials"] == 6
        assert result["best_val"] is not None

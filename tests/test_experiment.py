"""End-to-end experiment tests: lagom over the thread runner pool.

This is SURVEY.md §7.2 milestone 3 made a test: the full stack (driver +
RPC + executors + optimizer + early stopping + artifacts) on one host, with
a fast closed-form train function standing in for MNIST.
"""

import json
import os
import time

import pytest

from maggy_tpu import OptimizationConfig, Searchspace
from maggy_tpu import experiment
from maggy_tpu.core.environment import EnvSing
from maggy_tpu.core.environment.abstractenvironment import LocalEnv

# Heavy module (e2e tests): excluded from the fast lane (pytest -m 'not slow').
pytestmark = pytest.mark.slow


@pytest.fixture(autouse=True)
def local_env(tmp_path):
    env = LocalEnv(base_dir=str(tmp_path / "exp"))
    EnvSing.set_instance(env)
    yield env
    EnvSing.reset()


def train_quadratic(lr, units, reporter=None):
    """Stand-in train fn: 'accuracy' peaks at lr=0.1, units=32."""
    acc = 1.0 - ((lr - 0.1) ** 2 + ((units - 32) / 64.0) ** 2)
    if reporter is not None:
        for step in range(3):
            reporter.broadcast(acc * (step + 1) / 3.0, step=step)
    return {"metric": acc, "lr": lr}


def space():
    return Searchspace(lr=("DOUBLE", [0.0, 0.2]), units=("INTEGER", [8, 64]))


class TestRandomSearchE2E:
    def test_full_run(self, local_env):
        config = OptimizationConfig(
            name="rs_e2e", num_trials=8, optimizer="randomsearch",
            searchspace=space(), direction="max", num_workers=3,
            hb_interval=0.05, seed=7, es_policy="none",
        )
        result = experiment.lagom(train_quadratic, config)
        assert result["num_trials"] == 8
        assert result["best_val"] is not None and result["best_val"] <= 1.0
        assert result["best_val"] >= result["worst_val"]
        # Artifacts on disk: experiment.json, result.json, per-trial dirs.
        exp_dirs = os.listdir(local_env.base_dir)
        assert len(exp_dirs) == 1
        exp_dir = os.path.join(local_env.base_dir, exp_dirs[0])
        assert json.loads(local_env.load(exp_dir + "/result.json"))["num_trials"] == 8
        meta = json.loads(local_env.load(exp_dir + "/experiment.json"))
        assert meta["state"] == "FINISHED"
        # A trial dir is one holding trial.json (exp_dir also carries the
        # experiment-level tensorboard/ hparams-config dir).
        trial_dirs = [d for d in os.listdir(exp_dir)
                      if os.path.exists(os.path.join(exp_dir, d, "trial.json"))]
        assert len(trial_dirs) == 8
        assert os.path.isdir(os.path.join(exp_dir, "tensorboard"))
        for td in trial_dirs:
            full = os.path.join(exp_dir, td)
            assert os.path.exists(full + "/.hparams.json")
            assert os.path.exists(full + "/.metric")
            assert os.path.exists(full + "/trial.json")

    def test_result_is_actually_best(self, local_env):
        config = OptimizationConfig(
            num_trials=6, optimizer="randomsearch", searchspace=space(),
            direction="max", num_workers=2, hb_interval=0.05, seed=1,
            es_policy="none",
        )
        result = experiment.lagom(train_quadratic, config)
        # Recompute: reported best matches the true objective at best_hp.
        hp = result["best_hp"]
        expected = train_quadratic(hp["lr"], hp["units"])["metric"]
        assert abs(expected - result["best_val"]) < 1e-9


class TestGridSearchE2E:
    def test_grid(self, local_env):
        sp = Searchspace(pool=("DISCRETE", [2, 3]), act=("CATEGORICAL", ["relu", "gelu"]))

        def train(pool, act):
            return float(pool + (act == "gelu"))

        config = OptimizationConfig(
            optimizer="gridsearch", searchspace=sp, direction="max",
            num_workers=2, hb_interval=0.05, es_policy="none",
        )
        result = experiment.lagom(train, config)
        assert result["num_trials"] == 4
        assert result["best_val"] == 4.0  # pool=3, gelu
        assert result["best_hp"] == {"pool": 3, "act": "gelu"}


class TestAshaE2E:
    def test_asha(self, local_env):
        def train(lr, units, budget, reporter=None):
            # Budget-aware objective: converges toward lr with more budget.
            return {"metric": lr * (1 - 1.0 / (1 + budget))}

        config = OptimizationConfig(
            optimizer=__import__("maggy_tpu.optimizers", fromlist=["Asha"]).Asha(
                reduction_factor=3, resource_min=1, resource_max=9, seed=0),
            num_trials=9, searchspace=space(), direction="max",
            num_workers=3, hb_interval=0.05, es_policy="none",
        )
        result = experiment.lagom(train, config)
        assert result["num_trials"] >= 9  # rung-0 + promotions
        assert result["best_val"] > 0


class TestFailureRecovery:
    def test_failing_trial_marks_error_and_continues(self, local_env):
        calls = []

        def train(lr, units):
            calls.append(lr)
            if len(calls) == 2:
                raise RuntimeError("boom")
            return lr

        config = OptimizationConfig(
            num_trials=5, optimizer="randomsearch", searchspace=space(),
            direction="max", num_workers=1, hb_interval=0.05, seed=3,
            es_policy="none",
        )
        result = experiment.lagom(train, config)
        # One trial errored; the rest finalized with metrics.
        assert result["num_trials"] == 4
        exp_dir = os.path.join(local_env.base_dir, os.listdir(local_env.base_dir)[0])
        statuses = []
        for d in os.listdir(exp_dir):
            tj = os.path.join(exp_dir, d, "trial.json")
            if os.path.exists(tj):
                statuses.append(json.loads(local_env.load(tj))["status"])
        assert statuses.count("ERROR") == 1
        assert statuses.count("FINALIZED") == 4


class TestEarlyStopE2E:
    def test_median_rule_stops_bad_trials(self, local_env):
        def train(lr, units, reporter=None):
            # Bad configs (lr < 0.05) report low metrics slowly.
            base = 1.0 if lr >= 0.05 else 0.01
            for step in range(30):
                reporter.broadcast(base * (step + 1) / 30.0, step=step)
                time.sleep(0.01)
            return base

        config = OptimizationConfig(
            num_trials=10, optimizer="randomsearch", searchspace=space(),
            direction="max", num_workers=2, hb_interval=0.02, seed=5,
            es_policy="median", es_interval=1, es_min=3,
        )
        result = experiment.lagom(train, config)
        assert result["num_trials"] == 10
        # At least one slow trial was early stopped, and its final metric is
        # the last broadcast value, not the return value.
        assert result["early_stopped"] >= 1


class TestStartupLatency:
    def test_no_heavy_imports_on_experiment_path(self, tmp_path):
        """A plain sweep must not drag TensorFlow or sklearn into the
        process: both sat on the lagom critical path once (TF via the
        HParams helper modules ~5 s, sklearn via the eager gp/tpe registry
        ~2.5 s) and turned experiment startup into 7.4 s of imports
        (BASELINE.md round-3 profile). tensorboard's writer must run on
        its bundled TF stub. Subprocess: in-process sys.modules is
        polluted by whichever tests ran earlier."""
        import subprocess
        import sys

        script = """
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["MAGGY_TPU_BASE_DIR"] = {base!r}
from maggy_tpu import OptimizationConfig, Searchspace, experiment


config = OptimizationConfig(
    name="startup", num_trials=2, optimizer="randomsearch",
    searchspace=Searchspace(lr=("DOUBLE", [0.0, 0.2])),
    direction="max", num_workers=1, es_policy="none", seed=0)
result = experiment.lagom(lambda lr: {{"metric": lr}}, config)
assert result["num_trials"] == 2, result
assert "tensorflow" not in sys.modules, "TF on the experiment path"
assert "sklearn" not in sys.modules, "sklearn on the experiment path"
print("STARTUP_CLEAN")
""".format(base=str(tmp_path / "exp"))
        out = subprocess.run(
            [sys.executable, "-c", script], capture_output=True, text=True,
            timeout=180,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        assert "STARTUP_CLEAN" in out.stdout


class TestGuards:
    def test_unknown_config_type(self):
        with pytest.raises(TypeError, match="Unsupported config"):
            experiment.lagom_driver(object(), "app", 0)

    def test_unknown_optimizer(self):
        from maggy_tpu.core.driver.optimization_driver import OptimizationDriver

        with pytest.raises(ValueError, match="Unknown optimizer"):
            OptimizationDriver(
                OptimizationConfig(optimizer="sgd", searchspace=space()), "a", 0
            )


def train_suicidal(lr, units, reporter=None):
    """First trial to claim the flag file hard-kills its runner process
    (no FINAL, no further heartbeats) — simulating a runner crash."""
    flag = os.environ["MAGGY_TEST_KILL_FLAG"]
    try:
        fd = os.open(flag, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
        os.close(fd)
        os._exit(42)
    except FileExistsError:
        pass
    return {"metric": 1.0 - (lr - 0.1) ** 2}


def train_wedged(lr, units, reporter=None):
    """First trial to claim the flag file SIGSTOPs its own runner process —
    the process stays ALIVE but frozen (all threads, heartbeat included),
    modeling a runner wedged in an uninterruptible native call. Unlike
    train_suicidal it never exits on its own: only the driver's
    kill-on-heartbeat-loss can reap it, otherwise the pool join hangs."""
    import signal

    flag = os.environ["MAGGY_TEST_WEDGE_FLAG"]
    try:
        fd = os.open(flag, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
        os.close(fd)
        os.kill(os.getpid(), signal.SIGSTOP)
        # Only reachable if something SIGCONTs the process (nothing should:
        # the driver SIGKILLs it). Fail loudly rather than finish the trial.
        os._exit(43)
    except FileExistsError:
        pass
    return {"metric": 1.0 - (lr - 0.1) ** 2}


class TestAutoWorkers:
    def test_auto_sizes_pool_from_device_inventory(self, local_env):
        config = OptimizationConfig(
            name="auto_w", num_trials=8, optimizer="randomsearch",
            searchspace=space(), direction="max", num_workers="auto",
            hb_interval=0.05, seed=2, es_policy="none")
        result = experiment.lagom(train_quadratic, config)
        assert result["num_trials"] == 8

    def test_resolve_counts(self):
        import types

        from maggy_tpu.core.runner_pool import resolve_num_workers

        import jax

        n = jax.local_device_count()
        cfg = types.SimpleNamespace(num_workers="auto", pool="thread")
        assert resolve_num_workers(cfg) == n
        cfg = types.SimpleNamespace(num_workers="auto", pool="tpu",
                                    chips_per_trial=2)
        assert resolve_num_workers(cfg) == n // 2
        cfg = types.SimpleNamespace(num_workers="auto", pool="elastic",
                                    chips_per_trial=2)
        assert resolve_num_workers(cfg) == n // 2
        cfg = types.SimpleNamespace(num_workers=3, pool="thread")
        assert resolve_num_workers(cfg) == 3
        cfg = types.SimpleNamespace(num_workers="auto", pool="remote")
        with pytest.raises(ValueError, match="auto"):
            resolve_num_workers(cfg)

    def test_bad_string_rejected_at_config(self):
        with pytest.raises(ValueError, match="auto"):
            OptimizationConfig(name="x", searchspace=space(),
                               num_workers="all")


def train_printing(lr, units):
    """No reporter arg at all: print() is the only channel — exactly the
    reference-style user code ship_prints exists for."""
    print("USER_PRINT lr={:.4f}".format(lr))
    return {"metric": 1.0 - (lr - 0.1) ** 2}


class TestShipPrints:
    def _run(self, **kw):
        config = OptimizationConfig(
            name="prints", num_trials=3, optimizer="randomsearch",
            searchspace=space(), direction="max", num_workers=2,
            hb_interval=0.05, seed=11, es_policy="none", **kw)
        return experiment.lagom(train_printing, config)

    def _executor_logs(self, local_env):
        exp_base = local_env.base_dir
        exp_dir = os.path.join(exp_base, os.listdir(exp_base)[0])
        text = ""
        for f in os.listdir(exp_dir):
            if f.startswith("executor_") and f.endswith(".log"):
                with open(os.path.join(exp_dir, f)) as fh:
                    text += fh.read()
        return text

    def test_opt_in_ships_user_prints(self, local_env):
        result = self._run(ship_prints=True)
        assert result["num_trials"] == 3
        # The print() line rode the reporter log channel (and from there
        # the heartbeat stream the monitor CLI tails).
        assert "USER_PRINT lr=" in self._executor_logs(local_env)

    def test_default_does_not_ship(self, local_env):
        self._run()
        assert "USER_PRINT" not in self._executor_logs(local_env)


def train_pinned_virtual(lr, units, reporter=None):
    """Asserts, from INSIDE a TPURunnerPool child process, that the chip
    visibility env landed before backend init and yields exactly that
    device subset. The real libtpu honors TPU_VISIBLE_CHIPS; the CPU
    backend stands in for it here by forcing the host-platform device
    count to the visible-chip count (same read-env-before-init contract,
    virtual devices)."""
    chips = os.environ["TPU_VISIBLE_CHIPS"].split(",")
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count={}".format(len(chips)))
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    n = jax.local_device_count()
    assert n == len(chips), \
        "runner saw {} devices, expected its {}-chip subset {}".format(
            n, len(chips), chips)
    with open(os.path.join(os.environ["MAGGY_TEST_PIN_DIR"],
                           chips[0].replace(",", "-")), "a") as f:
        f.write("{}\n".format(os.getpid()))
    # Slow trials so the schedule spreads over BOTH pinned runners (the
    # disjoint-subset assertion needs each to see work).
    time.sleep(0.3)
    return {"metric": 1.0 - (lr - 0.1) ** 2}


class TestVirtualChipPinning:
    def test_tpu_pool_pins_disjoint_subsets(self, local_env, tmp_path,
                                            monkeypatch):
        """Spawn N pinned runner processes (pool='tpu') over virtual
        devices; each must see ONLY its chip subset and the
        schedule must complete across them."""
        pin_dir = tmp_path / "pins"
        pin_dir.mkdir()
        monkeypatch.setenv("MAGGY_TEST_PIN_DIR", str(pin_dir))
        config = OptimizationConfig(
            name="pin_smoke", num_trials=6, optimizer="randomsearch",
            searchspace=space(), direction="max", num_workers=2,
            chips_per_trial=2, hb_interval=0.1, seed=5,
            es_policy="none", pool="tpu",
        )
        result = experiment.lagom(train_pinned_virtual, config)
        assert result["num_trials"] == 6
        # Runner 0 -> chips {0,1} (marker "0"), runner 1 -> {2,3} ("2"):
        # disjoint subsets, both exercised.
        markers = sorted(os.listdir(pin_dir))
        assert markers == ["0", "2"], markers


class TestChipPinningEnv:
    """The env a pinned runner starts with, and its refusal to carry on
    anywhere but on the chips it was leased (runner_pool.pin_env /
    _check_leased_chips; the values are what libtpu 0.0.34 needed on a
    four-chip v5e host, CHANGES.md PR 21)."""

    def test_one_chip_needs_only_the_visible_set(self):
        from maggy_tpu.core.runner_pool import chip_env

        assert chip_env(3) == {"TPU_VISIBLE_CHIPS": "3",
                               "ALLOW_MULTIPLE_LIBTPU_LOAD": "1"}

    def test_two_chip_subslice_carries_its_bounds(self):
        from maggy_tpu.core.runner_pool import chip_env, pin_env

        assert chip_env(1, chips_per_trial=2) == pin_env([2, 3]) == {
            "TPU_VISIBLE_CHIPS": "2,3", "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,2,1",
            "TPU_PROCESS_BOUNDS": "1,1,1"}

    def test_pinned_runner_refuses_another_backend(self, monkeypatch):
        """JAX drops to the CPU when libtpu finds no chip; a runner of a
        TPU pool must die there instead of reporting CPU trials."""
        from maggy_tpu.core import runner_pool

        monkeypatch.setenv("JAX_PLATFORMS", "")  # what a TPU host may have
        ran = []
        with pytest.raises(RuntimeError, match="refusing to register"):
            runner_pool._process_entry(ran.append, 0, runner_pool.chip_env(0))
        assert ran == []

    def test_cpu_first_platform_skips_the_check(self, monkeypatch):
        from maggy_tpu.core import runner_pool

        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        ran = []
        runner_pool._process_entry(ran.append, 7, runner_pool.chip_env(0))
        assert ran == [7]

    def test_probe_counts_chips_from_list_coords(self, monkeypatch, capsys):
        """A TPU device's ``coords`` is a list (found on the chip: the
        probe put them in a set and died, silently, so num_workers="auto"
        never worked on a real TPU). Two cores of one chip are one chip."""
        import sys
        import types

        from maggy_tpu.core import runner_pool

        devs = [types.SimpleNamespace(coords=c)
                for c in ([0, 0, 0], [0, 0, 0], [1, 0, 0])]
        monkeypatch.setitem(sys.modules, "jax", types.SimpleNamespace(
            local_devices=lambda: devs))
        exec(runner_pool._DEVICE_PROBE_CODE, {})
        assert capsys.readouterr().out == "2 3"

    def test_failed_probe_says_why(self, monkeypatch):
        from maggy_tpu.core import runner_pool

        monkeypatch.setattr(runner_pool, "_DEVICE_PROBE_CODE",
                            "raise SystemExit('no libtpu here')")
        cfg = OptimizationConfig(name="x", searchspace=space(),
                                 num_workers="auto", pool="tpu")
        with pytest.raises(ValueError, match="no libtpu here"):
            runner_pool.resolve_num_workers(cfg)


def train_elastic(lr, units, budget=1, reporter=None):
    """Marks (budget, visible-chip-count) so the test can assert each
    trial ran on the sub-slice size its budget called for."""
    chips = os.environ.get("TPU_VISIBLE_CHIPS", "")
    n = len(chips.split(",")) if chips else 0
    marker = os.path.join(
        os.environ["MAGGY_TPU_ELASTIC_DIR"],
        "{}_{}_{}".format(int(budget), n, os.getpid()))
    with open(marker, "a") as f:
        f.write("x")
    time.sleep(0.05)
    return {"metric": 1.0 - (lr - 0.1) ** 2}


class TestElasticChipLeasing:
    # Each rung migration respawns pinned worker processes. The hard
    # timeout turns a respawn livelock into a FAILED test instead of a
    # silently-eaten CI budget.
    @pytest.mark.timeout(90)
    def test_budget_sized_subslices(self, local_env, tmp_path, monkeypatch):
        """SURVEY §7.3's central systems problem, virtually: ASHA promotes
        trials to bigger budgets; promoted budget-9 trials require 2-chip
        sub-slices, so 1-chip runners exit and respawn re-pinned (driver
        RESIZE protocol + ElasticTPURunnerPool chip leasing). Every trial
        must run on exactly the sub-slice size its budget maps to, and
        the schedule must complete."""
        from maggy_tpu.optimizers import Asha

        d = tmp_path / "elastic"
        d.mkdir()
        monkeypatch.setenv("MAGGY_TPU_ELASTIC_DIR", str(d))
        config = OptimizationConfig(
            name="elastic_e2e", num_trials=9,
            optimizer=Asha(reduction_factor=3, resource_min=1,
                           resource_max=9, seed=0),
            searchspace=space(), direction="max", num_workers=2,
            hb_interval=0.1, seed=4, es_policy="none",
            pool="elastic", chips_per_trial=1, total_chips=4,
            chips_per_budget={1: 1, 3: 1, 9: 2},
        )
        result = experiment.lagom(train_elastic, config)
        markers = os.listdir(d)
        assert markers, "no trials recorded"
        for m in markers:
            budget, chips, _ = m.split("_")
            assert (chips == "2") == (budget == "9"), \
                "budget {} ran on {} chip(s): {}".format(budget, chips, markers)
        # The promotion chain reached the 2-chip rung.
        assert any(m.startswith("9_") for m in markers), markers
        assert result["num_trials"] >= 9

    @pytest.mark.timeout(90)
    def test_pool_migrates_through_three_rung_sizes(self, local_env,
                                                    tmp_path, monkeypatch):
        """Chips must MIGRATE as rungs drain: 2 one-chip workers (4-chip
        lease budget) serve rung 0, then resize to 2-chip slices for rung
        1, then consolidate into one 4-chip slice for the final rung —
        exercising park, herd-bounded migration, and retirement."""
        from maggy_tpu.optimizers import Asha

        d = tmp_path / "elastic3"
        d.mkdir()
        monkeypatch.setenv("MAGGY_TPU_ELASTIC_DIR", str(d))
        config = OptimizationConfig(
            name="elastic_rungs", num_trials=9,
            optimizer=Asha(reduction_factor=3, resource_min=1,
                           resource_max=9, seed=1),
            searchspace=space(), direction="max", num_workers=2,
            hb_interval=0.1, seed=6, es_policy="none",
            pool="elastic", chips_per_trial=1, total_chips=4,
            chips_per_budget={1: 1, 3: 2, 9: 4},
        )
        result = experiment.lagom(train_elastic, config)
        markers = os.listdir(d)
        expect = {"1": "1", "3": "2", "9": "4"}
        for m in markers:
            budget, chips, _ = m.split("_")
            assert chips == expect[budget], (m, markers)
        assert {m.split("_")[0] for m in markers} == {"1", "3", "9"}
        assert result["num_trials"] >= 9


class TestHeartbeatLossE2E:
    def test_dead_runner_trial_requeued_and_experiment_completes(
            self, local_env, tmp_path, monkeypatch):
        monkeypatch.setenv("MAGGY_TEST_KILL_FLAG", str(tmp_path / "killed.flag"))
        config = OptimizationConfig(
            name="loss_e2e", num_trials=4, optimizer="randomsearch",
            searchspace=space(), direction="max", num_workers=2,
            hb_interval=0.1, hb_loss_timeout=2.0, seed=3,
            es_policy="none", pool="process",
        )
        result = experiment.lagom(train_suicidal, config)
        # One runner died mid-trial; its trial was requeued to the survivor
        # and every scheduled trial still finalized.
        assert result["num_trials"] == 4
        assert result.get("lost_runners", 0) >= 1
        assert os.path.exists(os.environ["MAGGY_TEST_KILL_FLAG"])

    def test_wedged_runner_killed_trial_completes_elsewhere(
            self, local_env, tmp_path, monkeypatch):
        """A runner HUNG (not dead) mid-trial must be
        killed by heartbeat-loss detection — not the whole experiment —
        and its trial must complete on a surviving runner. Without the
        kill, the SIGSTOPped process would block the pool join forever
        and this test would time out."""
        monkeypatch.setenv("MAGGY_TEST_WEDGE_FLAG", str(tmp_path / "wedged.flag"))
        config = OptimizationConfig(
            name="wedge_e2e", num_trials=4, optimizer="randomsearch",
            searchspace=space(), direction="max", num_workers=2,
            hb_interval=0.1, hb_loss_timeout=2.0, seed=3,
            es_policy="none", pool="process",
        )
        result = experiment.lagom(train_wedged, config)
        # The wedge fired, the frozen runner was reaped, its trial re-ran
        # elsewhere, and the full schedule still finalized.
        assert os.path.exists(os.environ["MAGGY_TEST_WEDGE_FLAG"])
        assert result["num_trials"] == 4
        assert result.get("lost_runners", 0) >= 1


class TestLagomKwargsCompat:
    """The reference's 0.x notebook style: lagom(train_fn, searchspace=...,
    optimizer=..., ...) builds an OptimizationConfig (docs/migration.md)."""

    def test_kwargs_build_config(self, local_env):
        result = experiment.lagom(
            train_quadratic, searchspace=space(), optimizer="randomsearch",
            num_trials=3, direction="max", num_workers=2, seed=9,
            es_policy="none", hb_interval=0.05)
        assert result["num_trials"] == 3

    def test_config_plus_kwargs_rejected(self):
        with pytest.raises(TypeError, match="not both"):
            experiment.lagom(
                train_quadratic,
                OptimizationConfig(searchspace=space(), num_trials=1),
                optimizer="randomsearch")

    def test_neither_rejected(self):
        with pytest.raises(TypeError, match="config object"):
            experiment.lagom(train_quadratic)

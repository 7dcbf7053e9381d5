"""GCSEnv contract tests against fsspec's in-memory filesystem.

The production filesystem (gcsfs) needs credentials + network; the contract
— dump/load/ls/delete/mkdir/registry/build_summary — is filesystem-agnostic
through fsspec, so an injected MemoryFileSystem exercises every code path.
"""

import json

import pytest
from fsspec.implementations.memory import MemoryFileSystem

from maggy_tpu import util
from maggy_tpu.core.environment.abstractenvironment import GCSEnv

BASE = "gs://bucket/maggy-exp"


@pytest.fixture
def env():
    fs = MemoryFileSystem()
    # MemoryFileSystem is process-global storage; isolate each test.
    fs.store.clear()
    return GCSEnv(BASE, fs=fs)


class TestContract:
    def test_requires_gs_scheme(self):
        with pytest.raises(ValueError, match="gs://"):
            GCSEnv("/local/path", fs=MemoryFileSystem())

    def test_mkdir_is_real(self, env):
        path = BASE + "/exp_0"
        assert not env.isdir(path)
        env.mkdir(path)
        assert env.isdir(path)
        assert env.ls(path) == []

    def test_dump_load_exists(self, env):
        path = BASE + "/exp_0/trial.json"
        assert not env.exists(path)
        env.dump('{"a": 1}', path)
        assert env.exists(path)
        assert json.loads(env.load(path)) == {"a": 1}

    def test_ls_bare_names(self, env):
        env.dump("x", BASE + "/exp_0/t1/trial.json")
        env.dump("x", BASE + "/exp_0/t2/trial.json")
        env.dump("y", BASE + "/exp_0/result.json")
        names = env.ls(BASE + "/exp_0")
        assert names == ["result.json", "t1", "t2"]

    def test_ls_missing_is_empty(self, env):
        assert env.ls(BASE + "/nope") == []

    def test_delete(self, env):
        env.dump("x", BASE + "/exp_0/a.json")
        env.delete(BASE + "/exp_0/a.json")
        assert not env.exists(BASE + "/exp_0/a.json")
        env.delete(BASE + "/exp_0/a.json")  # idempotent like LocalEnv
        env.dump("x", BASE + "/exp_1/t/f.json")
        env.delete(BASE + "/exp_1", recursive=True)
        assert not env.exists(BASE + "/exp_1/t/f.json")

    def test_open_file_roundtrip(self, env):
        with env.open_file(BASE + "/exp_0/log.txt", "w") as f:
            f.write("line\n")
        with env.open_file(BASE + "/exp_0/log.txt") as f:
            assert GCSEnv.str_or_byte(f.read()) == "line\n"


class TestRegistry:
    def test_register_update_finalize(self, env):
        exp_dir = env.register_experiment("app", 3, {"name": "n"})
        assert exp_dir == BASE + "/app_3"
        meta = json.loads(env.load(exp_dir + "/experiment.json"))
        assert meta["state"] == "RUNNING" and meta["name"] == "n"
        env.update_experiment(exp_dir, {"extra": 1})
        env.finalize_experiment(exp_dir, "FINISHED", {"result": {"best": 2}})
        meta = json.loads(env.load(exp_dir + "/experiment.json"))
        assert meta["state"] == "FINISHED"
        assert meta["extra"] == 1 and meta["result"]["best"] == 2


class TestObjectStoreResume:
    """Resume against a RENAME-LESS backend: GCS has no
    atomic tmp+rename, so the driver's torn-artifact tolerance — not
    LocalEnv's os.replace — is what guarantees old-or-nothing semantics on
    object stores. Drive a full interrupt/tear/resume cycle entirely
    through a gs:// experiment dir."""

    def test_interrupt_tear_resume_full_schedule(self, env, monkeypatch,
                                                 tmp_path):
        import os

        from maggy_tpu import OptimizationConfig, Searchspace, experiment
        from maggy_tpu.core.environment import EnvSing

        count_dir = tmp_path / "counts"
        count_dir.mkdir()
        monkeypatch.setenv("MAGGY_TEST_COUNT_DIR", str(count_dir))
        EnvSing.set_instance(env)
        try:
            def cfg(n, **kw):
                return OptimizationConfig(
                    name="gcs_resume", num_trials=n, optimizer="randomsearch",
                    searchspace=Searchspace(lr=("DOUBLE", [0.0, 0.2]),
                                            units=("INTEGER", [8, 64])),
                    direction="max", num_workers=2, hb_interval=0.05,
                    seed=5, es_policy="none",
                    experiment_dir=BASE + "/runs", **kw)

            from tests.test_resume import train_counting

            r1 = experiment.lagom(train_counting, cfg(3))
            assert r1["num_trials"] == 3
            exp_dir = BASE + "/runs/" + env.ls(BASE + "/runs")[0]
            # Tear one finalized artifact the way an object store can
            # surface it (crashed writer, partial multipart): truncated
            # JSON, no rename to hide behind.
            torn = None
            for name in env.ls(exp_dir):
                p = "{}/{}/trial.json".format(exp_dir, name)
                if env.exists(p):
                    torn = p
                    env.dump(env.load(p)[:17], p)
                    break
            assert torn is not None

            r2 = experiment.lagom(train_counting, cfg(6, resume=True))
            # 2 restored + the torn one re-ran + 3 fresh = 6 total.
            assert r2["num_trials"] == 6
            # The torn trial's artifact was re-written whole.
            import json as _json

            _json.loads(env.load(torn))
        finally:
            EnvSing.reset()


class TestBuildSummary:
    def test_summary_over_trial_dirs(self, env):
        exp_dir = env.register_experiment("app", 0, {})
        for tid, metric in [("t1", 0.5), ("t2", 0.9)]:
            env.dump(json.dumps({"lr": 0.1}),
                     "{}/{}/.hparams.json".format(exp_dir, tid))
            env.dump(json.dumps({"metric": metric}),
                     "{}/{}/.outputs.json".format(exp_dir, tid))
        summary = util.build_summary(exp_dir, env=env)
        assert len(summary["combinations"]) == 2
        ids = {c["id"] for c in summary["combinations"]}
        assert ids == {"t1", "t2"}
        assert env.exists(exp_dir + "/.summary.json")


class TestRegistryOnGCS:
    def test_register_and_resolve_through_gcs(self, env, tmp_path):
        """DatasetRegistry must work unchanged on a bucket-backed env:
        manifests go through the env fs, data paths stay wherever the
        data lives (here, local npz)."""
        import numpy as np

        from maggy_tpu.train.registry import DatasetRegistry

        p = str(tmp_path / "d.npz")
        np.savez(p, x=np.arange(6, dtype=np.float32))
        reg = DatasetRegistry(env=env)
        v = reg.register("toy", p, description="bucketed manifest")
        assert v == 1
        assert reg.root.startswith("gs://")
        m = reg.get("toy")
        assert m["path"] == p and m["schema"] == {"x": "float32"}
        assert reg.names() == ["toy"] and reg.versions("toy") == [1]

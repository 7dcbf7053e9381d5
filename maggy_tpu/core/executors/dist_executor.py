"""Distributed-training executor: one SPMD process of the training world.

Parity: reference `maggy/core/executors/dist_executor.py:40-224` — register +
heartbeat (logs), `await_reservations` barrier, coordinator rendezvous
(TORCH_CONFIG -> DIST_CONFIG), environment setup, process-group init,
model wrapping, train_fn invocation, FINAL metric.

Redesign (SURVEY.md §5.8): `dist.init_process_group("nccl")` + DDP becomes
`jax.distributed.initialize(coordinator, num_processes, process_id)` +
a `ShardingEnv` (mesh + named shardings). Gradient all-reduce is emitted by
GSPMD inside the user's jit step — there is no wrapper object. Seeding
mirrors the reference's determinism setup (`dist_executor.py:208-214`) via a
fixed `jax.random.PRNGKey` handed through the env.
"""

from __future__ import annotations

import inspect
import os
import traceback
from typing import Callable, Optional, Tuple

from maggy_tpu import util
from maggy_tpu.core.environment import EnvSing
from maggy_tpu.core.reporter import Reporter
from maggy_tpu.core.rpc import Client
from maggy_tpu.parallel.mesh import ShardingEnv, make_mesh


class DistExecutor:
    """Module-level class: picklable for process pools."""

    def __init__(
        self,
        server_addr: Tuple[str, int],
        secret: str,
        hb_interval: float,
        exp_dir: str,
        train_fn: Callable,
        config,
        num_workers: int,
        profile: bool = False,
    ):
        self.server_addr = server_addr
        self.secret = secret
        self.hb_interval = hb_interval
        self.exp_dir = exp_dir
        self.train_fn = train_fn
        self.config = config
        self.num_workers = num_workers
        self.profile = profile or bool(getattr(config, "profile", False))

    def __call__(self, partition_id: int) -> None:
        env = EnvSing.get_instance()
        util.enable_compile_cache()
        task_attempt = int(os.environ.get("MAGGY_TPU_TASK_ATTEMPT", "0"))
        reporter = Reporter(
            log_file="{}/worker_{}_{}.log".format(self.exp_dir, partition_id, task_attempt)
        )
        reporter.reset(trial_id="dist")
        client = Client(self.server_addr, partition_id, task_attempt,
                        self.hb_interval, self.secret)
        # Worker-side telemetry, same channel as trial runners: broadcast
        # cadence + heartbeat RTT + memory, delta-encoded onto heartbeats.
        # The whole job is one "trial" from the stats' point of view.
        from maggy_tpu.telemetry.runnerstats import RunnerStats

        stats = RunnerStats()
        stats.trial_start("dist")
        reporter.stats = stats
        client.runner_stats = stats
        try:
            # Advertise our coordinator endpoint; worker 0's is the rendezvous
            # address (reference `rpc.py:409-416`).
            coord_port = int(os.environ.get("MAGGY_TPU_COORD_PORT", "7733"))
            host = env.get_ip_address()
            client.register(host_port="{}:{}".format(host, coord_port))
            client.start_heartbeat(reporter)
            import time as _time

            t_barrier = _time.monotonic()
            client.await_reservations()
            dist_config = client.get_dist_config()
            # Registration-barrier + coordinator-rendezvous latency, as the
            # WORKER saw it; shipped on FINAL so the driver's telemetry can
            # histogram world bring-up without instrumenting each host.
            rendezvous_ms = (_time.monotonic() - t_barrier) * 1e3

            sharding_env = self._init_cluster(dist_config, partition_id, reporter)
            if self.profile:
                import jax

                logdir = "{}/tensorboard_worker{}".format(self.exp_dir, partition_id)
                with jax.profiler.trace(logdir):
                    metric = self._run_train_fn(sharding_env, reporter)
            else:
                metric = self._run_train_fn(sharding_env, reporter)
            client.finalize_metric(
                metric, reporter,
                extra={"telem": {"rendezvous_ms": round(rendezvous_ms, 3)}})
        except Exception:  # noqa: BLE001
            reporter.log("Distributed worker {} failed:\n{}".format(
                partition_id, traceback.format_exc()))
            with reporter.lock:
                client._request({"type": "FINAL", "trial_id": "dist", "value": None,
                                 "error": True, "logs": reporter.get_data()["logs"]})
                reporter.reset()
            raise
        finally:
            client.stop()

    def _init_cluster(self, dist_config, partition_id: int, reporter) -> ShardingEnv:
        """Bring up the JAX world and build the mesh (replaces
        `_init_cluster`'s NCCL setup, reference `dist_executor.py:197-223`)."""
        import jax

        num_processes = dist_config["num_processes"]
        multiprocess = (
            num_processes > 1
            and os.environ.get("MAGGY_TPU_DIST_INIT", "1") == "1"
            and not _in_thread_pool()
        )
        if multiprocess:
            jax.distributed.initialize(
                coordinator_address=dist_config["coordinator_address"],
                num_processes=num_processes,
                process_id=partition_id,
            )
            reporter.log("jax.distributed initialized: {}/{} at {}".format(
                partition_id, num_processes, dist_config["coordinator_address"]))
        mesh = make_mesh(self.config.mesh_shape or {})
        return ShardingEnv(
            mesh=mesh,
            process_index=jax.process_index() if multiprocess else partition_id,
            process_count=num_processes,
        )

    def _run_train_fn(self, sharding_env: ShardingEnv, reporter) -> Optional[float]:
        kwargs = {}
        sig = inspect.signature(self.train_fn).parameters
        if "model" in sig:
            kwargs["model"] = self.config.model
        if "train_set" in sig:
            kwargs["train_set"] = self.config.train_set
        if "test_set" in sig:
            kwargs["test_set"] = self.config.test_set
        if "sharding_env" in sig:
            kwargs["sharding_env"] = sharding_env
        if "reporter" in sig:
            kwargs["reporter"] = reporter
        retval = self.train_fn(**kwargs)
        if isinstance(retval, dict):
            return float(retval.get("metric", next(iter(retval.values()))))
        return float(retval) if retval is not None else None


def _in_thread_pool() -> bool:
    """True when running inside a ThreadRunnerPool (workers share one JAX
    runtime; per-process distributed init is impossible)."""
    import threading

    return threading.current_thread().name.startswith("runner-")


def dist_executor_fn(**kwargs) -> DistExecutor:
    return DistExecutor(**kwargs)

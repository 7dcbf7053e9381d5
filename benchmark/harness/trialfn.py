"""The benchmark's trial function, and the measured window as a runner sees it.

`lagom` has no time budget, so the window is kept by the trial function that
every cell hands to it. Per runner (a thread of the measuring process, or a
pinned process of its own) there is one `_Runner`:

- *warm-up* lasts until the runner has done what the mix's ``warmup`` says
  (``{"trials": n}``: finished n trial functions, save included;
  ``{"steps": n}``: dispatched n steps of its trial). It then drops
  ``ready.<partition>`` into ``<exp_dir>/bench/``. All runners share that
  directory and the host's clock.
- the *window* opens on a runner at the first moment at which every runner's
  marker exists and this runner has synced its device: ``t0``. Its deadline
  is ``t0 + seconds``.
- the step loop asks `_Runner.poll` once per dispatched step, and once more
  when the trial's last loss has reached the host. Past the deadline it
  blocks on the last loss, stamps ``t1`` = now and the trial returns without
  saving: every step the window counts has then finished inside it, and
  the window is ``--seconds`` plus what was in flight at the deadline (the
  dispatch queue, at most one short trial). Only a runner that is between
  trials or inside a save at the deadline, with no step in flight, has
  ``t1`` = the deadline. Every later trial returns at once, so the rest of
  the sweep drains in milliseconds a trial.

The loop itself is `Trainer.fit`'s: ``trainer.step(trainer.place_batch(b))``
then ``reporter.broadcast(loss, step=i)``, with no sync of the harness's own
inside the window.

What a trial did goes into its return value, which `lagom` writes to the
trial's ``.outputs.json``; what the runner's window was goes to
``bench/window.<partition>.json``. ``--trace 1`` adds a `jax.profiler` trace
of a few seconds inside the window, taken by the process that holds the chip.
"""

from __future__ import annotations

import json
import os
import threading
import time

from benchmark.harness import spec

WARMUP, WINDOW, DONE = "warmup", "window", "done"

#: What a trial past the deadline reports as its metric: finite, and worse
#: than any loss, so that no real trial is ranked behind it.
DRAINED_METRIC = 1e9

#: The trace starts this share of the window after ``t0`` and lasts
#: ``TRACE_SECONDS`` (or a fifth of a short window). Short, because stopping a
#: session costs from 6 to 50 s per traced second.
TRACE_START_SHARE = 0.4
TRACE_SECONDS = 1.5

_LOCK = threading.Lock()
_RUNNERS: dict = {}
_BATCHES: dict = {}
_TRACE_OWNER: dict = {}


def bench_dir(exp_dir: str) -> str:
    return os.path.join(exp_dir, "bench")


def _write_json(path: str, obj) -> None:
    tmp = "{}.tmp.{}".format(path, threading.get_ident())
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


def _cache_counters() -> dict:
    """Persistent-cache hits and misses of this runner so far
    (`jax.monitoring` events counted by ``train/warm.py``)."""
    from maggy_tpu.train import warm

    scope = warm.current_scope()
    stats = getattr(scope, "stats", None)
    snap = stats.snapshot() if stats is not None else {}
    return {k: int(snap.get("xla_cache_" + k, 0)) for k in ("hits", "misses")}


class _Runner:
    """One runner's view of the window."""

    def __init__(self, cell: dict, exp_dir: str, partition: int):
        self.cell = cell
        self.dir = bench_dir(exp_dir)
        self.partition = partition
        self.phase = WARMUP
        self.ready = False
        self.warm_trials = 0
        self.t0 = self.t1 = self.deadline = None
        self.counters0 = self.counters1 = None
        self.pallas_calls = None
        self.trace = None
        self._trace_thread = None
        os.makedirs(self.dir, exist_ok=True)

    # ------------------------------------------------------------ warm-up
    def mark_ready(self) -> None:
        if not self.ready:
            self.ready = True
            _write_json(os.path.join(
                self.dir, "ready.{}".format(self.partition)),
                {"t": time.time()})

    def _all_ready(self) -> bool:
        n = sum(1 for f in os.listdir(self.dir) if f.startswith("ready.")
                and ".tmp." not in f)
        return n >= self.cell["n_runners"]

    # ------------------------------------------------------------- window
    def poll(self, sync=None):
        """Advance the phase. ``sync`` is the last dispatched loss, or None
        where no step of this runner is in flight (between trials, or in a
        save). Returns "opened" or "closed" when this call did that."""
        if self.phase == WARMUP:
            if self.ready and self._all_ready():
                import jax

                if sync is not None:
                    jax.block_until_ready(sync)
                self.t0 = time.time()
                self.deadline = self.t0 + self.cell["seconds"]
                self.counters0 = _cache_counters()
                self.phase = WINDOW
                self._start_trace()
                self.write()
                return "opened"
        elif self.phase == WINDOW and time.time() >= self.deadline:
            if sync is not None:
                import jax

                jax.block_until_ready(sync)
                self._close(time.time())
            else:
                self._close(self.deadline)
            return "closed"
        return None

    def _close(self, t1: float) -> None:
        self.t1 = t1
        self.counters1 = _cache_counters()
        self.phase = DONE
        if self._trace_thread is not None:
            self._trace_thread.join(timeout=240)
        self.write()

    def write(self) -> None:
        import jax

        device = jax.devices()[0]
        memory = device.memory_stats() or {}
        _write_json(os.path.join(
            self.dir, "window.{}.json".format(self.partition)), {
            "partition": self.partition, "pid": os.getpid(),
            "t0": self.t0, "deadline": self.deadline, "t1": self.t1,
            "counters0": self.counters0, "counters1": self.counters1,
            "platform": device.platform, "device_kind": device.device_kind,
            "n_devices": len(jax.devices()),
            "memory_stats": memory,
            "pallas_calls": self.pallas_calls, "trace": self.trace,
        })

    # -------------------------------------------------------------- trace
    def _start_trace(self) -> None:
        if not self.cell["trace"]:
            return
        with _LOCK:  # one profiler session per process
            if _TRACE_OWNER.setdefault(self.dir, self.partition) \
                    != self.partition:
                return
        self._trace_thread = threading.Thread(
            target=self._trace_worker, name="bench-trace", daemon=True)
        self._trace_thread.start()

    def _trace_worker(self) -> None:
        import jax

        seconds = self.cell["seconds"]
        time.sleep(TRACE_START_SHARE * seconds)
        log_dir = os.path.join(self.dir, "trace.{}".format(self.partition))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # device and runtime events only
        options.enable_hlo_proto = False  # stopping is slow enough without
        started = time.time()
        jax.profiler.start_trace(log_dir, profiler_options=options)
        time.sleep(min(TRACE_SECONDS, 0.2 * seconds))
        stopping = time.time()
        jax.profiler.stop_trace()
        self.trace = {"dir": log_dir, "t_start": started, "t_stop": stopping,
                      "stop_took_s": time.time() - stopping}
        self.write()  # the sweep may run out of trials before the deadline


def _runner(cell: dict, ctx) -> _Runner:
    key = (ctx.exp_dir, int(ctx.info.get("partition", 0)))
    with _LOCK:
        if key not in _RUNNERS:
            _RUNNERS[key] = _Runner(cell, key[0], key[1])
        return _RUNNERS[key]


def _host_batches(cell: dict, family):
    """The mix's host batches, made once per process from the seed."""
    mix = cell["mix"]
    key = (cell["name"], cell["seed"])
    with _LOCK:
        if key not in _BATCHES:
            _BATCHES[key] = family.batches(
                cell["config"]["model"], mix["batch"], mix["seq"],
                cell["seed"])
        return _BATCHES[key]


def _target_steps(mix: dict, budget):
    """Steps a trial trains in all (its parent's included), or None for
    "until the deadline"."""
    length = mix["trial_steps"]
    if length == "until_deadline":
        return None
    if "fixed" in length:
        return int(length["fixed"])
    return int(round(float(budget) * length["per_budget_unit"]))


def trial(reporter=None, ctx=None, *, cell, **hparams):
    """One trial of any cell. ``cell`` is plain data: the configuration, the
    mix, the seed, the window's length and the number of runners."""
    import jax

    run = _runner(cell, ctx)
    run.poll()
    if run.phase == DONE:
        return {"metric": DRAINED_METRIC, "drained": True}

    import optax

    from maggy_tpu.parallel import make_mesh
    from maggy_tpu.train import Trainer, swept_transform

    if jax.default_backend() != cell["platform"]:
        raise RuntimeError("the cell needs backend {!r}, JAX reports {!r}"
                           .format(cell["platform"], jax.default_backend()))
    mix, config = cell["mix"], cell["config"]
    family = spec.load_module("families", config["family"])
    batches = _host_batches(cell, family)
    module, _ = family.build(config["model"])
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    # lr rides in opt_state, so a whole sweep is ONE program.
    trainer = Trainer(
        module, swept_transform(
            optax.adamw, learning_rate=hparams.get("lr", mix.get("lr"))),
        family.loss, mesh, strategy="dp")
    example, init_kwargs = family.init_args(batches[0])
    trainer.init(jax.random.key(cell["seed"]), example,
                 init_kwargs=init_kwargs)

    start = 0
    if ctx.resume_step is not None:
        live = {"variables": trainer.variables, "opt_state": trainer.opt_state}
        state = ctx.restore_checkpoint(jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding), live))
        trainer.variables, trainer.opt_state = \
            state["variables"], state["opt_state"]
        start = ctx.resume_step + 1

    total = _target_steps(mix, hparams.get("budget"))
    warm_steps = mix["warmup"].get("steps")
    # Steps of this trial dispatched before the window opened: 0 when it
    # was open at entry, None while it has not opened yet.
    opened_at = 0 if run.phase == WINDOW else None
    first_loss = loss = None
    step = start
    while total is None or step < total:
        loss = trainer.step(trainer.place_batch(batches[step % len(batches)]))
        if first_loss is None:
            first_loss = loss
        reporter.broadcast(loss, step=step)  # lazy: no host sync in the loop
        step += 1
        if warm_steps is not None and step - start >= warm_steps:
            run.mark_ready()
        event = run.poll(loss)
        if event == "opened":
            opened_at = step - start
        elif event == "closed":
            break
    metric = DRAINED_METRIC
    if loss is not None:
        # As `Trainer.fit` returns: the final loss on the host, which waits
        # for every dispatched step. A short trial's steps are all in the
        # runtime's queue by now, so the deadline passes here far more often
        # than in the loop: the window closes now, when the steps it counts
        # have finished, and not back at the deadline.
        metric = float(loss)
        if run.poll(loss) == "opened":
            opened_at = step - start

    if run.phase != DONE and mix["checkpoint"] and step > start:
        ctx.save_checkpoint(step - 1, {"variables": trainer.variables,
                                       "opt_state": trainer.opt_state})
    if run.phase == WARMUP:
        run.warm_trials += 1
        if run.warm_trials >= mix["warmup"].get("trials", float("inf")):
            run.mark_ready()
    if run.phase != WINDOW and run.pallas_calls is None:
        # The executable that ran; Mosaic kernels are tpu_custom_call in its
        # HLO. Read once per runner, never inside the window.
        text = getattr(trainer._active_step, "as_text", lambda: None)()
        if text is not None:
            run.pallas_calls = text.count(
                'custom_call_target="tpu_custom_call"')
            if run.phase == DONE:
                run.write()
    if run.poll() == "opened":  # the device is idle: nothing to sync
        opened_at = step - start
    return {
        "metric": metric, "drained": False,
        "first_loss": None if first_loss is None else float(first_loss),
        "first_step": start, "steps_run": step - start,
        "opened_at_step": opened_at, "target_steps": total,
        "pallas_calls": run.pallas_calls,
    }

"""Benchmark: ASHA trials/hour through the full framework stack on one chip.

The BASELINE metric (BASELINE.md / BASELINE.json): the reference publishes
no numbers, so the comparison point is STAGE-BASED execution — what the
reference's own pitch positions async scheduling against
(`README.rst:21-26`). Two baselines run over the sweep's executed schedule:

- PRIMARY (``vs_baseline``): synchronous successive halving — each rung's
  runs packed over the workers, a BARRIER between rungs, early-stopped
  trials at full budget. This is the best a stage scheduler can actually
  do: rung N+1's trial set is computed from rung N's results, so no stage
  system can overlap rungs, and it has no mid-trial control (ASHA paper,
  arXiv:1810.05934, makes the same comparison).
- SECONDARY (``detail.oracle_replay``): the async run's OWN executed
  schedule replayed packed with no barriers at all — an oracle no real
  scheduler could produce (it needs the outcomes before running them). The
  framework-to-oracle ratio isolates pure scheduling+control overhead.

Output contract: up to TWO JSON lines on stdout — the headline
{"metric", "value", "unit", "vs_baseline"} printed before any extra bench
touches the device, then (when extras ran) an enriched line with the SAME
headline values plus extras merged into "detail". A consumer taking either
the first or the last JSON line reads the same headline numbers. The headline
and the extras are device measurements: where JAX finds no TPU the run exits
non-zero and prints no metric.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import numpy as np


def make_data(n=2048, key=0):
    rng = np.random.default_rng(key)
    X = rng.normal(size=(n, 16, 16, 1)).astype(np.float32)
    y = (X.mean(axis=(1, 2, 3)) > 0).astype(np.int32)
    return X, y


DATA_X, DATA_Y = make_data()
STEPS_PER_BUDGET = int(os.environ.get("BENCH_STEPS", "40"))
# Swept batch sizes: trial DURATION varies ~4x across the space — the
# normal shape of a real sweep (batch/width/depth hparams change cost), and
# precisely what stage-based execution pays for: every synchronized wave
# waits for its slowest member, while the async scheduler backfills.
BATCH_CHOICES = [128, 256, 512]


def _bench_loss(logits, batch):
    from maggy_tpu.train import cross_entropy_loss

    return cross_entropy_loss(logits, batch["labels"])


def train_mnist(lr, batch=256, budget=1, reporter=None):
    """One ASHA trial: budget-scaled training of the MNIST CNN. Shapes
    depend only on the DISCRETE batch hparam, so the whole sweep compiles
    exactly len(BATCH_CHOICES) train steps — shared through the warm
    cache's AUTOMATIC program key (model config + mesh + swept-optimizer
    family; no hand-written step_key), the compile-once default."""
    import jax
    import jax.numpy as jnp
    import optax

    from maggy_tpu.models import MnistCNN
    from maggy_tpu.train import (ShardedBatchIterator, Trainer,
                                 cross_entropy_loss, swept_transform)
    from maggy_tpu.parallel import make_mesh

    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    model = MnistCNN(kernel_size=3, pool_size=2, features=16, num_classes=2)
    # lr rides in opt_state (swept_transform), so every trial of the sweep
    # is the SAME program: repeat-shape trials reuse the warm slot's
    # compiled step and init programs.
    trainer = Trainer(
        model, swept_transform(optax.adam, learning_rate=lr),
        _bench_loss, mesh, strategy="dp",
    )
    trainer.init(jax.random.key(0), (jnp.zeros((1, 16, 16, 1)),))
    steps = max(1, int(STEPS_PER_BUDGET * budget))
    it = iter(ShardedBatchIterator({"x": DATA_X, "y": DATA_Y},
                                   batch_size=int(batch), epochs=None, seed=1))
    loss = None
    for i in range(steps):
        b = next(it)
        loss = trainer.step(trainer.place_batch(
            {"inputs": (jnp.asarray(b["x"]),), "labels": jnp.asarray(b["y"])}))
        if reporter is not None and i % 2 == 0:
            # Maps step onto the shared [0, max-budget] resource axis so the
            # median rule compares trials at equal progress. The metric is
            # passed as a LAZY device scalar — the reporter materializes it
            # on the heartbeat thread, so the step stream stays pipelined
            # (a blocking float() here would wait for the device each time).
            reporter.broadcast(-loss, step=i)
    return {"metric": -float(loss)}


# --vmap micro-trial knobs: the trial body must DOMINATE the per-trial
# control-plane cost (dir mint, journal edges, FINAL round-trip) or the
# block's K-for-one dispatch saving drowns in fixed overhead and the
# speedup gate measures the scheduler, not the engine.
VMAP_STEPS = int(os.environ.get("BENCH_VMAP_STEPS", "2500"))
VMAP_BATCH = int(os.environ.get("BENCH_VMAP_BATCH", "256"))


def train_mnist_vmap(lr, lanes=None, reporter=None):
    """Micro-trial for the --vmap gate: a tiny MnistMLP (matmul +
    elementwise only — the model family the lane-parity property is
    stated on) trained full-batch for VMAP_STEPS. Lanes-capable: under
    ``config.vmap_lanes`` > 1 the executor hands a `LaneSet` and the K
    configs train as ONE vmapped program; with ``lanes=None`` (scalar
    dispatch, and the warm-up trial every runner's first dispatch always
    is) it degrades to the plain Trainer path."""
    import jax
    import jax.numpy as jnp
    import optax

    from maggy_tpu.models import MnistMLP
    from maggy_tpu.parallel import make_mesh
    from maggy_tpu.train import Trainer, VmapTrainer, swept_transform

    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    model = MnistMLP(features=8, num_classes=2)
    batch = {"inputs": (jnp.asarray(DATA_X[:VMAP_BATCH]),),
             "labels": jnp.asarray(DATA_Y[:VMAP_BATCH])}
    rng = jax.random.key(0)
    if lanes is None:
        trainer = Trainer(
            model, swept_transform(optax.adam, learning_rate=lr),
            _bench_loss, mesh, strategy="dp")
        trainer.init(rng, (batch["inputs"][0][:1],))
        loss = None
        for i in range(VMAP_STEPS):
            loss = trainer.step(trainer.place_batch(batch))
            if reporter is not None and i % 100 == 0:
                reporter.broadcast(-loss, step=i)
        return {"metric": -float(loss)}
    # Vectorized block: one AOT executable trains every lane in lockstep.
    # The raw (unplaced) batch is broadcast across lanes by the trainer
    # (in_axes=None on the batch leaf).
    vt = VmapTrainer(
        model, optax.adam,
        [{"learning_rate": h["lr"]} for h in lanes.hparams],
        _bench_loss, mesh, strategy="dp")
    vt.init(rng, (batch["inputs"][0][:1],))
    losses = None
    for i in range(VMAP_STEPS):
        losses = vt.step(batch)
        if i % 100 == 0:
            reporter.broadcast_lanes(-jnp.asarray(losses), step=i)
            for li in lanes.take_stopped():
                lanes.retire(li, -float(np.asarray(losses)[li]))
    final = np.asarray(losses)
    return {tid: -float(final[i])
            for i, tid in enumerate(lanes.trial_ids)}


def run_framework_sweep(num_trials=None, workers=3):
    if num_trials is None:
        num_trials = int(os.environ.get("BENCH_NUM_TRIALS", "18"))
    from maggy_tpu import OptimizationConfig, Searchspace, experiment
    from maggy_tpu.optimizers import Asha

    sp = Searchspace(lr=("DOUBLE_LOG", [1e-4, 3e-2]),
                     batch=("DISCRETE", BATCH_CHOICES))
    # ASHA multi-fidelity schedule + median-rule mid-trial early stopping:
    # the two async control loops the reference pitches against stage-based
    # execution (`README.rst:21-26`). The wave baseline below runs the SAME
    # trials without them — a stage scheduler cannot stop a running trial.
    config = OptimizationConfig(
        name="bench_asha", num_trials=num_trials,
        optimizer=Asha(reduction_factor=3, resource_min=1, resource_max=9, seed=0),
        searchspace=sp, direction="max", num_workers=workers,
        hb_interval=0.1, es_policy="median", es_interval=1, es_min=3, seed=0,
    )
    t0 = time.time()
    result = experiment.lagom(train_mnist, config)
    wall = time.time() - t0
    return result, wall


def run_packed_baseline(schedule, workers=3):
    """Runs executed by ``workers`` bare threads pulling from a shared
    queue — packed/backfilled, no synchronization beyond the final join.
    This models tasks inside ONE stage (a Spark stage backfills tasks onto
    free executors); device parallelism is identical to the framework run,
    with none of its control plane."""
    import queue as _queue
    import threading

    q = _queue.SimpleQueue()
    for args in schedule:
        q.put(args)
    errors = []

    def worker():
        while True:
            try:
                lr, batch, budget = q.get_nowait()
            except _queue.Empty:
                return
            try:
                train_mnist(lr, batch=batch, budget=budget)
            except BaseException as e:  # noqa: BLE001
                errors.append(e)
                return

    t0 = time.time()
    threads = [threading.Thread(target=worker) for _ in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        # A failed baseline trial would silently shrink the measurement.
        raise errors[0]
    return time.time() - t0


def run_sync_sha_baseline(rung_schedule, workers=3):
    """Synchronous successive halving: each rung's runs packed over the
    workers, with a BARRIER between rungs (a stage scheduler must finish
    rung k to compute rung k+1's promotions), and no mid-trial control
    (early-stopped trials at full budget). The PRIMARY stage-based
    comparator."""
    t0 = time.time()
    for rung in sorted(rung_schedule):
        run_packed_baseline(rung_schedule[rung], workers=workers)
    return time.time() - t0


def log(msg):
    print("[bench] {}".format(msg), file=sys.stderr, flush=True)


def _current_platform():
    """The substrate THIS process measures on — stamped into every
    detail block (numbers are only comparable within one platform) and
    checked by the A/B parity comparator, which refuses to compare
    mixed-platform arms."""
    import jax

    return str(jax.default_backend())


def _device_stamp():
    """platform / device_kind / device count as JAX reports them: every
    JSON a chip-bound bench child prints carries it."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def _require_tpu():
    """Chip-bound children (--headline, --extra) measure a device metric:
    where JAX found no TPU they fail instead of measuring the CPU under
    the metric's name."""
    stamp = _device_stamp()
    if stamp["platform"] != "tpu":
        raise SystemExit(
            "bench: this measurement needs a TPU, but JAX reports {} — "
            "refusing to run it on another platform".format(stamp))
    return stamp


def run_compile_ab(trials=None, workers=1):
    """Repeat-shape warm_start A/B (ROADMAP item 3's gate): the SAME
    fixed-shape random-search sweep run twice on the SAME platform — warm
    path on (the default) vs off (legacy build-per-trial). Returns per-arm
    wall/ttfm numbers plus the gate: within the WARM run (cold first trial
    vs warm repeats — same run, same platform), repeat-shape warm ttfm p50
    must land >=5x below the cold ttfm p50.
    """
    import functools
    import glob as _glob

    from maggy_tpu import OptimizationConfig, Searchspace, experiment
    from maggy_tpu.telemetry import JOURNAL_NAME, replay_journal
    from maggy_tpu.train import clear_warm

    if trials is None:
        trials = int(os.environ.get("BENCH_AB_TRIALS", "6"))
    # Fixed batch/budget: every trial is the same program+shape, so trial
    # 1 is the arm's only cold compile and 2..N are pure repeat-shape.
    train_fn = functools.partial(train_mnist, batch=256, budget=0.5)
    out = {}
    for arm, warm_on in (("warm", True), ("cold", False)):
        clear_warm()  # each arm starts from an empty warm cache
        arm_dir = os.path.join(os.environ["MAGGY_TPU_BASE_DIR"],
                               "compile_ab_{}".format(arm))
        config = OptimizationConfig(
            name="bench_ab_{}".format(arm), num_trials=trials,
            optimizer="randomsearch",
            searchspace=Searchspace(lr=("DOUBLE_LOG", [1e-4, 3e-2])),
            direction="max", num_workers=workers, hb_interval=0.1,
            es_policy="none", seed=11, warm_start=warm_on,
            experiment_dir=arm_dir,
        )
        t0 = time.time()
        experiment.lagom(train_fn, config)
        wall = time.time() - t0
        exp_dirs = sorted(d for d in _glob.glob(os.path.join(arm_dir, "*"))
                          if os.path.isdir(d))
        derived = replay_journal(os.path.join(exp_dirs[-1], JOURNAL_NAME))
        comp = derived.get("compile") or {}
        out[arm] = {
            "wall_s": round(wall, 2),
            "trials": trials,
            "warm_hits": comp.get("warm_hits", 0),
            "warm_misses": comp.get("warm_misses", 0),
            "ttfm_warm": comp.get("ttfm_warm") or {},
            "ttfm_cold": comp.get("ttfm_cold") or {},
            # The arm's chip-time ledger + platform: --goodput gates
            # warm-vs-cold COMPILE badput on these, and the stamp feeds
            # the same-platform refusal.
            "goodput": derived.get("goodput") or {},
            "platform": _current_platform(),
        }
    warm_p50 = (out["warm"]["ttfm_warm"] or {}).get("median_ms")
    cold_p50 = (out["warm"]["ttfm_cold"] or {}).get("median_ms")
    gate = {"warm_ttfm_p50_ms": warm_p50, "cold_ttfm_p50_ms": cold_p50}
    if warm_p50 and cold_p50:
        gate["ratio"] = round(cold_p50 / warm_p50, 2)
        gate["gate_ok"] = cold_p50 >= 5.0 * warm_p50
    if out["warm"]["wall_s"] and out["cold"]["wall_s"]:
        gate["trials_per_hour_ratio"] = round(
            out["cold"]["wall_s"] / out["warm"]["wall_s"], 3)
    out["gate"] = gate
    return out


def handoff_gaps(trials):
    """FALLBACK hand-off estimator from trial.json dicts (start+duration
    -> same runner's next start), for experiment dirs that predate the
    telemetry journal. The artifact of record is now the journal:
    `scheduling_telemetry` replays <exp_dir>/telemetry.jsonl through
    `maggy_tpu.telemetry.replay_journal`, whose driver-observed span
    timestamps ("finalized" -> same partition's next "running") measure
    the control plane directly instead of reconstructing it. Gaps
    spanning rung-barrier idle waits are excluded by capping at 2 s
    (idling on purpose is scheduling, not overhead) — both paths share
    that rule, so the numbers stay comparable across rounds."""
    by_partition = {}
    for t in trials:
        pid = (t.get("info_dict") or {}).get("partition")
        if pid is None or t.get("start") is None or t.get("duration") is None:
            continue
        by_partition.setdefault(pid, []).append(
            (t["start"], t["start"] + t["duration"]))
    gaps = []
    for runs in by_partition.values():
        runs.sort()
        for (s0, e0), (s1, _) in zip(runs, runs[1:]):
            gap = s1 - e0
            if 0 <= gap < 2.0:
                gaps.append(gap * 1e3)
    if not gaps:
        return {}
    gaps.sort()
    return {"median_ms": round(gaps[len(gaps) // 2], 1),
            "p95_ms": round(gaps[int(len(gaps) * 0.95)], 1),
            "n": len(gaps)}


def scheduling_telemetry(exp_dir, trial_dicts):
    """Hand-off gap + early-stop reaction latency for the detail block,
    derived from the experiment's telemetry journal. The journal is the
    reproducibility contract: `maggy_tpu.telemetry.replay_journal` over
    the SAME file yields the SAME numbers offline, so a BENCH_*.json
    detail block can be re-derived from the artifact alone. Falls back to
    the trial.json reconstruction for pre-telemetry experiment dirs."""
    from maggy_tpu.telemetry import JOURNAL_NAME, replay_journal

    journal = os.path.join(exp_dir, JOURNAL_NAME)
    if os.path.exists(journal):
        derived = replay_journal(journal)
        return {
            "handoff": derived.get("handoff") or {},
            "early_stop_reaction": derived.get("early_stop_reaction") or {},
            # Pipelined hand-off health: prefetch hit/miss counts + hit
            # rate and controller suggest() latency (empty when the sweep
            # ran with config.prefetch=False or a pre-pipeline journal).
            "suggest": derived.get("suggest") or {},
            # Compile-once hot path: warm-slot hit rate, ttfm split
            # cold/warm, phase breakdown, persistent-cache counters
            # (empty for warm_start=False or pre-warm journals).
            "compile": derived.get("compile") or {},
            # Chip-time goodput ledger: where every held chip-second of
            # the sweep went (train vs init/compile/ckpt/rework/handoff/
            # queue_wait/idle badput, unaccounted residual).
            "goodput": derived.get("goodput") or {},
            "source": "telemetry_journal",
            "journal": journal,
        }
    return {"handoff": handoff_gaps(trial_dicts),
            "early_stop_reaction": {},
            "suggest": {},
            "compile": {},
            "goodput": {},
            "source": "trial_json_fallback"}


def analysis_detail(witness=None):
    """``detail.analysis``: the static-analysis posture of the package
    this bench ran against — finding/suppression counts per checker, the
    lock inventory, and (when a soak ran under the lock-order witness)
    the dynamically observed edge count. Recorded in every BENCH_*.json
    so concurrency-discipline drift shows up in the trajectory next to
    the perf numbers (a new suppression or a findings spike is visible
    without re-running the analyzer against an old checkout)."""
    try:
        from maggy_tpu.analysis import run_analysis

        report = run_analysis()
        out = {
            "findings": len(report["findings"]),
            "per_checker": report["summary"],
            "suppressed": len(report["suppressed"]),
            "locks": report["num_locks"],
            "order_edges": len(report.get("lock_edges", [])),
        }
    except Exception as e:  # noqa: BLE001 - posture is best-effort here;
        # the tier-1 conformance test is the enforcement point
        out = {"error": repr(e)}
    if witness:
        out["witness_edges"] = witness.get("edge_count")
        out["witness_violations"] = len(witness.get("violations") or [])
    return out


# ------------------------------------------------------------- MFU + kernels

# Peak bf16 matmul throughput per chip, by device_kind prefix. Source: Google
# Cloud TPU documentation, the "System architecture" page of each generation
# (v5e: 197 TFLOP/s bf16 per chip). A device that is not in the table is an
# error, not a default.
CHIP_PEAK_FLOPS = [
    ("TPU v5 lite", 197e12),  # v5e
    ("TPU v5e", 197e12),
    ("TPU v5p", 459e12),
    ("TPU v6", 918e12),
    ("TPU v4", 275e12),
    ("TPU v3", 123e12),
]


def chip_peak_flops():
    import jax

    kind = jax.devices()[0].device_kind
    for prefix, peak in CHIP_PEAK_FLOPS:
        if kind.startswith(prefix):
            return kind, peak
    raise ValueError(
        "no peak FLOP/s on record for device_kind {!r}; add it to "
        "CHIP_PEAK_FLOPS with its source".format(kind))


def _time_fn(fn, *args, iters=10, warmup=2):
    """Median wall time of ``fn(*args)`` with device sync per call."""
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def bench_llama_mfu(num_layers=None, remat=False):
    """Jitted train step of a one-chip Llama config (bf16, flash attention)
    -> step time + model FLOPs utilization. FLOPs counted as the standard
    6 * params * tokens plus the attention term 12 * L * H * D * S^2
    (fwd+bwd, causal halves the scores but the bwd recompute restores it).

    With ``remat=True`` the TRUE FLOPs are ~8*params*tokens (forward
    recomputed in the backward); MFU is still reported on the 6N
    convention and the artifact carries ``remat`` so the number reads
    honestly."""
    import jax
    import jax.numpy as jnp
    import optax

    from maggy_tpu.models import Llama, LlamaConfig
    from maggy_tpu.parallel import make_mesh
    from maggy_tpu.train import Trainer, next_token_loss

    B = int(os.environ.get("BENCH_LLAMA_BATCH", "4"))
    S = int(os.environ.get("BENCH_LLAMA_SEQ", "2048"))
    # Sized so that a cold compile fits the extra's time budget.
    cfg = LlamaConfig(
        vocab_size=32000,
        hidden_dim=int(os.environ.get("BENCH_LLAMA_HIDDEN", "2048")),
        intermediate_dim=int(os.environ.get("BENCH_LLAMA_INTER", "5632")),
        num_layers=int(num_layers if num_layers is not None
                       else os.environ.get("BENCH_LLAMA_LAYERS", "4")),
        num_heads=16, num_kv_heads=8, head_dim=128, max_seq_len=S,
        dtype=jnp.bfloat16,
        # Default no rematerialization: activations at this size fit HBM,
        # and remat recomputes the forward (real FLOPs ~8NP vs the 6NP
        # counted), understating MFU. The llama8 extra opts in to afford
        # the deeper config.
        remat=remat,
    )
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    model = Llama(cfg)
    trainer = Trainer(
        model, optax.adamw(3e-4),
        lambda logits, batch: next_token_loss(logits, batch["tokens"]),
        mesh, strategy="dp")
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(B, S)), jnp.int32)
    trainer.init(jax.random.key(0), (tokens,))
    n_params = sum(int(np.prod(x.shape)) for x in
                   jax.tree_util.tree_leaves(trainer.variables))
    batch = trainer.place_batch({"inputs": (tokens,), "tokens": tokens})

    def step(b):
        return trainer.step(b)

    sec = _time_fn(step, batch, iters=8)
    tokens_per_step = B * S
    attn_flops = 12 * cfg.num_layers * cfg.num_heads * cfg.head_dim * S * S * B
    flops = 6.0 * n_params * tokens_per_step + attn_flops
    kind, peak = chip_peak_flops()
    return {
        "model": "llama {}L/{}h (bf16, flash{})".format(
            cfg.num_layers, cfg.hidden_dim, ", remat" if remat else ""),
        "params_m": round(n_params / 1e6, 1),
        "step_time_ms": round(sec * 1e3, 2),
        "tokens_per_s": round(tokens_per_step / sec),
        "mfu": round(flops / sec / peak, 4),
        "remat": bool(remat),
        "chip": kind,
    }


def bench_bert_mfu():
    """BERT-base fwd+bwd step time (head_dim 64 + padding mask: the shapes
    that now dispatch to the Pallas kernel)."""
    import jax
    import jax.numpy as jnp
    import optax

    from maggy_tpu.models import BertConfig, BertEncoder
    from maggy_tpu.parallel import make_mesh
    from maggy_tpu.train import Trainer, cross_entropy_loss

    B = int(os.environ.get("BENCH_BERT_BATCH", "32"))
    S = int(os.environ.get("BENCH_BERT_SEQ", "128"))
    cfg = BertConfig.base(num_classes=2)
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    model = BertEncoder(cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(B, S)), jnp.int32)
    attn_mask = jnp.asarray(
        np.arange(S)[None, :] < rng.integers(S // 2, S + 1, size=(B, 1)))
    labels = jnp.asarray(rng.integers(0, 2, size=(B,)), jnp.int32)
    trainer = Trainer(
        model, optax.adamw(3e-5),
        lambda logits, batch: cross_entropy_loss(logits, batch["labels"]),
        mesh, strategy="dp")
    trainer.init(jax.random.key(0), (tokens,),
                 init_kwargs={"attention_mask": attn_mask})
    n_params = sum(int(np.prod(x.shape)) for x in
                   jax.tree_util.tree_leaves(trainer.variables))
    batch = trainer.place_batch(
        {"inputs": (tokens, attn_mask), "labels": labels})
    sec = _time_fn(lambda b: trainer.step(b), batch, iters=8)
    kind, peak = chip_peak_flops()
    flops = 6.0 * n_params * B * S
    return {
        "model": "bert-base S={} (padding-mask flash)".format(S),
        "params_m": round(n_params / 1e6, 1),
        "step_time_ms": round(sec * 1e3, 2),
        "examples_per_s": round(B / sec, 1),
        "mfu": round(flops / sec / peak, 4),
        "chip": kind,
    }


def bench_flash_vs_xla():
    """flash_attention vs attention_reference, fwd+bwd, at S = 2k/4k/8k.
    The dispatch default is Pallas on TPU; this records the measured edge."""
    import jax
    import jax.numpy as jnp

    from maggy_tpu.ops.attention import attention_reference, flash_attention

    out = {}
    for S, B in ((2048, 4), (4096, 2), (8192, 1)):
        H, D = 8, 128
        rng = np.random.default_rng(S)
        q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.bfloat16)
                   for _ in range(3))

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, None, True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

        g_flash = jax.jit(jax.grad(loss_flash, (0, 1, 2)))
        g_ref = jax.jit(jax.grad(loss_ref, (0, 1, 2)))
        t_flash = _time_fn(g_flash, q, k, v, iters=6)
        t_ref = _time_fn(g_ref, q, k, v, iters=6)
        out["S{}".format(S)] = {
            "flash_ms": round(t_flash * 1e3, 2),
            "xla_ms": round(t_ref * 1e3, 2),
            "speedup": round(t_ref / t_flash, 2),
        }
    return out


EXTRA_BENCHES = {
    "llama": bench_llama_mfu,
    # Deeper/remat variant, NOT in the default set (first compile can blow
    # the budget on a cold cache): run via BENCH_EXTRAS=llama8 once the
    # persistent compile cache is warm.
    "llama8": lambda: bench_llama_mfu(num_layers=8, remat=True),
    "bert": bench_bert_mfu,
    "flash_vs_xla": bench_flash_vs_xla,
}


HEADLINE_METRIC = "ASHA trials/hour (MNIST CNN sweep, 1 chip, 3 concurrent runners)"
HEADLINE_UNIT = "trials/hour"


def _pin_bench_env(cpu=False, fake_devices=None):
    """Shared prologue for every bench child/gate: mint the shared base
    dir once (NOT setdefault(k, mkdtemp()) — the fallback arg evaluates
    eagerly, so every child spawned by the orchestrator, which already
    exported the shared base dir, would mint and abandon an empty
    /tmp/bench_* dir), and for the CPU-pinned A/B gates set
    JAX_PLATFORMS=cpu BEFORE any jax import. ``fake_devices`` adds the
    xla_force_host_platform_device_count flag for soaks whose topology
    is N fake host devices."""
    if "MAGGY_TPU_BASE_DIR" not in os.environ:
        os.environ["MAGGY_TPU_BASE_DIR"] = tempfile.mkdtemp(prefix="bench_")
    if cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if fake_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count={}"
                .format(fake_devices)).strip()


def headline_main():
    """Child process: warm-up, framework sweep, stage-based baselines.
    Prints the headline JSON line (no extras) on stdout."""
    _pin_bench_env()
    from maggy_tpu.util import enable_compile_cache

    enable_compile_cache()
    stamp = _require_tpu()
    log("devices: {}".format(stamp))

    # Warm-up: compile every step shape (one per batch choice) so both
    # measurements see a warm cache (the persistent compilation cache does
    # this across runs).
    t0 = time.time()
    for bs in BATCH_CHOICES:
        train_mnist(1e-3, batch=bs, budget=0.2)
    log("warm-up done in {:.1f}s".format(time.time() - t0))

    result, wall = run_framework_sweep()
    n_runs = result["num_trials"]
    trials_per_hour = n_runs / wall * 3600
    log("framework sweep: {} trials in {:.1f}s ({} early-stopped, best={})".format(
        n_runs, wall, result.get("early_stopped"), result.get("best_val")))

    # Stage-based baselines over the schedule the sweep executed (same
    # trials, same budgets, same 3-way worker parallelism — only the
    # scheduling differs; see module docstring).
    import glob, json as _json

    exp_dirs = sorted(glob.glob(os.path.join(
        os.environ["MAGGY_TPU_BASE_DIR"], "*")))
    trial_dicts = []
    for td in glob.glob(os.path.join(exp_dirs[-1], "*", "trial.json")):
        with open(td) as f:
            trial_dicts.append(_json.load(f))
    schedule = [(t.get("start") or 0,
                 (t.get("info_dict") or {}).get("rung", 0),
                 t["params"]["lr"],
                 t["params"].get("batch", 256),
                 t["params"].get("budget", 1)) for t in trial_dicts]
    # Submission order (start timestamps) within each rung — the order a
    # stage scheduler would see.
    schedule.sort()
    rung_schedule = {}
    for _, rung, lr, batch, budget in schedule:
        rung_schedule.setdefault(rung, []).append((lr, batch, budget))
    sched = scheduling_telemetry(exp_dirs[-1], trial_dicts)
    handoff = sched["handoff"]
    if handoff:
        log("hand-off gap ms ({}): median {} p95 {} (n={})".format(
            sched["source"], handoff["median_ms"], handoff["p95_ms"],
            handoff["n"]))
    if sched["early_stop_reaction"]:
        log("early-stop reaction ms: median {} p95 {} (n={})".format(
            sched["early_stop_reaction"]["median_ms"],
            sched["early_stop_reaction"]["p95_ms"],
            sched["early_stop_reaction"]["n"]))
    if sched["suggest"]:
        log("hand-off pipeline: {} prefetch hits / {} misses (hit rate "
            "{}), suggest latency {}".format(
                sched["suggest"].get("prefetch_hits"),
                sched["suggest"].get("prefetch_misses"),
                sched["suggest"].get("hit_rate"),
                sched["suggest"].get("latency")))
    if sched["compile"]:
        log("compile-once: {} warm / {} cold (hit rate {}), ttfm p50 warm "
            "{} vs cold {}".format(
                sched["compile"].get("warm_hits"),
                sched["compile"].get("warm_misses"),
                sched["compile"].get("warm_hit_rate"),
                (sched["compile"].get("ttfm_warm") or {}).get("median_ms"),
                (sched["compile"].get("ttfm_cold") or {}).get("median_ms")))
    trace_path = _export_trace_artifact(exp_dirs[-1])

    # Two interleaved runs per baseline, keeping each baseline's MIN wall:
    # sustained-load drift (host thermal/noisy-neighbor — measured +12%
    # across back-to-back identical runs on the CPU proxy) would otherwise
    # penalize whichever baseline happens to run last. The min leans
    # conservative: sync-SHA (the primary comparator) gets the earliest,
    # coolest slot.
    oracle_sched = [args[2:] for args in schedule]
    sha_wall = oracle_wall = float("inf")
    for _ in range(2):
        sha_wall = min(sha_wall, run_sync_sha_baseline(rung_schedule))
        oracle_wall = min(oracle_wall, run_packed_baseline(oracle_sched))
    sha_trials_per_hour = len(schedule) / sha_wall * 3600
    log("sync-SHA baseline (rung barriers, min of 2): {} trials in {:.1f}s".format(
        len(schedule), sha_wall))
    log("oracle replay (packed, no barriers, min of 2): {} trials in {:.1f}s".format(
        len(schedule), oracle_wall))

    # Repeat-shape warm A/B: the compile-once gate (same platform and same
    # run as the headline).
    compile_ab = {}
    try:
        compile_ab = run_compile_ab()
        log("compile A/B: gate {} (warm ttfm p50 {} ms vs cold {} ms, "
            "ratio {}; wall warm {}s vs cold {}s)".format(
                compile_ab["gate"].get("gate_ok"),
                compile_ab["gate"].get("warm_ttfm_p50_ms"),
                compile_ab["gate"].get("cold_ttfm_p50_ms"),
                compile_ab["gate"].get("ratio"),
                compile_ab["warm"]["wall_s"], compile_ab["cold"]["wall_s"]))
    except Exception as e:  # noqa: BLE001 - A/B must not cost the headline
        compile_ab = {"error": repr(e)}
        log("compile A/B failed (headline unaffected): {!r}".format(e))

    print(json.dumps({
        "metric": HEADLINE_METRIC,
        "value": round(trials_per_hour, 1),
        "unit": HEADLINE_UNIT,
        "vs_baseline": round(trials_per_hour / sha_trials_per_hour, 3),
        "detail": {
            "framework_wall_s": round(wall, 1),
            "sync_sha_baseline_wall_s": round(sha_wall, 1),
            "oracle_replay_wall_s": round(oracle_wall, 1),
            "vs_oracle": round(oracle_wall / wall, 3),
            "trials": n_runs,
            "early_stopped": result.get("early_stopped", 0),
            "handoff": handoff,
            "early_stop_reaction": sched["early_stop_reaction"],
            "suggest": sched["suggest"],
            "compile": sched["compile"],
            "goodput": sched["goodput"],
            "compile_ab": compile_ab,
            "handoff_source": sched["source"],
            **stamp,
            "trace": trace_path,
            "analysis": analysis_detail(),
        },
    }), flush=True)
    return 0


def _export_trace_artifact(exp_dir):
    """Export the sweep's Perfetto timeline next to its journal and return
    its path — but ONLY after re-reading the written file and validating
    it parses as Chrome-trace JSON: a path recorded in a BENCH artifact
    must point at something a human can actually load."""
    from maggy_tpu.telemetry import JOURNAL_NAME, read_events
    from maggy_tpu.telemetry.trace import validate_trace, write_trace

    journal = os.path.join(exp_dir, JOURNAL_NAME)
    if not os.path.exists(journal):
        return None
    trace_path = os.path.join(exp_dir, "trace.json")
    try:
        n = write_trace(read_events(journal), trace_path)
        with open(trace_path) as f:
            validate_trace(json.load(f))
    except Exception as e:  # noqa: BLE001 - the artifact is best-effort
        log("trace export failed (not recorded): {!r}".format(e))
        return None
    log("trace: {} events -> {} (perfetto-loadable)".format(n, trace_path))
    return trace_path


def chaos_main():
    """``bench.py --chaos``: deterministic fault-injection soak (see
    maggy_tpu/chaos/). Runs the standard plan (runner kill mid-trial,
    false preemption, METRIC drops, severed FINAL replies) against a real
    local sweep and prints one JSON line with the invariant verdict and
    the fault->requeue recovery latencies replayed from the telemetry
    journal. Exit 1 if any recovery invariant is violated."""
    _pin_bench_env()
    from maggy_tpu.chaos.harness import run_soak

    seed = int(os.environ.get("BENCH_CHAOS_SEED", "7"))
    t0 = time.time()
    report = run_soak(seed=seed,
                      num_trials=int(os.environ.get("BENCH_CHAOS_TRIALS",
                                                    "12")),
                      lock_witness=True,
                      # Invariant 9: the obs endpoints must stay
                      # responsive while runners are killed and replies
                      # severed — the soak doubles as the kill-side obs
                      # responsiveness check (the stall side lives in
                      # the tier-1 obs soak test).
                      obs=True)
    print(json.dumps({
        "metric": "chaos soak (kill+preempt+drop+sever, journal-checked)",
        "value": 1.0 if report["ok"] else 0.0,
        "unit": "invariants_ok",
        "detail": {
            "seed": seed,
            "wall_s": round(time.time() - t0, 1),
            "violations": report["violations"],
            "faults": report["faults"],
            "recoveries": report["recoveries"],
            "trials": report["trials"],
            "health": report.get("health"),
            "obs": report.get("obs"),
            "client_retries": report["client_retries"],
            "goodput": report.get("goodput"),
            "platform": _current_platform(),
            "journal": report["journal"],
            # The soak timeline (chaos injections + health flags as
            # instant markers): validated perfetto-loadable or None.
            "trace": _export_trace_artifact(
                os.path.dirname(report["journal"])),
            # Static posture + the witness edges this soak observed: the
            # soak doubles as a dynamic race check (run_soak fails on any
            # forbidden edge, so a green soak certifies zero).
            "analysis": analysis_detail(report.get("witness")),
        },
    }), flush=True)
    return 0 if report["ok"] else 1


def _journal_goodput(journal_path):
    """Fold one journal's chip-time goodput ledger for a detail block
    (best-effort: a missing/torn journal yields {} rather than costing
    the bench)."""
    try:
        from maggy_tpu.telemetry import read_events
        from maggy_tpu.telemetry.goodput import compute_goodput

        return compute_goodput(read_events(journal_path))
    except Exception as e:  # noqa: BLE001 - accounting must not fail a gate
        return {"error": repr(e)}


def _finalized_ids(events):
    """Finalized trial ids of a journal (content-addressed over params,
    so two runs of the same seeded schedule produce identical sets)."""
    return sorted({ev["trial"] for ev in events
                   if ev.get("ev") == "trial"
                   and ev.get("phase") == "finalized"})


def journal_schedule_parity(events_a, events_b,
                            label_a="a", label_b="b",
                            platform_a=None, platform_b=None):
    """Journal-replayed A/B schedule comparator — the ONE home of the
    same-platform-baseline parity rule: two
    arms of an A/B (``--fork`` forking-on vs forking-off), or a
    recovered run vs an uninterrupted reference (``--failover``),
    executed the SAME schedule exactly when their finalized trial-id
    sets match. Returns {match, <label_a>, <label_b>,
    symmetric_difference, platform?}.

    When both arms carry a platform stamp the comparator REFUSES a
    mixed-platform comparison outright (ValueError naming both sides):
    a cross-substrate A/B is not a measurement, and silently returning
    numbers would let one into a BENCH artifact."""
    if platform_a is not None and platform_b is not None \
            and platform_a != platform_b:
        raise ValueError(
            "refusing cross-platform A/B: arm {!r} ran on {!r} but arm "
            "{!r} ran on {!r} — re-run both arms on one platform".format(
                label_a, platform_a, label_b, platform_b))
    ids_a, ids_b = _finalized_ids(events_a), _finalized_ids(events_b)
    out = {"match": ids_a == ids_b,
           label_a: len(ids_a), label_b: len(ids_b),
           "symmetric_difference": sorted(set(ids_a) ^ set(ids_b))}
    if platform_a is not None:
        out["platform"] = platform_a
    return out


def rung0_events(events):
    """Restrict a journal to its RUNG-0 trials' events — the seeded base
    schedule. An ASHA A/B whose arms differ in trial DURATION (forking
    on vs off) can legitimately top the ladder at different wall times,
    so the promotion TAIL is timing-dependent; the rung-0 sample set is
    the seed-deterministic half schedule parity is well-defined over."""
    rung0 = {ev["trial"] for ev in events
             if ev.get("ev") == "trial" and ev.get("phase") == "queued"
             and (ev.get("info") or {}).get("rung", 0) == 0}
    return [ev for ev in events if ev.get("trial") in rung0]


def failover_main():
    """``bench.py --failover``: crash-only driver failover gate (see
    maggy_tpu/chaos/driver_soak.py). Runs the kill_driver soak — a real
    driver process SIGKILLed mid-sweep (twice by default) over surviving
    runner-agent processes, restarted with resume=True each time — and
    gates (a) invariant 13 over the multi-incarnation journal, (b)
    journal-replay recovery MTTR (kill -> ``recovered`` marker) p50
    under the bound, and (c) replayed-vs-live parity: the recovered
    sweep's final trial-id set must be IDENTICAL to an uninterrupted run
    of the same seeded schedule. Exit 1 on any violation."""
    _pin_bench_env()
    from maggy_tpu.chaos.driver_soak import run_driver_soak

    seed = int(os.environ.get("BENCH_FAILOVER_SEED", "7"))
    kills = int(os.environ.get("BENCH_FAILOVER_KILLS", "2"))
    trials = int(os.environ.get("BENCH_FAILOVER_TRIALS", "8"))
    mttr_bound_s = float(os.environ.get("BENCH_FAILOVER_MTTR_S", "60"))
    t0 = time.time()
    report = run_driver_soak(trials=trials, workers=3, seed=seed,
                             kills=kills, lock_witness=True)
    mttr_s = sorted(r["mttr_s"] for r in report["failover"]["recoveries"]
                    if r.get("mttr_s") is not None)
    mttr_p50 = mttr_s[len(mttr_s) // 2] if mttr_s else None
    mttr_p95 = mttr_s[int(len(mttr_s) * 0.95)] if mttr_s else None
    violations = list(report["violations"])
    if mttr_p50 is None:
        violations.append("no recovery MTTR measured: no kill produced a "
                          "recovered marker")
    elif mttr_p50 > mttr_bound_s:
        violations.append(
            "recovery too slow: journal-replay MTTR p50 {:.1f}s exceeds "
            "the {:.0f}s bound".format(mttr_p50, mttr_bound_s))

    # Parity: an UNINTERRUPTED run of the same seeded schedule must
    # produce the identical final trial-id set (trial ids are
    # content-addressed over the params, so this compares the executed
    # schedules exactly; the quick closed-form trial body is fine — ids
    # do not depend on trial duration).
    from maggy_tpu import OptimizationConfig, Searchspace, experiment
    from maggy_tpu.chaos.harness import _soak_train_fn
    from maggy_tpu.telemetry import JOURNAL_NAME, read_events

    ref_base = tempfile.mkdtemp(prefix="maggy_failover_ref_")
    ref_cfg = OptimizationConfig(
        name="failover_ref", num_trials=trials, optimizer="randomsearch",
        searchspace=Searchspace(lr=("DOUBLE", [0.0, 0.2]),
                                units=("INTEGER", [8, 64])),
        direction="max", num_workers=3, seed=seed, es_policy="none",
        hb_interval=0.05, experiment_dir=ref_base)
    experiment.lagom(_soak_train_fn, ref_cfg)
    ref_dirs = sorted(d for d in os.listdir(ref_base)
                      if os.path.isdir(os.path.join(ref_base, d)))
    ref_events = read_events(os.path.join(ref_base, ref_dirs[-1],
                                          JOURNAL_NAME))
    soak_events = read_events(report["journal"])

    platform = _current_platform()
    parity_rec = journal_schedule_parity(soak_events, ref_events,
                                         label_a="soak_trials",
                                         label_b="reference_trials",
                                         platform_a=platform,
                                         platform_b=platform)
    parity = parity_rec["match"]
    if not parity:
        violations.append(
            "replayed-vs-live parity broken: recovered sweep finalized {} "
            "trial(s), uninterrupted run {} — symmetric difference "
            "{}".format(parity_rec["soak_trials"],
                        parity_rec["reference_trials"],
                        parity_rec["symmetric_difference"]))
    ok = not violations
    print(json.dumps({
        "metric": "driver failover (SIGKILL x{} + journal-replay "
                  "recovery)".format(kills),
        "value": 1.0 if ok else 0.0,
        "unit": "invariants_ok",
        "detail": {"failover": {
            "seed": seed, "kills": kills, "trials": trials,
            "wall_s": round(time.time() - t0, 1),
            "violations": violations,
            "mttr_p50_ms": round(mttr_p50 * 1e3, 1)
            if mttr_p50 is not None else None,
            "mttr_p95_ms": round(mttr_p95 * 1e3, 1)
            if mttr_p95 is not None else None,
            "mttr_bound_s": mttr_bound_s,
            "driver_epochs": report["failover"]["driver_epochs"],
            "adopted": report["failover"]["adopted"],
            "requeued": report["trials"]["requeued"],
            "recoveries": report["failover"]["recoveries"],
            "parity": parity_rec,
            # The multi-incarnation ledger: killed attempts surface as
            # rework badput, the restart seam as handoff/queue_wait.
            "goodput": _journal_goodput(report["journal"]),
            "platform": platform,
            "witness": report.get("witness"),
            "journal": report["journal"],
        }},
    }), flush=True)
    return 0 if ok else 1


def fork_main():
    """``bench.py --fork``: the checkpoint-forking A/B gate (ROADMAP
    item 3). The SAME fixed ASHA sweep runs twice on the SAME platform —
    forking ON (config.fork, the default) vs OFF (from-scratch
    promotions) — and the gate asserts:

    (a) top-rung re-trained steps drop by >= the rung ratio: with
        forking OFF every top-rung trial re-trains its parent's whole
        prefix; with forking ON it resumes past it (re-trained ~0);
    (b) exact step-for-step loss parity: every forked trial's recorded
        trajectory equals a from-checkpoint continuation of its parent
        (the trial body is a closed form of (lr, step), so equality is
        bitwise — a fork that silently restarted or loaded the wrong
        step cannot pass);
    (c) trials/hour improves (wall_off / wall_on > 1), and both arms
        executed the IDENTICAL schedule (journal_schedule_parity — the
        same-platform-baseline rule shared with --failover).

    Always CPU-pinned (closed-form trial body; the fake accelerator adds
    nothing) with detail.platform recorded. Exit 1 on any gate failure."""
    _pin_bench_env(cpu=True)
    import glob as _glob

    from maggy_tpu import OptimizationConfig, Searchspace, experiment
    from maggy_tpu.chaos.harness import (fork_ckpt_train_fn,
                                         fork_step_metric)
    from maggy_tpu.optimizers import Asha
    from maggy_tpu.telemetry import (JOURNAL_NAME, read_events,
                                     replay_journal)

    seed = int(os.environ.get("BENCH_FORK_SEED", "7"))
    trials = int(os.environ.get("BENCH_FORK_TRIALS", "9"))
    rf = int(os.environ.get("BENCH_FORK_RF", "3"))
    workers = int(os.environ.get("BENCH_FORK_WORKERS", "3"))
    steps_per_budget = 4  # fork_ckpt_train_fn's contract
    t_start = time.time()
    arms = {}
    for arm, fork_on in (("fork", True), ("scratch", False)):
        arm_dir = os.path.join(os.environ["MAGGY_TPU_BASE_DIR"],
                               "fork_ab_{}".format(arm))
        config = OptimizationConfig(
            name="bench_fork_{}".format(arm), num_trials=trials,
            optimizer=Asha(reduction_factor=rf, resource_min=1,
                           resource_max=rf * rf, seed=seed),
            searchspace=Searchspace(lr=("DOUBLE", [0.05, 0.2])),
            direction="max", num_workers=workers, hb_interval=0.02,
            es_policy="none", seed=seed, fork=fork_on,
            # prefetch invalidation re-draws dropped rung-0 samples with
            # fresh RNG state, making the rung-0 id set timing-dependent;
            # the schedule-parity gate needs strictly sequential draws.
            prefetch=False,
            experiment_dir=arm_dir)
        t0 = time.time()
        experiment.lagom(fork_ckpt_train_fn, config)
        wall = time.time() - t0
        exp_dir = sorted(d for d in _glob.glob(os.path.join(arm_dir, "*"))
                         if os.path.isdir(d))[-1]
        events = read_events(os.path.join(exp_dir, JOURNAL_NAME))
        trial_dicts = []
        for td in _glob.glob(os.path.join(exp_dir, "*", "trial.json")):
            with open(td) as f:
                trial_dicts.append(json.load(f))
        arms[arm] = {
            "wall_s": round(wall, 2), "events": events,
            "trials": trial_dicts,
            "derived": replay_journal(os.path.join(exp_dir, JOURNAL_NAME)),
            "platform": _current_platform(),
        }
        log("{} arm: {} trials in {:.1f}s (fork block: {})".format(
            arm, len(trial_dicts), wall,
            arms[arm]["derived"].get("fork")))

    violations = []

    def _fork_steps(events):
        """trial -> forked step from the journal's genealogy edges."""
        return {ev["trial"]: ev.get("step") for ev in events
                if ev.get("ev") == "trial"
                and ev.get("phase") == "forked_from"}

    def _retrained_top_rung(arm):
        """Sum over top-rung trials of the parent-prefix steps the trial
        RE-TRAINED: the whole prefix when dispatched from scratch, the
        part below its fork point when forked (0 at the fork default —
        the fork point is the parent's last step)."""
        info_of = {t["id"]: t.get("info_dict") or {}
                   for t in arms[arm]["trials"]}
        top = max((i.get("rung", 0) for i in info_of.values()), default=0)
        forked_at = _fork_steps(arms[arm]["events"])
        total = 0
        n = 0
        for tid, info in info_of.items():
            if info.get("rung", 0) != top or info.get("parent") is None:
                continue
            n += 1
            parent_budget = (rf ** (top - 1)) * 1
            parent_steps = steps_per_budget * parent_budget
            resume_offset = forked_at.get(tid)
            executed_from = 0 if resume_offset is None else resume_offset + 1
            total += max(0, parent_steps - executed_from)
        return total, n, top

    retrained_fork, n_top_fork, top_rung = _retrained_top_rung("fork")
    retrained_scratch, n_top_scratch, _ = _retrained_top_rung("scratch")
    if n_top_fork == 0 or n_top_scratch == 0:
        violations.append("no top-rung promotions ran: the sweep never "
                          "climbed the ladder (nothing gated)")
    elif retrained_fork * rf > retrained_scratch:
        violations.append(
            "top-rung re-trained steps did not drop by the rung ratio: "
            "forking-on re-trained {} steps vs {} forking-off "
            "(needed <= {}/{} = {})".format(
                retrained_fork, retrained_scratch, retrained_scratch,
                rf, retrained_scratch / rf))

    # (b) exact fork parity: each forked trial's recorded trajectory ==
    # the from-checkpoint continuation of its parent (closed form).
    forked_at = _fork_steps(arms["fork"]["events"])
    parity_checked = 0
    for t in arms["fork"]["trials"]:
        tid = t["id"]
        if tid not in forked_at or forked_at[tid] is None:
            continue
        s_fork = int(forked_at[tid])
        lr = t["params"]["lr"]
        budget = t["params"].get("budget", 1)
        total_steps = max(1, int(round(steps_per_budget * budget)))
        recorded = dict(zip(t.get("step_history") or [],
                            t.get("metric_history") or []))
        if [s for s in recorded if s <= s_fork]:
            violations.append(
                "forked trial {} re-trained its parent's prefix: "
                "recorded steps {} at or below fork point {}".format(
                    tid, sorted(s for s in recorded if s <= s_fork),
                    s_fork))
            continue
        if not recorded:
            continue  # all broadcasts raced the FINAL; nothing to check
        bad = [s for s, v in recorded.items()
               if v != fork_step_metric(lr, int(s))]
        if bad:
            violations.append(
                "fork parity broken: trial {} steps {} diverge from the "
                "parent's from-checkpoint continuation".format(
                    tid, sorted(bad)))
        else:
            parity_checked += 1
        want_final = fork_step_metric(lr, total_steps - 1)
        if t.get("final_metric") is not None \
                and t["final_metric"] != want_final:
            violations.append(
                "fork final-metric parity broken: trial {} finalized {} "
                "vs continuation {}".format(tid, t["final_metric"],
                                            want_final))
    if not forked_at:
        violations.append("forking-on arm journaled zero forked_from "
                          "edges: the hot path never engaged")

    # (c) throughput + identical seeded base schedule across arms (the
    # promotion TAIL is timing-dependent by design: forking tops the
    # ladder sooner — rung0_events scopes parity to what must match).
    schedule_parity = journal_schedule_parity(
        rung0_events(arms["fork"]["events"]),
        rung0_events(arms["scratch"]["events"]),
        label_a="fork_trials", label_b="scratch_trials",
        platform_a=arms["fork"]["platform"],
        platform_b=arms["scratch"]["platform"])
    if not schedule_parity["match"]:
        violations.append(
            "arms executed different rung-0 schedules: symmetric "
            "difference {}".format(
                schedule_parity["symmetric_difference"]))
    wall_ratio = round(arms["scratch"]["wall_s"]
                       / max(arms["fork"]["wall_s"], 1e-9), 3)
    if wall_ratio <= 1.0:
        violations.append(
            "trials/hour did not improve: forking-on wall {}s vs "
            "forking-off {}s (ratio {})".format(
                arms["fork"]["wall_s"], arms["scratch"]["wall_s"],
                wall_ratio))

    ok = not violations
    print(json.dumps({
        "metric": "checkpoint-forking A/B (same ASHA sweep, forking on "
                  "vs off, journal-replayed)",
        "value": 1.0 if ok else 0.0,
        "unit": "fork_gate_ok",
        "detail": {"fork_ab": {
            "seed": seed, "trials": trials, "rung_ratio": rf,
            "wall_s": round(time.time() - t_start, 1),
            "platform": "cpu (pinned; closed-form trial body — "
                        "comparable across hosts per the ROADMAP note)",
            "violations": violations,
            "top_rung": top_rung,
            "retrained_steps_fork_on": retrained_fork,
            "retrained_steps_fork_off": retrained_scratch,
            "top_rung_trials": n_top_fork,
            "parity_trials_checked": parity_checked,
            "schedule_parity": schedule_parity,
            "trials_per_hour_ratio": wall_ratio,
            "wall_fork_on_s": arms["fork"]["wall_s"],
            "wall_fork_off_s": arms["scratch"]["wall_s"],
            "fork": arms["fork"]["derived"].get("fork"),
            "fork_off": arms["scratch"]["derived"].get("fork"),
            # Per-arm chip-time ledgers: forking-on must show as LESS
            # rework badput than from-scratch (--goodput gates this on
            # its own smaller A/B; recorded here for the trajectory).
            "goodput": arms["fork"]["derived"].get("goodput"),
            "goodput_off": arms["scratch"]["derived"].get("goodput"),
        }},
    }), flush=True)
    return 0 if ok else 1


def _vmap_lane_parity():
    """Engine-level parity sub-gate for --vmap (idiom shared with
    tests/test_vmap.py): K scalar Trainer runs vs one VmapTrainer block
    over the SAME configs must agree per lane, per step, to the parity
    the platform gives (`train/vmap.py`, module docstring: the K-lane
    program batches its matmuls, so `LANE_VS_SCALAR_ULP` float32 ulp over
    the first `LANE_VS_SCALAR_STEPS` steps, not bitwise). Returns a
    violations list (empty = parity holds)."""
    import jax
    import jax.numpy as jnp
    import optax

    from maggy_tpu.models import MnistMLP
    from maggy_tpu.parallel import make_mesh
    from maggy_tpu.train import (Trainer, VmapTrainer, clear_warm,
                                 swept_transform)
    from maggy_tpu.train.vmap import (LANE_VS_SCALAR_STEPS as steps,
                                      LANE_VS_SCALAR_ULP, ulp_distance)

    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    model = MnistMLP(features=8, num_classes=2)
    X = DATA_X[:128]
    batch = {"inputs": (jnp.asarray(X),),
             "labels": jnp.asarray(DATA_Y[:128])}
    rng = jax.random.key(0)
    lrs = [1e-3, 3e-3, 1e-2, 3e-2]

    def scalar_run(lr):
        tr = Trainer(model, swept_transform(optax.adam, learning_rate=lr),
                     _bench_loss, mesh, strategy="dp")
        tr.init(rng, (batch["inputs"][0][:1],))
        return np.asarray([float(tr.step(tr.place_batch(batch)))
                           for _ in range(steps)])

    clear_warm()
    scalar = {lr: scalar_run(lr) for lr in lrs}
    clear_warm()
    vt = VmapTrainer(model, optax.adam,
                     [{"learning_rate": lr} for lr in lrs],
                     _bench_loss, mesh, strategy="dp")
    vt.init(rng, (batch["inputs"][0][:1],))
    vlosses = np.stack([np.asarray(vt.step(batch)) for _ in range(steps)])
    clear_warm()
    violations = []
    for i, lr in enumerate(lrs):
        ulp = ulp_distance(scalar[lr], vlosses[:, i])
        if ulp.max() > LANE_VS_SCALAR_ULP:
            d = int(np.argmax(ulp))
            violations.append(
                "lane {} (lr={}) is {} ulp from its scalar run at step {}: "
                "{!r} vs {!r}".format(i, lr, int(ulp[d]), d, scalar[lr][d],
                                      vlosses[d, i]))
    return violations


def vmap_main():
    """``bench.py --vmap``: the vectorized micro-trials gate (ROADMAP
    item 4). THREE arms of the SAME seeded random-search micro-sweep on
    ONE pinned platform:

      scalar — vmap_lanes unset (the default 1): one trial per dispatch;
      lanes1 — vmap_lanes=1 explicitly: must journal-replay to the
               IDENTICAL schedule as scalar (the bit-for-bit
               compatibility contract of the default);
      vmap   — vmap_lanes=K: the driver assembles K program-compatible
               suggestions into blocks, each block one vmapped program.

    Gates: (a) trials/hour ratio wall_scalar / wall_vmap >= 5 (the
    micro-trial regime is dispatch-overhead-dominated, so K lanes per
    program approaches Kx even on CPU); (b) engine-level
    per-lane parity vs scalar runs (`_vmap_lane_parity`); (c) scalar vs
    lanes1 finalized-schedule parity via `journal_schedule_parity` with
    per-arm platform stamps; (d) the vmap arm actually assembled blocks
    (lane-tagged journal edges — a silently-scalar run must not pass).

    Always CPU-pinned, with detail.platform stamped. Exit 1 on any gate
    failure."""
    _pin_bench_env(cpu=True)
    import glob as _glob

    from maggy_tpu import OptimizationConfig, Searchspace, experiment
    from maggy_tpu.telemetry import JOURNAL_NAME, read_events, replay_journal

    seed = int(os.environ.get("BENCH_VMAP_SEED", "7"))
    trials = int(os.environ.get("BENCH_VMAP_TRIALS", "25"))
    lanes_k = int(os.environ.get("BENCH_VMAP_LANES", "8"))
    need = float(os.environ.get("BENCH_VMAP_SPEEDUP", "5"))
    t_start = time.time()
    arms = {}
    for arm, k in (("scalar", None), ("lanes1", 1), ("vmap", lanes_k)):
        arm_dir = os.path.join(os.environ["MAGGY_TPU_BASE_DIR"],
                               "vmap_ab_{}".format(arm))
        config = OptimizationConfig(
            name="bench_vmap_{}".format(arm), num_trials=trials,
            optimizer="randomsearch",
            searchspace=Searchspace(lr=("DOUBLE_LOG", [1e-3, 3e-2])),
            direction="max", num_workers=1, hb_interval=0.05,
            es_policy="none", seed=seed, experiment_dir=arm_dir,
            **({"vmap_lanes": k} if k is not None else {}))
        t0 = time.time()
        experiment.lagom(train_mnist_vmap, config)
        wall = time.time() - t0
        exp_dir = sorted(d for d in _glob.glob(os.path.join(arm_dir, "*"))
                         if os.path.isdir(d))[-1]
        events = read_events(os.path.join(exp_dir, JOURNAL_NAME))
        arms[arm] = {
            "wall_s": round(wall, 2), "events": events,
            "derived": replay_journal(os.path.join(exp_dir, JOURNAL_NAME)),
            "platform": _current_platform(),
        }
        n_lane = len([e for e in events if e.get("phase") == "assigned"
                      and e.get("lane") is not None])
        log("{} arm: {} trials in {:.1f}s ({} lane-tagged assignments)"
            .format(arm, trials, wall, n_lane))

    violations = []

    # (a) throughput: K lanes per program must beat scalar dispatch by
    # the gate factor in the dispatch-bound micro-trial regime.
    speedup = round(arms["scalar"]["wall_s"]
                    / max(arms["vmap"]["wall_s"], 1e-9), 2)
    if speedup < need:
        violations.append(
            "vectorized trials/hour gate missed: scalar {}s / vmap {}s "
            "= {}x (need >= {}x)".format(
                arms["scalar"]["wall_s"], arms["vmap"]["wall_s"],
                speedup, need))

    # (b) per-lane loss parity at the engine level.
    parity_violations = _vmap_lane_parity()
    violations.extend(parity_violations)

    # (c) vmap_lanes=1 is the scalar path bit-for-bit: identical
    # journal-replayed schedule (same seed => same content-addressed ids).
    schedule_parity = journal_schedule_parity(
        arms["scalar"]["events"], arms["lanes1"]["events"],
        label_a="scalar_trials", label_b="lanes1_trials",
        platform_a=arms["scalar"]["platform"],
        platform_b=arms["lanes1"]["platform"])
    if not schedule_parity["match"]:
        violations.append(
            "vmap_lanes=1 executed a different schedule than the scalar "
            "default: symmetric difference {}".format(
                schedule_parity["symmetric_difference"]))
    lanes1_tagged = [e for e in arms["lanes1"]["events"]
                     if e.get("lane") is not None]
    if lanes1_tagged:
        violations.append(
            "vmap_lanes=1 journaled {} lane-tagged edges; the scalar "
            "path must be bit-for-bit untouched".format(len(lanes1_tagged)))

    # (d) the vmap arm really rode blocks: all but the warm-up scalar
    # dispatches should carry lane-tagged assignment edges.
    lane_assigned = [e for e in arms["vmap"]["events"]
                     if e.get("phase") == "assigned"
                     and e.get("lane") is not None]
    blocks = sorted({e.get("block") for e in lane_assigned})
    if len(lane_assigned) < trials - lanes_k:
        violations.append(
            "vmap arm barely vectorized: only {}/{} trials rode blocks "
            "(need >= {}) — block assembly is not engaging".format(
                len(lane_assigned), trials, trials - lanes_k))

    ok = not violations
    print(json.dumps({
        "metric": "vectorized micro-trials A/B (K configs per chip as one "
                  "vmapped program, journal-replayed)",
        "value": speedup if ok else 0.0,
        "unit": "x_trials_per_hour_vs_scalar",
        "detail": {"vmap_ab": {
            "seed": seed, "trials": trials, "vmap_lanes": lanes_k,
            "steps": VMAP_STEPS,
            "wall_s": round(time.time() - t_start, 1),
            "platform": "cpu (pinned; CPU-proxy micro-trials — "
                        "comparable across hosts per the ROADMAP note)",
            "violations": violations,
            "speedup": speedup, "speedup_needed": need,
            "wall_scalar_s": arms["scalar"]["wall_s"],
            "wall_lanes1_s": arms["lanes1"]["wall_s"],
            "wall_vmap_s": arms["vmap"]["wall_s"],
            "lane_parity_lanes_checked": 4 - len(parity_violations),
            "schedule_parity": schedule_parity,
            "blocks": blocks,
            "lane_assignments": len(lane_assigned),
            # Chip-time ledger of the vectorized arm: block chip-seconds
            # split across lanes, masked tails billed to lane_idle.
            "goodput": arms["vmap"]["derived"].get("goodput"),
            "goodput_scalar": arms["scalar"]["derived"].get("goodput"),
        }},
    }), flush=True)
    return 0 if ok else 1


def goodput_main():
    """``bench.py --goodput``: the chip-time ledger gate. Two
    journal-replayed A/Bs on ONE pinned platform prove the ledger
    measures what it claims:

    (a) warm-start A/B (run_compile_ab): the warm arm's COMPILE badput
        chip-seconds must land strictly below the cold arm's — the
        compile-once win shows up as measured badput reduction, not
        just a ttfm distribution;
    (b) fork A/B (small ASHA sweep, forking on vs off): the forking
        arm's REWORK badput must land strictly below from-scratch — a
        from-scratch promotion re-trains its parent's prefix and the
        accountant books exactly that time as rework;
    (c) every arm's ``unaccounted`` residual stays <= 5% of held
        chip-time — the taxonomy is closed, a leak fails the gate;
    (d) both fork arms carry the SAME platform stamp
        (journal_schedule_parity raises on a mixed-platform A/B).

    CPU-pinned like --fork (closed-form/tiny trial bodies; the ledger
    under test is platform-independent journal arithmetic). Exit 1 on
    any gate failure."""
    _pin_bench_env(cpu=True)
    import glob as _glob

    from maggy_tpu import OptimizationConfig, Searchspace, experiment
    from maggy_tpu.chaos.harness import fork_ckpt_train_fn
    from maggy_tpu.optimizers import Asha
    from maggy_tpu.telemetry import (JOURNAL_NAME, read_events,
                                     replay_journal)

    seed = int(os.environ.get("BENCH_GOODPUT_SEED", "7"))
    rf = 3
    # ASHA's rung ladder needs rf**2 trials to build all three rungs.
    trials = max(int(os.environ.get("BENCH_GOODPUT_TRIALS", "9")), rf * rf)
    bound = float(os.environ.get("BENCH_GOODPUT_UNACCOUNTED", "0.05"))
    t_start = time.time()
    violations = []

    def _bucket(gp, name):
        return ((gp or {}).get("buckets") or {}).get(name) or 0.0

    # (a) warm-start A/B — run_compile_ab already replays each arm's
    # journal; its per-arm blocks now carry the goodput ledger.
    compile_ab = run_compile_ab()
    ledgers = {"warm": compile_ab["warm"]["goodput"],
               "cold": compile_ab["cold"]["goodput"]}
    warm_compile = sum(_bucket(ledgers["warm"], b)
                       for b in ("init", "trace", "compile"))
    cold_compile = sum(_bucket(ledgers["cold"], b)
                       for b in ("init", "trace", "compile"))
    if not warm_compile < cold_compile:
        violations.append(
            "warm-start did not show as measured compile badput "
            "reduction: warm arm {:.2f}s (init+trace+compile) vs cold "
            "arm {:.2f}s".format(warm_compile, cold_compile))
    log("warm A/B compile badput: warm {:.2f}s vs cold {:.2f}s".format(
        warm_compile, cold_compile))

    # (b) fork A/B — the --fork sweep at reduced size, gated on the
    # ledger's REWORK bucket instead of re-trained step counts.
    events_by_arm = {}
    for arm, fork_on in (("fork", True), ("scratch", False)):
        arm_dir = os.path.join(os.environ["MAGGY_TPU_BASE_DIR"],
                               "goodput_ab_{}".format(arm))
        config = OptimizationConfig(
            name="bench_goodput_{}".format(arm), num_trials=trials,
            optimizer=Asha(reduction_factor=rf, resource_min=1,
                           resource_max=rf * rf, seed=seed),
            searchspace=Searchspace(lr=("DOUBLE", [0.05, 0.2])),
            direction="max", num_workers=3, hb_interval=0.02,
            es_policy="none", seed=seed, fork=fork_on,
            # prefetch invalidation re-draws dropped rung-0 samples with
            # fresh RNG state, making the rung-0 id set timing-dependent;
            # the schedule-parity gate needs strictly sequential draws.
            prefetch=False,
            experiment_dir=arm_dir)
        experiment.lagom(fork_ckpt_train_fn, config)
        exp_dir = sorted(d for d in _glob.glob(os.path.join(arm_dir, "*"))
                         if os.path.isdir(d))[-1]
        events_by_arm[arm] = read_events(
            os.path.join(exp_dir, JOURNAL_NAME))
        ledgers[arm] = replay_journal(
            os.path.join(exp_dir, JOURNAL_NAME)).get("goodput") or {}
    fork_rework = _bucket(ledgers["fork"], "rework")
    scratch_rework = _bucket(ledgers["scratch"], "rework")
    if not fork_rework < scratch_rework:
        violations.append(
            "forking did not show as measured rework badput reduction: "
            "forking-on {:.2f}s rework vs from-scratch {:.2f}s".format(
                fork_rework, scratch_rework))
    log("fork A/B rework badput: fork {:.2f}s vs scratch {:.2f}s".format(
        fork_rework, scratch_rework))

    # (c) closed taxonomy: no arm may leak more than the bound.
    for arm, gp in sorted(ledgers.items()):
        if not gp:
            violations.append(
                "arm {} produced no goodput ledger (empty journal "
                "fold)".format(arm))
            continue
        frac = gp.get("unaccounted_fraction")
        if frac is None or frac > bound:
            violations.append(
                "arm {} unaccounted chip-time {} exceeds the {:.0%} "
                "bound".format(arm, frac, bound))

    # (d) same-platform rule: the comparator itself raises on a
    # mixed-platform A/B, so a green parity record certifies the stamp.
    platform = _current_platform()
    try:
        parity = journal_schedule_parity(
            rung0_events(events_by_arm["fork"]),
            rung0_events(events_by_arm["scratch"]),
            label_a="fork_trials", label_b="scratch_trials",
            platform_a=platform, platform_b=platform)
        if not parity["match"]:
            violations.append(
                "fork A/B arms executed different rung-0 schedules: "
                "symmetric difference {}".format(
                    parity["symmetric_difference"]))
    except ValueError as e:
        parity = {"match": False, "error": str(e)}
        violations.append(str(e))

    ok = not violations
    print(json.dumps({
        "metric": "chip-time goodput ledger (warm + fork A/B, "
                  "journal-replayed)",
        "value": 1.0 if ok else 0.0,
        "unit": "goodput_gate_ok",
        "detail": {"goodput_gate": {
            "seed": seed, "trials": trials,
            "wall_s": round(time.time() - t_start, 1),
            "platform": platform,
            "violations": violations,
            "unaccounted_bound": bound,
            "compile_badput_s": {"warm": round(warm_compile, 3),
                                 "cold": round(cold_compile, 3)},
            "rework_s": {"fork": round(fork_rework, 3),
                         "scratch": round(scratch_rework, 3)},
            "schedule_parity": parity,
            "arms": {arm: {
                "goodput_fraction": gp.get("goodput_fraction"),
                "unaccounted_fraction": gp.get("unaccounted_fraction"),
                "held_chip_s": gp.get("held_chip_s"),
                "badput_top": gp.get("badput_top"),
            } for arm, gp in sorted(ledgers.items()) if gp},
        }},
    }), flush=True)
    return 0 if ok else 1


def fleet_main():
    """``bench.py --fleet``: shared-fleet scheduling soak (see
    maggy_tpu/fleet/). Runs two concurrent experiments over one 2-runner
    fleet — a low-priority bulk sweep preempted mid-flight by a
    high-priority arrival — and prints one JSON line whose detail.fleet
    block carries the journal-replayed scheduling numbers (queue wait
    p50/p95, preemption count, share error vs the configured weights).
    Exit 1 if any fleet invariant is violated."""
    _pin_bench_env()
    from maggy_tpu.fleet.soak import run_fleet_soak

    seed = int(os.environ.get("BENCH_FLEET_SEED", "7"))
    t0 = time.time()
    report = run_fleet_soak(seed=seed)
    print(json.dumps({
        "metric": "fleet soak (2 experiments / 2 runners, preempt+resume, "
                  "journal-checked)",
        "value": 1.0 if report["ok"] else 0.0,
        "unit": "invariants_ok",
        "detail": {
            "seed": seed,
            "wall_s": round(time.time() - t0, 1),
            "violations": report["violations"],
            "results": report["results"],
            "fleet": report["detail"],
            # The fleet replay's per-tenant ledger roll-up (also inside
            # detail.fleet.goodput; hoisted for the trajectory reader).
            "goodput": (report["detail"] or {}).get("goodput"),
            "platform": _current_platform(),
            "journal": report["journal"],
        },
    }), flush=True)
    return 0 if report["ok"] else 1


def pack_main():
    """``bench.py --pack``: gang-scheduling pack soak (see
    maggy_tpu/gang.py). Runs the mixed sweep — 1-chip ASHA rung-0 trials
    + 4-chip fsdp gang promotions — on an 8-fake-device CPU proxy fleet
    and prints one JSON line whose detail.pack block carries the
    journal-replayed packing numbers (chip-seconds utilization,
    fragmentation stalls, gang assembly latency p50/p95). Always a CPU
    proxy (the fake-device count IS the topology under test), so runs
    are comparable across hosts per the ROADMAP platform-gating note.
    Exit 1 if the sweep deadlocks, utilization misses the 0.7 gate, or a
    gang trial diverges from the single-process sharded reference."""
    # Before any jax import: the pack soak's topology is 8 fake host
    # devices, regardless of what accelerator the host has.
    _pin_bench_env(cpu=True, fake_devices=8)
    from maggy_tpu.gang import run_pack_soak

    seed = int(os.environ.get("BENCH_PACK_SEED", "7"))
    t0 = time.time()
    report = run_pack_soak(seed=seed)
    pack = report["pack"]
    print(json.dumps({
        "metric": "gang pack soak (mixed 1-chip ASHA + 4-chip fsdp gangs "
                  "on 8 fake devices, journal-replayed)",
        "value": pack.get("chip_seconds_utilization") or 0.0,
        "unit": "chip_seconds_utilization",
        "detail": {
            "seed": seed,
            "wall_s": round(time.time() - t0, 1),
            "violations": report["violations"],
            "pack": pack,
            # Gang-vs-reference parity (MULTICHIP dryrun parity): each
            # gang trial's final loss against the single-process sharded
            # reference for its declared shape.
            "parity": report["parity"],
            "platform": "cpu proxy (8 fake devices via "
                        "--xla_force_host_platform_device_count)",
            "journal": report["journal"],
            "result": report["result"],
            # Gang assembly as grouped lanes + pack instants: validated
            # perfetto-loadable or None.
            "trace": _export_trace_artifact(
                os.path.dirname(report["journal"])),
        },
    }), flush=True)
    return 0 if report["ok"] else 1


def _obs_train_fn(lr, units, reporter=None):
    """Obs-bench trial: pure-python, deterministic, a few broadcast
    steps — the sweep exists to put live load on the scrape path, not to
    measure training."""
    import time as _time

    acc = 1.0 / (1.0 + abs(lr - 0.1) + units / 1e4)
    for step in range(4):
        reporter.broadcast(acc * (step + 1) / 4.0, step=step)
        _time.sleep(0.02)
    return {"metric": acc}


def obs_main():
    """``bench.py --obs``: observability-plane scrape bench (see
    maggy_tpu/telemetry/obs.py). Runs a small sweep with the obs server
    on (ephemeral port) while a scraper polls /metrics + /status +
    /healthz at ~30 Hz, and prints one JSON line whose detail.obs block
    carries per-route scrape latency p50/p95 under live load plus a
    scrape-vs-journal consistency verdict: every scraped finalized-count
    sample must sit between the journal-replayed counts bracketing the
    scrape's wall time. Always a CPU proxy (the plane under test is
    platform-independent Python; pinning the platform keeps rounds
    comparable — detail.platform records it). Exit 1 if the endpoints fail, stall, or disagree with the
    journal."""
    _pin_bench_env(cpu=True)
    import glob
    import threading
    import urllib.error
    import urllib.request

    from maggy_tpu import OptimizationConfig, Searchspace, experiment
    from maggy_tpu.telemetry import JOURNAL_NAME, obs, read_events
    from maggy_tpu.telemetry.spans import _dist_stats

    seed = int(os.environ.get("BENCH_OBS_SEED", "7"))
    trials = int(os.environ.get("BENCH_OBS_TRIALS", "10"))
    t0 = time.time()
    lat = {"/metrics": [], "/status": [], "/healthz": []}
    samples = []  # (wall_t, finalized count scraped from /metrics)
    failures = []
    healthz_bad = 0
    stop = threading.Event()

    def scraper():
        base = None
        while not stop.is_set():
            server = obs.active_server()
            if server is None:
                if base is not None:
                    return
                time.sleep(0.01)
                continue
            if base is None:
                base = "http://{}:{}".format(*server.address)
            try:
                bodies = {}
                for route in ("/metrics", "/status", "/healthz"):
                    r0 = time.monotonic()
                    try:
                        bodies[route] = urllib.request.urlopen(
                            base + route, timeout=5).read().decode()
                    except urllib.error.HTTPError as e:
                        # /healthz legitimately answers 503 (counted —
                        # this fault-free sweep must never be
                        # unhealthy); an error status on any OTHER
                        # route is a broken endpoint, not a scrape.
                        if route != "/healthz":
                            raise
                        bodies[route] = e.read().decode()
                        nonlocal_count["healthz_bad"] += 1
                    lat[route].append((time.monotonic() - r0) * 1e3)
                wall = time.time()
                count = 0
                for line in bodies["/metrics"].splitlines():
                    if line.startswith("maggy_tpu_trial_phase_total") \
                            and 'phase="finalized"' in line:
                        count = int(float(line.rsplit(" ", 1)[1]))
                samples.append((wall, count))
            except Exception as e:  # noqa: BLE001 - the failure IS the finding
                if obs.active_server() is not None:
                    failures.append(repr(e))
            time.sleep(0.03)

    nonlocal_count = {"healthz_bad": 0}
    thread = threading.Thread(target=scraper, daemon=True)
    thread.start()
    config = OptimizationConfig(
        name="bench_obs", num_trials=trials, optimizer="randomsearch",
        searchspace=Searchspace(lr=("DOUBLE", [0.0, 0.2]),
                                units=("INTEGER", [8, 64])),
        direction="max", num_workers=2, hb_interval=0.05, seed=seed,
        es_policy="none", obs_port=0)
    result = experiment.lagom(_obs_train_fn, config)
    stop.set()
    thread.join(timeout=5)
    healthz_bad = nonlocal_count["healthz_bad"]

    exp_dirs = sorted(d for d in glob.glob(os.path.join(
        os.environ["MAGGY_TPU_BASE_DIR"], "*")) if os.path.isdir(d))
    journal = os.path.join(exp_dirs[-1], JOURNAL_NAME)
    events = read_events(journal)
    fin_times = sorted(e["t"] for e in events
                       if e.get("ev") == "trial"
                       and e.get("phase") == "finalized")
    # Scrape-vs-journal: a live counter read at wall time T must agree
    # with the journal replayed to T, up to clock/step slack either side.
    slack = 0.5
    mismatches = []
    for wall, count in samples:
        lo = sum(1 for t in fin_times if t <= wall - slack)
        hi = sum(1 for t in fin_times if t <= wall + slack)
        if not lo <= count <= hi:
            mismatches.append({"t": wall, "scraped": count,
                               "journal_bounds": [lo, hi]})
    ok = bool(samples) and not failures and not mismatches \
        and healthz_bad == 0 and result.get("num_trials") == trials
    print(json.dumps({
        "metric": "obs scrape (live /metrics+/status+/healthz under a "
                  "{}-trial sweep, journal-checked)".format(trials),
        "value": 1.0 if ok else 0.0,
        "unit": "scrape_consistent",
        "detail": {
            "obs": {
                "scrapes": len(samples),
                "failures": failures,
                "healthz_not_ok": healthz_bad,
                "scrape_ms": {route.strip("/"): _dist_stats(vals)
                              for route, vals in lat.items()},
                "consistency": {"samples": len(samples),
                                "mismatches": mismatches,
                                "slack_s": slack,
                                "journal_finalized": len(fin_times),
                                "last_scraped": samples[-1][1]
                                if samples else None},
            },
            "platform": "cpu proxy (forced; the obs plane is "
                        "platform-independent — pinned for "
                        "cross-round comparability)",
            "seed": seed,
            "wall_s": round(time.time() - t0, 1),
            "journal": journal,
        },
    }), flush=True)
    return 0 if ok else 1


def scale_main():
    """``bench.py --scale``: service-scale control-plane soak (see
    maggy_tpu/fleet/soak.py run_scale_soak). Four phases against real
    fleets: (1) a >=500-concurrent-experiment churn through one fleet
    (lagom_submit + the spool path) gating tenant completion, scheduler
    decision throughput, and admission latency p99; (2) the SINK A/B —
    the same churn with telemetry re-enabled through the fleet's journal
    sink (``detail.sink``): decision throughput and admission p99 must
    stay within 10% of the telemetry-off baseline and the sink's
    replayed ingest lag p95 in bound — telemetry at churn scale must be
    near-free (BENCH_SCALE_SINK=0 skips the arm); (3) three weighted
    resident tenants gating journal-replayed fair-share error; (4) the
    slow-tenant A/B — per-tenant dispatch pools ON must hold the victim
    hand-off p95 isolation bound, and the pool-OFF (pre-fix shared-loop)
    arm must show the head-of-line inflation the pools remove. Always a
    CPU-pinned run (the plane under test is platform-independent Python;
    detail.platform records the pin per the ROADMAP comparability note).
    Exit 1 on any gate violation.

    ``--scale --remote`` runs the REMOTE variant instead (ROADMAP item 4
    remainder — "nothing yet measures hundreds of sockets"): the churn
    driven by real agent daemon processes over sockets
    (fleet/soak.py run_remote_scale_soak), recording ``detail.remote``:
    agent join latency p50/p95, ABIND lease round-trip p50/p95, and
    churn completion — with ``detail.platform`` pinned the same way for
    comparability against the in-process rounds."""
    _pin_bench_env(cpu=True)
    seed = int(os.environ.get("BENCH_SCALE_SEED", "7"))
    platform_note = ("cpu pinned (forced; the control plane under test "
                     "is platform-independent — pinned for cross-round "
                     "comparability)")
    t0 = time.time()
    if "--remote" in sys.argv:
        from maggy_tpu.fleet.soak import run_remote_scale_soak

        experiments = int(os.environ.get("BENCH_REMOTE_EXPERIMENTS", "40"))
        agents = int(os.environ.get("BENCH_REMOTE_AGENTS", "4"))
        runners = int(os.environ.get("BENCH_REMOTE_RUNNERS", "2"))
        report = run_remote_scale_soak(
            experiments=experiments, agents=agents, runners=runners,
            seed=seed)
        print(json.dumps({
            "metric": "remote scale soak ({} tenants churned through {} "
                      "real agent processes over sockets, "
                      "journal-checked)".format(experiments, agents),
            "value": report["detail"].get("experiments_per_s") or 0.0,
            "unit": "experiments_per_s",
            "detail": {
                "seed": seed,
                "wall_s": round(time.time() - t0, 1),
                "violations": report["violations"],
                "remote": report["detail"],
                "platform": platform_note,
                "journal": report["journal"],
            },
        }), flush=True)
        return 0 if report["ok"] else 1
    from maggy_tpu.fleet.soak import run_scale_soak

    experiments = int(os.environ.get("BENCH_SCALE_EXPERIMENTS", "520"))
    runners = int(os.environ.get("BENCH_SCALE_RUNNERS", "8"))
    max_active = int(os.environ.get("BENCH_SCALE_MAX_ACTIVE", "12"))
    sink_ab = os.environ.get("BENCH_SCALE_SINK", "1").strip().lower() \
        not in ("0", "false", "off")
    report = run_scale_soak(experiments=experiments, runners=runners,
                            max_active=max_active, seed=seed,
                            sink_ab=sink_ab)
    # The sink A/B block surfaces once, as detail.sink (popped from the
    # soak detail so the record doesn't serialize it twice).
    scale_detail = dict(report["detail"])
    sink_detail = scale_detail.pop("sink", None)
    churn = report["detail"]["churn"]
    print(json.dumps({
        "metric": "scale soak ({} tenants / {} runners churn + weighted "
                  "share + slow-tenant A/B, journal-checked)".format(
                      experiments, runners),
        "value": churn.get("experiments_per_s") or 0.0,
        "unit": "experiments_per_s",
        "detail": {
            "seed": seed,
            "wall_s": round(time.time() - t0, 1),
            "violations": report["violations"],
            "scale": scale_detail,
            "sink": sink_detail,
            "platform": platform_note,
            "journal": report["journal"],
        },
    }), flush=True)
    return 0 if report["ok"] else 1


def extra_main(name):
    """Child process: run ONE extra bench and print its JSON on stdout."""
    if name == "hang":  # test hook: simulates a compile stall / wedged op
        log("hang extra: sleeping forever (test hook)")
        time.sleep(1e9)
        return 0
    from maggy_tpu.util import enable_compile_cache

    enable_compile_cache()
    stamp = _require_tpu()
    result = EXTRA_BENCHES[name]()
    print(json.dumps({**result, **stamp}), flush=True)
    return 0


# ------------------------------------------------------------- orchestrator

def _last_json_line(text):
    for line in reversed(text.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                continue
    return None


def _run_child(argv, timeout_s):
    """Run a bench child; KILL it on timeout so this process never blocks
    on a child's stall. A dead process releases its chip; the next child
    that needs the chip is the probe.

    Returns (status, payload): status in {"ok", "timeout", "crash"};
    payload is the child's last stdout JSON line, or on crash a dict with
    the stderr tail. Child stderr is tee'd through live."""
    import subprocess
    import threading

    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)] + argv,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    out_parts, err_tail = [], []

    # Each pipe gets exactly ONE reader thread (communicate() alongside a
    # tee thread would race it for chunks and drop most of the content).
    def _read_out():
        for line in proc.stdout:
            out_parts.append(line)

    def _tee_err():
        for line in proc.stderr:
            sys.stderr.write(line)
            sys.stderr.flush()
            err_tail.append(line)
            del err_tail[:-40]

    readers = [threading.Thread(target=_read_out, daemon=True),
               threading.Thread(target=_tee_err, daemon=True)]
    for r in readers:
        r.start()
    try:
        proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        return "timeout", None
    for r in readers:  # EOF arrives once the child's pipe ends close
        r.join(timeout=5)
    parsed = _last_json_line("".join(out_parts))
    if proc.returncode != 0:
        return "crash", parsed if parsed is not None else {
            "stderr_tail": "".join(err_tail)[-2000:]}
    if parsed is None:
        return "crash", {"stderr_tail": "".join(err_tail)[-2000:]}
    return "ok", parsed


def main():
    """Orchestrator. Never imports jax in this process — every measurement
    runs in a killable child, one after the other, so this process never
    holds the chip a child needs. Order of output lines on stdout:

    1. the headline JSON (sweep + baselines, no extras) — printed BEFORE
       any extra bench runs, so a misbehaving extra cannot cost the
       already-measured number;
    2. the final enriched JSON (same headline values + extras in detail).

    A consumer taking either the first or the last JSON line gets the same
    headline numbers. A failed phase — no TPU, a headline or extra that
    crashed or timed out — is a non-zero exit, and a failed headline
    prints no metric at all."""
    # Share one base dir across children; remove it at exit when WE
    # minted it.
    if "MAGGY_TPU_BASE_DIR" not in os.environ:
        import atexit
        import shutil

        base = tempfile.mkdtemp(prefix="bench_")
        os.environ["MAGGY_TPU_BASE_DIR"] = base
        atexit.register(shutil.rmtree, base, True)

    status, headline = _run_child(
        ["--headline"], float(os.environ.get("BENCH_HEADLINE_TIMEOUT_S", "2400")))
    # A failed headline prints NO metric line: a zero under the metric's
    # name would be a number nobody measured.
    if status == "timeout":
        log("headline child timed out and was killed")
        return 1
    if headline is None or "metric" not in headline:
        log("headline child failed without a result: {}".format(
            (headline or {}).get("stderr_tail", "")[-500:]))
        return 1
    # Print the headline IMMEDIATELY — before extras can touch the device.
    print(json.dumps(headline), flush=True)
    if status == "crash" or headline.get("value", 0) == 0:
        return 1

    extras = run_extra_benches()
    if extras:
        enriched = dict(headline)
        enriched["detail"] = {**headline.get("detail", {}), **extras}
        print(json.dumps(enriched), flush=True)
    failed = sorted(n for n, e in extras.items() if "error" in e)
    if failed:
        log("extras failed: {}".format(failed))
        return 1
    return 0


def run_extra_benches():
    """MFU + kernel measurements, each in its own killable subprocess so a
    compile stall can neither abort this process nor keep the chip. An
    extra that crashed, timed out or was skipped is recorded as
    ``{"error": ...}``, which ``main`` turns into a non-zero exit."""
    extras = {}
    if os.environ.get("BENCH_SKIP_EXTRAS") == "1":
        return extras
    names = [n.strip() for n in os.environ.get(
        "BENCH_EXTRAS", "llama,bert,flash_vs_xla").split(",") if n.strip()]
    budget_s = float(os.environ.get("BENCH_EXTRA_TIMEOUT_S", "420"))
    total_s = float(os.environ.get("BENCH_EXTRA_TOTAL_S", "900"))
    started = time.time()
    for name in names:
        if name not in EXTRA_BENCHES and name != "hang":
            extras[name] = {"error": "unknown extra (valid: {})".format(
                ",".join(EXTRA_BENCHES))}
            continue
        remaining = total_s - (time.time() - started)
        if remaining <= 5:
            extras[name] = {"error": "skipped: extras total budget spent"}
            log("{} bench skipped (total extras budget {}s spent)".format(
                name, total_s))
            continue
        t0 = time.time()
        status, payload = _run_child(["--extra", name], min(budget_s, remaining))
        if status == "ok":
            extras[name] = payload
            log("{} bench done in {:.1f}s: {}".format(
                name, time.time() - t0, payload))
        elif status == "timeout":
            extras[name] = {"error": "timeout: killed after {:.0f}s".format(
                time.time() - t0)}
            log("{} bench TIMED OUT and was killed".format(name))
        else:
            tail = (payload or {}).get("stderr_tail", "")
            extras[name] = {"error": "crashed: {}".format(tail[-500:] or payload)}
            log("{} bench CRASHED: {}".format(name, tail[-1000:] or payload))
    return extras


if __name__ == "__main__":
    if "--headline" in sys.argv:
        sys.exit(headline_main())
    if "--extra" in sys.argv:
        sys.exit(extra_main(sys.argv[sys.argv.index("--extra") + 1]))
    if "--chaos" in sys.argv:
        sys.exit(chaos_main())
    if "--failover" in sys.argv:
        sys.exit(failover_main())
    if "--fork" in sys.argv:
        sys.exit(fork_main())
    if "--vmap" in sys.argv:
        sys.exit(vmap_main())
    if "--goodput" in sys.argv:
        sys.exit(goodput_main())
    if "--fleet" in sys.argv:
        sys.exit(fleet_main())
    if "--pack" in sys.argv:
        sys.exit(pack_main())
    if "--obs" in sys.argv:
        sys.exit(obs_main())
    if "--scale" in sys.argv:
        sys.exit(scale_main())
    sys.exit(main())

"""chip_smoke.py: the quickest proof that the main path still starts on the TPU.

    python chip_smoke.py

drives `experiment.lagom` -> `OptimizationDriver` -> runner pool ->
`trial_executor` -> `train.Trainer` (warm slot, AOT step that donates its state) ->
`models.BertEncoder` -> `ops.attention.multi_head_attention` -> the Pallas
flash kernels, at `BertConfig.base()` (published width and depth, B=32,
S=128, bf16, a ragged key-padding mask, synthetic tokens from a seed), as an
ASHA sweep whose promotions fork an orbax checkpoint. Two phases:

- ``thread``: a child process runs the sweep with ``pool="thread"`` and two
  runner threads sharing one chip, after checking `flash_attention` against
  `attention_reference` (forward and all three gradients, compiled).
- ``tpu``: this process runs the sweep with ``pool="tpu"``,
  ``num_workers="auto"``: one pinned runner process per chip.

One process owns a chip at a time, so this process never initialises a JAX
backend (checked at exit) and the thread child is gone before phase two.

Exit code 0 and, as the last two lines of stdout, the report of both phases as
one JSON object (also written to ``chiprun_out/chip_smoke/result.json``) and
then the verdict alone:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as JAX reports it. Any failed check, a missing accelerator or
a missing repository is a non-zero exit with the reason as the last line of
stderr and no result on stdout. The numbers in the report are bring-up
observations, not benchmark results.
"""

from __future__ import annotations

import argparse
import functools
import glob
import json
import math
import multiprocessing
import os
import shutil
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
THREAD_REPORT = os.path.join(OUT_DIR, "thread_report.json")

#: Whole-run limit (the contract allows 1200 s) and the thread child's share.
RUN_LIMIT_S = 1150
THREAD_PHASE_LIMIT_S = 700

BATCH, SEQ, STEPS_PER_BUDGET, DATA_SEED = 32, 128, 8, 0

#: Flash-vs-reference tolerance, fixed from the dtype: bfloat16 keeps 8
#: significant bits, so one rounding is 2**-9 of a value. The kernel rounds
#: its output once and feeds the MXU bf16 operands; four units in the last
#: place of the tensor's largest magnitude bounds that, while a wrong mask,
#: offset or scale is an error of order one.
FLASH_TOL = 4 * 2.0 ** -8
FLASH_SHAPES = (
    # name, B, Sq, H, Hkv, D, causal, ragged key mask
    ("bert_b32_s128_h12_d64_masked", 32, 128, 12, 12, 64, False, True),
    ("gqa_b1_s2048_h32_kv8_d128_causal", 1, 2048, 32, 8, 128, True, False),
    # The class of the benchmark's `bert-base.steady-s512` cell.
    ("bert_b64_s512_h12_d64_masked", 64, 512, 12, 12, 64, False, True),
)


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def require(cond, reason: str) -> None:
    if not cond:
        raise SmokeFailure(reason)


# ------------------------------------------------------------------ trial body


def smoke_loss(logits, batch):
    from maggy_tpu.train import cross_entropy_loss

    return cross_entropy_loss(logits, batch["labels"])


def synthetic_batches(cfg, batch: int, seq: int, n: int = 4):
    """``n`` seeded batches: tokens, a ragged key-padding mask (each row
    keeps between half and all of its positions) and binary labels."""
    import numpy as np

    rng = np.random.default_rng(DATA_SEED)
    out = []
    for _ in range(n):
        tokens = rng.integers(0, cfg.vocab_size, size=(batch, seq))
        lengths = rng.integers(seq // 2, seq + 1, size=(batch, 1))
        out.append({
            "inputs": (tokens.astype(np.int32),
                       np.arange(seq)[None, :] < lengths),
            "labels": (tokens[:, 0] % 2).astype(np.int32),
        })
    return out


def train_fn(lr, budget=1, reporter=None, ctx=None, *, model_cfg, platform,
             batch=BATCH, steps_per_budget=STEPS_PER_BUDGET):
    """One ASHA trial: ``budget * steps_per_budget`` optimizer steps in all,
    resuming after the forked parent's last step when the driver staged its
    checkpoint, and saving its own at the end of the budget."""
    import jax
    import optax

    from maggy_tpu.models import BertEncoder
    from maggy_tpu.parallel import make_mesh
    from maggy_tpu.train import Trainer, swept_transform, warm

    if jax.default_backend() != platform:
        raise RuntimeError("trial needs backend {!r}, JAX reports {!r}".format(
            platform, jax.default_backend()))
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    seq = min(SEQ, model_cfg.max_seq_len)
    batches = synthetic_batches(model_cfg, batch, seq)
    # lr rides in opt_state, so the whole sweep is ONE program.
    trainer = Trainer(BertEncoder(model_cfg),
                      swept_transform(optax.adamw, learning_rate=lr),
                      smoke_loss, mesh, strategy="dp")
    tokens, mask = batches[0]["inputs"]
    trainer.init(jax.random.key(0), (tokens,),
                 init_kwargs={"attention_mask": mask})

    start = 0
    if ctx.resume_step is not None:
        live = {"variables": trainer.variables, "opt_state": trainer.opt_state}
        state = ctx.restore_checkpoint(jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding), live))
        trainer.variables, trainer.opt_state = \
            state["variables"], state["opt_state"]
        start = ctx.resume_step + 1

    def cache_counts():
        # Persistent-cache events of THIS runner thread's compiles.
        snap = warm.current_scope().stats.snapshot()
        return {k: snap.get("xla_cache_" + k, 0) for k in ("hits", "misses")}

    total = int(budget * steps_per_budget)
    first_loss = loss = step_cache = None
    before = cache_counts()
    for step in range(start, total):
        loss = trainer.step(trainer.place_batch(batches[step % len(batches)]))
        if first_loss is None:
            first_loss = loss
            # What the cache did for the step program's compile (zero both
            # ways when the warm slot or another thread already held it).
            step_cache = {k: n - before[k] for k, n in cache_counts().items()}
        reporter.broadcast(loss, step=step)  # lazy: no host sync in the loop
    ctx.save_checkpoint(total - 1, {"variables": trainer.variables,
                                    "opt_state": trainer.opt_state})
    # The executable that ran: Mosaic kernels are tpu_custom_call in its HLO.
    hlo = trainer._active_step.as_text()
    return {
        "metric": float(loss),
        "first_loss": float(first_loss),
        "first_step": start,
        "steps_run": total - start,
        "pallas_calls": None if hlo is None else hlo.count(
            'custom_call_target="tpu_custom_call"'),
        "step_cache": step_cache,
        "platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "devices": [[d.id, list(getattr(d, "coords", ()))]
                    for d in mesh.devices.flat],
        "n_visible_devices": len(jax.devices()),
        "tpu_visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "pid": os.getpid(),
    }


# ------------------------------------------------------------------- the sweep


def run_sweep(model_cfg, pool: str, num_workers, base_dir: str,
              platform: str = "tpu", batch: int = BATCH) -> dict:
    """Run the smoke's ASHA-with-forks sweep through ``lagom`` and check it
    from what came back: the journal, each trial's ``.outputs.json`` and the
    goodput fold. Returns the phase's report; raises `SmokeFailure` when a
    check does not hold. ``platform="cpu"`` with ``BertConfig.tiny()`` is the
    CPU rehearsal (tests/test_chip_smoke.py)."""
    from maggy_tpu import (OptimizationConfig, Searchspace, experiment,
                           native, util)
    from maggy_tpu.optimizers import Asha
    from maggy_tpu.telemetry import JOURNAL_NAME, read_events
    from maggy_tpu.telemetry.spans import derive

    shutil.rmtree(base_dir, ignore_errors=True)
    os.makedirs(base_dir)
    config = OptimizationConfig(
        name="chip_smoke_" + pool, num_trials=4,
        optimizer=Asha(reduction_factor=2, resource_min=1, resource_max=4,
                       seed=0),
        searchspace=Searchspace(lr=("DOUBLE_LOG", [1e-5, 1e-4])),
        direction="min", es_policy="none", seed=0, pool=pool,
        num_workers=num_workers, chips_per_trial=1, experiment_dir=base_dir)
    t0 = time.time()
    experiment.lagom(
        functools.partial(train_fn, model_cfg=model_cfg, platform=platform,
                          batch=batch), config)
    wall_s = time.time() - t0

    exp_dir, = [d for d in glob.glob(os.path.join(base_dir, "*"))
                if os.path.isdir(d)]
    journal = os.path.join(exp_dir, JOURNAL_NAME)
    events = read_events(journal)
    trial_events = [e for e in events if e.get("ev") == "trial"]

    def phase_events(phase):
        return {e["trial"]: e for e in trial_events if e.get("phase") == phase}

    queued, finalized = phase_events("queued"), phase_events("finalized")
    compiled, forked = phase_events("compiled"), phase_events("forked_from")
    ckpt = phase_events("ckpt_saved")

    # Every trial the driver scheduled finalised, without error.
    require(queued, "the journal holds no scheduled trial")
    missing = sorted(set(queued) - set(finalized))
    require(not missing, "scheduled trials never finalised: {}".format(missing))
    errors = sorted(t for t, e in finalized.items() if e.get("error"))
    require(not errors, "trials finalised in error: {} (see {})".format(
        errors, exp_dir))
    rung_of = {t: (e.get("info") or {}).get("rung", 0) for t, e in queued.items()}
    for rung in (1, 2):
        require(any(r == rung for r in rung_of.values()),
                "no promotion reached rung {}".format(rung))

    outputs = {}
    for trial in queued:
        with open(os.path.join(exp_dir, trial, ".outputs.json")) as f:
            outputs[trial] = json.load(f)
    wrong = sorted(t for t, o in outputs.items() if o["platform"] != platform)
    require(not wrong, "trials ran on another platform than {!r}: {}".format(
        platform, {t: outputs[t]["platform"] for t in wrong}))
    for trial, o in outputs.items():
        require(math.isfinite(o["metric"]) and math.isfinite(o["first_loss"]),
                "trial {} has a non-finite loss: {}".format(trial, o))
        require(o["metric"] != o["first_loss"],
                "trial {}: the loss did not change over {} steps".format(
                    trial, o["steps_run"]))
    pallas = {o["pallas_calls"] for o in outputs.values()}
    require(None not in pallas, "a trial's step executable gave no HLO text")
    if platform == "tpu":
        require(min(pallas) > 0, "the attention that ran was not the Pallas "
                "kernel: no tpu_custom_call in a trial's step executable")

    # Promotions were served from a fork: journal edge, a restore, and the
    # child's first executed step right after the parent's last.
    forks_served = [
        t for t, e in forked.items()
        if outputs[t]["first_step"] == int(e["step"]) + 1
        and (ckpt.get(t) or {}).get("restores", 0) >= 1]
    require(forks_served, "no promotion was served from a fork "
            "(forked_from edges: {})".format(sorted(forked)))
    retrained = {t: o["first_step"] for t, o in outputs.items()
                 if rung_of[t] > 0 and t not in forks_served}
    require(not retrained, "promotions re-trained their parent's prefix: "
            "{}".format(retrained))

    # A trial after the first on some runner hit the warm slot.
    order = {}
    for e in sorted((e for e in trial_events if e.get("phase") == "running"),
                    key=lambda e: e["t"]):
        order.setdefault(e.get("partition"), []).append(e["trial"])
    later = {t for trials in order.values() for t in trials[1:]}
    warm_hits = sorted(t for t in later if (compiled.get(t) or {}).get("warm"))
    require(warm_hits, "no trial after the first on a runner hit the warm "
            "slot (compiled records: {})".format(
                {t: e.get("warm") for t, e in compiled.items()}))

    # The device-memory gauge rides the heartbeats; None would be silent.
    dev_mem = [e["dev_mem_mb"] for e in events
               if e.get("ev") == "runner_stats" and e.get("dev_mem_mb")]
    if platform == "tpu":
        require(dev_mem, "no runner_stats event carried dev_mem_mb")

    # The goodput fold (telemetry/goodput.py) closes: every held
    # chip-second is in exactly one bucket.
    derived = derive(events)
    held = derived["goodput"]["held_chip_s"]
    buckets = derived["goodput"]["buckets"]
    require(held > 0 and math.isclose(sum(buckets.values()), held,
                                      rel_tol=1e-9, abs_tol=1e-6),
            "the goodput fold does not close: sum(buckets)={} held={}".format(
                sum(buckets.values()), held))

    # pool="tpu": every registered runner is its own process, finalised a
    # trial, and holds one chip that no other runner holds.
    runners = {}
    for o in outputs.values():
        r = runners.setdefault(o["pid"], {"chips": set(), "trials": 0,
                                          "visible": o["n_visible_devices"]})
        r["chips"].add(o["tpu_visible_chips"])
        r["trials"] += 1
    if pool == "tpu":
        registered = {e["partition"] for e in events if e.get("ev") == "runner"
                      and e.get("phase") == "registered"}
        chips = [c for r in runners.values() for c in r["chips"]]
        require(len(runners) == len(registered),
                "{} runner processes finalised a trial, {} registered"
                .format(len(runners), len(registered)))
        require(len(set(chips)) == len(chips) == len(runners)
                and None not in chips,
                "runners do not hold one distinct chip each: {}".format(chips))
        require(all(r["visible"] == 1 for r in runners.values()),
                "a runner sees more than one chip: {}".format(runners))

    # The trial that built the step program (racing first trials wait for
    # it), and one that found the warm slot.
    first = max(compiled.values(), key=lambda e: e.get("compile_ms") or 0)
    warm_rec = compiled[warm_hits[0]]
    report = {
        "pool": pool,
        "wall_s": round(wall_s, 1),
        "trials_scheduled": len(queued),
        "trials_finalized": len(finalized),
        "rungs": {str(r): sum(1 for v in rung_of.values() if v == r)
                  for r in sorted(set(rung_of.values()))},
        "forks_served": len(forks_served),
        "warm_hits": len(warm_hits),
        "attention_path": "pallas" if min(pallas) > 0 else "reference",
        "pallas_calls_per_step": sorted(pallas),
        "first_trial_s": {k[:-3]: round((first.get(k) or 0) / 1e3, 2)
                          for k in ("init_ms", "trace_ms", "compile_ms")},
        "warm_trial_s": {k[:-3]: round((warm_rec.get(k) or 0) / 1e3, 2)
                         for k in ("init_ms", "trace_ms", "compile_ms")},
        "step_program_cache": {
            k: sum((o["step_cache"] or {}).get(k, 0) for o in outputs.values())
            for k in ("hits", "misses")},
        "persistent_cache": (derived.get("compile") or {}).get("cache") or
        {"hits": 0, "misses": 0},
        "cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or util.COMPILE_CACHE_DIR,
        "dev_mem_mb_max": max(dev_mem) if dev_mem else None,
        "goodput": {"held_chip_s": round(held, 2),
                    "buckets": {k: round(v, 2) for k, v in buckets.items() if v}},
        "runners": [{"pid": pid, "chip": sorted(r["chips"], key=str),
                     "trials": r["trials"]} for pid, r in sorted(runners.items())],
        "native_codec": native.is_native(),
        "journal": os.path.relpath(journal, HERE),
    }
    # The journal and the outputs come back; gigabytes of checkpoints do not.
    for d in glob.glob(os.path.join(exp_dir, "*", "checkpoints")):
        shutil.rmtree(d, ignore_errors=True)
    return report


# ----------------------------------------------------- flash vs the reference


def check_flash_attention(shapes=FLASH_SHAPES) -> dict:
    """The flash kernels (compiled on a TPU, never interpreted there)
    against `attention_reference` at full float32 matmul precision: forward
    and the gradients for q, k and v, at the sweep's own shape, at one
    causal GQA shape and at the benchmark cell's. Every shape runs twice:
    at the tiles `tile_plan` chooses from it (what `multi_head_attention`
    runs) and at an explicit 128 x 128 (what ring attention, Ulysses and
    the tests pass). Errors are relative to the reference tensor's largest
    magnitude. ``{shape: {"plan": ..., "planned" | "explicit_128":
    {out, dq, dk, dv}}}``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from maggy_tpu.ops.attention import (attention_reference, flash_attention,
                                         multi_head_attention, tile_plan)

    report = {}
    interpret = jax.default_backend() != "tpu"  # tests/test_chip_smoke.py
    for name, B, S, H, Hkv, D, causal, masked in shapes:
        rng = np.random.default_rng(S)
        q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.bfloat16)
        k, v = (jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.bfloat16)
                for _ in range(2))
        w = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        keep = None
        if masked:
            keep = jnp.asarray(np.arange(S)[None, :]
                               < rng.integers(S // 2, S + 1, size=(B, 1)))
        mask4 = None if keep is None else keep[:, None, None, :]

        def planned(q, k, v):  # the public entry, held to the kernels
            return multi_head_attention(q, k, v, causal=causal, mask=mask4,
                                        force="flash")

        def explicit_128(q, k, v):
            return flash_attention(q, k, v, keep, causal, 128, 128, interpret)

        def reference(q, k, v):
            with jax.default_matmul_precision("highest"):
                return attention_reference(q, k, v, causal=causal, mask=mask4)

        def run(fn):
            def loss(q, k, v):
                out = fn(q, k, v)
                return jnp.sum(out.astype(jnp.float32) * w), out

            (_, out), grads = jax.jit(jax.value_and_grad(
                loss, (0, 1, 2), has_aux=True))(q, k, v)
            return [np.asarray(x, np.float32) for x in (out, *grads)]

        want = run(reference)
        report[name] = {"plan": tile_plan(S, S, D, H, Hkv, 2, causal,
                                          masked).describe()}
        for tiles, fn in (("planned", planned),
                          ("explicit_128", explicit_128)):
            errs = {}
            for label, got, ref in zip(("out", "dq", "dk", "dv"), run(fn),
                                       want):
                require(np.isfinite(got).all(), "flash {} at {} ({}) is not "
                        "finite".format(label, name, tiles))
                errs[label] = float(np.abs(got - ref).max()
                                    / np.abs(ref).max())
            report[name][tiles] = {k: round(e, 5) for k, e in errs.items()}
            bad = {k: e for k, e in errs.items() if e > FLASH_TOL}
            require(not bad, "flash attention ({}) disagrees with the "
                    "reference at {} beyond {:.4f}: {}".format(
                        tiles, name, FLASH_TOL, bad))
    return report


# ---------------------------------------------------------------------- phases


def phase_thread_main() -> None:
    """Child process: owns the chip for the flash check and the thread-pool
    sweep, writes its report to ``THREAD_REPORT`` and exits."""
    import jax
    import jaxlib

    from maggy_tpu.models import BertConfig
    from maggy_tpu.util import enable_compile_cache

    # Before anything compiles: JAX decides at its first compile whether the
    # persistent cache is in use.
    enable_compile_cache()
    device = jax.devices()[0]
    require(device.platform == "tpu",
            "JAX found no accelerator: devices are {}".format(jax.devices()))
    import libtpu

    cfg = BertConfig.base()
    report = {
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices())},
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu.__version__},
        "model": {**{k: getattr(cfg, k) for k in (
            "num_layers", "hidden_dim", "num_heads", "intermediate_dim",
            "vocab_size")}, "dtype": "bfloat16", "batch": BATCH, "seq": SEQ},
        "flash_vs_reference": check_flash_attention(),
        "flash_tolerance": FLASH_TOL,
        "thread": run_sweep(cfg, "thread", 2, os.path.join(OUT_DIR, "thread")),
    }
    with open(THREAD_REPORT, "w") as f:
        json.dump(report, f)


def verdict(device: dict) -> str:
    """The last line of stdout: ``ok`` and the device as JAX reported it to
    the thread child, and no other key (the driver reads exactly this)."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def backend_initialised() -> bool:
    bridge = sys.modules.get("jax._src.xla_bridge")
    return bool(bridge is not None and bridge._backends)


def main() -> int:
    def on_alarm(signum, frame):
        raise SmokeFailure("run limit of {} s reached".format(RUN_LIMIT_S))

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(RUN_LIMIT_S)
    child = None
    try:
        require(os.path.isdir(os.path.join(HERE, "maggy_tpu")),
                "no maggy_tpu/ next to chip_smoke.py: it checks the "
                "repository it is part of")
        os.makedirs(OUT_DIR, exist_ok=True)
        if os.path.exists(THREAD_REPORT):
            os.unlink(THREAD_REPORT)
        child = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--phase", "thread"])
        rc = child.wait(timeout=THREAD_PHASE_LIMIT_S)
        result = {}
        if os.path.exists(THREAD_REPORT):
            with open(THREAD_REPORT) as f:
                result = json.load(f)
        require(rc == 0 and "failure" not in result and result,
                "phase thread: {}".format(result.get(
                    "failure", "exit code {}, see its traceback".format(rc))))

        from maggy_tpu.models import BertConfig

        result["tpu"] = run_sweep(BertConfig.base(), "tpu", "auto",
                                  os.path.join(OUT_DIR, "tpu"))
        require(len(result["tpu"]["runners"]) == result["device"]["count"],
                "phase tpu ran {} runner(s) on a host with {} chip(s)".format(
                    len(result["tpu"]["runners"]), result["device"]["count"]))
        require(not backend_initialised(),
                "the orchestrating process initialised a JAX backend: it "
                "would have held the chip its children need")
    except (SmokeFailure, subprocess.TimeoutExpired) as e:
        print("chip_smoke: FAILED: {}".format(e), file=sys.stderr, flush=True)
        return 1
    finally:
        signal.alarm(0)
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        for proc in multiprocessing.active_children():
            proc.kill()
            proc.join()
    device = result["device"]
    result = {"ok": True, "device": device, "platform": device["platform"],
              "device_kind": device["kind"],
              "n_chips": len(result["tpu"]["runners"]), **result}
    with open(os.path.join(OUT_DIR, "result.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    print(verdict(device), flush=True)
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phase", choices=["thread"],
                    help="internal: run one phase in this process")
    args = ap.parse_args()
    if args.phase == "thread":
        try:
            phase_thread_main()
        except SmokeFailure as e:
            with open(THREAD_REPORT, "w") as f:
                json.dump({"failure": str(e)}, f)
            sys.exit(1)
        sys.exit(0)
    sys.exit(main())

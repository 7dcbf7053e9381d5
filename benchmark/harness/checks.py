"""Correctness outside the window: the configuration's model, as the
program builds it, against the benchmark's plain float32 reference.

Same seeded weights into both; compared are the logits, the loss, and the
gradient of the weights the family names (`checked_grads`; for the encoders
the first layer's, which the gradient reaches last, through every other
layer). Each error is the largest absolute
difference over the tensor, relative to the reference tensor's largest
magnitude.

Tolerances, in the configuration's ``check``. The model computes in bfloat16
activations (8 significant bits: one rounding is 2**-9 of a value) over
float32 parameters, the reference in float32 at full matmul precision.
Roundings accumulate over 12 layers, each a chain of matmuls fed bf16
operands, and the gradient of layer 0 has been through all 12 forward and
all 12 back, on 2 sequences with nothing to average over. Both bounds are
16 * 2**-8 = 0.0625 of the tensor's largest magnitude. (The forward bound was
first fixed at 8 * 2**-8; the first chip runs showed 0.027 on the [2, 2]
logits at S=512, too near it, and it was widened; PERF.md says so. Largest
seen on the chip in 40 runs: 0.029 forward, 0.027 on a gradient.) A
wrong mask, scale, layer order or a missing term is an error of order one
(argued as ``chip_smoke.FLASH_TOL`` argues for the kernel alone). A leaf
whose reference gradient vanishes (the key bias: softmax does not see a
constant added to every score of a row) has no magnitude of its own to be
relative to and is held to the layer's largest gradient magnitude instead.

Run in-process where the measuring process holds the chip, and as
``python -m benchmark.harness.checks <payload.json>`` where runner
processes held it.
"""

from __future__ import annotations

import json
import math
import sys

from benchmark.harness import spec


def _rel_err(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def programs(config: dict) -> dict:
    """The check's three programs as functions of the seeded values (the rng
    key or the parameters, and the batch) and of nothing else: a value of
    the seed that a program closed over would be a constant in its HLO, the
    persistent cache would miss on every new seed, and every run would
    compile both models again (55 s of a ViT run's 150, my chip runs, PR
    22)."""
    import flax.linen as nn

    family = spec.load_module("families", config["family"])
    ref = spec.load_module("reference", config["family"])
    model = config["model"]
    module, _ = family.build(model)

    def init_fn(rng, batch):
        example, kwargs = family.init_args(batch)
        return nn.meta.unbox(module.init(rng, *example, **kwargs))["params"]

    def model_fn(p, batch):
        example, kwargs = family.init_args(batch)
        logits = module.apply({"params": p}, *example, **kwargs)
        return family.loss(logits, batch), logits

    def ref_fn(p, batch):
        logits = ref.forward(p, batch["inputs"], model)
        return ref.loss_from_logits(logits, batch["labels"]), logits

    return {"init": init_fn, "model": model_fn, "reference": ref_fn}


def model_vs_reference(config: dict, seq, seed: int) -> dict:
    """Errors of the program's model against the reference, at the
    configuration's widths, on ``check.sequences`` seeded examples."""
    import jax
    import jax.numpy as jnp

    family = spec.load_module("families", config["family"])
    fns = programs(config)
    batch = family.batches(config["model"], config["check"]["sequences"],
                           seq, seed, n=1)[0]
    batch = jax.tree_util.tree_map(jnp.asarray, batch)
    params = jax.jit(fns["init"])(jax.random.key(seed), batch)
    (m_loss, m_logits), m_grad = jax.jit(
        jax.value_and_grad(fns["model"], has_aux=True))(params, batch)
    (r_loss, r_logits), r_grad = jax.jit(
        jax.value_and_grad(fns["reference"], has_aux=True))(params, batch)

    @jax.jit
    def leaf_stats(got, want):
        """Per leaf: the largest difference, and the reference's largest
        magnitude."""
        return (jax.tree_util.tree_map(
                    lambda g, w: jnp.abs(g.astype(jnp.float32) - w).max(),
                    got, want),
                jax.tree_util.tree_map(lambda w: jnp.abs(w).max(), want))

    diffs, mags = jax.device_get(leaf_stats(
        family.checked_grads(m_grad), family.checked_grads(r_grad)))
    layer_max = float(max(jax.tree_util.tree_leaves(mags)))

    def grad_err(diff, mag):
        scale = layer_max if mag < 1e-6 * layer_max else max(mag, 1e-30)
        err = float(diff) / float(scale)
        return err if math.isfinite(err) else float("inf")

    grad_errs = jax.tree_util.tree_map(grad_err, diffs, mags)
    worst = max(jax.tree_util.tree_leaves_with_path(grad_errs),
                key=lambda kv: kv[1])
    errors = {
        "logits": _rel_err(m_logits, r_logits),
        "loss": abs(float(m_loss) - float(r_loss)) / abs(float(r_loss)),
        "grad": worst[1],
    }
    tol = {"logits": config["check"]["tolerance"],
           "loss": config["check"]["tolerance"],
           "grad": config["check"]["grad_tolerance"]}
    return {"errors": errors, "tolerances": tol,
            "worst_grad_leaf": jax.tree_util.keystr(worst[0]),
            "ok": all(errors[k] <= tol[k] for k in errors)}


if __name__ == "__main__":
    from maggy_tpu import util

    util.enable_compile_cache()
    with open(sys.argv[1]) as f:
        payload = json.load(f)
    print(json.dumps(model_vs_reference(
        payload["config"], payload["seq"], payload["seed"])))

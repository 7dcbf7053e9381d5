"""Controls of the ``sdar_moe`` reference check: what the check reads when
what stands in the program's place is known to be imprecise or wrong.

`checks.model_vs_reference` compares the model as the program builds it
with ``reference/sdar_moe.py``. A limit of that comparison is worth
something only if something fails it, so here the program's place is
taken by the reference's own equations, written out once more with two
knobs, and the harness's own comparison is run on them unchanged:

- ``bits``: every matmul operand, every activation that the program keeps
  in its activation dtype (embedding rows, norm outputs, projections,
  softmax probabilities, expert activations, the residual stream) and every
  cotangent that reaches them is rounded to that many mantissa bits (7:
  bfloat16's, the configuration's; 3: float8 e4m3's, the nearest below;
  2: e5m2's; 23: float32, nothing rounded). The exponent is left alone, so
  a low setting is that format at its kindest (no overflow, no underflow of
  gradients). What the configuration says stays float32 stays float32: the
  router's weights, logits and softmax, every norm's statistics, the
  attention softmax, the accumulation of every product.
- ``fault``: ``mask`` (a noised query also sees the clean copy of its own
  block, so the answer leaks), ``weights`` (the loss without 1/t),
  ``drop`` (a held expert takes 1.25 x the balanced rows and drops the
  rest, as a capacity factor does).

Both are traced values that ride in the batch, so one compiled program
serves every control. ``python3 benchmark/harness/sdar_controls.py --seeds
a,b --controls program,bits7,bits3,mask`` prints one line a reading and
writes ``chiprun_out/sdar_controls.json``; ``program`` is the unpatched
check. Faults are read at 7 bits, as a faulty program would run.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.harness import checks, spec  # noqa: E402

CELL = "sdar-30b-a3b.bd-steady-s4096"
FAULTS = {"none": 0, "mask": 1, "weights": 2, "drop": 3}
#: (mantissa bits, fault) of each control by name.
CONTROLS = {
    "bits23": (23, "none"), "bits7": (7, "none"), "bits5": (5, "none"),
    "bits4": (4, "none"), "bits3": (3, "none"), "bits2": (2, "none"),
    "mask": (7, "mask"), "weights": (7, "weights"), "drop": (7, "drop"),
}
#: The capacity factor of the ``drop`` fault.
CAPACITY_FACTOR = 1.25


def _round(x, bits):
    """float32 ``x`` rounded to ``bits`` explicit mantissa bits (a traced
    int32), ties away from zero; 23 returns ``x``."""
    drop = (23 - bits).astype(jnp.uint32)
    raw = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    one = jnp.uint32(1)
    half = jnp.where(drop > 0, one << (jnp.maximum(drop, one) - one), 0)
    keep = ~((one << drop) - one)
    return jax.lax.bitcast_convert_type((raw + half) & keep, jnp.float32)


@jax.custom_vjp
def rounded(x, bits):
    """`_round` forward, and the cotangent rounded the same way backward."""
    return _round(x, bits)


rounded.defvjp(lambda x, bits: (_round(x, bits), bits),
               lambda bits, g: (_round(g, bits), None))


def forward(ref, params, inputs, model: dict, bits, fault):
    """``reference/sdar_moe.py`` ``forward`` with the two knobs; with 23
    bits and no fault it computes what that computes."""
    def r(x):
        return rounded(x, bits)

    eps, d = model["rms_norm_eps"], model["head_dim"]
    heads, kv_heads = model["num_attention_heads"], \
        model["num_key_value_heads"]

    def attention(x, p):
        S = x.shape[0]
        L = S // 2
        positions = jnp.arange(S) % L
        q = r(x @ r(p["q_proj"]["kernel"])).reshape(S, heads, d)
        k = r(x @ r(p["k_proj"]["kernel"])).reshape(S, kv_heads, d)
        v = r(x @ r(p["v_proj"]["kernel"])).reshape(S, kv_heads, d)
        q = r(ref.rope(ref.rms_norm(q, p["q_norm"]["scale"], eps), positions,
                       model["rope_theta"]))
        k = r(ref.rope(ref.rms_norm(k, p["k_norm"]["scale"], eps), positions,
                       model["rope_theta"]))
        k = jnp.repeat(k, heads // kv_heads, axis=1)
        v = jnp.repeat(v, heads // kv_heads, axis=1)
        keys = jnp.arange(S)
        qb = min(ref.QUERY_BLOCK, S)
        block_of = (keys % L) // model["block_length"]

        @jax.checkpoint
        def block(args):
            q_blk, q_index = args
            scores = jnp.einsum("qhd,khd->hqk", q_blk, k) / math.sqrt(d)
            keep = ref.visible(q_index, keys, L, model["block_length"])
            own_clean = (q_index < L)[:, None] & (keys >= L)[None, :] & (
                block_of[None, :] == ((q_index % L)
                                      // model["block_length"])[:, None])
            keep = keep | ((fault == FAULTS["mask"]) & own_clean)
            probs = r(jax.nn.softmax(
                jnp.where(keep[None], scores, ref.NEG_INF), -1))
            return r(jnp.einsum("hqk,khd->qhd", probs, v))

        out = jax.lax.map(block, (q.reshape(S // qb, qb, heads, d),
                                  keys.reshape(S // qb, qb)))
        return r(out.reshape(S, heads * d) @ r(p["o_proj"]["kernel"]))

    def experts(x, p):
        probs = jax.nn.softmax(x @ p["router"], axis=-1)
        top, ids = jax.lax.top_k(probs, model["num_experts_per_tok"])
        if model["norm_topk_prob"]:
            top = top / jnp.sum(top, axis=-1, keepdims=True)
        rows = jnp.arange(x.shape[0])[:, None]
        gates = jnp.zeros_like(probs).at[rows, ids].set(top)
        first = model["first_expert"]
        held = gates[:, first:first + model["num_experts"]]
        if model["num_experts"] < model["num_experts_routed"]:
            held = jax.lax.stop_gradient(held)
        capacity = int(CAPACITY_FACTOR * x.shape[0]
                       * model["num_experts_per_tok"]
                       / model["num_experts_routed"])
        over = jnp.cumsum(held > 0, axis=0) > capacity
        held = jnp.where((fault == FAULTS["drop"]) & over, 0.0, held)

        @jax.checkpoint
        def one(out, expert):
            w_gate, w_up, w_down, gate = expert
            h = r(jax.nn.silu(r(x @ w_gate)) * r(x @ w_up))
            return out + gate[:, None] * r(h @ w_down), None

        out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
            r(p["gate_proj"]), r(p["up_proj"]), r(p["down_proj"]), held.T))
        return r(out)

    def layer(x, p):
        h = r(x + jax.vmap(lambda s: attention(s, p["attn"]))(
            r(ref.rms_norm(x, p["attn_norm"]["scale"], eps))))
        B, S, H = h.shape
        moe = experts(r(ref.rms_norm(h, p["mlp_norm"]["scale"], eps)).reshape(
            B * S, H), p["moe"])
        return r(h + moe.reshape(B, S, H))

    (tokens,) = inputs
    with jax.default_matmul_precision("highest"):
        L = tokens.shape[1] // 2
        x = r(params["embedding"])[tokens]
        for i in range(model["num_hidden_layers"]):
            x = jax.checkpoint(layer)(x, params["layer_{}".format(i)])
        x = r(ref.rms_norm(x[:, :L], params["final_norm"]["scale"], eps))
        return x @ r(params["lm_head"])


def reading(config: dict, seq, seed: int, control: str) -> dict:
    """`checks.model_vs_reference` with ``control`` in the program's place
    (``program``: the program itself)."""
    if control == "program":
        return checks.model_vs_reference(config, seq, seed)
    bits, fault = CONTROLS[control]
    family = spec.load_module("families", config["family"])
    ref = spec.load_module("reference", config["family"])
    model = config["model"]
    knobs = {"bits": np.int32(bits), "fault": np.int32(FAULTS[fault])}

    def control_fn(p, batch):
        k, labels = batch["knobs"], batch["labels"]
        logits = forward(ref, p, batch["inputs"], model, k["bits"],
                         k["fault"])
        w = labels["weights"]
        w = jnp.where(k["fault"] == FAULTS["weights"], (w > 0) / w.size, w)
        return ref.loss_from_logits(logits, dict(labels, weights=w)), logits

    programs, batches = checks.programs, family.batches
    checks.programs = lambda c: dict(programs(c), model=control_fn)
    family.batches = lambda *a, **kw: [dict(b, knobs=knobs)
                                       for b in batches(*a, **kw)]
    try:
        return checks.model_vs_reference(config, seq, seed)
    finally:
        checks.programs, family.batches = programs, batches


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--controls", default="program,bits7,bits3,mask,"
                                          "weights,drop")
    ap.add_argument("--rehearse", action="store_true",
                    help="the configuration's tiny preset, on the CPU")
    args = ap.parse_args(argv)
    from maggy_tpu import util

    util.enable_compile_cache()
    cell = spec.load_cell(CELL)
    config, seq = cell["config"], cell["mix"]["seq"]
    if args.rehearse:
        preset = config["rehearse"]
        config = dict(config, model=dict(config["model"], **preset["model"]),
                      check=preset["check"])
        seq = cell["mix"]["rehearse"]["seq"]
    out = []
    for seed in (int(s) for s in args.seeds.split(",")):
        for control in args.controls.split(","):
            got = reading(config, seq, seed, control)
            out.append({"seed": seed, "control": control,
                        "errors": got["errors"], "ok": got["ok"],
                        "worst_grad_leaf": got["worst_grad_leaf"]})
            print(json.dumps(out[-1]), flush=True)
    path = os.path.join(spec.ROOT, "chiprun_out", "sdar_controls.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump({"tolerances": got["tolerances"], "readings": out}, f,
                  indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Controls of the ``nemotron_h`` reference check: what the check reads when
what stands in the program's place is known to be imprecise or wrong.

`checks.model_vs_reference` compares the model as the program builds it
with ``reference/nemotron_h.py``. A limit of that comparison is worth
something only if something fails it, so here the program's place is taken
by the reference's own equations with its two knobs turned
(``reference/nemotron_h.py`` `Knobs`), and the harness's own comparison is
run on them unchanged:

- ``bits``: every product's operands, every activation that the program
  keeps in its activation dtype and every cotangent that reaches them is
  rounded to that many mantissa bits (``sdar_controls.rounded``: 7 is
  bfloat16's, the configuration's; 3 float8 e4m3's, the nearest below; 23
  float32, nothing rounded). What the configuration says stays float32
  stays float32: the router, every norm's statistics, the softplus, the
  recurrence's decays and state, the attention softmax.
- ``fault``: ``no_skip`` (the ``D x`` term left out), ``no_softplus`` (dt
  without its softplus), ``conv_ahead`` (a convolution that sees one
  position ahead), ``no_shared`` (the shared expert left out), ``no_scale``
  (the gates without the routed scaling factor), ``uncausal`` (attention
  sees the future).

Both are traced values that ride in the batch, so one compiled program
serves every control. ``python3 benchmark/harness/nemotron_h_controls.py
--seeds a,b --controls program,bits7,bits3,no_skip`` prints one line a
reading and writes ``chiprun_out/nemotron_h_controls.json``; ``program`` is
the unpatched check. Faults are read at 7 bits, as a faulty program would
run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402

from benchmark.harness import checks, spec  # noqa: E402
from benchmark.harness.sdar_controls import rounded  # noqa: E402

CELL = "nemotron-3-nano-30b-a3b.ntp-steady-s8192"
#: (mantissa bits, fault) of each control by name.
CONTROLS = {
    "bits23": (23, "none"), "bits7": (7, "none"), "bits5": (5, "none"),
    "bits4": (4, "none"), "bits3": (3, "none"), "bits2": (2, "none"),
    "no_skip": (7, "no_skip"), "no_softplus": (7, "no_softplus"),
    "conv_ahead": (7, "conv_ahead"), "no_shared": (7, "no_shared"),
    "no_scale": (7, "no_scale"), "uncausal": (7, "uncausal"),
}


def reading(config: dict, seq, seed: int, control: str) -> dict:
    """`checks.model_vs_reference` with ``control`` in the program's place
    (``program``: the program itself)."""
    if control == "program":
        return checks.model_vs_reference(config, seq, seed)
    bits, fault = CONTROLS[control]
    family = spec.load_module("families", config["family"])
    ref = spec.load_module("reference", config["family"])
    model = config["model"]
    knobs = {"bits": np.int32(bits), "fault": np.int32(ref.FAULTS[fault])}

    def control_fn(p, batch):
        k = batch["knobs"]
        logits = ref.forward(p, batch["inputs"], model, ref.Knobs(
            lambda x: rounded(x, k["bits"]), k["fault"]))
        return ref.loss_from_logits(logits, batch["labels"]), logits

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
    ap.add_argument("--controls", default="program,bits7,bits3,no_skip,"
                    "no_softplus,conv_ahead,no_shared,no_scale,uncausal")
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
    path = os.path.join(spec.ROOT, "chiprun_out", "nemotron_h_controls.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump({"tolerances": got["tolerances"], "readings": out}, f,
                  indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

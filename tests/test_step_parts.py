"""The partition: through `Trainer`, at toy sizes on the CPU, every device
operation of each model family's step program is in the ``compiled``
record's ``step_ops`` once or in ``step_mixed`` once, none in both, and
what is left ``unscoped`` is what XLA made without a name (relayouts,
copies) and the few paths listed here.

The families are the benchmark's five, built as its cells build them
(``benchmark/families``) at each configuration's ``rehearse`` preset.
"""

import os
import re
import sys

import jax
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import spec  # noqa: E402
from maggy_tpu.telemetry import hlo_scopes  # noqa: E402
from maggy_tpu.telemetry.runnerstats import RunnerStats, span  # noqa: E402
from maggy_tpu.telemetry.vocab import STEP_SCOPES  # noqa: E402

#: family: (cell, scopes the step must show, the ends of the ``op_name``
#: paths that may stay ``unscoped``: what the model does between its scopes).
FAMILIES = {
    "bert": ("bert-base.steady-s512",
             {"embed", "attn", "attn/attention", "mlp", "head", "loss",
              "optimizer"}, ()),
    "vit": ("vit-base-16.rs-short",
            {"embed", "attn", "attn/attention", "mlp", "head", "loss",
             "optimizer"}, ()),
    "sdar_moe": ("sdar-30b-a3b.bd-steady-s4096",
                 {"embed", "attn", "attn/attention", "block",
                  "block/moe_routing", "block/moe_experts",
                  "block/moe_combine", "head", "loss/weighted_ce",
                  "optimizer"}, ()),
    "nemotron_h": ("nemotron-3-nano-30b-a3b.ntp-steady-s8192",
                   {"embed", "block", "block/ssm_proj", "block/ssm_conv",
                    "block/ssm_scan", "block/ssm_gate_norm",
                    "block/attn", "block/attn/attention",
                    "block/moe_routing", "block/moe_experts",
                    "block/moe_shared", "head", "loss/weighted_ce",
                    "optimizer"},
                   # The causal mask of XLA's attention, hoisted out of the
                   # model as a constant of the step.
                   ("jit(tril)/ge", "jit(tril)/add",
                    "jit(_where)/broadcast_in_dim")),
    "ouro": ("ouro-2.6b.loop4-steady-s4096",
             {"embed", "loop_attn", "loop_attn/attention", "loop_mlp",
              "exit_norm", "exit_gate", "exit_head", "loss", "optimizer"},
             # The passes' states stacked for the exits and split again for
             # their gradients, and the sums over the passes of the shared
             # stack's gradients: the model's, outside every scope it names.
             ("jvp(Ouro)/concatenate", "transpose(jvp(Ouro))/split",
              "stack/add_any", "jvp(Ouro)/broadcast_in_dim")),
}


def _rehearsal(cell_name):
    """The cell's configuration and mix with their tiny presets laid over,
    as ``benchmark/run.py --rehearse`` lays them."""
    def merged(base, override):
        out = dict(base)
        for k, v in override.items():
            out[k] = merged(out[k], v) if isinstance(v, dict) \
                and isinstance(out.get(k), dict) else v
        return out

    cell = spec.load_cell(cell_name)
    return tuple(merged(cell[part], cell[part].get("rehearse", {}))
                 for part in ("config", "mix"))


def _compiled_record_and_text(cell_name):
    """One cold trial of the cell's family through `Trainer`: the record it
    leaves and the text of the executable that ran."""
    from maggy_tpu.parallel import make_mesh
    from maggy_tpu.train import Trainer, clear_warm, swept_transform, warm

    config, mix = _rehearsal(cell_name)
    family = spec.load_module("families", config["family"])
    module, _ = family.build(config["model"])
    batches = family.batches(config["model"], mix["batch"], mix["seq"], 7)
    stats = RunnerStats()
    stats.trial_start("t1")
    clear_warm()
    with warm.trial_scope(trial_id="t1", stats=stats), \
            span("trial", stats=stats, trial_id="t1"):
        trainer = Trainer(
            module, swept_transform(optax.adamw, learning_rate=1e-3),
            family.loss, make_mesh({"data": 1}, devices=jax.devices()[:1]),
            strategy="dp")
        example, kwargs = family.init_args(batches[0])
        trainer.init(jax.random.key(0), example, init_kwargs=kwargs)
        float(trainer.step(trainer.place_batch(batches[0])))
        text = trainer._active_step.as_text()
    stats.trial_end("t1")
    clear_warm()
    return stats.snapshot_delta()["compile_events"][0], text


def _events(text):
    """Every instruction of the text that is a device event, read apart
    from the module under test: ``{name: op_name or None}`` over the
    computations that no fusion calls and no reducer or comparator is, but
    the opcodes that move nothing and the containers."""
    called = set(re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", text))
    for listed in re.findall(r"called_computations=\{([^}]*)\}", text):
        called.update(re.findall(r"%([\w.\-]+)", listed))
    called -= {c for line in text.splitlines() if " call(" in line
               for c in re.findall(r"to_apply=%([\w.\-]+)", line)}
    events, inside = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(", line)
        if head:
            inside = head.group(1)
            continue
        inst = re.match(
            r"^\s*(?:ROOT )?%([\w.\-]+) = (?:\(.*?\)|\S+) ([\w\-]+)\(", line)
        if not inst or inside in called or inst.group(2) in (
                "parameter", "constant", "tuple", "get-tuple-element",
                "bitcast", "while", "conditional", "call"):
            continue
        path = re.search(r'op_name="([^"]*)"', line)
        events[inst.group(1)] = path.group(1) if path else None
    return events


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_step_is_partitioned(family):
    cell, shown, may_stay = FAMILIES[family]
    record, text = _compiled_record_and_text(cell)
    ops, mixed = record["step_ops"], record["step_mixed"]
    named = [n for names in ops.values() for n in names]
    # Once, and in one field only.
    assert len(named) == len(set(named))
    assert not set(named) & set(mixed)
    # Every event of the text, and nothing else.
    events = _events(text)
    assert set(named) | set(mixed) == set(events)
    # A part is scopes of the closed list and a pass.
    parts = set(ops) | {p for listed in mixed.values() for p, _f, _b in listed}
    for part in parts:
        # XLA's own instruction, at work for the part that uses it.
        part = part[len(hlo_scopes.LENT):] \
            if part.startswith(hlo_scopes.LENT) else part
        scopes, way = part.rsplit(":", 1)
        assert way in ("fwd", "bwd", "remat", "update"), part
        assert scopes == hlo_scopes.UNSCOPED \
            or set(scopes.split("/")) <= set(STEP_SCOPES), part
        assert (way == "update") == (scopes == "optimizer"), part
    assert shown <= {p.rsplit(":", 1)[0] for p in parts}
    # What is lent has no path at all (XLA's own relayouts and copies);
    # what stays unscoped has none either, or one of the listed ones.
    for part, names in ops.items():
        for name in names:
            path = events[name]
            if part.startswith(hlo_scopes.LENT):
                assert path is None, (name, path)
            elif part.startswith(hlo_scopes.UNSCOPED):
                assert path is None or path.split(";")[0].endswith(may_stay), \
                    (name, path)
    stay = [n for part, names in ops.items() for n in names
            if part.startswith(hlo_scopes.UNSCOPED + ":")]
    assert len(stay) <= 0.05 * len(events), stay
    # XLA fuses a weight's gradient with its update on the CPU too.
    assert any("optimizer:update" in [p for p, _f, _b in listed]
               for listed in mixed.values())
    # A mixed fusion lists each part once, its own name's first (one that
    # XLA left without a name goes by its first user's, as above).
    for name, listed in mixed.items():
        assert len(listed) > 1
        assert len({p for p, _f, _b in listed}) == len(listed)
        assert events[name] is None or listed[0][0] == hlo_scopes.part_of(
            events[name], frozenset(STEP_SCOPES))

"""The goodput fold with a time window: the benchmark's own copy.

``maggy_tpu/telemetry/goodput.py`` ``compute_goodput`` classifies every held
runner-second of a *whole* experiment into buckets. The benchmark must cut
warm-up and drain off, so it keeps this copy of the same arithmetic with one
addition: every classified second is also *placed* on the partition's time
line, and the buckets are summed over a window ``[t0, t1]`` per partition.
With the window left out (the whole experiment) the buckets equal
``compute_goodput``'s; ``benchmark/tests`` holds the two together.

Placement inside one attempt (``running`` to its terminal event). The
journal's ``compiled`` and ``ckpt_saved`` records give durations, not times,
so the pieces are laid end to end in the order the executor and the trial
function run them: fork staging, init, checkpoint restore, trace, compile,
re-trained prefix (rework), training, checkpoint save. Training is what is
left of the attempt, so the pieces always fill it exactly.

Not copied: gang members and vectorized lane blocks, which no cell uses yet;
a journal that holds either is refused, so that a cell which needs them
brings the arithmetic with it.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

#: The program's taxonomy (``telemetry/vocab.py`` ``GOODPUT_BUCKETS``).
BUCKETS = ("train", "init", "trace", "compile", "ckpt_save", "ckpt_restore",
           "fork_stage", "rework", "handoff", "queue_wait", "idle",
           "lane_idle", "unaccounted")

#: ``telemetry/spans.py`` ``HANDOFF_CAP_S``: a gap between two trials of a
#: partition below it is hand-off, at or above it scheduling idle.
HANDOFF_CAP_S = 2.0

#: Order of the pieces before training, with the record field of each.
_BEFORE_TRAIN = (("fork_stage", "compiled", "fork_load_ms"),
                 ("init", "compiled", "init_ms"),
                 ("ckpt_restore", "ckpt", "restore_ms"),
                 ("trace", "compiled", "trace_ms"),
                 ("compile", "compiled", "compile_ms"))

Piece = Tuple[float, float, str, Optional[str]]  # start, end, bucket, trial


def _attempts(events: List[Dict[str, Any]]):
    """Pass 1 of ``compute_goodput``: registrations, the experiment's end,
    and each trial's attempts (one ``running`` to its terminal event)."""
    reg_t: Dict[int, float] = {}
    exp_end = None
    life: Dict[str, list] = {}
    assigned: Dict[str, list] = {}
    compiled: Dict[str, dict] = {}
    ckpts: Dict[str, dict] = {}
    parent_of: Dict[str, str] = {}
    forked = set()
    for seq, ev in enumerate(events):
        if ev.get("t") is None:
            continue
        t, kind, phase = float(ev["t"]), ev.get("ev"), ev.get("phase")
        if kind == "runner" and phase == "registered":
            if ev.get("partition") is not None:
                reg_t.setdefault(int(ev["partition"]), t)
        elif kind == "experiment" and phase in ("finalized", "end"):
            exp_end = t if exp_end is None else max(exp_end, t)
        elif kind == "trial" and ev.get("trial"):
            trial = ev["trial"]
            pid = ev.get("partition")
            pid = int(pid) if pid is not None else None
            if phase == "queued":
                parent = (ev.get("info") or {}).get("parent")
                if parent is not None:
                    parent_of[trial] = parent
            elif phase == "assigned":
                if ev.get("block") is not None:
                    raise NotImplementedError(
                        "the windowed fold does not split vectorized blocks")
                assigned.setdefault(trial, []).append((t, pid))
            elif phase in ("running", "finalized", "preempted", "requeued",
                           "lost"):
                life.setdefault(trial, []).append(
                    (t, seq, phase, pid, ev.get("reason")))
            elif phase == "compiled":
                compiled.setdefault(trial, ev)
            elif phase == "ckpt_saved":
                ckpts.setdefault(trial, ev)
            elif phase == "forked_from":
                forked.add(trial)
            elif phase == "gang_assembled":
                raise NotImplementedError(
                    "the windowed fold does not mirror gang members")
    last_life = max((x[0] for seq_l in life.values() for x in seq_l),
                    default=None)
    ends = [x for x in (exp_end, last_life) if x is not None]
    if not ends:
        return None
    t_end = max(ends)

    attempts, pseudo = [], []
    for trial, seq_l in life.items():
        seq_l.sort(key=lambda x: (x[0], x[1]))
        marks = sorted(assigned.get(trial, []), key=lambda m: m[0])
        open_a, n_done, last_end = None, 0, None
        for t, _seq, phase, pid, reason in seq_l:
            if phase == "running":
                if open_a is not None:  # torn journal: close at next dispatch
                    open_a.update(t1=t, status="final")
                    attempts.append(open_a)
                    last_end = t
                    open_a = None
                if pid is not None:
                    open_a = {"trial": trial, "pid": pid, "t0": t,
                              "index": n_done}
                    n_done += 1
                continue
            preserved = phase in ("finalized", "preempted") or (
                phase == "requeued" and reason == "preempted")
            if open_a is not None:
                open_a.update(t1=t, status="final" if preserved else "dead")
                attempts.append(open_a)
                open_a, last_end = None, t
            else:
                hit = None
                for ta, pa in marks:
                    if ta > t:
                        break
                    if pa is not None and (last_end is None
                                           or ta >= last_end):
                        hit = (ta, pa)
                if hit is not None:
                    pseudo.append((hit[1], hit[0], t))
                    last_end = t
        if open_a is not None:  # still running at the journal's end
            open_a.update(t1=max(t_end, open_a["t0"]), status="final")
            attempts.append(open_a)
    attempts.sort(key=lambda a: a["t0"])
    return {"reg_t": reg_t, "t_end": t_end, "attempts": attempts,
            "pseudo": pseudo, "compiled": compiled, "ckpts": ckpts,
            "scratch": set(parent_of) - forked, "parent_of": parent_of}


def _place(a: dict, t_end: float, state: dict) -> List[Piece]:
    """One attempt as pieces laid end to end over ``[t0, min(t1, t_end)]``."""
    trial = a["trial"]
    t0, t1 = a["t0"], min(a["t1"], t_end)
    dur = max(0.0, t1 - t0)
    if a["status"] == "dead":
        return [(t0, t0 + dur, "rework", trial)]
    before: List[Tuple[str, float]] = []
    save = 0.0
    if trial not in state["subs_done"]:
        state["subs_done"].add(trial)
        records = {"compiled": state["compiled"].get(trial) or {},
                   "ckpt": state["ckpts"].get(trial) or {}}
        for bucket, rec, key in _BEFORE_TRAIN:
            if records[rec].get(key):
                before.append((bucket, float(records[rec][key]) / 1e3))
        save = float(records["ckpt"].get("save_ms") or 0.0) / 1e3
    sub_total = sum(s for _b, s in before) + save
    if sub_total > dur:  # measured phases exceed the attempt: scale down
        scale = dur / sub_total if sub_total else 0.0
        before = [(b, s * scale) for b, s in before]
        save *= scale
        train = 0.0
    else:
        train = dur - sub_total
    rework = 0.0
    if trial in state["scratch"]:
        # A promotion that was served no fork re-trains its parent's prefix.
        parent = state["parent_of"][trial]
        budget = state["trial_train"].get(parent, 0.0) \
            - state["carved"].get(trial, 0.0)
        rework = min(max(0.0, budget), train)
        train -= rework
        state["carved"][trial] = state["carved"].get(trial, 0.0) + rework
    state["trial_train"][trial] = state["trial_train"].get(trial, 0.0) + train
    pieces, at = [], t0
    for bucket, seconds in before + [("rework", rework), ("train", train),
                                     ("ckpt_save", save)]:
        if seconds > 0 or bucket == "train":
            pieces.append((at, at + seconds, bucket, trial))
            at += seconds
    return pieces


def windowed_fold(events: List[Dict[str, Any]],
                  windows: Optional[Dict[int, Tuple[float, float]]] = None,
                  handoff_cap_s: float = HANDOFF_CAP_S) -> Dict[str, Any]:
    """Journal events -> chip-time buckets over ``windows``
    (``{partition: (t0, t1)}``; None = each partition's whole held time,
    registration to the experiment's end, as ``compute_goodput`` has it).

    Returns ``{}`` for a journal with no runner activity, else
    ``{"held_chip_s", "buckets", "per_partition": {pid: {"held_s",
    "buckets"}}, "timeline": {pid: [(start, end, bucket, trial), ...]}}``.
    ``sum(buckets) == held`` exactly: the residual is ``unaccounted``."""
    parsed = _attempts(events)
    if parsed is None:
        return {}
    t_end = parsed["t_end"]
    state = dict(parsed, subs_done=set(), trial_train={}, carved={})
    timeline: Dict[int, List[Piece]] = {}
    coverage: Dict[int, List[Tuple[float, float]]] = {}
    for a in parsed["attempts"]:
        timeline.setdefault(a["pid"], []).extend(_place(a, t_end, state))
        coverage.setdefault(a["pid"], []).append(
            (a["t0"], min(a["t1"], t_end)))
    for pid, ta, t1 in parsed["pseudo"]:
        t1 = min(t1, t_end)
        timeline.setdefault(pid, []).append((ta, max(ta, t1), "unaccounted",
                                             None))
        coverage.setdefault(pid, []).append((ta, t1))

    out_parts: Dict[int, Dict[str, Any]] = {}
    fleet = {b: 0.0 for b in BUCKETS}
    held_total = 0.0
    for pid in sorted(set(timeline) | set(parsed["reg_t"])):
        cov = sorted(coverage.get(pid, []))
        starts = [x for x in [parsed["reg_t"].get(pid)] + [s for s, _e in cov]
                  if x is not None]
        if not starts:
            continue
        h0 = min(starts)
        # Gaps of the whole time line, classified by their whole length.
        pieces = timeline.setdefault(pid, [])
        prev, first_gap = h0, True
        for s, e in cov:
            s, e = max(s, h0), min(e, t_end)
            if s > prev:
                gap = s - prev
                bucket = "queue_wait" if first_gap else (
                    "handoff" if gap < handoff_cap_s else "idle")
                pieces.append((prev, s, bucket, None))
            if e > prev or s > prev:
                first_gap = False
            prev = max(prev, e)
        if prev < t_end:
            pieces.append((prev, t_end, "idle", None))
        pieces.sort(key=lambda p: (p[0], p[1]))

        w0, w1 = (h0, t_end) if windows is None else windows.get(pid, (0, 0))
        w0, w1 = max(w0, h0), min(w1, t_end)
        held = max(0.0, w1 - w0)
        bk = {b: 0.0 for b in BUCKETS}
        for s, e, bucket, _trial in pieces:
            bk[bucket] += max(0.0, min(e, w1) - max(s, w0))
        bk["unaccounted"] += held - sum(bk.values())
        held_total += held
        for b, v in bk.items():
            fleet[b] += v
        out_parts[pid] = {"held_s": held, "window": (w0, w1), "buckets": bk}
    if held_total <= 0:
        return {}
    return {"held_chip_s": held_total, "buckets": fleet,
            "per_partition": out_parts, "timeline": timeline}

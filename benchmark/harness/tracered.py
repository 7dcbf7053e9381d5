"""Reduction of a `jax.profiler` trace to device busy time, the operations
that took most of it, and the longest idle gaps.

Two steps, so that the arithmetic can be checked on a small recorded trace
without a chip: `load_xplane` turns the profiler's ``.xplane.pb`` into plain
data (``benchmark/tests/fixtures`` keeps one such dump, recorded on a v5e),
and `reduce_trace` works on that.

    {"start_ns": epoch ns of the session's start, "stop_ns": ... of its stop,
     "planes": {plane name: {line name: [[event name, start_ns, dur_ns]]}}}

Event times are nanoseconds from the session's start. A device is a plane
named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per executed
HLO operation (a Pallas kernel is one such operation), ``XLA Modules`` one
per executed program, ``Async XLA Ops`` the copies that overlap them. Busy
time is the union of the ``XLA Ops`` intervals.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Callable, Dict, List, Optional

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
#: On the CPU (``--rehearse`` only) there is no device plane; the XLA:CPU
#: client's threads stand in so that the same code runs end to end.
REHEARSAL_LINE_PREFIX = ("tf_XLAPjRtCpuClient", "tf_XLAEigen")
TOP = 10


_OPCODE = re.compile(r"([a-z][a-z0-9\-]*)\(")


def short_name(text: str) -> str:
    """An operation's name for a table. On the TPU an ``XLA Ops`` event is
    named by its whole HLO instruction (kilobytes for a fusion); kept are the
    instruction's own name, its opcode, and ``tpu_custom_call`` where it is
    a Mosaic (Pallas) kernel: ``%layer_9.3 custom-call tpu_custom_call``."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text[:120]
    opcode = _OPCODE.search(rest)
    parts = [name, opcode.group(1) if opcode else "?"]
    if 'custom_call_target="tpu_custom_call"' in rest:
        parts.append("tpu_custom_call")
    return " ".join(parts)


def wanted_line(plane: str, line: str) -> bool:
    """The lines the reduction reads (a filter for `load_xplane`)."""
    return (plane.startswith(DEVICE_PLANE_PREFIX) and line == OPS_LINE) \
        or line.startswith(REHEARSAL_LINE_PREFIX)


def find_xplane(log_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load_xplane(path: str, keep: Optional[Callable[[str, str], bool]] = None
                ) -> dict:
    """The trace as plain data. ``keep(plane, line)`` filters lines."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"start_ns": None, "stop_ns": None, "planes": {}}
    for plane in data.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            out["start_ns"] = int(stats["profile_start_time"])
            out["stop_ns"] = int(stats["profile_stop_time"])
        lines = {}
        for line in plane.lines:
            if keep is not None and not keep(plane.name, line.name):
                continue
            lines[line.name] = [[short_name(e.name), float(e.start_ns),
                                 float(e.duration_ns)] for e in line.events]
        if lines:
            out["planes"][plane.name] = lines
    return out


def device_lines(trace: dict, rehearse: bool = False) -> Dict[str, list]:
    """``{device: [[name, start_ns, dur_ns], ...]}``: each device's
    executed operations."""
    out = {}
    for plane, lines in trace["planes"].items():
        if plane.startswith(DEVICE_PLANE_PREFIX) and OPS_LINE in lines:
            out[plane] = lines[OPS_LINE]
    if not out and rehearse:
        events = [e for plane, lines in trace["planes"].items()
                  for name, evs in lines.items()
                  if name.startswith(REHEARSAL_LINE_PREFIX)
                  for e in evs if e[2] > 0]
        if events:
            out["rehearsal:cpu"] = events
    return out


def busy_union(events: List[list]):
    """(busy ns, merged intervals) of possibly overlapping events."""
    merged: List[List[float]] = []
    for _name, start, dur in sorted(events, key=lambda e: e[1]):
        if dur <= 0:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    return sum(e - s for s, e in merged), merged


def reduce_trace(trace: dict, label_gap: Optional[Callable] = None,
                 rehearse: bool = False, stop_epoch_s: Optional[float] = None
                 ) -> Optional[dict]:
    """Busy seconds, the traced span, the idle share, the ``TOP`` operations
    by total time and the ``TOP`` longest idle gaps, over the devices of one
    trace (averaged, for a process that holds several).

    The span begins at the first device operation the session recorded:
    starting the profiler stalls the host for up to a second, and where the
    dispatch queue is short the device runs dry behind it, which is the
    instrument's doing and not the program's. (A session that starts in a
    truly idle phase reads that phase short by the same cut.) The span ends
    at ``stop_epoch_s``, the moment the host asked the profiler to stop: the
    session goes on recording while it serialises (a minute or two on a v5e
    host, with the step loop stalled behind it), and that tail is cut off.

    ``label_gap(t0_epoch_s, t1_epoch_s)`` names what the host was doing in a
    gap; without it a gap is named after the operation that ended it.
    Returns None when no device operation was recorded."""
    devices = device_lines(trace, rehearse)
    if not devices:
        return None
    if trace.get("start_ns") is not None:
        end_ns = float(trace["stop_ns"] - trace["start_ns"])
        if stop_epoch_s is not None:
            end_ns = min(end_ns, stop_epoch_s * 1e9 - trace["start_ns"])
    else:
        end_ns = max(s + d for evs in devices.values() for _n, s, d in evs)
    devices = {device: [[n, s, min(d, end_ns - s)] for n, s, d in events
                        if s < end_ns and d > 0]
               for device, events in devices.items()}
    starts_ns = [s for events in devices.values() for _n, s, _d in events]
    if not starts_ns:
        return None
    begin_ns = min(starts_ns)
    span_ns = end_ns - begin_ns
    busy_ns = 0.0
    by_name: Dict[str, float] = {}
    gaps = []
    for events in devices.values():
        busy, merged = busy_union(events)
        busy_ns += busy
        for name, _start, dur in events:
            by_name[name] = by_name.get(name, 0.0) + dur
        ordered = sorted(events, key=lambda e: e[1])
        starts = {e[1]: e[0] for e in reversed(ordered)}
        edges = [[begin_ns, begin_ns]] + merged + [[end_ns, end_ns]]
        for (_s0, e0), (s1, _e1) in zip(edges, edges[1:]):
            if s1 > e0:
                gaps.append((e0, s1, starts.get(s1, "end of trace")))
    n = len(devices)
    gaps.sort(key=lambda g: g[0] - g[1])
    base = (trace.get("start_ns") or 0) / 1e9
    idle_gaps = []
    for g0, g1, next_op in gaps[:TOP]:
        label = "before " + next_op
        if label_gap is not None and trace.get("start_ns") is not None:
            label = label_gap(base + g0 / 1e9, base + g1 / 1e9) or label
        idle_gaps.append([label, (g1 - g0) / 1e9])
    top_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": span_ns / 1e9,
        "idle_pct": 100.0 * (1.0 - busy_ns / n / span_ns),
        "devices": n,
        "device_ops": [[name, ns / n / 1e9] for name, ns in top_ops],
        "idle_gaps": idle_gaps,
    }


def merge_reductions(parts: List[dict]) -> Optional[dict]:
    """Reductions of several processes' traces (one pinned runner each) as
    one: busy time and span averaged over the chips, operations summed per
    chip, the longest gaps of all."""
    parts = [p for p in parts if p]
    if not parts:
        return None
    n = sum(p["devices"] for p in parts)
    busy = sum(p["busy_s"] * p["devices"] for p in parts) / n
    span = sum(p["window_s"] * p["devices"] for p in parts) / n
    ops: Dict[str, float] = {}
    for p in parts:
        for name, s in p["device_ops"]:
            ops[name] = ops.get(name, 0.0) + s * p["devices"] / n
    gaps = sorted((g for p in parts for g in p["idle_gaps"]),
                  key=lambda g: -g[1])[:TOP]
    return {"busy_s": busy, "window_s": span,
            "idle_pct": 100.0 * (1.0 - busy / span), "devices": n,
            "device_ops": sorted(([k, v] for k, v in ops.items()),
                                 key=lambda kv: -kv[1])[:TOP],
            "idle_gaps": gaps}

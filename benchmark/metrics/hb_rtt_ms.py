"""Control plane: median heartbeat round trip the runners reported
(``runner_stats`` events) inside the window."""

from benchmark.harness.window import median


def read(w):
    t0 = min(r["t0"] for r in w.runners.values())
    t1 = max(r["t1"] for r in w.runners.values())
    return median([e["hb_rtt_ms"] for e in w.events
                   if e.get("ev") == "runner_stats"
                   and e.get("hb_rtt_ms") is not None and t0 <= e["t"] <= t1])

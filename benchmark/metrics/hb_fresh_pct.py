"""Control plane: of the heartbeats the runners sent inside the window
while a trial ran, the share that carried a (metric, step) newer than the
last one shipped (``hb_fresh`` / ``hb_beats``, cumulative counters in the
``runner_stats`` deltas). The full report carries the beats counted and the
median ``metric_lag_steps``."""

from benchmark.harness import annotated
from benchmark.harness.window import median


def read(w):
    beats = fresh = 0
    lags = []
    for partition, r in w.runners.items():
        at = {"hb_beats": [0, 0], "hb_fresh": [0, 0]}  # at t0, at t1
        for e in w.events:
            if e.get("ev") != "runner_stats" \
                    or e.get("partition") != partition or e["t"] > r["t1"]:
                continue
            for key, pair in at.items():
                if e.get(key) is not None:
                    pair[1] = e[key]
                    if e["t"] < r["t0"]:
                        pair[0] = e[key]
            if e["t"] >= r["t0"] and e.get("metric_lag_steps") is not None:
                lags.append(e["metric_lag_steps"])
        beats += at["hb_beats"][1] - at["hb_beats"][0]
        fresh += at["hb_fresh"][1] - at["hb_fresh"][0]
    annotated.note(w, "heartbeats", {"beats": beats, "fresh": fresh,
                                     "median_lag_steps": median(lags)})
    return 100.0 * fresh / beats if beats else None

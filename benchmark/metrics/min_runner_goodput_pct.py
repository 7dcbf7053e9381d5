"""Pools: the ``train`` share of the runner that had the least of it."""


def read(w):
    shares = [100.0 * p["buckets"]["train"] / p["held_s"]
              for p in w.fold["per_partition"].values() if p["held_s"] > 0]
    return min(shares) if shares else None

"""Framework-wide constants.

Parity: reference `maggy/constants.py:23-28` (allowed user-function return
types and numeric types). Extended with TPU-framework defaults.
"""

from __future__ import annotations

import os

import numpy as np


class USER_FCT:
    """Allowed return types of a user training function."""

    RETURN_TYPES = (float, int, np.number, dict)
    NUMERIC_TYPES = (float, int, np.number)


# Control-plane defaults (see BASELINE.md "scheduling constants").
DEFAULT_HEARTBEAT_INTERVAL_S = 1.0
DRIVER_IDLE_REQUEUE_TICK_S = 0.1
# First GET retry after a miss; doubles up to DRIVER_IDLE_REQUEUE_TICK_S.
CLIENT_GET_POLL_MIN_S = 0.005
# DIST_CONFIG rendezvous poll cap: same fast-start doubling as GET (from
# CLIENT_GET_POLL_MIN_S), backing off to this once the wait is clearly a
# still-registering world rather than a race.
CLIENT_DIST_CONFIG_POLL_MAX_S = 0.5
CLIENT_POLL_INTERVAL_S = 1.0
# Pipelined hand-off (config.prefetch): how long the FINAL fast path may
# wait for the driver's schedule lock before falling back to the worker
# queue (reply OK, runner GET-polls). The lock is only ever contended
# while the suggester thread is mid-model-fit, so this bounds the RPC
# event loop's worst-case stall per FINAL.
PREFETCH_FINAL_LOCK_TIMEOUT_S = 0.05
REGISTRATION_TIMEOUT_S = 600.0
# Checkpoint-forking search (config.fork): how long a forked trial may be
# held for the runner that ran its parent (parent affinity — warm slot +
# locally staged checkpoint) before ANY idle runner takes it. A few idle
# ticks: affinity is a preference, never a scheduling stall.
FORK_AFFINITY_HOLD_S = float(os.environ.get(
    "MAGGY_TPU_FORK_AFFINITY_HOLD_S", "0.5"))
# Bound between an elastic RESIZE request and the respawned runner's
# REGISTER. A respawn that hangs before registering (e.g. in backend
# init, while another process still holds its chips) never heartbeats, so
# heartbeat-loss detection cannot see it — this is its liveness bound.
RESIZE_RESPAWN_TIMEOUT_S = 120.0
RENDEZVOUS_TIMEOUT_S = 60.0
# Request retry budget. Env-overridable (MAGGY_TPU_CLIENT_MAX_RETRIES)
# because the right value depends on how long a DEAD CONTROL PLANE may
# stay dead: the default ~0.5 s horizon suits transient blips, while
# crash-only driver failover (the runner must outlive the driver's
# restart — process spawn + jax import + journal replay, seconds to tens
# of seconds) needs runners that keep retrying across the window; the
# driver soak raises it for its runner-agent processes.
CLIENT_MAX_RETRIES = int(os.environ.get("MAGGY_TPU_CLIENT_MAX_RETRIES",
                                        "3"))
# Client retry backoff: exponential from BASE doubling to CAP, with full
# jitter (a fixed cadence synchronizes every client's retry storm onto a
# recovering server).
CLIENT_RETRY_BACKOFF_BASE_S = 0.05
CLIENT_RETRY_BACKOFF_CAP_S = 2.0
RPC_RECV_BUFSIZE = 1 << 16
# Heartbeat batching: beats whose ship failed are kept client-side
# (coalesced per trial, rstats stripped — the rstats delta requeues into
# the runner-stats buffer separately) and shipped together as ONE BATCH
# frame on the next beat. The bounds cap memory on a long driver outage
# — beat COUNT and coalesced LOG LINES per banked beat; beyond them the
# oldest entries are dropped, which matches the pre-batching behavior
# (a failed beat's payload was simply lost).
CLIENT_MAX_PENDING_BEATS = 16
CLIENT_MAX_PENDING_LOG_LINES = 500
# Shared-fleet control plane (rpc.SharedServer): bounded per-tenant
# dispatch queue depth. A tenant whose handlers fall behind fills its own
# queue; further frames for THAT tenant are dropped with the connection
# (the client's retry/backoff path re-delivers), which is the per-tenant
# backpressure signal — other tenants' queues are unaffected.
TENANT_DISPATCH_QUEUE_DEPTH = 512

# Failure detection: a runner whose assigned trial has gone this many
# heartbeat intervals without any message is declared lost and its trial is
# requeued to another runner (floor guards against sub-second hb_interval
# settings declaring a compiling trial dead). Defaults for the
# ``hb_loss_factor`` / ``hb_loss_min_s`` config fields — override THOSE
# (e.g. chaos soaks tightening failure detection), not these globals.
HEARTBEAT_LOSS_FACTOR = 30.0
HEARTBEAT_LOSS_MIN_S = 10.0

# Multi-fidelity bracket-state checkpoint (resume=True with Hyperband).
PRUNER_STATE_FILE = ".pruner_state.json"

# Early-stop defaults (reference `maggy/experiment_config.py:33-35`).
DEFAULT_ES_INTERVAL = 1
DEFAULT_ES_MIN = 10
DEFAULT_ES_POLICY = "median"

"""DCN control plane: driver <-> trial-runner RPC.

Parity: reference `maggy/core/rpc.py` — message vocabulary
REG/QUERY/METRIC/FINAL/GET/LOG (+DIST_CONFIG replacing TORCH_CONFIG) with
replies OK/ERR/STOP/GSTOP/TRIAL (:295-437); `Reservations` barrier registry
(:35-113); length-prefixed wire protocol (:116-162); select-loop server in a
daemon thread with per-message shared-secret auth (:250-286); client with a
dedicated heartbeat socket, reconnect retries, and blocking suggestion polls
(:440-593); re-registration failure detection queueing BLACK (:308-326).

Deliberate redesigns (SURVEY.md §2.3 "TPU-native equivalent"):

- **msgpack, not cloudpickle**: the reference unpickles network input
  (`rpc.py:24,146,160`) — arbitrary code execution from any process that
  knows the port. Here every frame is a fixed-schema msgpack map; trial
  params are declarative data, never callables.
- **per-message HMAC** instead of plaintext secret comparison: the secret
  never travels on the wire after registration.
- The gradient plane is NOT here: that is `jax.distributed` + XLA collectives
  over ICI. This layer only brokers the coordinator rendezvous (DIST_CONFIG)
  the way the reference brokers MASTER_ADDR/PORT (`rpc.py:409-416`).
"""

from __future__ import annotations

import hashlib
import hmac
import queue
import secrets as pysecrets
import selectors
import socket
import struct
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import msgpack

from maggy_tpu import constants
from maggy_tpu.chaos.injectors import ChaosKilled
from maggy_tpu.chaos.injectors import active_engine as chaos_engine
from maggy_tpu.exceptions import AuthenticationError
from maggy_tpu.telemetry.metrics import MetricsRegistry
from maggy_tpu.trial import Trial

_LEN = struct.Struct(">I")
MAX_FRAME = 64 * 1024 * 1024

#: Process-wide client-side RPC metrics (retries/reconnects). Module-level
#: because clients outlive no experiment and may run in runner processes
#: with no driver telemetry; in-process pools share it with the driver, so
#: chaos soaks can assert the retry paths actually ran.
CLIENT_METRICS = MetricsRegistry()

# Sentinel trial id returned by Client.get_suggestion when the driver asks
# this runner to exit and respawn pinned to a different chip count.
RESIZE = "__resize__"


# --------------------------------------------------------------------- wire


def _sign(secret: bytes, payload: bytes) -> bytes:
    from maggy_tpu import native

    return native.hmac_sha256(secret, payload)


class MessageSocket:
    """Framed transport: 4-byte big-endian length || 32-byte HMAC || msgpack."""

    @staticmethod
    def send_msg(sock: socket.socket, msg: Dict[str, Any], secret: bytes) -> None:
        payload = msgpack.packb(msg, use_bin_type=True)
        if len(payload) > MAX_FRAME:
            raise ValueError("Frame too large: {} bytes".format(len(payload)))
        mac = _sign(secret, payload)
        sock.sendall(_LEN.pack(len(payload)) + mac + payload)

    @staticmethod
    def recv_msg(sock: socket.socket, secret: bytes) -> Dict[str, Any]:
        header = MessageSocket._recv_exact(sock, 4 + 32)
        (length,) = _LEN.unpack(header[:4])
        if length > MAX_FRAME:
            raise AuthenticationError("Oversized frame.")
        mac = header[4:]
        payload = MessageSocket._recv_exact(sock, length)
        if not hmac.compare_digest(mac, _sign(secret, payload)):
            raise AuthenticationError("Bad message HMAC.")
        return msgpack.unpackb(payload, raw=False, strict_map_key=False)

    @staticmethod
    def _recv_exact(sock: socket.socket, n: int) -> bytes:
        buf = bytearray()
        while len(buf) < n:
            chunk = sock.recv(min(constants.RPC_RECV_BUFSIZE, n - len(buf)))
            if not chunk:
                raise ConnectionError("Socket closed mid-frame.")
            buf.extend(chunk)
        return bytes(buf)


# -------------------------------------------------------------- reservations


class Reservations:
    """Thread-safe registry partition_id -> executor record, with barrier
    semantics (reference `rpc.py:35-113`)."""

    def __init__(self, required: int):
        self.required = required
        self.lock = threading.RLock()
        self._table: Dict[int, Dict[str, Any]] = {}  # guarded-by: lock
        # Evictions requested before the partition registered (fleet
        # preemption racing a fresh lease's REG): applied at add() so the
        # release is delivered instead of silently lost.
        self._pending_evict: set = set()  # guarded-by: lock

    def add(self, meta: Dict[str, Any]) -> None:
        with self.lock:
            rec = dict(meta)
            rec["last_beat"] = time.monotonic()
            pid = int(meta["partition_id"])
            if pid in self._pending_evict:
                self._pending_evict.discard(pid)
                rec["evict"] = True
            self._table[pid] = rec

    def touch(self, partition_id) -> None:
        """Record liveness: any message from the runner counts as a beat.
        A chaos mute window (see ``age_beat``) suppresses the update."""
        with self.lock:
            rec = self._table.get(int(partition_id))
            if rec is not None and \
                    rec.get("mute_until", 0.0) <= time.monotonic():
                rec["last_beat"] = time.monotonic()

    def age_beat(self, partition_id, age_s: float,
                 mute_s: float = 0.0) -> None:
        """Fault-injection support (maggy_tpu.chaos ``fake_preemption``):
        push the partition's last_beat ``age_s`` into the past and ignore
        fresh beats for ``mute_s`` seconds, so the heartbeat-loss scan
        sees a silent runner while the runner itself stays alive — the
        falsely-declared-lost race, injected on demand."""
        with self.lock:
            rec = self._table.get(int(partition_id))
            if rec is not None:
                now = time.monotonic()
                rec["last_beat"] = min(rec.get("last_beat", now),
                                       now - age_s)
                if mute_s > 0:
                    rec["mute_until"] = now + mute_s

    # locked-by: lock
    def _silent_locked(self, timeout: float):
        now = time.monotonic()
        return [
            pid for pid, rec in self._table.items()
            if not rec.get("released")
            and now - rec.get("last_beat", now) > timeout
        ]

    def silent(self, timeout: float):
        """Registered, unreleased partitions silent for longer than
        ``timeout`` — regardless of trial assignment (distributed workers
        hold no trials but must heartbeat for their whole run)."""
        with self.lock:
            return self._silent_locked(timeout)

    def is_silent(self, partition_id, timeout: float) -> bool:
        """Single-partition form of `silent`: registered, unreleased, and
        beat-less for longer than ``timeout``. The ONE home of the
        last_beat liveness predicate — JOIN admission and the driver's
        dead-partition checks both consult it."""
        with self.lock:
            rec = self._table.get(int(partition_id))
            if rec is None or rec.get("released"):
                return False
            return time.monotonic() - rec.get("last_beat", 0) > timeout

    def lost_assignments(self, timeout: float):
        """Silent partitions that hold a trial: [(partition_id, trial_id)].
        Read-only; the caller decides recovery."""
        with self.lock:
            return [
                (pid, self._table[pid]["trial_id"])
                for pid in self._silent_locked(timeout)
                if self._table[pid].get("trial_id") is not None
            ]

    def get(self, partition_id: int) -> Optional[Dict[str, Any]]:
        with self.lock:
            rec = self._table.get(int(partition_id))
            return dict(rec) if rec else None

    def capacity(self, partition_id: int) -> Optional[int]:
        """The runner's advertised chip capacity (None = not elastic)."""
        with self.lock:
            rec = self._table.get(int(partition_id))
            return rec.get("capacity") if rec else None

    def live_count(self) -> int:
        """Registered, unreleased partitions — the prefetch pipeline's
        queue bound (one pre-materialized suggestion per live runner)."""
        with self.lock:
            return sum(1 for rec in self._table.values()
                       if not rec.get("released"))

    def capacities(self) -> Dict[int, int]:
        """Count of live (registered, unreleased) runners by capacity."""
        with self.lock:
            out: Dict[int, int] = {}
            for rec in self._table.values():
                cap = rec.get("capacity")
                if cap is not None and not rec.get("released"):
                    out[cap] = out.get(cap, 0) + 1
            return out

    def request_resize(self, partition_id: int, chips: int) -> None:
        """Ask a runner to exit and respawn pinned to ``chips`` chips (the
        elastic pool does the respawn). Delivered on its next GET."""
        with self.lock:
            rec = self._table.get(int(partition_id))
            if rec is not None:
                rec["resize"] = int(chips)

    def pop_resize(self, partition_id: int) -> Optional[int]:
        with self.lock:
            rec = self._table.get(int(partition_id))
            if rec is None:
                return None
            return rec.pop("resize", None)

    def done(self) -> bool:
        with self.lock:
            return len(self._table) >= self.required

    def remaining(self) -> int:
        with self.lock:
            return max(0, self.required - len(self._table))

    def assign_trial(self, partition_id: int, trial_id: Optional[str]) -> None:
        with self.lock:
            if int(partition_id) in self._table:
                self._table[int(partition_id)]["trial_id"] = trial_id

    def clear_trial_if(self, partition_id: int,
                       trial_id: Optional[str]) -> None:
        """Clear the partition's assignment ONLY if it still names
        ``trial_id``. The FINAL handler must use this, not a blind
        assign_trial(None): under at-least-once delivery (reply lost,
        client retries) the retried FINAL arrives AFTER the driver has
        already assigned the partition its NEXT trial, and a blind wipe
        strands that trial in the store forever — the experiment never
        completes. Found by the chaos harness's sever_conn fault."""
        with self.lock:
            rec = self._table.get(int(partition_id))
            if rec is not None and rec.get("trial_id") == trial_id:
                rec["trial_id"] = None

    def mark_released(self, partition_id) -> None:
        """The runner has been told GSTOP — it will send nothing more."""
        with self.lock:
            rec = self._table.get(int(partition_id))
            if rec is not None:
                rec["released"] = True

    # ------------------------------------------------------- crash recovery

    def restore(self, partition_id, trial_id: Optional[str] = None,
                capacity: Optional[int] = None,
                host_port: Optional[str] = None) -> None:
        """Crash-only recovery: re-seed a pre-crash partition's record
        from the replayed journal. The record starts with a FRESH
        last_beat — every recovered partition gets exactly one liveness
        window to prove itself: a still-live runner's next heartbeat /
        retried FINAL re-binds it (``pop_recovered`` journals the
        ``adopted`` edge), a dead one goes silent past the loss bound and
        the ORDINARY slot-reclaim scan requeues its trial — recovery adds
        no second requeue path. Never overwrites a live registration."""
        with self.lock:
            pid = int(partition_id)
            if pid in self._table:
                return
            self._table[pid] = {
                "partition_id": pid, "trial_id": trial_id,
                "capacity": capacity, "host_port": host_port,
                "task_attempt": 0, "recovered": True,
                "last_beat": time.monotonic(),
            }

    def pop_recovered(self, partition_id) -> bool:
        """Consume the partition's recovered flag: True exactly once, on
        the first post-recovery message — the caller journals the
        ``adopted`` runner edge on it."""
        with self.lock:
            rec = self._table.get(int(partition_id))
            if rec is not None and rec.get("recovered"):
                rec.pop("recovered", None)
                return True
            return False

    # ------------------------------------------------------------ gang holds

    def hold_for_gang(self, partition_id, trial_id: str) -> None:
        """Conscript the runner into a gang: while held it is not free —
        the driver hands it no 1-chip work — but it keeps heartbeating
        and idle-polling; its chip belongs to ``trial_id``'s mesh slice
        until the gang releases."""
        with self.lock:
            rec = self._table.get(int(partition_id))
            if rec is not None:
                rec["gang"] = trial_id

    def gang_of(self, partition_id) -> Optional[str]:
        with self.lock:
            rec = self._table.get(int(partition_id))
            return rec.get("gang") if rec else None

    def release_gang(self, trial_id: str) -> list:
        """Free every member held for ``trial_id``; returns their pids so
        the driver can restart their work loops."""
        with self.lock:
            freed = []
            for pid, rec in self._table.items():
                if rec.get("gang") == trial_id:
                    rec.pop("gang", None)
                    rec.pop("gang_served", None)
                    freed.append(pid)
            return freed

    def mark_gang_served(self, partition_id, trial_id: str) -> bool:
        """One-shot delivery latch for a REMOTE gang's member program:
        True the first time this held member is served ``trial_id``'s
        member assignment, False on every retry/re-poll — the member
        runs the SPMD program exactly once per assembly (the latch
        clears with the hold in ``release_gang``, so a revoked-and-
        reassembled gang serves its members again)."""
        with self.lock:
            rec = self._table.get(int(partition_id))
            if rec is None or rec.get("gang") != trial_id:
                return False
            if rec.get("gang_served") == trial_id:
                return False
            rec["gang_served"] = trial_id
            return True

    def gang_members(self, trial_id: str) -> list:
        with self.lock:
            return sorted(pid for pid, rec in self._table.items()
                          if rec.get("gang") == trial_id)

    def free_pids(self) -> list:
        """Runners available for new work: registered, unreleased, not
        evicted, holding no trial and conscripted into no gang. The gang
        assembler's free set."""
        with self.lock:
            return sorted(
                pid for pid, rec in self._table.items()
                if not rec.get("released") and not rec.get("evict")
                and rec.get("trial_id") is None and rec.get("gang") is None)

    def request_stop(self, partition_id, trial_id: str) -> None:
        """Gang revocation: arm a one-shot preempt-STOP for the
        partition's next heartbeat about ``trial_id``. Used to abort a
        HEALTHY gang leader whose gang lost a member — the trial is
        already requeued, so the leader's preempt ack is dropped by the
        driver's idempotent preemption path and the runner returns to
        the pool. Reservation-level (not a trial flag) so the abort
        cannot be mistaken for a schedulable preemption."""
        with self.lock:
            rec = self._table.get(int(partition_id))
            if rec is not None:
                rec["stop_trial"] = trial_id

    def pop_stop(self, partition_id, trial_id) -> bool:
        """Consume an armed revocation STOP if it names ``trial_id``."""
        with self.lock:
            rec = self._table.get(int(partition_id))
            if rec is not None and trial_id is not None \
                    and rec.get("stop_trial") == trial_id:
                rec.pop("stop_trial", None)
                return True
            return False

    def request_evict(self, partition_id) -> bool:
        """Fleet preemption: ask that this partition's runner be released
        from the experiment (GSTOP) at its next reply opportunity — after
        its preempted FINAL lands, or on its next GET when idle. Cleared
        naturally when a future runner re-registers the slot (``add``
        builds a fresh record). An unknown partition's eviction is parked
        and applied at its registration — a fleet preemption may race the
        fresh lease's REG, and the release must not be silently lost."""
        with self.lock:
            rec = self._table.get(int(partition_id))
            if rec is None:
                self._pending_evict.add(int(partition_id))
                return True
            rec["evict"] = True
            return True

    def evict_requested(self, partition_id) -> bool:
        with self.lock:
            rec = self._table.get(int(partition_id))
            return bool(rec and rec.get("evict"))

    def all_released(self) -> bool:
        with self.lock:
            return all(rec.get("released") for rec in self._table.values())

    def get_assigned_trial(self, partition_id: int) -> Optional[str]:
        with self.lock:
            rec = self._table.get(int(partition_id))
            return rec.get("trial_id") if rec else None

    def all(self) -> Dict[int, Dict[str, Any]]:
        with self.lock:
            return {k: dict(v) for k, v in self._table.items()}


# --------------------------------------------------------------------- server


class Server:
    """Event-loop RPC server running in a daemon thread.

    The driver registers message callbacks keyed by type; unknown types get
    an ERR reply (reference `rpc.py:207-233,250-286`).
    """

    def __init__(self, num_executors: int, secret: Optional[str] = None):
        self.num_executors = num_executors
        # Telemetry facade (maggy_tpu.telemetry.Telemetry), attached by the
        # driver. None = no TELEM verb, no verb timing. Handlers must treat
        # it as optional: the server also runs driverless in tests.
        self.telemetry = None
        # One-shot flag so a broken periodic_check hook logs ONCE instead of
        # spamming (or silently dying) on every event-loop tick.
        self._periodic_check_failed = False
        self.secret_hex = secret or pysecrets.token_hex(16)
        self.secret = self.secret_hex.encode()
        self.reservations = Reservations(num_executors)
        # Remote-runner admission: the driver publishes the executor config
        # here when runners are external agents; None rejects JOINs.
        self.join_info: Optional[Dict[str, Any]] = None
        self._join_lock = threading.Lock()
        # pid -> monotonic issue time. A slot is "taken" while its JOIN is
        # fresher than the liveness bound or its holder has registered; an
        # issued-but-never-registered slot expires and becomes reclaimable
        # (the joining agent died before REG).
        self._issued_pids: Dict[int, float] = {}  # guarded-by: _join_lock
        # Heartbeat-liveness bound used by JOIN slot-reclaim checks (and, in
        # OptimizationServer, the loss scan). None disables.
        self.hb_loss_timeout: Optional[float] = None
        self._buffers: Dict[socket.socket, bytearray] = {}
        self._sel = selectors.DefaultSelector()
        self._listener: Optional[socket.socket] = None
        self._thread: Optional[threading.Thread] = None
        self._stop_event = threading.Event()
        # Set when this server is published on a fleet SharedServer
        # instead of its own listener: frames arrive through the shared
        # event loop (routed by which experiment secret authenticates
        # them) and stop() detaches rather than tearing a socket down.
        self._shared: Optional["SharedServer"] = None
        self._handlers: Dict[str, Callable[[Dict[str, Any]], Dict[str, Any]]] = {}
        self._register_handlers()

    # subclasses override
    def _register_handlers(self) -> None:
        self._handlers["QUERY"] = lambda msg: {
            "type": "QUERY",
            "done": self.reservations.done(),
        }
        self._handlers["JOIN"] = self._join
        # rpc-ok: TELEM produced by monitor --telem via a generic send_msg
        self._handlers["TELEM"] = self._telem

    def _telem(self, msg):
        """Telemetry snapshot: live metric registry + span-derived
        scheduling numbers. Same auth as every verb (per-message HMAC —
        an unauthenticated peer never reaches this handler); consumed by
        ``maggy_tpu.monitor --telem`` from any machine that can reach the
        control plane."""
        telem = self.telemetry
        if telem is None:
            return {"type": "ERR",
                    "error": "telemetry is not enabled for this experiment"}
        return {"type": "TELEM", **telem.snapshot()}

    def _join(self, msg):
        """Admit a remote runner agent: assign it a partition id and ship
        the executor config (exp_dir, hb_interval, ...). The DCN analogue of
        Spark handing a partition to an executor — but pull, not push: agents
        on other hosts dial in with the shared secret."""
        info = self.join_info
        if info is None:
            return {"type": "ERR",
                    "error": "this experiment does not accept remote runners"}
        want = msg.get("partition_id")
        liveness = self.hb_loss_timeout or 10.0
        now = time.monotonic()
        with self._join_lock:
            if want is not None and int(want) >= 0:
                # Explicit pid: a restarted agent resuming its slot (its REG
                # will take the re-registration BLACK path). Refuse slots
                # outside the experiment, slots whose holder is still alive,
                # AND slots issued to a not-yet-registered joiner — two
                # agents sharing a pid would interleave GET/FINAL and corrupt
                # trial bookkeeping (the adjacent-JOIN race: both JOIN before
                # either REGs).
                pid = int(want)
                if pid >= self.num_executors:
                    return {"type": "ERR",
                            "error": "partition_id {} out of range (experiment "
                                     "has {} slots)".format(pid, self.num_executors)}
                rec = self.reservations.get(pid)
                if rec is not None and not rec.get("released") and \
                        not self.reservations.is_silent(pid, liveness):
                    return {"type": "ERR",
                            "error": "slot {} is held by a live runner".format(pid)}
                # A fresh issue means another agent just took this slot (it
                # may not have REG'd yet) — checked on every path, stale or
                # released record included, or two replacements racing for
                # the same dead/released slot would both be admitted.
                issued = self._issued_pids.get(pid)
                if issued is not None and now - issued < liveness:
                    return {"type": "ERR",
                            "error": "slot {} was just issued to another "
                                     "joining runner".format(pid)}
                self._issued_pids[pid] = now
            else:
                registered = self.reservations.all()
                taken = set(registered) | {
                    p for p, t in self._issued_pids.items()
                    if now - t < liveness
                }
                pid = next((i for i in range(self.num_executors)
                            if i not in taken), None)
                if pid is None:
                    return {"type": "ERR", "error": "experiment full"}
                self._issued_pids[pid] = now
        return {"type": "JOIN", "partition_id": pid, **info}

    def start(self, host: str = "127.0.0.1", port: int = 0) -> Tuple[str, int]:
        # Warm the native codec BEFORE the event loop exists: the lazy g++
        # build (up to ~minutes on a loaded host) must not run inside the
        # single server thread while registrations queue up.
        from maggy_tpu import native

        native.get_lib()
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind((host, port))
        srv.listen(128)
        srv.setblocking(False)
        self._listener = srv
        self._sel.register(srv, selectors.EVENT_READ, self._accept)
        self._thread = threading.Thread(target=self._loop, daemon=True, name="rpc-server")
        self._thread.start()
        return srv.getsockname()

    def _accept(self, sock, mask):
        conn, _ = sock.accept()
        # Non-blocking with a per-connection reassembly buffer: a stalled or
        # half-dead client must never freeze the event loop (runner crashes
        # mid-send are exactly what this layer detects).
        conn.setblocking(False)
        self._buffers[conn] = bytearray()
        self._sel.register(conn, selectors.EVENT_READ, self._serve)

    def _serve(self, conn, mask):
        try:
            chunk = conn.recv(constants.RPC_RECV_BUFSIZE)
        except (BlockingIOError, InterruptedError):
            return
        except OSError:
            self._drop(conn)
            return
        if not chunk:
            self._drop(conn)
            return
        buf = self._buffers[conn]
        buf.extend(chunk)
        # Stop at the first drop: a dispatch may sever the connection
        # (chaos sever, send failure) while MORE complete frames sit in
        # the local buffer — processing them would reply into a closed
        # socket and, in the shared-server subclass, resurrect the
        # connection's routing entry (the sever-mid-frame leak).
        while conn in self._buffers:
            frame = self._try_extract_frame(conn, buf)
            if frame is None:
                return
            self._dispatch(conn, frame)

    def _try_extract_frame(self, conn, buf: bytearray):
        """Pop one complete authenticated frame from the buffer, or None.

        Scanning + HMAC verification run in the native codec
        (native/framing.cpp) when built; -1/-2 results (oversized frame /
        MAC mismatch) drop the connection."""
        from maggy_tpu import native

        result = native.frame_scan(buf, self.secret, MAX_FRAME)
        if result == 0:
            return None
        if result < 0:
            self._drop(conn)
            return None
        header = 4 + 32
        payload = bytes(buf[header:result])
        del buf[:result]
        return payload

    def _dispatch(self, conn, payload: bytes):
        sever_reply = False
        try:
            msg = msgpack.unpackb(payload, raw=False, strict_map_key=False)
            engine = chaos_engine()
            if engine is not None:
                action = engine.on_server_message(msg)
                if action is not None:
                    if action[0] == "drop":
                        # Message lost + connection reset: the client's
                        # retry/reconnect path re-delivers.
                        self._drop(conn)
                        return
                    if action[0] == "delay":
                        # Deliberately ON the event loop: a stalled
                        # control plane stalls every client, which is the
                        # fault being simulated.
                        time.sleep(action[1])
                    elif action[0] == "sever":
                        # Handle, then cut the connection INSTEAD of
                        # replying — the client retries and the handler
                        # runs twice (at-least-once delivery).
                        sever_reply = True
            resp = self.handle_message(msg)
        except (ConnectionError, socket.timeout, OSError):
            self._drop(conn)
            return
        except Exception as e:  # noqa: BLE001 - a bad message must never kill the loop
            resp = {"type": "ERR", "error": "handler error: {!r}".format(e)}
        if sever_reply:
            self._drop(conn)
            return
        try:
            conn.setblocking(True)
            MessageSocket.send_msg(conn, resp, self.secret)
        except OSError:
            self._drop(conn)
        finally:
            try:
                conn.setblocking(False)
            except OSError:
                pass

    def handle_message(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        """Handler lookup + per-verb service-time timing — the transport-
        free core of a dispatch, shared by this server's own event loop
        and a fleet ``SharedServer`` routing frames to it. Timing is
        recorded even when the handler raises: every registered verb MUST
        show up as an rpc.handle_ms.<verb> histogram after one dispatch
        (the conformance test pins it). Buffer-only recording (telemetry
        journals never write on this thread), so event loops stay
        I/O-free."""
        handler = self._handlers.get(msg.get("type"))
        if handler is None:
            return {"type": "ERR", "error": "unknown message type"}
        t0 = time.monotonic()
        try:
            return handler(msg)
        finally:
            telem = self.telemetry
            if telem is not None:
                telem.observe_ms(
                    "rpc.handle_ms.{}".format(msg.get("type")),
                    (time.monotonic() - t0) * 1e3)

    def _batch(self, msg):
        """Coalesced heartbeat batch: a client whose beats failed to ship
        (driver stall, reconnect storm) re-delivers them as ONE frame —
        ``beats`` is an oldest-first list of METRIC payloads, coalesced
        client-side per trial. Each beat runs through the ordinary METRIC
        handler (so liveness touches, rstats merges, and driver metric
        history all land), and the reply is the NEWEST beat's reply — a
        STOP/preempt decision about a retired beat's trial is stale by
        definition, and heartbeats re-draw STOP until honored anyway."""
        metric = self._handlers.get("METRIC")
        if metric is None:
            return {"type": "ERR",
                    "error": "this server does not accept heartbeats"}
        reply: Dict[str, Any] = {"type": "OK"}
        for beat in msg.get("beats") or []:
            b = dict(beat)
            b["type"] = "METRIC"
            b["partition_id"] = msg["partition_id"]
            b["task_attempt"] = msg.get("task_attempt")
            reply = metric(b)
        return reply

    def _drop(self, conn):
        self._buffers.pop(conn, None)
        try:
            self._sel.unregister(conn)
        except (KeyError, ValueError):
            pass
        try:
            conn.close()
        except OSError:
            pass

    def _loop(self):
        while not self._stop_event.is_set():
            events = self._sel.select(timeout=0.2)
            for key, mask in events:
                key.data(key.fileobj, mask)
            self._tick()
            engine = chaos_engine()
            if engine is not None:
                # Elapsed-time fault triggers ride the event-loop tick —
                # the same cadence the heartbeat-loss scan runs on.
                engine.tick()

    def _tick(self) -> None:
        """Periodic hook run on the event-loop thread between selects."""

    def await_reservations(
        self, timeout: float = constants.REGISTRATION_TIMEOUT_S,
        on_timeout: Optional[Callable[[], None]] = None,
    ) -> Dict[int, Dict[str, Any]]:
        """Driver-side registration barrier (reference `rpc.py:182-205`)."""
        deadline = time.monotonic() + timeout
        while not self.reservations.done():
            if time.monotonic() > deadline:
                if on_timeout:
                    on_timeout()
                raise TimeoutError(
                    "Registration barrier timed out: {} of {} executors missing.".format(
                        self.reservations.remaining(), self.num_executors
                    )
                )
            time.sleep(0.1)
        return self.reservations.all()

    def stop(self):
        if self._shared is not None:
            # Published on a fleet's shared listener: detach this
            # experiment's routing; the shared socket outlives it. The
            # OWN selector was allocated in __init__ but never used —
            # close it or a long-lived fleet host leaks one epoll fd per
            # submitted experiment.
            self._shared.detach(self)
            self._shared = None
            try:
                self._sel.close()
            except OSError:
                pass
            return
        self._stop_event.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
        for key in list(self._sel.get_map().values()):
            self._drop(key.fileobj)
        self._sel.close()


class _TenantDispatcher:
    """Bounded per-tenant handler pool: one daemon worker draining one
    FIFO queue of (conn, payload) frames for ONE attached experiment.
    A single worker per tenant keeps the ordering guarantee a dedicated
    listener gave — frames from one connection are handled and replied
    in arrival order — while isolating the tenant's handler latency from
    every other tenant. ``submit`` never blocks: a full queue returns
    False and the caller sheds the frame (per-tenant backpressure)."""

    def __init__(self, shared: "SharedServer", server: "Server",
                 depth: int):
        self.depth = int(depth)
        self._shared = shared
        self._server = server
        self._q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, daemon=True,
            name="rpc-dispatch-{}".format(server.secret_hex[:8]))
        self._thread.start()

    def submit(self, conn, payload: bytes) -> bool:
        try:
            self._q.put_nowait((conn, payload))
            return True
        except queue.Full:
            return False

    def qsize(self) -> int:
        return self._q.qsize()

    def stop(self, timeout: float = 2.0) -> None:
        self._stop.set()
        self._thread.join(timeout=timeout)

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                conn, payload = self._q.get(timeout=0.2)
            except queue.Empty:
                continue
            try:
                self._shared._dispatch(conn, self._server, payload)
            except Exception:  # noqa: BLE001 - one bad frame must not kill the tenant's pool
                pass


class SharedServer:
    """One listening socket multiplexing MANY experiments' control
    planes (fleet mode): each attached per-experiment ``Server`` keeps
    its own handlers, reservations, and secret, and frames route to the
    server whose HMAC secret authenticates them — the first authenticated
    frame binds the connection, so steady-state verification is one HMAC
    like a dedicated listener. Runner re-binding across experiments needs
    no new sockets on the driver host: the runner reconnects to the SAME
    address with the NEW experiment's secret.

    Dispatch architecture: the event loop does PURE frame work — accept,
    reassemble, authenticate/route — and hands each complete frame to the
    target experiment's ``_TenantDispatcher``, a bounded FIFO queue
    drained by one dedicated worker thread per attached server. Handlers
    (and their replies) run on that worker, so one tenant's slow handler
    (a FINAL fast path waiting out its bounded sched-lock timeout, a
    chaos ``delay_msg``, a degraded controller) stalls ONLY its own
    tenant's queue; every other experiment's replies keep flowing at
    loop speed. Ordering: one worker per tenant + in-order enqueue from
    the loop = per-connection FIFO handling and replies, exactly the
    guarantee a dedicated listener gave. Backpressure: a tenant whose
    queue is full has its overflowing frame AND connection shed (counted
    as ``rpc.tenant.backpressure_drops`` on the tenant's registry and
    journaled as a ``shed`` event with ``scope="rpc"``); the client's
    jittered retry/backoff path re-delivers, so a congested tenant slows
    itself down without consuming loop time. ``dispatch_pool=False`` (or
    MAGGY_TPU_SHARED_DISPATCH_POOL=0) restores the legacy
    handlers-on-the-loop behavior for A/B measurement — bench.py --scale
    uses exactly that switch to show the head-of-line isolation.

    The shared event loop also drives each attached server's ``_tick``
    (heartbeat-loss scans) and the chaos engine's elapsed-time triggers,
    exactly as a dedicated loop would."""

    def __init__(self, dispatch_pool: Optional[bool] = None,
                 tenant_queue_depth: Optional[int] = None):
        import os

        if dispatch_pool is None:
            dispatch_pool = os.environ.get(
                "MAGGY_TPU_SHARED_DISPATCH_POOL", "1").strip().lower() \
                not in ("0", "false", "off")
        self.dispatch_pool = bool(dispatch_pool)
        self.tenant_queue_depth = int(
            tenant_queue_depth
            if tenant_queue_depth is not None
            else os.environ.get("MAGGY_TPU_TENANT_QUEUE_DEPTH",
                                constants.TENANT_DISPATCH_QUEUE_DEPTH))
        self._lock = threading.RLock()
        self._servers: Dict[bytes, Server] = {}  # guarded-by: _lock
        self._dispatchers: Dict[bytes, _TenantDispatcher] = {}  # guarded-by: _lock
        self._conn_server: Dict[socket.socket, Server] = {}  # guarded-by: _lock
        self._buffers: Dict[socket.socket, bytearray] = {}  # guarded-by: _lock
        self._sel = selectors.DefaultSelector()
        self._listener: Optional[socket.socket] = None
        self._thread: Optional[threading.Thread] = None
        self._stop_event = threading.Event()
        self.addr: Optional[Tuple[str, int]] = None

    def attach(self, server: Server,
               host: str = "127.0.0.1") -> Tuple[str, int]:
        """Publish ``server`` on the shared listener (started lazily);
        returns the shared (host, port)."""
        with self._lock:
            self._servers[server.secret] = server
            if self.dispatch_pool:
                self._dispatchers[server.secret] = _TenantDispatcher(
                    self, server, self.tenant_queue_depth)
            server._shared = self
            if self._listener is None:
                self._start_locked(host)
        return self.addr

    def detach(self, server: Server) -> None:
        with self._lock:
            self._servers.pop(server.secret, None)
            dispatcher = self._dispatchers.pop(server.secret, None)
            stale = [c for c, s in self._conn_server.items() if s is server]
        for conn in stale:
            self._drop(conn)
        if dispatcher is not None:
            dispatcher.stop()

    def _start_locked(self, host: str, port: int = 0) -> None:
        from maggy_tpu import native

        native.get_lib()  # warm the codec off the event loop (see Server)
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind((host, port))
        srv.listen(128)
        srv.setblocking(False)
        self._listener = srv
        self.addr = srv.getsockname()
        self._sel.register(srv, selectors.EVENT_READ, self._accept)
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="rpc-shared-server")
        self._thread.start()

    def _accept(self, sock, mask):
        conn, _ = sock.accept()
        conn.setblocking(False)
        with self._lock:
            self._buffers[conn] = bytearray()
        self._sel.register(conn, selectors.EVENT_READ, self._serve)

    def _serve(self, conn, mask):
        try:
            chunk = conn.recv(constants.RPC_RECV_BUFSIZE)
        except (BlockingIOError, InterruptedError):
            return
        except OSError:
            self._drop(conn)
            return
        if not chunk:
            self._drop(conn)
            return
        with self._lock:
            buf = self._buffers.get(conn)
        if buf is None:
            return
        buf.extend(chunk)
        # Stop at the first drop: routing (shed), a pool-less dispatch,
        # or a bad frame may sever the connection while MORE complete
        # frames sit in the local buffer — continuing would dispatch
        # frames of a closed socket and re-bind it into _conn_server
        # (the sever-mid-frame bookkeeping leak).
        while self._tracked(conn):
            extracted = self._try_extract_frame(conn, buf)
            if extracted is None:
                return
            server, payload = extracted
            self._route(conn, server, payload)

    def _tracked(self, conn) -> bool:
        with self._lock:
            return conn in self._buffers

    def _try_extract_frame(self, conn, buf: bytearray):
        """Pop one complete frame and resolve which experiment it belongs
        to: a bound connection verifies against its server's secret only;
        an unbound one tries every attached secret and binds to the first
        match. No match = unauthenticated peer -> drop."""
        header = 4 + 32
        if len(buf) < header:
            return None
        (length,) = _LEN.unpack(bytes(buf[:4]))
        if length > MAX_FRAME:
            self._drop(conn)
            return None
        if len(buf) < header + length:
            return None
        mac = bytes(buf[4:header])
        payload = bytes(buf[header:header + length])
        with self._lock:
            bound = self._conn_server.get(conn)
            candidates = [bound] if bound is not None \
                else list(self._servers.values())
        server = next(
            (s for s in candidates
             if hmac.compare_digest(mac, _sign(s.secret, payload))), None)
        if server is None:
            self._drop(conn)
            return None
        if bound is None:
            with self._lock:
                # Bind only while the connection is still tracked: a
                # concurrent drop (pool-thread send failure) must not be
                # resurrected as a routing entry for a closed socket.
                if conn not in self._buffers:
                    return None
                self._conn_server[conn] = server
        del buf[:header + length]
        return server, payload

    def _route(self, conn, server: Server, payload: bytes) -> None:
        """Hand one authenticated frame to the tenant's dispatch pool —
        the event loop's ONLY job besides framing. Pool off (legacy /
        A/B) dispatches inline on the loop."""
        with self._lock:
            dispatcher = self._dispatchers.get(server.secret)
        if dispatcher is None:
            self._dispatch(conn, server, payload)
            return
        if not dispatcher.submit(conn, payload):
            # Per-tenant backpressure: THIS tenant's queue is full —
            # shed the frame and the connection (the client's jittered
            # retry re-delivers), leaving other tenants untouched.
            telem = server.telemetry
            if telem is not None:
                telem.metrics.counter(
                    "rpc.tenant.backpressure_drops").inc()
                telem.event("shed", scope="rpc",
                            queue_depth=dispatcher.depth)
            self._drop(conn)

    def _dispatch(self, conn, server: Server, payload: bytes):
        """Mirror of ``Server._dispatch`` with the target server resolved
        per frame: same chaos hooks, same error wrapping, reply signed
        with THAT experiment's secret. Runs on the tenant's dispatcher
        worker (pool mode), so a chaos ``delay_msg`` stalls only the
        targeted tenant — the fault's blast radius matches the new
        architecture's isolation claim."""
        sever_reply = False
        try:
            msg = msgpack.unpackb(payload, raw=False, strict_map_key=False)
            engine = chaos_engine()
            if engine is not None:
                action = engine.on_server_message(msg)
                if action is not None:
                    if action[0] == "drop":
                        self._drop(conn)
                        return
                    if action[0] == "delay":
                        time.sleep(action[1])
                    elif action[0] == "sever":
                        sever_reply = True
            resp = server.handle_message(msg)
        except (ConnectionError, socket.timeout, OSError):
            self._drop(conn)
            return
        except Exception as e:  # noqa: BLE001 - a bad message must never kill the loop
            resp = {"type": "ERR", "error": "handler error: {!r}".format(e)}
        if sever_reply:
            self._drop(conn)
            return
        try:
            conn.setblocking(True)
            MessageSocket.send_msg(conn, resp, server.secret)
        except OSError:
            self._drop(conn)
        finally:
            try:
                conn.setblocking(False)
            except OSError:
                pass

    def _drop(self, conn):
        """Thread-safe teardown of one connection's state — called from
        the event loop AND the tenant dispatcher workers (reply/send
        failures), so every table it touches is lock-guarded and every
        step tolerates a concurrent double-drop."""
        with self._lock:
            self._buffers.pop(conn, None)
            self._conn_server.pop(conn, None)
        try:
            self._sel.unregister(conn)
        except (KeyError, ValueError):
            pass
        try:
            conn.close()
        except OSError:
            pass

    def _loop(self):
        while not self._stop_event.is_set():
            events = self._sel.select(timeout=0.2)
            for key, mask in events:
                key.data(key.fileobj, mask)
            with self._lock:
                servers = list(self._servers.values())
            for server in servers:
                try:
                    server._tick()
                except Exception:  # noqa: BLE001 - one experiment's tick must not kill the loop
                    pass
            engine = chaos_engine()
            if engine is not None:
                engine.tick()

    def stop(self):
        self._stop_event.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
        with self._lock:
            servers = list(self._servers.values())
            self._servers.clear()
            dispatchers = list(self._dispatchers.values())
            self._dispatchers.clear()
        for dispatcher in dispatchers:
            dispatcher.stop()
        for server in servers:
            server._shared = None
        for key in list(self._sel.get_map().values()):
            self._drop(key.fileobj)
        self._sel.close()


class FleetAgentServer(Server):
    """The fleet host's control plane for REMOTE AGENTS — the daemon
    processes (``python -m maggy_tpu.fleet agent``) that turn the
    in-process fleet into a cross-process, cross-host one. Published on
    the fleet's ``SharedServer`` under the FLEET secret (the one the
    fleet ticket carries), so agent traffic shares the same listening
    socket as every tenant's control plane and re-binding an agent
    across experiments never needs a new driver-side socket.

    Verbs (the ABIND wire contract, docs/developer.md):

    - ``AJOIN``: an agent declares its capacity (host, chips, process
      index, optional ``coord_addr`` for remote-gang rendezvous, its OS
      pid for same-host chaos kills) and is admitted into an agent slot;
      the reply carries its ``agent`` id plus the poll cadence and
      liveness bound the fleet will hold it to.
    - ``ALEASE``: the agent's idle poll (doubles as its idle heartbeat).
      Replies: ``ABIND`` — a lease: the target experiment's SECRET,
      partition id, executor config, and the train function's dotted
      path (``warm_start`` rides along so the agent keeps warm slots
      across same-family re-leases within its process); ``OK`` — nothing
      to do; ``AGSTOP`` — the fleet is shutting down, exit.
    - ``ADONE``: the agent's executor loop returned (GSTOP observed or
      an error) — the lease closes and the agent returns to the idle
      pool instead of exiting.

    The handlers delegate to the attached ``fleet.agent.AgentPlane``;
    msg-key reads stay HERE so the rpcconf checker sees the full wire
    contract at the handler."""

    def __init__(self, max_agents: int, secret: Optional[str] = None):
        # The plane (maggy_tpu.fleet.agent.AgentPlane), attached by the
        # fleet. None rejects every agent verb.
        self.agent_plane = None
        super().__init__(max_agents, secret)

    def attach_plane(self, plane) -> None:
        self.agent_plane = plane

    def _register_handlers(self) -> None:
        super()._register_handlers()
        self._handlers.update(
            AJOIN=self._ajoin,
            ALEASE=self._alease,
            ADONE=self._adone,
        )

    def _ajoin(self, msg):
        plane = self.agent_plane
        if plane is None:
            return {"type": "ERR",
                    "error": "this fleet does not accept remote agents"}
        return plane.agent_join(
            host=msg.get("host"), chips=msg.get("chips"),
            process_index=msg.get("process_index"),
            coord_addr=msg.get("coord_addr"), os_pid=msg.get("os_pid"),
            agent=msg.get("agent"))

    def _alease(self, msg):
        plane = self.agent_plane
        if plane is None:
            return {"type": "ERR",
                    "error": "this fleet does not accept remote agents"}
        return plane.agent_lease(agent=msg.get("agent"),
                                 offset_s=msg.get("offset_s"),
                                 rtt_s=msg.get("rtt_s"))

    def _adone(self, msg):
        plane = self.agent_plane
        if plane is None:
            return {"type": "ERR",
                    "error": "this fleet does not accept remote agents"}
        return plane.agent_done(agent=msg.get("agent"),
                                error=msg.get("error"))


class SinkServer(Server):
    """The fleet host's JOURNAL SINK tenant (telemetry/sink.py): one
    more server published on the fleet's shared listener, under its OWN
    secret (a journal shipper must not be able to lease agents or speak
    any experiment's control plane). A single verb:

    - ``JSINK``: a batch of journal events from one SOURCE (a fleet-
      attached tenant or a remote agent), each stamped with the source's
      monotonic ``sid`` event id, plus an optional metric-counter
      snapshot for fleet-side federation. The reply acks the highest
      sid the sink now holds — at-least-once shipping with sink-side
      dedup makes delivery exactly-once per event id.

    Batches land on this tenant's ordinary dispatch pool, so journal
    ingestion is isolated from every experiment's control traffic and a
    full sink queue sheds frames (per-tenant backpressure) — which the
    shipper treats as sink death and degrades to its local journal.
    The handler delegates to the attached ``telemetry.sink.JournalSink``;
    msg-key reads stay HERE so the rpcconf checker sees the wire
    contract at the handler."""

    def __init__(self, secret: Optional[str] = None):
        # The sink service (maggy_tpu.telemetry.sink.JournalSink),
        # attached by the fleet. None rejects JSINK.
        self.sink = None
        super().__init__(1, secret)

    def attach_sink(self, sink) -> None:
        self.sink = sink

    def _register_handlers(self) -> None:
        super()._register_handlers()
        self._handlers.update(JSINK=self._jsink)

    def _jsink(self, msg):
        sink = self.sink
        if sink is None:
            return {"type": "ERR",
                    "error": "this fleet has no journal sink attached"}
        return sink.ingest(source=msg.get("source"),
                           events=msg.get("events"),
                           counters=msg.get("counters"),
                           client_t=msg.get("client_t"))


class OptimizationServer(Server):
    """HPO/ablation message semantics (reference `rpc.py:295-388`).

    The driver attaches itself via `attach_driver` so handlers can read
    trial state and enqueue worker messages.
    """

    def __init__(self, num_executors: int, secret: Optional[str] = None):
        self.driver = None
        self._last_loss_scan = time.monotonic()
        super().__init__(num_executors, secret)

    def attach_driver(self, driver) -> None:
        self.driver = driver

    def _register_handlers(self) -> None:
        super()._register_handlers()
        self._handlers.update(
            REG=self._reg,
            METRIC=self._metric,
            BATCH=self._batch,
            FINAL=self._final,
            GET=self._get,
            LOG=self._log,
        )

    def _note_adopted(self, partition_id) -> None:
        """First post-recovery message from a pre-crash partition: the
        runner survived the driver restart and re-bound (same secret,
        same address) — journal the ``adopted`` runner edge exactly once
        (the recovered flag is consumed)."""
        if self.reservations.pop_recovered(partition_id):
            telem = self.telemetry
            if telem is not None:
                telem.event("runner", phase="adopted",
                            partition=int(partition_id))

    def _tick(self) -> None:
        if self.driver is None:
            return
        now = time.monotonic()
        gate = min(1.0, self.hb_loss_timeout / 4) \
            if self.hb_loss_timeout is not None else 1.0
        if now - self._last_loss_scan < gate:
            return
        self._last_loss_scan = now
        check = getattr(self.driver, "periodic_check", None)
        if check is not None:
            try:
                check()
            except Exception:  # noqa: BLE001 - never kill the event loop
                if not self._periodic_check_failed:
                    self._periodic_check_failed = True
                    import traceback

                    traceback.print_exc()
        if self.hb_loss_timeout is None:
            return
        for pid, trial_id in self.reservations.lost_assignments(self.hb_loss_timeout):
            # Clear the assignment first so a racing re-registration takes
            # the BLACK path instead of double-requeueing this trial.
            self.reservations.assign_trial(pid, None)
            self.driver.enqueue({"type": "LOST", "trial_id": trial_id,
                                 "partition_id": pid})

    def _reg(self, msg):
        # Failure detection (reference `rpc.py:308-326`): a re-registration
        # from a partition already holding a trial means the executor died
        # and was relaunched -> mark that trial ERROR, queue BLACK.
        prev = self.reservations.get_assigned_trial(msg["partition_id"])
        self.reservations.add(
            {"partition_id": msg["partition_id"], "host_port": msg.get("host_port"),
             "task_attempt": msg.get("task_attempt", 0), "trial_id": prev,
             "capacity": msg.get("capacity")}
        )
        if prev is not None:
            self.driver.enqueue({"type": "BLACK", "trial_id": prev,
                                 "partition_id": msg["partition_id"]})
        else:
            # First registration: ask the driver worker for a first assignment.
            self.driver.enqueue({"type": "REG",
                                 "partition_id": msg["partition_id"],
                                 "capacity": msg.get("capacity")})
        telem = self.telemetry
        if telem is not None:
            telem.event("runner", phase="registered",
                        partition=int(msg["partition_id"]),
                        capacity=msg.get("capacity"),
                        reregistration=prev is not None)
        return {"type": "OK"}

    def _metric(self, msg):
        self.reservations.touch(msg["partition_id"])
        self._note_adopted(msg["partition_id"])
        telem = self.telemetry
        rstats = msg.pop("rstats", None)
        if rstats and telem is not None:
            # Runner-side stats piggybacked on the heartbeat (bounded,
            # delta-encoded): merge + journal with partition attribution.
            # Popped first so the driver worker's METRIC callback sees the
            # same payload shape it always did.
            telem.record_runner_stats(msg["partition_id"], rstats)
        self.driver.enqueue(dict(msg))
        trial_id = msg.get("trial_id")
        if trial_id and self.reservations.pop_stop(msg["partition_id"],
                                                  trial_id):
            # Gang revocation abort: preempt-shaped so the runner acks
            # and frees itself; the driver already requeued the trial.
            return {"type": "STOP", "span": msg.get("span"),
                    "preempt": True}
        if msg.get("lanes"):
            return self._metric_lanes(msg, trial_id)
        stop = False
        if trial_id:
            trial = self.driver.get_trial(trial_id)
            stop = bool(trial and trial.get_early_stop())
        if stop:
            # The moment the runner is FIRST told to stop: early-stop
            # reaction latency (stop_flagged -> finalized) brackets this
            # hop. once=True — heartbeats keep drawing STOP replies until
            # the training loop honors the flag, and re-journaling each
            # would bloat the journal by heartbeat rate x stop latency.
            # The STOP reply echoes the span so the runner side can
            # attribute the abort without re-deriving it.
            telem = self.telemetry
            if telem is not None:
                telem.trial_event(trial_id, "stop_sent", once=True,
                                  partition=int(msg["partition_id"]))
            # ``preempt``: this stop is a scheduler preemption, not an
            # early-stop verdict — the runner acks with a preempted FINAL
            # (carrying its last checkpoint step) instead of finalizing.
            return {"type": "STOP", "span": msg.get("span"),
                    "preempt": bool(trial and trial.get_preempt())}
        return {"type": "OK"}

    def _metric_lanes(self, msg, leader_id):
        """STOP routing for a vectorized block's heartbeat (one beat, K
        lane-tagged metric entries). Early stopping a lane must NOT tear
        down the block — the reply carries ``stop_lanes`` and the runner
        masks those lanes in place (train/vmap.py). A STOP reply is
        reserved for scheduler preemption, which aborts the whole block."""
        telem = self.telemetry
        stop_lanes = []
        preempt = False
        for beat in msg["lanes"]:
            lane_trial = self.driver.get_trial(beat.get("trial_id"))
            if lane_trial is None or not lane_trial.get_early_stop():
                continue
            if lane_trial.get_preempt():
                preempt = True
                continue
            stop_lanes.append(beat["trial_id"])
            if telem is not None:
                # once=True for the same reason as the scalar stop_sent:
                # the lane keeps appearing in beats until the runner's
                # training loop reaches its next mask boundary.
                telem.trial_event(beat["trial_id"], "stop_sent", once=True,
                                  partition=int(msg["partition_id"]),
                                  lane=beat.get("lane"))
        leader = self.driver.get_trial(leader_id) if leader_id else None
        if preempt or (leader and leader.get_early_stop()
                       and leader.get_preempt()):
            return {"type": "STOP", "span": msg.get("span"),
                    "preempt": True}
        reply = {"type": "OK"}
        if stop_lanes:
            reply["stop_lanes"] = stop_lanes
        return reply

    def _final(self, msg):
        """FINAL dispatch wrapper: the durability barrier runs AFTER the
        handler, BEFORE the reply is written (the dispatcher sends the
        returned dict) — so the journal, crash recovery's source of
        truth, can never trail a FINAL the runner saw acknowledged. On
        the inline fast path the finalized span edge and trial.json are
        both durable by the time the reply leaves; on the worker
        fallback the FINAL is still queued when the reply is written —
        a crash in that window re-runs the trial (at-least-once, never
        lost), documented in docs/developer.md."""
        try:
            return self._final_unbarriered(msg)
        finally:
            telem = self.telemetry
            if telem is not None:
                telem.barrier()

    def _final_unbarriered(self, msg):
        self.reservations.touch(msg["partition_id"])
        self._note_adopted(msg["partition_id"])
        if msg.get("block") is not None and not msg.get("last"):
            # Per-lane FINAL of a vectorized block (one FINAL per lane,
            # train/vmap.py): the partition still holds the block — no
            # assignment clear, no piggybacked hand-off. The driver
            # reports the lane's result to the controller inline so the
            # optimizer sees it at masking time, not at block teardown.
            fast = getattr(self.driver, "process_final_inline", None)
            if fast is None or not fast(msg):
                self.driver.enqueue(dict(msg))
            return {"type": "OK"}
        # Conditional, not assign_trial(None): a RETRIED final (severed /
        # lost reply) must not wipe the next trial assigned in between.
        # For a block's LAST lane the partition's assignment is the block
        # LEADER, which the closing lane need not be — clear by leader.
        self.reservations.clear_trial_if(msg["partition_id"],
                                         msg.get("block") or msg.get("trial_id"))
        # Pipelined hand-off (config.prefetch): the driver processes the
        # FINAL inline on this thread — report to the controller, drop any
        # schedule-stale prefetched suggestion, pick the next assignment —
        # and the reply carries it, so the freed runner skips the GET
        # round trip entirely. False = not processed (prefetch off, lock
        # briefly held by a mid-fit suggester, or an internal error): the
        # legacy path enqueues to the driver worker and the runner falls
        # back to GET polling.
        fast = getattr(self.driver, "process_final_inline", None)
        if fast is None or not fast(msg):
            self.driver.enqueue(dict(msg))
            if self.reservations.evict_requested(msg["partition_id"]) and \
                    msg.get("preempted"):
                # Worker-path preempt ack of an evicted runner: release it
                # now — the enqueued message only requeues the trial, and
                # the runner must not GET-poll an experiment it has been
                # preempted out of.
                self.reservations.mark_released(msg["partition_id"])
                return {"type": "GSTOP"}
            return {"type": "OK"}
        pid = msg["partition_id"]
        telem = self.telemetry
        reply = self._serve_assigned(pid)
        if reply is not None:
            if telem is not None and reply.get("type") == "TRIAL":
                # once=True: a retried FINAL (lost/severed reply)
                # re-serves the same undelivered assignment — one
                # hand-off, one hit, however many deliveries it takes.
                telem.trial_event(reply["trial_id"], "prefetch_hit",
                                  once=True, partition=int(pid))
            return reply
        if self.reservations.evict_requested(pid):
            # Fleet preemption: the runner's ack doubles as its release —
            # it re-binds to another experiment, not to this one's GET.
            self.reservations.mark_released(pid)
            return {"type": "GSTOP"}
        if self.driver.experiment_done:
            # Inline release: the runner's last FINAL doubles as its GSTOP.
            self.reservations.mark_released(pid)
            return {"type": "GSTOP"}
        if telem is not None and not msg.get("preempted"):
            # Nothing ready (controller IDLE / rung barrier / expensive
            # suggest still fitting): the runner falls back to GET.
            # once=True matches the hit side under retried FINALs. A
            # preempted ack is not a hand-off attempt — it must not count
            # as a pipeline miss.
            telem.trial_event(msg.get("trial_id"), "prefetch_miss",
                              once=True, partition=int(pid))
        return {"type": "OK"}

    def _serve_assigned(self, partition_id):
        """The TRIAL reply for the partition's currently-assigned trial —
        shared by GET and the FINAL piggyback. None = no assignment (the
        caller decides between GSTOP/RESIZE/OK)."""
        trial_id = self.reservations.get_assigned_trial(partition_id)
        if trial_id is None:
            return None
        trial = self.driver.get_trial(trial_id)
        if trial is None:
            return {"type": "OK", "trial_id": None}
        trial.set_status(Trial.RUNNING)
        trial.start = time.time()
        # Which runner served it: lets offline analysis (bench.py) compute
        # true per-partition hand-off gaps from the trial.json artifacts.
        with trial.lock:
            trial.info_dict["partition"] = partition_id
            # The run epoch rides in info so the FINAL can echo it: the
            # driver drops a dead run's in-flight FINAL by epoch mismatch
            # (same-partition re-dispatch makes partition checks blind).
            trial.info_dict["epoch"] = trial.run_epoch
            info = dict(trial.info_dict)
        telem = self.telemetry
        if telem is not None:
            # "running" = the TRIAL reply leaves the driver: the hand-off
            # gap's closing edge (its opening edge is the previous trial's
            # "finalized" on the same partition). The run epoch rides
            # along so crash recovery can reconstruct an in-flight
            # trial's epoch — a pre-crash runner's retried FINAL then
            # passes the stale-epoch guard (accepted exactly once), while
            # a dead incarnation's FINAL after a post-recovery requeue
            # (epoch bumped) still drops.
            telem.trial_event(trial.trial_id, "running",
                              partition=int(partition_id),
                              epoch=info.get("epoch"))
        block = info.get("vmap_block")
        if block:
            # Vectorized block delivery: every lane enters RUNNING with the
            # leader — each gets its own running edge so per-lane spans
            # (queued -> running -> finalized) close without inference.
            for entry in block.get("lanes", ()):
                if entry["trial_id"] == trial.trial_id:
                    continue
                lane_trial = self.driver.get_trial(entry["trial_id"])
                if lane_trial is None:
                    continue
                lane_trial.set_status(Trial.RUNNING)
                lane_trial.start = time.time()
                with lane_trial.lock:
                    lane_trial.info_dict["partition"] = partition_id
                    lane_trial.info_dict["epoch"] = lane_trial.run_epoch
                if telem is not None:
                    telem.trial_event(entry["trial_id"], "running",
                                      partition=int(partition_id),
                                      epoch=entry.get("epoch"),
                                      lane=entry.get("lane"),
                                      block=trial.trial_id)
        return {"type": "TRIAL", "trial_id": trial.trial_id,
                "params": trial.params, "info": info,
                "span": info.get("span")}

    def _get(self, msg):
        self.reservations.touch(msg["partition_id"])
        self._note_adopted(msg["partition_id"])
        pid = msg["partition_id"]
        if self.reservations.evict_requested(pid):
            # Fleet preemption of an idle (or between-trials) runner: hand
            # any undelivered assignment back to the schedule as a
            # never-started preemption (requeue-from-scratch) and release
            # the runner so it can re-bind to another experiment.
            tid = self.reservations.get_assigned_trial(pid)
            if tid is not None:
                self.reservations.clear_trial_if(pid, tid)
                self.driver.enqueue({"type": "FINAL", "trial_id": tid,
                                     "partition_id": pid, "preempted": True,
                                     "step": None, "logs": []})
            self.reservations.mark_released(pid)
            return {"type": "GSTOP"}
        # Serve an already-assigned trial BEFORE honoring experiment-done:
        # the last suggestion may be assigned concurrently with another
        # FINAL ending the experiment, and must still run.
        reply = self._serve_assigned(msg["partition_id"])
        if reply is not None:
            return reply
        member = self._serve_gang_member(pid)
        if member is not None:
            return member
        if self.driver.experiment_done:
            self.reservations.mark_released(msg["partition_id"])
            return {"type": "GSTOP"}
        resize = self.reservations.pop_resize(msg["partition_id"])
        if resize is not None:
            # The runner exits and its pool respawns it pinned to
            # ``chips`` chips; released here so liveness checks ignore
            # the gap until it re-registers.
            self.reservations.mark_released(msg["partition_id"])
            return {"type": "RESIZE", "chips": resize}
        return {"type": "OK", "trial_id": None}

    def _serve_gang_member(self, partition_id):
        """REMOTE-gang member delivery: a gang-held member whose gang
        carries a ``rendezvous`` block lives in ANOTHER process, so it
        must run the SPMD program itself (every process of a
        jax.distributed world runs the same program, or the leader's
        collectives hang). Serve it the gang trial ONCE per assembly,
        flagged ``gang_role="member"`` — the executor joins the
        rendezvous, runs the program, discards the result, and never
        finalizes (exactly one FINAL, from the leader). In-process gangs
        (no rendezvous) never reach this: their members keep idling, the
        leader computes over all local chips as before."""
        res = self.reservations
        tid = res.gang_of(partition_id)
        if tid is None or res.get_assigned_trial(partition_id) == tid:
            return None
        gang_info = getattr(self.driver, "gang_info", None)
        info_g = gang_info(tid) if gang_info is not None else None
        if not info_g or not info_g.get("rendezvous"):
            return None
        if int(partition_id) == int(info_g.get("leader", -1)):
            # Assembly window: _gangs is stored a few statements before
            # assign_trial(leader) — a leader GET landing in between
            # must wait for its LEADER assignment, not burn the member
            # latch and run the program twice.
            return None
        if not res.mark_gang_served(partition_id, tid):
            return None
        trial = self.driver.get_trial(tid)
        if trial is None:
            return None
        with trial.lock:
            info = dict(trial.info_dict)
        info["partition"] = int(partition_id)
        info["gang_role"] = "member"
        return {"type": "TRIAL", "trial_id": trial.trial_id,
                "params": trial.params, "info": info,
                "span": info.get("span")}

    def _log(self, msg):
        return {"type": "LOG", **self.driver.progress_snapshot()}


class DistributedServer(Server):
    """Adds the coordinator rendezvous: DIST_CONFIG returns partition-0's
    advertised host plus world size, replacing the reference's TORCH_CONFIG
    MASTER_ADDR/PORT brokering (`rpc.py:391-437`). Runners pass it to
    `jax.distributed.initialize`."""

    def __init__(self, num_executors: int, secret: Optional[str] = None):
        self.driver = None
        self._last_loss_scan = time.monotonic()
        super().__init__(num_executors, secret)

    def attach_driver(self, driver) -> None:
        self.driver = driver

    def _register_handlers(self) -> None:
        super()._register_handlers()
        self._handlers.update(
            REG=self._reg,
            METRIC=self._metric,
            BATCH=self._batch,
            FINAL=self._final,
            DIST_CONFIG=self._dist_config,
            LOG=self._log,
        )

    def _reg(self, msg):
        self.reservations.add(
            {"partition_id": msg["partition_id"], "host_port": msg.get("host_port"),
             "task_attempt": msg.get("task_attempt", 0), "trial_id": None}
        )
        telem = self.telemetry
        if telem is not None:
            telem.event("worker", phase="registered",
                        partition=int(msg["partition_id"]))
        return {"type": "OK"}

    def _metric(self, msg):
        self.reservations.touch(msg["partition_id"])
        telem = self.telemetry
        rstats = msg.pop("rstats", None)
        if rstats and telem is not None:
            telem.record_runner_stats(msg["partition_id"], rstats)
        if self.driver is not None:
            self.driver.enqueue(dict(msg))
        return {"type": "OK"}

    def _final(self, msg):
        # FINAL is a dist worker's last message — it never polls GET/GSTOP,
        # so release its slot here for the remote pool's teardown ack.
        self.reservations.touch(msg["partition_id"])
        self.reservations.mark_released(msg["partition_id"])
        if self.driver is not None:
            self.driver.enqueue(dict(msg))
        telem = self.telemetry
        if telem is not None:
            telem.event("worker", phase="finalized",
                        partition=int(msg["partition_id"]),
                        error=bool(msg.get("error")))
            # Worker-measured rendezvous latency rides the FINAL payload
            # (the dist analogue of a trial span's phase timestamps).
            stats = msg.get("telem") or {}
            if stats.get("rendezvous_ms") is not None:
                telem.observe_ms("dist.rendezvous_ms",
                                 float(stats["rendezvous_ms"]))
        return {"type": "OK"}

    def _tick(self) -> None:
        """An SPMD worker whose heartbeats stopped is dead, and a dead rank
        wedges every collective in the world — surface it instead of letting
        the experiment (and a remote pool's completion wait) hang forever."""
        if self.hb_loss_timeout is None or self.driver is None:
            return
        now = time.monotonic()
        if now - self._last_loss_scan < min(1.0, self.hb_loss_timeout / 4):
            return
        self._last_loss_scan = now
        for pid in self.reservations.silent(self.hb_loss_timeout):
            self.reservations.mark_released(pid)
            self.driver.enqueue({"type": "DEAD_WORKER", "partition_id": pid})

    def _dist_config(self, msg):
        rec = self.reservations.get(0)
        if rec is None or not self.reservations.done():
            return {"type": "OK", "config": None}
        return {
            "type": "DIST_CONFIG",
            "config": {
                "coordinator_address": rec["host_port"],
                "num_processes": self.num_executors,
            },
        }

    def _log(self, msg):
        snap = self.driver.progress_snapshot() if self.driver else {}
        return {"type": "LOG", **snap}


# --------------------------------------------------------------------- client


class Client:
    """Executor-side control-plane client (reference `rpc.py:440-593`).

    One request socket + one dedicated heartbeat socket; the heartbeat
    daemon ships (metric, step, logs) every ``hb_interval`` and applies STOP
    replies to the reporter.
    """

    def __init__(
        self,
        server_addr: Tuple[str, int],
        partition_id: int,
        task_attempt: int,
        hb_interval: float,
        secret: str,
    ):
        self.server_addr = tuple(server_addr)
        self.partition_id = partition_id
        self.task_attempt = task_attempt
        self.hb_interval = hb_interval
        self.secret = secret.encode() if isinstance(secret, str) else secret
        self.done = False
        self.last_info: dict = {}
        # Next assignment piggybacked on a FINAL reply (pipelined
        # hand-off): (trial_id, params, info), consumed by the next
        # get_suggestion call without any round trip.
        self._piggyback: Optional[tuple] = None
        # Reconnect generation (bumped by _request's reconnect path): lets
        # pollers notice a reconnect happened mid-loop and restart their
        # adaptive backoff from the fast end.
        self.reconnects = 0
        # Runner-side stat buffer (telemetry.runnerstats.RunnerStats),
        # attached by the executor. When set, the heartbeat loop measures
        # its round-trip time into it and piggybacks the delta-encoded
        # stats on the METRIC payload ("rstats" field) — no new socket.
        self.runner_stats = None
        self._sock = self._connect()
        self._hb_sock = self._connect()
        self._hb_thread: Optional[threading.Thread] = None
        self._hb_stop = threading.Event()
        self._lock = threading.Lock()  # serializes the request socket

    def _connect(self) -> socket.socket:
        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        sock.settimeout(30.0)
        sock.connect(self.server_addr)
        return sock

    def _request(self, msg: Dict[str, Any], sock: Optional[socket.socket] = None,
                 lock: bool = True) -> Dict[str, Any]:
        """Send one message with reconnect retries (reference `rpc.py:465-493`).

        Retries back off exponentially with full jitter, capped: the fixed
        cadence this replaces synchronized every client's retry storm onto
        a recovering server (64 runners reconnecting in lockstep after a
        driver stall is its own outage). Retries and reconnects are
        counted in ``CLIENT_METRICS`` so chaos soaks can assert the
        degraded paths actually ran."""
        import random as _random

        target = sock or self._sock
        msg = {**msg, "partition_id": self.partition_id,
               "task_attempt": self.task_attempt}
        last_err = None
        delay = constants.CLIENT_RETRY_BACKOFF_BASE_S
        for attempt in range(constants.CLIENT_MAX_RETRIES + 1):
            engine = chaos_engine()
            if engine is not None:
                # May sleep (cooperative stall) or raise ChaosKilled (a
                # condemned runner dies here, outside the retry net).
                engine.on_client_request(msg)
            try:
                if lock and target is self._sock:
                    with self._lock:
                        MessageSocket.send_msg(target, msg, self.secret)
                        return MessageSocket.recv_msg(target, self.secret)
                MessageSocket.send_msg(target, msg, self.secret)
                return MessageSocket.recv_msg(target, self.secret)
            except ChaosKilled:
                raise
            except (ConnectionError, socket.timeout, OSError) as e:
                last_err = e
                if attempt >= constants.CLIENT_MAX_RETRIES:
                    break
                CLIENT_METRICS.counter("rpc.client.retries").inc()
                # Full jitter in [delay/2, delay]: staggered, still bounded.
                time.sleep(delay * (0.5 + 0.5 * _random.random()))
                delay = min(delay * 2, constants.CLIENT_RETRY_BACKOFF_CAP_S)
                try:
                    fresh = self._connect()
                except OSError as conn_err:
                    # Server not back yet: keep the stale socket as the
                    # nominal target and burn another attempt.
                    last_err = conn_err
                    continue
                CLIENT_METRICS.counter("rpc.client.reconnects").inc()
                self.reconnects += 1
                if target is self._sock:
                    self._sock = fresh
                elif target is self._hb_sock:
                    self._hb_sock = fresh
                target = fresh
        raise ConnectionError("RPC request failed after retries: {}".format(last_err))

    # ----------------------------------------------------------------- calls

    def register(self, host_port: Optional[str] = None,
                 capacity: Optional[int] = None) -> None:
        """``capacity``: chips this runner is pinned to (elastic pools);
        None for non-elastic runners."""
        msg = {"type": "REG", "host_port": host_port}
        if capacity is not None:
            msg["capacity"] = int(capacity)
        self._request(msg)

    def await_reservations(self, timeout: float = constants.REGISTRATION_TIMEOUT_S) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            resp = self._request({"type": "QUERY"})
            if resp.get("done"):
                return
            time.sleep(constants.CLIENT_POLL_INTERVAL_S)
        raise TimeoutError("Registration barrier not reached.")

    @staticmethod
    def _queue_beat(pending: list, payload: Dict[str, Any]) -> None:
        """Bank a failed beat for BATCH re-delivery: coalesce with the
        newest pending beat when both describe the SAME trial (keep the
        fresher metric/step/span, concatenate logs — the driver only
        wants the latest sample plus every log line), and bound the
        backlog to CLIENT_MAX_PENDING_BEATS, dropping oldest-first (the
        pre-batching behavior for ALL failed beats). The caller strips
        ``rstats`` first: that delta requeues through the runner-stats
        buffer's own ledger and must not ship twice."""
        beat = {k: v for k, v in payload.items() if k != "rstats"}
        if pending and pending[-1].get("trial_id") == beat.get("trial_id"):
            merged = dict(beat)
            # Bounded, newest-last: an unbounded concatenation would let
            # a chatty trial grow one banked beat past MAX_FRAME over a
            # long outage — the beat-count bound alone caps nothing.
            merged["logs"] = ((pending[-1].get("logs") or [])
                              + (beat.get("logs") or []))[
                -constants.CLIENT_MAX_PENDING_LOG_LINES:]
            pending[-1] = merged
            return
        pending.append(beat)
        del pending[:-constants.CLIENT_MAX_PENDING_BEATS]

    def start_heartbeat(self, reporter) -> None:
        def beat():
            # Beats whose ship failed, oldest first — re-delivered as ONE
            # BATCH frame on the next successful beat instead of being
            # silently lost (and instead of a reconnect storm replaying
            # them one frame at a time against a recovering driver).
            pending: list = []
            while not self._hb_stop.is_set():
                try:
                    data = reporter.get_data()
                except Exception as e:  # noqa: BLE001
                    # Metric materialization failures (poisoned device
                    # value) must neither kill this thread NOR silence the
                    # beat: a missed beat reads as runner death -> false
                    # LOST -> duplicate trial run. Beat with no metric.
                    try:
                        reporter.log("heartbeat error: {!r}".format(e))
                    except Exception:  # noqa: BLE001
                        pass
                    data = {"metric": None, "step": None, "logs": []}
                sent_tid = data.get("trial_id", reporter.trial_id)
                payload = {"type": "METRIC", "trial_id": sent_tid,
                           "value": data["metric"], "step": data["step"],
                           "logs": data["logs"],
                           # The span the (metric, step) pair belongs to —
                           # same rollover rule as sent_tid.
                           "span": data.get("span")}
                if data.get("lanes"):
                    # Vectorized block: one beat, K lane-tagged metric
                    # entries (the batched-beat path ships them as one
                    # frame either way).
                    payload["lanes"] = data["lanes"]
                stats = self.runner_stats
                delta = None
                if stats is not None:
                    # Whether this beat carries a newer (metric, step)
                    # than the last, and how far it lags the loop.
                    lanes = data.get("lanes")
                    stats.on_heartbeat(
                        lanes[0]["step"] if lanes else data["step"],
                        data.get("newest_step"))
                    delta = stats.snapshot_delta()
                    if delta:
                        payload["rstats"] = delta
                if pending:
                    # The current beat rides LAST so the server's reply
                    # (STOP decisions included) is about the newest data.
                    send = {"type": "BATCH", "beats": pending + [payload]}
                else:
                    send = payload
                t_send = time.monotonic()
                try:
                    resp = self._request(send, sock=self._hb_sock,
                                         lock=False)
                    if pending:
                        CLIENT_METRICS.counter(
                            "rpc.client.batched_beats").inc(len(pending))
                        pending = []
                    if stats is not None:
                        # Retries/backoff included ON PURPOSE: this is the
                        # control-plane latency the runner experiences, the
                        # signal the health engine's RTT-degradation check
                        # feeds on.
                        stats.observe_hb_rtt(
                            (time.monotonic() - t_send) * 1e3)
                    if resp.get("type") == "STOP":
                        # Only stop the trial the beat was ABOUT: the
                        # runner may have rolled over to the next trial
                        # while this beat was in flight. ``preempt``
                        # marks a scheduler preemption (ack with a
                        # preempted FINAL, not a finalize).
                        reporter.early_stop(trial_id=sent_tid,
                                            preempt=bool(
                                                resp.get("preempt")))
                    elif resp.get("stop_lanes"):
                        # Per-lane early stops of a vectorized block: the
                        # training loop consumes these via
                        # take_stopped_lanes() and masks the lanes in
                        # place — the block keeps running.
                        reporter.stop_lanes(resp["stop_lanes"])
                except ConnectionError:
                    if stats is not None and delta:
                        # The ship failed — put the delta back so the next
                        # beat re-sends it instead of silently losing it.
                        stats.requeue_delta(delta)
                    self._queue_beat(pending, payload)
                except ValueError:
                    # Frame too large (send_msg's MAX_FRAME guard): the
                    # banked batch can never ship — drop it rather than
                    # retry-grow it forever or kill this thread (a dead
                    # heartbeat thread reads as runner death).
                    pending = []
                    if stats is not None and delta:
                        stats.requeue_delta(delta)
                self._hb_stop.wait(self.hb_interval)

        self._hb_thread = threading.Thread(target=beat, daemon=True, name="heartbeat")
        self._hb_thread.start()

    def get_suggestion(self, timeout: Optional[float] = None):
        """Blocking poll for the next trial; returns (trial_id, params) or
        (None, None) when the experiment is over (reference `rpc.py:537-546`).

        Zero-round-trip fast path: an assignment piggybacked on the last
        FINAL reply (see ``finalize_metric``) is returned immediately
        without touching the wire — GET polling is the fallback for
        registration, idle wake-ups, and requeues.

        Adaptive poll: the common miss is the race between this GET and the
        driver worker processing the FINAL we just sent (sub-ms), so the
        first retries come fast (5 ms doubling) and only a genuinely idle
        wait (rung barrier) backs off to the 0.1 s driver tick — per-trial
        hand-off latency stays in single-digit ms instead of a flat 0.1 s.
        The backoff restarts from the fast end after a reconnect: the
        post-reconnect state is a fresh race (the driver likely processed
        our retried message already), not a continuation of the idle wait
        the decayed tick was calibrated for."""
        pg = self._piggyback
        if pg is not None:
            self._piggyback = None
            trial_id, params, info = pg
            self.last_info = info
            return trial_id, params
        if self.done:
            return None, None
        deadline = time.monotonic() + timeout if timeout else None
        delay = constants.CLIENT_GET_POLL_MIN_S
        reconnect_gen = self.reconnects
        while True:
            resp = self._request({"type": "GET"})
            if self.reconnects != reconnect_gen:
                reconnect_gen = self.reconnects
                delay = constants.CLIENT_GET_POLL_MIN_S
            rtype = resp.get("type")
            if rtype == "GSTOP":
                self.done = True
                return None, None
            if rtype == "TRIAL":
                # Scheduler metadata (budget, promoted-trial parent, sample
                # type) rides along for TrialContext consumers.
                self.last_info = resp.get("info", {})
                return resp["trial_id"], resp["params"]
            if rtype == "RESIZE":
                # Elastic pools: this process must exit and be respawned
                # pinned to resp["chips"] chips (pinning happens before
                # backend init, so it cannot resize in place).
                self.done = True
                return RESIZE, {"chips": resp["chips"]}
            if deadline and time.monotonic() > deadline:
                return None, None
            time.sleep(delay)
            delay = min(delay * 2, constants.DRIVER_IDLE_REQUEUE_TICK_S)

    def get_dist_config(self, timeout: float = constants.RENDEZVOUS_TIMEOUT_S):
        """Blocking poll for the coordinator rendezvous config. Same
        adaptive fast-start poll as GET (the common wait is the last
        sibling's REG landing milliseconds after ours), backing off to
        CLIENT_DIST_CONFIG_POLL_MAX_S for a genuinely slow world; resets
        after a reconnect like GET does."""
        deadline = time.monotonic() + timeout
        delay = constants.CLIENT_GET_POLL_MIN_S
        reconnect_gen = self.reconnects
        while time.monotonic() < deadline:
            resp = self._request({"type": "DIST_CONFIG"})
            if self.reconnects != reconnect_gen:
                reconnect_gen = self.reconnects
                delay = constants.CLIENT_GET_POLL_MIN_S
            if resp.get("config"):
                return resp["config"]
            time.sleep(delay)
            delay = min(delay * 2, constants.CLIENT_DIST_CONFIG_POLL_MAX_S)
        raise TimeoutError("Coordinator rendezvous timed out.")

    def _handle_final_reply(self, resp: Dict[str, Any]) -> None:
        """Bank a FINAL reply's piggybacked next assignment (TRIAL) or
        release (GSTOP) so the next get_suggestion is wire-free."""
        rtype = resp.get("type")
        if rtype == "TRIAL":
            self._piggyback = (resp["trial_id"], resp["params"],
                               resp.get("info", {}))
        elif rtype == "GSTOP":
            self.done = True

    def finalize_metric(self, metric, reporter,
                        extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        """Send FINAL and reset the reporter atomically under its lock
        (reference `rpc.py:584-593`). ``extra`` merges additional payload
        fields (e.g. a dist worker's telemetry stats). The reply may
        piggyback the next assignment (pipelined hand-off) — banked for
        the next get_suggestion call — and is returned for callers that
        want to inspect it."""
        with reporter.lock:
            data = reporter.get_data()
            # No "span" key: the driver attributes FINALs through the span
            # tracker by trial id (spans are per trial, not per attempt),
            # so a span echo here was dead payload — the rpcconf checker
            # flags any key no handler reads.
            resp = self._request(
                {"type": "FINAL", "trial_id": reporter.trial_id,
                 "value": metric, "logs": data["logs"],
                 "epoch": (self.last_info or {}).get("epoch"),
                 **(extra or {})}
            )
            reporter.reset()
        self._handle_final_reply(resp)
        return resp

    def finalize_error(self, trial_id: str, reporter) -> Dict[str, Any]:
        """Report a failed trial (train_fn raised): FINAL with the error
        flag, no metric. Routed through the same reply handling as
        finalize_metric so an errored trial's freed runner still gets its
        piggybacked next assignment."""
        with reporter.lock:
            data = reporter.get_data()
            resp = self._request(
                {"type": "FINAL", "trial_id": trial_id, "value": None,
                 "error": True, "logs": data["logs"],
                 "epoch": (self.last_info or {}).get("epoch")}
            )
            reporter.reset()
        self._handle_final_reply(resp)
        return resp

    def finalize_lane(self, trial_id: str, metric, reporter, *,
                      lane: int, block: str, epoch=None, last: bool = False,
                      error: bool = False) -> Dict[str, Any]:
        """Send one lane's FINAL for a vectorized K-lane block. Every lane
        gets its own FINAL; only the ``last`` one releases the partition
        (the server skips the assignment clear and the piggybacked
        hand-off for the others) and resets the reporter. ``epoch`` is the
        LANE trial's run epoch (stamped per lane in the block's TRIAL
        info) — the leader's epoch would let a stale lane FINAL through
        the driver's epoch guard."""
        with reporter.lock:
            data = reporter.get_data() if last else {"logs": []}
            payload = {"type": "FINAL", "trial_id": trial_id,
                       "value": None if error else metric,
                       "logs": data.get("logs") or [],
                       "epoch": epoch,
                       "lane": int(lane), "block": block,
                       "last": bool(last)}
            if error:
                payload["error"] = True
            resp = self._request(payload)
            if last:
                reporter.reset()
        if last:
            self._handle_final_reply(resp)
        return resp

    def preempt_ack(self, trial_id: str, reporter,
                    step: Optional[int] = None) -> Dict[str, Any]:
        """Acknowledge a scheduler preemption: FINAL flagged ``preempted``
        with the trial's last checkpoint ``step`` (None = it never
        checkpointed; the driver requeues from scratch). Routed through
        the same reply handling as finalize_metric so an evicted runner's
        GSTOP — or a surviving runner's piggybacked next assignment —
        lands the same way."""
        with reporter.lock:
            data = reporter.get_data()
            resp = self._request(
                {"type": "FINAL", "trial_id": trial_id, "value": None,
                 "preempted": True,
                 "step": int(step) if step is not None else None,
                 "logs": data["logs"]}
            )
            reporter.reset()
        self._handle_final_reply(resp)
        return resp

    def get_progress(self) -> Dict[str, Any]:
        return self._request({"type": "LOG"})

    def stop(self) -> None:
        self._hb_stop.set()
        if self._hb_thread is not None:
            self._hb_thread.join(timeout=2)
        stats = self.runner_stats
        if stats is not None:
            # Last-gasp stats flush: the final trial's pending records
            # (e.g. its ``compile_events`` ttfm breakdown, finalized at
            # trial end) would otherwise wait for a heartbeat that never
            # comes — the GSTOP that ended the work loop also ends the
            # beats. Idle-beat shaped (trial_id None), so the driver
            # worker treats it like any other metric-free beat. ONE
            # attempt, no retry loop, and a short socket deadline: a
            # server that is already gone (or half-open after a severed
            # connection) must not stall shutdown — without the clamp the
            # 30 s request timeout applies to send AND recv.
            try:
                delta = stats.snapshot_delta()
                if delta:
                    msg = {"type": "METRIC", "trial_id": None,
                           "value": None, "step": None, "logs": [],
                           "span": None, "rstats": delta,
                           "partition_id": self.partition_id,
                           "task_attempt": self.task_attempt}
                    with self._lock:
                        self._sock.settimeout(2.0)
                        MessageSocket.send_msg(self._sock, msg, self.secret)
                        MessageSocket.recv_msg(self._sock, self.secret)
            except Exception:  # noqa: BLE001 - shutdown must not fail
                pass
        for sock in (self._sock, self._hb_sock):
            try:
                sock.close()
            except OSError:
                pass

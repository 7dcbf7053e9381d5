"""The journal vocabulary: every string the telemetry journal speaks.

One home for the stringly-typed contract between EMITTERS (``Telemetry.
trial_event`` / ``Telemetry.event`` call sites across the package) and
CONSUMERS (``spans.derive`` / ``replay_journal``, ``trace.py``,
``monitor``, ``chaos/harness.py`` invariants, ``fleet.replay_fleet_
journal``). An emitter typo used to vanish silently from replay,
Perfetto, and invariant checking all at once; the ``journalvocab``
checker (``python -m maggy_tpu.analysis``) now statically verifies

- every literal phase/kind/reason EMITTED appears here,
- every entry here is emitted somewhere (no orphan vocabulary), and
- every literal a CONSUMER matches against appears here (a consumer typo
  matches nothing — the worst kind of false green).

Extend the vocabulary here FIRST, then emit/consume. Entries are plain
frozensets so the checker (pure AST, no imports) can read them
literally: keep every entry a literal string in this file.
"""

from __future__ import annotations

#: Trial-span lifecycle + annotation phases (``ev: "trial"`` events).
#: Nominal order; see telemetry/spans.py for the semantics of each.
SPAN_PHASES = (
    "suggested", "queued", "assigned", "running", "first_metric",
    "stop_flagged", "stop_sent", "finalized", "lost", "requeued",
    "profile_skipped", "prefetch_hit", "prefetch_miss",
    "preempt_requested", "preempted", "resumed", "compiled",
    # Gang scheduling (maggy_tpu.gang): the trial's contiguous chip
    # block became fully held and the leader was dispatched / the
    # block's chips returned to the pool (fields: members, chips; the
    # pair brackets the trial's N-chip busy interval in replay_pack).
    "gang_assembled", "gang_released",
    # Checkpoint-forking search (docs/user.md "Forking search"): this
    # trial was dispatched to RESUME from another trial's checkpoint —
    # an ASHA promotion continuing its rung parent, a PBT exploit
    # copying the winner, a BO near-duplicate warm start. Fields:
    # parent (the source trial id), step (the checkpoint step forked
    # from), partition. The genealogy edge trace.py renders as a
    # parent→child Perfetto flow arrow and derive()'s fork block counts
    # steps_saved from.
    "forked_from",
    # Runner-measured checkpoint I/O totals for one trial, shipped once
    # at trial end through the heartbeat stats channel (mirrors
    # "compiled"). Fields: save_ms, restore_ms, saves, restores,
    # partition. The goodput ledger's ckpt_save / ckpt_restore badput
    # buckets fold from this record.
    "ckpt_saved",
)

#: Top-level journal event kinds (the ``ev`` field).
EVENT_KINDS = frozenset({
    "trial",                  # span phase occurrence (phase in SPAN_PHASES)
    "suggest",                # controller suggest() latency sample
    "runner_stats",           # heartbeat-piggybacked runner stats delta
    "runner",                 # trial-runner lifecycle (phase: RUNNER_PHASES)
    "worker",                 # dist-worker lifecycle (phase: WORKER_PHASES)
    "experiment",             # experiment lifecycle (phase: EXPERIMENT_PHASES)
    "prefetch_invalidated",   # schedule-stale prefetches dropped
    "chaos",                  # one fault injection
    "chaos_armed",            # chaos engine armed for the experiment
    "chaos_summary",          # end-of-experiment injection tally
    "health",                 # health engine finding / lifecycle
    "fleet",                  # fleet lifecycle (phase: FLEET_PHASES)
    "fleet_submit",           # experiment submitted to the fleet
    "fleet_admit",            # experiment admitted past the queue
    "fleet_experiment",       # per-experiment fleet lifecycle
    "lease",                  # runner lease start/end (phase: LEASE_PHASES)
    "preempt",                # fleet preemption decision
    "pack",                   # gang placer decision (op: init/reserve/
                              #   stall/release — maggy_tpu.gang)
    "obs_started",            # observability server bound (host, port) —
                              #   journaled so tools can discover an
                              #   ephemeral (port 0) bind
    "profile_captured",       # device profile + thread dump artifact
                              #   written (path, reason: manual|auto,
                              #   check, partition — telemetry.profiling)
    "shed",                   # load shed: an admission refused at the
                              #   fleet's max_queued bound (scope=
                              #   "admission", fleet journal) or a frame+
                              #   connection dropped at a tenant's full
                              #   dispatch queue (scope="rpc", tenant
                              #   journal) — rpc.SharedServer /
                              #   fleet.FleetScheduler
    "agent",                  # remote fleet-agent lifecycle (phase:
                              #   AGENT_PHASES — fleet journal lane per
                              #   agent; maggy_tpu.fleet.agent)
    "jsink",                  # journal-sink ingest record: one JSINK
                              #   batch demuxed into a per-source
                              #   segment (source, n, dup, sid, lag_ms —
                              #   fleet journal; telemetry/sink.py)
    "sink_degraded",          # a source's shipper lost the sink and
                              #   fell back to its local journal
                              #   (telemetry/sink.py SinkJournal)
    "sink_recovered",         # the shipper reconnected; the spooled
                              #   suffix re-ships (sid-deduped)
    "clock_offset",           # RTT-bounded clock-offset estimate for
                              #   one agent vs the fleet host (offset_s,
                              #   rtt_s — Cristian's algorithm over the
                              #   AJOIN/ALEASE exchange; journaled
                              #   fleet-side per agent and agent-side)
    "driver_epoch",           # driver incarnation boundary: a (re)started
                              #   driver journals the epoch it claimed via
                              #   util.claim_driver_epoch — the seam
                              #   crash-only recovery and invariant 13
                              #   split a multi-incarnation journal on
    "ckpt_gc",                # checkpoint garbage collection: a parent
                              #   rung's checkpoint dir retired once no
                              #   live or schedulable child can still
                              #   fork from it (trial, parent of no one
                              #   pending — fields: trial, why; bounds
                              #   disk growth of forking sweeps)
})

#: ``reason=`` on a trial ``requeued`` phase: why it re-entered the
#: schedule.
REQUEUE_REASONS = frozenset({
    "blacklist",        # executor died and re-registered (BLACK path)
    "heartbeat_loss",   # runner went silent holding the trial (LOST path)
    "dead_partition",   # fresh suggestion rerouted off a dead runner
    "preempted",        # graceful scheduler preemption (resume-capable)
    "gang_member_lost",  # a gang member died: whole lease revoked, the
                         # trial reassembles a fresh gang (exactly once)
    "fork_source_lost",  # a forked trial's staged checkpoint AND its
                         # parent's vanished before re-dispatch (disk
                         # loss / raced GC): the fork is downgraded to a
                         # from-scratch run — journaled so genealogy
                         # shows the downgrade instead of a silent
                         # restart-at-0
    "vmap_block_lost",   # a vectorized block's runner died (LOST/BLACK)
                         # or its leader was preempted: every live lane
                         # requeues exactly once as an individual scalar
                         # trial (chaos invariant 16 — no phantom FINALs,
                         # no lane lost to the block seam)
})

#: ``reason=`` on a ``profile_captured`` event: what triggered the
#: capture — an operator /profilez request or the health engine's
#: first-flag auto-capture hook (telemetry/profiling.py).
PROFILE_REASONS = frozenset({"manual", "auto"})

#: ``phase=`` per non-trial event kind.
#: ``recovered`` = crash-only recovery rebuilt the control plane from
#: the journal (trial store + reservations + controller state); fields
#: carry the reconstruction counts (inflight, adopted_partitions, ...).
EXPERIMENT_PHASES = frozenset({"start", "resumed", "recovered",
                               "finalized", "end"})
#: ``adopted`` = a pre-crash runner's first message re-bound it to the
#: restarted driver (JOIN resume path / heartbeat / retried FINAL).
RUNNER_PHASES = frozenset({"registered", "adopted"})
WORKER_PHASES = frozenset({"registered", "finalized"})
FLEET_PHASES = frozenset({"start", "stop"})
#: fleet_experiment mirrors the scheduler entry states.
FLEET_EXPERIMENT_PHASES = frozenset({"start", "done", "failed"})
LEASE_PHASES = frozenset({"start", "end"})
#: ``reason=`` on a lease ``end``. ``agent_lost`` = the remote agent
#: serving the lease went silent past the liveness bound mid-lease (the
#: fleet revoked it; the experiment's own slot-reclaim liveness requeues
#: the trial exactly once).
LEASE_END_REASONS = frozenset({"released", "error", "agent_lost"})
#: ``phase=`` on an ``agent`` event: one remote agent's lifecycle in the
#: fleet journal — join (AJOIN admitted), lease (ABIND delivered), done
#: (ADONE received, lease closed), lost (silent past the liveness
#: bound), leave (orderly exit / fleet shutdown).
AGENT_PHASES = frozenset({"join", "lease", "done", "lost", "leave"})

#: Chaos fault kinds — the ``kind=`` field of ``ev: "chaos"`` injection
#: records (mirrors chaos/plan.py KINDS; the chaos plan validates kinds
#: at build time, this copy lets replay/trace/invariant consumers be
#: checked without importing the chaos engine).
CHAOS_KINDS = frozenset({
    "kill_runner", "stall_runner", "fake_preemption", "preempt_trial",
    "kill_gang_member",
    "drop_msg", "delay_msg", "sever_conn", "env_write_fail",
    # Fleet scale soak (fleet/soak.py run_slow_tenant_soak): one tenant's
    # handlers artificially delayed — the head-of-line-isolation fault.
    # Injected by the soak harness (not a plan.py fault kind): it wraps
    # ONE experiment's handle_message, which per-verb plan targeting
    # cannot express (partition ids overlap across tenants).
    "slow_tenant",
    # Agent soak (fleet/soak.py run_agent_soak): a remote agent process
    # SIGKILLed mid-lease — invariant 11 (lease revoked, trial requeued
    # exactly once). Harness-injected like slow_tenant: the chaos plan's
    # pool-level kill cannot reach an agent in another OS process.
    "kill_agent",
    # Sink soak (fleet/soak.py run_sink_soak): the fleet's journal-sink
    # tenant detached mid-soak — invariant 12 (shippers degrade to local
    # journals, re-ship on reconnect, zero lost / zero duplicate events
    # per event id, zero experiment failures). Harness-injected: the
    # sink is fleet infrastructure, not an experiment-plan target.
    "kill_sink",
    # Driver soak (chaos/driver_soak.py run_driver_soak): the DRIVER
    # process SIGKILLed mid-sweep and restarted with resume — invariant
    # 13 (journal replay rebuilds the control plane; no trial lost, no
    # duplicate FINAL, completed trials never re-run, the sweep
    # completes on survivors). Harness-injected: the fault kills the
    # process that owns the chaos engine, so no in-process plan can
    # record it — the soak appends the record to the quiesced journal.
    "kill_driver",
    # Fork soak (chaos/harness.py run_fork_soak, `--fork`): the runner
    # a forked trial was just dispatched to is killed (plan kind, fired
    # on_phase=forked_from) — invariant 14: exactly-once requeue
    # resuming from the SAME fork point, genealogy intact.
    "kill_fork",
})

#: The goodput ledger's closed chip-time taxonomy (telemetry/goodput.py):
#: every held runner-second folds into exactly one bucket. ``train`` is
#: goodput; everything else is badput; ``unaccounted`` is the explicit
#: residual the bench gate bounds (never silently absorbed into another
#: bucket). Order is the canonical reporting order.
GOODPUT_BUCKETS = (
    "train",          # inside train_fn, productive (first-run) steps
    "init",           # sharded state init (compiled record init_ms)
    "trace",          # jaxpr trace (compiled record trace_ms)
    "compile",        # XLA compile (compiled record compile_ms)
    "ckpt_save",      # checkpoint writes (ckpt_saved record save_ms)
    "ckpt_restore",   # checkpoint reads (ckpt_saved record restore_ms)
    "fork_stage",     # parent-checkpoint staging (fork_load_ms)
    "rework",         # re-trained work: dead attempts + from-scratch
                      #   promotions re-running the parent prefix
    "handoff",        # FINAL -> next running gap (< HANDOFF_CAP_S)
    "queue_wait",     # runner registered -> first trial running
    "idle",           # reserved but trial-less (rung barriers, drain)
    "lane_idle",      # vectorized blocks (config.vmap_lanes): a masked
                      #   (early-stopped) lane's share of block chip-time
                      #   after its own FINAL while surviving lanes kept
                      #   training — the price of lockstep execution
    "unaccounted",    # residual the accounting could not attribute
)

#: Names `telemetry.runnerstats.span` is opened with. SPAN_NAMES are the
#: per-trial phases it also records as ``[name, t_start, t_end]`` in the
#: ``spans`` of the trial's ``compiled`` / ``ckpt_saved`` record (``trial``
#: is the span around ``train_fn`` that the others lie in; ``fork_stage``
#: precedes it). ANNOTATION_NAMES are the per-step host annotations that
#: exist only in a profiler trace.
SPAN_NAMES = (
    "trial", "init", "trace", "compile", "fork_stage", "ckpt_save",
    "ckpt_restore",
)
ANNOTATION_NAMES = ("place_batch", "train_step", "report")

#: What a trial's ``compiled`` record carries beside the ``*_ms`` sums of
#: its spans and the ``spans`` themselves (`RunnerStats.note_compile`; first
#: write wins): ``warm`` (the trial found a resident program), ``forked``
#: (it resumed a staged parent checkpoint), ``vmap_lanes`` (lanes of a
#: vectorized block), ``first_dispatch`` (epoch seconds of the trial's
#: first step dispatch) and ``flash_plan``: the tiles of the Pallas flash
#: kernels in the step program this trial traced, as
#: `ops.attention.FlashPlan.describe` writes them (``fwd q512 k512 h4;
#: dkdv q512 k512 h4; dq q512 k512 h4``: per kernel the query and key tile
#: and the heads a grid step covers; several shapes in one program are
#: joined by `` | ``; under a mask description the plan also says the mask
#: kind, its block length, per kernel the tiles of a head that run of
#: those there are, and what each kernel's grid walks for them (steps that
#: run a tile + steps that only open and close an accumulator, and the
#: tiles that need the index mask): ``; block_diffusion b4 L4096 tiles fwd
#: 80/256 dkdv 80/256 dq 80/256; walk fwd 80+0 (24 partial) dkdv 80+0 (24
#: partial) dq 80+0 (24 partial)``). Absent where the trial traced
#: nothing (a warm trial) or the program holds no flash kernel.
#: ``moe_plan``: what each
#: dropless expert layer (`models.moe.ExpertShareMLP`) holds and how it
#: multiplies (``experts 0+16/128 top8 rows 139264 chunk 2048 tile 512
#: pallas_gmm``: first expert + experts held / routed over, pairs a token,
#: the index buffer's rows, the rows a chunk handles, the row tile, what
#: multiplies the groups). ``moe_ops``: ``{scope: [HLO instruction names]}``
#: of the step executable for the layer's `jax.named_scope`s
#: (``moe_routing``, ``moe_dispatch``, ``moe_experts``, ``moe_combine``,
#: and ``moe_shared`` where the layer has a shared expert; the plan then
#: ends ``sigmoid+bias x2.5 relu2 shared 3712``: the scoring and its scale,
#: the expert kind, the shared expert's width), by which a trace's
#: operations are told apart. ``ssm_plan``: what each state-space mixer
#: (`models.nemotron_h.Mamba2Mixer`) scans (``heads 64x64 groups 8 state 128
#: conv 4 chunk 128 S 8192 pallas 1024 conv pallas 512x1024``: heads x
#: channels, groups, states, convolution taps, the scan's chunk, the length,
#: what multiplies: on a TPU, at shapes they tile, the `ops.ssd` kernels and
#: the positions one of their grid steps holds; elsewhere ``xla_products``;
#: then what convolves: ``conv pallas`` and the block of `ssd_conv_fwd` and
#: `ssd_conv_bwd`, rows by channels, or ``conv xla``);
#: ``ssm_ops``: the step's instructions under the mixer's scopes
#: (``ssm_proj``, ``ssm_conv``, ``ssm_scan``, ``ssm_gate_norm``).
#: ``loop_plan``: what a looped model (`models.ouro.Ouro`) runs (``4 passes
#: x 6 layers heads 16x128 S 4096 head chunks vocab 4096 x 12 over 32768
#: rows``: passes x layers held, the attention heads, the length, and the
#: fused head-and-loss's chunks over the exits' rows); ``loop_ops``: the
#: step's instructions under the model's scopes (``loop_attn``,
#: ``loop_mlp``, ``exit_norm``, ``exit_gate``, ``exit_head``), a layer's
#: once a pass and the head's once a chunk.
#: ``remat_plan``: what a
#: model whose layers are rematerialised keeps of each layer beside its
#: input, by the names the parts give those values (``layer keeps
#: flash_out flash_lse moe_route``, `models.sdar`: the forward kernel and
#: the routing then run once a step and not again in the backward pass;
#: `models.nemotron_h` says ``block keeps flash_out flash_lse moe_route
#: ssd_out``, the scan kernel's output with them);
#: absent where the traced model keeps nothing by name. Plans and scopes
#: reach the record through `telemetry.plans` (``<kind>_plan``,
#: ``<kind>_ops``), and the vocabulary checker holds these entries to the
#: `remember_plan` calls.
#: ``step_ops`` and ``step_mixed`` (`plans.STEP_FIELDS`, for every program
#: `Trainer` compiles, whether a kind spoke or not): every device operation
#: of the step program under exactly one **part** ``"<scopes>:<pass>"``,
#: the `STEP_SCOPES` on its ``op_name`` path outermost first joined by
#: ``/`` (``unscoped`` where it holds none) and ``fwd``, ``bwd``, ``remat``
#: (a forward pass made again in the backward one) or, under ``optimizer``,
#: ``update``. ``step_ops`` = ``{part: [HLO instruction names]}``: plain
#: operations, and the fusions whose bodies hold one part. ``step_mixed`` =
#: ``{instruction: [[part, flops, bytes], ...]}``: the fusions whose bodies
#: hold several (a weight's gradient product fused with that weight's
#: update), first the part of the fusion's own name, with each part's
#: product FLOPs and directly read and written bytes by the text's shapes,
#: raw: a reader divides the fusion's measured time by them
#: (`hlo_scopes.Program.step_parts`; ``docs/telemetry.md``).
COMPILED_FIELDS = ("warm", "forked", "vmap_lanes", "first_dispatch",
                   "flash_plan", "moe_plan", "moe_ops", "remat_plan",
                   "ssm_plan", "ssm_ops", "loop_plan", "loop_ops",
                   "step_ops", "step_mixed")

#: Every `jax.named_scope` the package opens, a closed list (this module
#: imports no model; the vocabulary checker holds the list to the
#: `named_scope` calls and to the models' ``*SCOPES`` tuples, both ways). A
#: scope is a component of the ``op_name`` of every operation traced under
#: it, and with it of the operation's part in ``step_ops``.
#: ``loss_and_grad`` is the frame around the model's passes and names no
#: part; ``optimizer`` is a part whose pass is ``update``.
STEP_SCOPES = (
    "loss_and_grad", "optimizer",     # train/trainer.py, the step's halves
    "loss",                           # ... the loss function and its sum
    "embed",                          # token / patch and position embedding
    "attn",                           # an attention sub-layer's norm,
                                      #   projections, rope and residual
    "attention",                      # ops/attention.py, inside ``attn``
    "mlp",                            # models/bert.py, the dense sub-layer
    "block",                          # a block's norm and residual where
                                      #   the mixer names its own parts
    "head",                           # final norm and the head's product
    "chunked_ce", "weighted_ce",      # ops/losses.py
    "moe_routing", "moe_dispatch", "moe_experts", "moe_combine",
    "moe_shared",                     # models/moe.py SCOPES, SHARED_SCOPE
    "ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm",  # ops/ssd.py
    "loop_attn", "loop_mlp", "exit_norm", "exit_gate",
    "exit_head",                      # models/ouro.py LOOP_SCOPES
)

#: Health-engine event fields (``ev: "health"``).
HEALTH_STATUSES = frozenset({"raised", "cleared", "started", "error"})
HEALTH_CHECKS = frozenset({"engine", "straggler", "hb_rtt", "hang"})

#: Everything a consumer may match a ``phase`` field against — the union
#: the journalvocab checker verifies consumer literals into.
ALL_PHASES = (frozenset(SPAN_PHASES) | EXPERIMENT_PHASES | RUNNER_PHASES
              | WORKER_PHASES | FLEET_PHASES | FLEET_EXPERIMENT_PHASES
              | LEASE_PHASES | AGENT_PHASES)
ALL_REASONS = REQUEUE_REASONS | LEASE_END_REASONS | PROFILE_REASONS

__all__ = [
    "SPAN_PHASES", "EVENT_KINDS", "REQUEUE_REASONS", "PROFILE_REASONS",
    "GOODPUT_BUCKETS", "SPAN_NAMES", "ANNOTATION_NAMES", "COMPILED_FIELDS",
    "STEP_SCOPES",
    "EXPERIMENT_PHASES", "RUNNER_PHASES", "WORKER_PHASES",
    "FLEET_PHASES", "FLEET_EXPERIMENT_PHASES", "LEASE_PHASES",
    "LEASE_END_REASONS", "AGENT_PHASES", "CHAOS_KINDS",
    "HEALTH_STATUSES", "HEALTH_CHECKS", "ALL_PHASES", "ALL_REASONS",
]

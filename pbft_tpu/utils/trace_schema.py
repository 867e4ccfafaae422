"""THE schema manifest for trace events and metrics — single source of truth.

pbftd (core/net.cc) and the Python processes round it (net/service.py,
net/verify_service.py, net/gateway.py, net/client.py) emit JSONL trace
events and Prometheus metrics that one set of scripts merges and one
scrape aggregates, so their names and field sets are fixed here. This
module is the contract; scripts/check_trace_schema.py lints every emitter
against it
(wired into tier-1 via tests/test_trace_schema.py), and core/metrics.cc
mirrors the metric table (checked by the same lint).

Event schema entries:
    required  fields every event of this name must carry
    optional  fields an emitter may add
    emitters  the source files allowed to emit this event name

Changing an event or metric here without updating every emitter (or vice
versa) fails the lint — that is the point.
"""

from __future__ import annotations

# -- trace events (JSONL lines: {"ts": .., "ev": <name>, ...fields}) --------

EVENT_SCHEMAS = {
    # The verify service's line is one LAUNCH, and carries its stages, all
    # on time.monotonic(): queue_s (window cut minus the arrival of the
    # oldest request in it), slot_s (launch slot acquired minus window
    # cut), pending_at_cut / pending_at_launch (items queued at those two
    # moments), cut_full (0/1: the window was cut at service.MAX_WINDOW with
    # requests left queued behind it, i.e. pending_at_cut above 0), hold_s
    # (the hold for company the window was granted at its cut, 0 where
    # none) and the 0/1 pair held_out (cut because that hold ran out) /
    # in_step (cut early because nobody in step was still out), and
    # block_items (the items of the window whose rows reached an executable
    # as the blocks they came off the wire as: all of them where the sharded
    # engine took the dispatcher's window, 0 where a backend took the items
    # as a list of triples, i.e. a host verifier or the fallback before ready);
    # and, where the sharded engine ran it, the engine's five
    # steps summed over chunks (pad_s, put_s, dispatch_s, wait_s, unpack_s:
    # they add up to secs), rung (the padded slots the chunks really ran
    # at), promoted (chunks run on a larger shape than the smallest that
    # fits, by the engine's serving table), chunks (executables run for the
    # window) with the 0/1 field split (more than one: the engine's chunk
    # plan, or a window beyond the largest shape), t_dev (absolute stamp
    # at the first dispatch), devices (the chips the window's executables
    # are sharded over, as their input sharding said at warm-up),
    # rows_per_chip (the slots of the window's smallest chunk over devices)
    # and fused (the share of rung that ran on executables whose multiply
    # chains live in VMEM, 0 to 1: warm_stats.per_shape[].chains "vmem").
    # pbftd's line is one BATCH of one replica, written when its verdicts
    # have been worked through; its 0/1 field ahead says the next batch was
    # launched before this one's verdicts were applied, apply_s (kept spans
    # only) how long working through them took (pbft_verdict_apply_seconds'
    # reading, ending at ts), and loop_us the loop clock's seven running
    # totals at ts, microseconds, in LOOP_STAGES' order: two lines of one
    # replica bracket an interval with its split by kind of work
    # (scripts/trace_report.py, against verifyd's t_dev on the same clock).
    # With --net-threads above 1 the line also carries shard_us and pipe_us
    # (the front-end threads' running totals summed over the replica's
    # shards / pipelines, SHARD_STAGES' / PIPE_STAGES' order) and handoff
    # ([drains observed, seconds]: pbft_shard_handoff_seconds so far).
    "verify_batch": {
        "required": {"ts", "ev", "replica", "size", "rejected", "secs"},
        "optional": {
            "view", "executed", "requests",
            "queue_s", "slot_s", "pending_at_cut", "pending_at_launch",
            "hold_s", "held_out", "in_step", "cut_full", "block_items",
            "pad_s", "put_s", "dispatch_s", "wait_s", "unpack_s", "rung", "promoted",
            "chunks", "split", "t_dev", "devices", "rows_per_chip", "fused", "ahead",
            "apply_s", "loop_us", "shard_us", "pipe_us", "handoff",
        },
        "emitters": {"service.py", "net.cc"},
    },
    "verify_window_failed": {
        "required": {"ts", "ev", "replica", "size", "requests", "rejected", "secs"},
        "optional": set(),
        "emitters": {"service.py"},
    },
    "verify_batch_error": {
        "required": {"ts", "ev", "replica", "size", "secs"},
        "optional": set(),
        "emitters": {"service.py"},
    },
    # What the chip process does in a stall (ISSUE 38; ROADMAP A7): ONE
    # record for a launch in flight longer than service.STALL_S, written by
    # a watcher thread while the launch still hangs: its size, rung (None
    # until the engine has said), age, the launch thread's id, every Python
    # thread's innermost frames (stacks), every OS thread's [tid, name,
    # state, wchan] from /proc/self/task (tasks: the runtime's own threads
    # are there), and what the backend's owner can say about its device
    # (memory: every local device's memory_stats(); None on a bare
    # service). launch_stall_ended follows when the launch returns, with
    # its whole length. Also on stderr, with or without --trace.
    "launch_stalled": {
        "required": {"ts", "ev", "replica", "size", "age_s", "thread", "stacks", "tasks"},
        "optional": {"rung", "memory"},
        "emitters": {"service.py"},
    },
    "launch_stall_ended": {
        "required": {"ts", "ev", "replica", "size", "secs"},
        "optional": set(),
        "emitters": {"service.py"},
    },
    "view_change_start": {
        "required": {"ts", "ev", "replica", "pending_view", "backoff"},
        "optional": set(),
        "emitters": {"net.cc"},
    },
    # One span per executed (view, seq): absolute monotonic stamps for each
    # consensus phase this replica observed. "request" is primary-only (a
    # backup's first sighting is the pre-prepare); stamps are comparable
    # across processes on one host (CLOCK_MONOTONIC is per-boot).
    "consensus_span": {
        "required": {"ts", "ev", "replica", "view", "seq", "pre_prepare", "executed"},
        "optional": {"request", "prepared", "committed"},
        "emitters": {"net.cc"},
    },
    # The wedged-async-verifier bound: the
    # inflight launch overran its deadline, the connection was dropped and
    # the batch re-verified on the CPU safety net.
    "verify_deadline_fired": {
        "required": {"ts", "ev", "replica", "size", "age_secs"},
        "optional": set(),
        "emitters": {"net.cc"},
    },
    # Tentative mode (ISSUE 32): the committed floor passed a sequence
    # number this replica had executed at PREPARED, lag_s after it did. A
    # line of its own, because the span closed at "executed" and carries
    # no "committed" stamp (scripts/consensus_timeline.py prints the lag).
    "commit_lag": {
        "required": {"ts", "ev", "replica", "seq", "lag_s"},
        "optional": set(),
        "emitters": {"net.cc"},
    },
    # -- request-level latency waterfall (ISSUE 9) --------------------------
    #
    # Requests are uniquely keyed by (client, req_ts) and batches by
    # (view, seq); batch_sealed carries the [client, req_ts] pairs it
    # sealed, so client-side send/recv stamps join to replica-side
    # consensus spans purely in post-processing — zero wire changes
    # (scripts/consensus_timeline.py --waterfall).
    "request_rx": {
        "required": {"ts", "ev", "replica", "client", "req_ts"},
        "optional": set(),
        "emitters": {"net.cc"},
    },
    # The primary sealed its open batch under a sequence number. wait_s is
    # how long the first request sat in the open batch (the "batch wait"
    # waterfall segment); reqs is the ordered [[client, req_ts], ...] join
    # key list.
    "batch_sealed": {
        "required": {"ts", "ev", "replica", "view", "seq", "batch", "wait_s"},
        "optional": {"reqs"},
        "emitters": {"net.cc"},
    },
    "reply_tx": {
        "required": {"ts", "ev", "replica", "client", "req_ts", "view"},
        "optional": set(),
        "emitters": {"net.cc"},
    },
    # -- view-change spans (ROADMAP item 4) ---------------------------------
    #
    # view_timer_fired (the runtime's progress timer expired) ->
    # view_change_sent (the replica broadcast VIEW-CHANGE toward
    # pending_view) -> new_view_installed (it entered the view). Ordering
    # is machine-checked by consensus_timeline.py --check-invariants
    # (consensus/invariants.py check_view_events).
    "view_timer_fired": {
        "required": {"ts", "ev", "replica", "view", "backoff"},
        "optional": set(),
        "emitters": {"net.cc"},
    },
    "view_change_sent": {
        "required": {"ts", "ev", "replica", "pending_view"},
        "optional": set(),
        "emitters": {"net.cc"},
    },
    "new_view_installed": {
        "required": {"ts", "ev", "replica", "view"},
        "optional": set(),
        "emitters": {"net.cc"},
    },
    # Client-side half of the waterfall (net/client.py write_trace): send /
    # first-reply / f+1-quorum monotonic stamps per (client, req_ts).
    # Comparable to replica stamps on one host (CLOCK_MONOTONIC).
    # "overloaded" counts explicit admission-control rejections the client
    # absorbed for this request (ISSUE 12) — distinct from silent timeouts.
    "client_request": {
        "required": {"ts", "ev", "client", "req_ts", "send"},
        "optional": {"first_reply", "quorum", "overloaded"},
        "emitters": {"client.py"},
    },
}

# -- metrics (Prometheus text format at --metrics-port) ---------------------
#
# name -> (type, emitters). pbftd (net.cc) emits the full replica set; the
# verifier service emits the verify subset under the SAME names, the gateway
# its own.

METRIC_SCHEMAS = {
    "pbft_frames_in_total": ("counter", {"net.cc"}),
    "pbft_executed_total": ("counter", {"net.cc"}),
    "pbft_view_changes_total": ("counter", {"net.cc"}),
    "pbft_verify_batches_total": ("counter", {"service.py", "net.cc"}),
    "pbft_verify_items_total": ("counter", {"service.py", "net.cc"}),
    "pbft_verify_rejected_total": ("counter", {"service.py", "net.cc"}),
    "pbft_verify_deadline_fired_total": ("counter", {"net.cc"}),
    # Batches a replica verified on the host although a verify service is
    # configured: the service was warming, unreachable, killed mid-stream
    # or (pbftd) past its verify deadline. The fallback is the liveness
    # guarantee; the count is what keeps it from hiding a dead device.
    # metrics_json mirrors it as verify_service_fallbacks.
    "pbft_verify_service_fallbacks_total": ("counter", {"net.cc"}),
    "pbft_verify_queue_depth": ("gauge", {"service.py", "net.cc"}),
    "pbft_verify_inflight_age_seconds": ("gauge", {"net.cc"}),
    # Native verify-pool surface (core/verify_pool.cc): pool width, windows
    # queued by the last dispatch, lifetime busy/(wall*threads) ratio, and
    # the per-dispatch RLC window width.
    "pbft_verify_pool_threads": ("gauge", {"net.cc"}),
    "pbft_verify_pool_queue_depth": ("gauge", {"net.cc"}),
    "pbft_verify_pool_utilization": ("gauge", {"net.cc"}),
    "pbft_verify_pool_window_size": ("histogram", {"net.cc"}),
    # Wire-codec surface (ISSUE 3): the serialize-once invariant counter —
    # encodes are counted per BROADCAST (lazy, at most once per codec),
    # never per peer, so in a single-codec cluster
    # pbft_broadcast_encodes_total tracks the broadcast count instead of
    # broadcasts x peers. (The two outbound-frames-per-codec counters went
    # in ISSUE 38: nothing read them.)
    "pbft_broadcast_encodes_total": ("counter", {"net.cc"}),
    # Batching surface (ISSUE 4): requests executed vs three-phase
    # instances executed (their ratio is the batch amplification), and
    # the per-accepted-pre-prepare batch occupancy histogram. Note
    # pbft_executed_total counts per SEQUENCE (span closes), so it tracks
    # pbft_consensus_rounds_total, not requests.
    "pbft_requests_executed_total": ("counter", {"net.cc"}),
    "pbft_consensus_rounds_total": ("counter", {"net.cc"}),
    # Chaos/fault-injection surface (ISSUE 5): behaviors the --fault mode
    # actually fired (corrupted signatures, equivocating pre-prepares,
    # muted sends, stutter replays) and outbound frames the seeded
    # --chaos-drop-pct link dropped. Both zero on a healthy replica — a
    # nonzero value in production is an alarm, in a chaos test it is the
    # proof the injection ran.
    "pbft_faults_injected_total": ("counter", {"net.cc"}),
    "pbft_chaos_dropped_total": ("counter", {"net.cc"}),
    # Persistent verify-service surface (ISSUE 7): XLA launches the
    # coalescing dispatcher actually shipped, items per launch window,
    # and how many client connections each merged window carried. The
    # warm/cold compile gauges record the once-per-deploy startup cost
    # (cold = traced+compiled shapes, warm = serialized-executable or
    # cache reloads) so the bench can report it OUTSIDE the timed
    # region. Registered in core/metrics.cc too (eager registration:
    # zero-valued where the lifecycle can't happen).
    "pbft_verify_service_launches_total": (
        "counter",
        {"service.py", "net.cc"},
    ),
    "pbft_verify_service_window_size": (
        "histogram",
        {"service.py", "net.cc"},
    ),
    "pbft_verify_service_coalesced_clients": (
        "histogram",
        {"service.py", "net.cc"},
    ),
    "pbft_verify_service_cold_compile_seconds": (
        "gauge",
        {"verify_service.py", "net.cc"},
    ),
    "pbft_verify_service_warm_compile_seconds": (
        "gauge",
        {"verify_service.py", "net.cc"},
    ),
    # Scale-out surface (ISSUE 10). Replica side: live sockets, event-loop
    # readiness wakeups (epoll_wait/poll returns), bounded-outbound drops
    # + partial-write backpressure episodes, and client requests received over gateway
    # links. Gateway side (pbft_tpu/net/gateway.py): downstream client
    # connections open and requests forwarded upstream — the tier's
    # multiplexing ratio is gateway_clients_open vs the replicas'
    # connections_open.
    "pbft_connections_open": ("gauge", {"net.cc"}),
    "pbft_epoll_wakeups_total": ("counter", {"net.cc"}),
    "pbft_write_backpressure_events_total": (
        "counter",
        {"net.cc", "gateway.py"},
    ),
    "pbft_gateway_clients_open": ("gauge", {"gateway.py"}),
    "pbft_gateway_forwarded_total": (
        "counter",
        {"gateway.py", "net.cc"},
    ),
    # The gateway works by the read (ISSUE 33): one write a destination a
    # loop turn, so messages a write = (forwarded + replies routed) /
    # writes says how many messages a flush carried (1.0 before it).
    "pbft_gateway_writes_total": ("counter", {"gateway.py"}),
    # Perf-under-faults surface (ISSUE 12). Backoff level: the view
    # timer's current exponential multiplier (1 = fresh, doubles per
    # consecutive no-progress expiry, §4.5.2) — a sustained high level is
    # a cluster failing to converge. Overload rejections: client requests
    # answered with an explicit {"type":"overloaded"} instead of being
    # queued into the tail (admission control: per-client in-flight caps
    # + the global backlog watermark; gateway and pbftd).
    # Gateway failovers: a gateway-fabric link had to be replaced — a
    # client failing over to another gateway (GatewayClient), a gateway
    # re-dialing a dead replica link (ClientGateway), or a replica losing
    # a live gateway link (pbftd).
    "pbft_view_timer_backoff_level": ("gauge", {"net.cc"}),
    # Multi-core surface (ISSUE 13). Loop threads: event-loop shards the
    # replica runs (pbftd net_threads). Offload depth: aggregate
    # occupancy of the per-shard crypto-pipeline queues (AEAD seal/open + codec work held
    # off the loop threads). Cross-thread wakes: eventfd/pipe wakes
    # crossing the loop-shard / crypto-pipeline / consensus boundaries —
    # the handoff cost the sharding pays for its parallelism.
    "pbft_net_loop_threads": ("gauge", {"net.cc"}),
    "pbft_crypto_offload_queue_depth": ("gauge", {"net.cc"}),
    "pbft_cross_thread_wakes_total": ("counter", {"net.cc"}),
    "pbft_overload_rejections_total": (
        "counter",
        {"gateway.py", "net.cc"},
    ),
    "pbft_gateway_failovers_total": (
        "counter",
        {"gateway.py", "net.cc"},
    ),
    # Fast-path surface (ISSUE 14, protocol 1.3.0). MAC frames: outbound
    # normal-case frames authenticated by a per-link session-MAC vector
    # instead of hot-path signature verification (zero in signature mode
    # and against pre-1.3.0 peers). Tentative executions: sequences
    # executed at PREPARED (one commit round-trip early); rollbacks:
    # tentative sequences undone by a view change / certified-checkpoint
    # catch-up — nonzero rollbacks with zero client-visible divergence is
    # exactly the §5.3 story the chaos matrix checks.
    "pbft_mac_frames_total": ("counter", {"net.cc"}),
    "pbft_tentative_executions_total": ("counter", {"net.cc"}),
    "pbft_tentative_rollbacks_total": ("counter", {"net.cc"}),
    # Durable-recovery surface (ISSUE 15). WAL appends: records written
    # to the write-ahead log (votes, view transitions, stable
    # checkpoints); fsyncs: group-commit fsync syscalls (one per emit
    # boundary with pending records — NOT one per message; zero with
    # wal_fsync off); bytes: file bytes written (appends + compactions).
    # Recovery seconds: wall time of the last WAL replay + state
    # reinstall (gauge; 0 = this life started fresh).
    "pbft_wal_appends_total": ("counter", {"net.cc"}),
    "pbft_wal_fsyncs_total": ("counter", {"net.cc"}),
    "pbft_wal_bytes_total": ("counter", {"net.cc"}),
    "pbft_recovery_seconds": ("gauge", {"net.cc"}),
    # Health-introspection surface (ISSUE 16). Resource gauges a soak can
    # gate flat: resident set (/proc/self/statm x page size), open file
    # descriptors (/proc/self/fd entries), and the WAL file's on-disk
    # byte size (0 with WAL off). Progress gauges a stall detector can
    # watch: seconds since executed_upto last advanced (as observed at
    # scrape/refresh time) and the verify-inbox depth. All five refresh
    # lazily when the status/metrics surface is rendered — a dead-idle
    # replica pays nothing for them.
    "pbft_process_rss_bytes": ("gauge", {"net.cc"}),
    "pbft_open_fds": ("gauge", {"net.cc"}),
    "pbft_wal_disk_bytes": ("gauge", {"net.cc"}),
    "pbft_last_progress_seconds": ("gauge", {"net.cc"}),
    "pbft_inbox_depth": ("gauge", {"net.cc"}),
    "pbft_batch_size": ("histogram", {"net.cc"}),
    "pbft_verify_batch_size": ("histogram", {"service.py", "net.cc"}),
    "pbft_verify_seconds": ("histogram", {"service.py", "net.cc"}),
    "pbft_phase_pre_prepare_seconds": ("histogram", {"net.cc"}),
    "pbft_phase_prepare_seconds": ("histogram", {"net.cc"}),
    "pbft_phase_commit_seconds": ("histogram", {"net.cc"}),
    "pbft_phase_reply_seconds": ("histogram", {"net.cc"}),
    "pbft_request_reply_seconds": ("histogram", {"net.cc"}),
    # One verify trip, the replica's share (pbftd only). Inbox wait: observed
    # once per verify batch at launch, launch time minus the arrival of the
    # oldest item no earlier launch took (an item that arrives while a batch
    # is in flight waits out that whole trip). WAL flush: round one
    # group-commit flush (write + fsync) that had records pending.
    "pbft_verify_inbox_wait_seconds": ("histogram", {"net.cc"}),
    "pbft_wal_flush_seconds": ("histogram", {"net.cc"}),
    # The order of a pass on the async branch (pbftd only; ISSUE 37): the
    # verifier's event reads a batch's verdicts and keeps them, the pass's
    # end launches the span of the inbox behind that batch and only then
    # works through the kept verdicts. Launched ahead: launches made while
    # a span of verdicts was kept (over pbft_verify_batches_total: how
    # often a trip runs behind the replica's own pass; /status:
    # verify_launched_ahead). Verdict held: once a batch on the async
    # branch, whether or not a launch went ahead, from the verdicts read
    # to their delivery beginning (one clock read a batch, none a message).
    "pbft_verify_launched_ahead_total": ("counter", {"net.cc"}),
    "pbft_verdict_held_seconds": ("histogram", {"net.cc"}),
    # The span that closes the verify cycle (pbftd only; ISSUE 38): once a
    # KEPT batch, from the delivery of its verdicts beginning (the clock
    # read that ends pbft_verdict_held_seconds) to deliver_verified
    # returning: dispatch, execute, sign, WAL flush, sends for one batch's
    # verdicts. One more clock read a batch; /status: verify_apply.
    "pbft_verdict_apply_seconds": ("histogram", {"net.cc"}),
    # The net loop's stage clock (pbftd only; ISSUE 38; core/net.h
    # LoopClock): exclusive wall time of the loop thread by kind of work,
    # microseconds, nested (a frame is read, authenticated, dispatched,
    # executed, signed, flushed and answered inside one handle_readable
    # call): wait (inside the poller's wait), read (socket reads, frame
    # decode, link authentication, accepts), protocol (every call into
    # Replica, its timers), wal (flush_wal with records pending), send
    # (emit after the flush: encode, MAC tags, queue, send(), reply
    # dial-backs), verify (the verify inbox: pending_items, the begin_batch
    # write, the blocking verify, the verdicts' read, a batch's
    # accounting), other (the rest of a pass: scrapes, sweeps, discovery).
    # The seven sum to pbft_loop_us_total to the microsecond and to the
    # thread's elapsed CLOCK_MONOTONIC time; pbft_epoll_wakeups_total is
    # the passes they were spent in. One clock read a stage switch; the
    # registry is brought up to date where a scrape or /status is rendered
    # (fold_counters), never on a per-frame path. With --net-threads above
    # 1 the clock covers the consensus thread alone. /status: loop_us.
    "pbft_loop_us_total": ("counter", {"net.cc"}),
    "pbft_loop_wait_us_total": ("counter", {"net.cc"}),
    "pbft_loop_read_us_total": ("counter", {"net.cc"}),
    "pbft_loop_protocol_us_total": ("counter", {"net.cc"}),
    "pbft_loop_wal_us_total": ("counter", {"net.cc"}),
    "pbft_loop_send_us_total": ("counter", {"net.cc"}),
    "pbft_loop_verify_us_total": ("counter", {"net.cc"}),
    "pbft_loop_other_us_total": ("counter", {"net.cc"}),
    # The multi-core front end's own clocks (pbftd only; ISSUE 40;
    # core/net_shard.h FrontClock): with --net-threads N each of the N
    # shard threads and N pipeline threads runs one LoopClock of its own
    # (one clock read where the stage changes) and publishes it once a
    # pass; the consensus thread sums them over a replica's shards /
    # pipelines where a scrape or /status is rendered. Shard: wait (the
    # poller), read (recv, framing, the link prologue, accepts), send
    # (queue_bytes, flush, the send() calls), other. Pipeline: wait (its
    # condition variable), decode (open, parse, signable, MAC check, the
    # push to the consensus inbox), encode (encode, MAC tags, seal, frame,
    # the push to the shard), other. Each four sum to the threads' elapsed
    # time since the clocks came on. /status: shard_us, pipe_us (a list,
    # one object a thread). All zero at --net-threads 1.
    "pbft_shard_wait_us_total": ("counter", {"net.cc", "net_shard.cc"}),
    "pbft_shard_read_us_total": ("counter", {"net.cc", "net_shard.cc"}),
    "pbft_shard_send_us_total": ("counter", {"net.cc", "net_shard.cc"}),
    "pbft_shard_other_us_total": ("counter", {"net.cc", "net_shard.cc"}),
    "pbft_pipe_wait_us_total": ("counter", {"net.cc", "net_shard.cc"}),
    "pbft_pipe_decode_us_total": ("counter", {"net.cc", "net_shard.cc"}),
    "pbft_pipe_encode_us_total": ("counter", {"net.cc", "net_shard.cc"}),
    "pbft_pipe_other_us_total": ("counter", {"net.cc", "net_shard.cc"}),
    # The hand-off pipeline -> consensus thread: once a drain of the shard
    # inbox that found something, the drain's instant minus the push of
    # the OLDEST entry it took (a push that finds its queue empty stamps
    # it: one clock read a drain on each side, none a message).
    "pbft_shard_handoff_seconds": ("histogram", {"net.cc", "net_shard.cc"}),
    # Messages lost at a thread boundary (a full pipeline queue or an
    # over-budget connection, a full consensus inbox, a reply's dial-back
    # refused or expired in a shard); /status: shard_dropped, by kind. A
    # healthy run reads 0: a run that drops votes between its own threads
    # is a slower protocol, not a faster front end.
    "pbft_shard_dropped_total": ("counter", {"net.cc", "net_shard.cc"}),
    # One flush a connection an emit (pbftd only; ISSUE 41): frames (and
    # dial-back lines) handed to a connection's send queue, and send()
    # system calls made, by the net loop and, with --net-threads, by its
    # shard threads, added into the same two names where a scrape or
    # /status is rendered. Their ratio is the frames a system call carries:
    # the loop queues everything one emit() holds for a connection and
    # flushes it once (a shard: everything one drained stretch of commands
    # holds), so it follows the bunches the input produced; it read 1.0 by
    # construction while every frame was followed by its own flush.
    "pbft_frames_out_total": ("counter", {"net.cc", "net_shard.cc"}),
    "pbft_send_calls_total": ("counter", {"net.cc", "net_shard.cc"}),
    # Signatures the replica made (Replica::sign; pbftd only): every reply
    # carries one, so it is the largest countable item inside `protocol`.
    "pbft_signs_total": ("counter", {"net.cc"}),
    # What the fast path adds to a reply's path, and what it takes off it
    # (ISSUE 32); all four in both modes' series sets. Request wait: on the
    # primary, once a batch at its seal (the "request" phase stamp), seal
    # time minus the arrival of the OLDEST request in the batch; a seal
    # refused by a closed watermark window (counted by
    # pbft_seal_refused_total) leaves the start where it was. Commit lag:
    # tentative mode, once a sequence number, from its execution at
    # PREPARED to the committed floor passing it: how long its replies
    # stay revocable and its undo record lives (a histogram of its own,
    # not a phase stamp: "committed" never follows "executed" in a span).
    # Inline verifies: signature checks done on the host in the normal
    # case, i.e. a checkpoint's embedded signature in MAC mode (the view
    # change's proofs are not counted); /status: inline_verifies.
    "pbft_request_wait_seconds": ("histogram", {"net.cc"}),
    "pbft_seal_refused_total": ("counter", {"net.cc"}),
    "pbft_tentative_commit_lag_seconds": ("histogram", {"net.cc"}),
    "pbft_inline_verifies_total": ("counter", {"net.cc"}),
}

# Fixed histogram bucket upper edges (le semantics: v <= edge). Shared by
# pbftd and the Python processes — core/metrics.cc mirrors these values; the
# lint compares.
LATENCY_BUCKETS_S = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
    0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
)
BATCH_SIZE_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)

# The loop clock's stages, in the order a verify_batch line's loop_us lists
# them (core/net.h kLoopStageNames).
LOOP_STAGES = ("wait", "read", "protocol", "wal", "send", "verify", "other")
# A shard thread's and a pipeline thread's stages (core/net_shard.h
# kShardStageNames, kPipeStageNames): /status shard_us and pipe_us.
SHARD_STAGES = ("wait", "read", "send", "other")
PIPE_STAGES = ("wait", "decode", "encode", "other")

# The consensus phases in protocol order. "request" exists only on the
# primary (it assigns the sequence number); every replica sees the rest.
PHASES = ("request", "pre_prepare", "prepared", "committed", "executed")

# -- black-box flight recorder (ISSUE 9) -------------------------------------
#
# pbftd and the gateway keep a fixed-size ring of compact binary records
# (core/flight.{h,cc} lock-free atomics; pbft_tpu/utils/flight.py a
# bounded deque) dumped to a file on SIGTERM/fatal/invariant-failure and
# decoded by scripts/flight_dump.py. The on-disk format is shared:
#
#   header  FLIGHT_MAGIC (8B) + u32le version + u32le record count
#   record  u64le t_ns, u16le event id, i16le peer, i32le view, i32le seq
#
# Event ids are the contract below; core/flight.h mirrors
# them (enum FlightEvent). The "request" consensus phase records as
# batch_sealed (the primary's sequence assignment IS the seal).
FLIGHT_MAGIC = b"PBFTBBX1"
FLIGHT_VERSION = 1
FLIGHT_RECORD_SIZE = 20
FLIGHT_EVENTS = {
    1: "request_rx",
    2: "batch_sealed",
    3: "pre_prepare",
    4: "prepared",
    5: "committed",
    6: "executed",
    7: "reply_tx",
    8: "view_timer_fired",
    9: "view_change_sent",
    10: "new_view_installed",
    11: "verify_batch",
    # Perf-under-faults coverage (ISSUE 12): the view timer's backoff
    # level changed (seq = new level), a client request was answered with
    # an explicit overload rejection (seq = request timestamp), and a
    # gateway-fabric link was replaced (peer = replica id / gateway index
    # where meaningful).
    12: "backoff_level",
    13: "overload_rejected",
    14: "gateway_failover",
    # Fast-path coverage (ISSUE 14): a reply left at PREPARED (seq = the
    # request timestamp), and a tentative-suffix rollback on view change
    # / certified-checkpoint catch-up (seq = sequences rolled back).
    15: "tentative_reply",
    16: "tentative_rollback",
    # Durable recovery (ISSUE 15): WAL replay began (view = persisted
    # view, seq = the stable-checkpoint floor) and recovery finished
    # (seq = the recovered executed_upto). core/flight.h mirrors the ids.
    17: "recovery_started",
    18: "recovery_complete",
}
FLIGHT_EVENT_IDS = {name: i for i, name in FLIGHT_EVENTS.items()}

# -- the verify service's status JSON ----------------------------------------
#
# What ``VerifyServiceDaemon.status_json`` may carry (the 0xFFFFFFFF probe;
# ``scripts/verify_status.py`` prints it, ``chip_smoke.py``, ``bench.py`` and
# the benchmark read it), and inside it the engine's warm-up accounting.
# tests/test_verify_spans.py holds the daemon to these sets.
VERIFYD_STATUS_KEYS = {
    "state", "platform", "device_kind", "devices_seen", "devices",
    "warmed_shapes", "backend", "uptime_s", "requests",
    "engine_launches", "engine_items", "fallback_launches", "fallback_items",
    # Launches, without --trace: running totals of every stage, launches the
    # engine ran on a larger shape than the smallest fit, windows it ran as
    # several executables, windows whose hold ran out / ended early with
    # everybody in step back, windows cut at MAX_WINDOW with requests left
    # queued and the most items any cut left queued, launches by the padded
    # slots run ({"1024": n, ...}) and by the rows a chip of their thinnest
    # chunk ({"256": n, ...}), the slowest one; fused_launches: windows with
    # slots on executables that run the multiply chains out of VMEM;
    # block_items / listed_items: items that reached an executable as the
    # rows they came off the wire as, and items a backend took as a list.
    "stage_seconds", "promoted_launches", "split_launches", "fused_launches",
    "block_items", "listed_items",
    "held_out_launches",
    "in_step_launches", "windows_cut_full", "overflow_items_max",
    "launches_by_rung", "launches_by_rows_per_chip", "slowest_launch",
    # Launches that were in flight longer than service.STALL_S (each left a
    # launch_stalled record) and the longest of them that has ended.
    "stalls", "longest_stall_s",
    "memory_peak_bytes", "warm_stats", "warm_error",
}
VERIFYD_WARM_STATS_KEYS = {
    "cache_dir", "shapes", "per_shape", "compiled", "cache_hits",
    "cold_compile_s", "warm_load_s",
    # {smallest shape that fits: the shape such a window runs at}, from
    # every shape's launch_s (verify_service.serving_table).
    "serving_table",
    # {"1025-1280": "1024+256", ...}: the windows that run as several
    # launches of smaller shapes, and on which (verify_service.chunk_plan).
    "chunk_plan",
}
VERIFYD_PER_SHAPE_KEYS = {
    "size", "seconds", "cache_hit", "devices", "rows_per_device",
    # Seconds one launch of the shape takes on the engine's own device(s):
    # the least of a few timed launches of the all-pad window at warm-up.
    "launch_s",
    # "vmem" or "xla": where the shape's three long multiply chains run
    # (crypto.ed25519.chains_for of its rows a device).
    "chains",
}

# -- health document (ISSUE 16) ----------------------------------------------
#
# pbftd's metrics_json (and the gateway's /status) is a
# versioned health document: resource readings (rss_bytes, open_fds,
# wal_disk_bytes), progress watermarks (inbox_depth, sealed_unexecuted,
# waiting_requests, last_progress_seconds, uptime_seconds) and identity
# digests (chain_digest, state_digest) alongside the existing counters.
# health_version stamps the document shape so pbft_top and the detector
# library (pbft_tpu/analysis/health.py) can refuse snapshots from a
# process speaking a different schema. core/net.h mirrors the value
# (kHealthDocVersion — constants lint pair "health document version").
HEALTH_DOC_VERSION = 1

# phase-transition -> the latency histogram it feeds (observed at
# "executed" time from the span's stamps).
PHASE_HISTOGRAMS = {
    ("request", "pre_prepare"): "pbft_phase_pre_prepare_seconds",
    ("pre_prepare", "prepared"): "pbft_phase_prepare_seconds",
    ("prepared", "committed"): "pbft_phase_commit_seconds",
    ("committed", "executed"): "pbft_phase_reply_seconds",
}


def histogram_buckets(name: str):
    """The fixed bucket edges for a manifest histogram."""
    if METRIC_SCHEMAS[name][0] != "histogram":
        raise ValueError(f"{name} is not a histogram")
    if name in (
        "pbft_verify_batch_size",
        "pbft_verify_pool_window_size",
        "pbft_batch_size",
        "pbft_verify_service_window_size",
        "pbft_verify_service_coalesced_clients",
    ):
        return BATCH_SIZE_BUCKETS
    return LATENCY_BUCKETS_S

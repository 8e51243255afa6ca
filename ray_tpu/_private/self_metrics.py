"""Runtime self-metrics — the ``ray_tpu_`` instrument registry.

The reference exports scheduler/store/RPC internals as first-class metrics
(src/ray/stats/metric_defs.cc) next to user-defined instruments; until this
module, our ``/metrics`` endpoint carried **only** user metrics. Every
runtime component (lease transport, dispatch path, object store, RPC plane,
compiled-DAG channels, Serve router, Data executor) now feeds the instruments
below through the existing ``util.metrics`` KV-flush -> ``/metrics`` path —
zero new dependencies, one namespace (``ray_tpu_*``), HELP/TYPE on every
family.

Instruments are created lazily on first use (``instruments()``); hot paths
that cannot afford an instrument lock per event (the RPC frame pump) keep
plain int counters and fold them in via a flush-time collector
(``util.metrics.register_collector``).
"""

from __future__ import annotations

import threading

_lock = threading.Lock()
_instruments: dict | None = None

# Dispatch latency buckets: the warm-lease sync path sits around 1-3 ms on a
# loaded dev box and ~100 µs at the hardware floor; classic/raylet dispatch
# and cold leases land in the 10-100 ms decades.
_LATENCY_BOUNDS = [0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 1.0, 5.0]


def instruments() -> dict:
    """The process-wide ray_tpu_* instrument set (created on first call)."""
    global _instruments
    if _instruments is not None:
        return _instruments
    with _lock:
        if _instruments is not None:
            return _instruments
        from ray_tpu.util import metrics as m

        inst = {
            # --- warm-lease transport (lease_manager.py) ---
            "lease_grants": m.Counter(
                "ray_tpu_lease_grants_total",
                "Worker leases granted to this owner (cold path: one raylet round trip).",
            ),
            "lease_reuses": m.Counter(
                "ray_tpu_lease_reuses_total",
                "Tasks shipped onto an already-warm lease (zero raylet RPCs).",
            ),
            "lease_tasks": m.Counter(
                "ray_tpu_lease_tasks_total",
                "Tasks shipped over the lease transport; hit ratio = reuses/tasks.",
            ),
            "lease_pool": m.Gauge(
                "ray_tpu_lease_pool_size",
                "Currently-held worker leases in this owner.",
            ),
            # --- dispatch latency (sampled hop stamps; config.hop_sample_n) ---
            "dispatch_latency": m.Histogram(
                "ray_tpu_dispatch_latency_s",
                "End-to-end dispatch latency (submit -> completion visible at "
                "owner) from always-on 1-in-N sampled hop stamps.",
                boundaries=_LATENCY_BOUNDS,
                tag_keys=("path",),
            ),
            # --- object store arena (store/object_store.py) ---
            "store_bytes": m.Gauge(
                "ray_tpu_store_bytes_used", "Arena bytes currently allocated."
            ),
            "store_capacity": m.Gauge(
                "ray_tpu_store_capacity_bytes", "Arena capacity in bytes."
            ),
            "store_objects": m.Gauge(
                "ray_tpu_store_objects", "Objects resident in the node store."
            ),
            "store_seals": m.Counter(
                "ray_tpu_store_seals_total", "Objects sealed into the store."
            ),
            "store_spills": m.Counter(
                "ray_tpu_store_spills_total", "Objects spilled to external storage."
            ),
            "store_spilled_bytes": m.Counter(
                "ray_tpu_store_spilled_bytes_total", "Bytes spilled to external storage."
            ),
            "store_evictions": m.Counter(
                "ray_tpu_store_evictions_total",
                "Arena blocks evicted (freed after spill) under memory pressure.",
            ),
            # --- RPC plane (rpc.py WIRE counters via collector) ---
            "rpc_frames": m.Counter(
                "ray_tpu_rpc_frames_total",
                "Wire frames by direction.",
                tag_keys=("dir",),
            ),
            "rpc_bytes": m.Counter(
                "ray_tpu_rpc_bytes_total",
                "Wire bytes by direction.",
                tag_keys=("dir",),
            ),
            "rpc_connects": m.Counter(
                "ray_tpu_rpc_connects_total", "Client connections established."
            ),
            "rpc_resets": m.Counter(
                "ray_tpu_rpc_resets_total", "Client connections lost/reset."
            ),
            "rpc_hwm_stalls": m.Counter(
                "ray_tpu_rpc_write_hwm_stalls_total",
                "Writes that hit the socket write high-water mark (backpressure).",
            ),
            # --- transfer plane (push_manager.py / pull_manager.py) ---
            "transfer_bytes": m.Counter(
                "ray_tpu_transfer_bytes_total",
                "Object chunk payload bytes moved node-to-node, by direction.",
                tag_keys=("dir",),
            ),
            "transfer_chunks": m.Counter(
                "ray_tpu_transfer_chunks_total",
                "Object chunks moved node-to-node, by direction and wire "
                "framing (raw = zero-copy raw frames, msgpack = negotiated "
                "fallback).",
                tag_keys=("dir", "frame"),
            ),
            "transfer_pushes": m.Counter(
                "ray_tpu_transfer_pushes_total", "Outbound pushes committed."
            ),
            "transfer_pulls": m.Counter(
                "ray_tpu_transfer_pulls_total", "Pulls sealed into the local store."
            ),
            "transfer_relays": m.Counter(
                "ray_tpu_transfer_relays_total",
                "Cut-through broadcast relays completed (chunks forwarded "
                "downstream before the local copy sealed).",
            ),
            "transfer_pull_sources": m.Counter(
                "ray_tpu_transfer_pull_sources_total",
                "Source replicas that served chunks of a striped pull "
                "(per-pull average = this / pulls).",
            ),
            "transfer_admission_stalls": m.Counter(
                "ray_tpu_transfer_admission_stalls_total",
                "Pulls that queued on pull_admission_budget_bytes before "
                "allocating arena space.",
            ),
            "transfer_source_demotions": m.Counter(
                "ray_tpu_transfer_source_demotions_total",
                "Pull sources demoted to the back of the ranking after an "
                "error mid-transfer.",
            ),
            # --- compiled-DAG channel plane (experimental/channel/) ---
            "channel_writes": m.Counter(
                "ray_tpu_channel_writes_total", "Envelopes published to channels."
            ),
            "channel_backpressure": m.Counter(
                "ray_tpu_channel_backpressure_total",
                "Channel writes that blocked on a full ring.",
            ),
            "channel_occupancy": m.Gauge(
                "ray_tpu_channel_ring_occupancy",
                "Unconsumed slots observed at the last sampled channel write "
                "in this process (per-channel tags would leak one stale "
                "series per torn-down channel).",
            ),
            # --- MPMD pipeline / descriptor channel plane (PR 12) ---
            "pipeline_microbatches": m.Counter(
                "ray_tpu_pipeline_microbatches_total",
                "Resident-loop stage iterations completed in this process "
                "(one microbatch through one stage).",
            ),
            "pipeline_stall": m.Counter(
                "ray_tpu_pipeline_stall_seconds_total",
                "Seconds resident-loop stages spent blocked on input "
                "channels (pipeline bubble + upstream latency).",
            ),
            "pipeline_resolve_latency": m.Histogram(
                "ray_tpu_pipeline_resolve_latency_s",
                "Descriptor-slot resolution latency (KIND_DEVICE envelope "
                "to live value: inbox take / pull fallback / local).",
                boundaries=_LATENCY_BOUNDS,
            ),
            # --- Serve router (serve/_private/router.py) ---
            "serve_requests": m.Counter(
                "ray_tpu_serve_requests_total",
                "Requests routed to replicas.",
                tag_keys=("deployment",),
            ),
            "serve_queue_depth": m.Gauge(
                "ray_tpu_serve_router_queue_depth",
                "In-flight requests across this router's replicas.",
                tag_keys=("deployment",),
            ),
            "serve_migrations": m.Counter(
                "ray_tpu_serve_migrations_total",
                "Streaming requests migrated mid-stream to another replica "
                "after a replica death (proxy-side teacher-forced resume).",
                tag_keys=("deployment",),
            ),
            "serve_drains": m.Counter(
                "ray_tpu_serve_drains_total",
                "Replica drains completed before deliberate retirement "
                "(downscale / rolling update), by outcome.",
                tag_keys=("outcome",),
            ),
            "serve_latency": m.Histogram(
                "ray_tpu_serve_replica_latency_s",
                "Replica request latency observed at the handle (assign -> result).",
                boundaries=_LATENCY_BOUNDS,
                tag_keys=("deployment",),
            ),
            # --- continuous-batching LLM engine (serve/llm/engine.py) ---
            "serve_llm_running": m.Gauge(
                "ray_tpu_serve_llm_running_sequences",
                "Sequences occupying a decode slot in this process's engine.",
            ),
            "serve_llm_waiting": m.Gauge(
                "ray_tpu_serve_llm_waiting_sequences",
                "Prompts queued for a decode slot / KV blocks.",
            ),
            "serve_llm_kv_util": m.Gauge(
                "ray_tpu_serve_llm_kv_block_utilization",
                "Allocated fraction of the paged KV block pool (0..1; "
                "includes refs-0 prefix-cache blocks held for reuse).",
            ),
            "serve_llm_prefix_hits": m.Counter(
                "ray_tpu_serve_llm_prefix_hits_total",
                "Prompt blocks served from the prefix cache at admission "
                "(prefill skipped for those tokens).",
            ),
            "serve_llm_prefix_misses": m.Counter(
                "ray_tpu_serve_llm_prefix_misses_total",
                "Hashable prompt blocks that had to be prefilled.",
            ),
            "serve_llm_preemptions": m.Counter(
                "ray_tpu_serve_llm_preemptions_total",
                "Sequences preempted for KV blocks (recompute on readmission).",
            ),
            "serve_llm_evictions": m.Counter(
                "ray_tpu_serve_llm_prefix_evictions_total",
                "refs-0 prefix-cache blocks evicted under allocation pressure.",
            ),
            "serve_llm_handoffs": m.Counter(
                "ray_tpu_serve_llm_handoffs_total",
                "Completed prefill→decode KV handoffs (sealed payload "
                "imported on the decode side; descriptors only in-band, "
                "payloads on the direct-mailbox p2p plane).",
            ),
            "serve_llm_prefix_imports": m.Counter(
                "ray_tpu_serve_llm_prefix_imports_total",
                "Cluster-prefix-tier KV import attempts by outcome: hit "
                "(payload landed), miss (no registry row for any probed "
                "depth), error (row existed but the payload was gone or "
                "the fetch failed).",
                tag_keys=("outcome",),
            ),
            "serve_llm_chunk_tokens": m.Counter(
                "ray_tpu_serve_llm_prefill_chunk_tokens_total",
                "Tokens the fixed-shape prefill chunks carried, by kind: valid "
                "(a prompt's own) and padded (behind a prompt's last tokens: "
                "what the fixed chunk wastes).",
                tag_keys=("kind",),
            ),
            "serve_llm_state_resets": m.Counter(
                "ray_tpu_serve_llm_state_resets_total",
                "Admissions that made a slot's recurrent state start from zero "
                "(linear-attention layers; re-admissions after a preemption too).",
            ),
            "serve_llm_moe_assignments": m.Counter(
                "ray_tpu_serve_llm_moe_assignments_total",
                "Assignments of tokens to routed experts, as last read from the "
                "device's counters (one flush behind), by held: true (to experts "
                "the program holds), false (to the others' of an expert-parallel "
                "deployment, which this program computes nothing of) and identity "
                "(picks of a router's identity experts, which reach no matrix).",
                tag_keys=("held",),
            ),
            "serve_llm_state_slots": m.Gauge(
                "ray_tpu_serve_llm_state_slots_in_use",
                "Slots whose recurrent state (linear-attention layers) belongs "
                "to a running request.",
            ),
            "serve_llm_state_bytes": m.Gauge(
                "ray_tpu_serve_llm_state_bytes",
                "Bytes the linear-attention layers' state group holds on the "
                "device for all slots, whatever the requests' lengths.",
            ),
            "serve_llm_ttft": m.Histogram(
                "ray_tpu_serve_llm_ttft_s",
                "Time to first token: submit -> first token emitted "
                "(folded at flush from the engine's request ring).",
                boundaries=_LATENCY_BOUNDS,
            ),
            "serve_llm_tpot": m.Histogram(
                "ray_tpu_serve_llm_time_per_output_token_s",
                "Per-request mean inter-token latency (first -> last token; "
                "folded at flush from the engine's request ring).",
                boundaries=_LATENCY_BOUNDS,
            ),
            "serve_llm_loop_seconds": m.Counter(
                "ray_tpu_serve_llm_loop_seconds_total",
                "Scheduler-loop seconds by span (llm.iteration is the whole "
                "pass, the others its phases); rate() of it is that phase's "
                "share of wall time.",
                tag_keys=("phase",),
            ),
            "serve_llm_iterations": m.Counter(
                "ray_tpu_serve_llm_iterations_total",
                "Scheduler-loop passes that dispatched a program, by kind: "
                "decode (a decode step only), prefill (a prefill chunk "
                "only), mixed (both).",
                tag_keys=("kind",),
            ),
            # --- Data executor (data/_internal/) ---
            "data_rows": m.Counter(
                "ray_tpu_data_output_rows_total",
                "Rows produced per Data operator.",
                tag_keys=("op",),
            ),
            "data_bytes": m.Counter(
                "ray_tpu_data_output_bytes_total",
                "Bytes produced per Data operator.",
                tag_keys=("op",),
            ),
            "data_blocks": m.Counter(
                "ray_tpu_data_output_blocks_total",
                "Blocks produced per Data operator.",
                tag_keys=("op",),
            ),
            # --- device object plane (experimental/device_object/) ---
            "devobj_resident": m.Gauge(
                "ray_tpu_devobj_resident",
                "Device-resident objects held by this process.",
            ),
            "devobj_resident_bytes": m.Gauge(
                "ray_tpu_devobj_resident_bytes",
                "Bytes of device-resident object payloads held by this process.",
            ),
            "devobj_transfers": m.Counter(
                "ray_tpu_devobj_transfers_total",
                "Device-object resolutions by transfer kind "
                "(local = same-process zero-copy, collective = group p2p, "
                "host = inline/arena fallback).",
                tag_keys=("kind",),
            ),
            "devobj_spills": m.Counter(
                "ray_tpu_devobj_spills_total",
                "Device objects spilled device->host into the arena.",
            ),
            "devobj_restores": m.Counter(
                "ray_tpu_devobj_restores_total",
                "Spilled device objects restored host->device.",
            ),
            # --- group collectives (util/collective, PR 15) ---
            "collective_broadcasts": m.Counter(
                "ray_tpu_collective_broadcasts_total",
                "Group broadcasts fanned out by this process (one per "
                "device_object.broadcast on the holder).",
            ),
            "collective_broadcast_bytes": m.Counter(
                "ray_tpu_collective_broadcast_bytes_total",
                "Serialized payload bytes delivered by group broadcasts "
                "(payload size x delivered ranks).",
            ),
            "collective_bcast_recvs": m.Counter(
                "ray_tpu_collective_bcast_recvs_total",
                "Payloads this process took from its broadcast landing zone "
                "(descriptor resolves + explicit bcast_recv_payload).",
            ),
            "collective_bcast_fallbacks": m.Counter(
                "ray_tpu_collective_bcast_fallbacks_total",
                "Per-rank broadcast deliveries that fell back to the GCS-KV "
                "mailbox (member without a registered address).",
            ),
            "collective_bcast_failed_ranks": m.Counter(
                "ray_tpu_collective_bcast_failed_ranks_total",
                "Ranks a group broadcast could not deliver to (dead or "
                "severed members; named in CollectiveBroadcastError).",
            ),
            "collective_timeouts": m.Counter(
                "ray_tpu_collective_timeouts_total",
                "Typed collective timeouts raised (CollectiveTimeoutError: "
                "ring _collect and broadcast recv).",
            ),
            # --- relay-tree collectives (PR 16) ---
            "collective_tree_sends": m.Counter(
                "ray_tpu_collective_tree_broadcasts_total",
                "Group broadcasts that rode the binomial relay tree "
                "(vs the flat per-rank fan-out).",
            ),
            "collective_bcast_retries": m.Counter(
                "ray_tpu_collective_bcast_retries_total",
                "Ranks re-delivered DIRECTLY after a relay failure orphaned "
                "them (tree broadcast flat-fallback recoveries).",
            ),
            "collective_root_egress_bytes": m.Counter(
                "ray_tpu_collective_root_egress_bytes_total",
                "Payload bytes this process pushed as a broadcast ROOT — "
                "sub-O(K) on the tree topology (the relay fan-out carries "
                "the rest).",
            ),
            "collective_relay_forwards": m.Counter(
                "ray_tpu_collective_relay_forwards_total",
                "Relay legs completed by this process (every chunk of one "
                "tree broadcast forwarded to one child).",
            ),
            "collective_relay_bytes": m.Counter(
                "ray_tpu_collective_relay_bytes_total",
                "Payload bytes this process forwarded mid-tree (cut-through "
                "relay; counted at the forwarding member, not the root).",
            ),
            "collective_reduce_sends": m.Counter(
                "ray_tpu_collective_reduce_sends_total",
                "Tree-reduce participations by this process (one per "
                "group_reduce_send call that completed).",
            ),
            "collective_reduce_bytes": m.Counter(
                "ray_tpu_collective_reduce_bytes_total",
                "Combined-partial bytes this process pushed up the reduce "
                "tree toward its parent.",
            ),
            "collective_allreduces": m.Counter(
                "ray_tpu_collective_allreduces_total",
                "Allreduce participations (tree reduce up + broadcast "
                "back down) by this process.",
            ),
            "collective_reducescatters": m.Counter(
                "ray_tpu_collective_reducescatters_total",
                "Reduce-scatter participations (tree reduce up + per-rank "
                "shard fan-out from the root) by this process.",
            ),
            "collective_scatter_bytes": m.Counter(
                "ray_tpu_collective_scatter_bytes_total",
                "Serialized reduce-scatter shard bytes this process pushed "
                "to members as the scatter root.",
            ),
            "collective_host_sync_fallbacks": m.Counter(
                "ray_tpu_collective_host_sync_fallbacks_total",
                "Broadcast payloads a GROUP MEMBER had to resolve over the "
                "host pull path instead of its broadcast inbox — a fleet "
                "quietly riding pull-resolve (off the elastic fast path) "
                "shows up here, not in silence.",
            ),
            "collective_member_changes": m.Counter(
                "ray_tpu_collective_member_changes_total",
                "Roster epoch advances published by this process "
                "(join/rejoin/leave/death/advance of elastic group "
                "membership).",
            ),
            # --- actor lifecycle (gcs.py) ---
            "actor_restarts": m.Counter(
                "ray_tpu_actor_restarts_total", "Actor restarts driven by the GCS."
            ),
            # --- GCS fan-in hardening (gcs.py) ---
            "gcs_events_dropped": m.Counter(
                "ray_tpu_gcs_events_dropped_total",
                "Task events dropped (oldest-first) by the GCS ingest ring "
                "under overload — observability degrades, liveness never "
                "does (paired with the gcs_overload flight event).",
            ),
            "locality_hits": m.Counter(
                "ray_tpu_sched_locality_hits_total",
                "Tasks placed on a node already holding their reference "
                "args (locality-aware scheduling fast path).",
            ),
            # --- chaos fault-injection plane (chaos.py) ---
            "chaos_injected": m.Counter(
                "ray_tpu_chaos_injected_total",
                "Faults injected at the RPC frame seam by the active chaos "
                "plan, by kind (zero in production: no plan installed).",
                tag_keys=("kind",),
            ),
        }
        m.register_collector(_collect_wire_stats)
        m.register_collector(_collect_chaos_stats)
        m.register_collector(_collect_serve_llm_stats)
        m.register_collector(_collect_transfer_stats)
        m.register_collector(_collect_lease_stats)
        m.register_collector(_collect_channel_stats)
        m.register_collector(_collect_pipeline_stats)
        m.register_collector(_collect_devobj_stats)
        m.register_collector(_collect_collective_stats)
        _instruments = inst
    return _instruments


# Last-folded values per (source, attr): the plain-int stats objects are
# monotonic, Counters need deltas.
_folded: dict = {}


def _fold_value(key: tuple, cur: int, counter, tags, scale: float = 1) -> None:
    """Fold one monotonic plain int into a Counter: the growth since the
    last flush, times ``scale`` (1e-9 turns nanoseconds into seconds)."""
    delta = cur - _folded.get(key, 0)
    if delta > 0:
        _folded[key] = cur
        counter.inc(delta * scale, tags=tags)


def _fold(source_key: str, stats_obj, pairs) -> None:
    """Fold monotonic plain-int attrs of a hot-path stats object into
    Counters. ``pairs`` = [(attr, counter, tags-or-None)]."""
    if _instruments is None:
        return
    for attr, counter, tags in pairs:
        _fold_value((source_key, attr), getattr(stats_obj, attr), counter, tags)


def _collect_wire_stats():
    from ray_tpu._private.rpc import WIRE

    inst = _instruments
    if inst is None:
        return
    _fold("wire", WIRE, [
        ("frames_out", inst["rpc_frames"], {"dir": "out"}),
        ("frames_in", inst["rpc_frames"], {"dir": "in"}),
        ("bytes_out", inst["rpc_bytes"], {"dir": "out"}),
        ("bytes_in", inst["rpc_bytes"], {"dir": "in"}),
        ("connects", inst["rpc_connects"], None),
        ("resets", inst["rpc_resets"], None),
        ("hwm_stalls", inst["rpc_hwm_stalls"], None),
    ])


def _collect_chaos_stats():
    from ray_tpu._private.chaos import CHAOS_STATS

    inst = _instruments
    if inst is None:
        return
    _fold("chaos", CHAOS_STATS, [
        ("drops", inst["chaos_injected"], {"kind": "drop"}),
        ("delays", inst["chaos_injected"], {"kind": "delay"}),
        ("dups", inst["chaos_injected"], {"kind": "dup"}),
        ("resets", inst["chaos_injected"], {"kind": "reset"}),
        ("partition_blocks", inst["chaos_injected"], {"kind": "partition"}),
        ("kills", inst["chaos_injected"], {"kind": "kill"}),
    ])


def _collect_transfer_stats():
    from ray_tpu._private.transfer_stats import TRANSFER

    inst = _instruments
    if inst is None:
        return
    _fold("transfer", TRANSFER, [
        ("bytes_out", inst["transfer_bytes"], {"dir": "out"}),
        ("bytes_in", inst["transfer_bytes"], {"dir": "in"}),
        ("chunks_raw_out", inst["transfer_chunks"], {"dir": "out", "frame": "raw"}),
        ("chunks_msgpack_out", inst["transfer_chunks"], {"dir": "out", "frame": "msgpack"}),
        ("chunks_raw_in", inst["transfer_chunks"], {"dir": "in", "frame": "raw"}),
        ("chunks_msgpack_in", inst["transfer_chunks"], {"dir": "in", "frame": "msgpack"}),
        ("pushes", inst["transfer_pushes"], None),
        ("pulls", inst["transfer_pulls"], None),
        ("relays", inst["transfer_relays"], None),
        ("pull_sources", inst["transfer_pull_sources"], None),
        ("admission_stalls", inst["transfer_admission_stalls"], None),
        ("source_demotions", inst["transfer_source_demotions"], None),
    ])


def _collect_channel_stats():
    from ray_tpu.experimental.channel.channel import CHANNEL_STATS

    inst = _instruments
    if inst is None:
        return
    _fold("channel", CHANNEL_STATS, [
        ("writes", inst["channel_writes"], None),
        ("backpressure", inst["channel_backpressure"], None),
    ])
    if CHANNEL_STATS.writes:
        inst["channel_occupancy"].set(CHANNEL_STATS.last_occupancy)


def _collect_pipeline_stats():
    from ray_tpu.experimental.channel.channel import PIPELINE_STATS

    inst = _instruments
    if inst is None:
        return
    _fold("pipeline", PIPELINE_STATS, [
        ("microbatches", inst["pipeline_microbatches"], None),
    ])
    # Stall is kept as plain ns on the hot path; fold the delta as seconds.
    cur = PIPELINE_STATS.stall_ns
    key = ("pipeline", "stall_ns")
    delta = cur - _folded.get(key, 0)
    if delta > 0:
        _folded[key] = cur
        inst["pipeline_stall"].inc(delta / 1e9)
    # Drain buffered resolve-latency observations into the histogram at
    # flush cadence (the resolver appends plain floats, no instrument lock
    # per microbatch).
    samples = PIPELINE_STATS.resolve_samples
    while True:
        try:
            s = samples.popleft()
        except IndexError:
            break
        inst["pipeline_resolve_latency"].observe(s)


def _collect_devobj_stats():
    from ray_tpu.experimental.device_object.manager import DEVOBJ_STATS, active_manager

    inst = _instruments
    if inst is None:
        return
    _fold("devobj", DEVOBJ_STATS, [
        ("transfers_local", inst["devobj_transfers"], {"kind": "local"}),
        ("transfers_collective", inst["devobj_transfers"], {"kind": "collective"}),
        ("transfers_host", inst["devobj_transfers"], {"kind": "host"}),
        ("chan_sends", inst["devobj_transfers"], {"kind": "chan_send"}),
        ("chan_recvs", inst["devobj_transfers"], {"kind": "chan_recv"}),
        ("spills", inst["devobj_spills"], None),
        ("restores", inst["devobj_restores"], None),
    ])
    mgr = active_manager()
    if mgr is not None:
        usage = mgr.usage()
        inst["devobj_resident"].set(usage["resident_count"])
        inst["devobj_resident_bytes"].set(usage["resident_bytes"])


def _collect_collective_stats():
    from ray_tpu.util.collective.p2p import COLL

    inst = _instruments
    if inst is None:
        return
    _fold("collective", COLL, [
        ("bcast_sends", inst["collective_broadcasts"], None),
        ("bcast_send_bytes", inst["collective_broadcast_bytes"], None),
        ("bcast_recvs", inst["collective_bcast_recvs"], None),
        ("bcast_fallbacks", inst["collective_bcast_fallbacks"], None),
        ("bcast_failed_ranks", inst["collective_bcast_failed_ranks"], None),
        ("timeouts", inst["collective_timeouts"], None),
        ("tree_sends", inst["collective_tree_sends"], None),
        ("bcast_retries", inst["collective_bcast_retries"], None),
        ("root_egress_bytes", inst["collective_root_egress_bytes"], None),
        ("relay_forwards", inst["collective_relay_forwards"], None),
        ("relay_bytes", inst["collective_relay_bytes"], None),
        ("reduce_sends", inst["collective_reduce_sends"], None),
        ("reduce_bytes", inst["collective_reduce_bytes"], None),
        ("allreduces", inst["collective_allreduces"], None),
        ("reducescatters", inst["collective_reducescatters"], None),
        ("scatter_bytes", inst["collective_scatter_bytes"], None),
        ("host_sync_fallbacks", inst["collective_host_sync_fallbacks"], None),
        ("member_changes", inst["collective_member_changes"], None),
    ])


def _collect_serve_llm_stats():
    from ray_tpu.serve.llm.stats import (
        ENGINES,
        ITERATION_KINDS,
        LLM,
        RECORDERS,
        REQUEST_FIELDS,
        SPAN_NAMES,
    )

    inst = _instruments
    if inst is None:
        return
    for phase, ns in zip(SPAN_NAMES, LLM.span_ns):
        _fold_value(
            ("serve_llm_span_ns", phase), ns, inst["serve_llm_loop_seconds"], {"phase": phase}, 1e-9
        )
    for kind, n in zip(ITERATION_KINDS, LLM.iterations):
        _fold_value(("serve_llm_iterations", kind), n, inst["serve_llm_iterations"], {"kind": kind})
    # TTFT / TPOT: observed here, from the requests that ended since the last
    # flush, so that nothing takes an instrument lock on the token path.
    col = {name: i for i, name in enumerate(REQUEST_FIELDS)}
    for rec in list(RECORDERS):
        ended = rec.requests.since(rec.requests_folded)
        rec.requests_folded += len(ended)
        for r in ended:
            t_submit, t_first = r[col["t_submit_ns"]], r[col["t_first_ns"]]
            if not t_first:
                continue
            inst["serve_llm_ttft"].observe((t_first - t_submit) / 1e9)
            n = r[col["generated"]]
            if r[col["outcome"]] == "finished" and n > 1:
                inst["serve_llm_tpot"].observe((r[col["t_done_ns"]] - t_first) / 1e9 / (n - 1))
    _fold("serve_llm", LLM, [
        ("prefix_hit_blocks", inst["serve_llm_prefix_hits"], None),
        ("prefix_miss_blocks", inst["serve_llm_prefix_misses"], None),
        ("preemptions", inst["serve_llm_preemptions"], None),
        ("evicted_blocks", inst["serve_llm_evictions"], None),
        ("handoffs", inst["serve_llm_handoffs"], None),
        ("prefix_import_hits", inst["serve_llm_prefix_imports"], {"outcome": "hit"}),
        ("prefix_import_misses", inst["serve_llm_prefix_imports"], {"outcome": "miss"}),
        ("prefix_import_errors", inst["serve_llm_prefix_imports"], {"outcome": "error"}),
        ("chunk_tokens_valid", inst["serve_llm_chunk_tokens"], {"kind": "valid"}),
        ("chunk_tokens_padded", inst["serve_llm_chunk_tokens"], {"kind": "padded"}),
        ("state_resets", inst["serve_llm_state_resets"], None),
        ("moe_assignments_held", inst["serve_llm_moe_assignments"], {"held": "true"}),
        ("moe_assignments_elsewhere", inst["serve_llm_moe_assignments"], {"held": "false"}),
        ("moe_picks_identity", inst["serve_llm_moe_assignments"], {"held": "identity"}),
    ])
    engines = list(ENGINES)
    if not engines and not LLM.admitted:
        return  # no engine has ever lived in this process
    # Gauges are summed across LIVE engines at flush time (best-effort
    # plain-int reads, like LLMEngine.stats()): several engines fold into
    # one series, and once the last scheduler exits the sums — and the
    # exported gauges — honestly drop to zero instead of going stale.
    running = waiting = used = total = state_slots = state_bytes = 0
    for eng in engines:
        eng.refresh_moe_counts()  # read at the scheduler's next pass, folded above at the next flush
        running += sum(r is not None for r in eng._slots)
        if eng.state_slot_bytes:
            state_slots += sum(r is not None for r in eng._slots)
            state_bytes += eng.state_slot_bytes * eng.num_slots
        waiting += len(eng._waiting)
        used += (eng.num_blocks - 1) - len(eng._free)
        total += eng.num_blocks - 1
    inst["serve_llm_running"].set(running)
    inst["serve_llm_waiting"].set(waiting)
    inst["serve_llm_kv_util"].set(used / total if total else 0.0)
    inst["serve_llm_state_slots"].set(state_slots)
    inst["serve_llm_state_bytes"].set(state_bytes)


def _collect_lease_stats():
    from ray_tpu._private.lease_manager import LEASE_STATS

    inst = _instruments
    if inst is None:
        return
    _fold("lease", LEASE_STATS, [
        ("grants", inst["lease_grants"], None),
        ("reuses", inst["lease_reuses"], None),
        ("tasks", inst["lease_tasks"], None),
    ])

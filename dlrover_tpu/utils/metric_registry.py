"""Single source of truth for exported Prometheus metric names.

Every ``serving_*`` or ``dlrover_*`` metric-name literal in the package
must be declared here with help text — dlint's DL006 (``tools/dlint``)
enforces it, so a dashboard, the autoscaler, and the docs can never
fork on a misspelled or half-renamed series.  The exporter renders
these as ``# HELP`` lines on ``/metrics``, which makes the registry
visible to every scraper, not just to readers of this file.

Adding a metric: add the name + help here, then emit it from your
``metrics()`` source.  Using a ``serving_``- or ``dlrover_``-prefixed
string that is NOT a metric (an RPC kind, a table name, the package
name itself): add it to :data:`NON_METRIC_SERVING_NAMES` — the
registry arbitrates both string namespaces.

Families emitted via f-string prefixes (``dlrover_step_*`` from
``StepTimer.metrics``, ``dlrover_xprof_*`` from ``AutoProfiler``) are
declared here too even though DL006's literal scan cannot see the
joined names — the registry is the documentation surface, not just the
lint allowlist.
"""

from __future__ import annotations

from typing import Dict, Optional

#: Prometheus name -> help text (rendered as ``# HELP`` on /metrics).
METRIC_HELP: Dict[str, str] = {
    # -- serving router gauges (RouterMetrics.metrics) -----------------
    "serving_queue_depth": "requests waiting in the gateway",
    "serving_inflight": "requests currently placed on replicas",
    "serving_replica_up": "schedulable serving replicas",
    "serving_replica_draining": "replicas finishing in-flight work",
    "serving_ttft_seconds": (
        "time-to-first-token, sliding-window mean (streaming engines: "
        "submission to first TOKEN frame received)"
    ),
    "serving_ttft_seconds_p50": "TTFT reservoir p50 (lifetime)",
    "serving_ttft_seconds_p99": "TTFT reservoir p99 (lifetime)",
    "serving_tokens_per_second": (
        "generated-token throughput over the sliding window"
    ),
    "serving_generated_tokens_total": "tokens generated since start",
    # -- serving request lifecycle counters ----------------------------
    "serving_requests_submitted_total": "requests admitted by the gateway",
    "serving_requests_completed_total": "requests finished successfully",
    "serving_requests_rejected_total": (
        "requests refused at admission or by an engine (poison request)"
    ),
    "serving_requests_timed_out_total": "requests past their deadline",
    "serving_requests_requeued_total": (
        "failover replays — nonzero says a replica died; "
        "completed+timed_out still balancing says nothing was lost"
    ),
    "serving_requests_poisoned_total": (
        "requests failed for exceeding the failover-replay cap — "
        "nonzero says some request was crashing replicas"
    ),
    "serving_requests_cancelled_total": (
        "requests withdrawn by their caller (queued ones dropped, "
        "in-flight ones aborted with a CANCEL sent to the replica)"
    ),
    "serving_cancel_send_failures_total": (
        "CANCEL frames that could not be delivered to a replica — "
        "the slot is reclaimed anyway when the worker dies, but a "
        "live worker that missed a cancel keeps decoding a dropped "
        "request to completion"
    ),
    "serving_worker_quarantined_total": (
        "crash-looping workers the supervisor stopped respawning "
        "(sliding-window respawn budget exhausted) — each sits out a "
        "quarantine period before respawns resume"
    ),
    "serving_replica_probation": (
        "replicas currently held out of placement by crash-loop "
        "probation (joined, cooling down before schedulable again)"
    ),
    "serving_brownout_stage": (
        "per-priority brown-out ladder position: 0 normal, 1 new "
        "BATCH admissions shed, 2 queued+in-flight BATCH cancelled, "
        "3 new NORMAL admissions shed too — HIGH is never shed"
    ),
    # -- gray-failure plane (phi-accrual suspicion + request hedging,
    # -- fed by ServingRouter.step's observe sweep) --------------------
    "serving_phi_max": (
        "worst phi-accrual suspicion level across the fleet's remote "
        "replicas (Hayashibara SRDS 2004: -log10 P(silence this long "
        "| the replica is healthy)) — crosses phi_suspect into "
        "demotion, phi_dead into failover"
    ),
    "serving_replica_suspect": (
        "replicas currently demoted in placement by the gray-failure "
        "detector: phi-suspect now, or inside the flap-damping hold "
        "after a recovery — demoted replicas keep serving their "
        "in-flight work and never fail over on suspicion alone"
    ),
    "serving_replica_suspect_demotions_total": (
        "healthy->demoted transitions: a replica's interarrival phi "
        "crossed the suspect threshold and its placement weight was "
        "penalized (no failover, no lost requests)"
    ),
    "serving_replica_suspect_recoveries_total": (
        "suspect->healthy raw transitions: the replica's phi dropped "
        "back below the suspect threshold (full placement weight "
        "restores after the flap-damping hold elapses)"
    ),
    "serving_suspect_flaps_damped_total": (
        "re-suspicions absorbed inside the flap-damping hold: the "
        "link flapped faster than the (exponentially growing) hold, "
        "so the replica just stayed demoted — no placement churn"
    ),
    "serving_hedge_active": (
        "requests currently racing two attempts (a hedge dispatched, "
        "neither DONE yet) — bounded by the hedge budget fraction of "
        "in-flight"
    ),
    "serving_hedge_dispatched_total": (
        "second attempts dispatched by the hedging sweep: a RUNNING "
        "request went longer than the adaptive hedge delay (factor x "
        "rolling p99 progress gap) without a token, and a healthy "
        "second replica raced it — first DONE wins"
    ),
    "serving_hedge_won_total": (
        "hedge races the SECOND attempt won: the straggling primary "
        "was beaten by the hedge replica's DONE (the tail-latency "
        "cut hedging exists to buy)"
    ),
    "serving_hedge_cancelled_total": (
        "losing hedge-race attempts withdrawn with a CANCEL after "
        "the winner's DONE (each hedged completion cancels exactly "
        "one loser; the loser's late DONE is deduplicated)"
    ),
    "serving_hedge_budget_exhausted_total": (
        "hedge dispatches denied by the budget (concurrent hedges or "
        "cumulative dispatches past the configured fraction) — a "
        "saturated budget means more of the fleet is slow than "
        "hedging can paper over"
    ),
    "serving_hedge_promoted_total": (
        "hedge attempts promoted to primary because the primary "
        "replica DIED mid-race: the request completed on the hedge "
        "without a failover requeue (zero lost, zero replayed)"
    ),
    "serving_capacity_debt": (
        "capacity debts currently open: quarantined workers or "
        "probationary replicas whose replacement node has been "
        "launched but has not joined yet — each retires exactly once"
    ),
    # -- raw-speed engine aggregates (RouterMetrics, fed by the ------
    # -- router's per-step engine_metrics sweep over replicas)
    "serving_spec_accept_ratio": (
        "speculative-decode draft acceptance: accepted draft tokens "
        "over proposed, averaged across replicas whose engines report "
        "it — the live health signal behind tokens-per-forward (1.0 "
        "would mean every draft committed; the governor backs "
        "speculation off below its floor)"
    ),
    "serving_kv_quant_blocks": (
        "KV cache blocks held in int8-quantized pools across the "
        "fleet (0 = native-dtype pools) — at the same HBM an int8 "
        "pool holds ~2x the blocks, which is the continuous-batch "
        "capacity the placement ledger schedules on"
    ),
    "serving_prefill_chunk_seconds": (
        "cumulative wall seconds spent in bounded chunked-prefill "
        "dispatches across the fleet — the budget that keeps one "
        "long prompt from stalling every slot's token cadence "
        "(compare with serving_token_gap_seconds to verify the "
        "stall bound)"
    ),
    "serving_attention_impl": (
        "replicas per resolved paged decode-attention implementation, "
        'labeled impl="xla|pallas" — "pallas" is the fused paged '
        "kernel reading quantized pools in place, \"xla\" the fused-"
        "gather fallback; attention_impl=auto measures both at engine "
        "build and provably never picks the slower one"
    ),
    "serving_paged_kernel_step_seconds": (
        "cumulative decode-step wall seconds on replicas whose "
        "resolved attention impl is the fused Pallas paged kernel — "
        "zero with a nonzero pallas impl count says the kernel fleet "
        "is idle, not broken"
    ),
    "serving_paged_kv_stream_ratio": (
        "key rows the fused paged kernel copied per key row its "
        "decoding slots could see, over the fleet's decode forwards "
        "so far — the kernel streams whole page groups up to a slot's "
        "length, so short contexts read near 1-2 and a fleet at full "
        "context 1.0; 0 = no replica decodes with the kernel"
    ),
    "serving_engine_chained_dispatch_share": (
        "engine programs (prefills, prompt chunks, decode chunks) "
        "dispatched while an earlier program was still unread (of the "
        "same engine step, or the decode chunk the step before left in "
        "flight), over all programs dispatched, fleet-wide: the share "
        "of dispatches whose host preparation the device's queue hid; "
        "near 1 while steps look ahead, 0 = no replica reports"
    ),
    "serving_moe_walks_per_layer": (
        "walks of the served sparse MLP's sorted buffer over the picks a "
        "replica's experts hold, a sparse layer-forward, fleet-wide "
        "(serving/latent.py sparse_mlp): 1 while every layer's held "
        "picks fit the buffer, above 1 where a hot expert overflowed it, "
        "below 1 where layers held no pick; 0 = no replica serves "
        "sparse experts"
    ),
    "serving_engine_lookahead_steps_total": (
        "engine steps that returned with their decode chunk dispatched "
        "and unread (the next step reads it behind its own dispatches), "
        "fleet-wide; standing still beside rising decode traffic says "
        "steps read their own chunk (speculation on)"
    ),
    "serving_engine_wasted_lane_chunks_total": (
        "decode-chunk lanes whose request had already ended when the "
        "chunk was read (an end-of-sequence is learnt one chunk late), "
        "fleet-wide: each is one slot's chunk of forwards computed and "
        "dropped"
    ),
    "serving_dsa_selected_ratio": (
        "key rows attended per key row live on replicas whose model "
        "picks its keys with a learned indexer (LlamaConfig.index_topk), "
        "over decode forwards and prefill chunks so far: 1.0 while "
        "contexts fit the selection, index_topk / context beyond; 0 = "
        "no replica serves such a model"
    ),
    "serving_prefill_live_tile_share": (
        "tiles of queries with a real query among them over the tiles "
        "the prompt chunks' programs held, on replicas that serve a "
        "latent-attention model, over prefill chunks so far: 1.0 while "
        "every chunk is whole; what is missing is padding behind "
        "prompts' last tokens, which the attention kernel skips and the "
        "rest of a chunk still computes; 0 = no replica serves such a "
        "model"
    ),
    "serving_window_stream_ratio": (
        "latent rows the window layers' decode attention read over the "
        "rows inside their queries' windows, fleet-wide (LayerSpec.window: "
        "a ring of blocks a slot, streamed through a position-ordered "
        "table): 1.0 = a decode reads the window and no more, whole "
        "blocks read 1.25 at a window of 513 in blocks of 128; 0 = no "
        "replica serves window layers"
    ),
    "serving_window_cache_share": (
        "bytes of the window layers' rings and kept prefixes over all "
        "cache bytes (pools of blocks, rings, recurrent state), "
        "fleet-wide: the rings are sized by slots x window and do not "
        "grow with cache_blocks; 0 = no replica serves window layers"
    ),
    "serving_moe_held_share": (
        "router picks that fell on the experts a replica holds over all "
        "its picks, fleet-wide (a replica that is one share of an "
        "expert-parallel group, LlamaConfig.moe_experts_held): held / "
        "num_experts when routing is even; 0 = no replica serves "
        "sparse experts"
    ),
    "serving_rpc_retries_total": (
        "control-plane RPC retries under the typed backoff policy "
        "(common/retry) — a rising value under a steady fleet says "
        "the master/Brain link is flaky, not that calls are failing"
    ),
    # -- global prefix cache: engine-side COW sharing aggregates -------
    # -- (BlockManager.prefix_stats, summed across replicas by the
    # -- router's engine_metrics sweep)
    "serving_prefix_hits_total": (
        "full prompt blocks mapped into an existing committed KV block "
        "by chained-hash + content match instead of being recomputed — "
        "each hit is block_size tokens of prefill skipped fleet-wide"
    ),
    "serving_prefix_misses_total": (
        "full prompt blocks that found no committed twin and were "
        "prefilled fresh (the cold half of the hit ratio)"
    ),
    "serving_prefix_evictions_total": (
        "committed refcount-0 prefix blocks reclaimed LRU-first when "
        "the free list ran dry — capacity pressure on the prefix "
        "cache, not an error"
    ),
    "serving_prefix_cow_total": (
        "copy-on-write block copies: a writer diverging inside a "
        "shared (ref>1) block got a private copy first — the price of "
        "sharing, paid only at actual divergence"
    ),
    "serving_prefix_revivals_total": (
        "lingering refcount-0 committed blocks re-mapped by a later "
        "request before eviction reclaimed them — the cache-works-"
        "across-request-lifetimes signal"
    ),
    "serving_prefix_shared_tokens_total": (
        "prompt tokens served from shared KV blocks instead of "
        "prefill compute (hits x block_size)"
    ),
    "serving_prefix_lingers_total": (
        "committed blocks parked evictable when their refcount hit 0 "
        "— lingers - (revivals + evictions) reconciles against the "
        "lru_blocks gauge, so a leak in the park/reclaim cycle shows "
        "as drift instead of hiding"
    ),
    "serving_prefix_forgotten_total": (
        "committed registrations dropped outside eviction: COW "
        "privatization of a ref-1 block and cancelled mid-prefill "
        "writers whose content never became trustworthy"
    ),
    "serving_prefix_evicted_head_drops_total": (
        "evicted-head invalidations lost to the staging cap before "
        "the next STATS drain — the router keeps a stale route until "
        "its TTL; a rising value says the cap is too small for the "
        "eviction rate"
    ),
    "serving_prefix_shared_blocks": (
        "KV blocks currently mapped by more than one live sequence "
        "(ref>1) — the live deduplication the effective-KV-bytes-per-"
        "user gate measures"
    ),
    "serving_prefix_cached_blocks": (
        "committed (hash-indexed, content-verified) blocks currently "
        "reachable for sharing, live or lingering"
    ),
    "serving_prefix_lru_blocks": (
        "committed refcount-0 blocks lingering in the eviction LRU — "
        "reusable capacity the allocator reclaims before failing"
    ),
    # -- global prefix cache: router prefix-routing table --------------
    # -- (scheduler.PrefixRoutingTable, mirrored in the observe phase)
    "serving_prefix_route_entries": (
        "prefix-head -> replica routes currently held (bounded LRU; "
        "fed by each replica's hottest committed prefix heads riding "
        "STATS)"
    ),
    "serving_prefix_route_hits_total": (
        "scheduler lookups that found a live route for a request's "
        "prefix head — consulted AHEAD of recency affinity because "
        "the table knows residency, affinity only guesses it"
    ),
    "serving_prefix_route_misses_total": (
        "scheduler lookups with no route (cold prefix or short "
        "prompt) — placement falls back to affinity/least-loaded"
    ),
    "serving_prefix_route_invalidations_total": (
        "routes dropped for replica death/drain or because a newer "
        "advertisement no longer carried the head (advertised "
        "eviction) — stale routes never outlive their evidence"
    ),
    "serving_prefix_route_placements_total": (
        "requests actually committed onto the replica the routing "
        "table named (a route hit that also passed the capacity "
        "check) — the table's end-to-end usefulness counter"
    ),
    # -- per-request span tracing (utils/tracing.Tracer.metrics) -------
    "serving_request_trace_finished_total": (
        "request traces completed into the tracer's bounded ring"
    ),
    "serving_request_trace_active": (
        "traces still open (admitted requests not yet done/aborted)"
    ),
    "serving_request_trace_ring_size": (
        "finished traces currently held in the in-memory ring "
        "(bounded; served by the /traces endpoint)"
    ),
    "serving_request_trace_slowest_seconds": (
        "duration of the slowest trace in the ring — the /traces/"
        "slowest view names the request and the span the time went to"
    ),
    "serving_request_trace_orphan_spans_total": (
        "remote worker spans that arrived for an unknown trace "
        "(late DONE after failover) and were dropped"
    ),
    "serving_request_trace_flight_dumps_total": (
        "flight-recorder dumps emitted (deadline expiry, poisoning, "
        "replica death) — each is one structured log record with the "
        "request's span tree and the last fabric events"
    ),
    "serving_trace_sampled_total": (
        "finished traces retained by head sampling (incident "
        "overrides — failovers, expiries, cancellations — included)"
    ),
    "serving_trace_dropped_total": (
        "finished healthy traces dropped by the sample-rate knob — "
        "nonzero proves the knob is biting at high QPS"
    ),
    # -- latency histograms (utils/profiler.Histogram; OpenMetrics ----
    # -- text with trace_id exemplars, rendered as _bucket/_count/_sum)
    "serving_ttft_hist_seconds": (
        "time-to-first-token distribution (log-spaced buckets; "
        "bucket exemplars carry the trace_id of the latest sample — "
        "drill down via /traces)"
    ),
    "serving_queue_wait_seconds": (
        "gateway admission-to-placement wait distribution "
        "(per attempt; exemplars carry trace_ids)"
    ),
    "serving_e2e_latency_seconds": (
        "admission-to-completion latency distribution "
        "(exemplars carry trace_ids)"
    ),
    "serving_token_gap_seconds": (
        "seconds between two deliveries of tokens to one request, one "
        "sample a delivery but an attempt's first — a local engine's "
        "hand-over and a remote worker's TOKEN frame alike, as a "
        "streaming client sees them (exemplars carry trace_ids)"
    ),
    # -- router step-loop instrumentation (RouterMetrics, fed by -------
    # -- ServingRouter.step; the measure-first half of the data-plane
    # -- raw-speed discipline: attack what the histograms name)
    "serving_step_lock_hold_seconds": (
        "step-lock hold time per critical section of one router step "
        "— every membership call and has_work reader contends on this "
        "lock, so its tail IS the router's responsiveness tail"
    ),
    "serving_step_phase_seconds": (
        "wall seconds per router step phase, labeled phase=\"expire|"
        "cancel|brownout|failover|schedule|hedge|deliver|pump|retire|"
        "observe|autoscale|flush\" — where one step round's time went "
        "(deliver/flush run OUTSIDE the step lock by the DL007 "
        "discipline; the rest hold it)"
    ),
    "serving_sched_capacity_evals_total": (
        "scheduler (request x replica) capacity-fit evaluations — the "
        "O(replicas x queued) product the incremental placement index "
        "exists to kill; flat across steps while queue and capacity "
        "are unchanged proves the fast path is engaged"
    ),
    "serving_sched_rounds_skipped_total": (
        "placement rounds short-circuited because nothing changed "
        "since a round that placed nothing (same queue generation, "
        "same capacity generation) — the idle step's O(1) proof"
    ),
    # -- per-worker supervisor state (WorkerSupervisor.render_worker_ --
    # -- state: one labeled sample per supervised worker)
    "serving_worker_state": (
        "supervisor view of each worker process, labeled "
        'worker="name",state="running|backoff|quarantined" — the '
        "graceful-degradation dashboard's ground truth for WHICH "
        "worker is sitting out and why"
    ),
    # -- exporter self-observability (utils/profiler.MetricsExporter) --
    "dlrover_metrics_source_errors_total": (
        "metric-source callables that raised during a /metrics scrape "
        "— nonzero says some series on this endpoint are silently "
        "missing/stale"
    ),
    # -- step timing (StepTimer.metrics, prefix dlrover_step) ----------
    "dlrover_step_count": "train/serve steps observed by the StepTimer",
    "dlrover_step_seconds_ema": "EMA of per-step wall seconds",
    "dlrover_step_seconds_last": "wall seconds of the most recent step",
    "dlrover_step_seconds_p50": "reservoir p50 of per-step wall seconds",
    "dlrover_step_seconds_p99": "reservoir p99 of per-step wall seconds",
    "dlrover_step_seconds_total": "cumulative step wall seconds",
    # -- elastic agent self-healing (agent/elastic_agent.metrics) ------
    "dlrover_agent_heartbeat_failures_total": (
        "heartbeat ticks that failed after their in-tick retry budget "
        "— rising under a steady master is the control-plane-flakiness "
        "signal on the training plane"
    ),
    "dlrover_agent_master_outages_total": (
        "master outages entered (heartbeat failing past the retry "
        "deadline); workers keep running through them by contract"
    ),
    "dlrover_agent_master_reconnects_total": (
        "master outages survived: the heartbeat probe landed again"
    ),
    "dlrover_agent_rendezvous_rounds_total": (
        "rendezvous rounds this agent completed (spawn + every "
        "elastic restart)"
    ),
    "dlrover_agent_rendezvous_rejoins_total": (
        "rendezvous registrations re-established after a master "
        "restart wiped its state mid-round"
    ),
    "dlrover_agent_restarts_total": (
        "worker-group restarts (failure, hang, membership growth)"
    ),
    "dlrover_agent_breakpoint_saves_total": (
        "shm checkpoints persisted to storage at a failure breakpoint "
        "before a restart/exit wiped the workers"
    ),
    # -- flash checkpoint double-buffered saves (engine.ckpt_metrics) --
    "dlrover_ckpt_saves_staged_total": (
        "memory saves handed to the async writer (the in-loop cost is "
        "the hand-off, not the copy)"
    ),
    "dlrover_ckpt_saves_committed_total": (
        "generations fully written and atomically published — the "
        "commit-marker protocol's success count"
    ),
    "dlrover_ckpt_saves_collapsed_total": (
        "staged saves superseded by a newer one before the writer "
        "started them (newest wins; never silent)"
    ),
    "dlrover_ckpt_save_errors_total": (
        "async saves that failed to commit (e.g. donated-buffer "
        "misuse); the previous committed generation stays restorable"
    ),
    "dlrover_ckpt_inloop_pause_seconds_total": (
        "cumulative training-loop pause spent in save_to_memory "
        "(staging + residual pipeline wait) — the explicit attribution "
        "of whatever pause the double buffer did not remove"
    ),
    "dlrover_ckpt_commit_seconds_total": (
        "cumulative writer-thread time copying + publishing "
        "generations (overlapped with training, not a pause)"
    ),
    "dlrover_ckpt_lock_wait_seconds_total": (
        "cumulative writer-thread time waiting for the shm lock (the "
        "agent's saver holds it while it persists); part of commit"
    ),
    "dlrover_ckpt_d2h_seconds_total": (
        "cumulative writer-thread time bringing a generation's bytes "
        "from the device to the host (copy dispatch + the wait for "
        "them); part of commit"
    ),
    "dlrover_ckpt_shm_copy_seconds_total": (
        "cumulative writer-thread time copying host bytes into the "
        "shm segment; part of commit"
    ),
    "dlrover_ckpt_bytes_committed_total": (
        "bytes written into shm generations by this process"
    ),
    "dlrover_ckpt_d2h_bytes_total": (
        "bytes of every array a device-to-host copy was started on "
        "(or that was read with none started) while writing "
        "generations; over bytes_committed it reads 1.00 when each "
        "piece of the state crosses to the host once"
    ),
    "dlrover_ckpt_saves_skipped_total": (
        "memory saves refused at staging because the previous commit "
        "was still in flight past STAGE_BARRIER_S (training never "
        "blocks on storage; the save is lost, not late)"
    ),
    "dlrover_ckpt_committed_step": (
        "training step of the last fully-committed shm generation"
    ),
    # -- agent-side checkpoint persistence (agent/ckpt_saver) ----------
    "dlrover_ckpt_persists_total": (
        "shm checkpoint steps the agent-side saver persisted to "
        "storage (async persist loop + breakpoint saves)"
    ),
    "dlrover_ckpt_last_persisted_step": (
        "training step of the newest checkpoint the agent-side saver "
        "fully persisted to storage"
    ),
    # -- fleet coordinator (fleet/coordinator.FleetCoordinator) --------
    "dlrover_fleet_hosts_training": (
        "fleet hosts currently leased to the training world "
        "(FleetOwner.TRAINING)"
    ),
    "dlrover_fleet_hosts_serving": (
        "fleet hosts currently on loan to the serving fabric "
        "(FleetOwner.SERVING) — borrowed capacity"
    ),
    "dlrover_fleet_hosts_migrating": (
        "hosts with a handoff in flight (MIGRATING_OUT or "
        "MIGRATING_BACK) — should return to 0 quickly; a stuck value "
        "is a wedged migration"
    ),
    "dlrover_fleet_borrows_total": (
        "completed train->serve handoffs (checkpoint committed, world "
        "shrunk, worker serving)"
    ),
    "dlrover_fleet_returns_total": (
        "completed serve->train handoffs (replica drained zero-lost, "
        "host rejoined the rendezvous, training stepping again)"
    ),
    "dlrover_fleet_borrow_aborts_total": (
        "borrows rolled back (checkpoint barrier failed, or the "
        "worker never booted within its attempt budget) — the host "
        "returned to training, nothing was lost"
    ),
    "dlrover_fleet_worker_reboots_total": (
        "borrowed workers re-booted after dying on loan (a reopened "
        "debt episode, NOT a new borrow: no checkpoint ran, nothing "
        "shrank — counted apart so borrow handoff stats stay honest)"
    ),
    "dlrover_fleet_debts_open": (
        "capacity-handoff debts currently open: each borrow/return is "
        "a deliberate debt retired exactly once on join/return"
    ),
    "dlrover_fleet_debts_retired_total": (
        "handoff debts retired (exactly once each; compare with "
        "borrows+returns+aborts to audit the exactly-once discipline)"
    ),
    "dlrover_fleet_debts_reopened_total": (
        "borrow debts reopened because the borrowed worker died while "
        "on loan — a NEW episode, mirrored from the PR-8 replacement "
        "reopen rule"
    ),
    "dlrover_fleet_stale_claims_fenced_total": (
        "lease mutations refused for carrying a dead incarnation's "
        "epoch — nonzero proves the fencing earned its keep"
    ),
    "dlrover_fleet_recoveries_total": (
        "coordinator incarnations that rebuilt the lease ledger from "
        "master + supervisor ground truth (1 = the initial start)"
    ),
    "dlrover_fleet_lease_epoch": (
        "current lease-fencing epoch (bumped once per coordinator "
        "incarnation)"
    ),
    "dlrover_fleet_borrow_handoff_seconds": (
        "latest borrow decision -> serving-join handoff latency"
    ),
    "dlrover_fleet_return_handoff_seconds": (
        "latest return decision -> training-resumed handoff latency"
    ),
    # -- OTLP push pipeline (utils/otlp.OtlpExporter.metrics) ----------
    "dlrover_otlp_shipped_total": (
        "traces delivered to the telemetry collector — shipped + "
        "dropped always equals traces offered (the never-block "
        "accounting identity; periodic metric snapshots are re-reads "
        "and count into neither)"
    ),
    "dlrover_otlp_dropped_total": (
        "traces dropped instead of blocking the hot path: queue-full "
        "drops plus batches abandoned after the push retry budget — "
        "nonzero during a collector outage is the pipeline WORKING "
        "as designed"
    ),
    "dlrover_otlp_push_errors_total": (
        "OTLP pushes that exhausted their retry budget — rising says "
        "the collector is down/stalling; the exporter keeps dropping "
        "rather than buffering unboundedly"
    ),
    "dlrover_otlp_queue_depth": (
        "telemetry items currently buffered for push (bounded by the "
        "exporter's queue_capacity)"
    ),
    # -- SLO burn-rate engine (serving/router/slo.SloEngine; labeled ---
    # -- band=HIGH|NORMAL|BATCH, window=fast|slow)
    "serving_slo_compliance": (
        "fraction of the band's requests meeting BOTH the TTFT and "
        "e2e targets over the window (1.0 when idle); labeled "
        'band="…",window="fast|slow"'
    ),
    "serving_slo_burn_rate": (
        "error-budget consumption rate over the window: 1.0 = "
        "burning exactly at the objective's allowance, >1 = heading "
        "for exhaustion; the multi-window min feeds the autoscaler "
        "as SLO pressure"
    ),
    "serving_slo_budget_remaining": (
        "unspent error budget over the slow window (1.0 untouched, "
        "0.0 exhausted — every further violation is debt); labeled "
        'band="…"'
    ),
    "serving_slo_class_burn_rate": (
        "per-TENANT-CLASS error-budget consumption rate over the "
        "window (same arithmetic as serving_slo_burn_rate, keyed on "
        "the bounded tenancy vocabulary — a premium class burning "
        "while its band looks healthy is the noisy-neighbor "
        'signature); labeled tenant_class="…",window="fast|slow"'
    ),
    # -- per-tenant QoS (serving/tenancy; labeled by the BOUNDED -------
    # -- tenant_class vocabulary, never raw tenant ids — DL010)
    "serving_tenant_queue_depth": (
        "requests queued in the gateway per tenant class (raw tenant "
        "ids stay in logs/traces/JSON summaries; the label vocabulary "
        'is the closed tenancy.TENANT_CLASSES set); labeled '
        'tenant_class="…"'
    ),
    "serving_tenant_shed_total": (
        "requests refused or swept by the brown-out ladder per tenant "
        "class (admission sheds + proportional stage-2 queue sweeps); "
        'labeled tenant_class="…"'
    ),
    "serving_tenant_quota_rejected_total": (
        "requests refused by the tenant's own QoS contract (quota QPS "
        "token bucket or max_queued bound) per tenant class — 429s, "
        'not fleet 503s; labeled tenant_class="…"'
    ),
    # -- continuous sampling profiler (utils/contprof.py) --------------
    "dlrover_prof_samples_total": (
        "stack samples taken by the always-on sampling profiler since "
        "start/reset (all threads, ~19 Hz jittered)"
    ),
    "dlrover_prof_wait_samples_total": (
        "profiler samples whose leaf frame was a blocking primitive "
        "(wait/select/recv/...) — off-CPU time"
    ),
    "dlrover_prof_run_samples_total": (
        "profiler samples on-CPU (leaf frame not a known blocking "
        "primitive) — where GIL-holding cycles go"
    ),
    "dlrover_prof_stacks": (
        "distinct folded stacks currently held in the profiler's "
        "bounded table"
    ),
    "dlrover_prof_threads": (
        "distinct threads the profiler has sampled since start/reset"
    ),
    "dlrover_prof_stack_evictions_total": (
        "cold folded stacks evicted into the per-thread (other) "
        "bucket when the bounded table overflowed"
    ),
    "dlrover_prof_tick_lag_seconds": (
        "EMA of the sampler thread's own wake-up lateness — a "
        "GIL/scheduler starvation probe (runnable threads starve the "
        "sampler exactly when they starve each other)"
    ),
    "serving_prof_phase_samples": (
        "profiler samples attributed to each router step phase via "
        "per-thread phase marks — phase SELF time (on-thread samples) "
        "next to the serving_step_phase_seconds wall-clock histograms; "
        'labeled phase="…" from the closed STEP_PHASES vocabulary'
    ),
    # -- master goodput ledger (dist_master.master_metrics) ------------
    "dlrover_master_step_skew_seconds": (
        "per-rank step-time deviation from the fleet median "
        "(SpeedMonitor.step_skew) — positive means the rank is slower "
        "than its peers, the straggler evidence behind the "
        'check_straggler RPC; labeled rank="…" bounded by world size'
    ),
    "dlrover_master_goodput": (
        "productive-step time over available wall time since job "
        "start (planned-elasticity windows excluded from the "
        "denominator) — the paper's headline metric, scrapeable"
    ),
    "dlrover_master_steady_goodput": (
        "goodput measured from the FIRST step report (launch/compile "
        "cost amortized out) — the number comparable to the 95% claim"
    ),
    "dlrover_master_downtime_seconds_total": (
        "wall seconds lost to faults/restarts (planned elasticity "
        "excluded)"
    ),
    "dlrover_master_planned_elasticity_seconds_total": (
        "wall seconds inside coordinator-initiated shrink/regrow "
        "windows — deliberate chip repurposing, not downtime"
    ),
    "dlrover_master_restarts_observed_total": (
        "worker-group restarts the goodput ledger charged"
    ),
    "dlrover_master_rendezvous_rounds_total": (
        "rendezvous rounds completed by the elastic-training "
        "rendezvous manager (growth, shrink, restart each bump it)"
    ),
    "dlrover_master_nodes_waiting": (
        "agents currently waiting in the rendezvous for a new round"
    ),
    "dlrover_master_world_size": (
        "ranks in the current training comm world"
    ),
    # -- xprof auto-profiling (utils/xprof_metrics.AutoProfiler) -------
    "dlrover_xprof_profiles_total": "xprof captures taken so far",
    "dlrover_xprof_last_capture_timestamp": (
        "unix time of the most recent xprof capture"
    ),
    "dlrover_xprof_device_seconds": (
        "device busy time of the last captured step (self time: a loop "
        "is not counted again with its body)"
    ),
    "dlrover_xprof_collective_seconds_total": (
        "device self time in collectives during the last captured step"
    ),
    "dlrover_xprof_collective_seconds": (
        "per-collective device self time of the last captured step "
        "(labeled op=...)"
    ),
    "dlrover_xprof_scope_seconds": (
        "device self time of the last captured step by the program's own "
        "device scope (labeled scope=...: utils/profiler.device_scope "
        "names, (unscoped), (other programs))"
    ),
}

#: ``serving_``- or ``dlrover_``-prefixed strings that are deliberately
#: NOT metric names (RPC message kinds, datastore table names, the
#: package name, family prefixes).  Kept here so DL006 can tell "known
#: protocol vocabulary" from "accidentally minted metric".
NON_METRIC_SERVING_NAMES = frozenset({
    "serving_plan",      # BrainService RPC kind (brain/service.py)
    "serving_samples",   # datastore table (brain/datastore.py DDL)
    "serving_history",   # datastore query name
    "dlrover_tpu",       # the package/logger/namespace name itself
    "dlrover_step",      # StepTimer.metrics prefix (family above)
    "dlrover_xprof_",    # tempdir prefix (utils/xprof_metrics.py)
    "dlrover_tpu_ckpt",  # shared-memory segment prefix (shm_handler)
    "dlrover_tpu_factory",  # multi-process queue name (constants.py)
    "serving_join",      # fleet migration trace span name (coordinator)
    "serving_joined",    # fleet debt retire reason (coordinator)
    "serving_pressure",  # borrow-evidence trace root name (fleet)
    "serving_slo_",      # SLO family prefix (slo.py slices field names
                         # off it for the collector's /fleet/slo view)
})


#: Declared label keys per labeled metric family — the source of truth
#: dlint's DL010 (metric-label-cardinality) checks labeled-sample
#: construction against.  A family missing here must not be rendered
#: with labels; a key missing from its tuple is a finding; and label
#: VALUES must come from bounded vocabularies (worker names, states,
#: priority bands) — never from per-request identifiers (rid, trace
#: ids, erids) or host:port strings, which would mint one Prometheus
#: series per request and OOM every scraper that aggregates the fleet.
METRIC_LABELS: Dict[str, tuple] = {
    "serving_worker_state": ("worker", "state"),
    # resolved paged-attention impl: vocabulary is the closed
    # {"xla", "pallas"} set (RouterMetrics.render_labeled)
    "serving_attention_impl": ("impl",),
    # router step phases: the closed STEP_PHASES vocabulary in
    # serving/router/metrics.py (one histogram series per phase)
    "serving_step_phase_seconds": ("phase",),
    "serving_slo_compliance": ("band", "window"),
    "serving_slo_burn_rate": ("band", "window"),
    "serving_slo_budget_remaining": ("band",),
    # tenancy families: values come from the closed TENANT_CLASSES
    # vocabulary (serving/tenancy/registry.py), never raw tenant ids
    "serving_slo_class_burn_rate": ("tenant_class", "window"),
    "serving_tenant_queue_depth": ("tenant_class",),
    "serving_tenant_shed_total": ("tenant_class",),
    "serving_tenant_quota_rejected_total": ("tenant_class",),
    # profiler phase self-time: values come from the closed
    # STEP_PHASES vocabulary via ServingRouter's set_phase marks
    "serving_prof_phase_samples": ("phase",),
    # per-rank step skew: ranks are bounded by the training world size
    # (SpeedMonitor prunes departed workers), never per-request ids
    "dlrover_master_step_skew_seconds": ("rank",),
    # device time of the last captured step: collective names come from
    # the XLA module, scopes from the program's device_scope calls (both
    # bounded by the compiled program)
    "dlrover_xprof_collective_seconds": ("op",),
    "dlrover_xprof_scope_seconds": ("scope",),
}


def metric_help(name: str) -> Optional[str]:
    return METRIC_HELP.get(name)

"""Router observability: the serving Prometheus metric set.

Exported through :class:`~dlrover_tpu.utils.profiler.MetricsExporter`
(``exporter.add_source(metrics.metrics)``), the same per-process
``/metrics`` endpoint the trainer uses — one scrape surface for both
halves of the system.  These are also the autoscaler's input signals:
what Grafana plots is exactly what the Brain decides replica counts
from (goodput-style: one source of truth for humans and the control
loop).

Every name emitted here is declared with help text in
:mod:`dlrover_tpu.utils.metric_registry` — the single registry dlint's
DL006 check enforces (``python -m tools.dlint dlrover_tpu``), so the
``serving_*`` namespace cannot silently fork.

Gauge/counter names (stable API, documented in README + PERF.md):

- ``serving_queue_depth``        — requests waiting in the gateway
- ``serving_inflight``           — requests currently on replicas
- ``serving_replica_up``         — schedulable replicas
- ``serving_replica_draining``   — replicas finishing in-flight work
- ``serving_ttft_seconds``       — time-to-first-token, window mean
  (plus ``_p50`` / ``_p99`` from a reservoir)
- ``serving_tokens_per_second``  — generated-token throughput (window)
- ``serving_requests_{submitted,completed,rejected,timed_out,
  requeued,poisoned,cancelled}_total`` — lifecycle counters
  (``requeued`` counts failover replays: nonzero says a replica died;
  completed+timed_out+cancelled accounting still balancing says
  nothing was lost; ``poisoned`` counts requests failed for exceeding
  the failover-replay cap — a nonzero value says some request was
  crashing replicas; ``cancelled`` counts caller withdrawals)
- ``serving_cancel_send_failures_total`` — CANCEL frames that could
  not be delivered to a replica
- ``serving_worker_quarantined_total`` — crash-looping workers the
  supervisor stopped respawning (respawn budget exhausted)
- ``serving_replica_probation``  — replicas in crash-loop probation
  (joined but held out of placement during their cooldown)
- ``serving_phi_max`` / ``serving_replica_suspect`` — gray-failure
  detection: the fleet's worst phi-accrual suspicion level and the
  count of replicas currently demoted in placement (suspected, or
  inside the flap-damping hold after recovering)
- ``serving_replica_suspect_{demotions,recoveries}_total`` and
  ``serving_suspect_flaps_damped_total`` — suspicion lifecycle
  counters (a flap absorbed by the hold is damped, not a transition)
- ``serving_hedge_{dispatched,won,cancelled,budget_exhausted,
  promoted}_total`` + ``serving_hedge_active`` — request hedging:
  second attempts dispatched, races the hedge won, loser CANCELs,
  budget denials, primaries-died-hedge-took-over promotions, and the
  currently-racing count
- ``serving_{ttft_hist,queue_wait,e2e_latency,token_gap}_seconds``
  — OpenMetrics latency histograms (``_bucket``/``_count``/``_sum``,
  log-spaced buckets) with ``trace_id`` exemplars on the buckets, so
  "p99 TTFT spiked" drills down to the exact trace via ``/traces``
  (rendered by :meth:`RouterMetrics.render_histograms`)

TTFT semantics: for streaming engines (the remote replica fabric and
the in-process adapter) ``serving_ttft_seconds`` measures submission to
the FIRST TOKEN actually received, not to the first post-placement
router pump.

These aggregates answer "how is the fleet doing"; the per-request
companion — WHERE one request's time went — is the span tracer
(``utils/tracing.py``): the gateway traces every request from
admission, ``exporter.attach_tracer(router.tracer)`` adds the
``serving_request_trace_*`` gauges to this same scrape plus the
``/traces`` + ``/traces/slowest`` JSON views.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

from dlrover_tpu.common.retry import retry_metrics
from dlrover_tpu.utils.profiler import (
    Histogram,
    StepTimer,
    WindowGauge,
    log_buckets,
)

#: Closed label vocabulary for ``serving_step_phase_seconds`` — one
#: histogram series per router step phase (METRIC_LABELS declares the
#: ``phase`` key; dlint DL010 pins the family).  ``deliver`` and
#: ``flush`` run OUTSIDE the step lock (DL007 discipline), the rest
#: hold it — comparing their sums against
#: ``serving_step_lock_hold_seconds`` attributes the lock's tail.
STEP_PHASES = (
    "expire", "cancel", "brownout", "failover", "schedule", "hedge",
    "deliver", "pump", "retire", "observe", "autoscale", "flush",
)


class RouterMetrics:
    """Aggregates router signals into one Prometheus-ready dict, plus
    the OpenMetrics latency histograms (:meth:`render_histograms`) —
    TTFT, queue wait, end-to-end latency and decode-step time, each
    bucket carrying a ``trace_id`` exemplar so a spike drills down to
    the exact trace that caused it."""

    def __init__(self, window_seconds: float = 60.0):
        self.queue_depth = 0.0
        self.inflight = 0.0
        self.replica_up = 0.0
        self.replica_draining = 0.0
        self.replica_probation = 0.0
        # gray-failure plane (phi-accrual suspicion + hedging books),
        # written by the router's observe sweep each step
        self.phi_max = 0.0
        self.replica_suspect = 0.0
        self.suspect_demotions = 0.0
        self.suspect_recoveries = 0.0
        self.suspect_flaps_damped = 0.0
        self.hedge_active = 0.0
        self.hedge_dispatched = 0.0
        self.hedge_won = 0.0
        self.hedge_cancelled = 0.0
        self.hedge_budget_exhausted = 0.0
        self.hedge_promoted = 0.0
        # brown-out ladder position (0 normal .. 3 shed_normal),
        # written by the router's watermark sweep each step
        self.brownout_stage = 0.0
        # capacity debts currently open (quarantined workers /
        # probationary replicas awaiting their replacement), written by
        # the autoscaler's debt sweep
        self.capacity_debt = 0.0
        # raw-speed engine aggregates, written by the router's
        # engine-metrics sweep each step (replicas whose engines report
        # the introspection dict — local adapters and llama workers)
        self.spec_accept_ratio = 0.0
        self.kv_quant_blocks = 0.0
        self.prefill_chunk_seconds = 0.0
        self.paged_kernel_step_seconds = 0.0
        self.kv_rows_live = 0.0
        self.kv_rows_streamed = 0.0
        self.dsa_rows_live = 0.0
        self.attn_rows_selected = 0.0
        self.moe_picks = 0.0
        self.moe_picks_held = 0.0
        self.moe_buffer_walks = 0.0
        self.moe_layer_forwards = 0.0
        self.prefill_query_tiles = 0.0
        self.prefill_query_tiles_live = 0.0
        self.window_rows_in_window = 0.0
        self.window_rows_streamed = 0.0
        self.window_cache_bytes = 0.0
        self.cache_bytes = 0.0
        self.dispatches = 0.0
        self.chained_dispatches = 0.0
        self.lookahead_steps = 0.0
        self.wasted_lane_chunks = 0.0
        # prefix-cache fleet aggregates (engine-side COW ledger summed
        # over reporting replicas, same sweep as the raw-speed keys)
        self.prefix_hits = 0.0
        self.prefix_misses = 0.0
        self.prefix_evictions = 0.0
        self.prefix_cow = 0.0
        self.prefix_revivals = 0.0
        self.prefix_shared_tokens = 0.0
        self.prefix_lingers = 0.0
        self.prefix_forgotten = 0.0
        self.prefix_evicted_head_drops = 0.0
        self.prefix_shared_blocks = 0.0
        self.prefix_cached_blocks = 0.0
        self.prefix_lru_blocks = 0.0
        # router-side prefix-routing table counters, mirrored from the
        # scheduler by the observe sweep (like the sched_* counters)
        self.prefix_route_entries = 0.0
        self.prefix_route_hits = 0.0
        self.prefix_route_misses = 0.0
        self.prefix_route_invalidations = 0.0
        self.prefix_route_placements = 0.0
        # resolved paged-attention impl per reporting replica, counted
        # into the labeled serving_attention_impl family (bounded
        # vocabulary: "xla" | "pallas")
        self.attention_impls: Dict[str, int] = {}
        # per-tenant-CLASS QoS gauges/counters (tenancy.TENANT_CLASSES
        # keys only — raw tenant ids never reach a label value, DL010),
        # written by the router's observe sweep from the gateway's
        # registry books each step
        self.tenant_queue_depth: Dict[str, float] = {}
        self.tenant_shed: Dict[str, float] = {}
        self.tenant_quota_rejected: Dict[str, float] = {}
        self.submitted = 0
        self.completed = 0
        self.rejected = 0
        self.timed_out = 0
        self.requeued = 0
        self.poisoned = 0
        self.cancelled = 0
        self.cancel_send_failures = 0
        self.worker_quarantined = 0
        self.generated_tokens = 0
        self.ttft = StepTimer()
        self._ttft_window = WindowGauge(window_seconds)
        self._tokens_window = WindowGauge(window_seconds)
        self._depth_window = WindowGauge(window_seconds)
        # latency distributions (histogram names are distinct from the
        # window gauges above — serving_ttft_seconds stays the mean);
        # help text comes from the registry so docs can't fork
        from dlrover_tpu.utils.metric_registry import metric_help

        def _hist(name: str, **kw) -> Histogram:
            return Histogram(name, help_text=metric_help(name) or "",
                             **kw)

        self.ttft_hist = _hist("serving_ttft_hist_seconds")
        self.queue_wait_hist = _hist("serving_queue_wait_seconds")
        self.e2e_hist = _hist("serving_e2e_latency_seconds")
        self.token_gap_hist = _hist("serving_token_gap_seconds")
        # step-loop instrumentation (measure FIRST, then attack what
        # the histograms name): per-critical-section lock hold time +
        # per-phase wall time of each router step round.  µs-floor
        # buckets — a healthy step's phases are micro- not
        # milliseconds, and the ladder must resolve them
        self.step_lock_hist = _hist(
            "serving_step_lock_hold_seconds",
            buckets=log_buckets(1e-6, 1.0))
        self.step_phase_hists: Dict[str, Histogram] = {
            phase: Histogram(
                "serving_step_phase_seconds",
                help_text=metric_help("serving_step_phase_seconds")
                or "",
                buckets=log_buckets(1e-6, 1.0),
                labels={"phase": phase})
            for phase in STEP_PHASES
        }
        # scheduler fast-path counters, mirrored from the scheduler by
        # the router's observe sweep (regression surface for the
        # incremental placement index)
        self.sched_capacity_evals = 0.0
        self.sched_rounds_skipped = 0.0

    # ------------------------------------------------------- observe
    def observe_gauges(
        self,
        queue_depth: int,
        inflight: int,
        replica_up: int,
        replica_draining: int,
        now: Optional[float] = None,
        replica_probation: int = 0,
    ) -> None:
        now = time.monotonic() if now is None else now
        self.queue_depth = float(queue_depth)
        self.inflight = float(inflight)
        self.replica_up = float(replica_up)
        self.replica_draining = float(replica_draining)
        self.replica_probation = float(replica_probation)
        self._depth_window.observe(float(queue_depth), now)

    def observe_ttft(self, seconds: float,
                     now: Optional[float] = None,
                     trace_id: Optional[str] = None) -> None:
        self.ttft.observe(seconds)
        self._ttft_window.observe(seconds, now)
        self.ttft_hist.observe(seconds, trace_id=trace_id)

    def observe_queue_wait(self, seconds: float,
                           trace_id: Optional[str] = None) -> None:
        """Admission-to-placement wait of one placement attempt."""
        self.queue_wait_hist.observe(seconds, trace_id=trace_id)

    def observe_e2e(self, seconds: float,
                    trace_id: Optional[str] = None) -> None:
        """Admission-to-completion latency of a finished request."""
        self.e2e_hist.observe(seconds, trace_id=trace_id)

    def observe_token_gap(self, seconds: float,
                          trace_id: Optional[str] = None) -> None:
        """Between two deliveries of tokens to one request
        (``ServingRequest._delivered``; an attempt's first has none)."""
        self.token_gap_hist.observe(seconds, trace_id=trace_id)

    def observe_step_lock(self, seconds: float) -> None:
        """One step-lock critical section's hold time."""
        self.step_lock_hist.observe(seconds)

    def observe_step_phase(self, phase: str, seconds: float) -> None:
        """Wall seconds one router step spent in ``phase`` (must be in
        :data:`STEP_PHASES` — the label vocabulary is closed)."""
        hist = self.step_phase_hists.get(phase)
        if hist is not None:
            hist.observe(seconds)

    def observe_engine_metrics(self, dicts) -> None:
        """Fold per-replica engine introspection dicts into the fleet
        aggregates: accept ratio averages over reporting replicas (a
        fleet-health fraction), the int8 pool size sums (fleet KV
        capacity), chunk seconds sum (a counter across engines).
        Recomputed from scratch every sweep — when the reporting
        replicas leave the fleet the gauges must fall to zero, not
        freeze at the dead fleet's values."""
        dicts = [d for d in dicts if d]
        ratios = [d["spec_accept_ratio"] for d in dicts
                  if "spec_accept_ratio" in d]
        self.spec_accept_ratio = (
            sum(ratios) / len(ratios) if ratios else 0.0)
        self.kv_quant_blocks = sum(
            d.get("kv_quant_blocks", 0.0) for d in dicts)
        self.prefill_chunk_seconds = sum(
            d.get("prefill_chunk_seconds", 0.0) for d in dicts)
        self.paged_kernel_step_seconds = sum(
            d.get("paged_kernel_step_seconds", 0.0) for d in dicts)
        self.kv_rows_live = sum(
            d.get("kv_rows_live", 0.0) for d in dicts)
        self.kv_rows_streamed = sum(
            d.get("kv_rows_streamed", 0.0) for d in dicts)
        for name in ("dsa_rows_live", "attn_rows_selected", "moe_picks",
                     "moe_picks_held", "moe_buffer_walks",
                     "moe_layer_forwards", "prefill_query_tiles",
                     "prefill_query_tiles_live", "dispatches",
                     "chained_dispatches", "lookahead_steps",
                     "wasted_lane_chunks", "window_rows_in_window",
                     "window_rows_streamed", "window_cache_bytes",
                     "cache_bytes"):
            setattr(self, name, sum(d.get(name, 0.0) for d in dicts))
        for attr, key in (
            ("prefix_hits", "prefix_hits"),
            ("prefix_misses", "prefix_misses"),
            ("prefix_evictions", "prefix_evictions"),
            ("prefix_cow", "prefix_cow"),
            ("prefix_revivals", "prefix_revivals"),
            ("prefix_shared_tokens", "prefix_shared_tokens"),
            ("prefix_lingers", "prefix_lingers"),
            ("prefix_forgotten", "prefix_forgotten"),
            ("prefix_evicted_head_drops", "prefix_evicted_head_drops"),
            ("prefix_shared_blocks", "prefix_shared_blocks"),
            ("prefix_cached_blocks", "prefix_cached_blocks"),
            ("prefix_lru_blocks", "prefix_lru_blocks"),
        ):
            setattr(self, attr,
                    sum(d.get(key, 0.0) for d in dicts))
        impls: Dict[str, int] = {}
        for d in dicts:
            if "attention_impl_pallas" in d:
                key = ("pallas" if d["attention_impl_pallas"]
                       else "xla")
                impls[key] = impls.get(key, 0) + 1
        self.attention_impls = impls

    def observe_tenants(
        self,
        queue_depth: Dict[str, float],
        shed: Dict[str, float],
        quota_rejected: Dict[str, float],
    ) -> None:
        """Per-tenant-class books, already aggregated onto the bounded
        vocabulary by ``TenantRegistry.by_class`` — this layer never
        sees a raw tenant id."""
        self.tenant_queue_depth = dict(queue_depth)
        self.tenant_shed = dict(shed)
        self.tenant_quota_rejected = dict(quota_rejected)

    def observe_tokens(self, n: int, now: Optional[float] = None) -> None:
        self.generated_tokens += int(n)
        self._tokens_window.observe(float(n), now)

    # --------------------------------------------------------- views
    def queue_depth_mean(self, now: Optional[float] = None) -> float:
        return self._depth_window.mean(now)

    def ttft_mean(self, now: Optional[float] = None) -> float:
        return self._ttft_window.mean(now)

    def tokens_per_second(self, now: Optional[float] = None) -> float:
        return self._tokens_window.rate(now)

    def metrics(self) -> Dict[str, float]:
        """The Prometheus source (``MetricsExporter.add_source``)."""
        return {
            # process-wide control-plane retry counter (common/retry
            # owns the metric name): master + Brain RPC retries under
            # the backoff policy
            **retry_metrics(),
            "serving_queue_depth": self.queue_depth,
            "serving_inflight": self.inflight,
            "serving_replica_up": self.replica_up,
            "serving_replica_draining": self.replica_draining,
            "serving_ttft_seconds": self.ttft_mean(),
            "serving_ttft_seconds_p50": self.ttft.percentile(50),
            "serving_ttft_seconds_p99": self.ttft.percentile(99),
            "serving_tokens_per_second": self.tokens_per_second(),
            "serving_generated_tokens_total": float(self.generated_tokens),
            "serving_requests_submitted_total": float(self.submitted),
            "serving_requests_completed_total": float(self.completed),
            "serving_requests_rejected_total": float(self.rejected),
            "serving_requests_timed_out_total": float(self.timed_out),
            "serving_requests_requeued_total": float(self.requeued),
            "serving_requests_poisoned_total": float(self.poisoned),
            "serving_requests_cancelled_total": float(self.cancelled),
            "serving_cancel_send_failures_total": float(
                self.cancel_send_failures),
            "serving_worker_quarantined_total": float(
                self.worker_quarantined),
            "serving_replica_probation": self.replica_probation,
            "serving_phi_max": self.phi_max,
            "serving_replica_suspect": self.replica_suspect,
            "serving_replica_suspect_demotions_total":
                self.suspect_demotions,
            "serving_replica_suspect_recoveries_total":
                self.suspect_recoveries,
            "serving_suspect_flaps_damped_total":
                self.suspect_flaps_damped,
            "serving_hedge_active": self.hedge_active,
            "serving_hedge_dispatched_total": self.hedge_dispatched,
            "serving_hedge_won_total": self.hedge_won,
            "serving_hedge_cancelled_total": self.hedge_cancelled,
            "serving_hedge_budget_exhausted_total":
                self.hedge_budget_exhausted,
            "serving_hedge_promoted_total": self.hedge_promoted,
            "serving_brownout_stage": self.brownout_stage,
            "serving_capacity_debt": self.capacity_debt,
            "serving_spec_accept_ratio": self.spec_accept_ratio,
            "serving_kv_quant_blocks": self.kv_quant_blocks,
            "serving_prefill_chunk_seconds": self.prefill_chunk_seconds,
            "serving_paged_kernel_step_seconds":
                self.paged_kernel_step_seconds,
            "serving_paged_kv_stream_ratio": (
                self.kv_rows_streamed / self.kv_rows_live
                if self.kv_rows_live else 0.0),
            "serving_engine_chained_dispatch_share": (
                self.chained_dispatches / self.dispatches
                if self.dispatches else 0.0),
            "serving_moe_walks_per_layer": (
                self.moe_buffer_walks / self.moe_layer_forwards
                if self.moe_layer_forwards else 0.0),
            "serving_engine_lookahead_steps_total": self.lookahead_steps,
            "serving_engine_wasted_lane_chunks_total":
                self.wasted_lane_chunks,
            "serving_dsa_selected_ratio": (
                self.attn_rows_selected / self.dsa_rows_live
                if self.dsa_rows_live else 0.0),
            "serving_moe_held_share": (
                self.moe_picks_held / self.moe_picks
                if self.moe_picks else 0.0),
            "serving_prefill_live_tile_share": (
                self.prefill_query_tiles_live / self.prefill_query_tiles
                if self.prefill_query_tiles else 0.0),
            "serving_window_stream_ratio": (
                self.window_rows_streamed / self.window_rows_in_window
                if self.window_rows_in_window else 0.0),
            "serving_window_cache_share": (
                self.window_cache_bytes / self.cache_bytes
                if self.cache_bytes else 0.0),
            "serving_sched_capacity_evals_total":
                self.sched_capacity_evals,
            "serving_sched_rounds_skipped_total":
                self.sched_rounds_skipped,
            "serving_prefix_hits_total": self.prefix_hits,
            "serving_prefix_misses_total": self.prefix_misses,
            "serving_prefix_evictions_total": self.prefix_evictions,
            "serving_prefix_cow_total": self.prefix_cow,
            "serving_prefix_revivals_total": self.prefix_revivals,
            "serving_prefix_shared_tokens_total":
                self.prefix_shared_tokens,
            "serving_prefix_lingers_total": self.prefix_lingers,
            "serving_prefix_forgotten_total": self.prefix_forgotten,
            "serving_prefix_evicted_head_drops_total":
                self.prefix_evicted_head_drops,
            "serving_prefix_shared_blocks": self.prefix_shared_blocks,
            "serving_prefix_cached_blocks": self.prefix_cached_blocks,
            "serving_prefix_lru_blocks": self.prefix_lru_blocks,
            "serving_prefix_route_entries": self.prefix_route_entries,
            "serving_prefix_route_hits_total": self.prefix_route_hits,
            "serving_prefix_route_misses_total":
                self.prefix_route_misses,
            "serving_prefix_route_invalidations_total":
                self.prefix_route_invalidations,
            "serving_prefix_route_placements_total":
                self.prefix_route_placements,
        }

    def render_histograms(self) -> str:
        """OpenMetrics histogram text with trace-exemplar drill-down —
        wire via ``MetricsExporter.add_text_source`` (or the one-call
        ``exporter.attach_router(router)``)."""
        parts = [h.render() for h in (
            self.ttft_hist, self.queue_wait_hist,
            self.e2e_hist, self.token_gap_hist,
            self.step_lock_hist,
        )]
        # the phase histograms are ONE family fanned out by label: emit
        # the # TYPE/# HELP header once, then each phase's samples
        for i, phase in enumerate(STEP_PHASES):
            text = self.step_phase_hists[phase].render()
            if i:
                text = "".join(
                    line for line in text.splitlines(keepends=True)
                    if not line.startswith("# "))
            parts.append(text)
        return "".join(parts)

    def otlp_labeled(self) -> list:
        """Labeled gauges for the OTLP push path
        (``OtlpExporter.add_labeled_source``): the per-tenant-class
        usage counters, so the fleet collector's ``/fleet/metrics``
        sees the QoS books and not just the local ``/tenants/usage``
        JSON.  Same closed TENANT_CLASSES vocabulary (zero-filled) as
        the /metrics render — raw tenant ids never leave the gateway."""
        from dlrover_tpu.serving.tenancy import TENANT_CLASSES

        out = []
        for name, book in (
            ("serving_tenant_queue_depth", self.tenant_queue_depth),
            ("serving_tenant_shed_total", self.tenant_shed),
            ("serving_tenant_quota_rejected_total",
             self.tenant_quota_rejected),
        ):
            for cls in TENANT_CLASSES:
                out.append((name, {"tenant_class": cls},
                            float(book.get(cls, 0.0))))
        return out

    def render_labeled(self) -> str:
        """Labeled gauge text for the /metrics scrape: replicas per
        resolved paged-attention impl.  The ``impl`` vocabulary is
        bounded ("xla" | "pallas" — DL010-declared in the registry);
        both series render even at zero so a fleet-wide impl flip is a
        visible crossover, not a disappearing line."""
        from dlrover_tpu.utils.metric_registry import metric_help

        lines = [
            "# HELP serving_attention_impl "
            + (metric_help("serving_attention_impl") or ""),
            "# TYPE serving_attention_impl gauge",
        ]
        for impl in ("xla", "pallas"):
            n = self.attention_impls.get(impl, 0)
            lines.append(
                f'serving_attention_impl{{impl="{impl}"}} {n}')
        # tenancy families: every class in the closed vocabulary
        # renders even at zero, so a class going dark is a visible
        # flatline, not a disappearing series
        from dlrover_tpu.serving.tenancy import TENANT_CLASSES
        for name, book in (
            ("serving_tenant_queue_depth", self.tenant_queue_depth),
            ("serving_tenant_shed_total", self.tenant_shed),
            ("serving_tenant_quota_rejected_total",
             self.tenant_quota_rejected),
        ):
            lines.append(
                f"# HELP {name} " + (metric_help(name) or ""))
            lines.append(f"# TYPE {name} gauge")
            for cls in TENANT_CLASSES:
                lines.append(
                    f'{name}{{tenant_class="{cls}"}} '
                    f"{book.get(cls, 0.0):g}")
        return "\n".join(lines) + "\n"

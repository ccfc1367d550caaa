"""Open-loop traffic generation + the 10k-QPS gateway rig.

A closed-loop load test (N workers, each waiting for its answer before
sending the next request) measures the SERVER's pace and politely
backs off exactly when the system degrades — it cannot see the cliff.
Real traffic is **open-loop**: users arrive when they arrive, whether
or not the gateway is keeping up.  This module generates that traffic
and drives it at the in-process serving stack:

- :class:`OpenLoopGenerator` — a **seeded, replayable** arrival
  schedule: Poisson / bursty (on-off square wave) / diurnal
  (sinusoidal, a compressed day) arrival processes, heavy-tailed
  (Pareto) or fixed prompt lengths, and a per-priority mix.  Same
  config + seed -> byte-identical schedule, so a perf regression
  re-runs the EXACT offered load that exposed it;
- :func:`run_gateway_rig` — the gateway harness (``tests/test_otlp.py``
  drives it): replays a schedule against a router wall-clock
  open-loop, measuring what the GATEWAY itself costs — per-request
  admission latency (the ``submit()`` call: validation, brown-out
  check, queue insert, trace creation), admission→placement wait,
  shed behavior per priority band, SLO verdicts from the router's
  burn-rate engine, and the OTLP exporter's proof counters when one
  is wired.  The queue bound and the brown-out ladder are expected
  to bite at rate: shed requests ARE the measurement, not a failure.

- :func:`run_router_rig` — the FULL-pipeline twin
  (``tests/test_step_engine.py::test_router_rig_*``, ``test_tenancy``,
  ``test_prefix_cache``): the same open-loop schedule driven through the
  WHOLE serving path — admission, placement, submit, streamed tokens,
  DONE — against a fleet of in-process engines, measuring sustained
  **end-to-end** QPS, e2e latency percentiles from the completed
  requests themselves, and the zero-lost/books accounting identity
  (admitted == done + timed_out + cancelled + rejected + poisoned,
  poisoned == 0, nothing non-terminal after the drain).  This is the
  step loop's own perf trajectory next to the gateway's: the admission
  rig proved the front door sustains ~15k QPS, this one holds the
  step engine behind it to the ``router_qps_ok`` bar.  Seeded
  mid-flight cancels (``cancel_every``) make the nightly soak exercise
  the withdrawal machinery at rate.

Everything here is driver-side; the router under test is the real
one, unmodified — any object with ``submit``/``step``/``has_work``
(a :class:`~dlrover_tpu.serving.router.router.ServingRouter`) drives
identically.
"""

from __future__ import annotations

import dataclasses
import math
import random
import time
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from dlrover_tpu.common.constants import ServingRequestState
from dlrover_tpu.serving.router.gateway import (
    PRIORITY_BATCH,
    PRIORITY_HIGH,
    PRIORITY_NORMAL,
    AdmissionError,
    BrownoutShedError,
    QueueFullError,
    TenantQuotaError,
)
from dlrover_tpu.serving.router.slo import BAND_NAMES


@dataclasses.dataclass
class LoadgenConfig:
    """One replayable offered-load description."""

    seed: int = 0
    rate_qps: float = 12000.0       # mean offered arrival rate
    duration_s: float = 2.0         # schedule horizon (virtual time)
    arrival: str = "poisson"        # poisson | bursty | diurnal
    burst_factor: float = 4.0       # bursty: on-phase rate multiplier
    burst_period_s: float = 0.5     # bursty: one on+off cycle
    diurnal_period_s: float = 4.0   # diurnal: one compressed "day"
    diurnal_amplitude: float = 0.8  # peak/trough swing (0..1)
    prompt_mix: str = "heavy_tail"  # heavy_tail | fixed
    prompt_min: int = 8
    prompt_max: int = 512
    pareto_alpha: float = 1.5       # heavy tail: smaller = heavier
    max_new_tokens: int = 32
    # (priority, weight) admission mix — the default mirrors a fleet
    # where interactive traffic dominates and batch rides along
    priority_mix: Tuple[Tuple[int, float], ...] = (
        (PRIORITY_HIGH, 0.1),
        (PRIORITY_NORMAL, 0.6),
        (PRIORITY_BATCH, 0.3),
    )
    # (tenant, weight) identity mix; empty = untenanted legacy traffic
    # (arrivals carry tenant=None and submit omits the kwarg).  Tenant
    # picks draw from their OWN seeded stream so configuring a mix
    # cannot perturb the arrival times/prompts an existing seed
    # replays byte-identically.
    tenant_mix: Tuple[Tuple[str, float], ...] = ()
    # prompt CONTENT shape (the prefix-cache workloads):
    # - "independent": every prompt is unrelated content (the legacy
    #   shape; np.arange prompts, zero sharable prefix);
    # - "chat": multi-turn conversations over ``chat_sessions``
    #   concurrent sessions — each arrival is the next turn of a
    #   (seeded) random session, and a turn's prompt EXTENDS the
    #   previous turn's prompt + answer, so consecutive turns share a
    #   growing prefix (the COW cache's bread-and-butter reuse);
    # - "sysprompt": the shared-system-prompt flood — every arrival is
    #   one ``system_prompt_len``-token prompt shared by ALL users
    #   plus a unique per-user tail (the N-users-one-template shape
    #   the dedup gate measures).
    # Workload draws ride their OWN seeded streams: existing seeds of
    # the "independent" shape replay byte-identically.
    workload: str = "independent"   # independent | chat | sysprompt
    system_prompt_len: int = 256    # chat/sysprompt shared head
    chat_sessions: int = 8          # concurrent conversations
    chat_turn_tokens: int = 32      # new user tokens per turn


@dataclasses.dataclass
class Arrival:
    at_s: float          # offset from schedule start (virtual time)
    prompt_len: int
    max_new_tokens: int
    priority: int
    tenant: Optional[str] = None
    # prefix-workload identity (prompt CONTENT is a pure function of
    # these + the config, via prompt_tokens): uid distinguishes users
    # in the sysprompt flood; session/turn name the conversation slot
    # and its turn number in the chat workload
    uid: int = 0
    session: int = -1
    turn: int = 0


def _tok_stream(n: int, salt: int) -> np.ndarray:
    """Deterministic pseudo-token content: ``n`` int32 ids in
    [0, 32000) from a salted multiplicative stream.  Same (n, salt) ->
    identical array, and a longer stream with the same salt EXTENDS the
    shorter one — which is exactly the property the chat workload needs
    (turn t's prompt is a strict prefix-extension of turn t-1's)."""
    if n <= 0:
        return np.zeros(0, dtype=np.int32)
    return ((np.arange(n, dtype=np.int64) * 2654435761
             + salt * 40503 + 11) % 32000).astype(np.int32)


#: salt of the fleet-wide shared system prompt (sysprompt workload)
_SYSPROMPT_SALT = 0xC0FFEE
#: per-session stream base salt (chat workload)
_CHAT_SALT = 0x5E55


def prompt_tokens(arrival: Arrival, cfg: LoadgenConfig) -> np.ndarray:
    """The arrival's prompt CONTENT (deterministic; rigs call this
    instead of the np.arange pool for the prefix workloads).

    - chat: one salted stream per session slot, truncated at the
      turn's length — every turn extends the previous turn's prompt;
    - sysprompt: the shared system-prompt head (same salt for every
      user) + a unique per-uid tail;
    - independent: the legacy np.arange prompt."""
    if cfg.workload == "chat":
        return _tok_stream(
            arrival.prompt_len, _CHAT_SALT + arrival.session)
    if cfg.workload == "sysprompt":
        head = _tok_stream(cfg.system_prompt_len, _SYSPROMPT_SALT)
        tail = _tok_stream(
            arrival.prompt_len - cfg.system_prompt_len,
            1 + arrival.uid)
        return np.concatenate([head, tail])
    return np.arange(arrival.prompt_len, dtype=np.int32)


class OpenLoopGenerator:
    """Seeded arrival-schedule generator (see module docstring)."""

    def __init__(self, config: Optional[LoadgenConfig] = None):
        self.config = config or LoadgenConfig()
        if self.config.arrival not in ("poisson", "bursty", "diurnal"):
            raise ValueError(
                f"unknown arrival process {self.config.arrival!r}")
        if self.config.workload not in (
                "independent", "chat", "sysprompt"):
            raise ValueError(
                f"unknown workload {self.config.workload!r}")

    def _rate_at(self, t: float) -> float:
        cfg = self.config
        if cfg.arrival == "bursty":
            # square-wave on/off, NORMALIZED so the mean stays
            # rate_qps whatever the burst factor: the on half runs at
            # burst_factor x the (floored) off half, and both are
            # scaled by 2/(on+off) — a bursty-vs-poisson comparison
            # at equal nominal rate really compares shapes, not rates
            phase = (t % cfg.burst_period_s) / cfg.burst_period_s
            on = float(cfg.burst_factor)
            off = max(0.05, 2.0 - on)
            norm = 2.0 / (on + off)
            return cfg.rate_qps * norm * (on if phase < 0.5 else off)
        if cfg.arrival == "diurnal":
            swing = math.sin(2 * math.pi * t / cfg.diurnal_period_s)
            return cfg.rate_qps * (
                1.0 + cfg.diurnal_amplitude * swing)
        return cfg.rate_qps

    def _prompt_len(self, rng: random.Random) -> int:
        cfg = self.config
        if cfg.prompt_mix == "fixed":
            return cfg.prompt_min
        # Pareto body at prompt_min, tail clipped at prompt_max — the
        # heavy-tail mix where one long prompt rides among many short
        return int(min(cfg.prompt_max,
                       cfg.prompt_min * rng.paretovariate(
                           cfg.pareto_alpha)))

    def arrivals(self) -> Iterator[Arrival]:
        """The schedule, in arrival order.  Deterministic per config."""
        cfg = self.config
        rng = random.Random(cfg.seed)
        bands = [p for p, _ in cfg.priority_mix]
        weights = [w for _, w in cfg.priority_mix]
        # tenant identity draws from a SEPARATE seeded stream: adding
        # (or changing) a tenant mix must not move a single arrival
        # time, prompt length or band of an already-seeded schedule
        trng = random.Random(cfg.seed ^ 0x7E4A47)
        tenants = [t for t, _ in cfg.tenant_mix]
        tweights = [w for _, w in cfg.tenant_mix]
        # prefix-workload draws ride their own stream (same invariant
        # as the tenant stream: the chat/sysprompt shape must not move
        # an arrival time or band the main stream already determined)
        wrng = random.Random(cfg.seed ^ 0xC4A7)
        turn_of = [0] * max(1, cfg.chat_sessions)  # per-session turns
        uid = 0
        t = 0.0
        while True:
            rate = max(1e-6, self._rate_at(t))
            t += rng.expovariate(rate)
            if t >= cfg.duration_s:
                return
            # the main stream's draw happens UNCONDITIONALLY so the
            # legacy "independent" schedule replays byte-identically
            # whatever workload is configured on top of it
            drawn_len = self._prompt_len(rng)
            session, turn = -1, 0
            prompt_len = drawn_len
            if cfg.workload == "chat":
                session = wrng.randrange(max(1, cfg.chat_sessions))
                turn = turn_of[session]
                # turn t's prompt = system prompt + t completed
                # (user turn + answer) rounds + this turn's user text;
                # a conversation that would outgrow prompt_max resets
                # its slot (a fresh conversation, same session stream)
                prompt_len = (cfg.system_prompt_len
                              + turn * (cfg.chat_turn_tokens
                                        + cfg.max_new_tokens)
                              + cfg.chat_turn_tokens)
                if prompt_len > cfg.prompt_max and turn > 0:
                    turn_of[session] = 0
                    turn = 0
                    prompt_len = (cfg.system_prompt_len
                                  + cfg.chat_turn_tokens)
                turn_of[session] = turn + 1
            elif cfg.workload == "sysprompt":
                # shared head + the drawn length as the unique tail
                prompt_len = cfg.system_prompt_len + drawn_len
            yield Arrival(
                at_s=t,
                prompt_len=prompt_len,
                max_new_tokens=cfg.max_new_tokens,
                priority=rng.choices(bands, weights)[0],
                tenant=(trng.choices(tenants, tweights)[0]
                        if tenants else None),
                uid=uid,
                session=session,
                turn=turn,
            )
            uid += 1


def _quantiles(sorted_vals: List[float],
               qs: Tuple[float, ...]) -> List[float]:
    if not sorted_vals:
        return [0.0 for _ in qs]
    out = []
    for q in qs:
        idx = min(len(sorted_vals) - 1,
                  int(q / 100.0 * len(sorted_vals)))
        out.append(sorted_vals[idx])
    return out


def hist_quantile(snapshot: Dict[str, object], q: float) -> float:
    """Approximate quantile from a Histogram.snapshot(): linear
    interpolation inside the winning bucket (the standard Prometheus
    histogram_quantile estimate)."""
    counts = list(snapshot["counts"])
    bounds = list(snapshot["buckets"])
    total = sum(counts)
    if total == 0:
        return 0.0
    target = q / 100.0 * total
    cum = 0
    for i, c in enumerate(counts):
        if cum + c >= target and c > 0:
            lo = bounds[i - 1] if i > 0 else 0.0
            hi = bounds[i] if i < len(bounds) else bounds[-1] * 2
            frac = (target - cum) / c
            return lo + (hi - lo) * frac
        cum += c
    return bounds[-1]


def run_gateway_rig(
    router,
    config: Optional[LoadgenConfig] = None,
    step_every: int = 256,
    pace: bool = True,
    admission_reservoir: int = 200_000,
    drain_max_steps: int = 200_000,
    otlp_exporter=None,
) -> Dict[str, object]:
    """Replay one open-loop schedule against ``router`` on the wall
    clock and report the gateway's own cost.

    ``pace=True`` holds the driver to the schedule when it runs ahead
    (so bursty/diurnal shapes survive); it can never slow a driver
    that is BEHIND — achieved QPS below the offered rate is the
    honest "this gateway cannot admit that fast" answer, and the
    bench gates on it.  ``step_every`` bounds how much admission-only
    work happens between router pump rounds."""
    cfg = config or LoadgenConfig()
    gen = OpenLoopGenerator(cfg)
    # pre-built prompt pool: the rig measures the GATEWAY, and
    # np.arange per arrival would time numpy allocation instead.
    # Prefix workloads need CONTENT (shared heads), so they build per
    # arrival via prompt_tokens instead — those rigs measure the
    # cache, not the admission microseconds.
    content = cfg.workload != "independent"
    pool = ({} if content else
            {n: np.arange(n, dtype=np.int32)
             for n in sorted({a.prompt_len for a in gen.arrivals()})})

    # per-submit wall seconds, RESERVOIR-sampled (not first-N: on a
    # 60s soak the p99 must see the final seconds' tail, not only the
    # opening 17s); seeded so the sampling replays with the schedule
    lat: List[float] = []
    lat_rng = random.Random(cfg.seed ^ 0x5EED)
    lat_seen = 0
    # keyed on the CONFIGURED mix (a custom band outside the stock
    # three must count, not KeyError mid-run)
    shed = {band: 0 for band, _ in cfg.priority_mix}
    shed_kinds = {"queue_full": 0, "brownout": 0, "quota": 0,
                  "other": 0}
    admitted = 0
    offered = 0
    steps = 0

    t0 = time.perf_counter()
    since_step = 0
    for arrival in gen.arrivals():
        offered += 1
        if pace:
            ahead = arrival.at_s - (time.perf_counter() - t0)
            if ahead > 0.002:
                time.sleep(ahead)
        prompt = (prompt_tokens(arrival, cfg) if content
                  else pool[arrival.prompt_len])
        kw = ({"tenant": arrival.tenant}
              if arrival.tenant is not None else {})
        s0 = time.perf_counter()
        try:
            router.submit(prompt, arrival.max_new_tokens,
                          priority=arrival.priority, **kw)
            admitted += 1
        except BrownoutShedError:
            shed[arrival.priority] += 1
            shed_kinds["brownout"] += 1
        except QueueFullError:
            shed[arrival.priority] += 1
            shed_kinds["queue_full"] += 1
        except TenantQuotaError:
            shed[arrival.priority] += 1
            shed_kinds["quota"] += 1
        except AdmissionError:
            shed[arrival.priority] += 1
            shed_kinds["other"] += 1
        dt = time.perf_counter() - s0
        lat_seen += 1
        if len(lat) < admission_reservoir:
            lat.append(dt)
        else:  # reservoir sampling keeps the quantiles unbiased
            j = lat_rng.randint(0, lat_seen - 1)
            if j < admission_reservoir:
                lat[j] = dt
        since_step += 1
        if since_step >= step_every:
            since_step = 0
            router.step()
            steps += 1
    offer_wall_s = time.perf_counter() - t0

    # drain: the offered phase is over; pump until the admitted work
    # completes or expires so the SLO verdicts cover every request
    while router.has_work and steps < drain_max_steps:
        router.step()
        steps += 1
    drain_wall_s = time.perf_counter() - t0 - offer_wall_s

    lat.sort()
    p50, p99, p999 = _quantiles(lat, (50, 99, 99.9))
    now = time.monotonic()
    m = router.metrics.metrics()
    result: Dict[str, object] = {
        "gateway_offered": offered,
        "gateway_admitted": admitted,
        "gateway_shed": {BAND_NAMES.get(b, str(b)): n
                         for b, n in shed.items()},
        "gateway_shed_kinds": dict(shed_kinds),
        "gateway_offer_wall_s": round(offer_wall_s, 4),
        "gateway_drain_wall_s": round(drain_wall_s, 4),
        "gateway_qps": round(offered / max(1e-9, offer_wall_s), 1),
        "gateway_admission_p50_us": round(p50 * 1e6, 2),
        "gateway_admission_p99_us": round(p99 * 1e6, 2),
        "gateway_admission_p999_us": round(p999 * 1e6, 2),
        "gateway_router_steps": steps,
        "gateway_completed": int(
            m["serving_requests_completed_total"]),
        "gateway_timed_out": int(
            m["serving_requests_timed_out_total"]),
        "gateway_queue_wait_p50_s": round(hist_quantile(
            router.metrics.queue_wait_hist.snapshot(), 50), 6),
        "gateway_queue_wait_p99_s": round(hist_quantile(
            router.metrics.queue_wait_hist.snapshot(), 99), 6),
    }
    slo = getattr(router, "slo", None)
    if slo is not None:
        result["gateway_slo"] = slo.summary(now)
    if otlp_exporter is not None:
        result["gateway_otlp"] = {
            k: v for k, v in otlp_exporter.metrics().items()}
    return result


def run_router_rig(
    router,
    config: Optional[LoadgenConfig] = None,
    step_every: int = 64,
    pace: bool = True,
    cancel_every: int = 0,
    drain_max_steps: int = 500_000,
    drain_timeout_s: float = 120.0,
) -> Dict[str, object]:
    """Replay one open-loop schedule through the WHOLE pipeline on the
    wall clock: admission -> placement -> submit -> streamed tokens ->
    DONE, against whatever fleet is already joined on ``router``.

    Differences from :func:`run_gateway_rig`, deliberately:

    - every admitted request object is KEPT and audited at the end —
      zero-lost means zero requests outside a terminal state, and the
      books identity is computed from the requests themselves, not
      from the router's counters;
    - the headline number is sustained END-TO-END QPS: completed
      requests over the whole wall (offer + drain) — the step loop
      cannot hide behind a fast front door;
    - e2e percentiles come from ``finished_at - submitted_at`` of the
      completed requests (the router's own monotonic stamps);
    - ``cancel_every=N`` withdraws every Nth admitted request a step
      later (seeded by admission order, replayable): the mid-flight
      cancel mix the nightly soak runs.

    ``step_every`` bounds admissions between router rounds."""
    cfg = config or LoadgenConfig()
    gen = OpenLoopGenerator(cfg)
    content = cfg.workload != "independent"
    pool = ({} if content else
            {n: np.arange(n, dtype=np.int32)
             for n in sorted({a.prompt_len for a in gen.arrivals()})})

    admitted: List[object] = []
    shed = {band: 0 for band, _ in cfg.priority_mix}
    shed_kinds = {"queue_full": 0, "brownout": 0, "quota": 0,
                  "other": 0}
    # per-tenant refusal counts (admission raises before a request
    # object exists, so the ARRIVAL's tenant id is the key here; the
    # admitted-side audit below keys on the RESOLVED req.tenant)
    tenant_rejected: Dict[str, int] = {}
    offered = 0
    steps = 0
    cancelled_by_rig: List[object] = []
    to_cancel: List[object] = []

    t0 = time.perf_counter()
    since_step = 0
    for arrival in gen.arrivals():
        offered += 1
        if pace:
            ahead = arrival.at_s - (time.perf_counter() - t0)
            if ahead > 0.002:
                time.sleep(ahead)
        prompt = (prompt_tokens(arrival, cfg) if content
                  else pool[arrival.prompt_len])
        kw = ({"tenant": arrival.tenant}
              if arrival.tenant is not None else {})
        try:
            req = router.submit(prompt, arrival.max_new_tokens,
                                priority=arrival.priority, **kw)
            admitted.append(req)
            if cancel_every and len(admitted) % cancel_every == 0:
                # withdraw shortly after admission: flushed on the
                # next arrival (typically still queued — a request
                # cannot complete before a router step) or at the next
                # step boundary (by then often RUNNING), so both
                # cancel paths get traffic
                to_cancel.append(req)
            elif to_cancel:
                for marked in to_cancel:
                    if marked.cancel():
                        cancelled_by_rig.append(marked)
                to_cancel.clear()
        except BrownoutShedError:
            shed[arrival.priority] += 1
            shed_kinds["brownout"] += 1
            if arrival.tenant is not None:
                tenant_rejected[arrival.tenant] = \
                    tenant_rejected.get(arrival.tenant, 0) + 1
        except QueueFullError:
            shed[arrival.priority] += 1
            shed_kinds["queue_full"] += 1
            if arrival.tenant is not None:
                tenant_rejected[arrival.tenant] = \
                    tenant_rejected.get(arrival.tenant, 0) + 1
        except TenantQuotaError:
            shed[arrival.priority] += 1
            shed_kinds["quota"] += 1
            if arrival.tenant is not None:
                tenant_rejected[arrival.tenant] = \
                    tenant_rejected.get(arrival.tenant, 0) + 1
        except AdmissionError:
            shed[arrival.priority] += 1
            shed_kinds["other"] += 1
            if arrival.tenant is not None:
                tenant_rejected[arrival.tenant] = \
                    tenant_rejected.get(arrival.tenant, 0) + 1
        since_step += 1
        if since_step >= step_every:
            since_step = 0
            router.step()
            steps += 1
            for req in to_cancel:
                if req.cancel():
                    cancelled_by_rig.append(req)
            to_cancel.clear()
    # a request marked on the schedule's LAST arrival has no later
    # arrival or step boundary to flush it — withdraw it now, before
    # the drain, so "every Nth admitted request" means every Nth
    for req in to_cancel:
        if req.cancel():
            cancelled_by_rig.append(req)
    to_cancel.clear()
    offer_wall_s = time.perf_counter() - t0

    # drain: pump until every admitted request reaches a terminal
    # state (DONE, or the deadline/cancel machinery answers it)
    drain_deadline = time.perf_counter() + drain_timeout_s
    while router.has_work and steps < drain_max_steps \
            and time.perf_counter() < drain_deadline:
        router.step()
        steps += 1
    total_wall_s = time.perf_counter() - t0

    # the audit, from the request objects themselves
    by_state: Dict[str, int] = {}
    e2e: List[float] = []
    terminal_states = (ServingRequestState.DONE,
                       ServingRequestState.TIMED_OUT,
                       ServingRequestState.CANCELLED,
                       ServingRequestState.REJECTED,
                       ServingRequestState.POISONED)
    # per-RESOLVED-tenant books (raw ids are fine in this JSON report
    # — the DL010 bound applies to metric labels, not rig summaries)
    tenant_books: Dict[str, Dict[str, object]] = {}
    for req in admitted:
        by_state[req.state] = by_state.get(req.state, 0) + 1
        done_req = (req.state == ServingRequestState.DONE
                    and req.finished_at is not None)
        if done_req:
            e2e.append(req.finished_at - req.submitted_at)
        name = getattr(req, "tenant", None)
        if name is not None:
            book = tenant_books.setdefault(
                name, {"admitted": 0, "done": 0, "lost": 0,
                       "e2e": []})
            book["admitted"] += 1
            if done_req:
                book["done"] += 1
                book["e2e"].append(req.finished_at - req.submitted_at)
            if req.state not in terminal_states:
                book["lost"] += 1
    done = by_state.get(ServingRequestState.DONE, 0)
    terminal = terminal_states
    lost = sum(n for state, n in by_state.items()
               if state not in terminal)
    poisoned = by_state.get(ServingRequestState.POISONED, 0)
    accounted = sum(by_state.get(s, 0) for s in terminal)
    e2e.sort()
    p50, p99, p999 = _quantiles(e2e, (50, 99, 99.9))
    by_tenant: Dict[str, Dict[str, object]] = {}
    for name in sorted(set(tenant_books) | set(tenant_rejected)):
        book = tenant_books.get(
            name, {"admitted": 0, "done": 0, "lost": 0, "e2e": []})
        tl = sorted(book["e2e"])
        tp50, tp99, _ = _quantiles(tl, (50, 99, 99.9))
        by_tenant[name] = {
            "admitted": book["admitted"],
            "done": book["done"],
            "lost": book["lost"],
            "rejected": tenant_rejected.get(name, 0),
            "e2e_p50_s": round(tp50, 6),
            "e2e_p99_s": round(tp99, 6),
        }
    return {
        "router_offered": offered,
        "router_admitted": len(admitted),
        "router_shed": {BAND_NAMES.get(b, str(b)): n
                        for b, n in shed.items()},
        "router_shed_kinds": dict(shed_kinds),
        "router_by_state": dict(sorted(by_state.items())),
        "router_completed": done,
        "router_cancel_attempts": len(cancelled_by_rig),
        "router_lost": lost,
        "router_poisoned": poisoned,
        # the identity: every admitted request reached exactly one
        # terminal state and nothing fell through the failover /
        # cancel / expiry machinery
        "router_books_ok": bool(
            lost == 0 and accounted == len(admitted)),
        "router_offer_wall_s": round(offer_wall_s, 4),
        "router_total_wall_s": round(total_wall_s, 4),
        "router_steps": steps,
        # sustained END-TO-END throughput: completions over the whole
        # wall — the step loop's own number
        "router_qps": round(done / max(1e-9, total_wall_s), 1),
        "router_offered_qps": round(
            offered / max(1e-9, offer_wall_s), 1),
        "router_e2e_p50_s": round(p50, 6),
        "router_e2e_p99_s": round(p99, 6),
        "router_e2e_p999_s": round(p999, 6),
        # per-tenant slice of the same audit (empty when untenanted);
        # the noisy-neighbor gate reads victims' p99/lost from here
        "router_by_tenant": by_tenant,
    }

"""SLO burn-rate engine (ISSUE 12): window math, band isolation,
budget exhaustion, and the acceptance — ``serving_slo_burn_rate``
drives a scale-up in a scenario where queue depth alone would not.

Everything runs on a synthetic clock: the engine takes ``now``
everywhere, so window expiry and burn arithmetic are asserted exactly,
not raced.
"""

import numpy as np

from dlrover_tpu.brain.serving import ServingScalePolicy, ServingSignal
from dlrover_tpu.serving.remote.worker import FakeEngine
from dlrover_tpu.serving.router import (
    PRIORITY_BATCH,
    PRIORITY_HIGH,
    PRIORITY_NORMAL,
    ContinuousBatchScheduler,
    RouterMetrics,
    ServingAutoScaler,
    ServingRouter,
    SloEngine,
    SloObjective,
)


def _engine(fast=10.0, slow=40.0, target=0.9):
    return SloEngine(
        objectives=(
            SloObjective(PRIORITY_HIGH, ttft_target_s=0.1,
                         e2e_target_s=1.0, target=target),
            SloObjective(PRIORITY_NORMAL, ttft_target_s=0.2,
                         e2e_target_s=2.0, target=target),
            SloObjective(PRIORITY_BATCH, ttft_target_s=1.0,
                         e2e_target_s=10.0, target=target),
        ),
        fast_window_s=fast, slow_window_s=slow,
    )


# -- window math -------------------------------------------------------------


def test_compliance_and_burn_rate_math():
    slo = _engine(target=0.9)  # error budget = 0.1
    t = 1000.0
    # 8 good + 2 bad NORMAL completions -> 80% compliance
    for i in range(10):
        bad = i < 2
        slo.observe(PRIORITY_NORMAL,
                    ttft_s=(0.5 if bad else 0.01),
                    e2e_s=0.5, now=t + i * 0.1)
    t += 1.0
    assert abs(slo.compliance(PRIORITY_NORMAL, t, "fast") - 0.8) < 1e-9
    # bad fraction 0.2 over budget 0.1 -> burning at 2x
    assert abs(slo.burn_rate(PRIORITY_NORMAL, t, "fast") - 2.0) < 1e-9
    # slow window holds the same events right now
    assert abs(slo.burn_rate(PRIORITY_NORMAL, t, "slow") - 2.0) < 1e-9
    # idle band: perfect compliance, zero burn
    assert slo.compliance(PRIORITY_HIGH, t, "fast") == 1.0
    assert slo.burn_rate(PRIORITY_HIGH, t, "fast") == 0.0


def test_ttft_violation_alone_is_a_violation():
    slo = _engine()
    t = 50.0
    # e2e comfortably inside, TTFT blown: the user WAITED even though
    # the answer eventually streamed fast
    slo.observe(PRIORITY_HIGH, ttft_s=5.0, e2e_s=0.5, now=t)
    assert slo.compliance(PRIORITY_HIGH, t + 0.1, "fast") == 0.0
    # a missing TTFT (legacy non-streaming path) judges on e2e alone
    slo.observe(PRIORITY_HIGH, ttft_s=None, e2e_s=0.5, now=t + 0.2)
    assert abs(slo.compliance(PRIORITY_HIGH, t + 0.3, "fast") - 0.5) \
        < 1e-9


def test_fast_window_forgets_but_slow_window_remembers():
    slo = _engine(fast=10.0, slow=40.0)
    t = 100.0
    for i in range(5):
        slo.observe_violation(PRIORITY_NORMAL, now=t + i * 0.1)
    # inside both windows
    assert slo.burn_rate(PRIORITY_NORMAL, t + 1, "fast") > 0
    assert slo.burn_rate(PRIORITY_NORMAL, t + 1, "slow") > 0
    # 20s later: past the 10s fast window, inside the 40s slow one
    assert slo.burn_rate(PRIORITY_NORMAL, t + 20, "fast") == 0.0
    assert slo.burn_rate(PRIORITY_NORMAL, t + 20, "slow") > 0
    # 60s later: everything aged out; budget replenished
    assert slo.burn_rate(PRIORITY_NORMAL, t + 60, "slow") == 0.0
    assert slo.budget_remaining(PRIORITY_NORMAL, t + 60) == 1.0


def test_band_isolation():
    slo = _engine()
    t = 10.0
    for i in range(20):
        slo.observe_violation(PRIORITY_BATCH, now=t + i * 0.05)
        slo.observe(PRIORITY_HIGH, ttft_s=0.01, e2e_s=0.1,
                    now=t + i * 0.05)
    t += 2.0
    # BATCH is on fire; HIGH and NORMAL are untouched by it
    assert slo.burn_rate(PRIORITY_BATCH, t, "fast") > 1.0
    assert slo.burn_rate(PRIORITY_HIGH, t, "fast") == 0.0
    assert slo.compliance(PRIORITY_HIGH, t, "fast") == 1.0
    assert slo.burn_rate(PRIORITY_NORMAL, t, "fast") == 0.0
    assert slo.budget_remaining(PRIORITY_HIGH, t) == 1.0


def test_budget_exhaustion_clamps_and_pressure_needs_both_windows():
    slo = _engine(fast=10.0, slow=40.0, target=0.9)
    t = 200.0
    # 50% bad >> the 10% budget: remaining pins to 0, never negative
    for i in range(20):
        slo.observe(PRIORITY_NORMAL,
                    ttft_s=(9.9 if i % 2 else 0.01), e2e_s=0.1,
                    now=t + i * 0.1)
    t += 3.0
    assert slo.budget_remaining(PRIORITY_NORMAL, t) == 0.0
    # pressure = min(fast, slow) burn, max over bands
    assert slo.pressure(t) > 1.0
    # 15s later the fast window is clean -> the multi-window rule
    # stands down even though the slow window still remembers
    assert slo.burn_rate(PRIORITY_NORMAL, t + 15, "slow") > 0
    assert slo.pressure(t + 15) == 0.0


def test_summary_and_render_and_otlp_metrics():
    slo = _engine()
    t = 5.0
    slo.observe(PRIORITY_NORMAL, ttft_s=0.01, e2e_s=0.1, now=t)
    slo.observe_violation(PRIORITY_NORMAL, now=t)
    summary = slo.summary(t + 0.5)
    assert summary["NORMAL"]["observed"] == 2
    assert summary["NORMAL"]["violations"] == 1
    assert summary["NORMAL"]["met"] is False
    assert summary["HIGH"]["met"] is True
    text = slo.render()
    assert 'serving_slo_burn_rate{band="NORMAL",window="fast"}' in text
    assert "# HELP serving_slo_compliance" in text
    rows = slo.otlp_metrics(t + 0.5)
    names = {name for name, _, _ in rows}
    assert names == {"serving_slo_compliance", "serving_slo_burn_rate",
                     "serving_slo_budget_remaining",
                     "serving_slo_class_burn_rate"}
    bands = {attrs["band"] for _, attrs, _ in rows if "band" in attrs}
    assert bands == {"HIGH", "NORMAL", "BATCH"}
    classes = {attrs["tenant_class"] for _, attrs, _ in rows
               if "tenant_class" in attrs}
    assert classes == {"premium", "standard", "background"}


# -- the policy signal -------------------------------------------------------


def test_policy_scales_up_on_burn_where_queue_would_not():
    policy = ServingScalePolicy(
        min_replicas=1, max_replicas=8, queue_high=4.0,
        slo_burn_high=2.0)
    # depth 2 over 2 replicas = 1.0 per replica: inside the [queue_low,
    # queue_high) dead band — the queue alone moves nothing
    shallow_queue = [ServingSignal(queue_depth=2.0)] * 3
    assert policy.decide(shallow_queue, 2) == 2
    # same shallow queue, but the SLO budget is burning at 5x
    burning = [ServingSignal(queue_depth=2.0, slo_pressure=5.0)] * 3
    assert policy.decide(burning, 2) == 3
    # burn below the threshold: still no move
    mild = [ServingSignal(queue_depth=2.0, slo_pressure=1.5)] * 3
    assert policy.decide(mild, 2) == 2
    # slo_burn_high=None disables the signal entirely
    off = ServingScalePolicy(queue_high=4.0, slo_burn_high=None)
    assert off.decide(burning, 2) == 2
    # and burn holds off the scale-DOWN an empty queue would take
    assert policy.decide(burning, 3) == 4  # up, not down


def test_signal_dict_roundtrip_keeps_slo_pressure():
    s = ServingSignal(queue_depth=1.0, slo_pressure=3.5)
    assert ServingSignal.from_dict(s.to_dict()).slo_pressure == 3.5
    # a pre-SLO producer's dict (Brain RPC path) defaults to 0.0
    legacy = {"queue_depth": 1.0, "ttft_seconds": 0.1,
              "tokens_per_sec": 5.0}
    assert ServingSignal.from_dict(legacy).slo_pressure == 0.0


# -- the acceptance: burn-driven scale-up end to end -------------------------


class _PlanScaler:
    """Scaler stub recording executed plans."""

    def __init__(self):
        self.plans = []

    def scale(self, plan):
        self.plans.append(plan)


def _router_with_slow_engine(slo, t0):
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4),
        metrics=RouterMetrics(window_seconds=5.0),
        slo=slo,
    )
    # plenty of slots: the queue never builds, but generation takes
    # long enough (driven by the synthetic clock below) to blow TTFT
    router.join_replica("r0", FakeEngine(slots=64, tokens_per_step=1,
                                         blocks=100000), now=t0)
    return router


def _drive_slow_requests(router, auto, t0, rounds=30):
    """Submit one request per round and step with the clock jumping
    past the TTFT target each time: per-replica queue depth stays ~1
    (far below queue_high) while EVERY completion violates."""
    t = t0
    for i in range(rounds):
        router.submit(np.full(8, i % 251, np.int32), 1,
                      priority=PRIORITY_NORMAL, now=t)
        # the engine finishes in one step, but that step lands 0.5s
        # after submission — TTFT 0.5s against a 0.2s target
        t += 0.5
        router.step(now=t)
        t += 0.1
    return t


def test_burn_rate_drives_scale_up_where_queue_depth_would_not():
    slo = _engine(fast=10.0, slow=40.0, target=0.9)
    router = _router_with_slow_engine(slo, t0=1000.0)
    scaler = _PlanScaler()
    auto = ServingAutoScaler(
        router, scaler,
        policy=ServingScalePolicy(
            min_replicas=1, max_replicas=4, queue_high=50.0,
            queue_low=0.0, slo_burn_high=2.0),
        decide_interval=0.5, cooldown=2.0, min_samples=2)
    t = _drive_slow_requests(router, auto, t0=1000.0)

    # the queue never came close to the scale-up bar...
    assert all(s.queue_depth < 5.0 for s in auto._samples or [])
    # ...but the burn did, and a scale-up plan was executed
    assert slo.pressure(t) > 2.0
    up_plans = [p for p in auto.plans if p.node_group_resources]
    assert up_plans, "SLO burn must have driven a scale-up"
    count = sum(g.count for g in
                up_plans[0].node_group_resources.values())
    assert count >= 2
    # the autoscale trace recorded the decision (always-sampled)
    autoscale = router.tracer.traces_named("autoscale")
    assert autoscale, "the burn-driven decision must be traced"

    # CONTROL: identical drive with the SLO signal disabled — queue
    # depth alone never scales (proving the burn was the cause)
    slo2 = _engine(fast=10.0, slow=40.0, target=0.9)
    router2 = _router_with_slow_engine(slo2, t0=1000.0)
    scaler2 = _PlanScaler()
    auto2 = ServingAutoScaler(
        router2, scaler2,
        policy=ServingScalePolicy(
            min_replicas=1, max_replicas=4, queue_high=50.0,
            queue_low=0.0, slo_burn_high=None),
        decide_interval=0.5, cooldown=2.0, min_samples=2)
    _drive_slow_requests(router2, auto2, t0=1000.0)
    assert not [p for p in auto2.plans if p.node_group_resources], \
        "without the SLO signal the shallow queue must not scale"


def test_router_feeds_poisoning_as_violation():
    """A poisoned request (burned every failover replay) never
    answered its caller — the SLO engine must see it, or a
    crash-looping replica reads as perfect compliance."""
    from dlrover_tpu.serving.router import RequestGateway

    slo = _engine(fast=10.0, slow=40.0)
    router = ServingRouter(
        gateway=RequestGateway(max_requeues=0),
        scheduler=ContinuousBatchScheduler(block_size=4),
        metrics=RouterMetrics(window_seconds=5.0),
        slo=slo,
    )
    t = 700.0
    router.join_replica("r0", FakeEngine(slots=4, tokens_per_step=1,
                                         blocks=100000), now=t)
    req = router.submit(np.full(8, 1, np.int32), 8,
                        priority=PRIORITY_NORMAL, now=t)
    router.step(now=t)           # placed on r0
    router.fail_replica("r0")
    router.step(now=t + 0.1)     # reap -> requeue cap 0 -> poisoned
    assert router.metrics.metrics()[
        "serving_requests_poisoned_total"] == 1.0
    assert req.state == "Poisoned"
    assert slo.burn_rate(PRIORITY_NORMAL, t + 0.2, "fast") > 0


def test_router_feeds_expiry_as_violation():
    slo = _engine(fast=10.0, slow=40.0)
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4),
        metrics=RouterMetrics(window_seconds=5.0),
        slo=slo,
    )
    t = 500.0
    # no replicas: the request can only age out — an SLO violation
    router.submit(np.full(8, 1, np.int32), 4, timeout=0.5, now=t)
    router.manager.replicas.clear()
    router.step(now=t + 1.0)
    assert slo.burn_rate(PRIORITY_NORMAL, t + 1.1, "fast") > 0
    m = router.metrics.metrics()
    assert m["serving_requests_timed_out_total"] == 1.0

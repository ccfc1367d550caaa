"""Per-tenant QoS (serving/tenancy/): identity, WFQ admission,
token-bucket quotas, quota-aware shedding, and the noisy-neighbor gate.

The acceptance bar (ISSUE 16): one tenant flooding at 10x its quota
must not break its neighbors — victims lose ZERO requests and their
p99 stays within 2x the solo baseline; WFQ splits steady two-tenant
load by weight within 20%; metric output stays DL010-bounded (only
``tenant_class`` labels, never raw tenant ids); and every refusal is
counted exactly once whatever combination of brown-out, quota and
depth pressure produced it.
"""

import textwrap
import threading
from types import SimpleNamespace

import numpy as np
import pytest

from dlrover_tpu.common.constants import ServingRequestState
from dlrover_tpu.serving.remote.worker import FakeEngine
from dlrover_tpu.serving.router import (
    PRIORITY_BATCH,
    PRIORITY_HIGH,
    PRIORITY_NORMAL,
    BrownoutShedError,
    ContinuousBatchScheduler,
    RequestGateway,
    RouterMetrics,
    ServingRouter,
    TenantQuotaError,
)
from dlrover_tpu.serving.router.brownout import (
    STAGE_SHED_BATCH,
    BrownoutPolicy,
)
from dlrover_tpu.serving.router.gateway import AdmissionError
from dlrover_tpu.serving.router.loadgen import (
    LoadgenConfig,
    OpenLoopGenerator,
    run_router_rig,
)
from dlrover_tpu.serving.router.slo import SloEngine
from dlrover_tpu.serving.tenancy import (
    SHED_CLASSES,
    TENANT_CLASSES,
    TenantRegistry,
    TenantSpec,
    WfqBandQueue,
    plan_shed,
)
from dlrover_tpu.utils.metric_registry import METRIC_LABELS


def _prompt(i, n=8):
    return np.full(n, i % 251, np.int32)


def _req(tenant):
    return SimpleNamespace(tenant=tenant)


# ----------------------------------------------------------- specs


def test_tenant_spec_validation():
    with pytest.raises(ValueError):
        TenantSpec("z", weight=0.0)
    with pytest.raises(ValueError):
        TenantSpec("z", weight=-1.0)
    with pytest.raises(ValueError):
        TenantSpec("z", tenant_class="platinum")
    with pytest.raises(ValueError):
        TenantSpec("z", shed_class="never")
    with pytest.raises(ValueError):
        TenantSpec("z", quota_qps=0.0)
    spec = TenantSpec("ok", quota_qps=5.0, burst=7.0)
    assert spec.bucket_capacity == 7.0
    assert spec.tenant_class in TENANT_CLASSES
    assert spec.shed_class in SHED_CLASSES


def test_registry_resolves_unknown_to_default():
    reg = TenantRegistry([TenantSpec("a", weight=2.0)])
    assert reg.resolve("a").weight == 2.0
    assert reg.resolve("nobody-registered").name == "default"
    assert reg.resolve(None).name == "default"
    assert not reg.trivial
    assert TenantRegistry().trivial


# ------------------------------------------------------------- WFQ


def test_wfq_single_tenant_is_exact_fifo():
    q = WfqBandQueue(lambda t: 1.0)
    reqs = [_req("solo") for _ in range(32)]
    for r in reqs:
        q.append(r)
    assert q.scan(64) == reqs
    assert list(q) == reqs


def test_wfq_vclock_monotone_under_interleaved_service():
    q = WfqBandQueue(lambda t: 2.0 if t == "a" else 1.0)
    for i in range(60):
        q.append(_req("a" if i % 2 else "b"))
    last = q.vclock
    while q:
        head = q.scan(1)[0]
        q.remove(head)
        assert q.vclock >= last
        last = q.vclock


def test_wfq_weight_ratio_within_20pct():
    # both tenants permanently backlogged; service share over any
    # prefix must track the 2:1 weight ratio
    q = WfqBandQueue(lambda t: 2.0 if t == "heavy" else 1.0)
    for _ in range(300):
        q.append(_req("heavy"))
    for _ in range(300):
        q.append(_req("light"))
    served = {"heavy": 0, "light": 0}
    for _ in range(150):
        head = q.scan(1)[0]
        q.remove(head)
        served[head.tenant] += 1
    ratio = served["heavy"] / max(1, served["light"])
    assert abs(ratio - 2.0) / 2.0 <= 0.20, served


def test_wfq_flood_cannot_starve_light_tenant():
    q = WfqBandQueue(lambda t: 1.0)
    for _ in range(500):
        q.append(_req("flood"))
    light = _req("light")
    q.append(light)
    # equal weights: the newcomer's vstart snaps to the band's virtual
    # clock, so it is served within one "round", not after the backlog
    order = q.scan(10)
    assert light in order


def test_wfq_front_requeue_served_first():
    q = WfqBandQueue(lambda t: 1.0)
    a, b, failback = _req("a"), _req("b"), _req("a")
    q.append(a)
    q.append(b)
    q.appendleft(failback)
    assert q.scan(3) == [failback, a, b]
    q.remove(failback)
    assert q.scan(3) == [a, b]


def test_wfq_counts_and_discard():
    shared = {}
    q = WfqBandQueue(lambda t: 1.0, shared_counts=shared)
    reqs = [_req("a"), _req("a"), _req("b")]
    for r in reqs:
        q.append(r)
    assert q.counts_by_tenant() == {"a": 2, "b": 1}
    assert shared == {"a": 2, "b": 1}
    q.discard_ids({id(reqs[0])})
    assert shared == {"a": 1, "b": 1}
    taken = q.clear_all()
    assert set(map(id, taken)) == {id(reqs[1]), id(reqs[2])}
    assert shared == {} and len(q) == 0


# ----------------------------------------------------- quota buckets


def test_quota_bucket_rejects_with_retry_after():
    reg = TenantRegistry([TenantSpec("t", quota_qps=5.0, burst=1.0)])
    gw = RequestGateway(tenants=reg)
    gw.submit(_prompt(0), 4, tenant="t", now=100.0)
    with pytest.raises(TenantQuotaError) as err:
        gw.submit(_prompt(1), 4, tenant="t", now=100.0)
    assert err.value.retry_after_s is not None
    assert 0.0 < err.value.retry_after_s <= 1.0 / 5.0 + 1e-6
    assert err.value.tenant == "t"
    # the bucket refills at quota_qps: one second later one token back
    gw.submit(_prompt(2), 4, tenant="t", now=100.25)
    assert gw.rejected == 1
    assert reg.quota_rejected.get("t") == 1
    assert reg.admitted.get("t") == 2


def test_quota_exempts_high_priority():
    reg = TenantRegistry([TenantSpec("t", quota_qps=1.0, burst=1.0)])
    gw = RequestGateway(tenants=reg)
    # drain the bucket with metered NORMAL traffic...
    gw.submit(_prompt(0), 4, priority=PRIORITY_NORMAL,
              tenant="t", now=50.0)
    with pytest.raises(TenantQuotaError):
        gw.submit(_prompt(1), 4, priority=PRIORITY_NORMAL,
                  tenant="t", now=50.0)
    # ...HIGH is never quota-refused (and never burns a token): the
    # bucket stays dry for NORMAL while every HIGH offer lands
    for i in range(8):
        gw.submit(_prompt(2 + i), 4, priority=PRIORITY_HIGH,
                  tenant="t", now=50.0)
    with pytest.raises(TenantQuotaError):
        gw.submit(_prompt(11), 4, priority=PRIORITY_NORMAL,
                  tenant="t", now=50.0)


def test_max_queued_refused_before_bucket_burns():
    reg = TenantRegistry(
        [TenantSpec("t", quota_qps=100.0, burst=2.0, max_queued=1)])
    gw = RequestGateway(tenants=reg)
    first = gw.submit(_prompt(0), 4, tenant="t", now=10.0)
    with pytest.raises(TenantQuotaError):
        gw.submit(_prompt(1), 4, tenant="t", now=10.0)
    # the refusal must NOT have consumed a token: after the queued
    # request leaves, a submit at the SAME instant still has budget
    gw.remove(first)
    first.abort(ServingRequestState.CANCELLED)
    gw.submit(_prompt(2), 4, tenant="t", now=10.0)
    assert gw.rejected == 1


def test_unknown_tenant_never_crashes_submit():
    gw = RequestGateway()
    req = gw.submit(_prompt(0), 4, tenant="who-is-this")
    assert req.tenant == "default"
    req2 = gw.submit(_prompt(1), 4)
    assert req2.tenant == "default"


# ------------------------------------------- exactly-once reject books


def test_reject_books_exactly_once_under_combined_pressure():
    """Satellite: brown-out shed, quota refusal and depth refusal all
    hit the same gateway; every refusal increments ``rejected``
    exactly once and the admission identity balances."""
    reg = TenantRegistry([
        TenantSpec("quota", quota_qps=1.0, burst=1.0),
        TenantSpec("free"),
    ])
    gw = RequestGateway(max_pending=3, tenants=reg)
    policy = BrownoutPolicy()
    policy.stage = STAGE_SHED_BATCH
    gw.brownout = policy

    offered = 0
    raised = 0
    # brown-out refuses BATCH at the door
    for i in range(3):
        offered += 1
        with pytest.raises(BrownoutShedError):
            gw.submit(_prompt(i), 4, priority=PRIORITY_BATCH,
                      tenant="free", now=5.0)
        raised += 1
    # quota refuses the over-budget tenant (1 token, 3 offers)
    for i in range(3):
        offered += 1
        try:
            gw.submit(_prompt(i), 4, tenant="quota", now=5.0)
        except TenantQuotaError:
            raised += 1
    # depth refuses once the global bound fills
    for i in range(4):
        offered += 1
        try:
            gw.submit(_prompt(i), 4, tenant="free", now=5.0)
        except AdmissionError:
            raised += 1
    assert offered == gw.submitted + gw.rejected
    assert gw.rejected == raised
    assert reg.shed.get("free") == 3
    assert reg.quota_rejected.get("quota") == 2
    by_class = reg.by_class(reg.quota_rejected)
    assert set(by_class) == set(TENANT_CLASSES)
    assert sum(by_class.values()) == 2.0


def test_shared_retry_after_contract():
    assert issubclass(TenantQuotaError, AdmissionError)
    assert issubclass(BrownoutShedError, AdmissionError)
    quota = TenantQuotaError("q", tenant="t", retry_after_s=0.5)
    shed = BrownoutShedError("b", stage=1, stage_name="shed_batch",
                             retry_after_s=2.0)
    for err in (quota, shed):
        assert isinstance(err, AdmissionError)
        assert err.retry_after_s is not None and err.retry_after_s > 0


# --------------------------------------------------- max_inflight gate


def test_max_inflight_caps_placement_not_progress():
    reg = TenantRegistry([TenantSpec("capped", max_inflight=1)])
    gw = RequestGateway(tenants=reg)
    router = ServingRouter(
        gateway=gw, scheduler=ContinuousBatchScheduler(block_size=4))
    eng = FakeEngine(slots=4, tokens_per_step=64, step_delay=0.0)
    router.join_replica("r0", eng)
    reqs = [router.submit(_prompt(i), 4, tenant="capped")
            for i in range(4)]
    router.step()
    assert gw.tenant_inflight("capped") <= 1
    for _ in range(200):
        if all(r.state == ServingRequestState.DONE for r in reqs):
            break
        router.step()
    assert [r.state for r in reqs] == [ServingRequestState.DONE] * 4


# ------------------------------------------------- proportional shed


def test_plan_shed_orders_by_shed_class_then_overage():
    reg = TenantRegistry([
        TenantSpec("a", shed_class="first"),
        TenantSpec("b", shed_class="last"),
    ])
    # 20 queued, keep 10: "first" (allowance x0) pays before "last"
    plan = dict(plan_shed({"a": 10, "b": 10}, reg, keep_total=10))
    assert plan.get("a", 0) == 10
    assert plan.get("b", 0) == 0
    # keep nothing: everyone sheds everything
    plan = dict(plan_shed({"a": 2, "b": 3}, reg, keep_total=0))
    assert plan == {"a": 2, "b": 3}
    # keep everything: nobody sheds
    assert plan_shed({"a": 2, "b": 3}, reg, keep_total=5) == []


def test_shed_queued_proportional_keeps_in_quota_tenants():
    reg = TenantRegistry([
        TenantSpec("hog", shed_class="first"),
        TenantSpec("good", shed_class="last"),
    ])
    gw = RequestGateway(tenants=reg)
    for i in range(8):
        gw.submit(_prompt(i), 4, priority=PRIORITY_BATCH, tenant="hog")
    for i in range(4):
        gw.submit(_prompt(i), 4, priority=PRIORITY_BATCH, tenant="good")
    taken = gw.shed_queued(PRIORITY_BATCH, dump=False, keep_total=4)
    assert len(taken) == 8
    assert {r.tenant for r in taken} == {"hog"}
    depths = gw.tenant_queue_depths()
    # the flood pays for the brown-out; the in-quota tenant keeps its
    # whole queue
    assert depths.get("good") == 4
    assert depths.get("hog", 0) == 0
    assert reg.shed.get("hog") == 8
    assert gw.cancelled == 8


# ------------------------------------------------ metric cardinality


def _labeled_families(text):
    """Parse ``name{k="v",...} value`` lines -> {name: set(label_key)}
    plus every label value seen, for the DL010-style bound check."""
    import re

    fams, values = {}, set()
    for line in text.splitlines():
        m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)\{([^}]*)\}", line)
        if not m:
            continue
        name, body = m.group(1), m.group(2)
        keys = fams.setdefault(name, set())
        for pair in re.findall(r'(\w+)="([^"]*)"', body):
            keys.add(pair[0])
            values.add(pair[1])
    return fams, values


def test_metric_cardinality_bounded_under_50_tenant_ids():
    """50 distinct raw tenant ids in, only the bounded tenant_class
    vocabulary out — on the router metrics AND the SLO surface."""
    reg = TenantRegistry([
        TenantSpec("prem-0", tenant_class="premium"),
        TenantSpec("bg-0", tenant_class="background"),
    ])
    gw = RequestGateway(tenants=reg)
    metrics = RouterMetrics(window_seconds=1.0)
    slo = SloEngine()
    router = ServingRouter(
        gateway=gw, scheduler=ContinuousBatchScheduler(block_size=4),
        metrics=metrics, slo=slo)
    router.join_replica("r0", FakeEngine(slots=8))
    for i in range(50):
        router.submit(_prompt(i), 2, tenant=f"tenant-{i:02d}")
    router.submit(_prompt(99), 2, tenant="prem-0")
    router.submit(_prompt(98), 2, tenant="bg-0")
    for _ in range(100):
        if not router.has_work:
            break
        router.step()

    import time as _time

    rendered = metrics.render_labeled() + "\n".join(
        str(row) for row in slo.otlp_metrics(_time.monotonic()))
    assert "tenant-0" not in rendered and "tenant-4" not in rendered
    assert "prem-0" not in rendered and "bg-0" not in rendered
    fams, values = _labeled_families(metrics.render_labeled())
    for name, keys in fams.items():
        # in-test DL010: every label key must be declared for its
        # family in the central registry
        assert name in METRIC_LABELS, name
        assert keys <= set(METRIC_LABELS[name]), (name, keys)
    tenant_vals = {
        v for v in values if v in TENANT_CLASSES or "tenant" in v}
    assert tenant_vals <= set(TENANT_CLASSES)
    for fam in ("serving_tenant_queue_depth",
                "serving_tenant_shed_total",
                "serving_tenant_quota_rejected_total"):
        assert fam in fams, fam
        assert fams[fam] == {"tenant_class"}


_TENANT_LABEL_REGISTRY = """
    METRIC_HELP = {
        "serving_tenant_queue_depth": "queued per tenant class",
    }
    NON_METRIC_SERVING_NAMES = frozenset()
    METRIC_LABELS = {
        "serving_tenant_queue_depth": ("tenant_class",),
    }
"""


def _write_tree(root, files):
    for rel, src in files.items():
        path = root / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(src))


def test_dlint_dl010_guards_tenant_labels(tmp_path):
    """The DL010 checker itself refuses a raw-tenant-id label on the
    tenancy families and accepts the bounded tenant_class idiom
    (satellite regression: the metric-cardinality bound is enforced
    by lint, not just by this test file)."""
    from tools.dlint import DlintConfig, run_dlint

    config = DlintConfig(metric_registry_module="registry.py")
    bad = tmp_path / "bad"
    _write_tree(bad, {
        "registry.py": _TENANT_LABEL_REGISTRY,
        "mod.py": '''
            def render(req, depth):
                return (
                    f'serving_tenant_queue_depth{{tenant="{req.tenant}"'
                    f'}} {depth}')
        ''',
    })
    result = run_dlint([str(bad)], config=config)
    assert [v.code for v in result.new] == ["DL010"]

    good = tmp_path / "good"
    _write_tree(good, {
        "registry.py": _TENANT_LABEL_REGISTRY,
        "mod.py": '''
            TENANT_CLASSES = ("premium", "standard", "background")

            def render(book):
                lines = []
                for cls in TENANT_CLASSES:
                    lines.append(
                        "serving_tenant_queue_depth{"
                        f'tenant_class="{cls}"'
                        "} " + str(book.get(cls, 0.0)))
                return lines
        ''',
    })
    result = run_dlint([str(good)], config=config)
    assert not [v for v in result.new if v.code == "DL010"]


# ----------------------------------------------- SLO class objectives


def test_slo_class_burn_tracks_premium_separately():
    slo = SloEngine()
    now = 1000.0
    # meets every band target but blows the premium TTFT target
    for i in range(50):
        slo.observe(PRIORITY_NORMAL, ttft_s=0.8, e2e_s=2.0,
                    now=now + i * 0.01, tenant_class="premium")
    assert slo.class_burn_rate("premium", now + 1.0, "fast") > 1.0
    assert slo.class_burn_rate("background", now + 1.0, "fast") == 0.0
    assert slo.pressure(now + 1.0) > 0.0
    summary = slo.summary(now + 1.0)
    assert "class:premium" in summary


# ------------------------------------- one registry, several gateways


def test_two_gateways_on_two_threads_consume_one_quota():
    """ONE registry behind two plain gateways, each called from its own
    thread: the tenant's bucket is consumed once, not once a gateway
    (why the registry takes its own lock: neither gateway's admission
    lock covers the other's call)."""
    burst = 50
    reg = TenantRegistry([TenantSpec("t", quota_qps=1.0, burst=burst)])
    gateways = [RequestGateway(max_pending=4096, tenants=reg)
                for _ in range(2)]
    admitted, refused = [0, 0], [0, 0]
    barrier = threading.Barrier(2)

    def flood(i):
        barrier.wait()
        for n in range(200):
            try:
                gateways[i].submit(_prompt(n), 2, tenant="t", now=77.0)
                admitted[i] += 1
            except TenantQuotaError:
                refused[i] += 1

    threads = [threading.Thread(target=flood, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30.0)
    assert not any(t.is_alive() for t in threads)
    # the clock stands still, so the bucket holds its burst and no more
    assert sum(admitted) == burst and sum(refused) == 400 - burst
    assert reg.admitted["t"] == burst
    assert reg.quota_rejected["t"] == 400 - burst
    assert [gw.depth() for gw in gateways] == admitted


# ------------------------------------------------- noisy neighbor gate


def _rig_router(reg=None, slots=8):
    gw = RequestGateway(max_pending=4096, default_timeout=30.0,
                        tenants=reg)
    router = ServingRouter(
        gateway=gw, scheduler=ContinuousBatchScheduler(block_size=4),
        metrics=RouterMetrics(window_seconds=1.0))
    for i in range(2):
        router.join_replica(
            f"nn-{i}", FakeEngine(
                slots=slots, tokens_per_step=16, step_delay=0.0))
    return router


def _nn_config(tenant_mix, rate_qps, duration_s=1.0, seed=16):
    return LoadgenConfig(
        seed=seed, rate_qps=rate_qps, duration_s=duration_s,
        arrival="poisson", prompt_mix="fixed", prompt_min=8,
        max_new_tokens=8,
        priority_mix=((PRIORITY_NORMAL, 0.7), (PRIORITY_BATCH, 0.3)),
        tenant_mix=tenant_mix)


def _nn_registry():
    return TenantRegistry([
        TenantSpec("victim", weight=1.0, tenant_class="premium"),
        TenantSpec("bystander", weight=1.0),
        TenantSpec("flood", quota_qps=30.0, burst=8.0, weight=1.0,
                   tenant_class="background", shed_class="first"),
    ])


def test_noisy_neighbor_flood_cannot_hurt_victims():
    """THE gate: one tenant floods at ~10x its quota; the victims lose
    nothing and their p99 stays within 2x the solo baseline."""
    solo = run_router_rig(
        _rig_router(_nn_registry()),
        _nn_config((("victim", 0.5), ("bystander", 0.5)), 120.0),
        step_every=16)
    assert solo["router_books_ok"], solo
    solo_p99 = max(
        solo["router_by_tenant"]["victim"]["e2e_p99_s"],
        solo["router_by_tenant"]["bystander"]["e2e_p99_s"])

    # same victim offered load + the flood at ~10x its 30qps quota
    flood = run_router_rig(
        _rig_router(_nn_registry()),
        _nn_config((("victim", 0.15), ("bystander", 0.15),
                    ("flood", 0.7)), 400.0),
        step_every=16)
    by = flood["router_by_tenant"]
    assert flood["router_books_ok"], flood
    # quota actually bit: the flood got refused, the victims did not
    assert by["flood"]["rejected"] > 0
    assert by["victim"]["rejected"] == 0
    assert by["bystander"]["rejected"] == 0
    # zero victim requests lost
    assert by["victim"]["lost"] == 0
    assert by["bystander"]["lost"] == 0
    # isolation: victims' p99 within 2x solo (floored against timer
    # jitter on sub-10ms baselines)
    bound = max(2.0 * solo_p99, 0.10)
    assert by["victim"]["e2e_p99_s"] <= bound, (solo_p99, by)
    assert by["bystander"]["e2e_p99_s"] <= bound, (solo_p99, by)
    # per-tenant books balance: admitted splits into done + terminal
    for name, book in by.items():
        assert book["done"] <= book["admitted"], (name, book)
        assert book["lost"] == 0, (name, book)


@pytest.mark.slow
def test_tenancy_soak_60s_flood_plus_cancels():
    """Nightly: a minute of flood + mid-flight cancels; zero lost and
    the per-tenant books balance the whole way."""
    result = run_router_rig(
        _rig_router(_nn_registry()),
        _nn_config((("victim", 0.2), ("bystander", 0.1),
                    ("flood", 0.7)), 300.0, duration_s=60.0,
                   seed=61),
        step_every=16, cancel_every=97)
    assert result["router_books_ok"], result
    assert result["router_lost"] == 0
    by = result["router_by_tenant"]
    assert by["flood"]["rejected"] > 0
    for name, book in by.items():
        assert book["lost"] == 0, (name, book)
    assert result["router_cancel_attempts"] > 0

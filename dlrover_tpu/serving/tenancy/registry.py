"""Tenant identity, quotas and shed policy.

A tenant is the unit of isolation the fleet promises: each one gets a
rate quota (token bucket), buffer bounds (max queued / max in-flight),
a weighted-fair-queueing weight, an SLO class and a shed class.  The
registry is deliberately small-N: tenants are REGISTERED (a config
surface, not a per-request discovery), unknown tenant ids resolve to
one configurable default tenant — so an adversarial id stream can
neither crash admission nor grow per-tenant state without bound.

Metric cardinality is the trap DL010 exists for: per-tenant label
VALUES on a Prometheus family would explode with the tenant
population.  Every exported family therefore labels by
``tenant_class`` from the bounded :data:`TENANT_CLASSES` vocabulary;
raw tenant ids stay in logs, traces and JSON summaries only.
"""

from __future__ import annotations

import dataclasses
import json
import threading
from typing import Dict, Iterable, List, Optional, Tuple

#: The bounded metric-label vocabulary (``tenant_class``).  Closed by
#: design: adding a class means adding it HERE, where the registry
#: validates against it and the renderers enumerate it — never from a
#: request field.
TENANT_CLASSES = ("premium", "standard", "background")

#: Brown-out shed ordering: ``first`` sheds before ``fair`` sheds
#: before ``last`` (multipliers on the fair-share allowance below).
SHED_CLASSES = ("first", "fair", "last")

_SHED_RANK = {name: i for i, name in enumerate(SHED_CLASSES)}
_SHED_ALLOWANCE_MULT = {"first": 0.0, "fair": 1.0, "last": 2.0}


@dataclasses.dataclass(frozen=True)
class TenantSpec:
    """One tenant's QoS contract.

    ``quota_qps=None`` means unmetered (the default tenant ships that
    way — quotas are an opt-in per registered tenant); ``burst`` is
    the token-bucket capacity (defaults to one second of quota).
    ``weight`` is the WFQ share within a priority band; zero or
    negative weight is a CONFIG ERROR (it would starve the tenant
    structurally, which no operator means) and raises here rather
    than at the first starved request."""

    name: str
    quota_qps: Optional[float] = None
    burst: Optional[float] = None
    max_queued: Optional[int] = None
    max_inflight: Optional[int] = None
    weight: float = 1.0
    tenant_class: str = "standard"
    shed_class: str = "fair"

    def __post_init__(self):
        if self.weight <= 0.0:
            raise ValueError(
                f"tenant {self.name!r}: weight must be > 0 "
                f"(got {self.weight}) — a zero-weight tenant would "
                "never be served; delete it instead")
        if self.tenant_class not in TENANT_CLASSES:
            raise ValueError(
                f"tenant {self.name!r}: tenant_class "
                f"{self.tenant_class!r} not in the bounded vocabulary "
                f"{TENANT_CLASSES} (DL010: label values must be "
                "closed)")
        if self.shed_class not in SHED_CLASSES:
            raise ValueError(
                f"tenant {self.name!r}: shed_class "
                f"{self.shed_class!r} not in {SHED_CLASSES}")
        if self.quota_qps is not None and self.quota_qps <= 0:
            raise ValueError(
                f"tenant {self.name!r}: quota_qps must be > 0 or "
                f"None (got {self.quota_qps})")

    @property
    def bucket_capacity(self) -> float:
        if self.burst is not None:
            return max(1.0, float(self.burst))
        if self.quota_qps is not None:
            return max(1.0, float(self.quota_qps))
        return 1.0

    @property
    def shed_rank(self) -> int:
        return _SHED_RANK[self.shed_class]

    @property
    def shed_allowance_mult(self) -> float:
        return _SHED_ALLOWANCE_MULT[self.shed_class]


class _TokenBucket:
    """Classic token bucket: ``rate`` tokens/s up to ``capacity``.
    ``retry_after_s`` after a refusal is the time to the NEXT whole
    token — the honest Retry-After hint (coming back sooner cannot
    succeed; later wastes admitted capacity)."""

    __slots__ = ("rate", "capacity", "tokens", "stamp")

    def __init__(self, rate: float, capacity: float, now: float):
        self.rate = float(rate)
        self.capacity = float(capacity)
        self.tokens = float(capacity)
        self.stamp = float(now)

    def _refill(self, now: float) -> None:
        if now > self.stamp:
            self.tokens = min(
                self.capacity,
                self.tokens + (now - self.stamp) * self.rate)
        self.stamp = max(self.stamp, now)

    def consume(self, now: float) -> bool:
        self._refill(now)
        if self.tokens >= 1.0:
            self.tokens -= 1.0
            return True
        return False

    def retry_after_s(self, now: float) -> float:
        self._refill(now)
        if self.tokens >= 1.0:
            return 0.0
        return (1.0 - self.tokens) / max(1e-9, self.rate)


class TenantRegistry:
    """Registered tenants + resolution + quota state + accounting.

    Thread-safe where it must be: a gateway consults it under its own
    admission lock, but ONE registry may sit behind several gateways
    that are called from several threads (a registry a gateway would
    multiply every quota by their number), so bucket consumption
    takes the registry's own lock."""

    def __init__(self, specs: Iterable[TenantSpec] = (),
                 default_tenant: str = "default"):
        self.default_tenant = str(default_tenant)
        self._specs: Dict[str, TenantSpec] = {}
        self._buckets: Dict[str, _TokenBucket] = {}
        self._lock = threading.Lock()
        # per-tenant lifecycle accounting (names are bounded by the
        # registry: unknown ids resolve to the default tenant first)
        self.admitted: Dict[str, int] = {}
        self.quota_rejected: Dict[str, int] = {}
        self.shed: Dict[str, int] = {}
        self.tokens: Dict[str, int] = {}
        # SLO-burn WFQ boost: tenant_class -> temporary weight
        # multiplier (>= 1.0).  Fed by the router's observe phase from
        # the SLO engine's per-class burn rates; a class burning its
        # error budget gets a BOUNDED multiplier on every member
        # tenant's WFQ weight until the burn recovers, then the boost
        # decays geometrically back to 1.0.  Keyed on the closed
        # TENANT_CLASSES vocabulary, never raw ids.
        self._class_boost: Dict[str, float] = {}
        for spec in specs:
            self.register(spec)
        if self.default_tenant not in self._specs:
            self.register(TenantSpec(name=self.default_tenant))

    # ------------------------------------------------------ membership
    def register(self, spec: TenantSpec) -> TenantSpec:
        self._specs[spec.name] = spec
        self._buckets.pop(spec.name, None)  # re-arm on re-register
        self.admitted.setdefault(spec.name, 0)
        self.quota_rejected.setdefault(spec.name, 0)
        self.shed.setdefault(spec.name, 0)
        self.tokens.setdefault(spec.name, 0)
        return spec

    def names(self) -> List[str]:
        return list(self._specs)

    def get(self, name: str) -> Optional[TenantSpec]:
        return self._specs.get(name)

    def resolve(self, name: Optional[str]) -> TenantSpec:
        """Unknown (or absent) tenant ids land on the default tenant —
        admission NEVER crashes on identity, and per-tenant state stays
        bounded by the registered set."""
        if name is not None:
            spec = self._specs.get(name)
            if spec is not None:
                return spec
        return self._specs[self.default_tenant]

    @property
    def trivial(self) -> bool:
        """Only the default tenant is registered — the single-tenant
        fleet; callers keep the exact legacy (pre-tenancy) behavior."""
        return len(self._specs) == 1

    # ----------------------------------------------------------- quota
    def try_admit(self, spec: TenantSpec,
                  now: float) -> Tuple[bool, float]:
        """Consume one quota token; ``(admitted, retry_after_s)``.
        Unmetered tenants always admit."""
        if spec.quota_qps is None:
            return True, 0.0
        with self._lock:
            bucket = self._buckets.get(spec.name)
            if bucket is None or bucket.rate != spec.quota_qps:
                bucket = _TokenBucket(
                    spec.quota_qps, spec.bucket_capacity, now)
                self._buckets[spec.name] = bucket
            if bucket.consume(now):
                return True, 0.0
            return False, bucket.retry_after_s(now)

    # ------------------------------------------------------ accounting
    def count_admitted(self, name: str) -> None:
        self.admitted[name] = self.admitted.get(name, 0) + 1

    def count_quota_rejected(self, name: str) -> None:
        self.quota_rejected[name] = self.quota_rejected.get(name, 0) + 1

    def count_shed(self, name: str) -> None:
        self.shed[name] = self.shed.get(name, 0) + 1

    def by_class(self, counts: Dict[str, int]) -> Dict[str, float]:
        """Aggregate a per-tenant counter dict onto the bounded
        ``tenant_class`` vocabulary — the only shape metrics export."""
        out = {cls: 0.0 for cls in TENANT_CLASSES}
        for name, n in counts.items():
            out[self.resolve(name).tenant_class] += float(n)
        return out

    def note_tokens(self, tenant: Optional[str], n: int) -> None:
        """Book ``n`` generated tokens against the tenant (unknown ids
        land on the default tenant, so the book stays bounded by the
        registered set — same resolution rule as admission)."""
        if n <= 0:
            return
        name = self.resolve(tenant).name
        self.tokens[name] = self.tokens.get(name, 0) + int(n)

    def usage_snapshot(self) -> Dict[str, Dict[str, object]]:
        """Per-tenant usage books keyed by RAW tenant id — the JSON
        shape the ``/tenants/usage`` endpoint serves.  Raw ids are fine
        HERE (an on-demand JSON document, bounded by the registered
        set); they must never become Prometheus label values (DL010)."""
        out: Dict[str, Dict[str, object]] = {}
        for name in sorted(self._specs):
            spec = self._specs[name]
            out[name] = {
                "tenant_class": spec.tenant_class,
                "weight": spec.weight,
                "boosted_weight": self.boosted_weight(spec),
                "admitted": int(self.admitted.get(name, 0)),
                "quota_rejected": int(self.quota_rejected.get(name, 0)),
                "shed": int(self.shed.get(name, 0)),
                "tokens": int(self.tokens.get(name, 0)),
            }
        return out

    # ------------------------------------------- SLO-burn weight boost
    def update_slo_boosts(self, burns: Dict[str, float],
                          max_boost: float = 4.0,
                          decay: float = 0.5) -> None:
        """Drive the per-class WFQ boost from SLO burn rates.

        A class burning error budget (burn > 1.0) gets its boost raised
        to the burn rate, clamped to ``max_boost`` and never lowered by
        a same-round smaller burn; once the burn recovers (<= 1.0) the
        boost decays geometrically toward 1.0 and snaps there — the
        boost is TEMPORARY by construction, so a past incident cannot
        permanently skew the fair queue."""
        for cls, burn in burns.items():
            if cls not in TENANT_CLASSES:
                continue
            cur = self._class_boost.get(cls, 1.0)
            if burn > 1.0:
                new = min(float(max_boost), max(cur, float(burn)))
            else:
                new = 1.0 + (cur - 1.0) * float(decay)
                if new < 1.001:
                    new = 1.0
            if new <= 1.0:
                self._class_boost.pop(cls, None)
            else:
                self._class_boost[cls] = new

    def boost_of(self, tenant_class: str) -> float:
        return self._class_boost.get(tenant_class, 1.0)

    def boosted_weight(self, spec: TenantSpec) -> float:
        """The WFQ weight admission should use: the spec's configured
        weight times its class's current (bounded, decaying) boost."""
        return spec.weight * self.boost_of(spec.tenant_class)

    # ----------------------------------------------------- persistence
    _SPEC_FIELDS = ("quota_qps", "burst", "max_queued", "max_inflight",
                    "weight", "tenant_class", "shed_class")

    def to_file(self, path: str) -> None:
        """Persist the registered specs as JSON (atomic enough for a
        config file: whole-document write).  Only the QoS contracts are
        saved — usage books and quota bucket state are runtime, not
        config."""
        doc = {
            "default_tenant": self.default_tenant,
            "tenants": [
                dict(name=s.name,
                     **{f: getattr(s, f) for f in self._SPEC_FIELDS})
                for s in self._specs.values()
            ],
        }
        with open(path, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
            f.write("\n")

    @staticmethod
    def _specs_from_doc(doc: dict) -> Tuple[str, List[TenantSpec]]:
        default = str(doc.get("default_tenant", "default"))
        specs = []
        for entry in doc.get("tenants", []):
            kwargs = {k: entry[k] for k in TenantRegistry._SPEC_FIELDS
                      if k in entry}
            specs.append(TenantSpec(name=str(entry["name"]), **kwargs))
        return default, specs

    @classmethod
    def from_file(cls, path: str) -> "TenantRegistry":
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
        default, specs = cls._specs_from_doc(doc)
        return cls(specs, default_tenant=default)

    def reload_file(self, path: str) -> Tuple[int, int]:
        """Live reload IN PLACE (SIGHUP / admin endpoint): specs in the
        file are (re-)registered, registered tenants absent from it are
        dropped — except the default tenant, which always exists.
        Usage books for surviving tenants are kept (a config reload
        must not zero the accounting); a re-registered spec re-arms its
        quota bucket exactly like :meth:`register`.  The file is parsed
        and VALIDATED before any mutation, so a malformed reload leaves
        the live registry untouched.  Returns ``(registered,
        removed)``."""
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
        default, specs = self._specs_from_doc(doc)
        self.default_tenant = default
        for spec in specs:
            self.register(spec)
        keep = {s.name for s in specs} | {self.default_tenant}
        removed = [n for n in self._specs if n not in keep]
        for name in removed:
            del self._specs[name]
            self._buckets.pop(name, None)
        if self.default_tenant not in self._specs:
            self.register(TenantSpec(name=self.default_tenant))
        return len(specs), len(removed)


def plan_shed(counts: Dict[str, int], registry: TenantRegistry,
              keep_total: int) -> List[Tuple[str, int]]:
    """How many queued requests to shed per tenant to bring a band of
    ``sum(counts.values())`` down to ``keep_total``, taking from the
    tenants FURTHEST OVER their fair share first.

    Fair share of the survivor budget is weight-proportional over the
    tenants present, scaled by the shed-class multiplier (``first``
    tenants keep nothing, ``last`` keep double).  Two passes: the
    overage pass takes only above-allowance requests in
    (shed_rank, overage-descending) order; if the budget still is not
    met — every tenant within allowance but the band as a whole over
    budget — a second pass takes proportionally from what remains.
    Returns ``[(tenant, n_to_shed)]`` in take order."""
    total = sum(counts.values())
    to_shed = total - max(0, int(keep_total))
    if to_shed <= 0:
        return []
    weights = {t: registry.resolve(t).weight for t in counts}
    wsum = sum(weights.values()) or 1.0
    allow = {
        t: (registry.resolve(t).shed_allowance_mult
            * (weights[t] / wsum) * max(0, int(keep_total)))
        for t in counts
    }
    order = sorted(
        counts,
        key=lambda t: (registry.resolve(t).shed_rank,
                       -(counts[t] - allow[t])))
    plan: List[Tuple[str, int]] = []
    left = {t: counts[t] for t in counts}
    # pass 1: over-allowance only, worst offenders first
    for t in order:
        if to_shed <= 0:
            break
        over = int(min(left[t], max(0.0, counts[t] - allow[t])))
        take = min(over, to_shed)
        if take > 0:
            plan.append((t, take))
            left[t] -= take
            to_shed -= take
    # pass 2: the band is over budget even with everyone within
    # allowance — take the remainder in the same order
    for t in order:
        if to_shed <= 0:
            break
        take = min(left[t], to_shed)
        if take > 0:
            plan.append((t, take))
            left[t] -= take
            to_shed -= take
    return plan

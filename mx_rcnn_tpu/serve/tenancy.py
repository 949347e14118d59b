"""Per-tenant admission, rate limiting, and weighted-fair scheduling.

The serving stack below this module is tenant-blind: the batcher keys
queues by ``(model, bucket, lane)`` and the pool routes whatever the
batcher releases.  This module adds the missing identity layer (ISSUE
16): every request may carry a ``tenant`` tag, and three mechanisms keep
one aggressive tenant from starving the rest:

* **token-bucket rate limits** — :meth:`TenantTable.admit` spends one
  token per request against the tenant's ``rate``/``burst`` policy and
  raises :class:`TenantOverBudget` when the bucket is empty.  The check
  runs in the submitting thread BEFORE the request costs a queue slot,
  mirroring the quarantine fast-fail path (ISSUE 12): over-budget work
  is cheapest to reject at the door.
* **weighted-fair release** — :class:`WeightedFairScheduler` picks which
  tenant releases the next device batch by deficit accounting (surplus
  round-robin, the O(1)-per-decision deficit-round-robin variant): each
  release distributes its cost over the then-active tenants in weight
  proportion and deducts it from the served tenant, so long-run service
  converges to the weight ratio while an idle tenant banks nothing.
  Lane priority (PR 11) is preserved WITHIN the picked tenant's share —
  the scheduler chooses the tenant, the lane policy chooses the group.
* **shed the over-budget tenant first** — under queue pressure,
  :meth:`TenantTable.over_share` identifies tenants holding more than
  their weight share of the backlog; the engine rejects those first and
  keeps admitting under-share tenants until the hard cap.

Everything is opt-in: an engine without a :class:`TenantTable` (and
requests with ``tenant=None``) behaves exactly as before.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional

from mx_rcnn_tpu.analysis.lockcheck import make_lock

__all__ = [
    "TenantPolicy", "TenantTable", "WeightedFairScheduler",
    "UnknownTenant", "TenantOverBudget",
]


class UnknownTenant(RuntimeError):
    """Request carried a tenant id the table has no policy for — rejected
    at admission (the wire maps this to a typed error frame)."""


class TenantOverBudget(RuntimeError):
    """The tenant's token bucket is empty (sustained rate exceeded) or it
    holds more than its fair share of an overloaded queue — rejected
    without costing a queue slot.  The client backs off like QueueFull,
    but the signal is attributable: THIS tenant is over, not the system."""


@dataclass(frozen=True)
class TenantPolicy:
    """Per-tenant knobs.

    ``weight`` sets the fair-share ratio (a weight-3 tenant gets 3× the
    device batches of a weight-1 tenant under contention).  ``rate`` is
    the sustained admission rate in requests/second (None = unmetered);
    ``burst`` the bucket capacity (defaults to ``max(1, rate)``, i.e.
    one second of sustained rate may arrive at once)."""

    weight: float = 1.0
    rate: Optional[float] = None
    burst: Optional[float] = None

    def __post_init__(self):
        if self.weight <= 0:
            raise ValueError(f"tenant weight must be > 0, got {self.weight}")
        if self.rate is not None and self.rate <= 0:
            raise ValueError(f"tenant rate must be > 0, got {self.rate}")


class _Bucket:
    """One token bucket; caller holds the table lock."""

    __slots__ = ("tokens", "capacity", "rate", "t_last")

    def __init__(self, policy: TenantPolicy, now: float):
        self.rate = policy.rate
        self.capacity = (
            float(policy.burst) if policy.burst is not None
            else max(1.0, float(policy.rate or 1.0))
        )
        self.tokens = self.capacity
        self.t_last = now

    def take(self, now: float) -> bool:
        if self.rate is None:
            return True
        # elapsed clamped at 0: an injected test clock behind the
        # registration stamp must not drain the bucket negative
        self.tokens = min(
            self.capacity,
            self.tokens + max(now - self.t_last, 0.0) * self.rate,
        )
        self.t_last = now
        if self.tokens < 1.0:
            return False
        self.tokens -= 1.0
        return True


class TenantTable:
    """Registry of tenant policies + per-tenant admission accounting.

    ``strict=True`` (the default) rejects unknown tenants with
    :class:`UnknownTenant` — the multi-tenant front door's posture.
    ``strict=False`` auto-registers unknowns at the default policy (an
    internal deployment migrating incrementally).  ``tenant=None``
    always passes: untagged in-process callers are not tenants."""

    def __init__(self, strict: bool = True,
                 default: Optional[TenantPolicy] = None):
        self.strict = bool(strict)
        self._default = default or TenantPolicy()
        self._lock = make_lock("TenantTable._lock")
        self._policies: Dict[str, TenantPolicy] = {}
        self._buckets: Dict[str, _Bucket] = {}
        # per-tenant admission counters (the metrics partition mirrors
        # completion-side accounting; these are door-side)
        self.admitted: Dict[str, int] = {}
        self.over_budget: Dict[str, int] = {}
        self.shed: Dict[str, int] = {}
        self.unknown_rejected = 0

    # ---------------------------------------------------------- registry
    def register(self, tenant: str, weight: float = 1.0,
                 rate: Optional[float] = None,
                 burst: Optional[float] = None) -> TenantPolicy:
        pol = TenantPolicy(weight=weight, rate=rate, burst=burst)
        with self._lock:
            self._policies[tenant] = pol
            self._buckets[tenant] = _Bucket(pol, time.monotonic())
        return pol

    def known(self, tenant: Optional[str]) -> bool:
        if tenant is None:
            return True
        with self._lock:
            return tenant in self._policies or not self.strict

    def tenants(self) -> List[str]:
        with self._lock:
            return list(self._policies)

    def weight(self, tenant: Optional[str]) -> float:
        """Fair-share weight (1.0 for unknown/None — the scheduler must
        never KeyError on a tenant admitted before registration in
        non-strict mode)."""
        if tenant is None:
            return 1.0
        with self._lock:
            pol = self._policies.get(tenant)
        return pol.weight if pol is not None else self._default.weight

    # --------------------------------------------------------- admission
    def admit(self, tenant: Optional[str],
              now: Optional[float] = None) -> None:
        """Admission gate: unknown tenant (strict) raises
        :class:`UnknownTenant`; an empty token bucket raises
        :class:`TenantOverBudget`.  ``now`` is injectable so tests
        can drive the bucket clock deterministically."""
        if tenant is None:
            return
        t = time.monotonic() if now is None else now
        with self._lock:
            if tenant not in self._policies:
                if self.strict:
                    self.unknown_rejected += 1
                    raise UnknownTenant(
                        f"tenant {tenant!r} has no registered policy"
                    )
                self._policies[tenant] = self._default
                self._buckets[tenant] = _Bucket(self._default, t)
            if not self._buckets[tenant].take(t):
                self.over_budget[tenant] = self.over_budget.get(tenant, 0) + 1
                pol = self._policies[tenant]
                raise TenantOverBudget(
                    f"tenant {tenant!r} over rate limit "
                    f"({pol.rate:g} req/s, burst {pol.burst or 'auto'})"
                )
            self.admitted[tenant] = self.admitted.get(tenant, 0) + 1

    def over_share(self, tenant: Optional[str],
                   queued_by_tenant: Dict[Optional[str], int]) -> bool:
        """True when ``tenant`` already holds MORE than its weight share
        of the queued total — the shed-first predicate: under pressure
        the engine rejects over-share tenants while under-share ones
        keep landing until the hard cap."""
        if tenant is None:
            return False
        total = sum(queued_by_tenant.values())
        if total <= 0:
            return False
        # the share denominator is every PROVISIONED tenant (plus any
        # unregistered ones with queued work), not just the currently
        # active set — otherwise a lone flooder owns 100% of the queue
        # by definition and is never over share; idle tenants' shares
        # are exactly the headroom the shed keeps open for them
        with self._lock:
            names = set(self._policies)
        names.update(queued_by_tenant)
        names.add(tenant)
        weights = {t: self.weight(t) for t in names}
        wsum = sum(weights.values())
        share = weights[tenant] / wsum if wsum > 0 else 1.0
        return queued_by_tenant.get(tenant, 0) > share * total

    def note_shed(self, tenant: Optional[str]) -> None:
        if tenant is None:
            return
        with self._lock:
            self.shed[tenant] = self.shed.get(tenant, 0) + 1

    # ------------------------------------------------------ observability
    def snapshot(self) -> Dict:
        with self._lock:
            return {
                "strict": self.strict,
                "policies": {
                    t: {"weight": p.weight, "rate": p.rate, "burst": p.burst}
                    for t, p in self._policies.items()
                },
                "admitted": dict(self.admitted),
                "over_budget": dict(self.over_budget),
                "shed": dict(self.shed),
                "unknown_rejected": self.unknown_rejected,
            }


class WeightedFairScheduler:
    """Deficit-credit weighted-fair pick over tenants.

    Surplus-round-robin formulation of deficit round-robin: every tenant
    carries a credit counter.  When tenant T releases a batch of cost
    ``n`` (requests), the cost is distributed as credit over the tenants
    active at that moment, proportional to weight, and deducted from T —
    total credit granted equals total cost charged, so counters stay
    bounded by one batch regardless of runtime.  :meth:`pick` returns
    the most-underserved active tenant (highest credit; first-seen ring
    order breaks ties, giving round-robin at equal weights) and mutates
    nothing, so the batcher may call it any number of times while
    lingering without skewing fairness; only :meth:`charge` — called
    once per actual release — advances the state.

    Idle tenants bank nothing: credit is granted only to tenants with
    queued work at charge time, so a tenant returning from idle competes
    from par instead of bursting on saved credit.
    """

    def __init__(self, weight_fn=None):
        self._weight = weight_fn if weight_fn is not None else (lambda t: 1.0)
        self._credit: Dict[Optional[str], float] = {}
        self._ring: List[Optional[str]] = []  # first-seen order (tie-break)
        self.picks: Dict[Optional[str], int] = {}
        self.charged: Dict[Optional[str], float] = {}

    def _note(self, tenant: Optional[str]) -> None:
        if tenant not in self._credit:
            self._credit[tenant] = 0.0
            self._ring.append(tenant)

    def pick(self, active: Iterable[Optional[str]]) -> Optional[str]:
        """Most-underserved tenant among ``active`` (pure w.r.t.
        fairness state; unseen tenants are enrolled at credit 0)."""
        active = list(active)
        if not active:
            return None
        for t in active:
            self._note(t)
        best = None
        best_key = None
        for t in active:
            key = (-self._credit[t], self._ring.index(t))
            if best_key is None or key < best_key:
                best, best_key = t, key
        return best

    def charge(self, tenant: Optional[str], cost: float,
               active: Iterable[Optional[str]]) -> None:
        """Account one release: ``tenant`` served ``cost`` requests while
        ``active`` tenants had queued work."""
        self._note(tenant)
        active = set(active) | {tenant}
        for t in active:
            self._note(t)
        wsum = sum(max(self._weight(t), 1e-9) for t in active)
        for t in active:
            self._credit[t] += cost * max(self._weight(t), 1e-9) / wsum
        self._credit[tenant] -= cost
        self.picks[tenant] = self.picks.get(tenant, 0) + 1
        self.charged[tenant] = self.charged.get(tenant, 0.0) + cost

    def snapshot(self) -> Dict:
        return {
            "credit": {str(t): round(c, 4) for t, c in self._credit.items()},
            "picks": {str(t): n for t, n in self.picks.items()},
            "charged": {str(t): c for t, c in self.charged.items()},
        }

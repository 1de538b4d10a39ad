"""Property-style coverage for request queueing, admission control,
batching and tenant arbitration (serving.queueing).

The ``@given`` properties fuzz the policies with hypothesis.
"""

from collections import deque
from dataclasses import dataclass

import hypothesis.strategies as st
from hypothesis import given, settings

from repro.serving.queueing import (OpenLoopGenerator, TenantQueue,
                                    WeightedArbiter, coalesce)


@dataclass
class Item:
    uid: int
    deadline: float | None = None


# ---------------------------------------------------------------------------
# coalesce: batch formation + deadline expiry
# ---------------------------------------------------------------------------

def test_coalesce_fifo_order_and_cap():
    q = deque(Item(i) for i in range(10))
    batch, expired = coalesce(q, now=0.0, max_batch=4)
    assert [b.uid for b in batch] == [0, 1, 2, 3]
    assert expired == []
    assert [x.uid for x in q] == [4, 5, 6, 7, 8, 9]


def test_coalesce_expires_only_past_deadline():
    q = deque([Item(0, deadline=1.0), Item(1, deadline=5.0),
               Item(2), Item(3, deadline=1.5)])
    batch, expired = coalesce(q, now=2.0, max_batch=10)
    assert [b.uid for b in batch] == [1, 2]
    assert [e.uid for e in expired] == [0, 3]
    assert not q


def test_coalesce_expired_do_not_count_against_cap():
    q = deque([Item(0, deadline=0.0), Item(1, deadline=0.0), Item(2),
               Item(3)])
    batch, expired = coalesce(q, now=1.0, max_batch=2)
    assert [b.uid for b in batch] == [2, 3]
    assert len(expired) == 2


@given(st.lists(st.tuples(st.booleans(), st.floats(0.0, 10.0)),
                min_size=0, max_size=40),
       st.integers(1, 8), st.floats(0.0, 10.0))
@settings(max_examples=60, deadline=None)
def test_coalesce_partition_property(spec, max_batch, now):
    """Every queued item ends up in exactly one of (batch, expired,
    still-queued); batch and expired preserve arrival order; nothing in
    the batch is past its deadline."""
    items = [Item(i, deadline=(d if has_dl else None))
             for i, (has_dl, d) in enumerate(spec)]
    q = deque(items)
    batch, expired = coalesce(q, now=now, max_batch=max_batch)
    assert len(batch) <= max_batch
    seen = [b.uid for b in batch] + [e.uid for e in expired] \
        + [x.uid for x in q]
    assert sorted(seen) == [i.uid for i in items]
    assert [b.uid for b in batch] == sorted(b.uid for b in batch)
    assert [e.uid for e in expired] == sorted(e.uid for e in expired)
    assert all(b.deadline is None or now <= b.deadline for b in batch)
    assert all(e.deadline is not None and now > e.deadline for e in expired)


# ---------------------------------------------------------------------------
# TenantQueue: admission control
# ---------------------------------------------------------------------------

def test_admission_rejects_when_full():
    q = TenantQueue(max_queue=2)
    assert q.offer() and q.offer()
    assert not q.offer()
    assert (q.admitted, q.rejected, q.in_system) == (2, 1, 2)
    q.complete()
    assert q.offer()                      # slot freed by completion
    assert q.admitted == 3


def test_admission_accounting_balances():
    q = TenantQueue(max_queue=3)
    outcomes = [q.offer() for _ in range(5)]
    assert outcomes == [True, True, True, False, False]
    q.complete()
    q.expire()
    assert q.in_system == 1
    assert q.admitted == q.completed + q.expired + q.in_system


@given(st.lists(st.sampled_from(["offer", "complete", "expire"]),
                min_size=0, max_size=200),
       st.integers(1, 10))
@settings(max_examples=60, deadline=None)
def test_admission_invariants(ops, cap):
    """in_system never exceeds max_queue or goes negative, and the
    counter identity admitted == completed + expired + in_system holds
    under any interleaving."""
    q = TenantQueue(max_queue=cap)
    for op in ops:
        if op == "offer":
            q.offer()
        elif q.in_system > 0:
            getattr(q, op)()
        assert 0 <= q.in_system <= cap
        assert q.admitted == q.completed + q.expired + q.in_system


# ---------------------------------------------------------------------------
# WeightedArbiter: proportional grants, no starvation
# ---------------------------------------------------------------------------

def test_arbiter_grants_proportional_to_weights():
    arb = WeightedArbiter({"a": 3.0, "b": 1.0})
    for _ in range(400):
        arb.pick()
    assert abs(arb.grants["a"] - 300) <= 2
    assert abs(arb.grants["b"] - 100) <= 2


def test_arbiter_respects_eligibility():
    arb = WeightedArbiter({"a": 1.0, "b": 1.0})
    assert arb.pick({"b"}) == "b"
    assert arb.pick(set()) is None


def test_arbiter_new_tenant_does_not_monopolize():
    arb = WeightedArbiter({"a": 1.0})
    for _ in range(100):
        arb.pick()
    arb.add("b", 1.0)
    picks = [arb.pick() for _ in range(10)]
    # joined at the current floor: alternates instead of being handed
    # 100 rounds of accumulated credit
    assert picks.count("b") <= 6


@given(st.lists(st.floats(0.1, 20.0), min_size=1, max_size=6),
       st.integers(10, 300))
@settings(max_examples=60, deadline=None)
def test_arbiter_no_starvation(weights, rounds):
    """Over any horizon, every tenant's grant count is within one grant
    of its weight share — nobody starves no matter how skewed the
    weights are."""
    names = [f"t{i}" for i in range(len(weights))]
    arb = WeightedArbiter(dict(zip(names, weights)))
    for _ in range(rounds):
        arb.pick()
    total_w = sum(weights)
    for n, w in zip(names, weights):
        expected = rounds * w / total_w
        assert arb.grants[n] >= int(expected) - 1
        assert arb.grants[n] <= expected + 1 + len(weights)


# ---------------------------------------------------------------------------
# OpenLoopGenerator: seeded, ordered, bursty
# ---------------------------------------------------------------------------

def test_open_loop_deterministic_and_ordered():
    g1 = OpenLoopGenerator(rate_per_s=50.0, seed=7)
    g2 = OpenLoopGenerator(rate_per_s=50.0, seed=7)
    a, b = g1.arrivals(50), g2.arrivals(50)
    assert a == b
    assert a == sorted(a)
    assert OpenLoopGenerator(rate_per_s=50.0, seed=8).arrivals(50) != a


def test_open_loop_burst_raises_rate():
    base = OpenLoopGenerator(rate_per_s=20.0, seed=1)
    burst = OpenLoopGenerator(rate_per_s=20.0, seed=1, burst_factor=8.0,
                              burst_period_s=1.0, burst_duty=1.0)
    assert burst.arrivals(200)[-1] < base.arrivals(200)[-1]


def test_open_loop_requests_carry_payloads():
    gen = OpenLoopGenerator(rate_per_s=10.0, seed=0)
    reqs = gen.generate(5, make_payload=lambda rng, i: ("payload", i))
    assert [r.rid for r in reqs] == list(range(5))
    assert all(r.payload == ("payload", i) for i, r in enumerate(reqs))

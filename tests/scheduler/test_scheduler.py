"""Scheduler unit semantics: priority ordering, FIFO control arm,
stride-weighted fairness, starvation aging, EWMA occupancy, graded
admission — all deterministic via injected clocks."""

from __future__ import annotations

import asyncio

import pytest

from lodestar_tpu.scheduler import (
    BULK_CLASSES,
    AdmissionController,
    AdmissionState,
    OccupancyTracker,
    PriorityClass,
    PriorityWorkQueue,
)


class FakeNs:
    """Manually advanced monotonic-ns clock."""

    def __init__(self):
        self.now = 1_000_000

    def __call__(self) -> int:
        return self.now

    def advance_ms(self, ms: float) -> None:
        self.now += int(ms * 1e6)


def _drain_classes(q: PriorityWorkQueue) -> list[PriorityClass]:
    out = []
    while True:
        item = q.get_nowait()
        if item is None:
            return out
        out.append(item[1])


def test_urgent_class_dequeues_before_queued_bulk():
    q = PriorityWorkQueue(time_fn=FakeNs())
    for i in range(5):
        q.put_nowait(f"backfill{i}", PriorityClass.BACKFILL)
    q.put_nowait("block", PriorityClass.GOSSIP_BLOCK)
    item, cls, _ = q.get_nowait()
    # the block arrived LAST but dequeues FIRST — no head-of-line blocking
    assert item == "block" and cls is PriorityClass.GOSSIP_BLOCK
    assert len(q) == 5 and q.depth(PriorityClass.BACKFILL) == 5


def test_fifo_mode_preserves_arrival_order():
    clock = FakeNs()
    q = PriorityWorkQueue(fifo=True, time_fn=clock)
    q.put_nowait("backfill", PriorityClass.BACKFILL)
    clock.advance_ms(1)
    q.put_nowait("block", PriorityClass.GOSSIP_BLOCK)
    assert q.get_nowait()[0] == "backfill"  # FIFO: bulk ahead of the block
    assert q.get_nowait()[0] == "block"


def test_weighted_fairness_serves_bulk_a_trickle():
    q = PriorityWorkQueue(time_fn=FakeNs())
    for i in range(64):
        q.put_nowait(i, PriorityClass.GOSSIP_ATTESTATION)
    for i in range(8):
        q.put_nowait(i, PriorityClass.BACKFILL)
    order = _drain_classes(q)
    first_32 = order[:32]
    # attestations dominate (weight 16:1) but backfill is NOT starved:
    # the stride scheduler works some bulk in well before the queue drains
    assert first_32.count(PriorityClass.GOSSIP_ATTESTATION) >= 28
    assert PriorityClass.BACKFILL in first_32
    assert order.count(PriorityClass.BACKFILL) == 8


def test_idle_class_gets_no_burst_credit():
    q = PriorityWorkQueue(time_fn=FakeNs())
    # attestations consume service for a while
    for i in range(32):
        q.put_nowait(i, PriorityClass.GOSSIP_ATTESTATION)
    for _ in range(32):
        q.get_nowait()
    # backfill waking from idle must not get a catch-up burst ahead of
    # fresh urgent work
    for i in range(4):
        q.put_nowait(i, PriorityClass.BACKFILL)
    q.put_nowait("att", PriorityClass.GOSSIP_ATTESTATION)
    assert q.get_nowait()[1] is PriorityClass.GOSSIP_ATTESTATION


def test_starvation_aging_promotes_old_bulk():
    clock = FakeNs()
    q = PriorityWorkQueue(aging_ms=100.0, time_fn=clock)
    q.put_nowait("old-backfill", PriorityClass.BACKFILL)
    clock.advance_ms(150)  # past the aging window
    q.put_nowait("block", PriorityClass.GOSSIP_BLOCK)
    item, cls, waited_ns = q.get_nowait()
    assert item == "old-backfill" and cls is PriorityClass.BACKFILL
    assert q.starvation_promotions == 1
    assert waited_ns == pytest.approx(150e6)


def test_fully_aged_backlog_cannot_degenerate_to_global_fifo():
    clock = FakeNs()
    q = PriorityWorkQueue(aging_ms=100.0, time_fn=clock)
    for i in range(10):
        q.put_nowait(f"bf{i}", PriorityClass.BACKFILL)
    clock.advance_ms(500)  # the WHOLE bulk backlog is past the aging window
    q.put_nowait("block", PriorityClass.GOSSIP_BLOCK)
    order = [q.get_nowait()[0] for _ in range(11)]
    # aging alternates with the fair pick: the block waits out at most one
    # promotion instead of the entire aged backlog (oldest-first FIFO)
    assert order.index("block") <= 1, order


def test_async_get_wakes_on_put():
    async def go():
        q = PriorityWorkQueue()

        async def producer():
            await asyncio.sleep(0.01)
            q.put_nowait("x", PriorityClass.API)

        asyncio.ensure_future(producer())
        item, cls, _ = await asyncio.wait_for(q.get(), 2)
        assert item == "x" and cls is PriorityClass.API

    asyncio.run(go())


def test_occupancy_ewma_rises_and_decays():
    clock = FakeNs()
    occ = OccupancyTracker(tau_s=10.0, time_fn=clock)
    assert occ.occupancy() == 0.0
    occ.begin()
    clock.advance_ms(10_000)  # busy for one time constant
    occ.end()
    one_tau = occ.occupancy()
    assert 0.60 < one_tau < 0.66  # 1 - e^-1
    assert occ.busy_ns_total == 10_000 * 1_000_000
    clock.advance_ms(10_000)  # idle for one time constant
    assert 0.20 < occ.occupancy() < 0.25  # decayed by e^-1
    # overlapping launches don't double-count busy time
    occ2 = OccupancyTracker(tau_s=10.0, time_fn=clock)
    occ2.begin()
    occ2.begin()
    clock.advance_ms(5_000)
    occ2.end()
    clock.advance_ms(5_000)
    occ2.end()
    assert occ2.busy_ns_total == 10_000 * 1_000_000


class FixedOccupancy:
    def __init__(self, value: float):
        self.value = value

    def occupancy(self) -> float:
        return self.value


def test_admission_controller_grades():
    occ = FixedOccupancy(0.1)
    depth = [0]
    veto = [True]
    adm = AdmissionController(
        occ,
        shed_bulk_at=0.75,
        reject_at=0.95,
        depth_fn=lambda: depth[0],
        shed_bulk_depth=10,
        reject_depth=20,
        can_accept=lambda: veto[0],
    )
    assert adm.state() is AdmissionState.ACCEPT
    assert all(adm.admits(c) for c in PriorityClass)

    occ.value = 0.8  # occupancy past the bulk threshold
    assert adm.state() is AdmissionState.SHED_BULK
    assert adm.admits(PriorityClass.GOSSIP_BLOCK)
    assert not adm.admits(PriorityClass.BACKFILL)
    assert not adm.admits(PriorityClass.RANGE_SYNC)

    occ.value = 0.96
    assert adm.state() is AdmissionState.REJECT
    assert not any(adm.admits(c) for c in PriorityClass)

    occ.value = 0.1
    depth[0] = 15  # depth alone triggers shed
    assert adm.state() is AdmissionState.SHED_BULK
    depth[0] = 25
    assert adm.state() is AdmissionState.REJECT
    depth[0] = 0
    veto[0] = False  # the hard gate overrides everything
    assert adm.state() is AdmissionState.REJECT


def test_bulk_classes_cover_sync_paths():
    assert BULK_CLASSES == {PriorityClass.RANGE_SYNC, PriorityClass.BACKFILL}
    # priority order is the admission/docs contract
    assert (
        PriorityClass.GOSSIP_BLOCK
        < PriorityClass.GOSSIP_ATTESTATION
        < PriorityClass.API
        < PriorityClass.RANGE_SYNC
        < PriorityClass.BACKFILL
    )


def test_queued_shows_the_class_in_dequeue_order_and_moves_nothing():
    """`queued(cls)` is what `get_nowait(cls)` would pop, in order: no
    pop, no fairness accounting, no metric."""
    q = PriorityWorkQueue()
    assert list(q.queued(PriorityClass.BACKFILL)) == []
    q.put_nowait("b0", PriorityClass.BACKFILL)
    q.put_nowait("b1", PriorityClass.BACKFILL)
    q.put_nowait("g0", PriorityClass.GOSSIP_BLOCK)
    before = q.stats()
    assert list(q.queued(PriorityClass.BACKFILL)) == ["b0", "b1"]
    assert list(q.queued(PriorityClass.GOSSIP_BLOCK)) == ["g0"]
    assert list(q.queued(PriorityClass.API)) == []
    assert q.stats() == before and len(q) == 3
    assert q.get_nowait(PriorityClass.BACKFILL)[0] == "b0"
    assert list(q.queued(PriorityClass.BACKFILL)) == ["b1"]


@pytest.mark.parametrize("fifo", [False, True], ids=["scheduler", "fifo"])
def test_next_class_is_the_class_the_next_dequeue_serves_and_moves_nothing(fifo):
    """`next_class()` answers by the same stride and aging order as
    `get_nowait()`, through a saturated mix with an aged backlog, and
    leaves the fairness accounting, the promotion counter and the
    alternation of promotions where they were."""
    clock = FakeNs()
    q = PriorityWorkQueue(aging_ms=100.0, fifo=fifo, time_fn=clock)
    assert q.next_class() is None
    for i in range(6):
        q.put_nowait(f"bf{i}", PriorityClass.BACKFILL)
        clock.advance_ms(1)
        q.put_nowait(f"rs{i}", PriorityClass.RANGE_SYNC)
    clock.advance_ms(150)  # the bulk backlog is past the aging window
    for i in range(6):
        q.put_nowait(f"block{i}", PriorityClass.GOSSIP_BLOCK)
        clock.advance_ms(1)
    served = []
    while len(q):
        before = q.stats()
        said = q.next_class()
        assert q.next_class() is said and q.stats() == before
        served.append(q.get_nowait()[1])
        assert served[-1] is said
    assert len(served) == 18 and set(served) == {
        PriorityClass.BACKFILL, PriorityClass.RANGE_SYNC, PriorityClass.GOSSIP_BLOCK}
    assert (q.starvation_promotions > 0) is not fifo
    assert q.next_class() is None


def test_aged_holds_taken_out_work_to_the_queues_starvation_bound():
    clock = FakeNs()
    q = PriorityWorkQueue(aging_ms=100.0, time_fn=clock)
    taken = clock()
    assert not q.aged(taken)
    clock.advance_ms(99)
    assert not q.aged(taken)
    clock.advance_ms(1)
    assert q.aged(taken)

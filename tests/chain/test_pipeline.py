"""Double-buffered prep→verify pipeline invariants (chain/bls/pool.py):

* verdicts are bit-identical pipelined vs unpipelined (seeded replay),
* prep of batch k+1 is in flight WHILE batch k verifies (the overlap
  the bench line reports),
* a prep error in batch k+1 degrades only that batch to host prep —
  batch k's device verdict stands,
* close() drains both stages without stranding futures,
* 1-lane / no-mesh keeps the exact pre-pipeline launch schedule where
  the lane's staged prep is device work (the PR 8 single-lane equality
  doctrine) and stages the host parse where the lane says its staged
  prep is host-only, bulk packages too where the mesh cannot shard,
* an urgent arrival overtakes the package taken ahead, and the
  `parse_ns` / `parse_hidden_ns` counters say how much was hidden,
* staged inputs actually reach the lanes' verify_prepared seam.

A rig's lanes state their own facts (`FakeLaneRig(with_prepared=,
staged_prep_host_only=)`): that, and a job queued behind a held launch,
is what makes a pool stage here. The one-lane rigs that must stage are
`_host_parse_rig`s; the control arm is a rig of plain lanes.
"""

from __future__ import annotations

import asyncio
import random
import threading
import time

import pytest

from lodestar_tpu.chain.bls import BlsDeviceVerifierPool, VerifySignatureOpts
from lodestar_tpu.crypto.bls.api import SignatureSet
from lodestar_tpu.scheduler import PriorityClass
from lodestar_tpu.testing.mesh import FakeLaneRig


def _sets(n: int, tag: int = 0) -> list[SignatureSet]:
    return [
        SignatureSet(
            pubkey=bytes([1, tag, i % 256]) + bytes(45),
            message=bytes([2, tag, i % 256]) * 8 + bytes(8),
            signature=bytes([3, tag, i % 256]) + bytes(93),
        )
        for i in range(n)
    ]


def _run(coro):
    return asyncio.run(coro)


def _host_parse_rig(call_s: float = 0.0, with_sharded: bool = False) -> FakeLaneRig:
    """One lane as the single launch's: it takes staged inputs, and what
    is staged for it touches no device — so the pool stages behind it."""
    return FakeLaneRig(
        1, call_s=call_s, with_prepared=True, with_sharded=with_sharded,
        staged_prep_host_only=True,
    )


# -- verdict equivalence -------------------------------------------------------


def test_verdicts_identical_pipelined_vs_unpipelined():
    """Seeded replay: the same job stream (some invalid) produces the
    same per-job verdicts on two lanes that take staged inputs (the
    pool stages) and on two plain lanes (it cannot). tag==13 marks a
    set invalid, so the batch-then-retry road is exercised too."""

    def verdict_fn(sets):
        return all(s.message[1] != 13 for s in sets)

    def replay(staged: bool):
        rng = random.Random(42)
        rig = FakeLaneRig(2, with_prepared=staged, with_sharded=False)

        async def go():
            pool = BlsDeviceVerifierPool(
                mesh=rig.mesh,
                scheduler_enabled=True,
                prep_fn=FakeLaneRig.prep_fn if staged else None,
            )
            assert pool.pipeline_stats()["pipeline_enabled"] is staged
            jobs = []
            for i in range(24):
                tag = 13 if rng.random() < 0.25 else i % 7
                jobs.append(
                    pool.verify_signature_sets(
                        _sets(2, tag=tag),
                        VerifySignatureOpts(
                            batchable=rng.random() < 0.5,
                            priority=PriorityClass.GOSSIP_ATTESTATION,
                        ),
                    )
                )
            verdicts = await asyncio.gather(*jobs)
            await pool.close()
            return verdicts

        rig.verdict_fn = verdict_fn
        return _run(go())

    assert replay(False) == replay(True)


# -- overlap -------------------------------------------------------------------


def test_prep_of_next_batch_overlaps_verify_of_current():
    """While lane L verifies batch k, the stage loop preps batch k+1 —
    the overlap tracker must record concurrent prep+verify wall time."""
    rig = _host_parse_rig(call_s=0.08)

    def slow_prep(sets, lane_hint):
        time.sleep(0.04)
        return FakeLaneRig.prep_fn(sets, lane_hint)

    async def go():
        pool = BlsDeviceVerifierPool(
            mesh=rig.mesh,
            scheduler_enabled=True,
            prep_fn=slow_prep,
        )
        jobs = []
        for i in range(4):
            jobs.append(
                asyncio.ensure_future(
                    pool.verify_signature_sets(
                        # a bulk class: a job is a package, taken ahead of the busy lane
                        _sets(1, tag=i),
                        VerifySignatureOpts(batchable=False, priority=PriorityClass.RANGE_SYNC),
                    )
                )
            )
            await asyncio.sleep(0.02)  # arrive while the lane is busy
        ok = await asyncio.gather(*jobs)
        stats = pool.pipeline_stats()
        await pool.close()
        return ok, stats

    ok, stats = _run(go())
    assert all(ok)
    assert stats["pipeline_enabled"] is True
    assert stats["staged_packages"] >= 2
    assert stats["overlap_ns"] > 0, stats
    assert stats["overlap_occupancy_pct"] > 0.0


def test_staged_inputs_reach_the_prepared_verify_seam():
    """The job that finds the lane free takes the inline road; the one
    queued behind its launch is staged and goes through the seam."""
    rig = _host_parse_rig(call_s=0.03)
    ok, _stats, _metrics = _queued_behind_a_launch(rig, 2)
    assert ok == [True, True]
    assert rig.prepared_calls == [(0, 1)], "staged inputs never reached verify_prepared_fn"


# -- degradation ---------------------------------------------------------------


def test_prep_error_in_batch_k1_degrades_only_that_batch(monkeypatch):
    """The split schedule with device prep, as an accelerator runs it on
    the single launch's error road, forced here at the call: the lane is
    `_verify_sets_split` / `verify_prepared`, staged prep is
    `build_device_inputs`, and the resolver is patched so that prep runs
    on the device. The device prep of batch k+1 is injected to fail:
    batch k preps on device and its device verdict stands; batch k+1,
    staged behind k's launch, degrades to host prep (fallback counted
    once) and still verifies True. The degradation chain is
    build_device_inputs' own — the pipeline only moved WHERE it runs."""
    from lodestar_tpu.chain.bls.mesh import single_lane_mesh
    from lodestar_tpu.metrics import create_metrics
    from lodestar_tpu.models import batch_verify as bv
    from lodestar_tpu.ops import prep as dp

    metrics = create_metrics()
    bv.configure_device_prep(metrics.bls_prep)
    monkeypatch.setattr(bv, "single_launch_active", lambda: True)
    real = bv._prepare_sets_device_arrays
    sets_k = bv.make_synthetic_sets(4, seed=61)
    sets_k1 = bv.make_synthetic_sets(4, seed=62)

    def flaky(sets, size):
        if sets[0].pubkey == sets_k1[0].pubkey:
            raise RuntimeError("injected device prep fault in batch k+1")
        return real(sets, size)

    monkeypatch.setattr(bv, "_prepare_sets_device_arrays", flaky)
    # one CPU device has no sibling die to stage on: the lane says its
    # staged prep may share it, which is all `_staging` asks
    mesh = single_lane_mesh(
        bv._verify_sets_split, verify_prepared_fn=bv.verify_prepared, staged_prep_host_only=True
    )

    async def go():
        pool = BlsDeviceVerifierPool(
            mesh=mesh, prep_fn=lambda sets, lane_hint: bv.build_device_inputs(sets)
        )
        bulk = VerifySignatureOpts(batchable=False, priority=PriorityClass.RANGE_SYNC)
        futs = [
            asyncio.ensure_future(pool.verify_signature_sets(sets, bulk))
            for sets in (sets_k, sets_k1)
        ]
        ok = await asyncio.gather(*futs)
        stats = pool.pipeline_stats()
        await pool.close()
        return ok, stats

    try:
        (ok_k, ok_k1), stats = _run(go())
    finally:
        dp.configure_launch_counter(None)
        bv._prep_metrics = None
        bv.consume_prep_info()
    assert ok_k is True and ok_k1 is True
    assert stats["staged_packages"] == 1  # k found the lane free; k+1 was staged behind it
    assert metrics.bls_prep.sets.labels("device")._value.get() == 4
    assert metrics.bls_prep.sets.labels("host")._value.get() == 4
    assert metrics.bls_prep.fallbacks._value.get() == 1


# -- close ---------------------------------------------------------------------


def test_close_drains_both_stages_without_stranding_futures():
    rig = _host_parse_rig(call_s=0.2)

    def slow_prep(sets, lane_hint):
        time.sleep(0.1)
        return FakeLaneRig.prep_fn(sets, lane_hint)

    async def go():
        pool = BlsDeviceVerifierPool(
            mesh=rig.mesh,
            scheduler_enabled=True,
            prep_fn=slow_prep,
        )
        futures = [
            asyncio.ensure_future(
                pool.verify_signature_sets(
                    _sets(1, tag=i), VerifySignatureOpts(batchable=False)
                )
            )
            for i in range(6)
        ]
        await asyncio.sleep(0.05)  # one package of six units in hand, its first still in its parse
        await pool.close()
        results = await asyncio.gather(*futures, return_exceptions=True)
        return futures, results

    futures, results = _run(go())
    assert all(f.done() for f in futures)
    for r in results:
        assert isinstance(r, (bool, asyncio.CancelledError)), r


# -- 1-lane schedule regression ------------------------------------------------


def test_single_lane_of_plain_callables_keeps_pre_pipeline_schedule():
    """A 1-lane / no-mesh pool whose lane only speaks sets: the pipeline
    must NOT engage — launches stay serialized, one a job in arrival
    order, and nothing is staged."""
    rig = FakeLaneRig(1, call_s=0.01, with_sharded=False)

    async def go():
        pool = BlsDeviceVerifierPool(mesh=rig.mesh, scheduler_enabled=True)
        assert pool.pipeline_stats()["pipeline_enabled"] is False
        for i in range(5):
            assert await pool.verify_signature_sets(
                _sets(1, tag=i), VerifySignatureOpts(batchable=False)
            )
        stats = pool.pipeline_stats()
        await pool.close()
        return stats

    stats = _run(go())
    assert rig.calls == [(0, 1)] * 5
    assert stats["staged_packages"] == 0
    assert stats["prep_ns"] == 0  # the prep stage never ran


def _parse_that_takes_a_while(sets, lane_hint):
    time.sleep(0.01)
    return FakeLaneRig.prep_fn(sets, lane_hint)


def _queued_behind_a_launch(rig, n: int, priority=PriorityClass.RANGE_SYNC):
    """`n` one-set jobs of one class, all queued while the first holds
    the lane (a bulk class: one job a package): (verdicts, pipeline
    stats, pool metrics)."""

    async def go():
        pool = BlsDeviceVerifierPool(
            mesh=rig.mesh, scheduler_enabled=True, prep_fn=_parse_that_takes_a_while
        )
        futs = [
            asyncio.ensure_future(
                pool.verify_signature_sets(
                    _sets(1, tag=i), VerifySignatureOpts(batchable=False, priority=priority)
                )
            )
            for i in range(n)
        ]
        ok = await asyncio.gather(*futs)
        stats, metrics = pool.pipeline_stats(), dict(pool.metrics)
        await pool.close()
        return ok, stats, metrics

    return _run(go())


def test_single_lane_stages_the_host_parse_under_the_single_launch():
    """One lane whose staged prep is host-only (the single launch's): the
    pool stages the next package while the lane runs this one. The
    first package finds the lane free and has nothing to hide behind
    (inline); the rest go through the prepared seam, in the order a
    pool over a plain lane, which cannot stage, launches them."""
    rig = _host_parse_rig(call_s=0.03)
    ok, stats, metrics = _queued_behind_a_launch(rig, 5)
    off = FakeLaneRig(1, call_s=0.03, with_sharded=False)
    ok_off, stats_off, metrics_off = _queued_behind_a_launch(off, 5)
    assert ok == ok_off == [True] * 5
    assert rig.calls == off.calls  # identical lane/size launch sequence
    assert stats["pipeline_enabled"] is True and stats_off["pipeline_enabled"] is False
    assert stats["staged_packages"] == 4 and len(rig.prepared_calls) == 4
    assert stats_off["staged_packages"] == 0 and off.prepared_calls == []
    # the counters the benchmark reads: parse time, and the part of it a launch hid
    assert 0 < metrics["parse_hidden_ns"] <= metrics["parse_ns"]
    assert metrics["parse_ns"] == stats["prep_ns"] and metrics["parse_hidden_ns"] == stats["overlap_ns"]
    assert metrics_off["parse_ns"] == 0 == metrics_off["parse_hidden_ns"]


def test_single_lane_split_schedule_stages_nothing():
    """The same lane as the split schedule builds it (it takes staged
    inputs, but they are device launches on the die that verifies): the
    pool stages nothing."""
    rig = FakeLaneRig(1, call_s=0.01, with_prepared=True, with_sharded=False)
    ok, stats, metrics = _queued_behind_a_launch(rig, 4)
    assert ok == [True] * 4
    assert stats["pipeline_enabled"] is False and stats["staged_packages"] == 0
    assert rig.prepared_calls == [] and metrics["parse_ns"] == 0


@pytest.mark.parametrize("can_shard", [False, True], ids=["mesh-cannot-shard", "mesh-can-shard"])
def test_bulk_packages_are_staged_where_the_mesh_cannot_shard(can_shard):
    """A RANGE_SYNC package keeps its inline prep only for the
    collective road's sake: on a mesh that cannot shard it is staged
    like any other class."""
    rig = _host_parse_rig(call_s=0.03, with_sharded=can_shard)
    ok, stats, _ = _queued_behind_a_launch(rig, 4, priority=PriorityClass.RANGE_SYNC)
    assert ok == [True] * 4 and rig.sharded_calls == []  # one lane never shards; the stage only asks
    assert stats["staged_packages"] == (0 if can_shard else 3)
    assert len(rig.prepared_calls) == (0 if can_shard else 3)


def _overtaking(staged_class, arriving_class, aging_ms=None, staged_sets=1):
    """A launch in flight, a package of `staged_class` taken ahead of the
    lane (one the queue holds all of: a bulk job, or `staged_sets` a full
    package's worth), and then, during that launch, a job of
    `arriving_class`: the tags in the order the lane served them (0 in
    flight, 1 staged, 2 the late arrival)."""
    rig = _host_parse_rig(call_s=0.25)
    served = []

    def serve(sets):
        if served[-1:] != [sets[0].pubkey[1]]:  # a package of several launches reads once
            served.append(sets[0].pubkey[1])
        return True

    rig.verdict_fn = serve

    async def go():
        pool = BlsDeviceVerifierPool(
            mesh=rig.mesh, scheduler_enabled=True, prep_fn=FakeLaneRig.prep_fn,
            **({} if aging_ms is None else {"aging_ms": aging_ms}),
        )

        def submit(tag, cls, n=1):
            return asyncio.ensure_future(pool.verify_signature_sets(
                _sets(n, tag=tag), VerifySignatureOpts(batchable=False, priority=cls)))

        futs = [submit(0, staged_class)]
        await asyncio.sleep(0.01)  # a package of its own, whatever its class
        futs.append(submit(1, staged_class, staged_sets))
        for _ in range(40):  # 0 holds the lane (inline: it found it free); 1 is in hand, its parse under way or done
            if pool.pipeline_stats()["staged_packages"] == 1:
                break
            await asyncio.sleep(0.005)
        assert pool.pipeline_stats()["staged_packages"] == 1 and served == []
        futs.append(submit(2, arriving_class))
        assert all(await asyncio.gather(*futs))
        await pool.close()

    _run(go())
    return served


@pytest.mark.parametrize(
    "staged_class, arriving_class, aging_ms, staged_sets, want",
    [
        (PriorityClass.RANGE_SYNC, PriorityClass.GOSSIP_BLOCK, None, 1, [0, 2, 1]),
        (PriorityClass.RANGE_SYNC, PriorityClass.RANGE_SYNC, None, 1, [0, 1, 2]),
        (PriorityClass.RANGE_SYNC, PriorityClass.BACKFILL, None, 1, [0, 1, 2]),
        (PriorityClass.GOSSIP_BLOCK, PriorityClass.GOSSIP_BLOCK, None, 512, [0, 1, 2]),
        (PriorityClass.API, PriorityClass.GOSSIP_BLOCK, None, 512, [0, 2, 1]),
        (PriorityClass.RANGE_SYNC, PriorityClass.GOSSIP_BLOCK, 1.0, 1, [0, 1, 2]),
    ],
    ids=["urgent-overtakes", "own-class-waits", "less-urgent-waits", "urgent-behind-urgent",
         "urgent-overtakes-a-full-api-package", "past-the-starvation-bound"],
)
def test_an_urgent_arrival_overtakes_the_package_taken_ahead(
    staged_class, arriving_class, aging_ms, staged_sets, want
):
    """The look-ahead must not lengthen what an urgent job waits behind:
    a GOSSIP_BLOCK that arrives during a bulk launch goes before the
    bulk package already staged (which keeps its parse and goes after
    it), as the queue's own order would have had it; a job of the staged
    package's class or a less urgent one does not, and a package held
    for the queue's starvation bound is overtaken no more."""
    assert _overtaking(staged_class, arriving_class, aging_ms, staged_sets) == want


def _arrivals_during_a_launch(staged: bool) -> list:
    """One attestation starts a launch; one arrives 5 ms into it and
    nine more during it: the lane's launches, (lane, sets) each. On a
    lane the pool stages behind, or on a plain one."""
    rig = _host_parse_rig(call_s=0.3) if staged else FakeLaneRig(1, call_s=0.3, with_sharded=False)

    async def go():
        pool = BlsDeviceVerifierPool(
            mesh=rig.mesh, scheduler_enabled=True,
            prep_fn=FakeLaneRig.prep_fn if staged else None, buffer_wait_ms=1,
        )
        assert pool.pipeline_stats()["pipeline_enabled"] is staged

        def submit(tag):
            return asyncio.ensure_future(pool.verify_signature_sets(
                _sets(1, tag=tag),
                VerifySignatureOpts(batchable=True, priority=PriorityClass.GOSSIP_ATTESTATION)))

        futs = [submit(0)]
        while not rig.mesh.lanes[0].inflight:
            await asyncio.sleep(0.001)
        for tag in range(1, 11):
            await asyncio.sleep(0.005)
            futs.append(submit(tag))
        assert all(await asyncio.gather(*futs))
        await pool.close()

    _run(go())
    return rig.calls


def test_arrivals_during_a_launch_ride_the_next_launch_together():
    """Open-loop traffic of one class (gossip attestations): what arrives
    while the lane is busy is ONE package when it frees, staged or not.
    The dispatcher must not take the first arrival out of the queue
    alone, ahead of the lane, and leave the rest a launch behind."""
    staged = _arrivals_during_a_launch(True)
    assert staged == _arrivals_during_a_launch(False)
    assert [n for _lane, n in staged] == [1, 10]


def test_a_parse_the_launch_thread_waits_for_is_not_hidden():
    """`parse_hidden_ns` counts parse time under a launch actually
    dispatched. A two-unit package whose parse (100 ms a unit) outlasts
    its launches (20 ms): the second parse has the first launch to hide
    behind and no more, though the launch thread is in the package's
    verify all the while it waits for the hand-over."""
    rig = _host_parse_rig(call_s=0.02)

    def slow_prep(sets, lane_hint):
        time.sleep(0.1)
        return FakeLaneRig.prep_fn(sets, lane_hint)

    async def go():
        pool = BlsDeviceVerifierPool(mesh=rig.mesh, scheduler_enabled=True, prep_fn=slow_prep)
        futs = [
            asyncio.ensure_future(pool.verify_signature_sets(
                _sets(1, tag=i), VerifySignatureOpts(batchable=False)))
            for i in range(2)
        ]
        assert all(await asyncio.gather(*futs))
        metrics, stats = dict(pool.metrics), pool.pipeline_stats()
        await pool.close()
        return metrics, stats

    metrics, stats = _run(go())
    assert stats["staged_packages"] == 1 and len(rig.prepared_calls) == 2
    assert metrics["parse_ns"] >= 0.2e9
    # the first 20 ms launch hides that much of the second parse; the old window, all 100 ms of it
    assert metrics["parse_hidden_ns"] <= stats["verify_ns"] and metrics["parse_hidden_ns"] < 0.07e9


def _formed(cls, sizes, *, grouping=True, can_shard=False, scheduler=True) -> bool:
    """Whether a queue holding jobs of `sizes` sets, class `cls`, holds
    all of the package it would give."""
    from lodestar_tpu.chain.bls.pool import _Job

    rig = FakeLaneRig(1, with_prepared=True, with_sharded=can_shard)
    rig.mesh.grouping_available = lambda: grouping

    async def go():
        pool = BlsDeviceVerifierPool(mesh=rig.mesh, scheduler_enabled=scheduler)
        for i, n in enumerate(sizes):
            pool._enqueue(_Job(_sets(n, tag=i), False, cls))
        formed = pool._package_formed()
        package = (await pool._next_package())[0] if sizes else []
        await pool.close()
        return formed, [len(j.sets) for j in package]

    return _run(go())


@pytest.mark.parametrize(
    "cls, sizes, kwargs, formed, package",
    [
        (PriorityClass.API, [], {}, False, []),
        (PriorityClass.API, [128, 128, 128], {}, False, [128, 128, 128]),
        (PriorityClass.API, [128, 128, 128, 100, 20], {}, False, [128, 128, 128, 100, 20]),
        (PriorityClass.API, [128, 128, 128, 128, 40], {}, True, [128, 128, 128, 128]),
        (PriorityClass.GOSSIP_BLOCK, [66, 65] * 4, {}, True, [66, 65] * 4),
        (PriorityClass.GOSSIP_BLOCK, [66, 65] * 3, {}, False, [66, 65] * 3),
        (PriorityClass.RANGE_SYNC, [10, 10], {}, True, [10]),
        (PriorityClass.RANGE_SYNC, [128, 128, 128], {}, False, [128, 128, 128]),
        (PriorityClass.RANGE_SYNC, [128] * 5, {}, True, [128] * 4),
        (PriorityClass.RANGE_SYNC, [128, 128, 10, 128], {}, True, [128, 128]),
        (PriorityClass.RANGE_SYNC, [128, 128], {"grouping": False}, True, [128]),
        (PriorityClass.BACKFILL, [128, 128], {"can_shard": True}, True, [128]),
        (PriorityClass.RANGE_SYNC, [128] * 5, {"scheduler": False}, False, [128] * 5),
    ],
    ids=["empty", "api-open", "api-open-below-the-cap", "api-full", "block-wave-full",
         "block-wave-open", "bulk-small-job-alone", "bulk-group-open", "bulk-group-full",
         "bulk-group-closed-by-a-small-job", "bulk-no-grouping", "bulk-can-shard", "fifo-never"],
)
def test_a_package_is_formed_when_no_arrival_can_join_it(cls, sizes, kwargs, formed, package):
    """`_package_formed()` is what lets the dispatcher take a package
    ahead of a free lane: true only where `_next_package()` would give
    the same package however long it waited."""
    assert _formed(cls, sizes, **kwargs) == (formed, package)


def test_staged_hand_over_under_thread_switching_stress():
    """Prep thread, launch threads and the loop hand units to each other
    under a 10 us switch interval: every job gets its own verdict, every
    launch went through the staged seam or the inline road and never
    both, and the hidden parse never exceeds the parse."""
    import sys

    rig = FakeLaneRig(2, with_prepared=True, with_sharded=False)
    rig.verdict_fn = lambda sets: all(s.message[1] != 13 for s in sets)
    rng = random.Random(7)
    tags = [13 if rng.random() < 0.2 else i % 11 for i in range(240)]
    classes = [rng.choice(list(PriorityClass)) for _ in tags]

    async def go():
        pool = BlsDeviceVerifierPool(mesh=rig.mesh, scheduler_enabled=True, prep_fn=FakeLaneRig.prep_fn)
        futs = []
        for i, (tag, cls) in enumerate(zip(tags, classes)):
            futs.append(asyncio.ensure_future(pool.verify_signature_sets(
                _sets(1 + i % 3, tag=tag), VerifySignatureOpts(batchable=i % 4 == 0, priority=cls))))
            if i % 16 == 15:
                await asyncio.sleep(0.001)  # arrivals while packages are in hand and in flight
        got = await asyncio.wait_for(asyncio.gather(*futs), timeout=60)
        metrics, stats = dict(pool.metrics), pool.pipeline_stats()
        await pool.close()
        return got, metrics, stats

    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        got, metrics, stats = _run(go())
    finally:
        sys.setswitchinterval(prev)
    assert got == [tag != 13 for tag in tags]
    assert stats["staged_packages"] > 0 and rig.prepared_calls
    assert 0 <= metrics["parse_hidden_ns"] <= metrics["parse_ns"]
    assert metrics["jobs_started"] == len(tags) and metrics["errors"] == 0


def test_close_with_a_unit_half_staged_strands_no_future():
    """close() while the package in hand is still in its parse: its
    futures fail, the launch in flight resolves, nothing is left."""
    rig = _host_parse_rig(call_s=0.1)
    parsing = threading.Event()

    def slow_prep(sets, lane_hint):
        parsing.set()
        time.sleep(0.15)
        return FakeLaneRig.prep_fn(sets, lane_hint)

    async def go():
        pool = BlsDeviceVerifierPool(mesh=rig.mesh, scheduler_enabled=True, prep_fn=slow_prep)
        futs = [
            asyncio.ensure_future(pool.verify_signature_sets(
                _sets(1, tag=i), VerifySignatureOpts(batchable=False, priority=PriorityClass.RANGE_SYNC)))
            for i in range(4)
        ]
        while not parsing.is_set():
            await asyncio.sleep(0.005)
        await pool.close()
        results = await asyncio.wait_for(asyncio.gather(*futs, return_exceptions=True), timeout=5)
        return futs, results

    futs, results = _run(go())
    assert all(f.done() for f in futs)
    assert len(rig.calls) == 1 and rig.prepared_calls == []  # the launch in flight; nothing staged was launched
    assert all(isinstance(r, asyncio.CancelledError) for r in results[1:]), results


# -- review regressions --------------------------------------------------------


def test_mesh_launch_drops_staged_inputs_on_cross_lane_retry():
    """An error on a staged-inputs attempt may be input-bound, so the
    cross-lane retry must re-prep inline (verify_fn) instead of feeding
    every sibling the same poisoned inputs until the whole mesh wedges."""
    from lodestar_tpu.chain.bls.mesh import (
        MeshLane,
        PreparedSets,
        VerifierMesh,
        mesh_launch,
    )

    calls = []

    def l0_prepared(inputs):
        calls.append("l0-prepared")
        raise RuntimeError("poisoned staged inputs")

    def l0_plain(sets):
        calls.append("l0-plain")
        raise RuntimeError("unreachable on this path")

    def l1_prepared(inputs):
        calls.append("l1-prepared")
        return True

    def l1_plain(sets):
        calls.append("l1-plain")
        return True

    lanes = [
        MeshLane(0, l0_plain, verify_prepared_fn=l0_prepared),
        MeshLane(1, l1_plain, verify_prepared_fn=l1_prepared),
    ]
    mesh = VerifierMesh(lanes)
    ok, served = mesh_launch(
        mesh, _sets(1), prefer=lanes[0], prepared=PreparedSets(inputs=("staged",))
    )
    assert ok is True and served is lanes[1]
    assert calls == ["l0-prepared", "l1-plain"]


def test_dead_dispatcher_restarts_on_next_submit_and_fails_what_it_held():
    """A dispatcher that dies with a package in hand fails that
    package's futures (nobody else can see it) and the next submit
    starts a new one."""
    rig = _host_parse_rig(call_s=0.3)

    async def go():
        pool = BlsDeviceVerifierPool(
            mesh=rig.mesh,
            scheduler_enabled=True,
            prep_fn=FakeLaneRig.prep_fn,
        )
        futs = [
            asyncio.ensure_future(pool.verify_signature_sets(
                _sets(1, tag=i), VerifySignatureOpts(batchable=False, priority=PriorityClass.RANGE_SYNC)))
            for i in range(2)
        ]
        await asyncio.sleep(0.02)  # 0 in flight, 1 in the dispatcher's hand
        pool._runner.cancel()
        first = await asyncio.gather(*futs, return_exceptions=True)
        assert pool._runner.done()
        ok = await pool.verify_signature_sets(
            _sets(1, tag=2), VerifySignatureOpts(batchable=False)
        )
        await pool.close()
        return first, ok

    first, ok = _run(go())
    assert first[0] is True and isinstance(first[1], asyncio.CancelledError)
    assert ok is True


# -- live pipeline gauges (lodestar_bls_pipeline_*) ----------------------------


def test_pipeline_gauges_fresh_after_replay():
    """The pool's pipeline_stats() numbers are live Prometheus gauges
    (scrape-time set_function): after a pipelined replay the staged-
    package and busy-seconds gauges read nonzero WITHOUT any explicit
    refresh call — the satellite contract that un-traps the stats."""
    from lodestar_tpu.metrics import create_metrics

    m = create_metrics()
    rig = _host_parse_rig(call_s=0.05)

    def slow_prep(sets, lane_hint):
        time.sleep(0.03)
        return FakeLaneRig.prep_fn(sets, lane_hint)

    async def go():
        pool = BlsDeviceVerifierPool(
            mesh=rig.mesh,
            scheduler_enabled=True,
            prep_fn=slow_prep,
            pipeline_metrics=m.bls_pipeline,
        )
        jobs = []
        for i in range(4):
            jobs.append(
                asyncio.ensure_future(
                    pool.verify_signature_sets(
                        # a bulk class: a job is a package, taken ahead of the busy lane
                        _sets(1, tag=i),
                        VerifySignatureOpts(batchable=False, priority=PriorityClass.RANGE_SYNC),
                    )
                )
            )
            await asyncio.sleep(0.015)
        ok = await asyncio.gather(*jobs)
        await pool.close()
        return ok

    assert all(_run(go()))

    def gauge(name):
        for fam in m.creator.registry.collect():
            for s in fam.samples:
                if s.name == name:
                    return s.value
        raise AssertionError(f"gauge {name} not found")

    assert gauge("lodestar_bls_pipeline_staged_packages") >= 2
    assert gauge("lodestar_bls_pipeline_prep_seconds_total") > 0.0
    assert gauge("lodestar_bls_pipeline_verify_seconds_total") > 0.0
    # overlap percent is well-defined (the replay above overlaps, but
    # scheduling noise may land it anywhere in (0, 100])
    assert 0.0 <= gauge("lodestar_bls_pipeline_overlap_occupancy_pct") <= 100.0


def test_pipeline_gauges_read_zero_when_pipeline_never_engaged():
    """A pool that does not stage (one lane of the split schedule) keeps
    all four gauges at their zero/no-engagement values — the dashboard's
    '0 staged packages = never engaged' read is trustworthy."""
    from lodestar_tpu.metrics import create_metrics

    m = create_metrics()
    rig = FakeLaneRig(1, with_prepared=True, with_sharded=False)

    async def go():
        pool = BlsDeviceVerifierPool(
            mesh=rig.mesh,
            scheduler_enabled=True,
            pipeline_metrics=m.bls_pipeline,
        )
        ok = await pool.verify_signature_sets(
            _sets(2), VerifySignatureOpts(batchable=False)
        )
        await pool.close()
        return ok

    assert _run(go()) is True

    def gauge(name):
        for fam in m.creator.registry.collect():
            for s in fam.samples:
                if s.name == name:
                    return s.value
        raise AssertionError(f"gauge {name} not found")

    assert gauge("lodestar_bls_pipeline_staged_packages") == 0
    assert gauge("lodestar_bls_pipeline_prep_seconds_total") == 0.0
    # verify busy time accrues even unpipelined (the tracker wraps every
    # verify path) — only the PIPELINE legs must stay silent
    assert gauge("lodestar_bls_pipeline_overlap_occupancy_pct") == 0.0

"""Multi-job launches in the verifier pool: a package's non-batchable
jobs of the 128 size class ride ONE launch, a verdict each. Injected
lanes, no device program: the lane's grouped entry records the jobs it
was handed and answers from a table."""

from __future__ import annotations

import asyncio
import threading
import time

import pytest

from lodestar_tpu.chain.bls import BlsDeviceVerifierPool, VerifySignatureOpts
from lodestar_tpu.chain.bls.mesh import MeshLane, VerifierMesh
from lodestar_tpu.chain.bls.pool import (
    MAX_GROUP_JOBS,
    MAX_PACKAGE_SETS,
    MAX_SIGNATURE_SETS_PER_JOB,
    _Job,
    _launch_units,
)
from lodestar_tpu.crypto.bls.api import SignatureSet
from lodestar_tpu.models import batch_verify as bv
from lodestar_tpu.scheduler import PriorityClass


def _sets(n: int, tag: int = 0) -> list[SignatureSet]:
    return [
        SignatureSet(
            pubkey=bytes([1, tag, i % 256]) + bytes(45),
            message=bytes([2, tag, i % 256]) * 8 + bytes(8),
            signature=bytes([3, tag, i % 256]) + bytes(93),
        )
        for i in range(n)
    ]


def _tag(sets) -> int:
    return sets[0].pubkey[1]


class Rig:
    """One lane that speaks sets and jobs, as the single launch's lane
    does: the pool groups because the lane has the grouped entry.
    `launches` lists every call: ("single", [n_sets]) or ("grouped",
    [n_sets of each job]). A job is invalid where its tag is in `bad`.
    `prepared` gives the lane the staged-inputs seam, and with it the
    single launch's other fact: what is staged for it touches no
    device, so the one-lane pool stages."""

    def __init__(
        self,
        bad=(),
        grouped_error: Exception | None = None,
        hold: threading.Event | None = None,
        prepared=None,
    ):
        self.bad = set(bad)
        self.grouped_error = grouped_error
        self.hold = hold
        self.launches: list[tuple[str, list[int]]] = []
        self.tags: list[list[int]] = []
        self.mesh = VerifierMesh(
            [
                MeshLane(
                    0,
                    self.verify,
                    verify_grouped_fn=self.verify_grouped,
                    verify_prepared_fn=prepared,
                    staged_prep_host_only=prepared is not None,
                    wedge_threshold=8,
                )
            ]
        )

    def verify(self, sets) -> bool:
        if self.hold is not None:
            self.hold.wait(5)
        self.launches.append(("single", [len(sets)]))
        self.tags.append([_tag(sets)])
        return _tag(sets) not in self.bad

    def verify_grouped(self, jobs) -> list[bool]:
        if self.hold is not None:
            self.hold.wait(5)
        if self.grouped_error is not None:
            raise self.grouped_error
        self.launches.append(("grouped", [len(j) for j in jobs]))
        self.tags.append([_tag(j) for j in jobs])
        return [_tag(j) not in self.bad for j in jobs]


def _run(coro):
    return asyncio.run(coro)


async def _submit(pool, sets, priority, batchable=False):
    return await pool.verify_signature_sets(
        sets, VerifySignatureOpts(priority=priority, batchable=batchable)
    )


# -- the former ----------------------------------------------------------------


def _jobs(sizes, batchable=()):
    async def make():
        return [
            _Job(_sets(n, tag=i), i in batchable, PriorityClass.API) for i, n in enumerate(sizes)
        ]

    return _run(make())


@pytest.mark.parametrize(
    "sizes, batchable, want",
    [
        ((66, 65), (), [[0, 1]]),
        ((66, 65, 70), (), [[0, 1, 2]]),
        ((128, 128, 128, 128), (), [[0, 1, 2, 3]]),
        ((66,) * 6, (), [[0, 1, 2, 3], [4, 5]]),
        ((66,), (), [[0]]),
        ((64, 10, 1), (), [[0], [1], [2]]),
        ((66, 10, 65, 64, 70), (), [[0, 2, 4], [1], [3]]),
        ((66, 65, 100), (2,), [[0, 1]]),
    ],
    ids=["block", "three", "sync-committee", "six", "alone", "small", "mixed", "batchable-apart"],
)
def test_launch_units_group_128_class_jobs_in_queue_order(sizes, batchable, want):
    jobs = _jobs(sizes, batchable)
    chunks, units = _launch_units(jobs, grouping=True)
    assert [[jobs.index(j) for j in unit] for unit in units] == want
    assert [j for chunk in chunks for j in chunk] == [jobs[i] for i in sorted(batchable)]
    assert all(len(unit) <= MAX_GROUP_JOBS for unit in units)


def test_launch_units_without_grouping_are_one_job_each():
    jobs = _jobs((66, 65, 128, 3))
    _, units = _launch_units(jobs, grouping=False)
    assert units == [[j] for j in jobs]


def test_group_limit_follows_the_package_cap():
    assert MAX_GROUP_JOBS == MAX_PACKAGE_SETS // MAX_SIGNATURE_SETS_PER_JOB == 4


@pytest.mark.parametrize(
    "sizes, slot",
    [
        ((65,), 72), ((66,), 72), ((72,), 72), ((73,), 128), ((100,), 128), ((128,), 128),
        ((66, 65), 72), ((66, 100), 128), ((66, 65, 66, 65), 72), ((72, 72, 72, 73), 128),
        ((1,), None), ((33,), None), ((64,), None), ((129,), None),
    ],
    ids=str,
)
def test_the_slot_of_the_jobs_that_may_share_a_launch(sizes, slot):
    """The former's rule and the slot rule, side by side: jobs of the 128
    class share a launch, in slots of 72 rows where every one of them
    fits and of 128 otherwise; a job of 64 sets or fewer (or more than a
    job may hold) shares none, so the rung is asked about no other."""
    from lodestar_tpu import telemetry
    from lodestar_tpu.chain.bls.pool import _groupable

    jobs = _jobs(sizes)
    assert [_groupable(j) for j in jobs] == [slot is not None] * len(jobs)
    assert not _groupable(_jobs(sizes, batchable=(0,))[0])
    if slot is not None:
        assert telemetry.group_slot_rows(len(j.sets) for j in jobs) == slot
        assert slot in (telemetry.GROUP_SLOT_RUNG, MAX_SIGNATURE_SETS_PER_JOB)


# -- a gossip block: one launch, a verdict a job ---------------------------------


@pytest.mark.parametrize("bad, want", [((0,), [False, True]), ((1,), [True, False]), ((), [True, True])],
                         ids=["first-bad", "last-bad", "honest"])
def test_block_jobs_ride_one_launch_with_their_own_verdicts(bad, want):
    rig = Rig(bad=bad)

    async def go():
        pool = BlsDeviceVerifierPool(mesh=rig.mesh)
        jobs = [
            asyncio.ensure_future(_submit(pool, _sets(n, tag=i), PriorityClass.GOSSIP_BLOCK))
            for i, n in enumerate((66, 65))
        ]
        got = await asyncio.gather(*jobs)
        await pool.close()
        return got, dict(pool.metrics)

    got, metrics = _run(go())
    assert got == want
    assert rig.launches == [("grouped", [66, 65])]
    assert metrics["jobs_started"] == 2 and metrics["sig_sets_started"] == 131
    assert rig.mesh.lanes[0].launches == 1


def test_one_call_of_131_sets_is_one_launch_and_the_and_of_its_jobs():
    rig = Rig(bad=(0,))  # both jobs carry tag 0's sets; the cut is 66 + 65

    async def go():
        pool = BlsDeviceVerifierPool(mesh=rig.mesh)
        got = await _submit(pool, _sets(131), PriorityClass.GOSSIP_BLOCK)
        await pool.close()
        return got

    assert _run(go()) is False
    assert rig.launches == [("grouped", [66, 65])]


def test_three_jobs_ride_one_unit_and_nothing_else_resolves():
    rig = Rig(bad=(1,))

    async def go():
        pool = BlsDeviceVerifierPool(mesh=rig.mesh)
        futs = [
            asyncio.ensure_future(_submit(pool, _sets(n, tag=i), PriorityClass.API))
            for i, n in enumerate((66, 65, 100))
        ]
        got = await asyncio.gather(*futs)
        await pool.close()
        return got

    assert _run(go()) == [True, False, True]
    assert rig.launches == [("grouped", [66, 65, 100])]


def test_small_and_batchable_jobs_keep_todays_units():
    rig = Rig()

    async def go():
        pool = BlsDeviceVerifierPool(mesh=rig.mesh, buffer_wait_ms=1)
        futs = [
            asyncio.ensure_future(_submit(pool, _sets(n, tag=i), PriorityClass.API))
            for i, n in enumerate((64, 10))
        ]
        futs.append(asyncio.ensure_future(
            _submit(pool, _sets(100, tag=7), PriorityClass.GOSSIP_ATTESTATION, batchable=True)))
        got = await asyncio.gather(*futs)
        await pool.close()
        return got

    assert _run(go()) == [True, True, True]
    assert sorted(rig.launches) == [("single", [10]), ("single", [64]), ("single", [100])]


def test_lanes_without_a_grouped_entry_keep_todays_units():
    calls = []

    def backend(sets):
        calls.append(len(sets))
        return True

    async def go():
        pool = BlsDeviceVerifierPool(backend)
        got = await _submit(pool, _sets(131), PriorityClass.GOSSIP_BLOCK)
        await pool.close()
        return got

    assert _run(go()) is True
    assert calls == [66, 65]


# -- bulk: a package is one launch -----------------------------------------------


def _bulk(sizes):
    """Queue bulk jobs behind a held first launch, release, return the
    launches in order."""
    hold = threading.Event()
    rig = Rig(hold=hold)

    async def go():
        pool = BlsDeviceVerifierPool(mesh=rig.mesh)
        futs = [
            asyncio.ensure_future(_submit(pool, _sets(n, tag=i), PriorityClass.BACKFILL))
            for i, n in enumerate(sizes)
        ]
        await asyncio.sleep(0.05)
        hold.set()
        got = await asyncio.gather(*futs)
        await pool.close()
        return got

    assert all(_run(go()))
    return rig


def test_bulk_package_of_128_class_jobs_fills_to_four_and_never_beyond():
    rig = _bulk((66,) * 10)
    assert rig.launches == [("grouped", [66] * 4), ("grouped", [66] * 4), ("grouped", [66] * 2)]
    assert all(sum(sizes) <= MAX_PACKAGE_SETS for _, sizes in rig.launches)
    assert rig.tags == [[0, 1, 2, 3], [4, 5, 6, 7], [8, 9]]


def test_bulk_queue_of_small_jobs_is_still_one_job_a_package():
    rig = _bulk((8,) * 6)
    assert rig.launches == [("single", [8])] * 6


def test_bulk_fill_stops_at_the_first_job_that_is_not_groupable():
    rig = _bulk((66, 65, 8, 70, 70))
    assert rig.launches == [
        ("grouped", [66, 65]), ("single", [8]), ("grouped", [70, 70]),
    ]


def test_a_small_bulk_head_job_takes_nothing_with_it():
    rig = _bulk((8, 66, 65))
    assert rig.launches == [("single", [8]), ("grouped", [66, 65])]


def test_bulk_stays_one_job_a_package_where_the_mesh_can_shard():
    rig = Rig()
    rig.mesh.sharded_fn = lambda sets, devices: True  # one lane never shards; the former only asks

    async def go():
        pool = BlsDeviceVerifierPool(mesh=rig.mesh)
        futs = [
            asyncio.ensure_future(_submit(pool, _sets(66, tag=i), PriorityClass.RANGE_SYNC))
            for i in range(3)
        ]
        got = await asyncio.gather(*futs)
        await pool.close()
        return got

    assert all(_run(go()))
    assert rig.launches == [("single", [66])] * 3


# -- errors ----------------------------------------------------------------------


def test_an_error_in_the_grouped_call_fails_its_jobs_closed_and_counts_on_the_breaker():
    rig = Rig(grouped_error=RuntimeError("injected lane fault"))

    async def go():
        pool = BlsDeviceVerifierPool(mesh=rig.mesh)
        futs = [
            asyncio.ensure_future(_submit(pool, _sets(n, tag=i), PriorityClass.GOSSIP_BLOCK))
            for i, n in enumerate((66, 65))
        ]
        got = await asyncio.gather(*futs, return_exceptions=True)
        await pool.close()
        return got

    got = _run(go())
    assert all(isinstance(e, RuntimeError) for e in got), got
    lane = rig.mesh.lanes[0]
    assert lane.launches == 1 and not lane.wedged


def test_a_grouped_call_that_answers_short_fails_closed():
    rig = Rig()
    rig.mesh.lanes[0].verify_grouped_fn = lambda jobs: [True]

    async def go():
        pool = BlsDeviceVerifierPool(mesh=rig.mesh)
        futs = [
            asyncio.ensure_future(_submit(pool, _sets(n, tag=i), PriorityClass.GOSSIP_BLOCK))
            for i, n in enumerate((66, 65))
        ]
        got = await asyncio.gather(*futs, return_exceptions=True)
        await pool.close()
        return got

    assert all(isinstance(e, RuntimeError) for e in _run(go()))


def test_grouped_launch_retries_on_a_sibling_lane():
    served = []

    def sick(jobs):
        raise RuntimeError("sick die")

    def healthy(jobs):
        served.append([len(j) for j in jobs])
        return [True] * len(jobs)

    mesh = VerifierMesh([
        MeshLane(0, lambda sets: True, verify_grouped_fn=sick),
        MeshLane(1, lambda sets: True, verify_grouped_fn=healthy),
    ])

    async def go():
        pool = BlsDeviceVerifierPool(mesh=mesh)
        got = await _submit(pool, _sets(131), PriorityClass.GOSSIP_BLOCK)
        await pool.close()
        return got

    assert _run(go()) is True
    assert served == [[66, 65]]
    assert sum(lane.launches for lane in mesh.lanes) == 2


# -- the ledger entry --------------------------------------------------------------


@pytest.mark.parametrize(
    "sizes, rows",
    [((66, 65), 144), ((66, 65, 70), 288), ((66, 65, 66, 65), 288), ((72,) * 4, 288),
     ((66, 100), 256), ((73, 66, 65), 512), ((128,) * 4, 512)],
    ids=["block", "three", "two-blocks", "four-at-the-rung", "one-longer", "three-one-longer", "sync-committee"],
)
def test_grouped_launch_is_one_ledger_entry_with_the_launch_rows_as_its_class(sizes, rows):
    """The ledger's label is written where no array is in sight, from
    the slot rule; the host stage lays the arrays out by the same rule:
    the label is the rows of what the lane dispatched."""
    from lodestar_tpu import telemetry
    from lodestar_tpu.chain.bls.mesh import mesh_launch

    dispatched = []

    def grouped(jobs):
        gi = bv.prepare_grouped_launch_inputs(jobs)
        dispatched.append({a.shape[0] for a in (*gi.arrays, gi.bits, gi.mask)})
        return [True] * len(jobs)

    mesh = VerifierMesh([MeshLane(0, lambda sets: True, verify_grouped_fn=grouped)])
    telemetry.reset_launch_telemetry()
    telemetry.configure_launch_telemetry("on")
    try:
        ok, lane = mesh_launch(mesh, [_sets(n, tag=i) for i, n in enumerate(sizes)], grouped=True)
        entries = [e for e in telemetry.launch_ledger() if e["program"] == "bls_lane_verify"]
    finally:
        telemetry.reset_launch_telemetry()
    assert ok == [True] * len(sizes) and lane is mesh.lanes[0]
    assert [e["size_class"] for e in entries] == [rows]
    assert dispatched == [{rows}]


# -- the staged pipeline forms the same units --------------------------------------


def test_prep_package_and_verify_package_form_identical_units(monkeypatch):
    staged_jobs = []

    def fake_grouped_prep(job_sets, table=None):
        staged_jobs.append([len(s) for s in job_sets])
        return ("grouped-inputs", [_tag(s) for s in job_sets])

    monkeypatch.setattr(bv, "prepare_grouped_launch_inputs", fake_grouped_prep)
    prepared_seen = []

    def verify_prepared(inputs):
        prepared_seen.append(inputs)
        return [True] * len(inputs[1]) if inputs[0] == "grouped-inputs" else True

    rig = Rig(prepared=verify_prepared)

    async def go():
        pool = BlsDeviceVerifierPool(
            mesh=rig.mesh, prep_fn=lambda sets, hint: ("inputs", [_tag(sets)])
        )
        package = [_Job(_sets(n, tag=i), False, PriorityClass.API) for i, n in enumerate((66, 10, 65, 70))]
        prepped = pool._stage(package, PriorityClass.API)
        _, units = _launch_units(package, grouping=True)
        assert [u.jobs for u in prepped.units] == units
        pool._verify_package(package, rig.mesh.lanes[0], prepped=prepped)
        await asyncio.sleep(0.01)
        got = [j.future.result() for j in package]
        inline = Rig()
        pool_inline = BlsDeviceVerifierPool(mesh=inline.mesh)
        pool_inline._verify_package(package, inline.mesh.lanes[0], counted=True)
        await pool.close()
        await pool_inline.close()
        return got, inline

    got, inline = _run(go())
    assert got == [True] * 4
    assert staged_jobs == [[66, 65, 70]]
    # staged: the grouped unit, then the small job; unstaged: the same two launches
    assert prepared_seen == [("grouped-inputs", [0, 2, 3]), ("inputs", [1])]
    assert inline.launches == [("grouped", [66, 65, 70]), ("single", [10])]
    assert rig.launches == []  # every staged unit went through verify_prepared_fn


def test_the_staged_pipeline_serves_a_bulk_group_with_one_staged_multi_job_launch(monkeypatch):
    """Four bulk jobs of the 128 class queued behind a held launch: the
    queue holds all of their package, so it is taken ahead of the lane
    and its one unit, a multi-job launch, is staged — through the
    pool's own prep road, which hands a multi-job unit to
    `prepare_grouped_launch_inputs`."""
    monkeypatch.setattr(
        bv, "prepare_grouped_launch_inputs", lambda job_sets, table=None: ("grouped-inputs", [len(s) for s in job_sets])
    )
    prepared_seen = []

    def verify_prepared(inputs):
        prepared_seen.append(inputs)
        return [True, False, True, True]

    hold = threading.Event()
    rig = Rig(hold=hold, prepared=verify_prepared)

    async def go():
        pool = BlsDeviceVerifierPool(mesh=rig.mesh)
        futs = [
            asyncio.ensure_future(_submit(pool, _sets(n, tag=i), PriorityClass.RANGE_SYNC))
            for i, n in enumerate((10, 66, 65, 66, 65))
        ]
        while pool.pipeline_stats()["staged_packages"] == 0:
            await asyncio.sleep(0.005)
        hold.set()
        got = await asyncio.gather(*futs)
        stats = pool.pipeline_stats()
        await pool.close()
        return got, stats

    got, stats = _run(go())
    assert got == [True, True, False, True, True]
    # the first job found the lane free (inline); the group behind it was staged
    assert rig.launches == [("single", [10])]
    assert prepared_seen == [("grouped-inputs", [66, 65, 66, 65])]
    assert stats["pipeline_enabled"] and stats["staged_packages"] == 1


# -- staging is per launch unit: the parse of unit i+1 rides the launch of unit i ---


class StagedRig(Rig):
    """A `Rig` whose lane also takes staged inputs (unless `stages` is
    False: the control arm, the same lane without the seam), with a parse and a
    launch that take a while. The staged parse of a unit hands the
    lane what the unstaged entries are handed, so `launches` and `tags`
    read the same on both roads; `events` orders parse and launch
    starts and ends as they happened."""

    def __init__(self, monkeypatch, bad=(), parse_s=0.0, launch_s=0.0, stages=True):
        super().__init__(bad=bad, prepared=self.verify_prepared if stages else None)
        self.parse_s, self.launch_s = parse_s, launch_s
        self.events: list[tuple[str, int]] = []
        self._lock = threading.Lock()
        monkeypatch.setattr(bv, "prepare_grouped_launch_inputs", lambda jobs, table=None: self.parse("grouped", jobs))

    def note(self, what: str, first_tag: int) -> None:
        with self._lock:
            self.events.append((what, first_tag))

    def parse(self, kind: str, payload):
        first = _tag(payload[0]) if kind == "grouped" else _tag(payload)
        self.note("parse-start", first)
        time.sleep(self.parse_s)
        self.note("parse-end", first)
        return kind, payload

    def prep_fn(self, sets, lane_hint):
        return self.parse("single", sets)

    def verify_prepared(self, inputs):
        kind, payload = inputs
        return self.verify_grouped(payload) if kind == "grouped" else self.verify(payload)

    def verify(self, sets) -> bool:
        self.note("launch-start", _tag(sets))
        time.sleep(self.launch_s)
        self.note("launch-end", _tag(sets))
        # a chunk holds several jobs' sets: one bad set fails the batch
        return super().verify(sets) and not {s.pubkey[1] for s in sets} & self.bad

    def verify_grouped(self, jobs) -> list[bool]:
        self.note("launch-start", _tag(jobs[0]))
        time.sleep(self.launch_s)
        self.note("launch-end", _tag(jobs[0]))
        return super().verify_grouped(jobs)


def test_the_second_units_parse_rides_the_first_units_launch(monkeypatch):
    """Eight jobs of the 128 class in one latency-class package (four
    tenants' blocks): two units of four. The package is staged though
    it finds the lane free, because its second unit has the first to
    hide behind: the second parse starts and ends inside the first
    launch, and the second launch follows the first with no parse in
    between."""
    rig = StagedRig(monkeypatch, parse_s=0.03, launch_s=0.15)

    async def go():
        pool = BlsDeviceVerifierPool(mesh=rig.mesh, prep_fn=rig.prep_fn)
        futs = [
            asyncio.ensure_future(_submit(pool, _sets(66, tag=i), PriorityClass.GOSSIP_BLOCK))
            for i in range(8)
        ]
        got = await asyncio.gather(*futs)
        stats, metrics = pool.pipeline_stats(), dict(pool.metrics)
        await pool.close()
        return got, stats, metrics

    got, stats, metrics = _run(go())
    assert got == [True] * 8
    assert rig.launches == [("grouped", [66] * 4)] * 2 and rig.tags == [[0, 1, 2, 3], [4, 5, 6, 7]]
    at = {event: i for i, event in enumerate(rig.events)}
    assert len(at) == len(rig.events) == 8
    assert at["parse-end", 0] < at["launch-start", 0]  # only the first unit is waited for
    assert at["parse-end", 0] < at["parse-start", 4] < at["parse-end", 4] < at["launch-end", 0]
    assert at["launch-start", 4] == at["launch-end", 0] + 1  # no parse between the launches
    assert stats["staged_packages"] == 1
    # one parse of the two had a launch to hide behind
    assert 0 < metrics["parse_hidden_ns"] <= metrics["parse_ns"]
    assert 0.2 < metrics["parse_hidden_ns"] / metrics["parse_ns"] < 0.8


def test_a_block_that_finds_the_lane_free_keeps_the_inline_road(monkeypatch):
    """One caller, one block, the next only after the verdict: a
    package of one unit that finds the lane free has no launch to hide
    its parse behind, and staging it would put two thread hops into
    every verdict. It is launched as a pool that cannot stage launches
    it."""
    rig = StagedRig(monkeypatch, parse_s=0.01, launch_s=0.02)

    async def go():
        pool = BlsDeviceVerifierPool(mesh=rig.mesh, prep_fn=rig.prep_fn)
        got = [await _submit(pool, _sets(131, tag=i), PriorityClass.GOSSIP_BLOCK) for i in range(3)]
        stats, metrics = pool.pipeline_stats(), dict(pool.metrics)
        await pool.close()
        return got, stats, metrics

    got, stats, metrics = _run(go())
    assert got == [True] * 3
    assert stats["pipeline_enabled"] is True and stats["staged_packages"] == 0
    assert rig.launches == [("grouped", [66, 65])] * 3
    assert not any(what.startswith("parse") for what, _ in rig.events)
    assert metrics["parse_ns"] == 0 == metrics["parse_hidden_ns"]


@pytest.mark.parametrize(
    "priority, batchable_share",
    [(PriorityClass.GOSSIP_BLOCK, 0.0), (PriorityClass.RANGE_SYNC, 0.0), (PriorityClass.GOSSIP_ATTESTATION, 0.5)],
    ids=["latency-class-packages", "bulk-one-launch-packages", "with-batchable-chunks"],
)
def test_staged_replay_launches_the_unpipelined_pools_units_in_its_order(monkeypatch, priority, batchable_share):
    """Seeded replay, one class, everything queued behind a first
    launch, a quarter of the jobs planted bad: the launch sequence
    (units, sizes, order) and every job's verdict are those of a pool
    whose lane takes no staged inputs. Only where the parse ran differs."""
    import random

    def replay(stages: bool):
        rng = random.Random(20301)
        bad = {i for i in range(30) if rng.random() < 0.25}
        rig = StagedRig(monkeypatch, bad=bad, launch_s=0.005, stages=stages)

        async def go():
            pool = BlsDeviceVerifierPool(  # a batchable job goes to the queue as it comes
                mesh=rig.mesh, prep_fn=rig.prep_fn if stages else None, max_buffered_sigs=0
            )
            futs = [
                asyncio.ensure_future(_submit(
                    pool, _sets(rng.choice((8, 66, 65, 100, 128)), tag=i), priority,
                    batchable=rng.random() < batchable_share,
                ))
                for i in range(30)
            ]
            got = await asyncio.gather(*futs)
            stats = pool.pipeline_stats()
            await pool.close()
            return got, stats

        got, stats = _run(go())
        return got, rig.launches, rig.tags, stats, bad

    got, launches, tags, stats, bad = replay(True)
    got_off, launches_off, tags_off, stats_off, _ = replay(False)
    assert got == got_off == [i not in bad for i in range(30)]
    assert (launches, tags) == (launches_off, tags_off)
    assert stats["staged_packages"] > 0 == stats_off["staged_packages"]

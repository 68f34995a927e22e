"""Acceptance: priority inversion eliminated on a saturated backend.

With a slow fake backend and queued backfill batches, a gossip-block
verify job's `sched_queue_wait` is bounded (it dequeues after at most
the one in-flight bulk package) while FIFO ordering — scheduler disabled
— makes it wait behind the entire bulk queue. And the graded Status
frame lets a two-endpoint `BlsOffloadClient` route bulk work away from a
SHED_BULK server while urgent work still flows.
"""

from __future__ import annotations

import asyncio
import time

import pytest

from lodestar_tpu import tracing
from lodestar_tpu.chain.bls import BlsDeviceVerifierPool, VerifySignatureOpts
from lodestar_tpu.crypto.bls.api import SignatureSet
from lodestar_tpu.scheduler import AdmissionState, PriorityClass

N_BULK = 6
SLOW_CALL_S = 0.02


def _sets(n: int, tag: int = 0) -> list[SignatureSet]:
    return [
        SignatureSet(
            pubkey=bytes([1, tag, i % 256]) + bytes(45),
            message=bytes([2, tag, i % 256]) * 8 + bytes(8),
            signature=bytes([3, tag, i % 256]) + bytes(93),
        )
        for i in range(n)
    ]


class SlowBackend:
    """Every launch takes SLOW_CALL_S — a saturated device."""

    def __init__(self):
        self.calls = 0

    def __call__(self, sets):
        self.calls += 1
        time.sleep(SLOW_CALL_S)
        return True


async def _saturate(pool: BlsDeviceVerifierPool) -> tuple[int, list[str]]:
    """Queue N_BULK backfill jobs, let the runner sink its teeth into the
    first package, then submit one gossip-block job. Returns the gossip
    job's completion rank and the full completion order."""
    done: list[str] = []

    async def submit(name: str, priority: PriorityClass):
        ok = await pool.verify_signature_sets(
            _sets(1, tag=hash(name) % 250), VerifySignatureOpts(priority=priority)
        )
        assert ok
        done.append(name)

    bulk = [
        asyncio.ensure_future(submit(f"backfill{i}", PriorityClass.BACKFILL))
        for i in range(N_BULK)
    ]
    # let the runner dequeue its first bulk package and block in the
    # executor on the slow backend before the urgent job arrives
    await asyncio.sleep(SLOW_CALL_S / 2)
    gossip = asyncio.ensure_future(submit("block", PriorityClass.GOSSIP_BLOCK))
    await asyncio.gather(*bulk, gossip)
    await pool.close()
    return done.index("block"), done


def test_scheduler_bounds_gossip_block_wait_under_backfill_load():
    async def go():
        pool = BlsDeviceVerifierPool(SlowBackend(), scheduler_enabled=True)
        rank, order = await _saturate(pool)
        # bounded: the block waits out at most the ONE in-flight bulk
        # package, never the queue — it finishes ahead of the other bulk
        assert rank <= 1, f"gossip block ranked {rank} in {order}"

    asyncio.run(go())


def test_gossip_block_waits_out_one_multi_job_bulk_launch():
    """The same case with 66-set backfill jobs on a lane that takes a
    package of them in ONE launch: four jobs ride the in-flight launch,
    and the block is served next, ahead of the queued rest."""
    from lodestar_tpu.chain.bls.mesh import MeshLane, VerifierMesh

    launches: list[list[int]] = []

    def slow_single(sets):
        launches.append([len(sets)])
        time.sleep(SLOW_CALL_S)
        return True

    def slow_grouped(jobs):
        launches.append([len(j) for j in jobs])
        time.sleep(SLOW_CALL_S)
        return [True] * len(jobs)

    done: list[str] = []

    async def submit(pool, name, n_sets, priority):
        assert await pool.verify_signature_sets(
            _sets(n_sets, tag=hash(name) % 250), VerifySignatureOpts(priority=priority)
        )
        done.append(name)

    async def go():
        mesh = VerifierMesh([MeshLane(0, slow_single, verify_grouped_fn=slow_grouped)])
        pool = BlsDeviceVerifierPool(mesh=mesh, scheduler_enabled=True)
        bulk = [
            asyncio.ensure_future(submit(pool, f"backfill{i}", 66, PriorityClass.BACKFILL))
            for i in range(N_BULK)
        ]
        await asyncio.sleep(SLOW_CALL_S / 2)
        block = asyncio.ensure_future(submit(pool, "block", 1, PriorityClass.GOSSIP_BLOCK))
        await asyncio.gather(*bulk, block)
        await pool.close()

    asyncio.run(go())
    # one bulk launch in flight when the block arrives, the block's next
    assert launches == [[66] * 4, [1], [66] * 2], launches
    assert done.index("block") == 4, done


def test_fifo_control_arm_shows_the_inversion():
    async def go():
        pool = BlsDeviceVerifierPool(SlowBackend(), scheduler_enabled=False)
        rank, order = await _saturate(pool)
        # FIFO: the block sits behind every queued backfill job
        assert rank == N_BULK, f"gossip block ranked {rank} in {order}"

    asyncio.run(go())


def test_sched_queue_wait_span_records_class_and_bound():
    tracer = tracing.configure(enabled=True, slow_slot_ms=60_000.0)

    async def go():
        pool = BlsDeviceVerifierPool(SlowBackend(), scheduler_enabled=True)
        bulk = []
        with tracing.root("bulk_submit", slot=7):
            bulk = [
                asyncio.ensure_future(
                    pool.verify_signature_sets(
                        _sets(1, tag=i),
                        VerifySignatureOpts(priority=PriorityClass.BACKFILL),
                    )
                )
                for i in range(N_BULK)
            ]
        await asyncio.sleep(SLOW_CALL_S / 2)
        with tracing.root("block_import", slot=8):
            assert await pool.verify_signature_sets(
                _sets(1, tag=99), VerifySignatureOpts(priority=PriorityClass.GOSSIP_BLOCK)
            )
        await asyncio.gather(*bulk)
        await pool.close()

    asyncio.run(go())
    (block_trace,) = tracer.traces_for_slot(8)
    waits = [s for s in block_trace.spans if s.name == "sched_queue_wait"]
    assert waits, "gossip job must record its sched_queue_wait span"
    assert waits[0].attrs["class"] == "gossip_block"
    # bounded by the one in-flight bulk launch (generous CI margin)
    assert waits[0].duration_ms <= SLOW_CALL_S * 1000 * 3
    (bulk_trace,) = tracer.traces_for_slot(7)
    bulk_waits = [s for s in bulk_trace.spans if s.name == "sched_queue_wait"]
    assert len(bulk_waits) == N_BULK
    assert {s.attrs["class"] for s in bulk_waits} == {"backfill"}


class FixedAdmission:
    def __init__(self, state: AdmissionState):
        self._state = state

    def state(self) -> AdmissionState:
        return self._state


def _wait_for_probes(client, n: int, timeout_s: float = 5.0) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        states = client.endpoint_states()
        if sum(1 for s in states if s["extended"]) >= n:
            return
        time.sleep(0.02)
    raise AssertionError(f"probes never reported: {client.endpoint_states()}")


def test_two_endpoint_client_routes_bulk_away_from_shed_bulk_server():
    from lodestar_tpu.offload.client import BlsOffloadClient
    from lodestar_tpu.offload.server import BlsOffloadServer

    calls = {"shed": 0, "open": 0}

    def be_shed(sets):
        calls["shed"] += 1
        return True

    def be_open(sets):
        calls["open"] += 1
        return True

    shed = BlsOffloadServer(be_shed, admission=FixedAdmission(AdmissionState.SHED_BULK))
    open_ = BlsOffloadServer(be_open, admission=FixedAdmission(AdmissionState.ACCEPT))
    shed.start()
    open_.start()
    client = BlsOffloadClient(
        [f"127.0.0.1:{shed.port}", f"127.0.0.1:{open_.port}"], probe_interval_s=0.05
    )
    try:
        _wait_for_probes(client, 2)
        by_target = {s["target"]: s for s in client.endpoint_states()}
        assert by_target[f"127.0.0.1:{shed.port}"]["admission"] == "shed_bulk"
        assert by_target[f"127.0.0.1:{open_.port}"]["admission"] == "accept"

        async def go():
            # bulk classes route AWAY from the shedding server
            for _ in range(3):
                assert await client.verify_signature_sets(
                    _sets(2), VerifySignatureOpts(priority=PriorityClass.BACKFILL)
                )
            assert calls["open"] == 3 and calls["shed"] == 0
            # urgent work may still use either endpoint; both report 0
            # occupancy so the router just picks a healthy one
            assert await client.verify_signature_sets(
                _sets(2), VerifySignatureOpts(priority=PriorityClass.GOSSIP_BLOCK)
            )
            assert calls["open"] + calls["shed"] == 4

        asyncio.run(go())
    finally:
        asyncio.run(client.close())
        shed.stop()
        open_.stop()


def test_all_endpoints_shedding_still_serves_bulk_fail_safe():
    from lodestar_tpu.offload.client import BlsOffloadClient
    from lodestar_tpu.offload.server import BlsOffloadServer

    calls = {"n": 0}

    def be(sets):
        calls["n"] += 1
        return True

    a = BlsOffloadServer(be, admission=FixedAdmission(AdmissionState.SHED_BULK))
    b = BlsOffloadServer(be, admission=FixedAdmission(AdmissionState.SHED_BULK))
    a.start()
    b.start()
    client = BlsOffloadClient(
        [f"127.0.0.1:{a.port}", f"127.0.0.1:{b.port}"], probe_interval_s=0.05
    )
    try:
        _wait_for_probes(client, 2)

        async def go():
            # nowhere better to go: bulk still verifies (shed routes, it
            # never drops — dropping is the caller's backpressure call)
            assert await client.verify_signature_sets(
                _sets(1), VerifySignatureOpts(priority=PriorityClass.BACKFILL)
            )

        asyncio.run(go())
        assert calls["n"] == 1
    finally:
        asyncio.run(client.close())
        a.stop()
        b.stop()

"""Partition-mid-pipeline (chain/bls/pool.py): a lane that dies AFTER
`_stage_jobs` has staged a package but BEFORE `_dispatch_staged`
launches it must fail over — the staged future resolves through a
surviving lane, never strands, and the pipeline keeps serving.

The kill is injected from inside the staged package's own prep call,
which runs on an executor thread strictly between the two pipeline
stages — the exact window the chaos harness's partition events cannot
hit deterministically from outside.

Two lanes that take staged inputs: the pool stages every package of more
than one launch unit (one unit that finds a lane free has no launch to
hide its prep behind and is never staged), so each package here is two
jobs or more."""

from __future__ import annotations

import asyncio
import threading

import pytest

from lodestar_tpu.chain.bls import BlsDeviceVerifierPool, VerifySignatureOpts
from lodestar_tpu.crypto.bls.api import SignatureSet
from lodestar_tpu.scheduler import PriorityClass
from lodestar_tpu.testing.mesh import FakeLaneRig

OPTS = VerifySignatureOpts(batchable=False, priority=PriorityClass.GOSSIP_ATTESTATION)


def _sets(n: int, tag: int = 0) -> list[SignatureSet]:
    return [
        SignatureSet(
            pubkey=bytes([1, tag, i % 256]) + bytes(45),
            message=bytes([2, tag, i % 256]) * 8 + bytes(8),
            signature=bytes([3, tag, i % 256]) + bytes(93),
        )
        for i in range(n)
    ]


def test_lane_killed_between_staging_and_dispatch_fails_over():
    """Lane 0 dies while the first package's prep is in flight (staged,
    not yet launched). Every future must still resolve True via lane 1."""
    rig = FakeLaneRig(2, with_prepared=True, with_sharded=False)
    killed = threading.Event()

    def killing_prep(sets, lane_hint):
        # runs on the executor thread between _stage_jobs (submitted
        # this prep) and _dispatch_staged (awaits it): the partition
        # lands exactly mid-pipeline
        if not killed.is_set():
            with rig._record_lock:
                rig.failing.add(0)
            killed.set()
        return FakeLaneRig.prep_fn(sets, lane_hint)

    async def go():
        pool = BlsDeviceVerifierPool(
            mesh=rig.mesh,
            scheduler_enabled=True,
            prep_fn=killing_prep,
        )
        jobs = [
            asyncio.ensure_future(pool.verify_signature_sets(_sets(2, tag=i), OPTS))
            for i in range(6)
        ]
        verdicts = await asyncio.gather(*jobs)
        await pool.close()
        return verdicts

    verdicts = asyncio.run(go())
    assert killed.is_set(), "the kill must have fired from inside staging prep"
    assert verdicts == [True] * 6, "staged futures must fail over, not strand"
    with rig._record_lock:
        served = {lane for lane, _ in rig.calls} | {
            lane for lane, _ in rig.prepared_calls
        }
    assert 1 in served, "the surviving lane must have taken the work"


def test_lane_killed_mid_pipeline_then_healed_serves_again():
    """The wedged lane heals after the failover: later packages may use
    it again and nothing deadlocks on the staging slot."""
    rig = FakeLaneRig(2, with_prepared=True, with_sharded=False)
    state = {"n": 0}

    def prep(sets, lane_hint):
        state["n"] += 1
        if state["n"] == 1:
            with rig._record_lock:
                rig.failing.add(0)
        return FakeLaneRig.prep_fn(sets, lane_hint)

    async def go():
        pool = BlsDeviceVerifierPool(
            mesh=rig.mesh,
            scheduler_enabled=True,
            prep_fn=prep,
        )
        first = await asyncio.gather(
            *[pool.verify_signature_sets(_sets(2, tag=i), OPTS) for i in range(2)]
        )
        assert state["n"] >= 1 and rig.served_by(1), "the kill must have fired from staging prep"
        with rig._record_lock:
            rig.failing.discard(0)
        rest = await asyncio.gather(
            *[pool.verify_signature_sets(_sets(2, tag=2 + i), OPTS) for i in range(4)]
        )
        await pool.close()
        return [*first, *rest]

    assert asyncio.run(go()) == [True] * 6


def test_all_lanes_partitioned_fails_closed_not_stranded():
    """Both lanes dead at dispatch time: the staged future must resolve
    (False or an exception) within the run — a stranded future would
    hang gather forever. The pool stays closeable."""
    rig = FakeLaneRig(2, with_prepared=True, with_sharded=False)

    def prep(sets, lane_hint):
        with rig._record_lock:
            rig.failing.update({0, 1})
        return FakeLaneRig.prep_fn(sets, lane_hint)

    async def go():
        pool = BlsDeviceVerifierPool(
            mesh=rig.mesh,
            scheduler_enabled=True,
            prep_fn=prep,
        )
        try:
            futs = asyncio.gather(
                *[pool.verify_signature_sets(_sets(2, tag=9 + i), OPTS) for i in range(2)],
                return_exceptions=True,  # a fail-closed error is an acceptable resolution
            )
            verdicts = await asyncio.wait_for(futs, timeout=10.0)
            assert all(v is False or isinstance(v, Exception) for v in verdicts), verdicts
        except asyncio.TimeoutError:
            pytest.fail("staged future stranded with every lane dead")
        finally:
            await pool.close()
        assert pool.pipeline_stats()["staged_packages"] == 1

    asyncio.run(go())

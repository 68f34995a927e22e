"""Sets that name their signers by registry index, through
`BlsDeviceVerifierPool`: lanes as `build_device_mesh` makes them where
the backend runs the single launch, the real host stage, the pool's own
table; the device program alone is a stand-in, which judges every row
from what the host stage wrote for it (so a row whose index row names
other signers than its set is a failed job). K is 8 here."""

from __future__ import annotations

import asyncio

import jax
import numpy as np
import pytest

from lodestar_tpu import telemetry
from lodestar_tpu.chain.bls import BlsDeviceVerifierPool, VerifySignatureOpts
from lodestar_tpu.chain.bls.mesh import MeshLane, VerifierMesh
from lodestar_tpu.chain.bls.pubkey_table import PubkeyTable
from lodestar_tpu.crypto.bls import curve as C
from lodestar_tpu.crypto.bls.api import IndexedSignatureSet, SignatureSet, aggregate_pubkeys
from lodestar_tpu.crypto.bls.serdes import g1_to_bytes
from lodestar_tpu.models import batch_verify as bv
from lodestar_tpu.ops import prep as dp
from lodestar_tpu.scheduler import PriorityClass

K = 8
KEYS = [g1_to_bytes(C.g1_mul(C.G1_GEN, 3 + 5 * i)) for i in range(64)]
BAD = 0xEE  # a signature the stand-in holds wrong


def sig(tag: int, bad: bool = False) -> bytes:
    return bytes([0x8A, BAD if bad else 0x01, tag % 256, tag // 256]) + bytes(92)


def indexed(tag: int, signers=None, bad: bool = False) -> IndexedSignatureSet:
    signers = signers if signers is not None else [(tag * 7 + j) % 64 for j in range(1 + tag % K)]
    return IndexedSignatureSet(tuple(signers), bytes([tag % 256]) * 32, sig(tag, bad))


def block(first_tag: int = 0, bad_at: int | None = None) -> list:
    """131 sets as a block's: two of one signer, the rest aggregates."""
    return [indexed(first_tag + i, bad=(i == bad_at)) for i in range(131)]


class Judge:
    """The program's stand-in behind `_verify_single_prepared` and
    `_verify_grouped_prepared`: a row holds where the host stage found
    it structurally fine, its index row names exactly its set's
    signers (or, for a row the host summed, its pubkey limbs are the
    aggregate's), and its signature is not marked wrong."""

    def __init__(self, monkeypatch):
        self.launches: list[tuple[list[int], list[bool]]] = []
        self.devices: list = []
        monkeypatch.setattr(bv, "single_launch_active", lambda: True)
        monkeypatch.setattr(bv, "AGGREGATE_ROW_POINTS", K)
        monkeypatch.setattr(bv, "_verify_single_prepared", self.single)
        monkeypatch.setattr(bv, "_verify_grouped_prepared", self.grouped)

    def row_holds(self, inputs, row: int, s, device) -> bool:
        if not inputs.arrays[6][row] or s.signature[1] == BAD:
            return False
        if not isinstance(s, IndexedSignatureSet):
            return True
        if inputs.indexed is not None and inputs.indexed[1][row]:
            table_x, _, idx, _ = bv._indexed_args(inputs, device)
            self.devices.append(table_x.devices())
            named = idx[row][idx[row] > 0] - 1
            return named.tolist() == list(s.indices) and len(table_x) > named.max() + 1
        table = inputs.table
        want = dp.parse_g1_compressed(
            np.frombuffer(aggregate_pubkeys([table.pubkey_at(i) for i in s.indices]), dtype=np.uint8)[None]
        )[0][0]
        return bool((inputs.arrays[0][row] == want).all())

    def single(self, si, device=None) -> bool:
        ok = all(self.row_holds(si, row, s, device) for row, s in enumerate(si.sets))
        self.launches.append(([len(si.sets)], [ok]))
        return ok

    def grouped(self, gi, device=None) -> list[bool]:
        verdicts = [False] * len(gi.jobs)
        slot = len(gi.mask) // gi.groups if gi.groups else 0
        for g, i in enumerate(gi.riding):
            verdicts[i] = all(
                self.row_holds(gi, g * slot + r, s, device) for r, s in enumerate(gi.jobs[i])
            )
        self.launches.append(([len(j) for j in gi.jobs], verdicts))
        return verdicts


@pytest.fixture
def judge(monkeypatch):
    return Judge(monkeypatch)


def run(pool_factory, calls, priority=PriorityClass.RANGE_SYNC, between=None):
    """Submit every call at once; `between(pool)` runs before the submissions."""

    async def go():
        pool = pool_factory()
        if between is not None:
            between(pool)
        opts = VerifySignatureOpts(priority=priority)
        got = await asyncio.gather(*(pool.verify_signature_sets(c, opts) for c in calls))
        metrics = dict(pool.metrics)
        await pool.close()
        return got, metrics, pool

    return asyncio.run(go())


def device_pool():
    pool = BlsDeviceVerifierPool()
    pool.pubkey_table.extend(KEYS, trusted=True)
    return pool


# -- a block: the 66/65 split, a verdict a job -----------------------------------------


@pytest.mark.parametrize("bad_at, want, jobs", [
    (None, True, [True, True]), (3, False, [False, True]), (130, False, [True, False]),
], ids=["honest", "fault-in-the-first-job", "fault-in-the-last-job"])
def test_a_block_of_indexed_sets_rides_one_launch_with_a_verdict_a_job(judge, bad_at, want, jobs):
    got, metrics, pool = run(device_pool, [block(bad_at=bad_at)], PriorityClass.GOSSIP_BLOCK)
    assert got == [want]
    assert judge.launches == [([66, 65], jobs)]
    assert pool.takes_indexed_sets
    assert metrics["indexed_rows_started"] == 131 == metrics["sig_sets_started"]
    assert metrics["aggregate_points_started"] == sum(len(s.indices) for s in block())


def test_an_index_outside_the_table_fails_its_own_job_closed_and_no_other(judge):
    """Four jobs in one launch; the second names index 64 of a 64-key
    table, which a gather would clamp to a real key without a word."""
    poisoned = block(200)
    poisoned[100] = indexed(7, signers=[3, 64])
    got, _, _ = run(device_pool, [poisoned, block(400)])
    assert got == [False, True]
    assert judge.launches == [([66, 65, 66, 65], [True, False, True, True])]


@pytest.mark.parametrize("signers", [[], [-1], [5, 2**35]], ids=["none", "negative", "huge"])
def test_a_row_without_a_valid_signer_fails_its_job_closed(judge, signers):
    call = block()
    call[0] = indexed(9, signers=signers)
    got, _, _ = run(device_pool, [call], PriorityClass.GOSSIP_BLOCK)
    assert got == [False] and judge.launches == [([66, 65], [False, True])]


def test_a_call_may_mix_both_forms(judge):
    call = block()
    call[70] = SignatureSet(KEYS[9], bytes(32), sig(70))  # a key that is in no registry travels as bytes
    got, metrics, _ = run(device_pool, [call], PriorityClass.GOSSIP_BLOCK)
    assert got == [True] and metrics["indexed_rows_started"] == 130


# -- the counted fallback ---------------------------------------------------------------


@pytest.fixture
def prep_metrics():
    from lodestar_tpu.metrics import create_metrics

    metrics = create_metrics()
    bv.configure_device_prep(metrics.bls_prep)
    yield metrics.bls_prep
    dp.configure_launch_counter(None)
    bv._prep_metrics = None
    bv.consume_prep_info()


def test_more_than_k_signers_ride_as_a_byte_row_and_are_counted(judge, prep_metrics):
    call = block()
    call[5] = indexed(5, signers=list(range(K + 1)))
    got, _, _ = run(device_pool, [call], PriorityClass.GOSSIP_BLOCK)
    assert got == [True] and judge.launches == [([66, 65], [True, True])]
    assert prep_metrics.aggregate_fallbacks._value.get() == 1


def test_lanes_without_the_table_sum_on_the_host_and_count_every_set(judge, prep_metrics):
    """The single launch's lanes beside a table that no device holds."""
    table = PubkeyTable()
    table.extend(KEYS, trusted=True)

    def host_table_pool():
        lane = MeshLane(
            0, bv.verify_signature_sets_device, verify_prepared_fn=bv.verify_prepared,
            verify_grouped_fn=bv.verify_sets_grouped_launch, staged_prep_host_only=True,
        )
        return BlsDeviceVerifierPool(mesh=VerifierMesh([lane], table=table))

    sets = [indexed(i) for i in range(6)]
    got, _, pool = run(host_table_pool, [sets], PriorityClass.GOSSIP_BLOCK)
    assert got == [True] and not pool.takes_indexed_sets
    assert prep_metrics.aggregate_fallbacks._value.get() == 6


def test_a_pool_of_mock_lanes_has_no_table(judge):
    pool = BlsDeviceVerifierPool(verify_fn=lambda sets: True)
    assert pool.pubkey_table is None and not pool.takes_indexed_sets


# -- the table is the pool's, on every lane ------------------------------------------------


def test_an_append_between_two_calls_is_seen_by_the_second(judge):
    late = [indexed(1, signers=[2, 64])]

    async def go():
        pool = device_pool()
        first = await pool.verify_signature_sets(late)
        pool.pubkey_table.extend([g1_to_bytes(C.g1_mul(C.G1_GEN, 999))])  # a deposit
        second = await pool.verify_signature_sets(late)
        await pool.close()
        return first, second

    assert asyncio.run(go()) == (False, True)


def test_every_lane_of_a_forced_mesh_holds_the_table_and_serves_from_its_own_copy(judge):
    def mesh_pool():
        pool = BlsDeviceVerifierPool(mesh_mode="on")
        pool.pubkey_table.extend(KEYS, trusted=True)
        return pool

    got, _, pool = run(mesh_pool, [block(i * 150) for i in range(8)])
    lanes = [lane.label for lane in pool.mesh.lanes]
    assert len(lanes) == len(jax.devices()) > 1
    assert pool.pubkey_table.lanes() == {label: 64 for label in lanes}
    assert got == [True] * 8
    served = {next(iter(d)) for d in judge.devices}
    assert len(served) > 1  # several chips served, each from the copy it holds
    for d in served:
        assert pool.pubkey_table.arrays_on(d)[0].devices() == {d}


def test_the_launchs_ledger_entry_says_how_many_rows_were_indexed(judge):
    telemetry.reset_launch_telemetry()
    telemetry.configure_launch_telemetry(mode="on")
    try:
        run(device_pool, [block()], PriorityClass.GOSSIP_BLOCK)
        entries = [e for e in telemetry.launch_ledger() if e["program"] == "bls_lane_verify"]
    finally:
        telemetry.reset_launch_telemetry()
    assert [(e["size_class"], e["indexed_rows"]) for e in entries] == [(144, 131)]


def test_the_inline_road_resolves_through_the_tables_host_side():
    from lodestar_tpu.crypto.bls.api import SecretKey, sign

    sk = SecretKey(4242)

    async def go():
        pool = BlsDeviceVerifierPool(verify_fn=lambda sets: True, mesh=VerifierMesh(
            [MeshLane(0, lambda sets: True)], table=PubkeyTable()))
        pool.pubkey_table.extend([sk.to_pubkey()])
        opts = VerifySignatureOpts(verify_on_main_thread=True)
        good = await pool.verify_signature_sets([IndexedSignatureSet((0,), b"m" * 32, sign(sk, b"m" * 32))], opts)
        bad = await pool.verify_signature_sets([IndexedSignatureSet((1,), b"m" * 32, sign(sk, b"m" * 32))], opts)
        await pool.close()
        return good, bad

    assert asyncio.run(go()) == (True, False)

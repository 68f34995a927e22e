"""The single launch with rows that name their signers by registry index
(`IndexedSignatureSet`): the host stage (an index row a set, the bounds
check, the counted host aggregation), the launch's `bls.aggregate` stage
on what the host stage wrote, the dispatch's arguments, and, under
`slow` beside their siblings of `test_single_launch.py`, the flat and
the grouped program against the oracle. K is 8 here and the table holds
20 keys."""

from __future__ import annotations

import random

import jax
import numpy as np
import pytest

from lodestar_tpu.chain.bls.pubkey_table import IDENTITY_ROW, PubkeyTable
from lodestar_tpu.crypto.bls import api
from lodestar_tpu.crypto.bls.api import (
    IndexedSignatureSet,
    SecretKey,
    SignatureSet,
    aggregate_pubkeys,
    aggregate_signatures,
    sign,
)
from lodestar_tpu.models import batch_verify as bv
from lodestar_tpu.ops import msm
from lodestar_tpu.ops import prep as dp
from tests.ops.util import fp_from_dev

K = 8
_rng = random.Random(5)
SKS = [SecretKey(_rng.randrange(1, 2**200)) for _ in range(20)]
KEYS = [sk.to_pubkey() for sk in SKS]


def msg(k: int) -> bytes:
    return bytes([k]) * 32


def signed_by(indices, k: int) -> IndexedSignatureSet:
    return IndexedSignatureSet(
        tuple(indices), msg(k), aggregate_signatures([sign(SKS[i], msg(k)) for i in indices])
    )


def byte_set(i: int, k: int) -> SignatureSet:
    return SignatureSet(KEYS[i], msg(k), sign(SKS[i], msg(k)))


@pytest.fixture(scope="module")
def honest():
    """Aggregates of 3, 1, K and 3 signers (one twice), and a byte set."""
    return [
        signed_by([0, 1, 2], 1), signed_by([3], 2), signed_by(list(range(4, 4 + K)), 3),
        signed_by([5, 5, 6], 4), byte_set(12, 5),
    ]


@pytest.fixture
def table(monkeypatch):
    monkeypatch.setattr(bv, "AGGREGATE_ROW_POINTS", K)
    t = PubkeyTable()
    t.place_on([None], ["dev0"])
    t.extend(KEYS, trusted=True)
    return t


@pytest.fixture
def prep_metrics():
    from lodestar_tpu.metrics import create_metrics

    metrics = create_metrics()
    bv.configure_device_prep(metrics.bls_prep)
    yield metrics.bls_prep
    dp.configure_launch_counter(None)
    bv._prep_metrics = None
    bv.consume_prep_info()


def fallbacks(prep_metrics) -> float:
    return prep_metrics.aggregate_fallbacks._value.get()


# -- the host stage ------------------------------------------------------------------


def test_the_parse_writes_an_index_row_a_set_and_no_pubkey_bytes(table, honest, prep_metrics):
    base = dp.prep_launches_total()
    si = bv.prepare_single_launch_inputs(honest, table)
    assert dp.prep_launches_total() == base  # byte work only
    idx, is_indexed = si.indexed
    assert si.table is table and idx.shape == (8, K) and idx.dtype == np.int32
    assert is_indexed.tolist() == [True] * 4 + [False] * 4
    for row, s in enumerate(honest[:4]):
        n = len(s.indices)
        assert idx[row, :n].tolist() == [i + 1 for i in s.indices]  # registry index i is table row i + 1
        assert (idx[row, n:] == IDENTITY_ROW).all()
    assert (idx[4:] == IDENTITY_ROW).all()
    assert si.arrays[6][:5].all()  # every row structurally fine
    alone = bv.prepare_single_launch_inputs([honest[4]])
    assert alone.indexed is None  # a byte-only batch runs the byte-only program
    assert np.array_equal(si.arrays[0][4], alone.arrays[0][0])  # the byte row is parsed as ever
    assert fallbacks(prep_metrics) == 0


@pytest.mark.parametrize("indices", [(20,), (0, -1), (), (3, 2**40)], ids=["beyond", "negative", "none", "huge"])
def test_a_row_the_registry_does_not_bear_out_is_structurally_invalid(table, honest, indices):
    """A gather clamps an index outside the table silently: the host says so first."""
    s = honest[1]
    sets = [honest[0], IndexedSignatureSet(indices, s.message, s.signature), honest[4]]
    si = bv.prepare_single_launch_inputs(sets, table)
    assert si.arrays[6][:3].tolist() == [True, False, True]
    assert si.indexed[1][:3].tolist() == [True, False, False]


def test_more_than_k_signers_take_the_counted_host_aggregation(table, prep_metrics):
    wide = signed_by(list(range(K + 1)), 7)
    si = bv.prepare_single_launch_inputs([wide, signed_by([1, 2], 8)], table)
    assert si.indexed[1][:2].tolist() == [False, True]  # a byte row beside an indexed one
    assert fallbacks(prep_metrics) == 1
    want = bv.prepare_single_launch_inputs(
        [SignatureSet(aggregate_pubkeys(KEYS[: K + 1]), wide.message, wide.signature)]
    )
    assert np.array_equal(si.arrays[0][0], want.arrays[0][0]) and si.arrays[6][0]


def test_lanes_without_the_table_take_the_counted_host_aggregation(honest, prep_metrics):
    host_only = PubkeyTable()
    host_only.extend(KEYS, trusted=True)
    si = bv.prepare_single_launch_inputs(honest, host_only)
    assert si.indexed is None and si.arrays[6][:5].all()
    assert fallbacks(prep_metrics) == 4
    none = bv.prepare_single_launch_inputs(honest[:2], None)  # no registry at all: nothing to resolve from
    assert none.indexed is None and not none.arrays[6][:2].any()
    assert fallbacks(prep_metrics) == 6


def test_the_split_schedules_road_sums_on_the_host_and_counts(table, honest, prep_metrics):
    resolved = bv._host_aggregated(honest, table)
    assert [type(s) for s in resolved] == [SignatureSet] * 5 and resolved[4] is honest[4]
    assert resolved[0].pubkey == aggregate_pubkeys(KEYS[:3])
    assert fallbacks(prep_metrics) == 4
    beyond = IndexedSignatureSet((25,), msg(9), honest[1].signature)
    assert bv._host_aggregated([honest[0], beyond], table) is None  # a final structural verdict
    assert bv._host_aggregated([honest[4]], None) == [honest[4]]  # byte sets pass through, uncounted
    assert fallbacks(prep_metrics) == 6


def test_the_grouped_parse_gives_each_job_its_slot_of_the_index_matrix(table, honest, prep_metrics):
    gi = bv.prepare_grouped_launch_inputs([honest[:3], honest[3:]], table)
    idx, is_indexed = gi.indexed
    assert gi.groups == 2 and idx.shape == (16, K)
    assert is_indexed.tolist() == [True] * 3 + [False] * 5 + [True, False] + [False] * 6
    assert idx[8, :3].tolist() == [6, 6, 7] and (idx[9:] == IDENTITY_ROW).all()
    assert gi.mask.tolist() == [True] * 3 + [False] * 5 + [True] * 2 + [False] * 6
    assert fallbacks(prep_metrics) == 0  # a padding row repeats a set's message, never its signers


# -- the stage on what the host stage wrote --------------------------------------------


def test_the_stage_sums_each_indexed_row_to_the_oracles_aggregate(table, honest):
    si = bv.prepare_single_launch_inputs(honest, table)
    idx, _ = si.indexed
    pk_x, pk_y, ok = jax.jit(msm.aggregate_rows_g1)(*table.arrays_on(None), idx)
    xs, ys = fp_from_dev(np.asarray(pk_x)), fp_from_dev(np.asarray(pk_y))
    for row, s in enumerate(honest[:4]):
        want = api._decode_pubkey(aggregate_pubkeys([KEYS[i] for i in s.indices]))
        assert bool(ok[row]) and (xs[row], ys[row]) == want
    assert not np.asarray(ok)[4:].any()  # a row of padding sums to the identity


# -- the dispatch -----------------------------------------------------------------------


def _stand_in(verdict: bool, seen: list):
    def program(*arrays, **static):
        seen.append(arrays)
        groups = static.get("groups")
        v = np.asarray([verdict] * groups if groups else verdict, dtype=bool)
        return v, v

    program.__name__ = "_single_launch_verify"
    program.trace = lambda *arrays, **static: None
    return program


def test_the_launch_is_handed_the_table_of_the_chip_it_runs_on(table, honest, monkeypatch):
    seen: list = []
    monkeypatch.setattr(bv, "_single_launch_verify", _stand_in(True, seen))
    monkeypatch.setattr(bv, "_grouped_launch_verify", _stand_in(True, seen))
    assert bv.verify_sets_single_launch(honest, None, table) is True
    assert bv.verify_sets_single_launch(honest[4:], None, table) is True
    assert bv.verify_sets_grouped_launch([honest[:3], honest[3:]], None, table) == [True, True]
    flat, byte_only, grouped = seen
    assert len(flat) == 9 + 4 and len(byte_only) == 9 and len(grouped) == 9 + 4
    x, y = table.arrays_on(None)
    assert flat[9] is x and flat[10] is y and flat[11].shape == (8, K) and flat[12].dtype == bool
    assert grouped[11].shape == (16, K)


def test_an_append_after_the_parse_is_in_the_copy_the_launch_takes(table, honest, monkeypatch):
    seen: list = []
    monkeypatch.setattr(bv, "_single_launch_verify", _stand_in(True, seen))
    si = bv.prepare_single_launch_inputs(honest, table)
    table.extend([SecretKey(77).to_pubkey()])
    assert bv.verify_prepared(si) is True
    assert seen[0][9] is table.arrays_on(None)[0]  # the newer copy holds every row the parse checked


def test_a_device_error_degrades_to_the_split_schedule_with_the_table(table, honest, monkeypatch, prep_metrics):
    def broken(*arrays, **static):
        raise RuntimeError("device fault")

    broken.__name__ = "_single_launch_verify"
    went = []
    monkeypatch.setattr(bv, "_single_launch_verify", broken)
    monkeypatch.setattr(bv, "_verify_sets_split", lambda sets, device=None, table=None: went.append(table) or True)
    assert bv.verify_sets_single_launch(honest, None, table) is True
    assert went == [table]
    assert prep_metrics.single_launch_fallbacks._value.get() == 1


# -- the real programs against the oracle (slow: ~80 s of CPU compile each) ------------------


def _variants(honest):
    """name -> (sets, the faulty set's position or None)."""
    from perfbench.reference import bls as ref

    a, b, c, d, e = honest
    shift = sign(SKS[19], msg(99))
    moved = ref.shift_pubkey_off_subgroup(KEYS[12], 3)
    return {
        "honest": (honest, None),
        "swapped_signer": ([IndexedSignatureSet((0, 1, 3), a.message, a.signature), b, c, d, e], 0),
        "dropped_signer": ([a, b, IndexedSignatureSet(c.indices[:-1], c.message, c.signature), d, e], 2),
        "tampered_pair": ([
            IndexedSignatureSet(a.indices, a.message, ref.shift_signature(a.signature, shift, False)),
            b, c,
            IndexedSignatureSet(d.indices, d.message, ref.shift_signature(d.signature, shift, True)), e,
        ], 0),
        "off_subgroup_byte_row": ([a, b, c, d, SignatureSet(moved, e.message, e.signature)], 4),
        "beyond_the_table": ([a, IndexedSignatureSet((20,), b.message, b.signature), c, d, e], 1),
    }


@pytest.mark.slow
@pytest.mark.parametrize("name", ["honest", "swapped_signer", "dropped_signer", "tampered_pair",
                                  "off_subgroup_byte_row", "beyond_the_table"])
def test_the_flat_program_gives_the_oracles_verdict(table, honest, monkeypatch, name):
    monkeypatch.setattr(bv, "single_launch_active", lambda: True)
    sets, _ = _variants(honest)[name]
    want = api.verify_signature_sets(sets, table.pubkey_at)
    assert want is (name == "honest")
    assert bv.verify_sets_single_launch(sets, None, table) is want


@pytest.mark.slow
@pytest.mark.parametrize("name", ["honest", "swapped_signer", "dropped_signer", "tampered_pair",
                                  "off_subgroup_byte_row", "beyond_the_table"])
def test_the_grouped_program_gives_each_job_the_oracles_verdict(table, honest, monkeypatch, name):
    """A mixed job (indexed rows and a byte row) beside an indexed one:
    a fault fails its own job and no other."""
    monkeypatch.setattr(bv, "single_launch_active", lambda: True)
    sets, _ = _variants(honest)[name]
    jobs = [sets[:3], sets[3:]]
    want = [api.verify_signature_sets(job, table.pubkey_at) for job in jobs]
    assert bv.verify_sets_grouped_launch(jobs, None, table) == want
    if name == "tampered_pair":
        assert want == [False, False]  # one half of the pair in each job

"""The multi-job launch (`_grouped_launch_verify`): G jobs ride one
single-launch program, a slot of rows and a verdict each.

Tier-1 here: the per-slot folds against the ops' own folds at tiny
shapes, the host stage's layout (no dispatch), and the verify side on a
stand-in program (mapping of slots to jobs, shape check, degradation).
The real program against the oracle is under `slow` in
`test_single_launch.py`, beside its siblings."""

from __future__ import annotations

import numpy as np
import pytest

from lodestar_tpu import telemetry
from lodestar_tpu.crypto.bls.api import SignatureSet
from lodestar_tpu.models import batch_verify as bv
from lodestar_tpu.ops import curve as cv
from lodestar_tpu.ops import fp
from lodestar_tpu.ops import pairing as prg
from lodestar_tpu.ops import prep as dp


def _limbs(seed: int, *shape):
    r = np.random.default_rng(seed)
    return r.integers(0, 1 << fp.LIMB_BITS, size=shape + (fp.LIMBS,), dtype=np.int64).astype(np.int32)


# -- the folds, slot by slot -------------------------------------------------------


# a slot of 3 rows is no power of two long, as the 72-row rung is not: the folds pad it
@pytest.mark.parametrize("slot", [2, 3])
@pytest.mark.parametrize("groups", [1, 2, 4])
def test_fold_sum_slots_equals_fold_sum_of_each_slot(groups, slot):
    pts = tuple(_limbs(11 + i, groups * slot, 2) for i in range(3))
    got = bv._fold_sum_slots(cv.F2, pts, groups)
    for g in range(groups):
        want = cv.fold_sum(cv.F2, tuple(c[g * slot : (g + 1) * slot] for c in pts))
        for a, b in zip(got, want):
            assert np.array_equal(np.asarray(a[g]), np.asarray(b)), f"slot {g}"


@pytest.mark.parametrize("slot", [2, 3])
@pytest.mark.parametrize("groups", [1, 2, 4])
def test_fp12_product_fold_slots_equals_the_fold_of_each_slot(groups, slot):
    fs = _limbs(23, groups * slot, 2, 3, 2)
    mask = np.random.default_rng(29).integers(0, 2, size=groups * slot).astype(bool)
    mask[0] = False  # a masked row in the first slot at least
    got = bv._fp12_product_fold_slots(fs, mask, groups)
    assert got.shape == (groups, 2, 3, 2, fp.LIMBS)
    for g in range(groups):
        rows = slice(g * slot, (g + 1) * slot)
        want = prg.fp12_product_fold(fs[rows], mask=mask[rows])
        assert np.array_equal(np.asarray(got[g]), np.asarray(want)), f"slot {g}"


# -- the host stage ----------------------------------------------------------------


@pytest.fixture(scope="module")
def sets():
    return bv.make_synthetic_sets(7, seed=171)


# four jobs' set counts for each slot length the rule gives: the smallest
# size class, the 72-row rung (a block's halves) and the 128 class (a 66
# beside a 100 rides 128 rows)
SIZES = {8: (3, 2, 1, 2), 72: (66, 65, 72, 65), 128: (66, 100, 128, 73)}
SLOTS = pytest.mark.parametrize("slot", list(SIZES))


def _jobs(sets, sizes):
    """Jobs of these set counts from the seven sets in turn (a launch
    draws fresh blinding for every row, so a repeated set cancels
    nothing), each job from another set on."""
    return [[sets[(k + i) % len(sets)] for i in range(n)] for k, n in enumerate(sizes)]


def _short_sig(s: SignatureSet) -> SignatureSet:
    return SignatureSet(pubkey=s.pubkey, message=s.message, signature=b"\x00" * 95)


@pytest.mark.parametrize("sizes, slot", [
    ((66, 65), 72), ((72, 65, 72), 72), ((66, 100), 128), ((72, 73, 65), 128), ((3, 2), 8), ((64, 33), 64),
], ids=str)
def test_the_parse_lays_out_the_slot_the_rule_gives(sets, sizes, slot):
    """One rung inside the 128 class; every other slot is the longest
    job's size class (`tests/chain/test_grouped_units.py` has the rule
    size by size, beside the former's)."""
    assert telemetry.group_slot_rows(sizes) == slot
    gi = bv.prepare_grouped_launch_inputs(_jobs(sets, sizes))
    assert gi.mask.shape == (gi.groups * slot,)


@SLOTS
@pytest.mark.parametrize("jobs_riding, groups", [(2, 2), (3, 4), (4, 4)], ids=["two", "three", "four"])
def test_prepare_lays_each_job_in_its_own_slot(sets, slot, jobs_riding, groups):
    sizes = SIZES[slot][:jobs_riding]
    jobs = _jobs(sets, sizes)
    base = dp.prep_launches_total()
    gi = bv.prepare_grouped_launch_inputs(jobs)
    assert dp.prep_launches_total() == base  # byte work only
    assert gi.groups == groups == bv.grouped_launch_groups(len(jobs))
    assert gi.riding == list(range(len(jobs)))
    assert gi.mask.shape == (groups * slot,) and gi.bits.shape == (groups * slot, bv.COEFF_BITS)
    for arr in gi.arrays:
        assert arr.shape[0] == groups * slot
    for g in range(groups):
        n = sizes[g] if g < len(sizes) else 0  # an empty slot is fully masked
        assert gi.mask[g * slot : (g + 1) * slot].tolist() == [True] * n + [False] * (slot - n)
        # each slot's blinding is a batch's own: first coefficient 1, the rest nonzero
        coeffs = [int("".join(map(str, row)), 2) for row in gi.bits[g * slot : g * slot + n]]
        assert coeffs[:1] == [1][: n] and all(c != 0 for c in coeffs)
        assert not gi.bits[g * slot + n : (g + 1) * slot].any()
        # the slot's rows are the job's rows as the single launch parses them,
        # and its padding rows repeat the job's first row (an empty slot's, the launch's first)
        first = jobs[g][0] if n else jobs[0][0]
        alone = bv.prepare_single_launch_inputs((jobs[g] if n else []) + [first])
        for got, want in zip(gi.arrays, alone.arrays):
            assert np.array_equal(got[g * slot : g * slot + n], want[:n])
            assert (got[g * slot + n : (g + 1) * slot] == want[n]).all()


@SLOTS
def test_a_job_with_a_wrong_length_encoding_rides_no_slot(sets, slot):
    a, b, c = _jobs(sets, SIZES[slot][:3])
    b[-1] = _short_sig(b[-1])
    gi = bv.prepare_grouped_launch_inputs([a, b, c])
    assert gi.riding == [0, 2] and gi.groups == 2
    assert gi.mask.shape == (2 * slot,)
    none = bv.prepare_grouped_launch_inputs([[_short_sig(a[0])], b])
    assert none.riding == [] and none.arrays is None
    base = dp.prep_launches_total()
    assert bv._verify_grouped_prepared(none) == [False, False]
    assert dp.prep_launches_total() == base  # no slot, no launch


def test_a_job_that_rides_no_slot_lengthens_none(sets):
    """The rule reads the jobs that ride: a 100-set job with a
    wrong-length encoding beside a block's halves leaves them at 72."""
    a, b, c = _jobs(sets, (66, 100, 65))
    b[3] = _short_sig(b[3])
    gi = bv.prepare_grouped_launch_inputs([a, b, c])
    assert gi.riding == [0, 2] and gi.mask.shape == (2 * 72,)


def test_prepare_counts_the_real_sets(sets):
    from lodestar_tpu.metrics import create_metrics

    metrics = create_metrics()
    bv.configure_device_prep(metrics.bls_prep)
    try:
        bv.prepare_grouped_launch_inputs([sets[:3], sets[3:5]])
        bv.prepare_grouped_launch_inputs(_jobs(sets, (66, 65)))  # 13 padding rows are no sets
    finally:
        dp.configure_launch_counter(None)
        bv._prep_metrics = None
        bv.consume_prep_info()
    assert metrics.bls_prep.sets.labels("single_launch")._value.get() == 5 + 131


# -- the verify side, on a stand-in program ------------------------------------------


@pytest.fixture
def prep_metrics():
    from lodestar_tpu.metrics import create_metrics

    metrics = create_metrics()
    bv.configure_device_prep(metrics.bls_prep)
    yield metrics.bls_prep
    dp.configure_launch_counter(None)
    bv._prep_metrics = None
    bv.consume_prep_info()


def _stand_in(verdicts, valids=None, rows=None):
    """A program that answers `verdicts`; with `rows`, only to arrays of
    that many rows, every one of them."""
    def program(*arrays, groups):
        assert rows is None or [a.shape[0] for a in arrays] == [rows] * len(arrays)
        v = np.asarray(verdicts, dtype=bool)
        return v, np.asarray(v if valids is None else valids, dtype=bool)

    program.__name__ = "_grouped_launch_verify"
    program.trace = lambda *arrays, groups: None  # a lane traces a program once for all lanes before its first call
    return program


@SLOTS
@pytest.mark.parametrize("verdicts, want", [
    ((False, True), [False, True]), ((True, False), [True, False]), ((True, True), [True, True]),
], ids=["first-false", "last-false", "both-true"])
def test_each_job_gets_its_own_slots_verdict(sets, monkeypatch, slot, verdicts, want):
    monkeypatch.setattr(bv, "_grouped_launch_verify", _stand_in(verdicts, rows=2 * slot))
    base = dp.prep_launches_total()
    assert bv.verify_sets_grouped_launch(_jobs(sets, SIZES[slot][:2])) == want
    assert dp.prep_launches_total() - base == dp.SINGLE_LAUNCH_BUDGET  # one dispatch a launch


@SLOTS
def test_three_jobs_ignore_the_empty_slot_and_a_rejected_job_is_false(sets, monkeypatch, prep_metrics, slot):
    a, b, c = _jobs(sets, SIZES[slot][:3])
    # slot 3 is empty: whatever the program says of it resolves nothing
    monkeypatch.setattr(bv, "_grouped_launch_verify",
                        _stand_in((True, False, True, False), valids=(True, False, True, True), rows=4 * slot))
    assert bv.verify_sets_grouped_launch([a, b, c]) == [True, False, True]
    assert prep_metrics.rejected._value.get() == 1  # slot 1's structural veto, not the empty slot
    monkeypatch.setattr(bv, "_grouped_launch_verify", _stand_in((True, True), rows=2 * slot))
    got = bv.verify_sets_grouped_launch([a, b[:-1] + [_short_sig(b[-1])], c])
    assert got == [True, False, True]


@pytest.mark.parametrize("program", [
    _stand_in((True, True, True)),  # verdict of another shape
    lambda *a, groups: (np.ones(2, bool), np.ones((), bool)),  # batch_valid of another shape
    lambda *a, groups: (np.ones(2, np.int32), np.ones(2, bool)),  # not bools
], ids=["verdict-shape", "batch-valid-shape", "dtype"])
def test_a_shape_anomaly_degrades_to_one_launch_a_job(sets, monkeypatch, prep_metrics, program):
    served = []
    monkeypatch.setattr(bv, "_grouped_launch_verify", program)
    monkeypatch.setattr(bv, "verify_sets_single_launch", lambda job, device=None, table=None: served.append(len(job)) or len(job) == 3)
    assert bv.verify_sets_grouped_launch([sets[:3], sets[3:5]]) == [True, False]
    assert served == [3, 2]
    assert prep_metrics.single_launch_fallbacks._value.get() == 1


@SLOTS
def test_a_device_error_degrades_to_one_launch_a_job_counted_once(sets, monkeypatch, prep_metrics, slot):
    def boom(*a, **k):
        raise RuntimeError("injected grouped-launch device fault")

    served = []
    monkeypatch.setattr(bv, "_grouped_launch_verify", boom)
    monkeypatch.setattr(bv, "verify_sets_single_launch", lambda job, device=None, table=None: served.append(len(job)) or True)
    assert bv.verify_sets_grouped_launch(_jobs(sets, SIZES[slot][:3])) == [True, True, True]
    assert served == list(SIZES[slot][:3])
    assert prep_metrics.single_launch_fallbacks._value.get() == 1


def test_a_host_parse_error_degrades_to_one_launch_a_job(sets, monkeypatch, prep_metrics):
    def boom(*a, **k):
        raise RuntimeError("injected host-parse fault")

    served = []
    monkeypatch.setattr(bv, "_parse_host_arrays", boom)
    monkeypatch.setattr(bv, "verify_sets_single_launch", lambda job, device=None, table=None: served.append(len(job)) or True)
    assert bv.verify_sets_grouped_launch([sets[:3], sets[3:5]]) == [True, True]
    assert served == [3, 2]
    assert prep_metrics.single_launch_fallbacks._value.get() == 1


def test_verify_prepared_takes_the_grouped_staging(sets, monkeypatch):
    monkeypatch.setattr(bv, "_grouped_launch_verify", _stand_in((True, False)))
    gi = bv.prepare_grouped_launch_inputs([sets[:3], sets[3:5]])
    assert bv.verify_prepared(gi) == [True, False]
    assert bv.make_lane_verify_prepared_fn(0)(gi) == [True, False]
    assert bv.make_lane_verify_grouped_fn(0)([sets[:3], sets[3:5]]) == [True, False]

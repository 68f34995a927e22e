"""The plain references, their controls, and where they meet the
program's own CPU oracle."""

from __future__ import annotations

import hashlib

import numpy as np
import pytest

from perfbench.entries.reference import ReferenceSystem
from perfbench.reference import bls, merkle
from perfbench.reference.parallel import judge_many, parallel_map


@pytest.fixture(scope="module")
def sets():
    """Three honest sets, a cancelling pair made of the first two, and
    the third with its pubkey moved off the subgroup."""
    scalars = [11, 2**200 + 5, 2**250 + 9]
    messages = [bytes([i]) * 32 for i in range(3)]
    pks = [bytes.fromhex(h) for h in parallel_map("keys", scalars, workers=2)]
    sigs = [bytes.fromhex(h) for h in
            parallel_map("sign", [[s, m.hex()] for s, m in zip(scalars, messages)], workers=2)]
    honest = list(zip(pks, messages, sigs))
    pair = [
        (pks[0], messages[0], bls.shift_signature(sigs[0], sigs[2], False)),
        (pks[1], messages[1], bls.shift_signature(sigs[1], sigs[2], True)),
    ]
    off = (bls.shift_pubkey_off_subgroup(pks[2], 5), messages[2], sigs[2])
    return {"honest": honest, "pair": pair, "off": off}


def judge(triples):
    return judge_many(triples, workers=3)


def test_reference_accepts_honest_sets_and_rejects_each_fault(sets):
    assert bls.reference_verdict(judge(sets["honest"]))
    assert [bls.is_valid(j) for j in judge(sets["pair"])] == [False, False]
    off = judge([sets["off"]])[0]
    assert off["decodes"] and not off["in_subgroup"] and not bls.is_valid(off)
    garbage = judge([(b"\x00" * 48, b"m" * 32, b"\xff" * 96)])[0]
    assert not garbage["decodes"] and not bls.is_valid(garbage)


@pytest.mark.parametrize("control,passes", [
    ("no_blinding", "pair"), ("no_subgroup_check", "off"),
])
def test_each_control_lets_exactly_its_fault_through(sets, control, passes):
    calls = {"pair": sets["pair"] + sets["honest"][2:], "off": sets["honest"][:2] + [sets["off"]]}
    for fault, call in calls.items():
        judged = judge(call)
        assert not bls.reference_verdict(judged)
        assert bls.control_verdict(judged, control) == (fault == passes)
    assert bls.control_verdict(judge(sets["honest"]), control)


def test_an_unknown_control_is_an_error():
    with pytest.raises(ValueError):
        bls.control_verdict([], "no_such_control")
    with pytest.raises(ValueError):
        ReferenceSystem("no_such_control")


def test_the_programs_cpu_oracle_sides_with_the_reference(sets):
    """Independent code, same verdicts: the faults are real ones."""
    from lodestar_tpu.crypto.bls import api

    for pk, m, s in sets["honest"]:
        assert api.verify(pk, m, s)
    for pk, m, s in sets["pair"] + [sets["off"]]:
        assert not api.verify(pk, m, s)
    as_sets = [api.SignatureSet(pk, m, s) for pk, m, s in sets["pair"] + sets["honest"][2:]]
    assert not api.verify_signature_sets(as_sets)


def test_merkle_levels_are_hashlibs():
    leaves = bytes(range(256)) * 1  # 8 leaves
    levels = merkle.levels_from_leaves(leaves)
    assert [len(lv) // 32 for lv in levels] == [8, 4, 2, 1]
    h = lambda b: hashlib.sha256(b).digest()  # noqa: E731
    l1 = [h(leaves[i:i + 64]) for i in range(0, 256, 64)]
    l2 = [h(l1[0] + l1[1]), h(l1[2] + l1[3])]
    assert merkle.root(leaves) == h(l2[0] + l2[1])


@pytest.mark.parametrize("control,stale", [(None, False), ("stale_repeat", True)])
def test_reference_flush_rehashes_the_dirty_paths(control, stale):
    rng = np.random.default_rng(3)
    leaves = rng.integers(0, 256, size=(64, 32), dtype=np.uint8)
    system = ReferenceSystem(control)
    levels = system.build_stack(leaves)
    assert levels[-1][0].tobytes() == merkle.root(leaves.tobytes())
    dirty = np.array([3, 17, 40])
    for value in (1, 2):
        leaves[dirty, 0] = value
        system.flush(levels, dirty)
        fresh = levels[-1][0].tobytes() == merkle.root(leaves.tobytes())
        assert fresh == (not stale or value == 1)

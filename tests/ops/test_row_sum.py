"""The verify launch's `bls.aggregate` stage alone (`msm.aggregate_rows_g1`):
each row's signers gathered from a table and summed, against the CPU
oracle's `g1_add`. Row 0 of the table is the identity, as in
`chain/bls/pubkey_table.py`; one jitted program a K, all of a K's cases
the rows of one call."""

from __future__ import annotations

import functools
import random

import jax
import numpy as np
import pytest

from lodestar_tpu.crypto.bls import curve as C
from lodestar_tpu.ops import fp, msm

from .util import fp_from_dev

PAD = 0  # the identity's row
POINTS = 14


@functools.lru_cache(maxsize=None)
def table():
    """Rows 1..14: random G1 points; row 15: the negation of row 1."""
    rng = random.Random(35)
    points = [C.g1_mul(C.G1_GEN, rng.randrange(1, C.R)) for _ in range(POINTS)]
    points.append(C.g1_neg(points[0]))
    x = np.zeros((16, fp.LIMBS), dtype=np.int32)
    y = np.zeros((16, fp.LIMBS), dtype=np.int32)
    for row, pt in enumerate(points, start=1):
        x[row], y[row] = fp.mont_limbs_from_int(pt[0]), fp.mont_limbs_from_int(pt[1])
    return [None] + points, x, y


def rows_of(k: int) -> dict[str, list[int]]:
    """The cases at K columns: table rows a row names, padded to K."""
    cases = {"full": list(range(1, k + 1)), "all_padding": []}
    if k > 1:
        cases["padded"] = list(range(2, 2 + k // 2))
        cases["repeated_index"] = [3, 3] + list(range(4, 2 + k // 2))
        cases["cancelling_pair"] = [1, 15]  # P + (-P): the identity, the row is invalid
        cases["cancelling_pair_among_others"] = [1, 5, 15, 6]
    return {name: (rows + [PAD] * k)[:k] for name, rows in cases.items()}


@functools.lru_cache(maxsize=None)
def summed(k: int):
    _, x, y = table()
    cases = rows_of(k)
    idx = np.asarray(list(cases.values()) + [[PAD] * k] * (8 - len(cases)), dtype=np.int32)
    pk_x, pk_y, ok = jax.jit(msm.aggregate_rows_g1)(x, y, idx)
    xs, ys = fp_from_dev(np.asarray(pk_x)), fp_from_dev(np.asarray(pk_y))
    return {name: ((xs[i], ys[i]) if bool(ok[i]) else None) for i, name in enumerate(cases)}


CASES = [(k, name) for k in (1, 8, 13) for name in rows_of(k)]


@pytest.mark.parametrize("k, name", CASES, ids=[f"K{k}-{name}" for k, name in CASES])
def test_a_rows_sum_is_the_oracles(k, name):
    points, _, _ = table()
    want = None
    for row in rows_of(k)[name]:
        want = C.g1_add(want, points[row])
    assert summed(k)[name] == want
    if name in ("all_padding", "cancelling_pair"):
        assert want is None  # the identity: the launch holds the row invalid


def test_aggregate_points_is_the_same_sum():
    """`aggregate_points_g1` (one point out) and the launch's stage run
    the one `sum_affine_g1`."""
    points, x, y = table()
    got = msm.aggregate_points_g1((x[1:6], y[1:6]))
    want = None
    for pt in points[1:6]:
        want = C.g1_add(want, pt)
    z = fp_from_dev(np.asarray(got[2])[None])[0]
    aff = jax.jit(lambda p: msm.cv.jac_to_affine_batch(msm.cv.F1, p))(tuple(np.asarray(c)[None] for c in got))
    assert z != 0 and (fp_from_dev(np.asarray(aff[0]))[0], fp_from_dev(np.asarray(aff[1]))[0]) == want

"""The fixed-window chain under `fp.pow_const` and `prep._fp2_pow_bits`
(`fp.pow_windowed`): values against Python's `pow` and the oracle's
`F.fp2_pow` from canonical and relaxed inputs, the schedule for every
length of the top window, the multiplies each production chain really
runs, and the relaxed contract over `w` and `2w` squarings in a row."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lodestar_tpu.crypto.bls import fields as F
from lodestar_tpu.ops import fp, prep, tower as tw

from .util import assert_clean, rand_fp_ints

P = F.P
R_INV = pow(1 << (fp.LIMBS * fp.LIMB_BITS), -1, P)
W = fp.pow_window((P - 2).bit_length())  # the width of every production chain
E_FP2_SQRT = (P * P + 7) // 16

EXPONENTS = {
    "0": 0, "1": 1, "2": 2, "3": 3,
    "2^w-1": (1 << W) - 1, "2^w": 1 << W, "2^w+1": (1 << W) + 1,
    "65537": 65537, "(P-1)/2": (P - 1) // 2, "(P+1)/4": (P + 1) // 4, "P-2": P - 2,
}


def _value(limbs) -> int:
    """The field element a relaxed (signed-limb) Montgomery array stands for."""
    return fp.int_from_limbs(limbs) * R_INV % P


def _relaxed_inputs() -> np.ndarray:
    """(8, 33) Montgomery limbs: exact zero, 1, P - 1, two random canonical
    values, one with negative limbs (a borrow pushed down every other limb),
    one in (p, 1.03p), and a negative value as `fp.sub` leaves it."""
    canon = fp.mont_limbs_from_int
    a, b, c = rand_fp_ints(3, seed=32)
    borrowed = canon(c).copy()
    borrowed[0:32:2] -= 1 << fp.LIMB_BITS
    borrowed[1:33:2] += 1
    above_p = fp.limbs_from_int(P + P // 50)
    lo, hi = sorted([canon(1), canon(P - 5)], key=fp.int_from_limbs)
    negative = np.asarray(fp.sub(lo[None], hi[None]))[0]
    rows = [canon(0), canon(1), canon(P - 1), canon(a), canon(b), borrowed, above_p, negative]
    arr = np.stack(rows).astype(np.int32)
    assert arr[5].min() < 0 and _value(arr[5]) == c
    assert P < fp.int_from_limbs(arr[6]) < 1.03 * P and fp.int_from_limbs(arr[7]) < 0
    assert_clean(arr)
    return arr


class TestValues:
    @pytest.mark.parametrize("name", EXPONENTS)
    def test_fp_matches_python_pow(self, name):
        e = EXPONENTS[name]
        arr = _relaxed_inputs()
        got = np.asarray(fp.pow_const(arr, e))
        assert_clean(got)
        assert [_value(r) for r in got] == [pow(_value(r), e, P) for r in arr]
        if e > 0:
            assert not got[0].any()  # 0^e stays exact-zero limbs

    @pytest.mark.parametrize("name", [*EXPONENTS, "(P^2+7)/16"])
    def test_fp2_matches_oracle_pow(self, name):
        e = EXPONENTS.get(name, E_FP2_SQRT)
        rows = _relaxed_inputs()
        arr = np.stack([rows, np.roll(rows, 3, axis=0)], axis=1)  # (8, 2, 33); row 0 holds (0, b)
        arr[0, 1] = 0  # ... so make one exact zero of Fp2
        if e == 0:
            got = np.asarray(tw.fp2_one(arr.shape[:-2]))  # _fp2_pow_bits takes a leading 1 bit
        else:
            got = np.asarray(prep._fp2_pow_bits(arr, fp._exp_bits(e)))
        assert_clean(got)
        vals = [(_value(r[0]), _value(r[1])) for r in arr]
        assert [(_value(r[0]), _value(r[1])) for r in got] == [F.fp2_pow(v, e) for v in vals]
        if e > 0:
            assert not got[0].any()


# The schedule alone, on the group (Z_M, +) in place of the field: an
# element stands for its exponent, so `a` is 1, `one` is 0, a squaring
# doubles and a multiply adds, and a^e reads e mod M. Cheap enough to
# sweep bit lengths and to run a whole chain call by call in Python.
_M = 32749


class _Exponents:
    """`sq` / `mul` / `one` on exponents mod M, counting their calls."""

    def __init__(self):
        self.calls = {"sq": 0, "mul": 0}

    def sq(self, x):
        self.calls["sq"] += 1
        return 2 * x % _M

    def mul(self, x, y):
        self.calls["mul"] += 1
        return (x + y) % _M

    @staticmethod
    def one(batch_shape=()):
        return jnp.zeros((*batch_shape, 1), jnp.int32)


class TestSchedule:
    def test_width_minimises_the_multiplies(self):
        # the counts of ISSUE 32's table: 2^w - 2 for the table, one a window
        assert [fp.pow_window(n) for n in (1, 2, 3, 17, 379, 381, 758)] == [1, 1, 1, 2, 5, 5, 5]
        for n in (379, 381, 758):
            cost = {w: (1 << w) - 2 + -(-n // w) for w in range(1, 9)}
            assert cost[fp.pow_window(n)] == min(cost.values())

    @pytest.mark.parametrize("w,first", [(1, 1), (2, 6), (3, 29), (4, 106), (5, 337), (5, 754)])
    def test_every_top_window_length(self, w, first):
        """w + 1 bit lengths running leave a top window of every length
        1 .. w (w = 1: a window wider than the exponent is one digit)."""
        r = np.random.default_rng(first)
        tops = set()
        for nbits in range(first, first + w + 1):
            assert fp.pow_window(nbits) == w
            tops.add(nbits % w or w)
            e = (1 << (nbits - 1)) | int.from_bytes(r.bytes(96), "little") % (1 << (nbits - 1))
            g = _Exponents()
            out = fp.pow_windowed(g.one() + 1, fp._exp_bits(e), g.sq, g.mul, g.one())
            assert int(out[0]) == e % _M, nbits
        assert tops == set(range(1, w + 1))


#: production chains: bits, then ISSUE 32's count at w = 5 of squarings and
#: of multiplies (one a window after the top one, and the 2^w - 2 entries
#: of the table, of which a^2 is a squaring here); a multiply a bit was
#: 378 + 378, 380 + 380 and 757 + 757
CHAINS = {
    "fp_sqrt_(P+1)/4": (379, 378, 105),
    "fp_inv_P-2": (381, 380, 106),
    "fp2_sqrt_(P^2+7)/16": (758, 757, 181),
}


class TestMechanismEngaged:
    @pytest.mark.parametrize("name", CHAINS)
    def test_production_chain_runs_a_multiply_a_window(self, name, monkeypatch):
        """The callers themselves, their field operations swapped for the
        counting ones, run call by call: what the rolled loops execute."""
        bits, issue_sq, issue_mul = CHAINS[name]
        assert issue_mul == (1 << W) - 2 + -(-bits // W) - 1 and issue_sq == bits - 1
        g = _Exponents()
        if name.startswith("fp2"):
            one2 = lambda batch_shape: g.one(batch_shape)[..., None]
            for attr, f in [("fp2_sq", g.sq), ("fp2_mul", g.mul), ("fp2_one", one2)]:
                monkeypatch.setattr(tw, attr, f)
            e, a = E_FP2_SQRT, jnp.ones((3, 1, 1), jnp.int32)
            run = lambda: prep._fp2_pow_bits(a, prep._E_FP2_SQRT_BITS)
        else:
            for attr, f in [("mont_sq", g.sq), ("mont_mul", g.mul), ("one_mont", g.one)]:
                monkeypatch.setattr(fp, attr, f)
            e, a = (prep._E_FP_SQRT, P - 2)["inv" in name], jnp.ones((3, 1), jnp.int32)
            run = lambda: fp.inv(a) if "inv" in name else fp.pow_const(a, e)
        assert e.bit_length() == bits
        with jax.disable_jit():  # the loops run in Python: every call is counted
            out = run()
        assert out.shape == a.shape and (np.asarray(out) == e % _M).all()
        # a^2 is a squaring here and a multiply in the issue's count
        assert g.calls["sq"] <= bits and g.calls["mul"] <= issue_mul, g.calls
        assert g.calls["sq"] + g.calls["mul"] <= issue_sq + issue_mul


def _worst_relaxed(seed: int) -> np.ndarray:
    """(4, 33) limbs at the edges of the contract `ops/fp.py` states: values
    at 2.19p and -2.09p and limbs up to LIMB_LOOSE in magnitude."""
    r = np.random.default_rng(seed)
    rows = []
    for scale in (2.19, -2.09, 1.03, -0.001):
        v = int(abs(scale) * 1000) * P // 1000
        limbs = fp.limbs_from_int(v).astype(np.int64)
        limbs[:30] += r.integers(0, fp.LIMB_LOOSE - fp.LIMB_MASK + 1, 30)  # < 2^-20 p in value
        limbs[0:30:7] = fp.LIMB_LOOSE
        rows.append(limbs if scale > 0 else -limbs)
    arr = np.stack(rows).astype(np.int32)
    assert_clean(arr)
    assert np.abs(arr).max() > fp.LIMB_MASK
    return arr


class TestSquaringsInARow:
    """The chains so far never squared twice running; a window squares w
    times with no multiply between."""

    @pytest.mark.parametrize("k", [W, 2 * W])
    def test_mont_sq_stays_relaxed(self, k):
        r = _worst_relaxed(51)
        expect = [_value(row) for row in r]
        for _ in range(k):
            r = np.asarray(fp.mont_sq(r))
            assert_clean(r)
            # Montgomery outputs lie in (-0.001p, 1.03p): fp.redc
            assert all(-P // 1000 < fp.int_from_limbs(row) < 103 * P // 100 for row in r)
            expect = [v * v % P for v in expect]
        assert fp.ints_from_limbs(np.asarray(fp.canon(r))) == [
            v * (1 << (fp.LIMBS * fp.LIMB_BITS)) % P for v in expect
        ]

    @pytest.mark.parametrize("k", [W, 2 * W])
    def test_fp2_sq_stays_relaxed(self, k):
        rows = _worst_relaxed(52)
        r = np.stack([rows, rows[::-1]], axis=1)  # (4, 2, 33): every pairing of signs
        expect = [(_value(c[0]), _value(c[1])) for c in r]
        for _ in range(k):
            r = np.asarray(tw.fp2_sq(r))
            assert_clean(r)
            assert all(
                -P // 1000 < fp.int_from_limbs(c) < 103 * P // 100 for row in r for c in row
            )
            expect = [F.fp2_sq(v) for v in expect]
        assert tw.fp2_to_ints(r) == expect

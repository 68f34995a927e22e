"""The Pallas Fp kernels (`ops/fp_pallas.py`) are the default hot path on
the chip and run nowhere else, so tier-1 pins them two ways on the CPU:

* interpret-mode differential against the XLA path of `ops/fp.py`, limb
  for limb, on the inputs the relaxed contract allows (negative limbs,
  accumulator sums, exact zero, a row count the wrapper must pad);
* each kernel still lowers for the TPU platform (a Mosaic custom call in
  the module), so an installed JAX that can no longer lower one fails
  here and not on the chip.

What the Mosaic compiler then makes of them only the chip can say:
`chip_smoke.py` checks verdicts there.
"""

import jax
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from lodestar_tpu.ops import fp, fp_pallas

ROWS = 700  # not a multiple of BLOCK: exercises the zero-row padding


def _relaxed(seed: int, rows: int = ROWS) -> np.ndarray:
    """Signed relaxed elements: |limb| <= 4095 + 66, |value| < 2.2p, with
    an exact-zero row."""
    rng = np.random.default_rng(seed)
    x = rng.integers(-66, 4096 + 66, size=(rows, fp.LIMBS), dtype=np.int64).astype(np.int32)
    x[:, 32] = 0
    x[:, 31] = rng.integers(-66, 800, size=rows)
    x[0] = 0
    return x


def _interpret(fn, *args) -> np.ndarray:
    with pltpu.force_tpu_interpret_mode():
        return np.asarray(jax.jit(fn)(*args))


A, B = _relaxed(1), _relaxed(2)
# a signed sum of three products, as the tower feeds redc
ACC = np.asarray(
    fp.acc_sub(fp.acc_add(fp.mul_acc(A, B), fp.sq_acc(B)), fp.mul_acc(B, A[::-1].copy()))
)
CASES = {
    "mul_acc": (fp_pallas.mul_acc, fp.mul_acc, (A, B)),
    "sq_acc": (fp_pallas.sq_acc, fp.sq_acc, (A,)),
    "redc": (fp_pallas.redc, fp.redc, (ACC,)),
    "mont_mul": (fp_pallas.mont_mul, fp.mont_mul, (A, B)),
    "mont_sq": (fp_pallas.mont_sq, fp.mont_sq, (A,)),
}


def test_cpu_backend_keeps_the_xla_path():
    # the reference below IS the XLA path only while this holds
    assert not fp_pallas.use_pallas()


@pytest.mark.parametrize("name", CASES)
def test_interpret_matches_xla_path(name):
    kernel, reference, args = CASES[name]
    got = _interpret(kernel, *args)
    want = np.asarray(reference(*args))
    assert got.shape == want.shape
    np.testing.assert_array_equal(got, want)
    # exact zero in, exact zero out (Jacobian infinity rides on this)
    assert not got[0].any()


def test_redc_value_is_montgomery_reduction():
    """Independent of `ops/fp.py`: big-int check of the reduced value."""
    r_inv = pow(1 << (fp.LIMBS * fp.LIMB_BITS), -1, fp.P)
    got = _interpret(fp_pallas.redc, ACC)
    for i in range(0, ROWS, 53):
        t, out = fp.int_from_limbs(ACC[i]), fp.int_from_limbs(got[i])
        assert (out - t * r_inv) % fp.P == 0
        assert -fp.P < out < 4 * fp.P


def test_leading_axes_and_broadcast():
    a = _relaxed(3, rows=6).reshape(2, 3, fp.LIMBS)
    b = _relaxed(4, rows=3)
    got = _interpret(fp_pallas.mont_mul, a, b)
    np.testing.assert_array_equal(got, np.asarray(fp.mont_mul(a, b)))


@pytest.mark.parametrize("name", CASES)
def test_kernel_lowers_for_tpu(name):
    kernel, _, args = CASES[name]
    text = jax.jit(kernel).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text

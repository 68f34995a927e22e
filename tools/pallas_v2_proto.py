"""Pallas v2 prototype: fused conv/redc kernels in sublane-major layout.

The r4 Pallas v1 failed because limbs sat on the LANE axis, making every
shifted-window access an expensive lane shift (see bench-perf notes).
v2 transposes in-kernel to (limbs on sublanes, batch on lanes): the
schoolbook convolution becomes 33 sublane ROLLS + broadcasts (VPU-native)
and the whole multiply runs in VMEM, killing both the (B, 1089) HBM
intermediate and the 66x-redundant band matmul of the XLA path.

Run on hardware:  python tools/pallas_v2_proto.py [batch] [chain]
Prints correctness vs ops/fp + per-op times for XLA vs Pallas.
"""

import sys
import time

import numpy as np

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from lodestar_tpu.ops import fp
from lodestar_tpu.utils import enable_compile_cache

enable_compile_cache()

B = int(sys.argv[1]) if len(sys.argv) > 1 else 4096 * 54
K = int(sys.argv[2]) if len(sys.argv) > 2 else 64
BB = 512  # batch block (lanes)

L = fp.LIMBS  # 33
A = fp.ACC_LIMBS  # 66
PPRIME = [int(v) for v in fp.PPRIME_LIMBS]
P_L = [int(v) for v in fp.P_LIMBS]
TWO_RP = np.asarray(fp._TWO_RP, dtype=np.int32)  # (66,)
TWO_P = np.asarray(fp._TWO_P, dtype=np.int32)  # (33,)


def _carry_once_rows(x, drop_top: bool):
    """Signed carry pass along the SUBLANE (row) axis of (rows, BB)."""
    c = x >> 12
    if not drop_top:
        c = jnp.concatenate([c[:-1], jnp.zeros_like(c[:1])], axis=0)
    lo = x - (c << 12)
    return lo + jnp.concatenate([jnp.zeros_like(c[:1]), c[:-1]], axis=0)


def _carry2_rows(x, drop_top: bool = False):
    return _carry_once_rows(_carry_once_rows(x, drop_top), drop_top)


def _conv_var(at, bt, out_rows: int):
    """Variable-variable schoolbook conv on transposed operands:
    at, bt (33, BB) -> (out_rows, BB) via 33 sublane rolls."""
    at_pad = jnp.pad(at, ((0, out_rows - L), (0, 0)))
    acc = jnp.zeros((out_rows, at.shape[1]), jnp.int32)
    for j in range(L):
        rolled = at_pad if j == 0 else jnp.roll(at_pad, j, axis=0)
        acc = acc + rolled * bt[j][None, :]  # zeros wrap in from the pad
    return acc


def _conv_const(xt, coeffs, out_rows: int):
    """Constant-coefficient conv: coeffs are python ints (scalars)."""
    x_pad = jnp.pad(xt, ((0, out_rows - xt.shape[0]), (0, 0)))
    acc = jnp.zeros((out_rows, xt.shape[1]), jnp.int32)
    for j in range(L):
        if coeffs[j] == 0:
            continue
        rolled = x_pad if j == 0 else jnp.roll(x_pad, j, axis=0)
        acc = acc + rolled * np.int32(coeffs[j])
    return acc


def _mul_acc_kernel(a_ref, b_ref, out_ref):
    at = a_ref[...].T  # (33, BB)
    bt = b_ref[...].T
    t = _carry2_rows(_conv_var(at, bt, A))
    out_ref[...] = t.T


def _redc_rows(t, two_rp_col, two_p_col):
    """(66, BB) acc -> (33, BB) relaxed element (ops/fp.redc, transposed)."""
    t = _carry_once_rows(t, False)
    # full-width conv then truncate: position >= 33 coefficients are
    # multiples of R (drop), but sublane ROLL would WRAP them in
    m = _carry2_rows(_conv_const(t[:L], PPRIME, A)[:L], drop_top=True)
    s = _carry2_rows(t + _conv_const(m, P_L, A) + two_rp_col)
    carry = (s[L - 1] >= 2048).astype(jnp.int32)
    hi = s[L:]
    hi = jnp.concatenate([hi[:1] + carry[None, :], hi[1:]], axis=0)
    return _carry_once_rows(hi - two_p_col, False)


def _redc_kernel(t_ref, two_rp_ref, two_p_ref, out_ref):
    out_ref[...] = _redc_rows(
        t_ref[...].T, two_rp_ref[...].T, two_p_ref[...].T
    ).T


def _mont_mul_kernel(a_ref, b_ref, two_rp_ref, two_p_ref, out_ref):
    at = a_ref[...].T
    bt = b_ref[...].T
    t = _carry2_rows(_conv_var(at, bt, A))
    out_ref[...] = _redc_rows(t, two_rp_ref[...].T, two_p_ref[...].T).T


_TWO_RP_IN = TWO_RP[None, :]  # (1, 66)
_TWO_P_IN = TWO_P[None, :]  # (1, 33)


def _call(kernel, out_limbs, *args, consts=()):
    b = args[0].shape[0]
    grid = (b // BB,)
    in_specs = [pl.BlockSpec((BB, x.shape[1]), lambda i: (i, 0)) for x in args]
    in_specs += [
        pl.BlockSpec((1, c.shape[1]), lambda i: (0, 0)) for c in consts
    ]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((BB, out_limbs), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, out_limbs), jnp.int32),
    )(*args, *consts)


def pallas_mul_acc(a, b):
    return _call(_mul_acc_kernel, A, a, b)


def pallas_redc(t):
    return _call(_redc_kernel, L, t, consts=(_TWO_RP_IN, _TWO_P_IN))


def pallas_mont_mul(a, b):
    return _call(_mont_mul_kernel, L, a, b, consts=(_TWO_RP_IN, _TWO_P_IN))


# --- correctness + bench ------------------------------------------------------

rng = np.random.default_rng(0)


def rand_fp(n):
    vals = [int.from_bytes(rng.bytes(47), "big") % fp.P for _ in range(n)]
    return jnp.asarray(fp.limbs_from_ints(vals))


def xla_mul_acc(x, y):
    """Explicit XLA body: fp.mont_mul would route back to Pallas on TPU."""
    return fp._carry2(fp._conv_pair(x, y))


def xla_redc(t):
    t = fp._carry_once(t)
    m = fp._carry2(fp._conv_pprime_low(t[..., : fp.LIMBS]), drop_top=True)
    s = fp._carry2(t + fp._conv_p_full(m) + jnp.asarray(fp._TWO_RP))
    carry = s[..., fp.LIMBS - 1] >= 2048
    hi = s[..., fp.LIMBS :]
    hi0 = hi[..., :1] + carry[..., None].astype(jnp.int32)
    hi = jnp.concatenate([hi0, hi[..., 1:]], axis=-1)
    return fp._carry_once(hi - jnp.asarray(fp._TWO_P))


def xla_mont_mul(x, y):
    return xla_redc(xla_mul_acc(x, y))


def main():
    n = max(BB * 2, (B // BB) * BB)
    a = rand_fp(n)
    b = rand_fp(n)

    # correctness vs the explicit XLA bodies (value-level: canon both)
    got = np.asarray(fp.canon(pallas_mont_mul(a[:BB], b[:BB])))
    want = np.asarray(fp.canon(xla_mont_mul(a[:BB], b[:BB])))
    print("mont_mul correct:", bool((got == want).all()), flush=True)
    got = np.asarray(pallas_mul_acc(a[:BB], b[:BB]))
    want = np.asarray(xla_mul_acc(a[:BB], b[:BB]))
    same_val = [
        fp.int_from_limbs(got[i].astype(np.int64)) == fp.int_from_limbs(want[i].astype(np.int64))
        for i in range(8)
    ]
    print("mul_acc value-correct:", all(same_val), flush=True)

    def chained(op):
        @jax.jit
        def f(x, y):
            for _ in range(K):
                x = op(x, y)
            return x[0, :1]

        return f

    def timeit(name, op, iters=3):
        f = chained(op)
        np.asarray(f(a, b))
        t0 = time.perf_counter()
        for _ in range(iters):
            np.asarray(f(a, b))
        dt = (time.perf_counter() - t0) / iters / K
        print(f"{name:28s} {dt*1e3:9.3f} ms/call", flush=True)
        return dt

    timeit("mont_mul XLA", xla_mont_mul)
    timeit("mont_mul PALLAS", pallas_mont_mul)
    timeit("mul_acc+redc XLA", lambda x, y: xla_redc(xla_mul_acc(x, y)))
    timeit("mul_acc+redc PALLAS", lambda x, y: pallas_redc(pallas_mul_acc(x, y)))


if __name__ == "__main__":
    main()

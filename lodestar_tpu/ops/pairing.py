"""Batched optimal ate pairing on BLS12-381 for TPU (JAX).

Device counterpart of the CPU oracle `lodestar_tpu.crypto.bls.pairing`
(1:1 differential-tested), replacing the blst pairing the reference calls
through `verifyMultipleSignatures`
(`packages/beacon-node/src/chain/bls/maybeBatch.ts:18`,
`packages/beacon-node/src/chain/bls/multithread/worker.ts:30`).

TPU-first design decisions (vs the oracle's affine loop):

* **Inversion-free Miller loop.** The oracle divides by 2y (doubling) and
  x_T - x_Q (addition) per step. A field inversion on device is a 381-step
  Fermat chain — ruinous inside the 63-iteration loop. Instead the running
  point T stays in **Jacobian coordinates** over Fp2 and every line is
  scaled by its Fp2 denominator (2YZ^3 for doubling, Z*H for addition).
  Scaling lines by Fp2 elements is free: Fp2 lies in a proper subfield of
  Fp12, so the factor is annihilated by the easy part of the final
  exponentiation — the same argument the oracle already uses to scale
  lines by xi and drop vertical lines (see its module docstring).
* **One traced step.** The loop body is a `lax.scan` over the static bit
  array of |x|, with the (rare: 6 of 63) addition step under `lax.cond` —
  the graph contains each step once regardless of bit pattern, and the
  whole batch advances in lockstep.
* **Lazy reduction** (round 5): the sparse line multiplication runs in the
  accumulator domain of ops/fp.py — its 14 Fp2 products stay unreduced
  through the Fp6/Fp12 combine and ONE stacked Montgomery reduction
  materializes the 12 output coefficients (ops/tower.py docstring).
* The final exponentiation mirrors the oracle's cubed-pairing HHT hard
  part op-for-op, so device and oracle outputs are **equal Fp12 elements**,
  not merely equivalent predicates. `f^|x|` is a scan with conditional
  multiply; the two Fp12 inversions (easy part) are the only Fermat chains
  in the whole pairing.

Line representation: c0 + c3*w^3 + c5*w^5 with c_i in Fp2 (the sparse
untwist layout of the oracle's `_sparse_line`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from lodestar_tpu.crypto.bls.fields import BLS_X_ABS

from . import curve as cv
from . import fp
from . import tower as tw

__all__ = [
    "miller_loop",
    "final_exponentiation",
    "pairing",
    "fp12_product_fold",
    "multi_pairing_is_one",
]

# Bits of |x| below the MSB, MSB first (same schedule as the oracle).
_X_BITS = np.array([int(b) for b in bin(BLS_X_ABS)[3:]], dtype=np.int32)


def _mul_by_line(f, c0, c3, c5):
    """f * (c0 + c3 w^3 + c5 w^5), entirely in the accumulator domain.

    Sparse multiplication exploiting the line's zero slots: with
    l0 = (c0,0,0) and l1 = (0,c3,c5) in the Fp6[w] halves,
      t0    = a0*l0           (coefficient-wise scale by c0)
      t1    = a1*l1           (sparse Fp6 mul)
      cross = (a0+a1)(l0+l1)  (dense Fp6 mul; l0+l1 = (c0,c3,c5))
    The 8 non-dense Fp2 products ride ONE stacked fp2_mul_acc; `cross`
    rides the stacked fp6_mul_acc; 12 reductions total.
    """
    a0, a1 = f[..., 0, :, :, :], f[..., 1, :, :, :]
    x0, x1, x2 = a1[..., 0, :, :], a1[..., 1, :, :], a1[..., 2, :, :]

    # 8 Fp2 products in one dispatch: a0 coefficient-wise * c0 (3), and
    # the 5 products of the sparse a1 * (0, c3, c5) Karatsuba
    y0, y1, y2 = a0[..., 0, :, :], a0[..., 1, :, :], a0[..., 2, :, :]
    lhs = jnp.stack(
        [y0, y1, y2, x1, x2, tw.fp2_add(x1, x2), tw.fp2_add(x0, x1),
         tw.fp2_add(x0, x2)],
        axis=-3,
    )
    rhs = jnp.stack(
        [c0, c0, c0, c3, c5, tw.fp2_add(c3, c5), c3, c5], axis=-3
    )
    m = tw.fp2_mul_acc(lhs, rhs)
    t0 = m[..., 0:3, :, :]  # (.., 3, 2, 66) Fp6 accumulator
    p1, p2, m12, m01, m02 = (m[..., 3 + i, :, :] for i in range(5))
    d0 = tw._a2_mul_xi(fp.acc_sub(m12, fp.acc_add(p1, p2)))
    d1 = fp.acc_add(fp.acc_sub(m01, p1), tw._a2_mul_xi(p2))
    d2 = fp.acc_add(fp.acc_sub(m02, p2), p1)
    t1 = jnp.stack([d0, d1, d2], axis=-3)

    # cross = (a0 + a1) * (c0, c3, c5) dense
    cross = tw.fp6_mul_acc(tw.fp6_add(a0, a1), jnp.stack([c0, c3, c5], axis=-3))
    r0 = fp.acc_add(t0, tw._a6_mul_by_v(t1))
    r1 = fp.acc_sub(cross, fp.acc_add(t0, t1))
    return fp.redc(jnp.stack([r0, r1], axis=-4))


def _fp2_triple(a):
    return tw.fp2_add(tw.fp2_add(a, a), a)


@jax.jit
def miller_loop(p_aff, q_aff):
    """Batched f_{|x|,Q}(P), conjugated for the negative BLS parameter.

    p_aff: (xp, yp) G1 affine, mont-form (.., 33) limb arrays.
    q_aff: (xq, yq) twist affine over Fp2, (.., 2, 33) arrays.
    Neither input may encode infinity (callers mask separately, as the
    oracle's `pairing` does for None inputs).

    Matches `crypto.bls.pairing.miller_loop` exactly up to the line
    denominators (2YZ^3 / Z*H per step), which vanish under
    `final_exponentiation`.
    """
    with jax.named_scope("bls.miller"):
        xp, yp = p_aff
        xq, yq = q_aff
        one2 = tw.fp2_one(xq.shape[:-2])

        # T starts at Q (Jacobian, Z = 1 in Fp2)
        T = (xq, yq, jnp.broadcast_to(one2, xq.shape))
        f = tw.fp12_one(xp.shape[:-1])

        bits = jnp.asarray(_X_BITS)

        def dbl_line(T):
            X, Y, Z = T
            Z2 = tw.fp2_sq(Z)
            Y2 = tw.fp2_sq(Y)
            X2 = tw.fp2_sq(X)
            YZ3 = tw.fp2_mul(Y, tw.fp2_mul(Z, Z2))
            X3cube = tw.fp2_mul(X, X2)
            # c0 = 2*Y*Z^3 * xi * yP ; c3 = 3X^3 - 2Y^2 ; c5 = -3X^2Z^2 * xP
            c0 = tw.fp2_mul_fp(tw.fp2_mul_xi(tw.fp2_add(YZ3, YZ3)), yp)
            c3 = tw.fp2_sub(_fp2_triple(X3cube), tw.fp2_add(Y2, Y2))
            c5 = tw.fp2_neg(tw.fp2_mul_fp(_fp2_triple(tw.fp2_mul(X2, Z2)), xp))
            return c0, c3, c5

        def add_line(T):
            X, Y, Z = T
            Z2 = tw.fp2_sq(Z)
            Z3 = tw.fp2_mul(Z, Z2)
            theta = tw.fp2_sub(Y, tw.fp2_mul(yq, Z3))  # Y - yQ Z^3
            H = tw.fp2_sub(X, tw.fp2_mul(xq, Z2))  # X - xQ Z^2
            ZH = tw.fp2_mul(Z, H)
            c0 = tw.fp2_mul_fp(tw.fp2_mul_xi(ZH), yp)
            c3 = tw.fp2_sub(tw.fp2_mul(theta, xq), tw.fp2_mul(ZH, yq))
            c5 = tw.fp2_neg(tw.fp2_mul_fp(theta, xp))
            return c0, c3, c5

        def body(carry, bit):
            f, T = carry
            # doubling step: f <- f^2 * l_{T,T}(P); T <- 2T
            c0, c3, c5 = dbl_line(T)
            f = _mul_by_line(tw.fp12_sq(f), c0, c3, c5)
            T = cv.jac_double(cv.F2, T)

            def add_step(args):
                f, T = args
                c0, c3, c5 = add_line(T)
                f = _mul_by_line(f, c0, c3, c5)
                T = cv.jac_add_mixed(cv.F2, T, (xq, yq), one2)
                return f, T

            f, T = jax.lax.cond(bit != 0, add_step, lambda a: a, (f, T))
            return (f, T), None

        (f, _), _ = jax.lax.scan(body, (f, T), bits)
        # negative parameter: conjugate
        return tw.fp12_conj(f)


# --- final exponentiation ----------------------------------------------------


def _pow_u(f):
    """f^|x| — scan over the static bit schedule (square, cond-multiply)."""
    bits = jnp.asarray(_X_BITS)

    def body(r, bit):
        r = tw.fp12_sq(r)
        r = jax.lax.cond(bit != 0, lambda r: tw.fp12_mul(r, f), lambda r: r, r)
        return r, None

    r, _ = jax.lax.scan(body, f, bits)
    return r


def _pow_x(f):
    return tw.fp12_conj(_pow_u(f))


def _pow_xm1(f):
    return tw.fp12_conj(tw.fp12_mul(_pow_u(f), f))


@jax.jit
def final_exponentiation(f):
    """f^(3*(p^12-1)/r) — byte-exact mirror of the oracle's HHT hard part
    (`crypto/bls/pairing.py:112`); the cube keeps pairing-product equality
    semantics unchanged (gcd(3, r) = 1)."""
    with jax.named_scope("bls.final_exp"):
        with jax.named_scope("easy"):  # f^((p^6-1)(p^2+1))
            f = tw.fp12_mul(tw.fp12_conj(f), tw.fp12_inv(f))
            f = tw.fp12_mul(tw.fp12_frobenius(f, 2), f)
        with jax.named_scope("hard"):  # cyclotomic: inverse == conjugate
            y = _pow_xm1(f)
            y = _pow_xm1(y)
            y = tw.fp12_mul(_pow_x(y), tw.fp12_frobenius(y, 1))
            y = tw.fp12_mul(
                tw.fp12_mul(_pow_x(_pow_x(y)), tw.fp12_frobenius(y, 2)),
                tw.fp12_conj(y),
            )
            f3 = tw.fp12_mul(tw.fp12_mul(f, f), f)
            return tw.fp12_mul(y, f3)


def pairing(p_aff, q_aff):
    """Full batched (cubed) ate pairing e(P, Q)^3; no infinity inputs."""
    # two programs, two counted launches (lazy import: prep pulls in the
    # host oracle modules, which this module must not load at import)
    from . import prep

    return prep._dispatch(final_exponentiation, prep._dispatch(miller_loop, p_aff, q_aff))


def fp12_product_fold(f, mask=None):
    """Product of a batch of Fp12 values down axis 0 (tree fold).

    f: (B, 2, 3, 2, 33), or (B, ..., 2, 3, 2, 33) with further batch axes
    that the fold carries (the multi-job launch's slots). mask: optional
    (B,) bool — False entries are replaced with one (the device analogue
    of the oracle's skip-infinity in `multi_pairing`). B is padded to a
    power of two with ones. Returns f's shape without axis 0.
    """
    with jax.named_scope("bls.fold"):
        if mask is not None:
            ones = tw.fp12_one(f.shape[:1])
            f = jnp.where(mask[..., None, None, None, None], f, ones)
        b = f.shape[0]
        size = 1 if b <= 1 else 1 << (b - 1).bit_length()
        if size != b:
            pad_ones = tw.fp12_one((size - b,) + f.shape[1:-4])
            f = jnp.concatenate([f, pad_ones], axis=0)
        while f.shape[0] > 1:
            half = f.shape[0] // 2
            f = tw.fp12_mul(f[:half], f[half:])
        return f[0]


@jax.jit
def multi_pairing_is_one(p_aff, q_aff, mask=None):
    """Batch predicate prod_i e(P_i, Q_i) == 1 with ONE shared final
    exponentiation — the batch-verify core, same amortization as blst's
    `verifyMultipleSignatures` (`maybeBatch.ts:18`).

    p_aff/q_aff: batched affine points (batch axis 0). mask: optional (B,)
    bool, False = skip pair (treat as infinity).
    """
    fs = miller_loop(p_aff, q_aff)
    f = fp12_product_fold(fs, mask=mask)
    return tw.fp12_eq_one(final_exponentiation(f))

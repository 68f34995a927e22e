"""BLS12-381 field tower arithmetic (pure-Python reference implementation).

This is the host-side CPU oracle for the TPU (JAX/Pallas) kernels in
``lodestar_tpu.ops`` and the fallback verifier used when no device is present —
the same role ``@chainsafe/bls`` herumi (WASM) plays in the reference
implementation (see reference `packages/beacon-node/src/chain/bls/multithread/index.ts:127-132`
impl switch, and `packages/light-client/src/index.ts:160` initBls fallback).

Functional style (plain ints / tuples) on purpose: every function here has a
1:1 vectorized counterpart in ``lodestar_tpu/ops`` operating on limb arrays,
which makes differential testing of intermediates trivial.

Tower construction (standard for BLS12-381):
  Fp2  = Fp[u]  / (u^2 + 1)
  Fp6  = Fp2[v] / (v^3 - (u + 1))
  Fp12 = Fp6[w] / (w^2 - v)

All Fp2 elements are (c0, c1) tuples, Fp6 are 3-tuples of Fp2, Fp12 are
2-tuples of Fp6.
"""

from __future__ import annotations

# --- Curve constants -------------------------------------------------------
# Base field modulus p, subgroup order r, and the BLS parameter x (negative).
P = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
BLS_X = -0xD201000000010000  # the BLS family parameter; negative for BLS12-381
BLS_X_ABS = -BLS_X

# Consistency checks of the family construction (these tie P, R, BLS_X
# together; if any memorized constant were wrong these would fail at import):
#   r = x^4 - x^2 + 1
#   p = (x - 1)^2 * r / 3 + x
assert R == BLS_X**4 - BLS_X**2 + 1
assert P == (BLS_X - 1) ** 2 * R // 3 + BLS_X
assert P % 4 == 3  # sqrt in Fp via a^((p+1)/4)

# G1 curve: y^2 = x^3 + 4.  G2 (M-twist): y^2 = x^3 + 4(u+1) over Fp2.
B_G1 = 4
XI = (1, 1)  # u + 1, the sextic-twist / Fp6 non-residue

# --- Fp --------------------------------------------------------------------


def fp_add(a: int, b: int) -> int:
    return (a + b) % P


def fp_sub(a: int, b: int) -> int:
    return (a - b) % P


def fp_mul(a: int, b: int) -> int:
    return (a * b) % P


def fp_neg(a: int) -> int:
    return (-a) % P


def fp_inv(a: int) -> int:
    if a % P == 0:
        raise ZeroDivisionError("inverse of 0 in Fp")
    return pow(a, P - 2, P)


def fp_sqrt(a: int) -> int | None:
    """Square root in Fp (p ≡ 3 mod 4), or None if a is a non-residue."""
    c = pow(a, (P + 1) // 4, P)
    return c if c * c % P == a % P else None


# --- Fp2 = Fp[u]/(u^2+1) ---------------------------------------------------

FP2_ZERO = (0, 0)
FP2_ONE = (1, 0)


def fp2_add(a, b):
    return ((a[0] + b[0]) % P, (a[1] + b[1]) % P)


def fp2_sub(a, b):
    return ((a[0] - b[0]) % P, (a[1] - b[1]) % P)


def fp2_neg(a):
    return ((-a[0]) % P, (-a[1]) % P)


def fp2_conj(a):
    """Conjugate c0 - c1*u == Frobenius (a^p), since u^p = -u for p ≡ 3 mod 4."""
    return (a[0], (-a[1]) % P)


def fp2_mul(a, b):
    a0, a1 = a
    b0, b1 = b
    t0 = a0 * b0
    t1 = a1 * b1
    # (a0+a1)(b0+b1) - t0 - t1 = a0*b1 + a1*b0 (Karatsuba)
    return ((t0 - t1) % P, ((a0 + a1) * (b0 + b1) - t0 - t1) % P)


def fp2_sq(a):
    a0, a1 = a
    # (a0 + a1 u)^2 = (a0+a1)(a0-a1) + 2 a0 a1 u
    return ((a0 + a1) * (a0 - a1) % P, 2 * a0 * a1 % P)


def fp2_mul_scalar(a, k: int):
    return (a[0] * k % P, a[1] * k % P)


def fp2_mul_xi(a):
    """Multiply by xi = u + 1: (c0 - c1) + (c0 + c1) u."""
    return ((a[0] - a[1]) % P, (a[0] + a[1]) % P)


def fp2_inv(a):
    a0, a1 = a
    norm = (a0 * a0 + a1 * a1) % P  # a * conj(a) = a0^2 + a1^2
    ninv = fp_inv(norm)
    return (a0 * ninv % P, (-a1) * ninv % P)


def fp2_eq(a, b) -> bool:
    return a[0] % P == b[0] % P and a[1] % P == b[1] % P


def fp2_is_zero(a) -> bool:
    return a[0] % P == 0 and a[1] % P == 0


def fp2_pow(a, e: int):
    result = FP2_ONE
    base = a
    while e > 0:
        if e & 1:
            result = fp2_mul(result, base)
        base = fp2_sq(base)
        e >>= 1
    return result


def fp2_legendre(a) -> int:
    """Euler criterion in Fp2: a^((p^2-1)/2) is 1 (QR), p^2-1≡-1 (QNR), or 0."""
    t = fp2_pow(a, (P * P - 1) // 2)
    if fp2_eq(t, FP2_ONE):
        return 1
    if fp2_is_zero(t):
        return 0
    return -1


def _find_fp2_nonresidue():
    # small search; (u + k) for small k quickly yields a QNR
    for k in range(1, 20):
        cand = (k, 1)
        if fp2_legendre(cand) == -1:
            return cand
    raise RuntimeError("no Fp2 non-residue found")  # pragma: no cover


_FP2_QNR = _find_fp2_nonresidue()
# Tonelli-Shanks precomputation for Fp2: p^2 - 1 = Q * 2^S with Q odd
_TS_S = 3  # v2(p-1)=1, v2(p+1)=2
_TS_Q = (P * P - 1) >> _TS_S
assert _TS_Q & 1 == 1
_TS_Z = fp2_pow(_FP2_QNR, _TS_Q)  # generator of the 2-Sylow subgroup


def fp2_sqrt(a):
    """Square root in Fp2 via Tonelli-Shanks (S=3), or None for non-residues."""
    if fp2_is_zero(a):
        return FP2_ZERO
    if fp2_legendre(a) != 1:
        return None
    m = _TS_S
    c = _TS_Z
    t = fp2_pow(a, _TS_Q)
    r_ = fp2_pow(a, (_TS_Q + 1) // 2)
    while not fp2_eq(t, FP2_ONE):
        # find least i with t^(2^i) == 1
        i = 0
        t2 = t
        while not fp2_eq(t2, FP2_ONE):
            t2 = fp2_sq(t2)
            i += 1
        b = c
        for _ in range(m - i - 1):
            b = fp2_sq(b)
        m = i
        c = fp2_sq(b)
        t = fp2_mul(t, c)
        r_ = fp2_mul(r_, b)
    return r_


# --- Fp6 = Fp2[v]/(v^3 - xi) ----------------------------------------------

FP6_ZERO = (FP2_ZERO, FP2_ZERO, FP2_ZERO)
FP6_ONE = (FP2_ONE, FP2_ZERO, FP2_ZERO)


def fp6_add(a, b):
    return (fp2_add(a[0], b[0]), fp2_add(a[1], b[1]), fp2_add(a[2], b[2]))


def fp6_sub(a, b):
    return (fp2_sub(a[0], b[0]), fp2_sub(a[1], b[1]), fp2_sub(a[2], b[2]))


def fp6_neg(a):
    return (fp2_neg(a[0]), fp2_neg(a[1]), fp2_neg(a[2]))


def fp6_mul(a, b):
    a0, a1, a2 = a
    b0, b1, b2 = b
    t0 = fp2_mul(a0, b0)
    t1 = fp2_mul(a1, b1)
    t2 = fp2_mul(a2, b2)
    # c0 = t0 + xi*((a1+a2)(b1+b2) - t1 - t2)
    c0 = fp2_add(t0, fp2_mul_xi(fp2_sub(fp2_sub(fp2_mul(fp2_add(a1, a2), fp2_add(b1, b2)), t1), t2)))
    # c1 = (a0+a1)(b0+b1) - t0 - t1 + xi*t2
    c1 = fp2_add(fp2_sub(fp2_sub(fp2_mul(fp2_add(a0, a1), fp2_add(b0, b1)), t0), t1), fp2_mul_xi(t2))
    # c2 = (a0+a2)(b0+b2) - t0 - t2 + t1
    c2 = fp2_add(fp2_sub(fp2_sub(fp2_mul(fp2_add(a0, a2), fp2_add(b0, b2)), t0), t2), t1)
    return (c0, c1, c2)


def fp6_sq(a):
    return fp6_mul(a, a)


def fp6_mul_by_v(a):
    """Multiply by v: (a0, a1, a2) -> (xi*a2, a0, a1)."""
    return (fp2_mul_xi(a[2]), a[0], a[1])


def fp6_inv(a):
    a0, a1, a2 = a
    # Standard: c0 = a0^2 - xi a1 a2, c1 = xi a2^2 - a0 a1, c2 = a1^2 - a0 a2
    c0 = fp2_sub(fp2_sq(a0), fp2_mul_xi(fp2_mul(a1, a2)))
    c1 = fp2_sub(fp2_mul_xi(fp2_sq(a2)), fp2_mul(a0, a1))
    c2 = fp2_sub(fp2_sq(a1), fp2_mul(a0, a2))
    # t = a0 c0 + xi (a2 c1 + a1 c2)
    t = fp2_add(fp2_mul(a0, c0), fp2_mul_xi(fp2_add(fp2_mul(a2, c1), fp2_mul(a1, c2))))
    tinv = fp2_inv(t)
    return (fp2_mul(c0, tinv), fp2_mul(c1, tinv), fp2_mul(c2, tinv))


def fp6_eq(a, b) -> bool:
    return all(fp2_eq(x, y) for x, y in zip(a, b))


# --- Fp12 = Fp6[w]/(w^2 - v) -----------------------------------------------

FP12_ZERO = (FP6_ZERO, FP6_ZERO)
FP12_ONE = (FP6_ONE, FP6_ZERO)


def fp12_add(a, b):
    return (fp6_add(a[0], b[0]), fp6_add(a[1], b[1]))


def fp12_mul(a, b):
    a0, a1 = a
    b0, b1 = b
    t0 = fp6_mul(a0, b0)
    t1 = fp6_mul(a1, b1)
    c0 = fp6_add(t0, fp6_mul_by_v(t1))
    c1 = fp6_sub(fp6_sub(fp6_mul(fp6_add(a0, a1), fp6_add(b0, b1)), t0), t1)
    return (c0, c1)


def fp12_sq(a):
    return fp12_mul(a, a)


def fp12_conj(a):
    """Conjugation over Fp6 (i.e. a^(p^6)): (a0, -a1).

    For elements in the cyclotomic subgroup (post easy-part of the final
    exponentiation) this equals the inverse.
    """
    return (a[0], fp6_neg(a[1]))


def fp12_inv(a):
    a0, a1 = a
    # 1/(a0 + a1 w) = (a0 - a1 w) / (a0^2 - v a1^2)
    t = fp6_sub(fp6_sq(a0), fp6_mul_by_v(fp6_sq(a1)))
    tinv = fp6_inv(t)
    return (fp6_mul(a0, tinv), fp6_neg(fp6_mul(a1, tinv)))


def fp12_eq(a, b) -> bool:
    return fp6_eq(a[0], b[0]) and fp6_eq(a[1], b[1])


def fp12_pow(a, e: int):
    if e < 0:
        return fp12_pow(fp12_inv(a), -e)
    result = FP12_ONE
    base = a
    while e > 0:
        if e & 1:
            result = fp12_mul(result, base)
        base = fp12_sq(base)
        e >>= 1
    return result


# --- Frobenius endomorphism on Fp12 ---------------------------------------
# a^p computed coefficient-wise. For a = sum_{i<6} c_i * w^i with c_i in Fp2
# (w^2 = v, v^3 = xi, w^6 = xi), Frobenius maps c_i -> conj(c_i) * g_i where
# g_i = xi^(i*(p-1)/6) -- all computable at runtime, no magic tables.

_FROB_COEFF = tuple(fp2_pow(XI, i * (P - 1) // 6) for i in range(6))


def _fp12_to_w_coeffs(a):
    """Fp12 as ((c0,c2,c4),(c1,c3,c5)) over w-powers: a = sum c_i w^i."""
    (a00, a01, a02), (a10, a11, a12) = a
    return (a00, a10, a01, a11, a02, a12)


def _fp12_from_w_coeffs(c):
    return ((c[0], c[2], c[4]), (c[1], c[3], c[5]))


def fp12_frobenius(a, power: int = 1):
    """a^(p^power) for 1 <= power < 12."""
    out = a
    for _ in range(power % 12):
        coeffs = _fp12_to_w_coeffs(out)
        new = tuple(fp2_mul(fp2_conj(c), _FROB_COEFF[i]) for i, c in enumerate(coeffs))
        out = _fp12_from_w_coeffs(new)
    return out

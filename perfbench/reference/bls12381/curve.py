"""BLS12-381 curve groups G1 (over Fp) and G2 (over Fp2, M-twist).

Pure-Python reference; affine coordinates with None = point at infinity.
Counterpart of the blst C library's G1/G2 layer that the reference consumes
through `@chainsafe/bls` (reference `packages/beacon-node/src/chain/bls/maybeBatch.ts:18`).

G1: y^2 = x^3 + 4           over Fp
G2: y^2 = x^3 + 4(u+1)      over Fp2  (sextic M-twist)
"""

from __future__ import annotations

from . import fields as F
from .fields import P, R, BLS_X

# --- Standard generators (IETF / ZCash BLS12-381 ciphersuite) --------------
# Verified below at import: on-curve and of order R.
G1_GEN = (
    0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB,
    0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1,
)
G2_GEN = (
    (
        0x024AA2B2F08F0A91260805272DC51051C6E47AD4FA403B02B4510B647AE3D1770BAC0326A805BBEFD48056C8C121BDB8,
        0x13E02B6052719F607DACD3A088274F65596BD0D09920B61AB5DA61BBDC7F5049334CF11213945D57E5AC7D055D042B7E,
    ),
    (
        0x0CE5D527727D6E118CC9CDC6DA2E351AADFD9BAA8CBDD3A76D429A695160D12C923AC9CC3BACA289E193548608B82801,
        0x0606C4A02EA734CC32ACD2B02BC28B99CB3E287E85A763AF267492AB572E99AB3F370D275CEC1DA1AAA9075FF05F79BE,
    ),
)

# G2 curve coefficient b' = 4 * (u + 1)
B_G2 = (4, 4)

# Cofactors from the BLS12 family polynomials (checked against the curve
# orders below; h1 formula also cross-checked against #E(Fp) = p + 1 - t).
H1 = (BLS_X - 1) ** 2 // 3
H2 = (BLS_X**8 - 4 * BLS_X**7 + 5 * BLS_X**6 - 4 * BLS_X**4 + 6 * BLS_X**3 - 4 * BLS_X**2 - 4 * BLS_X + 13) // 9
_TRACE = BLS_X + 1
assert H1 * R == P + 1 - _TRACE  # #E(Fp)


# --- G1 --------------------------------------------------------------------


def g1_is_on_curve(pt) -> bool:
    if pt is None:
        return True
    x, y = pt
    return (y * y - x * x * x - 4) % P == 0


def g1_neg(pt):
    if pt is None:
        return None
    return (pt[0], (-pt[1]) % P)


def g1_double(pt):
    if pt is None:
        return None
    x, y = pt
    if y == 0:
        return None
    lam = 3 * x * x * F.fp_inv(2 * y % P) % P
    x3 = (lam * lam - 2 * x) % P
    y3 = (lam * (x - x3) - y) % P
    return (x3, y3)


def g1_add(p1, p2):
    if p1 is None:
        return p2
    if p2 is None:
        return p1
    x1, y1 = p1
    x2, y2 = p2
    if x1 == x2:
        if (y1 + y2) % P == 0:
            return None
        return g1_double(p1)
    lam = (y2 - y1) * F.fp_inv((x2 - x1) % P) % P
    x3 = (lam * lam - x1 - x2) % P
    y3 = (lam * (x1 - x3) - y1) % P
    return (x3, y3)


def g1_mul(pt, k: int):
    return g1_mul_raw(pt, k % R)


# -- Jacobian ladders ---------------------------------------------------------
# Scalar multiplication runs inversion-FREE in Jacobian coordinates with a
# single field inversion at the end: the affine double-and-add above costs
# one ~381-bit modexp inversion PER STEP (~0.3 ms), which made every
# hash-to-curve h_eff clearing (~900 steps) and subgroup check take ~0.3 s
# — the dominant host cost of batch-verify preparation. Formulas:
# dbl-2009-l and add-2007-bl for a=0 short Weierstrass curves.


def _jac_double(X, Y, Z, mul, sq, addf, subf, dbl):
    A = sq(X)
    B = sq(Y)
    C = sq(B)
    D = dbl(subf(subf(sq(addf(X, B)), A), C))
    E = addf(dbl(A), A)  # 3A
    F_ = sq(E)
    X3 = subf(F_, dbl(D))
    Y3 = subf(mul(E, subf(D, X3)), dbl(dbl(dbl(C))))  # E(D-X3) - 8C
    Z3 = dbl(mul(Y, Z))
    return X3, Y3, Z3


def _jac_add(P1, P2, mul, sq, addf, subf, dbl, is_zero):
    X1, Y1, Z1 = P1
    X2, Y2, Z2 = P2
    Z1Z1 = sq(Z1)
    Z2Z2 = sq(Z2)
    U1 = mul(X1, Z2Z2)
    U2 = mul(X2, Z1Z1)
    S1 = mul(Y1, mul(Z2, Z2Z2))
    S2 = mul(Y2, mul(Z1, Z1Z1))
    H = subf(U2, U1)
    r = dbl(subf(S2, S1))
    if is_zero(H):
        if is_zero(r):
            return _jac_double(X1, Y1, Z1, mul, sq, addf, subf, dbl)
        return None  # P + (-P) = infinity
    I = sq(dbl(H))
    J = mul(H, I)
    V = mul(U1, I)
    X3 = subf(subf(sq(r), J), dbl(V))
    Y3 = subf(mul(r, subf(V, X3)), dbl(mul(S1, J)))
    Z3 = mul(subf(subf(sq(addf(Z1, Z2)), Z1Z1), Z2Z2), H)
    return X3, Y3, Z3


def _jac_mul(pt_affine, k, one, mul, sq, addf, subf, dbl, is_zero, inv):
    """Affine point -> affine point*k via a Jacobian double-and-add with
    one inversion at the end. Returns None for infinity."""
    acc = None  # Jacobian accumulator, None = infinity
    add_pt = (pt_affine[0], pt_affine[1], one)
    while k:
        if k & 1:
            acc = add_pt if acc is None else _jac_add(acc, add_pt, mul, sq, addf, subf, dbl, is_zero)
        k >>= 1
        if k:
            add_pt = _jac_double(*add_pt, mul, sq, addf, subf, dbl)
    if acc is None or is_zero(acc[2]):
        return None
    X, Y, Z = acc
    zinv = inv(Z)
    zinv2 = sq(zinv)
    return mul(X, zinv2), mul(Y, mul(zinv, zinv2))


def g1_mul_raw(pt, k: int):
    """Scalar mul WITHOUT reducing k mod R (for cofactor clearing)."""
    if pt is None or k == 0:
        return None
    if k < 0:
        return g1_mul_raw(g1_neg(pt), -k)
    return _jac_mul(
        pt,
        k,
        1,
        lambda a, b: a * b % P,
        lambda a: a * a % P,
        lambda a, b: (a + b) % P,
        lambda a, b: (a - b) % P,
        lambda a: 2 * a % P,
        lambda a: a % P == 0,
        F.fp_inv,
    )


def g1_in_subgroup(pt) -> bool:
    """φ-eigenvalue subgroup membership (order-R ladder retained as
    g1_in_subgroup_order_check for differential tests)."""
    return g1_in_subgroup_fast(pt)


def g1_in_subgroup_order_check(pt) -> bool:
    return g1_is_on_curve(pt) and g1_mul_raw(pt, R) is None


def g1_eq(p1, p2) -> bool:
    if p1 is None or p2 is None:
        return p1 is None and p2 is None
    return p1[0] % P == p2[0] % P and p1[1] % P == p2[1] % P


# --- G2 --------------------------------------------------------------------


def g2_rhs(x):
    """Twist curve RHS: x^3 + 4(u+1)."""
    return F.fp2_add(F.fp2_mul(F.fp2_sq(x), x), B_G2)


def g2_is_on_curve(pt) -> bool:
    if pt is None:
        return True
    x, y = pt
    return F.fp2_eq(F.fp2_sq(y), g2_rhs(x))


def g2_neg(pt):
    if pt is None:
        return None
    return (pt[0], F.fp2_neg(pt[1]))


def g2_double(pt):
    if pt is None:
        return None
    x, y = pt
    if F.fp2_is_zero(y):
        return None
    lam = F.fp2_mul(F.fp2_mul_scalar(F.fp2_sq(x), 3), F.fp2_inv(F.fp2_mul_scalar(y, 2)))
    x3 = F.fp2_sub(F.fp2_sq(lam), F.fp2_mul_scalar(x, 2))
    y3 = F.fp2_sub(F.fp2_mul(lam, F.fp2_sub(x, x3)), y)
    return (x3, y3)


def g2_add(p1, p2):
    if p1 is None:
        return p2
    if p2 is None:
        return p1
    x1, y1 = p1
    x2, y2 = p2
    if F.fp2_eq(x1, x2):
        if F.fp2_is_zero(F.fp2_add(y1, y2)):
            return None
        return g2_double(p1)
    lam = F.fp2_mul(F.fp2_sub(y2, y1), F.fp2_inv(F.fp2_sub(x2, x1)))
    x3 = F.fp2_sub(F.fp2_sub(F.fp2_sq(lam), x1), x2)
    y3 = F.fp2_sub(F.fp2_mul(lam, F.fp2_sub(x1, x3)), y1)
    return (x3, y3)


def g2_mul_raw(pt, k: int):
    """Scalar mul WITHOUT reducing k mod R (Jacobian ladder, one fp2
    inversion total — see the G1 ladder note)."""
    if pt is None or k == 0:
        return None
    if k < 0:
        return g2_mul_raw(g2_neg(pt), -k)
    return _jac_mul(
        pt,
        k,
        F.FP2_ONE,
        F.fp2_mul,
        F.fp2_sq,
        F.fp2_add,
        F.fp2_sub,
        lambda a: F.fp2_add(a, a),
        F.fp2_is_zero,
        F.fp2_inv,
    )


def g2_mul(pt, k: int):
    return g2_mul_raw(pt, k % R)


def g2_in_subgroup(pt) -> bool:
    """ψ-eigenvalue subgroup membership (order-R ladder retained as
    g2_in_subgroup_order_check for differential tests)."""
    return g2_in_subgroup_fast(pt)


def g2_in_subgroup_order_check(pt) -> bool:
    return g2_is_on_curve(pt) and g2_mul_raw(pt, R) is None


def g2_eq(p1, p2) -> bool:
    if p1 is None or p2 is None:
        return p1 is None and p2 is None
    return F.fp2_eq(p1[0], p2[0]) and F.fp2_eq(p1[1], p2[1])


def g1_clear_cofactor(pt):
    return g1_mul_raw(pt, H1)


# --- import-time sanity checks --------------------------------------------
# --- psi endomorphism (G2) ----------------------------------------------------
# The untwist-Frobenius-twist endomorphism psi on the M-twist: psi(x, y) =
# (conj(x) * CX, conj(y) * CY) with CX = 1/(1+u)^((p-1)/3),
# CY = 1/(1+u)^((p-1)/2) — computed from the curve constants at import, no
# tabulated magic values. Powers the Budroni–Pintore fast cofactor
# clearing (RFC 9380 App. G.3) and the [x]-eigenvalue subgroup check,
# replacing 636/255-bit scalar ladders with 64-bit ones.

_PSI_CX = F.fp2_pow(F.fp2_inv((1, 1)), (P - 1) // 3)
_PSI_CY = F.fp2_pow(F.fp2_inv((1, 1)), (P - 1) // 2)


def g2_psi(pt):
    if pt is None:
        return None
    x, y = pt
    return (F.fp2_mul(F.fp2_conj(x), _PSI_CX), F.fp2_mul(F.fp2_conj(y), _PSI_CY))


def g2_psi2(pt):
    return g2_psi(g2_psi(pt))


def g2_clear_cofactor_fast(pt):
    """Budroni–Pintore clearing: [x^2-x-1]P + [x-1]psi(P) + psi^2([2]P),
    identical output to [h_eff]P (differentially pinned in
    tests/crypto test_psi_fast_paths_match_slow). c1 = -x = |BLS_X|
    since x < 0."""
    if pt is None:
        return None
    c1 = -BLS_X  # positive
    t1 = g2_neg(g2_mul_raw(pt, c1))  # [x]P
    t2 = g2_psi(pt)
    t3 = g2_psi2(g2_double(pt))  # psi^2([2]P)
    t3 = g2_add(t3, g2_neg(t2))  # psi^2(2P) - psi(P)
    t2 = g2_add(t1, t2)  # [x]P + psi(P)
    t2 = g2_neg(g2_mul_raw(t2, c1))  # [x]([x]P + psi(P))
    t3 = g2_add(t3, t2)
    t3 = g2_add(t3, g2_neg(t1))  # - [x]P
    return g2_add(t3, g2_neg(pt))  # - P


# --- phi endomorphism (G1) ---------------------------------------------------
# GLV endomorphism phi(x, y) = (beta*x, y) with beta a primitive cube root
# of unity in Fp. For THIS beta (2^((p-1)/3); the other root gives the
# conjugate eigenvalue x^2 - 1), phi acts on G1 as multiplication by
# lambda = -x^2 mod r — asserted against the generator below. Subgroup
# test per Scott (eprint 2021/1130, the check blst/zkcrypto ship): a point
# on the curve is in G1 iff phi(P) == -[x^2]P, replacing the 255-bit
# order ladder with a 127-bit one.

BETA_G1 = pow(2, (P - 1) // 3, P)
assert BETA_G1 != 1 and pow(BETA_G1, 3, P) == 1
BLS_X2 = BLS_X * BLS_X  # x^2 = |eigenvalue| of -phi (positive)


def g1_phi(pt):
    if pt is None:
        return None
    return (BETA_G1 * pt[0] % P, pt[1])


def g1_in_subgroup_fast(pt) -> bool:
    """phi-eigenvalue check: P on the curve is in G1 iff phi(P) == -[x^2]P
    (pinned against the order-R check in the differential tests; the
    eigenvalue itself is asserted at import)."""
    if pt is None:
        return True
    if not g1_is_on_curve(pt):
        return False
    return g1_eq(g1_phi(pt), g1_neg(g1_mul_raw(pt, BLS_X2)))


def g2_in_subgroup_fast(pt) -> bool:
    """[x]-eigenvalue check: P on the twist is in G2 iff psi(P) == [x]P
    (pinned against the order-R check in the differential tests; the
    eigenvalue itself is asserted at import)."""
    if pt is None:
        return True
    if not g2_is_on_curve(pt):
        return False
    return g2_eq(g2_psi(pt), g2_mul_raw(pt, BLS_X))


# import-time self-checks pinning the psi constants to the slow paths
assert g2_eq(g2_psi(G2_GEN), g2_mul_raw(G2_GEN, BLS_X))  # eigenvalue = x
assert g2_in_subgroup_fast(g2_mul_raw(G2_GEN, 12345))

# import-time self-checks pinning the phi eigenvalue and the fast G1 check
assert g1_eq(g1_phi(G1_GEN), g1_mul(G1_GEN, (-BLS_X2) % R))  # eigenvalue = -x^2
assert g1_in_subgroup_fast(g1_mul_raw(G1_GEN, 12345))


assert g1_is_on_curve(G1_GEN), "G1 generator not on curve"
assert g2_is_on_curve(G2_GEN), "G2 generator not on twist"
assert g1_in_subgroup(G1_GEN), "G1 generator wrong order"
assert g2_in_subgroup(G2_GEN), "G2 generator wrong order"

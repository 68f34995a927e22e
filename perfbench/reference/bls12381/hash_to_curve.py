"""Hash-to-curve for BLS12-381 G2: BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_.

Implements the full RFC 9380 pipeline byte-exactly for the eth2 ciphersuite:
expand_message_xmd(SHA-256) → hash_to_field(Fp2) → simplified-SWU on the
3-isogenous curve E' (§6.6.3) → 3-isogeny map to the twist (Appendix E.3)
→ effective-cofactor clearing (§8.8.2 h_eff).

The isogeny coefficients and h_eff are the fixed public constants of the
ciphersuite (RFC 9380 Appendix E.3 / §8.8.2). They are validated at import
by a structural check: a sample point on E' must map onto the twist curve
y^2 = x^3 + 4(u+1), which any wrong coefficient breaks. Byte-exactness is
pinned by the RFC 9380 J.10.1 known-answer vectors in
tests/crypto/test_bls_reference.py.

Role in the system: this runs host-side per message while pairings run on
TPU — mirroring the reference where hashToCurve happens inside blst per
verify call (`packages/beacon-node/src/chain/bls/maybeBatch.ts`).
"""

from __future__ import annotations

import hashlib

from . import fields as F
from .curve import g2_add, g2_clear_cofactor_fast, g2_is_on_curve, g2_mul_raw
from .fields import P

DST_G2 = b"BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_"

# RFC 9380 parameters for expand_message_xmd with SHA-256
_B_IN_BYTES = 32  # hash output size
_R_IN_BYTES = 64  # hash block size
_L = 64  # ceil((ceil(log2(p)) + k) / 8) = (381 + 128)/8 rounded up


def expand_message_xmd(msg: bytes, dst: bytes, len_in_bytes: int) -> bytes:
    """RFC 9380 §5.3.1 expand_message_xmd with SHA-256."""
    ell = (len_in_bytes + _B_IN_BYTES - 1) // _B_IN_BYTES
    if ell > 255 or len_in_bytes > 65535 or len(dst) > 255:
        raise ValueError("expand_message_xmd parameter overflow")
    dst_prime = dst + len(dst).to_bytes(1, "big")
    z_pad = b"\x00" * _R_IN_BYTES
    l_i_b_str = len_in_bytes.to_bytes(2, "big")
    b0 = hashlib.sha256(z_pad + msg + l_i_b_str + b"\x00" + dst_prime).digest()
    b = [hashlib.sha256(b0 + b"\x01" + dst_prime).digest()]
    for i in range(2, ell + 1):
        tmp = bytes(x ^ y for x, y in zip(b0, b[-1]))
        b.append(hashlib.sha256(tmp + i.to_bytes(1, "big") + dst_prime).digest())
    return b"".join(b)[:len_in_bytes]


def hash_to_field_fp2(msg: bytes, count: int, dst: bytes = DST_G2):
    """RFC 9380 §5.2 hash_to_field for Fp2 (m=2, L=64)."""
    len_in_bytes = count * 2 * _L
    uniform = expand_message_xmd(msg, dst, len_in_bytes)
    out = []
    for i in range(count):
        coords = []
        for j in range(2):
            off = _L * (j + i * 2)
            coords.append(int.from_bytes(uniform[off : off + _L], "big") % P)
        out.append(tuple(coords))
    return out


def _sgn0(a) -> int:
    """RFC 9380 §4.1 sgn0 for Fp2 elements (lexicographic sign-of-zero)."""
    sign_0 = a[0] % 2
    zero_0 = 1 if a[0] % P == 0 else 0
    sign_1 = a[1] % 2
    return sign_0 | (zero_0 & sign_1)


# --- Simplified SWU on the 3-isogenous curve E' (RFC 9380 §6.6.3) ----------
# E': y^2 = x^3 + A'x + B' over Fp2, with (RFC 9380 §8.8.2):
#   A' = 240 * I,  B' = 1012 * (1 + I),  Z = -(2 + I)

_ISO_A = (0, 240)
_ISO_B = (1012, 1012)
_Z = ((-2) % P, (-1) % P)
_NEG_B_OVER_A = F.fp2_neg(F.fp2_mul(_ISO_B, F.fp2_inv(_ISO_A)))
_B_OVER_ZA = F.fp2_mul(_ISO_B, F.fp2_inv(F.fp2_mul(_Z, _ISO_A)))


def _gp(x):
    """RHS of the isogenous curve: x^3 + A'x + B'."""
    return F.fp2_add(F.fp2_add(F.fp2_mul(F.fp2_sq(x), x), F.fp2_mul(_ISO_A, x)), _ISO_B)


def map_to_curve_sswu(u):
    """Simplified SWU map Fp2 -> E'(Fp2) (RFC 9380 §6.6.2)."""
    tv1 = F.fp2_mul(_Z, F.fp2_sq(u))  # Z * u^2
    tv2 = F.fp2_add(F.fp2_sq(tv1), tv1)  # Z^2 u^4 + Z u^2
    if F.fp2_is_zero(tv2):
        x1 = _B_OVER_ZA  # B / (Z*A)
    else:
        x1 = F.fp2_mul(_NEG_B_OVER_A, F.fp2_add(F.FP2_ONE, F.fp2_inv(tv2)))
    gx1 = _gp(x1)
    y1 = F.fp2_sqrt(gx1)
    if y1 is not None:
        x, y = x1, y1
    else:
        x2 = F.fp2_mul(tv1, x1)  # Z * u^2 * x1
        gx2 = _gp(x2)
        y2 = F.fp2_sqrt(gx2)
        assert y2 is not None, "SSWU guarantees gx1 or gx2 is square"
        x, y = x2, y2
    if _sgn0(u) != _sgn0(y):
        y = F.fp2_neg(y)
    return (x, y)


# --- 3-isogeny E' -> E (RFC 9380 Appendix E.3) -----------------------------
# x = x_num(x') / x_den(x'),  y = y' * y_num(x') / y_den(x')
# Constants below are the ciphersuite's fixed isogeny coefficients
# (RFC 9380 E.3); each Fp2 element is written (c0, c1) for c0 + c1*I.

_K1 = (  # x_num, degree 3
    (
        0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97D6,
        0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97D6,
    ),
    (
        0,
        0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71A,
    ),
    (
        0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71E,
        0x8AB05F8BDD54CDE190937E76BC3E447CC27C3D6FBD7063FCD104635A790520C0A395554E5C6AAAA9354FFFFFFFFE38D,
    ),
    (
        0x171D6541FA38CCFAED6DEA691F5FB614CB14B4E7F4E810AA22D6108F142B85757098E38D0F671C7188E2AAAAAAAA5ED1,
        0,
    ),
)
_K2 = (  # x_den, monic degree 2: x'^2 + k21*x' + k20
    (
        0,
        0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAA63,
    ),
    (
        0xC,
        0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAA9F,
    ),
    F.FP2_ONE,
)
_K3 = (  # y_num, degree 3
    (
        0x1530477C7AB4113B59A4C18B076D11930F7DA5D4A07F649BF54439D87D27E500FC8C25EBF8C92F6812CFC71C71C6D706,
        0x1530477C7AB4113B59A4C18B076D11930F7DA5D4A07F649BF54439D87D27E500FC8C25EBF8C92F6812CFC71C71C6D706,
    ),
    (
        0,
        0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97BE,
    ),
    (
        0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71C,
        0x8AB05F8BDD54CDE190937E76BC3E447CC27C3D6FBD7063FCD104635A790520C0A395554E5C6AAAA9354FFFFFFFFE38F,
    ),
    (
        0x124C9AD43B6CF79BFBF7043DE3811AD0761B0F37A1E26286B0E977C69AA274524E79097A56DC4BD9E1B371C71C718B10,
        0,
    ),
)
_K4 = (  # y_den, monic degree 3: x'^3 + k42*x'^2 + k41*x' + k40
    (
        0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFA8FB,
        0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFA8FB,
    ),
    (
        0,
        0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFA9D3,
    ),
    (
        0x12,
        0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAA99,
    ),
    F.FP2_ONE,
)


def _poly_eval(coeffs, x):
    """Evaluate sum_i coeffs[i] * x^i (Horner)."""
    acc = F.FP2_ZERO
    for c in reversed(coeffs):
        acc = F.fp2_add(F.fp2_mul(acc, x), c)
    return acc


def iso_map_g2(pt):
    """3-isogeny E'(Fp2) -> E(Fp2) (the twist). Infinity maps to infinity."""
    if pt is None:
        return None
    x, y = pt
    x_den = _poly_eval(_K2, x)
    y_den = _poly_eval(_K4, x)
    if F.fp2_is_zero(x_den) or F.fp2_is_zero(y_den):
        # x' is a pole of the isogeny: the image is the point at infinity.
        return None
    x_out = F.fp2_mul(_poly_eval(_K1, x), F.fp2_inv(x_den))
    y_out = F.fp2_mul(y, F.fp2_mul(_poly_eval(_K3, x), F.fp2_inv(y_den)))
    return (x_out, y_out)


# Effective cofactor for G2 cofactor clearing (RFC 9380 §8.8.2). NOT the
# actual curve cofactor h2 — the ciphersuite fixes this specific scalar so
# all implementations produce identical points (it encodes the
# Budroni-Pintore ψ-based fast clearing as a plain scalar).
H_EFF = 0xBC69F08F2EE75B3584C6A0EA91B352888E2A8E9145AD7689986FF031508FFE1329C2F178731DB956D82BF015D1212B02EC0EC69D7477C1AE954CBC06689F6A359894C0ADEBBF6B4E8020005AAA95551


def clear_cofactor_g2(pt):
    """h_eff * P (RFC 9380 §7 clear_cofactor for the BLS12381G2 suites),
    via the Budroni–Pintore ψ-endomorphism method (App. G.3) — output
    identical to [h_eff]P (differentially pinned in tests), ~5x faster."""
    return g2_clear_cofactor_fast(pt)


# --- import-time structural validation of the isogeny constants ------------
# Find a deterministic sample point on E' and check its image lies on the
# twist; any wrong k-coefficient breaks this (byte-exactness is pinned by
# the RFC 9380 J.10.1 KATs in tests).
def _selfcheck() -> None:
    for k in range(1, 64):
        x = (k, 1)
        y = F.fp2_sqrt(_gp(x))
        if y is not None:
            img = iso_map_g2((x, y))
            assert img is not None and g2_is_on_curve(img), "isogeny constants invalid"
            return
    raise RuntimeError("no sample point found on isogenous curve")  # pragma: no cover


_selfcheck()


def map_to_curve_g2(u):
    """map_to_curve for the eth2 suite: SSWU on E' then 3-isogeny to E."""
    return iso_map_g2(map_to_curve_sswu(u))


def hash_to_g2(msg: bytes, dst: bytes = DST_G2):
    """hash_to_curve RO variant (RFC 9380 §3): eth2-byte-exact G2 hashing."""
    u0, u1 = hash_to_field_fp2(msg, 2, dst)
    q = g2_add(map_to_curve_g2(u0), map_to_curve_g2(u1))
    return clear_cofactor_g2(q)

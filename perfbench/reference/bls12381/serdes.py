"""BLS12-381 point (de)serialization — ZCash compressed encoding.

48-byte G1 / 96-byte G2 compressed points with the standard flag bits in the
top byte: 0x80 = compressed, 0x40 = infinity, 0x20 = y is lexicographically
the larger root. This is the wire format of `BLSPubkey` (Bytes48) and
`BLSSignature` (Bytes96) used throughout the reference's SSZ types
(`packages/types/src/primitive/sszTypes.ts`) and the blst bindings.
"""

from __future__ import annotations

from . import fields as F
from .fields import P

_COMPRESSED = 0x80
_INFINITY = 0x40
_SIGN = 0x20
_HALF_P = (P - 1) // 2


class PointDecodeError(ValueError):
    pass


def _fp_is_larger(y: int) -> bool:
    return y > _HALF_P


def _fp2_is_larger(y) -> bool:
    """Lexicographic order on (c1, c0) per the ZCash convention."""
    if y[1] != 0:
        return y[1] > _HALF_P
    return y[0] > _HALF_P


def g1_to_bytes(pt) -> bytes:
    if pt is None:
        return bytes([_COMPRESSED | _INFINITY]) + b"\x00" * 47
    x, y = pt
    out = bytearray(x.to_bytes(48, "big"))
    out[0] |= _COMPRESSED
    if _fp_is_larger(y):
        out[0] |= _SIGN
    return bytes(out)


def g1_from_bytes(data: bytes):
    """Decompress a G1 point. On-curve enforced; subgroup check is separate."""
    if len(data) != 48:
        raise PointDecodeError("G1 compressed point must be 48 bytes")
    flags = data[0]
    if not flags & _COMPRESSED:
        raise PointDecodeError("uncompressed G1 encoding not supported")
    if flags & _INFINITY:
        if any(data[1:]) or flags & ~( _COMPRESSED | _INFINITY):
            raise PointDecodeError("malformed G1 infinity encoding")
        return None
    x = int.from_bytes(bytes([flags & 0x1F]) + data[1:], "big")
    if x >= P:
        raise PointDecodeError("G1 x coordinate >= p")
    y = F.fp_sqrt((x * x * x + 4) % P)
    if y is None:
        raise PointDecodeError("G1 x not on curve")
    if bool(flags & _SIGN) != _fp_is_larger(y):
        y = (-y) % P
    return (x, y)


def g2_to_bytes(pt) -> bytes:
    if pt is None:
        return bytes([_COMPRESSED | _INFINITY]) + b"\x00" * 95
    (x0, x1), y = pt
    out = bytearray(x1.to_bytes(48, "big") + x0.to_bytes(48, "big"))
    out[0] |= _COMPRESSED
    if _fp2_is_larger(y):
        out[0] |= _SIGN
    return bytes(out)


def g2_from_bytes(data: bytes):
    if len(data) != 96:
        raise PointDecodeError("G2 compressed point must be 96 bytes")
    flags = data[0]
    if not flags & _COMPRESSED:
        raise PointDecodeError("uncompressed G2 encoding not supported")
    if flags & _INFINITY:
        if any(data[1:]) or flags & ~( _COMPRESSED | _INFINITY):
            raise PointDecodeError("malformed G2 infinity encoding")
        return None
    x1 = int.from_bytes(bytes([flags & 0x1F]) + data[1:48], "big")
    x0 = int.from_bytes(data[48:], "big")
    if x0 >= P or x1 >= P:
        raise PointDecodeError("G2 x coordinate >= p")
    x = (x0, x1)
    from .curve import g2_rhs

    y = F.fp2_sqrt(g2_rhs(x))
    if y is None:
        raise PointDecodeError("G2 x not on twist curve")
    if bool(flags & _SIGN) != _fp2_is_larger(y):
        y = F.fp2_neg(y)
    return (x, y)

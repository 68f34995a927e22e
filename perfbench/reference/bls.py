"""The verify cells' plain reference: one signature set at a time.

A set is valid when its pubkey and signature decode, the pubkey is not
the identity, both lie in their prime-order subgroups, and
e(-g1, S) * e(PK, H(m)) == 1. That is the definition; the program's
blinded batch has to give `all(valid(s) for s in sets)` for every call.

`judge` returns the two facts a verdict is made of, so that the
controls (see `control_verdict`) can be computed from the same numbers
with one guarantee left out.
"""

from __future__ import annotations

from .bls12381 import curve as C
from .bls12381 import fields as F
from .bls12381.hash_to_curve import hash_to_g2
from .bls12381.pairing import final_exponentiation, miller_loop
from .bls12381.serdes import (
    PointDecodeError,
    g1_from_bytes,
    g1_to_bytes,
    g2_from_bytes,
    g2_to_bytes,
)

CONTROLS = ("no_blinding", "no_subgroup_check")


def pubkey(scalar: int) -> bytes:
    return g1_to_bytes(C.g1_mul(C.G1_GEN, scalar))


def sign(scalar: int, message: bytes) -> bytes:
    return g2_to_bytes(C.g2_mul(hash_to_g2(message), scalar))


def shift_pubkey_off_subgroup(pk: bytes, seed: int) -> bytes:
    """`pk + T` with T a point of the curve's cofactor torsion: the
    pairing equation still holds for the original message and signature
    (a reduced pairing is 1 on a point of order prime to r), so only the
    subgroup check tells this key from the honest one."""
    import random

    rng = random.Random(seed)
    while True:
        x = rng.randrange(F.P)
        y = F.fp_sqrt((x * x * x + 4) % F.P)
        if y is None:
            continue
        torsion = C.g1_mul_raw((x, y), F.R)
        if torsion is not None:
            return g1_to_bytes(C.g1_add(g1_from_bytes(pk), torsion))


def shift_signature(sig: bytes, by: bytes, negate: bool) -> bytes:
    """`sig + by` or `sig - by`: a well-formed signature in the subgroup
    that no longer fits its message. A pair shifted by +D and -D leaves
    the plain sum of a batch's signatures unchanged."""
    d = g2_from_bytes(by)
    return g2_to_bytes(C.g2_add(g2_from_bytes(sig), C.g2_neg(d) if negate else d))


def judge(pk: bytes, message: bytes, sig: bytes) -> dict:
    """{"decodes", "in_subgroup", "defect"}: `defect` is the set's own
    e(-g1, S) * e(PK, H(m)) after the final exponentiation, as nested
    lists of integers (None where a point does not decode)."""
    try:
        pk_pt = g1_from_bytes(pk)
        sig_pt = g2_from_bytes(sig)
    except PointDecodeError:
        return {"decodes": False, "in_subgroup": False, "defect": None}
    if pk_pt is None or sig_pt is None:
        return {"decodes": False, "in_subgroup": False, "defect": None}
    in_subgroup = C.g1_in_subgroup(pk_pt) and C.g2_in_subgroup(sig_pt)
    f = F.fp12_mul(
        miller_loop(C.g1_neg(C.G1_GEN), sig_pt), miller_loop(pk_pt, hash_to_g2(message))
    )
    return {
        "decodes": True,
        "in_subgroup": bool(in_subgroup),
        "defect": _listify(final_exponentiation(f)),
    }


def _listify(x):
    return [_listify(v) for v in x] if isinstance(x, (tuple, list)) else x


def _tuplify(x):
    return tuple(_tuplify(v) for v in x) if isinstance(x, list) else x


def is_valid(judgement: dict) -> bool:
    return (
        judgement["decodes"]
        and judgement["in_subgroup"]
        and F.fp12_eq(_tuplify(judgement["defect"]), F.FP12_ONE)
    )


def reference_verdict(judgements: list[dict]) -> bool:
    """What every call has to return: each of its sets valid on its own."""
    return all(is_valid(j) for j in judgements)


def control_verdict(judgements: list[dict], control: str) -> bool:
    """The reference with one guarantee left out, for one call's sets.

    `no_subgroup_check`: each set by its pairing equation alone.
    `no_blinding`: the batch equation with every coefficient 1, that is
    the product of the sets' defects, so defects that cancel pass."""
    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}, have {CONTROLS}")
    if not all(j["decodes"] for j in judgements):
        return False
    if control == "no_subgroup_check":
        return all(F.fp12_eq(_tuplify(j["defect"]), F.FP12_ONE) for j in judgements)
    if not all(j["in_subgroup"] for j in judgements):
        return False
    acc = F.FP12_ONE
    for j in judgements:
        acc = F.fp12_mul(acc, _tuplify(j["defect"]))
    return F.fp12_eq(acc, F.FP12_ONE)

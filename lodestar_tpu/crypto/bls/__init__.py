"""BLS12-381 pure-Python reference implementation (CPU oracle + fallback).

Device-side counterparts live in ``lodestar_tpu.ops`` (limb-vectorized field
arithmetic, batched Miller loops) and ``lodestar_tpu.models.batch_verify``
(the flagship batched verification pipeline).
"""

from .api import (
    G2_INFINITY,
    IndexedSignatureSet,
    PointDecodeError,
    SecretKey,
    SignatureSet,
    aggregate_pubkeys,
    aggregate_signatures,
    aggregate_verify,
    eth_fast_aggregate_verify,
    fast_aggregate_verify,
    sign,
    sk_to_pk,
    verify,
    verify_signature_sets,
)

__all__ = [
    "G2_INFINITY",
    "IndexedSignatureSet",
    "PointDecodeError",
    "SecretKey",
    "SignatureSet",
    "aggregate_pubkeys",
    "aggregate_signatures",
    "aggregate_verify",
    "eth_fast_aggregate_verify",
    "fast_aggregate_verify",
    "sign",
    "sk_to_pk",
    "verify",
    "verify_signature_sets",
]

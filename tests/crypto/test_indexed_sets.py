"""`IndexedSignatureSet` at the oracle: a set that names its signers by
registry index gets `fast_aggregate_verify`'s answer over the pubkeys a
resolver gives for them."""

from __future__ import annotations

import asyncio

import pytest

from lodestar_tpu.chain.bls import BlsSingleThreadVerifier
from lodestar_tpu.crypto.bls import api
from lodestar_tpu.crypto.bls.api import (
    IndexedSignatureSet,
    SecretKey,
    SignatureSet,
    aggregate_signatures,
    fast_aggregate_verify,
    resolve_signature_set,
    sign,
    verify_signature_sets,
)

SKS = [SecretKey(1000 + 37 * i) for i in range(6)]
REGISTRY = [sk.to_pubkey() for sk in SKS]
MSG = b"\x07" * 32


def resolver(i: int):
    return REGISTRY[i] if 0 <= i < len(REGISTRY) else None


def signed_by(indices, msg=MSG) -> IndexedSignatureSet:
    return IndexedSignatureSet(
        tuple(indices), msg, aggregate_signatures([sign(SKS[i], msg) for i in indices])
    )


def test_it_is_frozen_and_hashable():
    a, b = signed_by([0, 1]), signed_by([0, 1])
    assert a == b and hash(a) == hash(b) and len({a, b}) == 1
    with pytest.raises(Exception):
        a.indices = (2,)


@pytest.mark.parametrize("indices", [(0,), (0, 1, 2), (3, 3, 4), (5, 4, 3, 2, 1, 0)], ids=str)
def test_an_honest_set_verifies_as_fast_aggregate_verify_does(indices):
    s = signed_by(indices)
    assert verify_signature_sets([s], resolver)
    assert fast_aggregate_verify([REGISTRY[i] for i in indices], s.message, s.signature)


@pytest.mark.parametrize("wrong", [
    lambda s: IndexedSignatureSet((0, 2), s.message, s.signature),  # a swapped signer
    lambda s: IndexedSignatureSet((0,), s.message, s.signature),  # a dropped signer
    lambda s: IndexedSignatureSet((), s.message, s.signature),  # no signer
    lambda s: IndexedSignatureSet((0, 6), s.message, s.signature),  # an index the registry lacks
    lambda s: IndexedSignatureSet((0, -1), s.message, s.signature),
], ids=["swapped", "dropped", "none", "beyond", "negative"])
def test_what_the_registry_does_not_bear_out_is_false(wrong):
    assert not verify_signature_sets([wrong(signed_by([0, 1]))], resolver)


def test_signers_that_sum_to_the_identity_are_false():
    from lodestar_tpu.crypto.bls.fields import R

    pair = [SecretKey(5).to_pubkey(), SecretKey(R - 5).to_pubkey()]
    s = IndexedSignatureSet((0, 1), MSG, sign(SKS[0], MSG))
    assert resolve_signature_set(s, lambda i: pair[i]) is None
    assert not verify_signature_sets([s], lambda i: pair[i])


def test_without_a_resolver_an_indexed_set_is_false_and_a_byte_set_is_as_before():
    byte_set = SignatureSet(REGISTRY[2], MSG, sign(SKS[2], MSG))
    assert verify_signature_sets([byte_set])
    assert not verify_signature_sets([byte_set, signed_by([0])])
    assert verify_signature_sets([byte_set, signed_by([0])], resolver)  # a call may mix both forms


def test_a_resolved_set_is_the_set_with_the_summed_pubkey():
    s = signed_by([1, 2, 3])
    got = resolve_signature_set(s, resolver)
    assert got == SignatureSet(api.aggregate_pubkeys([REGISTRY[i] for i in (1, 2, 3)]), s.message, s.signature)
    byte_set = SignatureSet(REGISTRY[2], MSG, s.signature)
    assert resolve_signature_set(byte_set, None) is byte_set


def test_the_single_thread_verifier_takes_a_resolver():
    sets = [signed_by([0, 4]), SignatureSet(REGISTRY[2], MSG, sign(SKS[2], MSG))]
    assert asyncio.run(BlsSingleThreadVerifier(resolver).verify_signature_sets(sets))
    assert not asyncio.run(BlsSingleThreadVerifier().verify_signature_sets(sets))

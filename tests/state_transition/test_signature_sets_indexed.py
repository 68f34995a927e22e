"""Every signature-set producer in its indexed form: the set it gives
with `indexed=True`, resolved from the state's registry and summed on
the host, is the set it gives by default."""

from __future__ import annotations

import pytest

from lodestar_tpu import params
from lodestar_tpu.crypto.bls.api import (
    IndexedSignatureSet,
    SignatureSet,
    resolve_signature_set,
    verify_signature_sets,
)
from lodestar_tpu.state_transition import process_slots
from lodestar_tpu.state_transition import signature_sets as ss
from lodestar_tpu.state_transition.genesis import create_interop_genesis_state, interop_secret_keys
from lodestar_tpu.types import ssz_types

from .test_state_transition import _empty_block_at

N = 32


@pytest.fixture(scope="module", autouse=True)
def minimal_preset():
    prev = params.active_preset()
    params.set_active_preset("minimal")
    yield params.active_preset()
    params.set_active_preset(prev)


@pytest.fixture(scope="module")
def env(minimal_preset):
    p = minimal_preset
    sks = interop_secret_keys(N)
    genesis = create_interop_genesis_state(N, p=p)
    signed = _empty_block_at(genesis, 1, sks, p)
    state = genesis.copy()
    ctx = process_slots(state, 1, p)
    return p, state, ctx, signed


def _indexed_attestation(p, indices, tag: int):
    att = ssz_types(p).IndexedAttestation.default()
    att.attesting_indices = list(indices)
    att.data.slot = 1
    att.data.index = tag
    att.signature = bytes([0x80 + tag]) + bytes(95)
    return att


def _header(p, proposer: int, slot: int, tag: int):
    signed = ssz_types(p).SignedBeaconBlockHeader.default()
    signed.message.slot = slot
    signed.message.proposer_index = proposer
    signed.message.body_root = bytes([tag]) * 32
    signed.signature = bytes([0x90 + tag]) + bytes(95)
    return signed


def _produce(name: str, env, indexed: bool):
    p, state, ctx, signed = env
    t = ssz_types(p)
    if name == "proposer":
        return [ss.block_proposer_signature_set(state, signed, ctx, indexed)]
    if name == "randao":
        return [ss.randao_signature_set(state, signed.message.body, ctx, indexed)]
    if name == "attestation":
        return [ss.indexed_attestation_signature_set(state, _indexed_attestation(p, [3, 7, 11, 20], 1), ctx, indexed)]
    if name == "attestation_of_one":
        return [ss.indexed_attestation_signature_set(state, _indexed_attestation(p, [5], 2), ctx, indexed)]
    if name == "proposer_slashing":
        ps = t.ProposerSlashing.default()
        ps.signed_header_1, ps.signed_header_2 = _header(p, 9, 1, 1), _header(p, 9, 1, 2)
        return ss.proposer_slashing_signature_sets(state, ps, ctx, indexed)
    if name == "attester_slashing":
        als = t.AttesterSlashing.default()
        als.attestation_1 = _indexed_attestation(p, [1, 2, 3], 3)
        als.attestation_2 = _indexed_attestation(p, [2, 3, 4, 5], 4)
        return ss.attester_slashing_signature_sets(state, als, ctx, indexed)
    if name == "voluntary_exit":
        ex = t.SignedVoluntaryExit.default()
        ex.message.validator_index = 13
        ex.signature = b"\xa0" + bytes(95)
        return [ss.voluntary_exit_signature_set(state, ex, ctx, indexed)]
    assert name == "block"
    return ss.get_block_signature_sets(state, signed, ctx, indexed=indexed)


PRODUCERS = ["proposer", "randao", "attestation", "attestation_of_one", "proposer_slashing",
             "attester_slashing", "voluntary_exit", "block"]


@pytest.mark.parametrize("name", PRODUCERS)
def test_the_indexed_set_resolves_to_the_set_produced_today(env, name):
    _, state, _, _ = env
    today = _produce(name, env, indexed=False)
    by_index = _produce(name, env, indexed=True)
    assert len(today) == len(by_index) >= 1
    assert all(type(s) is SignatureSet for s in today)
    assert all(type(s) is IndexedSignatureSet for s in by_index)
    resolver = lambda i: bytes(state.validators[i].pubkey) if 0 <= i < len(state.validators) else None  # noqa: E731
    assert [resolve_signature_set(s, resolver) for s in by_index] == today


def test_the_indexed_sets_of_a_block_verify_at_the_oracle_through_the_resolver(env):
    _, state, _, _ = env
    sets = _produce("block", env, indexed=True)
    assert [s.indices for s in sets] == [(int(env[3].message.proposer_index),)] * 2
    assert verify_signature_sets(sets, lambda i: bytes(state.validators[i].pubkey))
    assert not verify_signature_sets(sets)  # no registry, no verdict


def test_an_indexed_producer_reads_no_pubkey(env, monkeypatch):
    def never(pks):
        raise AssertionError("aggregate_pubkeys on the indexed road")

    monkeypatch.setattr(ss, "aggregate_pubkeys", never)
    assert len(_produce("attester_slashing", env, indexed=True)) == 2
    with pytest.raises(AssertionError):
        _produce("attestation", env, indexed=False)

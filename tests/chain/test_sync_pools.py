"""Sync-committee pools + gossip validation.

Pins: message-pool aggregation into contributions (syncCommitteeMessagePool.ts),
best-per-subnet merge into a spec-valid SyncAggregate
(syncContributionAndProofPool.ts getSyncAggregate), and the
sync_committee_{subnet} / contribution_and_proof validation checks with
real BLS signatures end-to-end through eth_fast_aggregate_verify."""

from __future__ import annotations

import hashlib
from types import SimpleNamespace

import pytest

from lodestar_tpu import params
from lodestar_tpu.chain.op_pools import InsertOutcome
from lodestar_tpu.chain.sync_pools import (
    G2_INFINITY,
    SeenSlotKeyed,
    SyncCommitteeMessagePool,
    SyncContributionAndProofPool,
)
from lodestar_tpu.chain.validation import (
    GossipValidationError,
    is_sync_committee_aggregator,
    validate_sync_committee_contribution,
    validate_sync_committee_message,
)
from lodestar_tpu.config import minimal_chain_config
from lodestar_tpu.crypto.bls import api as bls
from lodestar_tpu.params import DOMAIN_SYNC_COMMITTEE, SYNC_COMMITTEE_SUBNET_COUNT
from lodestar_tpu.state_transition import process_slots
from lodestar_tpu.state_transition.genesis import create_interop_genesis_state, interop_secret_keys
from lodestar_tpu.state_transition.util import get_domain
from lodestar_tpu.types import ssz_types

N = 16


@pytest.fixture(scope="module", autouse=True)
def minimal_preset():
    prev = params.active_preset()
    params.set_active_preset("minimal")
    yield params.active_preset()
    params.set_active_preset(prev)


@pytest.fixture(scope="module")
def sks():
    return interop_secret_keys(N)


@pytest.fixture(scope="module")
def altair_state(minimal_preset, sks):
    p = minimal_preset
    far = 2**64 - 1
    cfg = minimal_chain_config().replace(
        ALTAIR_FORK_EPOCH=1, BELLATRIX_FORK_EPOCH=far, CAPELLA_FORK_EPOCH=far, DENEB_FORK_EPOCH=far
    )
    state = create_interop_genesis_state(N, p=p, genesis_fork_version=cfg.GENESIS_FORK_VERSION)
    process_slots(state, p.SLOTS_PER_EPOCH, p, cfg)
    return state


def _signing_root(block_root: bytes, domain: bytes) -> bytes:
    return hashlib.sha256(block_root + domain).digest()


def _sign_subnet(state, sks, subnet, block_root, slot, p):
    """Signed SyncCommitteeMessages for every member of the subnet's
    subcommittee; returns [(msg, index_in_subcommittee)]."""
    t = ssz_types(p)
    sks_by_pk = {sk.to_pubkey(): sk for sk in sks}
    sub_size = p.SYNC_COMMITTEE_SIZE // SYNC_COMMITTEE_SUBNET_COUNT
    pks = [bytes(pk) for pk in state.current_sync_committee.pubkeys]
    domain = get_domain(state, DOMAIN_SYNC_COMMITTEE, slot // p.SLOTS_PER_EPOCH)
    root = _signing_root(block_root, domain)
    out = []
    vindex_by_pk = {sk.to_pubkey(): i for i, sk in enumerate(sks)}
    for i, pk in enumerate(pks[subnet * sub_size : (subnet + 1) * sub_size]):
        msg = t.SyncCommitteeMessage.default()
        msg.slot = slot
        msg.beacon_block_root = block_root
        msg.validator_index = vindex_by_pk[pk]
        msg.signature = bls.sign(sks_by_pk[pk], root)
        out.append((msg, i))
    return out


def test_message_pool_aggregates_into_contribution(minimal_preset, sks, altair_state):
    p = minimal_preset
    state = altair_state
    block_root = b"\x07" * 32
    slot = int(state.slot)
    pool = SyncCommitteeMessagePool(p)
    msgs = _sign_subnet(state, sks, 0, block_root, slot, p)
    for msg, idx in msgs:
        assert pool.add(0, msg, idx) == InsertOutcome.AGGREGATED
    # duplicate is rejected
    assert pool.add(0, msgs[0][0], msgs[0][1]) == InsertOutcome.ALREADY_KNOWN

    c = pool.get_contribution(0, slot, block_root)
    assert c is not None
    assert all(c.aggregation_bits)
    # the aggregate verifies over the subcommittee pubkeys
    sub_size = p.SYNC_COMMITTEE_SIZE // SYNC_COMMITTEE_SUBNET_COUNT
    pks = [bytes(pk) for pk in state.current_sync_committee.pubkeys][:sub_size]
    domain = get_domain(state, DOMAIN_SYNC_COMMITTEE, slot // p.SLOTS_PER_EPOCH)
    assert bls.eth_fast_aggregate_verify(pks, _signing_root(block_root, domain), bytes(c.signature))
    # unknown (subnet, root) -> None
    assert pool.get_contribution(1, slot, b"\x08" * 32) is None
    # prune drops old slots
    pool.prune(slot + 10)
    assert pool.get_contribution(0, slot, block_root) is None


def test_contribution_pool_merges_full_sync_aggregate(minimal_preset, sks, altair_state):
    p = minimal_preset
    state = altair_state
    block_root = b"\x09" * 32
    slot = int(state.slot)
    t = ssz_types(p)
    msg_pool = SyncCommitteeMessagePool(p)
    contrib_pool = SyncContributionAndProofPool(p)

    for subnet in range(SYNC_COMMITTEE_SUBNET_COUNT):
        for msg, idx in _sign_subnet(state, sks, subnet, block_root, slot, p):
            msg_pool.add(subnet, msg, idx)
        contribution = msg_pool.get_contribution(subnet, slot, block_root)
        cp = t.ContributionAndProof.default()
        cp.aggregator_index = 0
        cp.contribution = contribution
        assert contrib_pool.add(cp) == InsertOutcome.NEW_DATA
        # a worse (fewer participants) contribution does not replace
        worse = contribution.copy()
        bits = list(worse.aggregation_bits)
        bits[0] = False
        worse.aggregation_bits = bits
        cp2 = t.ContributionAndProof.default()
        cp2.aggregator_index = 1
        cp2.contribution = worse
        assert contrib_pool.add(cp2) == InsertOutcome.NOT_BETTER_THAN

    agg = contrib_pool.get_sync_aggregate(slot, block_root)
    assert all(agg.sync_committee_bits)
    all_pks = [bytes(pk) for pk in state.current_sync_committee.pubkeys]
    domain = get_domain(state, DOMAIN_SYNC_COMMITTEE, slot // p.SLOTS_PER_EPOCH)
    assert bls.eth_fast_aggregate_verify(
        all_pks, _signing_root(block_root, domain), bytes(agg.sync_committee_signature)
    )
    # empty key -> infinity signature, no bits
    empty = contrib_pool.get_sync_aggregate(slot, b"\x0a" * 32)
    assert not any(empty.sync_committee_bits)
    assert bytes(empty.sync_committee_signature) == G2_INFINITY


class _FakeChain(SimpleNamespace):
    def get_head_state(self):
        return self._head_state


def _fake_chain(state, p, current_slot):
    return _FakeChain(
        p=p,
        _head_state=state,
        fork_choice=SimpleNamespace(current_slot=current_slot),
        seen_sync_messages=SeenSlotKeyed(),
        seen_sync_aggregators=SeenSlotKeyed(),
    )


def test_validate_sync_committee_message(minimal_preset, sks, altair_state):
    p = minimal_preset
    state = altair_state
    slot = int(state.slot)
    chain = _fake_chain(state, p, slot)
    block_root = b"\x0b" * 32
    msg, idx = _sign_subnet(state, sks, 0, block_root, slot, p)[0]

    res = validate_sync_committee_message(chain, msg, 0)
    assert idx in res.indices_in_subcommittee
    (sig_set,) = res.signature_sets
    assert bls.verify(sig_set.pubkey, sig_set.message, sig_set.signature)

    # seen cache registers only after verification; then duplicate -> IGNORE
    res2 = validate_sync_committee_message(chain, msg, 0)  # not seen yet
    assert res2.signature_sets
    res.register_seen()
    with pytest.raises(GossipValidationError, match="already seen"):
        validate_sync_committee_message(chain, msg, 0)
    # stale slot -> IGNORE
    chain2 = _fake_chain(state, p, slot + 5)
    with pytest.raises(GossipValidationError, match="not current"):
        validate_sync_committee_message(chain2, msg, 0)
    # wrong subnet membership -> REJECT (validator 0 is not in every subnet)
    chain3 = _fake_chain(state, p, slot)
    sub_size = p.SYNC_COMMITTEE_SIZE // SYNC_COMMITTEE_SUBNET_COUNT
    pks = [bytes(pk) for pk in state.current_sync_committee.pubkeys]
    msg_pk = bytes(state.validators[int(msg.validator_index)].pubkey)
    for wrong_subnet in range(1, SYNC_COMMITTEE_SUBNET_COUNT):
        window = pks[wrong_subnet * sub_size : (wrong_subnet + 1) * sub_size]
        if msg_pk not in window:
            with pytest.raises(GossipValidationError, match="not in subcommittee"):
                validate_sync_committee_message(chain3, msg, wrong_subnet)
            break


def test_validate_sync_committee_contribution(minimal_preset, sks, altair_state):
    from lodestar_tpu.params import (
        DOMAIN_CONTRIBUTION_AND_PROOF,
        DOMAIN_SYNC_COMMITTEE_SELECTION_PROOF,
    )
    from lodestar_tpu.state_transition import compute_signing_root

    p = minimal_preset
    state = altair_state
    slot = int(state.slot)
    t = ssz_types(p)
    block_root = b"\x0c" * 32
    epoch = slot // p.SLOTS_PER_EPOCH

    # aggregate subnet 0 and find a subnet-0 member that IS an aggregator
    pool = SyncCommitteeMessagePool(p)
    for msg, idx in _sign_subnet(state, sks, 0, block_root, slot, p):
        pool.add(0, msg, idx)
    contribution = pool.get_contribution(0, slot, block_root)

    sel_data = t.SyncAggregatorSelectionData.default()
    sel_data.slot = slot
    sel_data.subcommittee_index = 0
    sel_domain = get_domain(state, DOMAIN_SYNC_COMMITTEE_SELECTION_PROOF, epoch)
    sel_root = compute_signing_root(t.SyncAggregatorSelectionData, sel_data, sel_domain)

    sub_size = p.SYNC_COMMITTEE_SIZE // SYNC_COMMITTEE_SUBNET_COUNT
    pks = [bytes(pk) for pk in state.current_sync_committee.pubkeys][:sub_size]
    vindex_by_pk = {sk.to_pubkey(): i for i, sk in enumerate(sks)}
    aggregator = None
    for pk in pks:
        vi = vindex_by_pk[pk]
        proof = bls.sign(sks[vi], sel_root)
        if is_sync_committee_aggregator(proof, p):
            aggregator = (vi, proof)
            break
    assert aggregator is not None, "no aggregator among subcommittee (modulo=1 on minimal)"
    ai, proof = aggregator

    cp = t.ContributionAndProof.default()
    cp.aggregator_index = ai
    cp.contribution = contribution
    cp.selection_proof = proof
    outer_domain = get_domain(state, DOMAIN_CONTRIBUTION_AND_PROOF, epoch)
    signed = t.SignedContributionAndProof.default()
    signed.message = cp
    signed.signature = bls.sign(
        sks[ai], compute_signing_root(t.ContributionAndProof, cp, outer_domain)
    )

    chain = _fake_chain(state, p, slot)
    res = validate_sync_committee_contribution(chain, signed)
    assert len(res.signature_sets) == 3
    for s in res.signature_sets:
        assert bls.verify(s.pubkey, s.message, s.signature)

    # where the verifier holds the registry table, the contribution's set
    # names its participants by index and resolves to the same set
    from lodestar_tpu.chain.chain import BeaconChain

    by_index_chain = _fake_chain(state, p, slot)
    by_index_chain.indexed_sets = True
    by_index_chain._pubkey2index = {}
    by_index_chain.registry_indices = lambda st, pks: BeaconChain.registry_indices(by_index_chain, st, pks)
    by_index = validate_sync_committee_contribution(by_index_chain, signed).signature_sets[2]
    assert isinstance(by_index, bls.IndexedSignatureSet) and len(by_index.indices) == sum(contribution.aggregation_bits)
    assert bls.resolve_signature_set(
        by_index, lambda i: bytes(state.validators[i].pubkey)
    ) == res.signature_sets[2]

    # duplicate aggregator -> IGNORE (after post-verify registration)
    res.register_seen()
    with pytest.raises(GossipValidationError, match="already seen"):
        validate_sync_committee_contribution(chain, signed)
    # empty bits -> REJECT
    chain2 = _fake_chain(state, p, slot)
    bad = signed.copy()
    bad.message.contribution.aggregation_bits = [False] * sub_size
    with pytest.raises(GossipValidationError, match="empty"):
        validate_sync_committee_contribution(chain2, bad)

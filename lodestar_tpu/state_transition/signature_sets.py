"""Signature-set producers: every BLS check in a block as a SignatureSet.

Reference `state-transition/src/signatureSets/index.ts:26`
(getBlockSignatureSets) — the bridge between the STF and the batched
verifier: instead of verifying inline, the block pipeline collects all
~100 sets per block and ships them to the device batch verifier in one
RLC batch (`verifyBlocksSignatures.ts:16` runs this in parallel with the
signature-free STF, which is why every process_* function here takes
`verify_signatures=False`).

Every producer has the reference's two forms of ISignatureSet
(`util/signatureSets.ts:10`). By default a set carries its pubkey, and
an aggregate set (an attestation) its signers' pubkeys summed on the
host, the reference's main-thread aggregation
(`multithread/index.ts:152,177`). With `indexed=True`, which the chain
passes where its verifier sums signers from the registry table on the
chip (`BlsDeviceVerifierPool.takes_indexed_sets`), a set names its
signers by validator index (`IndexedSignatureSet`) and no pubkey is
read, decompressed or added here.
"""

from __future__ import annotations

from lodestar_tpu import ssz, tracing
from lodestar_tpu.crypto.bls.api import IndexedSignatureSet, SignatureSet, aggregate_pubkeys
from lodestar_tpu.params import (
    DOMAIN_BEACON_ATTESTER,
    DOMAIN_BEACON_PROPOSER,
    DOMAIN_RANDAO,
    DOMAIN_VOLUNTARY_EXIT,
)
from lodestar_tpu.types import ssz_types

from .cache import EpochContext
from .util import (
    compute_epoch_at_slot,
    compute_signing_root,
    get_current_epoch,
    get_domain,
)

__all__ = [
    "signature_set_of",
    "block_proposer_signature_set",
    "randao_signature_set",
    "indexed_attestation_signature_set",
    "voluntary_exit_signature_set",
    "get_block_signature_sets",
]


def signature_set_of(state, signers, message: bytes, signature: bytes, indexed: bool):
    """One set over validators of `state`'s registry, in the form asked
    for: their indices, or their pubkeys summed here on the host (one
    signer: its pubkey as it stands). A producer calls it with what it
    has at hand, the signers' registry indices."""
    signers = [int(i) for i in signers]
    if indexed:
        return IndexedSignatureSet(indices=tuple(signers), message=message, signature=signature)
    pubkeys = [bytes(state.validators[i].pubkey) for i in signers]
    pubkey = pubkeys[0] if len(pubkeys) == 1 else aggregate_pubkeys(pubkeys)
    return SignatureSet(pubkey=pubkey, message=message, signature=signature)


def block_proposer_signature_set(state, signed_block, ctx: EpochContext, indexed: bool = False):
    from .block import block_types_for

    block = signed_block.message
    domain = get_domain(state, DOMAIN_BEACON_PROPOSER, compute_epoch_at_slot(block.slot, ctx.p))
    block_type, _ = block_types_for(state, ctx.p)
    return signature_set_of(
        state,
        [block.proposer_index],
        compute_signing_root(block_type, block, domain),
        bytes(signed_block.signature),
        indexed,
    )


def randao_signature_set(state, body, ctx: EpochContext, indexed: bool = False):
    epoch = get_current_epoch(state)
    domain = get_domain(state, DOMAIN_RANDAO)
    return signature_set_of(
        state,
        [ctx.get_beacon_proposer(state.slot)],
        compute_signing_root(ssz.uint64, epoch, domain),
        bytes(body.randao_reveal),
        indexed,
    )


def indexed_attestation_signature_set(state, indexed_att, ctx: EpochContext, indexed: bool = False):
    t = ssz_types(ctx.p)
    domain = get_domain(state, DOMAIN_BEACON_ATTESTER, indexed_att.data.target.epoch)
    return signature_set_of(
        state,
        indexed_att.attesting_indices,
        compute_signing_root(t.AttestationData, indexed_att.data, domain),
        bytes(indexed_att.signature),
        indexed,
    )


def proposer_slashing_signature_sets(state, ps, ctx: EpochContext, indexed: bool = False) -> list:
    t = ssz_types(ctx.p)
    out = []
    for signed in (ps.signed_header_1, ps.signed_header_2):
        domain = get_domain(
            state, DOMAIN_BEACON_PROPOSER, compute_epoch_at_slot(signed.message.slot, ctx.p)
        )
        out.append(
            signature_set_of(
                state,
                [ps.signed_header_1.message.proposer_index],
                compute_signing_root(t.BeaconBlockHeader, signed.message, domain),
                bytes(signed.signature),
                indexed,
            )
        )
    return out


def attester_slashing_signature_sets(state, als, ctx: EpochContext, indexed: bool = False) -> list:
    return [
        indexed_attestation_signature_set(state, att, ctx, indexed)
        for att in (als.attestation_1, als.attestation_2)
    ]


def voluntary_exit_signature_set(state, signed_exit, ctx: EpochContext, indexed: bool = False):
    t = ssz_types(ctx.p)
    domain = get_domain(state, DOMAIN_VOLUNTARY_EXIT, signed_exit.message.epoch)
    return signature_set_of(
        state,
        [signed_exit.message.validator_index],
        compute_signing_root(t.VoluntaryExit, signed_exit.message, domain),
        bytes(signed_exit.signature),
        indexed,
    )


@tracing.traced("signature_sets")
def get_block_signature_sets(
    state,
    signed_block,
    ctx: EpochContext,
    *,
    include_proposer: bool = True,
    indexed: bool = False,
) -> list:
    """All BLS checks for one block (reference getBlockSignatureSets).
    The state must already be advanced to the block's slot. `indexed`:
    sets that name their signers by registry index (module docstring)."""
    from .block import get_indexed_attestation

    body = signed_block.message.body
    sets: list = []
    if include_proposer:
        sets.append(block_proposer_signature_set(state, signed_block, ctx, indexed))
    sets.append(randao_signature_set(state, body, ctx, indexed))
    for ps in body.proposer_slashings:
        sets.extend(proposer_slashing_signature_sets(state, ps, ctx, indexed))
    for als in body.attester_slashings:
        sets.extend(attester_slashing_signature_sets(state, als, ctx, indexed))
    for att in body.attestations:
        sets.append(
            indexed_attestation_signature_set(
                state, get_indexed_attestation(att, ctx), ctx, indexed
            )
        )
    for ex in body.voluntary_exits:
        sets.append(voluntary_exit_signature_set(state, ex, ctx, indexed))
    return sets

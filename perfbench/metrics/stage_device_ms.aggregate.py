"""field/curve/pairing ops: device ms a `bls_lane_verify` launch of the traced span spends under `bls.aggregate`: the gather of a row's signers from the resident pubkey table and their sum."""

from perfbench.readers import stage_device_ms


def read(ctx):
    return stage_device_ms(ctx, "bls.aggregate")

"""field/curve/pairing ops: device ms a `bls_lane_verify` launch of the traced span spends under `bls.prep_subgroup`: input prep's subgroup ladders."""

from perfbench.readers import stage_device_ms


def read(ctx):
    return stage_device_ms(ctx, "bls.prep_subgroup")

"""field/curve/pairing ops: device ms a `bls_lane_verify` launch of the traced span spends under `bls.blind`: the blinded scalar multiplications, the fold to each slot's aggregate signature and the affine conversions."""

from perfbench.readers import stage_device_ms


def read(ctx):
    return stage_device_ms(ctx, "bls.blind")

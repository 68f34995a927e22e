"""field/curve/pairing ops: device ms a `bls_lane_verify` launch of the traced span spends under `bls.final_exp`: the final exponentiation, easy and hard part."""

from perfbench.readers import stage_device_ms


def read(ctx):
    return stage_device_ms(ctx, "bls.final_exp")

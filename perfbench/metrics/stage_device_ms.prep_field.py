"""field/curve/pairing ops: device ms a `bls_lane_verify` launch of the traced span spends under `bls.prep_field`: input prep's field stage: decompression chains, the shared Fp2 square-root chain, SSWU and the 3-isogeny."""

from perfbench.readers import stage_device_ms


def read(ctx):
    return stage_device_ms(ctx, "bls.prep_field")

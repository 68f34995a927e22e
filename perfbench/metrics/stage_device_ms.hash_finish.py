"""field/curve/pairing ops: device ms a `bls_lane_verify` launch of the traced span spends under `bls.hash_finish`: hash-to-curve's finish: the add, cofactor clearing and batch affine."""

from perfbench.readers import stage_device_ms


def read(ctx):
    return stage_device_ms(ctx, "bls.hash_finish")

"""Offload wire: mean wall of the window's `offload_rpc` ledger entries, a verify RPC as its tenant's thread saw it, ms (the mean `offload_serve` wall plus `offload_wire_ms`)."""

from perfbench.offload_readers import RPC, mean, walls_ms


def read(ctx):
    return mean(walls_ms(ctx, RPC))

"""Offload wire: median of the faster half of the window's `offload_rpc` walls (the RPCs a wave's first launch answers, where a wave is two launches), ms."""

from perfbench.offload_readers import RPC, half_median, walls_ms


def read(ctx):
    return half_median(walls_ms(ctx, RPC), upper=False)

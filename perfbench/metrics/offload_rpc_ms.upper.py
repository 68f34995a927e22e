"""Offload wire: median of the slower half of the window's `offload_rpc` walls (the RPCs a wave's last launch answers: what the last node waits for its verdict), ms."""

from perfbench.offload_readers import RPC, half_median, walls_ms


def read(ctx):
    return half_median(walls_ms(ctx, RPC), upper=True)

"""Offload host: median of the slower half of the window's `offload.backend` phases (the RPCs a wave's last launch answers), ms."""

from perfbench.offload_readers import half_median, serve_phases_ms


def read(ctx):
    return half_median(serve_phases_ms(ctx, "offload.backend"), upper=True)

"""Offload host: mean `offload.backend` of the window's `offload_serve` entries (hand-over to the pool until its verdict: hold, queue, package, launches), ms."""

from perfbench.offload_readers import mean, serve_phases_ms


def read(ctx):
    return mean(serve_phases_ms(ctx, "offload.backend"))

"""Offload host: median `offload.decode` of the window's `offload_serve` entries (frame to signature sets and tenant trailer), ms."""

from perfbench.offload_readers import median, serve_phases_ms


def read(ctx):
    return median(serve_phases_ms(ctx, "offload.decode"))

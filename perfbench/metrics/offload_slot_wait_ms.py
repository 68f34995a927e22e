"""Offload host: median `offload.slot_wait` of the window's `offload_serve` entries (the stride-fair wait for a service slot), ms."""

from perfbench.offload_readers import median, serve_phases_ms


def read(ctx):
    return median(serve_phases_ms(ctx, "offload.slot_wait"))

"""Arithmetic for the per-layer metrics of the offload wire and host,
read from what the program names itself in its launch ledger: one entry
`offload_rpc` a verify RPC on the tenant's side (phases `offload.encode`,
`offload.call`, `offload.check`) and one entry `offload_serve` an RPC on
the host's (phases `offload.decode`, `offload.slot_wait`,
`offload.backend`, `offload.reply`). `ctx` is `perfbench.readers`'. All
cover the whole window, need no trace, and return None where the program
records no such entry (a commit before the spans, the reference entry).

A wave's RPCs are answered in groups, a group a launch (two at ~265 ms
and two at ~527 ms where a wave is two 512-row launches), so the walls
and the backend phase are bimodal and their median jumps by a group
with one entry at the window's edge. They are read as a mean, which is
additive over the same entries (rpc = serve + wire), and as the medians
of the lower and of the upper half of the sorted values, which are the
groups' own medians where the groups are halves.
"""

from __future__ import annotations

import statistics

from perfbench.readers import steady_launches

RPC = "offload_rpc"  # a tenant's ledger entry for one verify RPC
SERVE = "offload_serve"  # the host's ledger entry for one verify RPC


def walls_ms(ctx, program: str) -> list[float]:
    return [1000.0 * e["seconds"] for e in steady_launches(ctx, program)]


def serve_phases_ms(ctx, phase: str) -> list[float]:
    """One phase of the window's `offload_serve` entries that have it (a
    shed RPC has a decode and no backend)."""
    return [
        1000.0 * e["phases"][phase]
        for e in steady_launches(ctx, SERVE)
        if phase in (e.get("phases") or {})
    ]


def mean(values: list[float]):
    return sum(values) / len(values) if values else None


def median(values: list[float]):
    return statistics.median(values) if values else None


def half_median(values: list[float], upper: bool):
    """Median of the upper or of the lower half of the sorted values
    (the middle one of an odd count is in neither)."""
    half = len(values) // 2
    if not half:
        return None
    ordered = sorted(values)
    return statistics.median(ordered[-half:] if upper else ordered[:half])


def wire_ms(ctx):
    """What an RPC costs beside the host's own serving of it: the mean
    `offload_rpc` wall less the mean `offload_serve` wall (gRPC both
    ways, the hops between threads, the digest check)."""
    rpc, serve = mean(walls_ms(ctx, RPC)), mean(walls_ms(ctx, SERVE))
    return rpc - serve if rpc is not None and serve is not None else None

"""Verify schedules: share of the wall in which one thread holds the staged host parse (`pool.parse_ns` over the span the counters cover: the window's start to its last answer); nothing where no parse was staged."""

from perfbench.readers import counter_delta


def read(ctx):
    parsed = counter_delta(ctx, "pool.parse_ns")
    done = [r.done for r in ctx["records"] if r.done]
    if not parsed or not done or max(done) <= ctx["start"]:
        return None
    return 100.0 * parsed / 1e9 / (max(done) - ctx["start"])

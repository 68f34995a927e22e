"""Pool: share of the staged host parse during which a lane had a launch in flight, over the window (`pool.parse_hidden_ns` over `pool.parse_ns`; nothing where the pool keeps no such counters or staged no parse)."""

from perfbench.readers import counter_delta


def read(ctx):
    parsed = counter_delta(ctx, "pool.parse_ns")
    return 100.0 * counter_delta(ctx, "pool.parse_hidden_ns") / parsed if parsed else None

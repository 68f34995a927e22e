"""Pool: mean wait of a job in the scheduler queue over the window (`lodestar_sched_queue_wait_seconds`)."""

from perfbench.readers import counter_delta


def read(ctx):
    count = counter_delta(ctx, "lodestar_sched_queue_wait_seconds_count")
    if not count:
        return None
    return 1000.0 * counter_delta(ctx, "lodestar_sched_queue_wait_seconds_sum") / count

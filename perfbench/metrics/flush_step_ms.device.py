"""State root: mean milliseconds a flush spends in `htr.device`: the `merkle_level` calls and the waits for them."""

from perfbench.phase_readers import flush_step_ms


def read(ctx):
    return flush_step_ms(ctx, "htr.device")

"""State root: mean milliseconds a flush spends in `htr.scatter`: writing roots back into the level stack."""

from perfbench.phase_readers import flush_step_ms


def read(ctx):
    return flush_step_ms(ctx, "htr.scatter")

"""State root: mean milliseconds a flush spends in `htr.index`: index arithmetic over the dirty set (`add_stack_job`'s sort, `np.unique`, pair indices)."""

from perfbench.phase_readers import flush_step_ms


def read(ctx):
    return flush_step_ms(ctx, "htr.index")

"""State root: mean milliseconds a flush spends in `htr.gather`: gathering dirty pairs into launch layout (fancy indexing, padding, `tobytes`, `words_from_bytes`)."""

from perfbench.phase_readers import flush_step_ms


def read(ctx):
    return flush_step_ms(ctx, "htr.gather")

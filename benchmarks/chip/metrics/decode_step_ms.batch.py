"""Mean host time of a `step` call (one decode step over all slots), ms,
in a cell that reports tokens_per_s."""
import readers


def read(ctx):
    return readers.decode_step_ms(ctx)

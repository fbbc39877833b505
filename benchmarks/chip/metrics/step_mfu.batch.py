"""FLOPs of every prefill and decode step over the host time inside
`admit` and `step` spans, as a percent of every chip's bf16 peak."""
import readers


def read(ctx):
    return readers.step_mfu(ctx)

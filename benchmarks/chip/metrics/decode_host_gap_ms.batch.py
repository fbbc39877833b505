"""Device-idle time inside the driver's `step` spans (each wraps one
`ServingEngine.step`), per step started in the window, ms: the part of a
decode step in which the device waits for the host."""
import enginetrace
import readers


def read(ctx):
    steps = readers.steps(ctx)
    if ctx.trace is None or not steps:
        return None
    return 1e3 * enginetrace.idle_in(ctx.trace, "step") / len(steps)

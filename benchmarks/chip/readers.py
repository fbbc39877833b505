"""Arithmetic shared by the per-layer metric files under `metrics/`.

`ctx` carries the driver's calls (host clock, seconds from the window's
start), the configuration's module (`arch`, whose counts give the work) and
its shapes (`dims`), the chip's peaks, the chip count and the reduced trace.
Only calls that started inside the window count.
"""
from __future__ import annotations


def _in(ctx, calls):
    return [c for c in calls if 0 <= c[0] < ctx.seconds]


def admits(ctx):
    return _in(ctx, ctx.calls.admits)


def steps(ctx):
    return _in(ctx, ctx.calls.steps)


def mean_ms(calls):
    return 1e3 * sum(c[1] - c[0] for c in calls) / len(calls) if calls else None


def prefill_flops(ctx) -> float:
    return sum(ctx.arch.prefill_flops(ctx.dims, c[2]) for c in admits(ctx))


def decode_flops(ctx) -> float:
    return sum(ctx.arch.decode_flops(ctx.dims, c[3]) for c in steps(ctx))


def decode_bytes(ctx) -> float:
    return sum(ctx.arch.decode_bytes(ctx.dims, c[3], groups=ctx.chips // (c[2] or 1))
               for c in steps(ctx))


def share(work: float, seconds: float, ctx, peak_key: str):
    """Percent of the peak of every chip used over `seconds`."""
    if not work or seconds <= 0 or ctx.peaks is None:
        return None
    return 100.0 * work / (seconds * ctx.chips * ctx.peaks[peak_key])


def step_mfu(ctx):
    host = sum(c[1] - c[0] for c in admits(ctx) + steps(ctx))
    return share(prefill_flops(ctx) + decode_flops(ctx), host, ctx, "bf16_flops_per_s")


def decode_hbm_share(ctx):
    if ctx.trace is None:
        return None
    return share(decode_bytes(ctx), ctx.trace.device_in("step"), ctx, "hbm_bytes_per_s")


def decode_step_ms(ctx):
    return mean_ms(steps(ctx))


def device_idle_share(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share

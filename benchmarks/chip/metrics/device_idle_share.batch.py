"""1 - the union of device-op intervals over the traced window, percent,
averaged over the chips."""
import readers


def read(ctx):
    return readers.device_idle_share(ctx)

"""Bytes decode needs (weights once per data-parallel group, live
key/value context of occupied slots) over the device time inside `step`
spans, as a percent of every chip's HBM bandwidth."""
import readers


def read(ctx):
    return readers.decode_hbm_share(ctx)

"""Process start to window start: imports, weights made on the device,
warm-up of every program the cell uses, and a batch cell's first fill, s."""


def read(s):
    return s.setup_s

"""Tokens generated inside the window over the window, tokens/s."""


def read(s):
    return sum(r.tokens_in_window for r in s.records) / s.seconds

"""Median of every inter-token gap in the window, client side: the pace at
which a chat user sees tokens stream, one decode step and the host's work
around it."""

from clientstats import inter_token_gaps, percentile


def read(res, name):
    p = percentile(inter_token_gaps(res["records"], res["t0"], res["t1"]), 50)
    return None if p is None else p * 1e3

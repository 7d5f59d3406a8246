"""Output tokens the client saw in the window, over the window."""

from clientstats import tokens_in_window


def read(res, name):
    t0, t1 = res["t0"], res["t1"]
    return tokens_in_window(res["records"], t0, t1) / (t1 - t0)

"""95th percentile, over every request due in the window, of due time to
first token; a request with no token at the close counts at its age."""

from clientstats import percentile, ttfts


def read(res, name):
    p = percentile(ttfts(res["records"], res["t0"], res["t1"]), 95)
    return None if p is None else p * 1e3

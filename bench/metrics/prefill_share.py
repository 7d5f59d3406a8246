"""Share of the window's wall time spent in engine steps that launched a
prefill (admission and its prefill launches, and the decode launch of the
same step), from the host clock; ``entries/serve_mla.py`` times them."""


def read(res, name):
    s = res.get("prefill_step_s")
    if s is None:
        return None
    return 100.0 * s / (res["t1"] - res["t0"])

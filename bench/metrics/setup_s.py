"""Set-up: process start to the start of the measured window (loading,
weights, compiling or loading every executable, warm-up)."""


def read(res, name):
    return res["setup_s"]

"""Mean wall time of the benchmark's ``bench.step`` span around each
``ServeEngine.step()`` in the traced window."""


def read(res, name):
    tr = res.get("trace")
    s = tr and tr["host_spans"].get("bench.step")
    if not s or not s["count"]:
        return None
    return 1e3 * s["total_s"] / s["count"]

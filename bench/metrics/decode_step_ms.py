"""Device time of the engine's decode program per launch, in the trace."""


def read(res, name):
    tr = res.get("trace")
    p = tr and tr["programs"].get("decode")
    if not p or not p["launches"]:
        return None
    return 1e3 * p["device_s"] / p["launches"]

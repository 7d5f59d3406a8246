"""Active slots per decode launch over the engine's slots, in the window
(``EngineStats`` counters)."""


def read(res, name):
    c = res["counters"]
    if not c["decode_steps"]:
        return None
    return 100.0 * c["slot_steps_active"] / c["decode_steps"] / c["batch"]

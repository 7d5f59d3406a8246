"""Device time of the decode program's ops under the ``moe`` scope at any
depth (router, top-k, dispatch, combine, and the experts' projections
inside it), per decode launch; ``entries/serve_mla.py`` reads it."""

from trace_scopes import per_decode_ms


def read(res, name):
    tr = res.get("trace") or {}
    return per_decode_ms(res, tr.get("moe_s"))

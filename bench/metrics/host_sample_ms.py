"""Device-idle time under the engine's ``serve.decode.sample`` span (host
sampling and bookkeeping of each decoded row), per decode launch."""

from trace_scopes import per_decode_ms


def read(res, name):
    tr = res.get("trace") or {}
    return per_decode_ms(res, tr.get("idle_by_span", {}).get(
        "serve.decode.sample"))

"""Device-idle time under the engine's ``serve.decode.launch`` span (jit
dispatch and the input transfers of a decode launch), per decode launch."""

from trace_scopes import per_decode_ms


def read(res, name):
    tr = res.get("trace") or {}
    return per_decode_ms(res, tr.get("idle_by_span", {}).get(
        "serve.decode.launch"))

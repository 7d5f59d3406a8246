"""Device-idle time under the engine's ``serve.decode.fetch`` span (the
copy of the decode logits and finiteness flags to the host), per decode
launch."""

from trace_scopes import per_decode_ms


def read(res, name):
    tr = res.get("trace") or {}
    return per_decode_ms(res, tr.get("idle_by_span", {}).get(
        "serve.decode.fetch"))

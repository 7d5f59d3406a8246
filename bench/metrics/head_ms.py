"""Self device time of the decode program's ops under the ``head`` scope
(the logits head), per decode launch."""

from trace_scopes import per_decode_ms


def read(res, name):
    tr = res.get("trace") or {}
    return per_decode_ms(res, tr.get("device_scopes", {}).get("head"))

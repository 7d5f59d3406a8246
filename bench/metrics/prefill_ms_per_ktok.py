"""Device time of the engine's prefill programs per 1,000 prompt tokens
admitted in the traced window (unpadded; a request counts when its first
token arrives there)."""


def read(res, name):
    tr = res.get("trace")
    p = tr and tr["programs"].get("prefill")
    a, b = res.get("trace_t", (None, None))
    if not p or not p["launches"] or a is None or b is None:
        return None
    toks = sum(r.prompt_len for r in res["records"]
               if r.tok_times and a <= r.tok_times[0] <= b)
    return 1e6 * p["device_s"] / toks if toks else None

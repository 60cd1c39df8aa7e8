"""Delta of one program counter over delta of another, across the window:
params {"num", "den": keys of rec["counters"], "scale"}. A zero
denominator is nothing to read."""


def read(params, rec, ctx):
    c = rec.get("counters", {})
    den = c.get(params["den"], 0.0)
    if not den:
        return None
    return params.get("scale", 1.0) * c.get(params["num"], 0.0) / den

"""One number the harness took itself: params {"key": key of rec}."""


def read(params, rec, ctx):
    return rec.get(params["key"])

"""1 - (union of the device's operation intervals) / slice, from the
trace, averaged over the chips used."""


def read(params, rec, ctx):
    tr = rec.get("trace")
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])

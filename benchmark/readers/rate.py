"""A count of the window over the window's seconds (all the work, all the
time): params {"count": key of rec["counts"]}."""


def read(params, rec, ctx):
    return rec["counts"][params["count"]] / rec["window_s"]

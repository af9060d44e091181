"""Device busy time per iteration of the traced slice, in ms: the union
of the ``XLA Ops`` intervals inside the slice over the iterations in it."""


def read(run):
    t = run["trace"]
    if not t or not t["iterations"]:
        return None
    return t["busy_s"] / t["iterations"] * 1e3

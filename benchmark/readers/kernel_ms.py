"""Summed device time of the named Pallas kernels' ops per iteration of
the traced slice, in ms. Nothing where the trace shows none of them."""


def kernel_seconds(run, kernels):
    t = run["trace"]
    if not t or not t["iterations"]:
        return None
    total = sum(t["kernel_s"].get(k, 0.0) for k in kernels)
    return total or None


def read(run, kernels):
    total = kernel_seconds(run, kernels)
    return None if total is None else total / run["trace"]["iterations"] * 1e3

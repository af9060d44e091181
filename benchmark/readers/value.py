"""One raw sample as it stands: ``samples[key]``."""


def read(run, key):
    return run["samples"].get(key)

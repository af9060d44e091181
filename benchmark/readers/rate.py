"""A count over the seconds it took: ``samples[count] / samples[seconds]``,
over all the work and all the time of the window."""


def read(run, count, seconds):
    s = run["samples"]
    if count not in s or not s.get(seconds):
        return None
    return s[count] / s[seconds]

"""Host time of the tile cache per served frame: the program's ``cache``
spans (the probe of a request's tiles at submit, and each put of a retired
frame's tiles), summed over the window."""
from spans import durations_s


def read(run):
    work = durations_s(run, "cache")
    if not work or not run["frames"]:
        return None
    return sum(work) / run["frames"] * 1e3

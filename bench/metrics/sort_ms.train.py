"""Device time of the sorts (depth sort, binning merges) per train step,
mean over the chips."""
from ops import is_sort


def read(run):
    s = run["trace"].op_seconds(is_sort)
    if s <= 0 or not run["steps"]:
        return None
    return s / run["steps"] * 1e3

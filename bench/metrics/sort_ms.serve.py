"""Device time of the sorts (depth sort, binning merges) per served frame."""
from ops import is_sort


def read(run):
    s = run["trace"].op_seconds(is_sort)
    if s <= 0 or not run["frames"]:
        return None
    return s / run["frames"] * 1e3

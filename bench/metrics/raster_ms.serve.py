"""Device time of the rasterizer kernel per served frame."""
from ops import is_raster


def read(run):
    s = run["trace"].op_seconds(is_raster)
    if s <= 0 or not run["frames"]:
        return None
    return s / run["frames"] * 1e3

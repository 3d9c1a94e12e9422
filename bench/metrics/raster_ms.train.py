"""Device time of the rasterizer kernels (forward and backward) per train
step, mean over the chips."""
from ops import is_raster


def read(run):
    s = run["trace"].op_seconds(is_raster)
    if s <= 0 or not run["steps"]:
        return None
    return s / run["steps"] * 1e3

"""The rasterizer's share of its roofline in training: the least time the
chip could take for its operations or its bytes (``counts.raster_*``,
whichever bounds), over the kernels' device time. Each chip rasterizes
1 / chips of every view."""
from ops import is_raster


def read(run):
    s = run["trace"].op_seconds(is_raster)
    if s <= 0:
        return None
    c, peak = run["counts"], run["peak"]
    views = run["views"] / run["chips"]
    flops = c.raster_flops(run["config"], views, backward=True)
    nbytes = c.raster_bytes(run["config"], views, backward=True)
    least = max(flops / peak["flops_bf16_per_s"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / s

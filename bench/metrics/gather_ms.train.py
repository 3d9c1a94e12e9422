"""Device time of the tile gather per train step: the program's
``tile_gather`` scope, forward (each tile's splats gathered for the
rasterizer) and backward (the scatter-add of per-tile splat gradients back
into the splats), mean over the chips."""
import scopes


def read(run):
    return scopes.scope_ms(run, "tile_gather", "steps")

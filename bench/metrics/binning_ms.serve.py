"""Device time of the tile binning per served frame: the program's
``binning`` scope, the whole per-tile front-most-K scan with its merge
sorts."""
import scopes


def read(run):
    return scopes.scope_ms(run, "binning", "frames")

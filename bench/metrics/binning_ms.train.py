"""Device time of the tile binning per train step: the program's
``binning`` scope, the whole per-tile front-most-K scan with its merge
sorts, mean over the chips."""
import scopes


def read(run):
    return scopes.scope_ms(run, "binning", "steps")

"""Device time of the depth sort per train step: the program's
``depth_sort`` scope, forward (the sort and the permutation gather) and
backward (the scatter back through the permutation), mean over the chips."""
import scopes


def read(run):
    return scopes.scope_ms(run, "depth_sort", "steps")

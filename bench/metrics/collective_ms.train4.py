"""Device time of the collectives per train step on four chips, mean over
the chips: every all-gather, reduce-scatter, all-reduce and
collective-permute of the step, matched by opcode (``bench/collectives.py``).
That is the splat exchange and its backward, the loss's psums, the SSIM
strip halos, and the reductions of the gradients and the densification
statistics."""
from collectives import is_collective


def read(run):
    s = run["trace"].op_seconds(is_collective)
    if s <= 0 or not run["steps"]:
        return None
    return s / run["steps"] * 1e3

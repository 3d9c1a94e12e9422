"""Where the Pallas kernels run: compiled by Mosaic on a TPU, interpreted on
the CPU (the test platform). No other platform has a Pallas path here."""
from __future__ import annotations

import jax


def interpret_mode(*, varying: bool = False):
    """``interpret=`` for a kernel built on the default backend.

    False on a TPU, True on the CPU. Any other platform raises: the
    interpreter there would run the kernel silently and slowly instead of
    the compiled path the kernel was written for. Callers that compile for
    a TPU described but not attached pass ``interpret=False`` themselves.

    ``varying``: the kernel's operands vary over the mesh axes of a
    ``shard_map`` that checks them (``check_vma``). The CPU's HLO
    interpreter cannot type such a kernel's body, so the TPU interpreter
    (``pltpu.InterpretParams``, slower per grid step) runs it there.
    """
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        if varying:
            from jax.experimental.pallas import tpu as pltpu

            return pltpu.InterpretParams()
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on a TPU or interpreted on the CPU, "
        f"not on {platform!r}; use backend='ref' there"
    )

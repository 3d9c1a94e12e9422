"""Where the Pallas kernels run: compiled by Mosaic on a TPU, interpreted on
the CPU (the test platform). No other platform has a Pallas path here."""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """``interpret=`` for a kernel built on the default backend.

    False on a TPU, True on the CPU. Any other platform raises: the
    interpreter there would run the kernel silently and slowly instead of
    the compiled path the kernel was written for. Callers that compile for
    a TPU described but not attached pass ``interpret=False`` themselves.
    """
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on a TPU or interpreted on the CPU, "
        f"not on {platform!r}; use backend='ref' there"
    )

"""Device time of the splat exchange per train step on four chips: the
program's ``splat_exchange`` scope, forward (the model-axis all-gather of
the projected splats) and backward (its reduce-scatter of their gradients),
mean over the chips.

``bench/scopes.py`` resolves only the scopes of its own list, which
predates this one, so this reader runs its own copy of that module with the
exchange added to the list."""
from pathlib import Path

from harness import load_module

scopes = load_module(Path(__file__).resolve().parents[1] / "scopes.py")
scopes.SCOPES = scopes.SCOPES + ("splat_exchange",)


def read(run):
    return scopes.scope_ms(run, "splat_exchange", "steps")

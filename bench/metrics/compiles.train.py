"""Programs compiled (or loaded from the compile cache) in the window: the
program's ``compile`` spans. Zero when nothing compiles there."""
from spans import in_window


def read(run):
    return sum(1 for name, _, _ in in_window(run) if name == "compile")
